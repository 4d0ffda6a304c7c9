"""mmlspark_tpu.obs.device — best-effort device-memory accounting.

Polled at step boundaries (:func:`mmlspark_tpu.obs.steps.end` calls
:func:`poll`), throttled by ``MMLSPARK_TPU_OBS_DEVICE_POLL_EVERY``
(default every 4th step) so the per-step cost stays a counter bump on
the common path:

- ``device.hbm_in_use{device=}`` / ``device.hbm_peak{device=}`` gauges
  from each addressable device's ``memory_stats()`` (``bytes_in_use`` /
  ``peak_bytes_in_use``), plus the process-lifetime watermark
  ``device.hbm_peak_seen``;
- ``device.live_buffer_bytes`` from ``jax.live_arrays()`` byte totals
  (the host-visible ledger of what obs-enabled code kept alive).

Backends without device ``memory_stats`` (the CPU) still report the
``live_arrays`` total; a zero-byte total is a valid reading.  jax is
looked up in ``sys.modules`` only (the obs spine never imports it).

Compile-event counters, unified with the jit_cache spans: the three
places a program identity can cost wall time each bump
``device.compile_events{kind=}`` at the exact site that already carries
the matching span/counter —

- ``kind=trace``       — a Python (re-)trace: every top-level trip
  through jit's tracing path, from jax's own trace-duration event
  (``core/jit_cache._on_jit_duration``, beside ``jit.traces``), a
  ``trace_cache.miss``'s export and a plain ``jax.jit`` retrace alike;
- ``kind=compile``     — an XLA compile paid (``jit_cache.miss``);
- ``kind=deserialize`` — an AOT executable loaded from disk instead
  (``jit_cache.aot_deserialize`` span / ``aot_hits`` counter).

``summary()`` folds both families into the ``device`` section rendered
by ``python -m tools.obs report``.

Device regions by the program's own names: the program marks its regions
with ``jax.named_scope`` (:data:`SCOPES`), and a compiled module carries
each instruction's scope path in its ``op_name`` metadata.  A dispatch
site hands :func:`note_program` the callable and its arguments while obs
is enabled (their shapes, dtypes and shardings are kept, nothing else);
:func:`regions` lowers and compiles each noted program WHEN ASKED (the
compile is a load from the persistent cache of the executable that ran)
and returns ``{(instruction, result shape): region}`` for it, which a
reader joins with a device trace's table of seconds by op.
"""

from __future__ import annotations

import os
import re
import sys
import threading
from typing import Optional

from mmlspark_tpu.obs import _state, metrics


def _env_int(name: str, default: int) -> int:
    try:
        return int(os.environ.get(name, default))
    except ValueError:
        return default


_POLL_EVERY = max(1, _env_int("MMLSPARK_TPU_OBS_DEVICE_POLL_EVERY", 4))

_lock = threading.Lock()
_poll_seq = 0
_peak_seen = 0.0


def reset() -> None:
    """Re-arm the throttle, drop the watermark and the noted programs
    (test isolation)."""
    global _poll_seq, _peak_seen
    with _lock:
        _poll_seq = 0
        _peak_seen = 0.0
        _PROGRAMS.clear()


def compile_event(kind: str) -> None:
    """Count one trace/compile/deserialize event (called from the
    jit_cache / trace_cache sites that own the matching spans)."""
    if not _state.enabled:
        return
    metrics.registry.inc("device.compile_events", kind=kind)


def poll(force: bool = False) -> Optional[dict]:
    """Sample device memory into gauges; returns the sample (or ``None``
    when disabled or throttled)."""
    global _poll_seq, _peak_seen
    if not _state.enabled:
        return None
    with _lock:
        _poll_seq += 1
        if not force and _poll_seq % _POLL_EVERY != 1 and _POLL_EVERY > 1:
            return None
    jax = sys.modules.get("jax")
    if jax is None:
        return None
    sample: dict = {"devices": {}}
    try:
        for d in jax.local_devices():
            try:
                stats = d.memory_stats()
            except Exception:
                stats = None
            if not stats:
                continue
            in_use = float(stats.get("bytes_in_use", 0.0))
            peak = float(stats.get("peak_bytes_in_use", in_use))
            label = str(getattr(d, "id", len(sample["devices"])))
            sample["devices"][label] = {"in_use": in_use, "peak": peak}
            metrics.registry.gauge("device.hbm_in_use", in_use,
                                   device=label)
            metrics.registry.gauge("device.hbm_peak", peak, device=label)
            with _lock:
                if peak > _peak_seen:
                    _peak_seen = peak
                    metrics.registry.gauge("device.hbm_peak_seen", peak)
        nbytes = 0
        for a in jax.live_arrays():
            try:
                nbytes += int(a.nbytes)
            except Exception:
                continue
        sample["live_buffer_bytes"] = float(nbytes)
        metrics.registry.gauge("device.live_buffer_bytes", float(nbytes))
    except Exception:
        return None
    return sample


def summary(snapshot: Optional[dict] = None) -> dict:
    """The ``device`` report section from a snapshot (defaults to the
    live registry): hbm gauges + compile-event counters, or an empty
    dict when the run recorded neither."""
    snap = snapshot if snapshot is not None else metrics.registry.snapshot()
    gauges = snap.get("gauges", {})
    counters = snap.get("counters", {})
    out: dict = {}
    hbm = {
        k: v for k, v in gauges.items() if k.startswith("device.hbm")
    }
    if "device.live_buffer_bytes" in gauges:
        hbm["device.live_buffer_bytes"] = gauges["device.live_buffer_bytes"]
    if hbm:
        out["memory"] = hbm
    compile_events = {
        k: v for k, v in counters.items()
        if k.startswith("device.compile_events")
    }
    if compile_events:
        out["compile_events"] = compile_events
    return out


# The named scopes the program marks its device regions with, each where
# the work is: engine/tree.py (split_scan, hist_build, quant_hist,
# quant_refine, quant_round, leaf_stats, replay_step, row_route),
# engine/booster.py (leaf_delta, quant_round), ops/histogram.py
# (chunk_copy: the scatter functions' slices of a chunk) and
# ops/pallas_hist.py (chunk_copy: the slice and pad of a chunk that is no
# whole number of row blocks; any other is read in place and the region
# holds nothing), parallel/distributed.py (hist_merge),
# ops/objectives.py (rank_grad), ops/rank_plan.py (rank_ndcg); a fit of
# K > 1 trees an iteration (engine/booster.py) marks its (K, n) gradient
# class_grad and its K-row score update class_update.
SCOPES = (
    "split_scan", "hist_build", "quant_hist", "quant_refine", "quant_round",
    "leaf_stats", "leaf_delta", "replay_step", "hist_merge", "rank_grad",
    "rank_ndcg", "row_route", "chunk_copy", "goss_select", "goss_compact",
    "goss_route", "class_grad", "class_update",
)

_PROGRAMS: dict = {}  # (label, same, argument tree, shapes) -> [callable, abstract args, map]
_PROGRAMS_MAX = 16
_INSTRUCTION = re.compile(r"^\s*(?:ROOT )?%?([\w.\-]+) = (.*)$")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLS = re.compile(r"\bcalls=%?([\w.\-]+)")


def note_program(label: str, same, fn, args) -> None:
    """Keep what :func:`regions` needs to ask for the program that ``fn``
    dispatches on ``args``: the callable and the arguments' shapes, dtypes
    and (where committed) shardings.  ``same`` tells one program of a label
    from another of the same shapes (the jitted program's identity, a
    scorer's kind); a program noted again keeps its map.  Nothing is
    lowered here, and nothing is kept with obs off."""
    if not _state.enabled:
        return
    jax = sys.modules.get("jax")
    if jax is None:
        return

    leaves, tree = jax.tree_util.tree_flatten(args)
    placed = tuple(
        (x.shape, x.dtype, x.sharding if getattr(x, "committed", False) else None)
        for x in leaves
    )
    key = (label, same, tree, placed)
    with _lock:
        entry = _PROGRAMS.get(key)
        if entry is not None:
            entry[0] = fn
            return
        if len(_PROGRAMS) >= _PROGRAMS_MAX:
            _PROGRAMS.pop(next(iter(_PROGRAMS)))
        shapes = tree.unflatten(
            [jax.ShapeDtypeStruct(shape, dtype, sharding=sharding) for shape, dtype, sharding in placed]
        )
        _PROGRAMS[key] = [fn, shapes, None]


def module_regions(hlo_text: str) -> dict:
    """``{(instruction, result shape): region}`` of one compiled module's
    text: the region is the innermost name of :data:`SCOPES` on the
    instruction's ``op_name`` path, ``None`` where there is none.  A fusion
    the compiler left without a scope of its own takes its fused
    computation's: the root's, else the one most of its instructions carry.
    The shape is the text up to its layout (of a tuple, its first
    element's), as a device trace's op names give it."""
    out, calls, bodies = {}, {}, {}
    body = None
    for line in hlo_text.splitlines():
        m = _INSTRUCTION.match(line)
        if m is None:
            head = line.split(" ", 2)
            if line.endswith("{") and len(head) > 1:  # "%name (params) -> shape {", "ENTRY %name ..."
                body = bodies.setdefault(head[head[0] == "ENTRY"].lstrip("%"), [])
            continue
        name, rest = m.groups()
        path = _OP_NAME.search(rest)
        region = None
        for part in reversed(path.group(1).split("/")) if path else ():
            region = next((t for t in re.findall(r"\w+", part) if t in SCOPES), None)
            if region is not None:
                break
        key = name, rest.split("{", 1)[0].split(" ", 1)[0]
        out[key] = region
        if body is not None:
            body.append((region, line.lstrip().startswith("ROOT ")))
        called = _CALLS.search(rest)
        if region is None and called:
            calls[key] = called.group(1)
    for key, computation in calls.items():
        inside = [r for r, _ in bodies.get(computation, ()) if r is not None]
        root = next((r for r, is_root in bodies.get(computation, ()) if is_root), None)
        if inside:
            out[key] = root or max(inside, key=inside.count)
    return out


def regions() -> dict:
    """``{program: {(instruction, result shape): region}}`` for every
    program noted under obs (:func:`note_program`: a fit's scan program,
    a booster's binned scorer), ``program`` being its label and a running
    number.  Lazy: a program is lowered and compiled at its first call
    here (a load from the persistent cache where the executable that ran
    is kept there) and its map memoised; call it after the timed work."""
    with _lock:
        noted = list(_PROGRAMS.items())
    out = {}
    for i, ((label, *_), entry) in enumerate(noted):
        fn, shapes, found = entry
        if found is None:
            found = entry[2] = module_regions(fn.lower(*shapes).compile().as_text())
        out[f"{label}:{i}"] = found
    return out
