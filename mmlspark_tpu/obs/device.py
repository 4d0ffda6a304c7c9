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
"""

from __future__ import annotations

import os
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
    """Re-arm the throttle and drop the watermark (test isolation)."""
    global _poll_seq, _peak_seen
    with _lock:
        _poll_seq = 0
        _peak_seen = 0.0


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
