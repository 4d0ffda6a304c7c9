"""mmlspark_tpu.obs.flight — the always-on black-box flight recorder.

The obs export (PR 2) answers "how fast was it" across a whole run; this
module answers "what happened in the last few seconds before it died".
Every span begin/end, counter bump, collective, and watchdog event is
appended to a per-thread fixed-size ring buffer — even when the metrics
enable flag is OFF — so the recent past is always reconstructable.  The
rings live purely in memory (no I/O, no locks on the hot path: one
``deque.append`` of a small tuple) and are dumped as rank-stamped
``blackbox.rank<R>.jsonl`` files when something goes wrong:

- a collective watchdog bark (``obs.watchdog`` triggers the dump, so the
  one "stuck in collective" log line now arrives with the events that led
  up to it);
- an unhandled exception (``sys.excepthook`` / ``threading.excepthook``
  chain);
- a fatal signal (SIGTERM/SIGINT — handlers chain to whatever was
  installed before, and are only installed when a dump destination is
  configured);
- a serving 5xx (``io/http/serving.py`` calls :func:`auto_dump` from its
  response choke point);
- an explicit ``obs.flight.dump(reason)``.

Dumps need a DESTINATION to be a no-op-free operation: the
``MMLSPARK_TPU_OBS_FLIGHT_DIR`` env var, or (fallback) the directory of an
active ``MMLSPARK_TPU_OBS=<path>`` export.  With neither configured,
``dump`` returns None and writes nothing — recording stays armed either
way, so arming the destination late still captures the preceding events.

Memory bound: at most ``_MAX_RINGS`` rings of ``_CAP`` events each.
Threads beyond the bound (a ThreadingHTTPServer spawns one per
connection) share one overflow ring — ``deque.append`` is thread-safe, so
sharing costs nothing on the hot path; rings of dead threads are evicted
when a new thread registers.

The rings are also the ONE in-memory span log: :func:`spans` pairs the
begin/end events still in them into ``(name, start_ns, end_ns, parent,
attrs)`` records on this module's ``monotonic_ns`` clock, for code that
measures the process it runs in (a benchmark's per-layer metrics), and
:func:`pair_spans` is the same pairing for events read back from a dump
(``python -m tools.obs timeline`` calls it).

Each dump appends a ``flight_header`` record carrying a paired
``(ts, mono_ns)`` wall/monotonic anchor; events carry raw
``monotonic_ns`` stamps.  The reader (``python -m tools.obs timeline``)
reconstructs each event's wall time as ``ts - (mono_ns - t_ns)/1e9`` and
merges ranks on the shared wall clock — the per-rank monotonic-offset
alignment ROADMAP item 1's multi-host parity harness builds on.

Env knobs: ``MMLSPARK_TPU_OBS_FLIGHT`` (``0`` disarms everything),
``MMLSPARK_TPU_OBS_FLIGHT_CAP`` (events per ring, default 2048),
``MMLSPARK_TPU_OBS_FLIGHT_DIR`` (dump destination),
``MMLSPARK_TPU_OBS_FLIGHT_MIN_INTERVAL_S`` (auto-dump throttle, default
30; explicit ``dump()`` is never throttled).
"""

from __future__ import annotations

import collections
import json
import os
import signal
import sys
import threading
import time
from typing import Optional

from mmlspark_tpu.obs import _state


def _env_flag(name: str, default: bool) -> bool:
    raw = os.environ.get(name, "").strip().lower()
    if not raw:
        return default
    return raw not in ("0", "false", "off")


def _env_int(name: str, default: int) -> int:
    try:
        return int(os.environ.get(name, default))
    except ValueError:
        return default


_armed: bool = _env_flag("MMLSPARK_TPU_OBS_FLIGHT", True)
_CAP: int = max(16, _env_int("MMLSPARK_TPU_OBS_FLIGHT_CAP", 2048))
_MAX_RINGS: int = 64

_rings_lock = threading.Lock()
# thread ident -> (thread name, ring).  The overflow ring (shared by
# threads past the bound) lives under ident -1.
_rings: "dict[int, tuple[str, collections.deque]]" = {}
_tls = threading.local()
_gen = 0  # bumped by reset() so cached tls rings are dropped


def armed() -> bool:
    return _armed


def set_armed(on: bool) -> None:
    """Programmatic arm/disarm (tests; embedders that want the pre-PR-6
    zero-allocation disabled span back)."""
    global _armed
    _armed = bool(on)


def capacity() -> int:
    return _CAP


def _new_ring() -> collections.deque:
    """Register the calling thread's ring (bounded; evicts dead threads;
    overflows into one shared ring past the bound)."""
    ident = threading.get_ident()
    name = threading.current_thread().name
    ring: collections.deque = collections.deque(maxlen=_CAP)
    with _rings_lock:
        if ident in _rings:  # re-registration after reset()
            ring = _rings[ident][1]
        elif len(_rings) >= _MAX_RINGS:
            alive = {t.ident for t in threading.enumerate()}
            for dead in [i for i in _rings if i not in alive and i != -1]:
                del _rings[dead]
            if len(_rings) >= _MAX_RINGS:
                if -1 not in _rings:
                    _rings[-1] = ("overflow", collections.deque(maxlen=_CAP))
                ring = _rings[-1][1]
            else:
                _rings[ident] = (name, ring)
        else:
            _rings[ident] = (name, ring)
    _tls.ring = ring
    _tls.gen = _gen
    return ring


def record(kind: str, name: str, detail=None, t_ns: Optional[int] = None) -> None:
    """Append one event to this thread's ring.  The hot path: one
    monotonic read + one bounded deque append; no locks, no I/O.
    ``t_ns`` is a ``monotonic_ns`` stamp the caller already took (an
    enabled ``Span`` stamps once, for the ring and for its own record)."""
    if not _armed:
        return
    ring = getattr(_tls, "ring", None)
    if ring is None or getattr(_tls, "gen", -1) != _gen:
        ring = _new_ring()
    ring.append((t_ns or time.monotonic_ns(), kind, name, detail))


class FlightSpan:
    """The disabled-mode span: rings begin/end events (so the blackbox
    sees recent spans even with metrics off) and records nothing else.
    Returned by ``obs.span`` when metrics are disabled but the flight
    recorder is armed."""

    __slots__ = ("name", "attrs", "_late")

    def __init__(self, name: str, attrs: Optional[dict]):
        self.name = name
        self.attrs = attrs
        self._late = None

    def set(self, **attrs) -> None:
        """Attributes known only once the work is done (bytes sent, a
        cache hit): they ride the end event."""
        self._late = {**self._late, **attrs} if self._late else attrs

    def __enter__(self):
        record("sb", self.name, self.attrs or None)
        return self

    def __exit__(self, exc_type, exc, tb):
        record("se", self.name, self._late)
        return False


# ---------------------------------------------------------------- reader


def pair_spans(events) -> "list[dict]":
    """THE place a span's begin meets its end.  ``events`` are
    ``(t_ns, kind, name, detail, thread)`` in time order (``thread`` is any
    hashable that keeps one thread's events apart: the live reader passes
    the ring's thread name, ``tools/obs`` a ``(rank, thread)`` pair); the
    result is one record per completed span::

        {"id", "name", "start_ns", "end_ns", "parent", "parent_id",
         "thread", "attrs"}

    in order of completion.  ``sb``/``se`` pair stack-wise per thread, by
    name, so a child lies inside its parent; ``parent`` is the enclosing
    open span's name on that thread and ``parent_id`` its record's ``id``
    (ids count begins).  ``attrs`` are the begin event's detail plus
    whatever the end event carries (``set``).  A pre-measured ``span``
    event (``obs.record_span`` with metrics off) ends at its stamp and
    starts ``dur_s`` before it.  An ``se`` whose ``sb`` the ring has
    already dropped, and an ``sb`` still open, give no record."""
    out: "list[dict]" = []
    stacks: "dict[object, list]" = {}  # per thread: open (id, name, t0, detail)
    next_id = 0

    def done(sid, name, t0, t1, up, thread, attrs):
        out.append({
            "id": sid, "name": name, "start_ns": t0, "end_ns": t1,
            "parent": up[1] if up else None,
            "parent_id": up[0] if up else None,
            "thread": thread, "attrs": attrs,
        })

    for t, kind, name, detail, thread in events:
        if kind == "sb":
            stacks.setdefault(thread, []).append((next_id, name, t, detail))
            next_id += 1
        elif kind == "se":
            stack = stacks.get(thread, ())
            for i in range(len(stack) - 1, -1, -1):
                if stack[i][1] == name:
                    sid, _, t0, begun = stack.pop(i)
                    done(sid, name, t0, t, stack[i - 1] if i else None,
                         thread, {**(begun or {}), **(detail or {})})
                    break
        elif kind == "span":
            attrs = dict(detail or {})
            dur_ns = int(float(attrs.pop("dur_s", 0.0) or 0.0) * 1e9)
            stack = stacks.get(thread, ())
            done(next_id, name, t - dur_ns, t, stack[-1] if stack else None,
                 thread, attrs)
            next_id += 1
    return out


def spans(name: Optional[str] = None) -> "list[dict]":
    """The completed spans still in this process's rings, as
    :func:`pair_spans` records on the ``monotonic_ns`` clock — the public
    reader for code that runs in the process it measures (a benchmark's
    per-layer metrics).  ``name`` keeps only spans of that name."""
    recs = pair_spans(_all_events())
    return [r for r in recs if r["name"] == name] if name else recs


# ------------------------------------------------------------------ dump


def flight_dir() -> Optional[str]:
    """Where dumps go: ``MMLSPARK_TPU_OBS_FLIGHT_DIR``, else the directory
    of the active obs JSONL export, else None (dumps disabled)."""
    d = os.environ.get("MMLSPARK_TPU_OBS_FLIGHT_DIR", "").strip()
    if d:
        return d
    from mmlspark_tpu.obs import tracing  # runtime import: avoid cycle

    p = tracing.exporter_path()
    if p:
        return os.path.dirname(os.path.abspath(p))
    return None


def blackbox_path(directory: Optional[str] = None) -> Optional[str]:
    d = directory or flight_dir()
    if not d:
        return None
    # fleet replicas (all rank 0 on one host) get a .rep<ID> tag so their
    # dumps never clobber each other; blackbox.rank*.jsonl globs still match
    rid = _state.replica_id()
    rep = f".rep{rid}" if rid is not None else ""
    return os.path.join(
        d, f"blackbox.rank{_state.process_index()}{rep}.jsonl"
    )


def _snapshot_rings() -> "list[tuple[str, list]]":
    """Copy every ring (append-racy: a concurrent append can invalidate
    iteration, so retry once and fall back to skipping that ring)."""
    with _rings_lock:
        rings = list(_rings.values())
    out = []
    for name, ring in rings:
        for _ in range(2):
            try:
                out.append((name, list(ring)))
                break
            except RuntimeError:  # deque mutated during iteration
                continue
    return out


def _all_events() -> "list[tuple]":
    """Every ring's events as ``(t_ns, kind, name, detail, thread name)``
    in time order (each ring's own order kept among equal stamps)."""
    events = []
    for tname, ring in _snapshot_rings():
        events.extend((t, kind, name, detail, tname)
                      for (t, kind, name, detail) in ring)
    events.sort(key=lambda e: e[0])
    return events


def dump(reason: str, directory: Optional[str] = None) -> Optional[str]:
    """Flush every thread's ring to ``blackbox.rank<R>.jsonl`` (appended,
    so a bark followed by a crash leaves two anchored segments).  Returns
    the path, or None when no destination is configured.  Never raises —
    this runs from excepthooks and signal handlers."""
    try:
        path = blackbox_path(directory)
        if path is None or not _armed:
            return None
        events = _all_events()
        rank = _state.process_index()
        rid = _state.replica_id()
        pi = _state.jax_process_index()
        header = {
            "kind": "flight_header",
            "rank": rank,
            # jax's own index rides alongside the launcher rank so the
            # timeline merge can split records when the two disagree
            **({"process_index": pi} if pi is not None else {}),
            **({"replica": rid} if rid is not None else {}),
            "reason": reason,
            # Paired wall/monotonic anchor: wall(ev) = ts - (mono_ns - t_ns)/1e9
            "ts": time.time(),
            "mono_ns": time.monotonic_ns(),
            "cap": _CAP,
            "events": len(events),
        }
        d = os.path.dirname(os.path.abspath(path))
        os.makedirs(d, exist_ok=True)
        with open(path, "a") as f:
            f.write(json.dumps(header, separators=(",", ":"),
                               default=str) + "\n")
            for t, kind, name, detail, tname in events:
                rec = {"kind": "flight", "rank": rank, "t_ns": t,
                       "ev": kind, "name": name, "thread": tname}
                if detail is not None:
                    rec["detail"] = detail
                f.write(json.dumps(rec, separators=(",", ":"),
                                   default=str) + "\n")
        return path
    except Exception:
        return None


_last_auto_dump = 0.0


def auto_dump(reason: str) -> Optional[str]:
    """Throttled dump for automatic triggers (watchdog barks, 5xx bursts
    must not turn into a dump storm).  Explicit ``dump()`` is exempt."""
    global _last_auto_dump
    try:
        min_interval = float(os.environ.get(
            "MMLSPARK_TPU_OBS_FLIGHT_MIN_INTERVAL_S", 30.0))
    except ValueError:
        min_interval = 30.0
    now = time.monotonic()
    if now - _last_auto_dump < min_interval:
        return None
    _last_auto_dump = now
    return dump(reason)


# ------------------------------------------------------------------ hooks


_hooks_installed = False
_signals_installed = False


def _chain_excepthooks() -> None:
    prev_sys = sys.excepthook

    def hook(exc_type, exc, tb):
        if exc_type not in (SystemExit, KeyboardInterrupt):
            auto_dump(f"unhandled_exception:{exc_type.__name__}")
        prev_sys(exc_type, exc, tb)

    sys.excepthook = hook

    prev_thr = threading.excepthook

    def thr_hook(args):
        if args.exc_type not in (SystemExit, KeyboardInterrupt):
            auto_dump(f"thread_exception:{args.exc_type.__name__}")
        prev_thr(args)

    threading.excepthook = thr_hook


def _chain_signal(sig: int) -> None:
    prev = signal.getsignal(sig)

    def handler(signum, frame):
        auto_dump(f"signal:{signal.Signals(signum).name}")
        if callable(prev):
            prev(signum, frame)
        elif prev == signal.SIG_DFL:
            # Restore the default disposition and re-deliver so the
            # process still dies with the right status.
            signal.signal(signum, signal.SIG_DFL)
            os.kill(os.getpid(), signum)
        # SIG_IGN: swallow, matching the prior disposition.

    signal.signal(sig, handler)


def install_hooks() -> None:
    """Idempotent.  Excepthooks always chain (a dump without a destination
    is a no-op, so this is safe); SIGTERM/SIGINT handlers are installed
    only when a dump destination is configured at install time AND we are
    on the main thread (``signal.signal`` requires it)."""
    global _hooks_installed, _signals_installed
    if not _armed:
        return
    if not _hooks_installed:
        _chain_excepthooks()
        _hooks_installed = True
    if (not _signals_installed and flight_dir()
            and threading.current_thread() is threading.main_thread()):
        try:
            _chain_signal(signal.SIGTERM)
            _chain_signal(signal.SIGINT)
            _signals_installed = True
        except (ValueError, OSError):
            pass  # non-main thread / restricted env: excepthooks still work


# ------------------------------------------------------------------ reset


def reset() -> None:
    """Drop every ring (tests).  Cached per-thread rings are invalidated
    via a generation bump; recording stays armed."""
    global _gen
    with _rings_lock:
        _rings.clear()
        _gen += 1


def ring_stats() -> dict:
    """Bound diagnostics for tests: ring count and per-ring sizes."""
    with _rings_lock:
        return {
            "rings": len(_rings),
            "cap": _CAP,
            "max_rings": _MAX_RINGS,
            "sizes": {name: len(ring) for name, ring in _rings.values()},
            "total_events": sum(len(r) for _, r in _rings.values()),
        }
