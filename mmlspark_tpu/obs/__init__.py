"""mmlspark_tpu.obs — rank-aware tracing + metrics for the train/predict/
serve hot paths.

Dependency-free (stdlib only; jax is used opportunistically when already
imported, never imported from here).  Everything is off by default and
near-zero-cost when off: each public recording entry point checks one
module-level flag and returns.

Usage::

    from mmlspark_tpu import obs

    with obs.span("booster.iteration", it=i):
        ...                                   # monotonic timing + nesting
    obs.inc("jit_cache.hit")                  # counter (labels allowed)
    obs.gauge("http.queue_depth", q.qsize())  # gauge
    obs.observe("http.request_latency_s", dt) # histogram
    obs.snapshot()                            # one dict with everything

Enabling:

- ``MMLSPARK_TPU_OBS=<path>`` — enable + stream spans to ``<path>`` as
  JSONL (per-rank suffix under multi-process), with a final snapshot
  record at interpreter exit.  ``MMLSPARK_TPU_OBS=1`` enables in-memory
  metrics without an export file.
- ``obs.enable(path=None)`` / ``obs.disable()`` — programmatic control.

Inspect an export with ``python -m tools.obs report [--json] [path]``.
See ``tools/obs/README.md`` for env vars and naming conventions.

The collective watchdog (:class:`collective_watchdog`) is independent of
the enable flag — hang diagnostics are emitted even with metrics off.
So is the black-box flight recorder (:mod:`mmlspark_tpu.obs.flight`):
span/counter/collective events always enter bounded per-thread ring
buffers, dumped as ``blackbox.rank<R>.jsonl`` on watchdog bark, crash,
fatal signal, serving 5xx, or ``obs.flight.dump(reason)`` — read them
with ``python -m tools.obs timeline``.  Request-scoped trace propagation
(:func:`bind_trace` / :func:`trace_attrs`, minted by ``serve/app.py``
from ``X-Request-Id``) makes any one request reconstructable via
``python -m tools.obs trace <request_id>``.
"""

from __future__ import annotations

import os
from typing import Optional

from mmlspark_tpu.obs import _state, device, flight, metrics, steps, tracing
from mmlspark_tpu.obs.context import (  # noqa: F401
    bind_trace,
    current_trace_id,
    trace_attrs,
)
from mmlspark_tpu.obs.tracing import Span, get_logger, record_span as _record_span
from mmlspark_tpu.obs.watchdog import collective_watchdog  # noqa: F401

__all__ = [
    "enabled",
    "enable",
    "disable",
    "reset",
    "span",
    "record_span",
    "inc",
    "gauge",
    "observe",
    "snapshot",
    "export_snapshot",
    "export_path",
    "process_index",
    "get_logger",
    "collective_watchdog",
    "flight",
    "steps",
    "device",
    "bind_trace",
    "trace_attrs",
    "current_trace_id",
]


class _NullSpan:
    """Reusable no-op context (returned by :func:`span` when disabled)."""

    __slots__ = ()

    def set(self, **attrs) -> None:
        pass

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_NULL_SPAN = _NullSpan()


def enabled() -> bool:
    return _state.enabled


def enable(path: Optional[str] = None) -> None:
    """Turn metric/span recording on; ``path`` additionally streams spans
    and a final snapshot to a JSONL file (see module docstring)."""
    if path:
        tracing.open_exporter(path)
        flight.install_hooks()  # a dump destination now exists
    _state.enabled = True


def disable() -> None:
    """Turn recording off and close any export file (after writing the
    final snapshot record, so short-lived enables still round-trip
    through ``tools.obs report``)."""
    if tracing._EXPORTER is not None:
        tracing._at_exit()
    _state.enabled = False


def reset() -> None:
    """Clear all recorded metrics/spans (the export file is left as-is)
    and drop the cached rank (tests re-resolve it after env changes)."""
    metrics.registry.reset()
    steps.reset()
    device.reset()
    _state.reset_rank_cache()


def span(name: str, **attrs):
    """``with obs.span("booster.iteration", it=i): ...`` — when enabled, a
    monotonic timed span with nesting + JSONL export +
    ``jax.profiler.TraceAnnotation`` pass-through.  When disabled, the
    flight recorder still rings a begin/end event pair (bounded memory,
    no I/O — the blackbox contract), unless flight is disarmed too, in
    which case the shared zero-allocation null context returns.

    The record is ``(name, start_ns, end_ns, parent, attrs)`` on the
    ``monotonic_ns`` clock (read them back with ``obs.flight.spans()``);
    where :func:`bind_trace` has bound a request, its ids join the attrs,
    so the spans of one request share an identifier.  ``.set(**attrs)`` on
    the returned span adds what is known only at the end."""
    ids = trace_attrs()
    if ids:
        attrs = {**ids, **attrs}
    if _state.enabled:
        return Span(name, attrs)
    if flight._armed:
        return flight.FlightSpan(name, attrs)
    return _NULL_SPAN


def record_span(name: str, dur_s: float, **attrs) -> None:
    """Record an externally-measured duration as a span (used where the
    timing already exists, e.g. Timer stages and derived per-iteration
    times in the fused scan path)."""
    if not _state.enabled:
        if flight._armed:
            flight.record("span", name, {"dur_s": dur_s, **attrs})
        return
    _record_span(name, dur_s, attrs)


def inc(name: str, value: float = 1.0, /, **labels) -> None:
    if not _state.enabled:
        if flight._armed:
            flight.record("ctr", name, labels or None)
        return
    metrics.registry.inc(name, value, **labels)


def gauge(name: str, value: float, /, **labels) -> None:
    if not _state.enabled:
        return
    metrics.registry.gauge(name, value, **labels)


def observe(name: str, value: float, /, **labels) -> None:
    if not _state.enabled:
        return
    metrics.registry.observe(name, value, **labels)


def snapshot(with_buckets: bool = False) -> dict:
    """Everything recorded so far: counters/gauges/histograms/span
    aggregates, tagged with this process's rank.  ``with_buckets=True``
    adds cumulative bucket counts per histogram (the Prometheus
    ``_bucket{le=}`` exposition needs them; the JSON default stays
    unchanged)."""
    snap = metrics.registry.snapshot(with_buckets=with_buckets)
    snap["process_index"] = _state.process_index()
    snap["enabled"] = _state.enabled
    return snap


def export_snapshot() -> None:
    """Append a snapshot record to the JSONL export now (also written
    automatically at interpreter exit)."""
    import time

    tracing.write_record(
        {
            "kind": "snapshot",
            "ts": time.time(),
            "rank": _state.process_index(),
            "snapshot": snapshot(),
        }
    )


def export_path() -> Optional[str]:
    return tracing.exporter_path()


def process_index() -> int:
    return _state.process_index()


def _init_from_env() -> None:
    raw = os.environ.get("MMLSPARK_TPU_OBS", "").strip()
    if not raw or raw.lower() in ("0", "false", "off"):
        return
    if raw.lower() in ("1", "true", "on"):
        enable()
    else:
        enable(path=raw)


tracing._configure_logger()
_init_from_env()
# The flight recorder's excepthooks always chain (dumps are no-ops
# without a destination); signal handlers only install when
# MMLSPARK_TPU_OBS_FLIGHT_DIR (or an export path) is configured.
flight.install_hooks()
