"""mmlspark_tpu.obs.tracing — spans, the JSONL exporter, and the library
logger.

A span is ``(name, start_ns, end_ns, parent, attrs)`` on ONE clock,
``time.monotonic_ns`` — the flight ring's (``obs/flight.py``; on Linux
``perf_counter_ns`` reads the same clock).  An enabled span stamps its
begin and its end once each: the stamp goes into the ring (whose public
reader, ``obs.flight.spans()``, pairs them back into such records) and
into the span's own record, which is (a) aggregated into the metric
registry's span table and (b) appended as one JSON line to the export
file when ``MMLSPARK_TPU_OBS=path`` (or ``obs.enable(path=...)``) is
active.  When jax is already imported, spans also enter a
``jax.profiler.TraceAnnotation`` so they show up in XLA device profiles,
on the profiler's own clock — jax is never imported from here (obs stays
dependency-free).

JSONL record shapes (``ts`` is the wall clock at the write, for merging
ranks; ``start_ns``/``end_ns`` are this process's ``monotonic_ns``)::

    {"kind": "span", "ts": <unix>, "rank": R, "name": ..., "dur_s": ...,
     "start_ns": ..., "end_ns": ..., "depth": D, "parent": <name|null>,
     "attrs": {...}}
    {"kind": "snapshot", "ts": <unix>, "rank": R, "snapshot": {...}}

Under multiple processes every rank writes its own file
(``<path>.rank<R>``) so lines never interleave across writers.
"""

from __future__ import annotations

import atexit
import json
import logging
import os
import sys
import threading
import time
from typing import Optional

from mmlspark_tpu.obs import _state, flight, metrics

_LOGGER_NAME = "mmlspark_tpu"


def get_logger(name: str = _LOGGER_NAME) -> logging.Logger:
    return logging.getLogger(name)


class _LiveStderrHandler(logging.Handler):
    """StreamHandler variant resolving ``sys.stderr`` at EMIT time, so
    stream redirection (pytest capture, contextlib.redirect_stderr) sees
    library log lines instead of the stderr object alive at obs import."""

    def emit(self, record):
        try:
            sys.stderr.write(self.format(record) + "\n")
        except Exception:
            pass


def _configure_logger() -> logging.Logger:
    """Attach a stderr handler to the library logger (once).

    The pre-obs library printed its (two) diagnostics with bare ``print``;
    routing through logging must keep them visible by default, so the
    library logger gets its own handler rather than relying on the root
    logger being configured.  Propagation stays on so pytest's ``caplog``
    (and any app-level root handlers) still see the records.
    """
    logger = logging.getLogger(_LOGGER_NAME)
    if not any(getattr(h, "_mmlspark_tpu_obs", False) for h in logger.handlers):
        h = _LiveStderrHandler()
        h.setFormatter(
            logging.Formatter("%(asctime)s %(levelname)s %(name)s: %(message)s")
        )
        h._mmlspark_tpu_obs = True
        logger.addHandler(h)
        level = os.environ.get("MMLSPARK_TPU_OBS_LOG_LEVEL", "INFO").upper()
        logger.setLevel(getattr(logging, level, logging.INFO))
    return logger


_tls = threading.local()


def _stack() -> list:
    s = getattr(_tls, "stack", None)
    if s is None:
        s = _tls.stack = []
    return s


def current_span_name() -> Optional[str]:
    """The name of the innermost enabled span open on this thread."""
    stack = getattr(_tls, "stack", None)
    return stack[-1].name if stack else None


_TA_CLS: object = 0  # 0 = unresolved, None = unavailable


def _trace_annotation():
    """``jax.profiler.TraceAnnotation`` iff jax is already imported."""
    global _TA_CLS
    if _TA_CLS == 0:
        if "jax" in sys.modules:
            try:
                from jax.profiler import TraceAnnotation

                _TA_CLS = TraceAnnotation
            except Exception:
                _TA_CLS = None
        else:
            return None  # keep unresolved: jax may be imported later
    return _TA_CLS


class Span:
    """Context manager measuring one named region.  Construct via
    ``obs.span(name, **attrs)`` — which returns a shared null context when
    obs is disabled, so this class only ever runs enabled."""

    __slots__ = ("name", "attrs", "start_ns", "end_ns", "_late", "_ta",
                 "_depth", "_parent")

    def __init__(self, name: str, attrs: dict):
        self.name = name
        self.attrs = attrs
        self._late = None

    def set(self, **attrs) -> None:
        """Attributes known only once the work is done (bytes sent, a
        cache hit): they ride the ring's end event and the record."""
        self._late = {**self._late, **attrs} if self._late else attrs

    def __enter__(self):
        stack = _stack()
        self._parent = stack[-1].name if stack else None
        self._depth = len(stack)
        stack.append(self)
        ta_cls = _trace_annotation()
        self._ta = ta_cls(self.name) if ta_cls else None
        if self._ta is not None:
            self._ta.__enter__()
        self.start_ns = time.monotonic_ns()
        flight.record("sb", self.name, self.attrs or None, self.start_ns)
        return self

    def __exit__(self, exc_type, exc, tb):
        self.end_ns = time.monotonic_ns()
        flight.record("se", self.name, self._late, self.end_ns)
        if self._late:
            self.attrs.update(self._late)
        if self._ta is not None:
            try:
                self._ta.__exit__(exc_type, exc, tb)
            except Exception:
                pass
        stack = _stack()
        if stack and stack[-1] is self:
            stack.pop()
        record_span(
            self.name, (self.end_ns - self.start_ns) / 1e9, self.attrs,
            depth=self._depth, parent=self._parent,
            start_ns=self.start_ns, end_ns=self.end_ns,
        )
        return False


def record_span(
    name: str,
    dur_s: float,
    attrs: Optional[dict] = None,
    depth: int = 0,
    parent: Optional[str] = None,
    start_ns: Optional[int] = None,
    end_ns: Optional[int] = None,
) -> None:
    """Record a completed span: aggregate + export.  A pre-measured one
    (no stamps given) ends now and started ``dur_s`` ago."""
    metrics.registry.observe_span(name, dur_s)
    exp = _EXPORTER
    if exp is not None:
        if end_ns is None:
            end_ns = time.monotonic_ns()
            start_ns = end_ns - int(dur_s * 1e9)
        exp.write(
            {
                "kind": "span",
                "ts": time.time(),
                "rank": _state.process_index(),
                "name": name,
                "dur_s": dur_s,
                "start_ns": start_ns,
                "end_ns": end_ns,
                "depth": depth,
                "parent": parent,
                "attrs": attrs or {},
            }
        )


class _Exporter:
    """Line-buffered JSONL writer; per-rank file under multi-process."""

    def __init__(self, path: str):
        self._requested = path
        self._lock = threading.Lock()
        self._f = None
        self.path: Optional[str] = None

    def _open(self):
        if self._f is None:
            # rank suffix under multi-process; .rep<ID> tag for fleet
            # replicas (same-host, all rank 0) — see _state.file_suffix
            path = self._requested + _state.file_suffix()
            d = os.path.dirname(os.path.abspath(path))
            os.makedirs(d, exist_ok=True)
            self._f = open(path, "a", buffering=1)
            self.path = path
        return self._f

    def write(self, rec: dict) -> None:
        try:
            rid = _state.replica_id()
            if rid is not None and "replica" not in rec:
                rec["replica"] = rid  # fleet merge key (tools/obs)
            # jax's process index alongside the launcher rank: tools/obs
            # disambiguates real multi-process records on the pair when
            # the coordinator renumbered (ISSUE 14 satellite).
            pi = _state.jax_process_index()
            if pi is not None and "process_index" not in rec:
                rec["process_index"] = pi
            line = json.dumps(rec, separators=(",", ":"), default=str)
            with self._lock:
                self._open().write(line + "\n")
        except Exception:
            pass  # export is best-effort; never break the caller

    def close(self) -> None:
        with self._lock:
            if self._f is not None:
                try:
                    self._f.close()
                except Exception:
                    pass
                self._f = None


_EXPORTER: Optional[_Exporter] = None
_ATEXIT_DONE = False


def open_exporter(path: str) -> None:
    global _EXPORTER, _ATEXIT_DONE
    close_exporter()
    _EXPORTER = _Exporter(path)
    if not _ATEXIT_DONE:
        atexit.register(_at_exit)
        _ATEXIT_DONE = True


def exporter_path() -> Optional[str]:
    exp = _EXPORTER
    if exp is None:
        return None
    return exp.path or exp._requested


def write_record(rec: dict) -> None:
    exp = _EXPORTER
    if exp is not None:
        exp.write(rec)


def close_exporter() -> None:
    global _EXPORTER
    if _EXPORTER is not None:
        _EXPORTER.close()
        _EXPORTER = None


def _at_exit() -> None:
    """Final snapshot line so the report CLI sees counters, not just spans."""
    if _EXPORTER is not None:
        snap = metrics.registry.snapshot()
        snap["process_index"] = _state.process_index()
        write_record(
            {
                "kind": "snapshot",
                "ts": time.time(),
                "rank": _state.process_index(),
                "snapshot": snap,
            }
        )
        close_exporter()
