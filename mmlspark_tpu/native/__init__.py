"""Native (C++) host-side components, loaded via ctypes.

The reference keeps its performance-critical host path (Dataset build /
feature binning) in native code shipped as prebuilt binaries (SURVEY.md
§2.9, L2/L3 layers); here the equivalent is a small C++ library compiled
on first use with the local toolchain and bound with ctypes (no pybind11
in the image — task env rules).  Every native entry point has a pure
numpy fallback in the calling module, selected automatically when the
toolchain or the compiled library is unavailable (or when
``MMLSPARK_TPU_NO_NATIVE=1``).
"""

from __future__ import annotations

import ctypes
import glob
import hashlib
import os
import subprocess
import threading
import time

from mmlspark_tpu import obs

_HERE = os.path.dirname(__file__)
_SRC = os.path.join(_HERE, "binner.cpp")

_lock = threading.Lock()
_libs: dict = {}  # source path -> _TimedLib | None (None = tried, unavailable)


class _TimedLib:
    """Transparent CDLL proxy timing every ``mml_*`` entry point.

    Records call count + cumulative wall ns per symbol into the obs
    registry (``native.calls{symbol=...}`` / ``native.ns{symbol=...}``).
    Symbol lookup semantics are preserved exactly: a missing symbol still
    raises ``AttributeError`` (``hasattr``/``getattr(..., None)`` probes
    for optional symbols like ``mml_binner_transform_cat`` behave as on
    the raw CDLL), and non-``mml_`` attributes pass straight through.
    ctypes signatures are bound on the RAW library before wrapping, so
    ``argtypes``/``restype`` setup never sees the proxy.
    """

    def __init__(self, lib):
        self._lib = lib
        self._timed: dict = {}

    def __getattr__(self, name):
        fn = getattr(self._lib, name)  # AttributeError propagates
        if not name.startswith("mml_") or not callable(fn):
            return fn
        timed = self._timed.get(name)
        if timed is None:

            def timed(*args, _fn=fn, _name=name):
                t0 = time.perf_counter_ns()
                try:
                    return _fn(*args)
                finally:
                    try:
                        dt = time.perf_counter_ns() - t0
                        obs.inc("native.calls", symbol=_name)
                        obs.inc("native.ns", dt, symbol=_name)
                    except Exception:
                        pass  # never let accounting break a native call

            self._timed[name] = timed
        return timed


def load_native_lib(src: str, bind) -> "ctypes.CDLL | None":
    """Shared build-if-absent + CDLL + bind loader for the C++ components.

    The binary is named after its source — ``_<stem>-<sha12 of src>.so``
    beside it — and built with the local toolchain when that name is
    absent (atomic tmp+replace, per-process tmp name).  A copied or
    unpacked tree resets mtimes, so freshness is never judged by them: an
    edited source simply has another name, and binaries left from other
    source versions are removed after a successful build.  Then the
    library is loaded and ``bind(lib)`` sets the ctypes signatures.
    Returns None — the caller's numpy fallback — when
    ``MMLSPARK_TPU_NO_NATIVE=1``, when there is no toolchain, or when the
    build fails (logged with the compiler's message).
    """
    if src in _libs:
        return _libs[src]
    with _lock:
        if src in _libs:
            return _libs[src]
        lib = None
        if not os.environ.get("MMLSPARK_TPU_NO_NATIVE"):
            so = _so_path(src)
            if os.path.exists(so) or _build(src, so):
                lib = ctypes.CDLL(so)
                bind(lib)
                lib = _TimedLib(lib)
        _libs[src] = lib
        return lib


def _so_path(src: str) -> str:
    with open(src, "rb") as f:
        digest = hashlib.sha256(f.read()).hexdigest()[:12]
    stem = os.path.splitext(os.path.basename(src))[0]
    return os.path.join(os.path.dirname(src), f"_{stem}-{digest}.so")


def _build(src: str, so: str) -> bool:
    tmp = so + f".tmp{os.getpid()}"
    try:
        subprocess.run(
            ["g++", "-O3", "-shared", "-fPIC", "-std=c++17",
             "-pthread", src, "-o", tmp],
            check=True, capture_output=True, timeout=120,
        )
        os.replace(tmp, so)
    except (OSError, subprocess.SubprocessError) as e:
        detail = getattr(e, "stderr", b"") or b""
        obs.get_logger("mmlspark_tpu.native").warning(
            "native build of %s failed (%r); using the numpy fallback\n%s",
            os.path.basename(src), e, detail.decode(errors="replace")[-2000:],
        )
        try:
            os.unlink(tmp)
        except OSError:
            pass
        return False
    for stale in glob.glob(so.rsplit("-", 1)[0] + "-*.so"):
        if stale != so:
            try:
                os.unlink(stale)
            except OSError:
                pass
    return True


def _bind_binner(lib):
    # Fixed-width c_int64 throughout: the C side declares int64_t, and a
    # platform-width c_long would misread the tables on LLP64 (Windows).
    c_double_p = ctypes.POINTER(ctypes.c_double)
    c_int_p = ctypes.POINTER(ctypes.c_int)
    c_u8_p = ctypes.POINTER(ctypes.c_uint8)
    lib.mml_binner_fit.argtypes = [
        c_double_p, ctypes.c_int64, ctypes.c_int64,
        ctypes.c_int, ctypes.c_int, c_u8_p,
        c_double_p, c_int_p, ctypes.c_int,
    ]
    lib.mml_binner_fit.restype = None
    lib.mml_binner_transform.argtypes = [
        c_double_p, ctypes.c_int64, ctypes.c_int64,
        c_double_p, c_int_p, ctypes.c_int, ctypes.c_int,
        c_u8_p, ctypes.c_int,
    ]
    lib.mml_binner_transform.restype = None
    # Optional symbol (r5): a cached pre-r5 .so must only lose the cat
    # kernel (numpy cats + C++ numerics), not the whole library.
    cat_fn = getattr(lib, "mml_binner_transform_cat", None)
    if cat_fn is not None:
        c_i64_p = ctypes.POINTER(ctypes.c_int64)
        cat_fn.argtypes = [
            c_double_p, ctypes.c_int64, ctypes.c_int64,
            c_i64_p, ctypes.c_int64, c_i64_p, c_i64_p,
            ctypes.c_int, c_u8_p, ctypes.c_int,
        ]
        cat_fn.restype = None


def get_binner_lib():
    """The compiled binner library, or None (numpy fallback)."""
    return load_native_lib(_SRC, _bind_binner)


def default_threads() -> int:
    return min(16, os.cpu_count() or 1)
