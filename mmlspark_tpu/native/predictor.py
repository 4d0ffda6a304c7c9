"""Low-latency native predictor over the LightGBM v3 text model format.

The serving-parity path (SURVEY.md §7.1(c) / §3.2): the reference scores
single rows through its native booster
(UPSTREAM: LightGBMBooster.score → LGBM_BoosterPredictForMatSingleRow —
[REF-EMPTY]); the XLA predict path is right for batched DataFrame scoring
but pays a dispatch round-trip per call, so HTTP serving of one request
wants this host-side C++ walker instead (~µs/row).

Falls back to the pure-Python oracle walker when the toolchain is
unavailable, so behavior is identical either way.
"""

from __future__ import annotations

import ctypes
import os

import numpy as np

_HERE = os.path.dirname(__file__)
_SRC = os.path.join(_HERE, "predictor.cpp")


def _bind(lib):
    dp = ctypes.POINTER(ctypes.c_double)
    ip = ctypes.POINTER(ctypes.c_int)
    lib.mml_model_load.argtypes = [ctypes.c_char_p]
    lib.mml_model_load.restype = ctypes.c_void_p
    lib.mml_model_info.argtypes = [ctypes.c_void_p, ip, ip, ip]
    lib.mml_model_info.restype = None
    lib.mml_model_predict.argtypes = [
        ctypes.c_void_p, dp, ctypes.c_int64, ctypes.c_int64,
        ctypes.c_int, dp,
    ]
    lib.mml_model_predict.restype = None
    lib.mml_model_free.argtypes = [ctypes.c_void_p]
    lib.mml_model_free.restype = None


def _get_lib():
    from mmlspark_tpu.native import load_native_lib

    return load_native_lib(_SRC, _bind)


class NativePredictor:
    """Score raw feature rows against a LightGBM v3 model string."""

    def __init__(self, model_string: str):
        self._text = model_string
        self._lib = _get_lib()
        self._handle = None
        self._fallback = None  # lazily-parsed Booster (no-toolchain path)
        if self._lib is not None:
            h = self._lib.mml_model_load(model_string.encode())
            if not h:
                raise ValueError(
                    "native predictor rejected the model string "
                    "(malformed tree structure)"
                )
            self._handle = ctypes.c_void_p(h)
            nc = ctypes.c_int()
            nt = ctypes.c_int()
            mf = ctypes.c_int()
            self._lib.mml_model_info(
                self._handle, ctypes.byref(nc), ctypes.byref(nt),
                ctypes.byref(mf),
            )
            self.num_class = max(1, nc.value)
            self.num_trees = nt.value
            self.max_feature_idx = mf.value
        else:  # pure-Python fallback: same semantics via the importer
            header = {}
            for line in model_string.splitlines():
                if line.startswith("Tree="):
                    break
                if "=" in line:
                    k, _, v = line.partition("=")
                    header[k.strip()] = v.strip()
            ntpi = int(header.get("num_tree_per_iteration", 1))
            self.num_class = max(int(header.get("num_class", 1)), ntpi, 1)
            self.num_trees = sum(
                1 for ln in model_string.splitlines()
                if ln.startswith("Tree=")
            )
            self.max_feature_idx = int(header.get("max_feature_idx", 0))

    @property
    def native(self) -> bool:
        return self._handle is not None

    def predict(self, X, raw_score: bool = False) -> np.ndarray:
        X = np.ascontiguousarray(X, dtype=np.float64)
        one_row = X.ndim == 1
        if one_row:
            X = X[None, :]
        n, F = X.shape
        if F < self.max_feature_idx + 1:
            raise ValueError(
                f"number of features in data ({F}) does not match the "
                f"model ({self.max_feature_idx + 1})"
            )
        if self._handle is not None:
            out = np.empty((n, self.num_class), dtype=np.float64)
            self._lib.mml_model_predict(
                self._handle,
                X.ctypes.data_as(ctypes.POINTER(ctypes.c_double)),
                n, F, int(bool(raw_score)),
                out.ctypes.data_as(ctypes.POINTER(ctypes.c_double)),
            )
        else:
            if self._fallback is None:  # parse once; the text is immutable
                from mmlspark_tpu.engine.booster import Booster

                self._fallback = Booster.from_model_string(self._text)
            out = np.asarray(self._fallback.predict(X, raw_score=raw_score))
            out = out.reshape(n, -1)
        res = out[:, 0] if self.num_class == 1 else out
        return res[0] if one_row else res

    def __del__(self):
        h, lib = getattr(self, "_handle", None), getattr(self, "_lib", None)
        if h is not None and lib is not None:
            try:
                lib.mml_model_free(h)
            except Exception:
                pass


def native_available() -> bool:
    return _get_lib() is not None
