"""PackedForest / device-binning / Pallas-predict parity suite (ISSUE 5).

The acceptance contract for the fused inference stack is BITWISE equality
with the seed scan path: the packed SoA traversal, the Pallas kernel
(interpret mode on CPU), and the on-device binner must reproduce the scan
backend's predictions exactly — same float accumulation order per class,
same routing for missing/default-left and categorical splits, same bin
ids at every boundary for f32-representable inputs.  ``np.array_equal``
throughout; no tolerances.
"""

import dataclasses
import pickle

import numpy as np
import pytest

from mmlspark_tpu.engine.booster import Dataset, train


def _clone(booster, backend):
    """Fresh booster pinned to one traversal backend.  The pickle
    round-trip drops every device cache, so each clone rebuilds its own
    packed table / binner from scratch (what a new serving process does)."""
    b = pickle.loads(pickle.dumps(booster))
    b.config = dataclasses.replace(b.config, predict_backend=backend)
    return b


def _toy_xy(n=400, f=6, seed=0, nan_frac=0.0):
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(n, f))
    if nan_frac:
        X[rng.random(size=X.shape) < nan_frac] = np.nan
    z = np.where(np.isnan(X), 0.0, X)
    y = z[:, 0] * 2.0 - np.sin(z[:, 1]) + 0.3 * rng.normal(size=n)
    return X, y


@pytest.fixture(scope="module")
def reg_booster():
    """Regression forest trained WITH missing values so default-left
    routing is exercised on real split decisions."""
    X, y = _toy_xy(nan_frac=0.08)
    return train(
        {"objective": "regression", "num_iterations": 20, "num_leaves": 15,
         "min_data_in_leaf": 4, "learning_rate": 0.2},
        Dataset(X, y),
    ), X


@pytest.fixture(scope="module")
def multi_booster():
    rng = np.random.default_rng(3)
    X = rng.normal(size=(450, 5))
    y = (X[:, 0] + 0.7 * X[:, 1] > 0.4).astype(int) + (X[:, 2] > 0.6)
    return train(
        {"objective": "multiclass", "num_class": 3, "num_iterations": 12,
         "num_leaves": 7, "min_data_in_leaf": 3, "learning_rate": 0.3},
        Dataset(X, y.astype(np.float64)),
    ), X


@pytest.fixture(scope="module")
def cat_booster():
    rng = np.random.default_rng(7)
    n = 400
    Xc = rng.integers(0, 12, size=(n, 2)).astype(np.float64)
    Xn = rng.normal(size=(n, 3))
    X = np.concatenate([Xc, Xn], axis=1)
    y = (np.isin(Xc[:, 0], [1, 4, 9]).astype(float) * 2.0
         + Xn[:, 0] + 0.2 * rng.normal(size=n))
    booster = train(
        {"objective": "regression", "num_iterations": 15, "num_leaves": 15,
         "min_data_in_leaf": 4, "categorical_feature": [0, 1]},
        Dataset(X, y),
    )
    assert bool(np.any(np.asarray(booster.trees.split_cat) >= 0)), \
        "fixture must actually take categorical splits"
    return booster, X


# ---------------------------------------------------------------------------
# scan vs packed vs pallas_interpret: public predict() surface
# ---------------------------------------------------------------------------
class TestBitwiseParity:
    def test_regression_predict_and_raw(self, reg_booster):
        booster, X = reg_booster
        scan = _clone(booster, "scan")
        packed = _clone(booster, "packed")
        pallas = _clone(booster, "pallas_interpret")
        for raw in (False, True):
            ref = scan.predict(X, raw_score=raw)
            assert np.array_equal(ref, packed.predict(X, raw_score=raw))
            assert np.array_equal(ref, pallas.predict(X, raw_score=raw))

    def test_num_iteration_slices(self, reg_booster):
        booster, X = reg_booster
        scan = _clone(booster, "scan")
        packed = _clone(booster, "packed")
        for T in (1, 7, None):
            assert np.array_equal(
                scan.predict(X, num_iteration=T),
                packed.predict(X, num_iteration=T),
            )

    def test_multiclass(self, multi_booster):
        booster, X = multi_booster
        scan = _clone(booster, "scan")
        packed = _clone(booster, "packed")
        pallas = _clone(booster, "pallas_interpret")
        ref = scan.predict(X)
        assert ref.shape == (X.shape[0], 3)
        assert np.array_equal(ref, packed.predict(X))
        assert np.array_equal(ref, pallas.predict(X))
        raw = scan.predict(X, raw_score=True)
        assert np.array_equal(raw, packed.predict(X, raw_score=True))

    @pytest.mark.parametrize("entry", ["predict", "raw_scores_binned"])
    def test_categorical(self, cat_booster, entry):
        booster, X = cat_booster
        assert bool(np.asarray(booster.trees.split_cat).any())
        scan = _clone(booster, "scan")
        packed = _clone(booster, "packed")
        probe = np.concatenate(
            # unseen categories + NaN in a categorical column
            [X, np.array([[99.0, -1.0, 0.0, 0.0, 0.0],
                          [np.nan, 3.0, 1.0, -1.0, 0.5]])],
            axis=0,
        )
        if entry == "predict":
            assert np.array_equal(scan.predict(probe), packed.predict(probe))
            return
        # the entry warm start and a holdout evaluation score through;
        # pallas_interpret is not admitted for membership splits and
        # resolves to packed (next test)
        bins = booster.bin_mapper.transform(probe)
        ref = np.asarray(scan._raw_scores_binned(bins))
        assert ref.shape == (1, probe.shape[0])
        for other in (packed, _clone(booster, "pallas_interpret")):
            assert np.array_equal(ref, np.asarray(other._raw_scores_binned(bins)))

    def test_categorical_forces_packed_over_pallas(self, cat_booster):
        booster, _ = cat_booster
        b = _clone(booster, "pallas_interpret")
        # the Pallas kernel is numeric-only; resolution must fall back
        assert b._resolved_predict_backend(b.num_iterations) == "packed"

    def test_all_missing_rows(self, reg_booster):
        booster, X = reg_booster
        probe = np.full((8, X.shape[1]), np.nan)
        assert np.array_equal(
            _clone(booster, "scan").predict(probe),
            _clone(booster, "packed").predict(probe),
        )

    def test_pred_leaf(self, reg_booster, multi_booster):
        for booster, X in (reg_booster, multi_booster):
            scan = _clone(booster, "scan")
            packed = _clone(booster, "packed")
            ref = scan.predict(X, pred_leaf=True)
            out = packed.predict(X, pred_leaf=True)
            assert out.shape == ref.shape
            assert np.array_equal(ref, out)


# ---------------------------------------------------------------------------
# padded serving buckets: resident f32 path vs the host-binned oracle
# ---------------------------------------------------------------------------
class TestPaddedBuckets:
    def _f32_probe(self, X):
        # the padded wire contract is f32 rows; feed f32-representable
        # values so host-f64 and device-f32 binning must agree exactly
        return X.astype(np.float32).astype(np.float64)

    @pytest.mark.parametrize("backend", ["packed", "pallas_interpret"])
    def test_padded_matches_offline(self, reg_booster, backend):
        booster, X = reg_booster
        Xr = self._f32_probe(np.nan_to_num(X, nan=np.nan))  # keep NaNs
        n_valid, B = 10, 64
        padded = np.zeros((B, X.shape[1]))
        padded[:n_valid] = Xr[:n_valid]
        b = _clone(booster, backend)
        out = b.predict_padded(padded, n_valid)
        ref = _clone(booster, "scan").predict(Xr[:n_valid])
        assert out.shape == (n_valid,)
        assert np.array_equal(ref, out)

    def test_padded_scan_backend_falls_back(self, reg_booster):
        booster, X = reg_booster
        b = _clone(booster, "scan")
        padded = np.zeros((32, X.shape[1]))
        padded[:5] = X[:5]
        out = b.predict_padded(padded, 5)
        assert np.array_equal(out, b.predict(X[:5]))

    def test_padding_tail_does_not_leak(self, reg_booster):
        booster, X = reg_booster
        Xr = self._f32_probe(X)
        b = _clone(booster, "packed")
        pad_a = np.zeros((64, X.shape[1]))
        pad_b = np.full((64, X.shape[1]), 7.25)  # different garbage tail
        pad_a[:6] = Xr[:6]
        pad_b[:6] = Xr[:6]
        assert np.array_equal(
            b.predict_padded(pad_a, 6), b.predict_padded(pad_b, 6)
        )


# ---------------------------------------------------------------------------
# on-device binning: exact agreement with the host BinMapper
# ---------------------------------------------------------------------------
class TestDeviceBinning:
    def _assert_binning_matches(self, bm, X):
        from mmlspark_tpu.ops.device_binning import DeviceBinner

        db = DeviceBinner.from_mapper(bm)
        got = np.asarray(db.transform(X.astype(np.float32)))
        want = bm.transform(X).astype(np.int32)
        np.testing.assert_array_equal(got, want)

    def test_numeric_with_nan(self, reg_booster):
        booster, X = reg_booster
        probe = X.astype(np.float32).astype(np.float64)
        self._assert_binning_matches(booster.bin_mapper, probe)

    def test_exact_boundary_values(self, reg_booster):
        """Rows sitting EXACTLY on bin upper bounds (rounded to f32):
        host searchsorted(side='left') sends a value equal to a bound to
        the bin above it; the double-single device predicate must agree
        even when f32 rounding moved the value across the f64 bound."""
        booster, _ = reg_booster
        bm = booster.bin_mapper
        F = bm.num_features
        rows = []
        for f in range(F):
            for ub in np.asarray(bm.upper_bounds[f], np.float64):
                if not np.isfinite(ub):
                    continue
                v32 = np.float32(ub)
                r = np.zeros(F)
                r[f] = float(v32)
                rows.append(r)
                for nudged in (np.nextafter(v32, np.float32(-np.inf)),
                               np.nextafter(v32, np.float32(np.inf))):
                    r = np.zeros(F)
                    r[f] = float(nudged)
                    rows.append(r)
        self._assert_binning_matches(bm, np.asarray(rows))

    def test_categorical_and_unseen(self, cat_booster):
        booster, X = cat_booster
        probe = np.concatenate(
            [X, np.array([[99.0, -3.0, 0.0, 0.0, 0.0],
                          [np.nan, 2.0, 0.5, 0.5, 0.5]])],
            axis=0,
        )
        self._assert_binning_matches(booster.bin_mapper, probe)


# ---------------------------------------------------------------------------
# cache behavior: build-once residency, dropped on pickle
# ---------------------------------------------------------------------------
class TestCaches:
    def test_packed_built_once_and_reused(self, reg_booster):
        booster, X = reg_booster
        b = _clone(booster, "packed")
        assert b._packed_forests == {} and b._device_binner is None
        b.predict(X)
        T = b.num_iterations
        assert set(b._packed_forests) == {T}
        pf = b._packed_forests[T]
        b.predict(X)
        assert b._packed_forests[T] is pf  # no rebuild on the warm call
        b.predict(X, num_iteration=5)
        assert set(b._packed_forests) == {T, 5}

    def test_scan_device_slices_cached(self, reg_booster):
        booster, X = reg_booster
        b = _clone(booster, "scan")
        assert b._dev_slices == {}
        b.predict(X)
        T = b.num_iterations
        assert set(b._dev_slices) == {T}
        dev = b._dev_slices[T]
        b.predict(X)
        assert b._dev_slices[T] is dev

    def test_pickle_drops_device_state(self, reg_booster):
        booster, X = reg_booster
        b = _clone(booster, "packed")
        b.predict_padded(np.zeros((16, X.shape[1])), 1)
        assert b._packed_forests and b._device_binner is not None
        b2 = pickle.loads(pickle.dumps(b))
        assert b2._packed_forests == {}
        assert b2._pallas_forests == {}
        assert b2._dev_slices == {}
        assert b2._device_binner is None
        assert b2._predict_warm == set()
        # and the revived booster still predicts identically
        assert np.array_equal(b.predict(X), b2.predict(X))
