"""Native (C++) binner vs pure-numpy parity: identical boundaries and bins.

The native path is the SURVEY.md §7.1 "C++ where the reference was native"
host-side binner (reference N1 Dataset-build path); correctness contract is
bit-identity with the numpy implementation on the same inputs.
"""

import os
import numpy as np
import pytest

from mmlspark_tpu.native import get_binner_lib
from mmlspark_tpu.ops.binning import BinMapper


def _data(n=20_000, F=9, seed=0):
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(n, F))
    X[:, 1] = rng.integers(0, 5, size=n)  # low cardinality → exact bins
    X[:, 2] = rng.exponential(size=n)
    X[rng.random((n, F)) < 0.05] = np.nan  # missing values everywhere
    X[:, 3] = rng.integers(0, 30, size=n)  # categorical column
    return X


def _fit_both(X, **kw):
    import mmlspark_tpu.ops.binning as binning

    native = BinMapper(**kw).fit(X)
    orig = binning.BinMapper._fit_native
    binning.BinMapper._fit_native = lambda self, Xs, cs: None
    try:
        numpy_bm = BinMapper(**kw).fit(X)
    finally:
        binning.BinMapper._fit_native = orig
    return native, numpy_bm


@pytest.mark.skipif(get_binner_lib() is None, reason="native binner unavailable")
class TestNativeBinner:
    def test_lib_compiles_and_loads(self):
        assert get_binner_lib() is not None

    @pytest.mark.parametrize("max_bin", [15, 255])
    def test_fit_boundaries_identical(self, max_bin):
        X = _data()
        nat, ref = _fit_both(X, max_bin=max_bin, categorical_features=[3])
        assert len(nat.upper_bounds) == len(ref.upper_bounds)
        for f, (a, b) in enumerate(zip(nat.upper_bounds, ref.upper_bounds)):
            np.testing.assert_array_equal(a, b, err_msg=f"feature {f}")

    def test_transform_bins_identical(self):
        X = _data()
        nat, ref = _fit_both(X, max_bin=63, categorical_features=[3])
        import mmlspark_tpu.ops.binning as binning

        b_nat = nat.transform(X)
        orig = binning.BinMapper._transform_native
        binning.BinMapper._transform_native = lambda self, X_, cs: (None, False)
        try:
            b_ref = ref.transform(X)
        finally:
            binning.BinMapper._transform_native = orig
        np.testing.assert_array_equal(b_nat, b_ref)

    def test_train_end_to_end_with_native(self):
        from mmlspark_tpu.engine.booster import Dataset, train

        X = _data(n=2000)
        y = (np.nan_to_num(X[:, 0]) > 0).astype(np.float64)
        booster = train(
            dict(objective="binary", num_iterations=5, num_leaves=7,
                 categorical_feature=[3]),
            Dataset(X, y),
        )
        p = booster.predict(X)
        assert np.isfinite(p).all()


class TestSanitizers:
    def test_asan_ubsan_harness_passes(self):
        """SURVEY.md §5.2 (rebuild note): the C++ binner AND predictor get
        an ASAN/UBSAN pass.  Compiles native/sanitize_main.cpp (binner
        edge cases + predictor model-walk/malformed-load cases) with both
        sanitizers (-fno-sanitize-recover aborts on any finding); exit 0 =
        memory- and UB-clean."""
        import shutil
        import subprocess
        import tempfile

        if shutil.which("g++") is None:
            pytest.skip("no g++ toolchain")
        import mmlspark_tpu.native as native

        src_dir = os.path.dirname(native.__file__)
        with tempfile.TemporaryDirectory() as td:
            exe = os.path.join(td, "binner_sanitize")
            build = subprocess.run(
                [
                    "g++", "-std=c++17", "-O1", "-g", "-pthread",
                    "-fsanitize=address,undefined",
                    "-fno-sanitize-recover=all",
                    os.path.join(src_dir, "binner.cpp"),
                    os.path.join(src_dir, "predictor.cpp"),
                    os.path.join(src_dir, "sanitize_main.cpp"),
                    "-o", exe,
                ],
                capture_output=True, text=True, timeout=180,
            )
            if build.returncode != 0 and "asan" in build.stderr.lower():
                pytest.skip(f"toolchain lacks sanitizer runtimes: {build.stderr[-300:]}")
            assert build.returncode == 0, build.stderr[-2000:]
            run = subprocess.run(
                [exe], capture_output=True, text=True, timeout=300,
            )
            assert run.returncode == 0, (run.stdout + run.stderr)[-2000:]
            assert "all cases OK" in run.stdout

    def test_tsan_harness_passes(self):
        """SURVEY.md §5.2 + VERDICT r3 #8: the binner is THREADED
        (std::thread over features), so data races need ThreadSanitizer —
        ASAN/UBSAN cannot see them (and TSAN cannot combine with ASAN,
        hence the separate build).  The harness's multi-thread cases
        (incl. threads > features) run under -fsanitize=thread."""
        import shutil
        import subprocess
        import tempfile

        if shutil.which("g++") is None:
            pytest.skip("no g++ toolchain")
        import mmlspark_tpu.native as native

        src_dir = os.path.dirname(native.__file__)
        with tempfile.TemporaryDirectory() as td:
            exe = os.path.join(td, "binner_tsan")
            build = subprocess.run(
                [
                    "g++", "-std=c++17", "-O1", "-g", "-pthread",
                    "-fsanitize=thread",
                    "-fno-sanitize-recover=all",
                    os.path.join(src_dir, "binner.cpp"),
                    os.path.join(src_dir, "predictor.cpp"),
                    os.path.join(src_dir, "sanitize_main.cpp"),
                    "-o", exe,
                ],
                capture_output=True, text=True, timeout=180,
            )
            if build.returncode != 0 and "tsan" in build.stderr.lower():
                pytest.skip(f"toolchain lacks TSAN runtime: {build.stderr[-300:]}")
            assert build.returncode == 0, build.stderr[-2000:]
            run = subprocess.run([exe], capture_output=True, text=True,
                                 timeout=300)
            assert run.returncode == 0, (run.stdout + run.stderr)[-2000:]
            assert "all cases OK" in run.stdout


class TestNativeCatTransform:
    def test_cat_columns_identical_to_numpy(self):
        """r5: the categorical transform moved into C++ (the 26-cat numpy
        pass was ~10.8 s of a 4M-row criteo-schema Dataset build).  The
        kernel must match the numpy reference bit for bit, including
        NaN → missing, unseen categories → missing, negative and
        non-contiguous category ids."""
        import mmlspark_tpu.ops.binning as binning
        from mmlspark_tpu.ops.binning import BinMapper

        rng = np.random.default_rng(0)
        n = 5000
        cats1 = rng.choice([-7, -1, 0, 3, 8, 120, 9999], size=n).astype(float)
        cats2 = rng.integers(0, 40, size=n).astype(float)
        num = rng.normal(size=n)
        X = np.column_stack([cats1, num, cats2])
        X[::97, 0] = np.nan
        X[::41, 2] = np.nan
        bm = BinMapper(max_bin=63, categorical_features=(0, 2)).fit(X)

        # unseen categories at transform time
        X2 = X.copy()
        X2[::13, 0] = 55555.0
        X2[::17, 2] = -3.0
        b_nat = bm.transform(X2)

        orig = binning.BinMapper._transform_native
        binning.BinMapper._transform_native = (
            lambda self, X_, cs: (None, False)
        )
        try:
            b_ref = bm.transform(X2)
        finally:
            binning.BinMapper._transform_native = orig
        np.testing.assert_array_equal(b_nat, b_ref)

    def test_mixed_native_numeric_numpy_cat_agree(self):
        # the cats_native=False path (e.g. a build without the cat symbol)
        # still composes: numeric via C++, cats via numpy
        import mmlspark_tpu.ops.binning as binning
        from mmlspark_tpu.ops.binning import BinMapper

        rng = np.random.default_rng(1)
        X = np.column_stack([
            rng.integers(0, 9, size=800).astype(float),
            rng.normal(size=800),
        ])
        bm = BinMapper(max_bin=31, categorical_features=(0,)).fit(X)
        full = bm.transform(X)

        orig = binning.BinMapper._transform_native

        def numeric_only(self, X_, cs):
            out, _ = orig(self, X_, cs)
            return out, False  # pretend the cat kernel is unavailable

        binning.BinMapper._transform_native = numeric_only
        try:
            mixed = bm.transform(X)
        finally:
            binning.BinMapper._transform_native = orig
        np.testing.assert_array_equal(full, mixed)


class TestCatTransformEdgeCases:
    def _both(self, bm, X):
        import mmlspark_tpu.ops.binning as binning

        nat = bm.transform(X)
        orig = binning.BinMapper._transform_native
        binning.BinMapper._transform_native = (
            lambda self, X_, cs: (None, False)
        )
        try:
            ref = bm.transform(X)
        finally:
            binning.BinMapper._transform_native = orig
        return nat, ref

    def test_all_nan_cat_column_is_all_missing(self):
        # r5 review: an all-NaN-at-fit categorical column has an EMPTY
        # category table; both paths must yield missing_bin everywhere
        # (the numpy path used to IndexError on it).
        from mmlspark_tpu.ops.binning import BinMapper

        X = np.column_stack([np.full(200, np.nan), np.arange(200.0)])
        bm = BinMapper(max_bin=15, categorical_features=(0,)).fit(X)
        X2 = X.copy()
        X2[::3, 0] = 7.0  # even real values: no fitted categories -> missing
        nat, ref = self._both(bm, X2)
        np.testing.assert_array_equal(nat, ref)
        assert (nat[:, 0] == bm.missing_bin).all()

    def test_out_of_int64_range_ids_match_numpy(self):
        # 1e19-style hash ids overflow int64: numpy's astype gives
        # INT64_MIN (and the fit table CONTAINS it), so the C++ cast must
        # replicate that, not UB
        from mmlspark_tpu.ops.binning import BinMapper

        rng = np.random.default_rng(2)
        col = np.where(rng.random(400) < 0.5, 1e19, 3.0)
        X = np.column_stack([col, rng.normal(size=400)])
        with np.errstate(invalid="ignore"):
            bm = BinMapper(max_bin=15, categorical_features=(0,)).fit(X)
            nat, ref = self._both(bm, X)
        np.testing.assert_array_equal(nat, ref)

    def test_negative_categorical_index_ignored(self):
        # bogus negative entries in categorical_features stay ignored
        from mmlspark_tpu.ops.binning import BinMapper

        rng = np.random.default_rng(3)
        X = rng.normal(size=(300, 3))
        bm = BinMapper(max_bin=15, categorical_features=(-1,)).fit(X)
        nat, ref = self._both(bm, X)
        np.testing.assert_array_equal(nat, ref)


class TestBuiltFromSource:
    def test_binary_is_named_after_its_source(self, tmp_path, monkeypatch):
        """A copied tree resets mtimes, so a stale binary must never be
        trusted by age: the library is named after a hash of its source,
        an edited source builds under another name, and the binary of the
        old source is removed."""
        import shutil

        import mmlspark_tpu.native as native

        if shutil.which("g++") is None:
            pytest.skip("no g++ toolchain")
        src = tmp_path / "binner.cpp"
        shutil.copy(os.path.join(os.path.dirname(native.__file__), "binner.cpp"), src)
        monkeypatch.delenv("MMLSPARK_TPU_NO_NATIVE", raising=False)
        assert native.load_native_lib(str(src), native._bind_binner) is not None
        first = sorted(p.name for p in tmp_path.glob("_binner-*.so"))
        assert len(first) == 1
        # a stale binary that is NEWER than an edited source is not used
        src.write_text(src.read_text() + "\n// edited\n")
        os.utime(src, (1, 1))
        native._libs.pop(str(src))
        assert native.load_native_lib(str(src), native._bind_binner) is not None
        second = sorted(p.name for p in tmp_path.glob("_binner-*.so"))
        assert len(second) == 1 and second != first
        native._libs.pop(str(src))
