"""GBDT engine tests: binning, histograms, tree growth, boosting quality.

Quality thresholds follow the reference's benchmark-pinned test style
(SURVEY.md §4.3–4.4: AUC-threshold asserts on small datasets), with sklearn's
HistGradientBoosting as the offline stand-in oracle for stock LightGBM
(BASELINE.md "Actions" item 3)."""

import numpy as np
import pytest

from mmlspark_tpu.engine.booster import Dataset, train
from mmlspark_tpu.ops.binning import BinMapper, merge_samples_and_fit
from mmlspark_tpu.ops.objectives import get_objective


def _toy_xy(n=400, f=8, seed=0):
    assert f >= 4
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(n, f))
    logits = X[:, 0] * 1.5 - X[:, 1] + 0.5 * X[:, 2] * X[:, 3]
    y = (logits + rng.normal(scale=0.5, size=n) > 0).astype(np.float64)
    return X, y


class TestBinning:
    def test_distinct_values_get_exact_bins(self):
        X = np.array([[0.0], [1.0], [2.0], [1.0], [0.0]])
        bm = BinMapper(max_bin=255).fit(X)
        b = bm.transform(X)[:, 0]
        assert set(b) == {0, 1, 2}
        # raw thresholds are midpoints
        assert bm.bin_to_threshold(0, 0) == 0.5

    def test_quantile_binning_balanced(self):
        rng = np.random.default_rng(0)
        X = rng.normal(size=(10_000, 1))
        bm = BinMapper(max_bin=16).fit(X)
        b = bm.transform(X)[:, 0]
        counts = np.bincount(b, minlength=16)
        assert counts[:16].min() > 200  # roughly equal mass

    def test_missing_goes_to_missing_bin(self):
        X = np.array([[1.0], [np.nan], [2.0]])
        bm = BinMapper(max_bin=8).fit(X)
        b = bm.transform(X)[:, 0]
        assert b[1] == bm.missing_bin
        assert b[0] != bm.missing_bin

    def test_categorical_binning(self):
        X = np.array([[3.0], [3.0], [7.0], [9.0], [7.0], [3.0]])
        bm = BinMapper(max_bin=8, categorical_features=[0]).fit(X)
        b = bm.transform(X)[:, 0]
        assert len(set(b)) == 3
        # unseen category → missing bin
        b2 = bm.transform(np.array([[5.0]]))[:, 0]
        assert b2[0] == bm.missing_bin

    def test_merged_sample_fit(self):
        X, _ = _toy_xy()
        bm = merge_samples_and_fit([X[:200], X[200:]], max_bin=32)
        assert bm.num_features == X.shape[1]
        assert bm.transform(X).max() < bm.num_bins

    def test_roundtrip_dict(self):
        X, _ = _toy_xy(100, 4)
        bm = BinMapper(max_bin=16).fit(X)
        bm2 = BinMapper.from_dict(bm.to_dict())
        np.testing.assert_array_equal(bm.transform(X), bm2.transform(X))


def _hist_chunk_fn(kind, backend):
    """The chunk function a builder hands its chunk loop, called as ``fn(bins_t,
    vals, leaf, W, B)``.  A plain build reads no leaf ids; on the Pallas
    backend it is the factorized body at one slot with every row in slot 0
    (its builder parks the masked-out rows, whose zeroed ``vals`` add
    nothing there either)."""
    import jax.numpy as jnp

    from mmlspark_tpu.ops import histogram as H
    from mmlspark_tpu.ops import pallas_hist as PH

    return {
        ("plain", "scatter"): lambda b, v, leaf, W, B: H._scatter_hist_chunk(b, v, B),
        ("plain", "pallas"): lambda b, v, leaf, W, B: PH.pallas_hist_by_leaf_nibble_chunk(
            b, v, jnp.zeros(b.shape[1], jnp.int32), 1, B)[:, 0],
        ("by_leaf", "scatter"): H._scatter_hist_by_leaf_chunk,
        ("by_leaf", "pallas"): PH.pallas_hist_by_leaf_nibble_chunk,
    }[kind, backend]


def _numpy_hist(kind, bins_t, vals, leaf, rows, W, B):
    """float64 sums over ``rows`` of (F, n) bins: (3, F, B), or (3, W, F, B) by leaf."""
    F = bins_t.shape[0]
    want = np.zeros((3, F, B) if kind == "plain" else (3, W, F, B), np.float64)
    for c in range(3):
        for f in range(F):
            if kind == "plain":
                np.add.at(want[c, f], bins_t[f, rows], vals[c, rows])
            else:
                np.add.at(want[c, :, f], (leaf[rows], bins_t[f, rows]), vals[c, rows])
    return want


class TestHistogram:
    def test_scatter_matches_numpy(self):
        import jax.numpy as jnp

        from mmlspark_tpu.ops.histogram import build_histogram

        rng = np.random.default_rng(1)
        n, F, B = 257, 5, 16
        bins = rng.integers(0, B, size=(n, F))
        grad = rng.normal(size=n)
        hess = rng.uniform(0.1, 1, size=n)
        mask = rng.random(n) > 0.3
        vals = np.stack([grad, hess, np.ones(n)], 0)  # (3, n) channel-major
        hist = np.asarray(
            build_histogram(jnp.asarray(bins.T), jnp.asarray(vals), jnp.asarray(mask), B)
        )  # (3, F, B)
        for f in range(F):
            for b in range(B):
                sel = (bins[:, f] == b) & mask
                np.testing.assert_allclose(hist[0, f, b], grad[sel].sum(), rtol=1e-5, atol=1e-5)
                np.testing.assert_allclose(hist[2, f, b], sel.sum(), rtol=1e-6)

    def test_chunked_matches_unchunked(self):
        import jax.numpy as jnp

        from mmlspark_tpu.ops.histogram import build_histogram

        rng = np.random.default_rng(3)
        n, F, B = 512, 3, 8
        bins = jnp.asarray(rng.integers(0, B, size=(F, n)))
        vals = jnp.asarray(rng.normal(size=(3, n)))
        mask = jnp.ones(n, bool)
        h1 = build_histogram(bins, vals, mask, B, chunk=128)
        h2 = build_histogram(bins, vals, mask, B, chunk=1024)
        np.testing.assert_allclose(np.asarray(h1), np.asarray(h2), rtol=1e-4, atol=1e-4)

    # -- the chunk loop over the growers' (F, n) matrix: n = 4 chunks of 256 --
    _N, _CHUNK, _B, _W = 1024, 256, 256, 8
    _chunk_cases = pytest.mark.parametrize(
        "kind,backend,F",
        [(k, b, F) for k in ("by_leaf", "plain") for b in ("scatter", "pallas") for F in (5, 39)],
    )

    def _chunk_inputs(self, F, seed):
        """uint8 bins (n, F), vals (3, n), leaf ids parked on both sides of
        ``[0, W)``, a row mask; chunk 1 is all parked, chunk 2 all masked."""
        rng = np.random.default_rng(seed)
        n, c = self._N, self._CHUNK
        bins = rng.integers(0, self._B, size=(n, F)).astype(np.uint8)
        vals = rng.normal(size=(3, n)).astype(np.float32)
        leaf = rng.integers(-2, self._W + 2, size=n).astype(np.int32)
        leaf[c:2 * c] = np.where(rng.random(c) < 0.5, -1, self._W)
        mask = rng.random(n) > 0.3
        mask[2 * c:3 * c] = False
        return bins, vals, leaf, mask

    def _chunk_build(self, kind, bins, vals, leaf, mask, **kw):
        """Either builder on the same rows; by-leaf takes the mask as zeroed ``vals``."""
        import jax.numpy as jnp

        from mmlspark_tpu.ops.histogram import build_histogram, build_histogram_by_leaf

        if kind == "plain":
            return build_histogram(jnp.asarray(bins), jnp.asarray(vals), jnp.asarray(mask), self._B, **kw)
        return build_histogram_by_leaf(
            jnp.asarray(bins), jnp.asarray(np.where(mask[None, :], vals, 0)), jnp.asarray(leaf),
            self._W, self._B, **kw,
        )

    @_chunk_cases
    def test_scan_sums_are_the_chunk_function_on_host_slices(self, kind, backend, F):
        """A chunk sliced out of (F, n) inside the scan is the chunk a host
        slice hands the same chunk function: the scan's sums equal theirs,
        added in order, bit for bit, and the unchunked call's to float
        tolerance."""
        import jax.numpy as jnp

        bins, vals, leaf, mask = self._chunk_inputs(F, seed=F)
        kw = dict(backend=backend, chunk=self._CHUNK)
        t = np.asarray(self._chunk_build(kind, bins.T, vals, leaf, mask, **kw))
        u = np.asarray(self._chunk_build(kind, bins.T, vals, leaf, mask, backend=backend, chunk=self._N))
        fn = _hist_chunk_fn(kind, backend)
        per_row = [np.where(mask[None, :], vals, 0).astype(np.float32), leaf]
        want = jnp.zeros(t.shape, jnp.float32)
        for i in range(self._N // self._CHUNK):
            sl = slice(i * self._CHUNK, (i + 1) * self._CHUNK)
            want = want + fn(jnp.asarray(bins.T[:, sl]), *(jnp.asarray(x[..., sl]) for x in per_row), self._W, self._B)
        np.testing.assert_array_equal(t, np.asarray(want))
        np.testing.assert_allclose(t, u, rtol=1e-5, atol=1e-4)

    @_chunk_cases
    def test_chunks_drop_parked_and_masked_rows(self, kind, backend, F):
        """Parked leaf ids and masked rows drop out in every chunk: the sums
        are numpy's over the kept rows, whatever the dropped rows hold."""
        bins, vals, leaf, mask = self._chunk_inputs(F, seed=100 + F)
        kw = dict(backend=backend, chunk=self._CHUNK)
        got = np.asarray(self._chunk_build(kind, bins.T, vals, leaf, mask, **kw))
        kept = mask if kind == "plain" else mask & (leaf >= 0) & (leaf < self._W)
        want = _numpy_hist(kind, bins.T, vals, leaf, np.flatnonzero(kept), self._W, self._B)
        np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)
        scrambled = np.where(kept[:, None], bins, np.uint8(255) - bins)
        again = np.asarray(self._chunk_build(kind, scrambled.T, vals, leaf, mask, **kw))
        np.testing.assert_array_equal(got, again)

    @_chunk_cases
    def test_chunk_loop_relays_no_whole_array(self, kind, backend, F):
        """The relayout cannot come back unseen on a CPU: the chunked
        call's jaxpr holds no transpose or reshape of the whole
        integer bins matrix and no transpose of the whole ``vals``; every
        chunk function here slices (the scatter functions; the Pallas body,
        whose 1,024-row blocks a chunk of 256 rows does not fill), and
        slices the matrix itself."""
        import jax

        bins, *rest = self._chunk_inputs(F, seed=0)
        jaxpr = jax.make_jaxpr(
            lambda b: self._chunk_build(kind, b, *rest, backend=backend, chunk=self._CHUNK)
        )(bins.T)
        whole_bins, whole_vals, sliced = self._N * F, 3 * self._N, []

        def walk(jp):
            for eqn in jp.eqns:
                name = eqn.primitive.name
                for a in (v.aval for v in eqn.invars[:1]):  # the operand, where there is one
                    if np.issubdtype(a.dtype, np.integer) and a.size == whole_bins:
                        assert name not in ("transpose", "reshape"), eqn
                        if name == "dynamic_slice":
                            sliced.append(a.shape)
                    assert not (name == "transpose" and a.size == whole_vals), eqn
                for sub in jax.core.jaxprs_in_params(eqn.params):
                    walk(sub)

        walk(jaxpr.jaxpr)
        assert sliced == [(F, self._N)]

    # -- the kernels read the matrix in place: 3 chunks of whole row blocks --
    _bodies = ("plain", "by_leaf", "nibble")

    def _in_place_build(self, body, bins_t, vals, leaf, chunk):
        """The route at 256 bins: ``plain`` the per-feature build (the
        factorized body at one slot), ``by_leaf`` a window of W = 32 and
        ``nibble`` one of W = 8."""
        import jax.numpy as jnp

        from mmlspark_tpu.ops import histogram as H

        hq = H.HistQuantize("int32", 0, jnp.ones(3, jnp.float32)) if vals.dtype == np.int16 else None
        kw = dict(backend="pallas", chunk=chunk, quantize=hq)
        if body == "plain":
            return H.build_histogram(bins_t, vals, jnp.ones(bins_t.shape[1], bool), self._B, **kw)
        return H.build_histogram_by_leaf(bins_t, vals, leaf, 32 if body == "by_leaf" else 8, self._B, **kw)

    @pytest.mark.parametrize("F", [13, 39, 1])
    @pytest.mark.parametrize("dtype", ["float32", "int16"])
    @pytest.mark.parametrize("body", ["plain", "by_leaf", "nibble"])
    def test_reading_in_place_sums_what_each_chunks_own_slice_sums(self, body, dtype, F):
        """The whole matrix with a chunk index, against the parent's path
        written out: the same body on each chunk's own host slice, added in
        order.  Equal to the bit, at column counts that are no whole
        blocks of 8, with rows parked on both sides of the window."""
        import jax.numpy as jnp

        from mmlspark_tpu.ops import pallas_hist as PH

        rng = np.random.default_rng(F)
        chunk, W = 1024, {"plain": 1, "by_leaf": 32, "nibble": 8}[body]
        n = 3 * chunk
        bins_t = rng.integers(0, self._B, size=(F, n)).astype(np.uint8)
        if dtype == "int16":
            vals = rng.integers(-127, 128, size=(3, n)).astype(np.int16)
        else:
            vals = rng.normal(size=(3, n)).astype(np.float32)
        leaf = rng.integers(-2, W + 2, size=n).astype(np.int32)
        leaf[chunk:chunk + 300] = np.where(rng.random(300) < 0.5, -1, W)
        got = self._in_place_build(body, jnp.asarray(bins_t), jnp.asarray(vals), jnp.asarray(leaf), chunk)
        fn = _hist_chunk_fn("plain" if body == "plain" else "by_leaf", "pallas")
        want = 0
        for i in range(n // chunk):
            sl = slice(i * chunk, (i + 1) * chunk)
            want = want + fn(jnp.asarray(bins_t[:, sl]), vals[:, sl], leaf[sl], W, self._B)
        assert want.dtype == (jnp.int32 if dtype == "int16" else jnp.float32)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want).astype(np.float32))

    def test_pallas_chunk_loop_touches_no_array_as_long_as_the_matrix(self):
        """Traced under ``backend="pallas"`` with chunks of whole row blocks,
        the scan's body holds the kernel's call on the whole arrays and no
        ``pad``, ``dynamic_slice`` or ``convert_element_type`` of anything
        with the matrix's row count, on any of the three routes."""
        import jax
        import jax.numpy as jnp

        chunk, F = 1024, 39
        n = 3 * chunk
        args = (jnp.zeros((F, n), jnp.uint8), jnp.zeros((3, n), jnp.float32), jnp.zeros(n, jnp.int32))
        for body in self._bodies:
            jaxpr = jax.make_jaxpr(lambda *a, body=body: self._in_place_build(body, *a, chunk))(*args)
            bodies, calls = [], []

            def walk(jp, in_scan):
                for eqn in jp.eqns:
                    name = eqn.primitive.name
                    if in_scan and name in ("pad", "dynamic_slice", "convert_element_type"):
                        assert all(n not in v.aval.shape for v in eqn.invars if hasattr(v.aval, "shape")), (body, eqn)
                    if in_scan and eqn.params.get("name") == "_pallas_hist_by_leaf_nibble":
                        calls.append([v.aval.shape for v in eqn.invars])
                    if name == "scan":
                        bodies.append(eqn.params["length"])
                    for sub in jax.core.jaxprs_in_params(eqn.params):
                        walk(sub, in_scan or name == "scan")

            walk(jaxpr.jaxpr, False)
            assert bodies[0] == 3
            assert calls == [[(F, n), (3, n), (1, n), (1,)]], (body, calls)

    def test_pallas_matches_scatter(self):
        import jax.numpy as jnp

        from mmlspark_tpu.ops.histogram import build_histogram

        rng = np.random.default_rng(5)
        for (n, F, B) in [(257, 5, 16), (1024, 9, 64)]:
            bins = jnp.asarray(rng.integers(0, B, size=(F, n)))
            vals = jnp.asarray(rng.normal(size=(3, n)))
            mask = jnp.asarray(rng.random(n) > 0.3)
            h1 = build_histogram(bins, vals, mask, B, backend="scatter")
            h2 = build_histogram(bins, vals, mask, B, backend="pallas")
            np.testing.assert_allclose(np.asarray(h1), np.asarray(h2), rtol=1e-4, atol=1e-4)



class TestSharedChunkBodies:
    """One body per kernel whatever the value dtype, one layout, two backends."""

    _N, _F, _B, _W = 1024, 5, 256, 8

    @staticmethod
    def _kernels_reached(fn, *args):
        """Names of the jitted Pallas wrappers in ``fn``'s jaxpr."""
        import jax

        found = set()

        def walk(jp):
            for eqn in jp.eqns:
                name = eqn.params.get("name", "")
                if eqn.primitive.name in ("jit", "pjit") and name.startswith("_pallas_hist"):
                    found.add(name)
                for sub in jax.core.jaxprs_in_params(eqn.params):
                    walk(sub)

        walk(jax.make_jaxpr(fn)(*args).jaxpr)
        return found

    @pytest.mark.parametrize(
        "kind,backend,dtype,B,W",
        [(k, b, d, 256, 8) for k in ("plain", "by_leaf") for b in ("scatter", "pallas") for d in ("float32", "int16")]
        # a wider window (M = 3*W*2 over 128) and 128 bins (nothing to factor: H = 1) take the same body
        + [("by_leaf", "pallas", d, B, W) for d in ("float32", "int16") for B, W in ((256, 32), (128, 8))],
    )
    def test_body_sums_in_the_accumulator_its_vals_ask_for(self, kind, backend, dtype, B, W):
        """The shared chunk body gives numpy's sums: int16 buckets exactly
        and as int32, float32 values to 1e-4 and as float32.  Through
        either builder, the Pallas backend reaches the factorized body at
        every shape, whatever it sums."""
        import jax.numpy as jnp

        from mmlspark_tpu.ops import histogram as H

        rng = np.random.default_rng(29)
        n, F = self._N, self._F
        bins_t = rng.integers(0, B, size=(F, n)).astype(np.uint8)
        leaf = rng.integers(-2, W + 2, size=n).astype(np.int32)
        if dtype == "int16":
            vals = rng.integers(-H.QMAX, H.QMAX + 1, size=(3, n)).astype(np.int16)
        else:
            vals = rng.normal(size=(3, n)).astype(np.float32)
        fn = _hist_chunk_fn(kind, backend)
        got = fn(jnp.asarray(bins_t), jnp.asarray(vals), jnp.asarray(leaf), W, B)
        rows = np.flatnonzero((leaf >= 0) & (leaf < W)) if kind == "by_leaf" else np.arange(n)
        want = _numpy_hist(kind, bins_t, vals, leaf, rows, W, B)
        if dtype == "int16":
            assert got.dtype == jnp.int32
            np.testing.assert_array_equal(np.asarray(got), want.astype(np.int32))
        else:
            assert got.dtype == jnp.float32
            np.testing.assert_allclose(np.asarray(got), want, rtol=1e-4, atol=1e-4)
        if backend == "pallas":
            hq = H.HistQuantize("int32", 0, jnp.ones(3, jnp.float32)) if dtype == "int16" else None
            kw = dict(backend="pallas", quantize=hq)
            build = (
                (lambda b, v, l: H.build_histogram_by_leaf(b, v, l, W, B, **kw)) if kind == "by_leaf"
                else (lambda b, v, l: H.build_histogram(b, v, l >= 0, B, **kw))
            )
            assert self._kernels_reached(build, bins_t, vals, leaf) == {"_pallas_hist_by_leaf_nibble"}

    # the slots of each route: the per-feature build's one, a window of 32, one of 8
    @pytest.mark.parametrize(
        "W", [pytest.param(1, id="_pallas_hist"), pytest.param(32, id="_pallas_hist_by_leaf"),
              pytest.param(8, id="_pallas_hist_by_leaf_nibble")],
    )
    def test_float_kernel_bodies_hold_no_int32_accumulator(self, W):
        """The dtype branch is a Python ``if``: a float build's traced
        kernel casts no float to int32 and holds no int32 array as wide as
        an accumulator tile, where the bucket build of the same body holds
        both, at every number of slots."""
        import jax
        import jax.numpy as jnp

        from mmlspark_tpu.ops import pallas_hist as PH

        n, F, B, bf = 1536, 8, 256, 8
        acc_lanes = {bf * PH._NIBBLE_LO}  # no other array here is this wide
        static = dict(num_leaves=W, num_bins=B, bm=512, bf=bf, rm=256, chunk=n, interpret=True, precision="highest")
        rows = [jnp.zeros((1, n), jnp.int32), jnp.zeros(1, jnp.int32)]  # the leaf ids, the chunk's index

        def int32_accumulation(val_dtype):
            jaxpr = jax.make_jaxpr(lambda *a: PH._pallas_hist_by_leaf_nibble(*a, **static))(
                jnp.zeros((F, n), jnp.uint8), jnp.zeros((3, n), val_dtype), *rows
            )
            hits, seen = [], set()

            def walk(jp):
                for eqn in jp.eqns:
                    seen.add(eqn.primitive.name)
                    for aval in (v.aval for v in eqn.outvars):
                        if getattr(aval, "dtype", None) == jnp.int32 and aval.shape[-1:] and aval.shape[-1] in acc_lanes:
                            hits.append(eqn)
                    if eqn.primitive.name == "convert_element_type" and eqn.params["new_dtype"] == jnp.int32:
                        if jnp.issubdtype(eqn.invars[0].aval.dtype, jnp.floating):
                            hits.append(eqn)
                    for sub in jax.core.jaxprs_in_params(eqn.params):
                        walk(sub)

            walk(jaxpr.jaxpr)
            assert {"pallas_call", "dot_general"} <= seen
            return hits, jaxpr.out_avals[0].dtype

        hits, out = int32_accumulation(jnp.float32)
        assert not hits and out == jnp.float32, hits[:1]
        hits, out = int32_accumulation(jnp.int16)
        assert hits and out == jnp.int32

    @pytest.mark.parametrize(
        "builder,rows",
        [("build_histogram", ["mask"]), ("build_histogram_by_leaf", ["leaf_ids", "num_leaves"])],
    )
    def test_builders_take_one_layout_two_backends_no_wire(self, builder, rows):
        """Ten and eleven parameters: no layout flag, no wire dtype; the
        third backend's name is unknown."""
        import inspect

        import jax.numpy as jnp

        from mmlspark_tpu.ops import histogram as H

        fn = getattr(H, builder)
        assert list(inspect.signature(fn).parameters) == (
            ["bins", "vals", *rows, "num_bins", "backend", "chunk", "axis_name", "precision", "merge", "quantize"]
        )
        per_row = (jnp.ones(4, bool),) if builder == "build_histogram" else (jnp.zeros(4, jnp.int32), 2)
        with pytest.raises(ValueError, match="unknown hist backend"):
            fn(jnp.zeros((2, 4), jnp.uint8), jnp.zeros((3, 4)), *per_row, 4, backend="onehot")


class TestByLeafKernels:
    @pytest.mark.parametrize("B,W", [(256, 12), (255, 12), (129, 5), (256, 1)])
    def test_nibble_kernel_parity(self, B, W):
        """The factorized hi/lo body must match numpy's float64 sums to
        float-summation ulps (run in CPU interpret mode here) — it is every
        Pallas build's body and its output feeds split decisions directly."""
        import jax.numpy as jnp

        from mmlspark_tpu.ops.pallas_hist import pallas_hist_by_leaf_nibble_chunk

        rng = np.random.default_rng(B + W)
        n, F = 2048, 9
        # inclusive of bin B-1: the top bin exercises the nibble kernel's
        # hi plane and the H*128 -> num_bins slice at non-power-of-two B
        bins = rng.integers(0, B, size=(F, n))
        vals = rng.normal(size=(3, n)).astype(np.float32)
        # parked ids on both sides of the window range
        leaf = rng.integers(-3, W + 2, size=(n,)).astype(np.int32)
        got = np.asarray(pallas_hist_by_leaf_nibble_chunk(jnp.asarray(bins), jnp.asarray(vals), jnp.asarray(leaf), W, B))
        want = _numpy_hist("by_leaf", bins, vals.astype(np.float64), leaf, np.flatnonzero((leaf >= 0) & (leaf < W)), W, B)
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)

    @pytest.mark.parametrize("vals", ["random", "extreme"])
    @pytest.mark.parametrize("precision", ["default", "highest"])
    @pytest.mark.parametrize("B", [255, 256])
    def test_nibble_kernel_sums_buckets_bit_for_bit(self, B, precision, vals):
        """int16 buckets through the factorized body: int32 sums equal to
        numpy's and to the scatter reference's bit for bit, with parked leaf
        ids on both sides of the window, rows that are no whole ``rm``
        block, and every value at +-QMAX (a sub-block's sum at its
        largest)."""
        import jax.numpy as jnp

        from mmlspark_tpu.ops.histogram import QMAX, _scatter_hist_by_leaf_chunk
        from mmlspark_tpu.ops.pallas_hist import pallas_hist_by_leaf_nibble_chunk

        rng = np.random.default_rng(B)
        n, F, W = 2048 + 37, 9, 8
        bins = rng.integers(0, B, size=(F, n)).astype(np.uint8)
        bins[:, :300] = B - 1  # the top bin, 300 rows deep: the hi plane and the H*128 -> num_bins slice
        if vals == "extreme":
            q = rng.choice(np.array([-QMAX, QMAX], np.int16), size=(3, n))
            q[:, :300] = QMAX  # one bin's sum well past what bf16 holds: 300 * 127
        else:
            q = rng.integers(-QMAX, QMAX + 1, size=(3, n)).astype(np.int16)
        leaf = rng.integers(-3, W + 2, size=n).astype(np.int32)
        args = (jnp.asarray(bins), jnp.asarray(q), jnp.asarray(leaf), W, B)
        got = pallas_hist_by_leaf_nibble_chunk(*args, rm=256, precision=precision)
        assert got.dtype == jnp.int32 and got.shape == (3, W, F, B)
        keep = np.flatnonzero((leaf >= 0) & (leaf < W))
        want = _numpy_hist("by_leaf", bins, q.astype(np.int64), leaf, keep, W, B)
        np.testing.assert_array_equal(np.asarray(got), want.astype(np.int32))
        np.testing.assert_array_equal(np.asarray(got), np.asarray(_scatter_hist_by_leaf_chunk(*args)))

    @pytest.mark.parametrize("F", [39, 64, 136])
    @pytest.mark.parametrize("dtype,B", [("uint8", 256), ("int32", 512)])
    def test_block_shapes_obey_what_the_v5e_compile_taught(self, F, dtype, B):
        """The block choice of ``_prep_by_leaf_chunk``, against the rules
        the Mosaic compile for v5e established (PR 21, PR 37): (a) a block
        is as tall as the matrix or a multiple of 8, for 1-byte bins as for
        4-byte bins, and no column is padded: the operand is the matrix
        handed in; (b) the limit is scoped VMEM, reached when the (3·W,
        bf·B) accumulator passes ``_ACC_BUDGET_ELS``; (c) the bins stay at
        their HBM width (the kernel widens in VMEM); (d) ``rm`` a power of
        two, ``bm`` whole ``rm`` blocks and the chunk whole ``bm`` blocks."""
        import jax.numpy as jnp

        from mmlspark_tpu.ops.pallas_hist import (
            _ACC_BUDGET_ELS,
            _prep_by_leaf_chunk,
        )

        n = 4096
        matrix = jnp.zeros((F, n), dtype)
        for W in (8, 32):
            bins_t, _, leaf_row, c, chunk, bm, bf, rm, _ = _prep_by_leaf_chunk(
                matrix, jnp.zeros((3, n)), jnp.zeros((n,), jnp.int32), W, B, 16384, 32, 1024,
            )
            assert bins_t is matrix and c.shape == (1,) and chunk == n
            assert bf == F or (bf % 8 == 0 and 8 <= bf <= 48)
            assert -(-F // bf) * bf - F < bf  # the last block holds a real column
            assert 3 * W * bf * B <= _ACC_BUDGET_ELS
            assert rm >= 256 and rm & (rm - 1) == 0
            assert bm % rm == 0 and chunk % bm == 0
            assert leaf_row.shape == (1, n)
        if B == 256:
            # the swept blocks of the benchmarked schemas: criteo's 39 columns
            # one block of their own height, the wider ones as they were
            assert bf == {39: 39, 64: 32, 136: 48}[F]

    def test_by_leaf_dispatch_through_build_histogram(self):
        """build_histogram_by_leaf's pallas dispatch (the factorized body at
        every shape: a small window, a wide one, and 64 bins) must agree
        with the scatter reference backend."""
        import jax.numpy as jnp

        from mmlspark_tpu.ops.histogram import build_histogram_by_leaf

        rng = np.random.default_rng(7)
        n, F = 1024, 6
        for B, W in ((256, 8), (256, 32), (64, 8)):
            bins = jnp.asarray(rng.integers(0, B, size=(F, n)))
            vals = jnp.asarray(rng.normal(size=(3, n)), dtype=jnp.float32)
            leaf = jnp.asarray(rng.integers(-1, W + 1, size=(n,)), dtype=jnp.int32)
            ref = np.asarray(build_histogram_by_leaf(bins, vals, leaf, W, B,
                                                     backend="scatter"))
            pal = np.asarray(build_histogram_by_leaf(bins, vals, leaf, W, B,
                                                     backend="pallas"))
            np.testing.assert_allclose(ref, pal, rtol=1e-5, atol=1e-5)


class TestGrowTree:
    def test_single_obvious_split(self):
        """A perfectly separable single feature must split at the boundary."""
        import jax.numpy as jnp

        from mmlspark_tpu.engine.tree import GrowConfig, grow_tree

        n = 100
        bins = np.zeros((n, 1), np.int32)
        bins[50:, 0] = 1
        grad = np.where(np.arange(n) < 50, 1.0, -1.0)
        hess = np.ones(n)
        cfg = GrowConfig(num_bins=9, num_leaves=4, min_data_in_leaf=1, learning_rate=1.0)
        tree, leaf_ids = grow_tree(
            cfg,
            jnp.asarray(bins),
            jnp.asarray(grad, jnp.float32),
            jnp.asarray(hess, jnp.float32),
            jnp.ones(n, jnp.float32),
            jnp.ones(1, bool),
        )
        assert int(tree.num_leaves) == 2  # second split has no gain
        assert int(tree.split_feat[0]) == 0
        assert int(tree.split_bin[0]) == 0
        lv = np.asarray(tree.leaf_value)
        # leaf values = -G/H: left leaf (bin 0) → -1, right → +1
        np.testing.assert_allclose(sorted(lv[:2]), [-1.0, 1.0], atol=1e-5)
        assert (np.asarray(leaf_ids)[:50] != np.asarray(leaf_ids)[50:]).all()

    def test_min_data_constraint(self):
        import jax.numpy as jnp

        from mmlspark_tpu.engine.tree import GrowConfig, grow_tree

        n = 20
        bins = np.zeros((n, 1), np.int32)
        bins[-2:, 0] = 1  # only 2 rows on the right
        grad = np.where(bins[:, 0] == 1, -1.0, 1.0)
        cfg = GrowConfig(num_bins=9, num_leaves=4, min_data_in_leaf=5)
        tree, _ = grow_tree(
            cfg,
            jnp.asarray(bins),
            jnp.asarray(grad, jnp.float32),
            jnp.ones(n, jnp.float32),
            jnp.ones(n, jnp.float32),
            jnp.ones(1, bool),
        )
        assert int(tree.num_leaves) == 1  # split blocked by min_data_in_leaf

    def test_predict_replay_matches_growth(self):
        import jax.numpy as jnp

        from mmlspark_tpu.engine.tree import (
            GrowConfig,
            grow_tree,
            predict_tree_binned,
        )

        rng = np.random.default_rng(4)
        n, F, B = 300, 5, 17
        bins = rng.integers(0, B - 1, size=(n, F))
        grad = rng.normal(size=n)
        cfg = GrowConfig(num_bins=B, num_leaves=8, min_data_in_leaf=5, learning_rate=0.5)
        tree, leaf_ids = grow_tree(
            cfg,
            jnp.asarray(bins),
            jnp.asarray(grad, jnp.float32),
            jnp.ones(n, jnp.float32),
            jnp.ones(n, jnp.float32),
            jnp.ones(F, bool),
        )
        pred = predict_tree_binned(tree, jnp.asarray(bins), B)
        expect = np.asarray(tree.leaf_value)[np.asarray(leaf_ids)]
        np.testing.assert_allclose(np.asarray(pred), expect, rtol=1e-6)


class TestBoosterQuality:
    def test_binary_auc_parity_with_sklearn(self, binary_df):
        from sklearn.ensemble import HistGradientBoostingClassifier
        from sklearn.metrics import roc_auc_score
        from sklearn.model_selection import train_test_split

        from mmlspark_tpu.engine.booster import Dataset, train

        X = np.stack(binary_df["features"])
        y = binary_df["label"]
        Xtr, Xte, ytr, yte = train_test_split(X, y, test_size=0.3, random_state=0)

        booster = train(
            {"objective": "binary", "num_iterations": 40, "num_leaves": 15,
             "learning_rate": 0.2, "min_data_in_leaf": 5},
            Dataset(Xtr, ytr),
        )
        ours = roc_auc_score(yte, booster.predict(Xte, raw_score=True))

        ref = HistGradientBoostingClassifier(
            max_iter=40, max_leaf_nodes=15, learning_rate=0.2, min_samples_leaf=5,
            early_stopping=False,
        ).fit(Xtr, ytr)
        theirs = roc_auc_score(yte, ref.decision_function(Xte))
        assert ours > 0.97
        assert ours > theirs - 0.01, f"ours={ours:.4f} sklearn={theirs:.4f}"

    def test_regression_beats_mean_baseline(self, regression_df):
        from sklearn.model_selection import train_test_split

        from mmlspark_tpu.engine.booster import Dataset, train

        X = np.stack(regression_df["features"])
        y = regression_df["label"]
        Xtr, Xte, ytr, yte = train_test_split(X, y, test_size=0.3, random_state=0)
        booster = train(
            {"objective": "regression", "num_iterations": 50, "num_leaves": 15,
             "learning_rate": 0.1, "min_data_in_leaf": 5},
            Dataset(Xtr, ytr),
        )
        pred = booster.predict(Xte)
        mse = np.mean((pred - yte) ** 2)
        base = np.mean((np.mean(ytr) - yte) ** 2)
        assert mse < base  # beats the mean predictor

        from sklearn.ensemble import HistGradientBoostingRegressor

        ref = HistGradientBoostingRegressor(
            max_iter=50, max_leaf_nodes=15, learning_rate=0.1, min_samples_leaf=5,
            early_stopping=False,
        ).fit(Xtr, ytr)
        ref_mse = np.mean((ref.predict(Xte) - yte) ** 2)
        assert mse < ref_mse * 1.05, f"ours={mse:.1f} sklearn={ref_mse:.1f}"

    def test_early_stopping(self, binary_df):
        from mmlspark_tpu.engine.booster import Dataset, train

        X = np.stack(binary_df["features"])
        y = binary_df["label"]
        booster = train(
            {"objective": "binary", "num_iterations": 200, "num_leaves": 31,
             "early_stopping_round": 3, "metric": "auc", "min_data_in_leaf": 5},
            Dataset(X[:300], y[:300]),
            valid_sets=[Dataset(X[300:], y[300:])],
        )
        assert booster.best_iteration >= 0
        assert booster.num_iterations < 200

    def test_multiclass(self):
        from sklearn.datasets import load_iris

        from mmlspark_tpu.engine.booster import Dataset, train

        X, y = load_iris(return_X_y=True)
        booster = train(
            {"objective": "multiclass", "num_class": 3, "num_iterations": 20,
             "num_leaves": 7, "min_data_in_leaf": 3, "learning_rate": 0.3},
            Dataset(X, y),
        )
        proba = booster.predict(X)
        assert proba.shape == (150, 3)
        np.testing.assert_allclose(proba.sum(axis=1), 1.0, atol=1e-5)
        acc = (proba.argmax(axis=1) == y).mean()
        assert acc > 0.93

    def test_goss_mode(self, binary_df):
        from sklearn.metrics import roc_auc_score

        from mmlspark_tpu.engine.booster import Dataset, train

        X = np.stack(binary_df["features"])
        y = binary_df["label"]
        booster = train(
            {"objective": "binary", "boosting": "goss", "num_iterations": 30,
             "num_leaves": 15, "min_data_in_leaf": 5, "learning_rate": 0.2},
            Dataset(X, y),
        )
        assert roc_auc_score(y, booster.predict(X, raw_score=True)) > 0.97

    def test_weights_shift_predictions(self):
        from mmlspark_tpu.engine.booster import Dataset, train

        X, y = _toy_xy(300, 4, seed=5)
        w_hi = np.where(y > 0, 10.0, 1.0)
        cfgd = {"objective": "binary", "num_iterations": 10, "num_leaves": 7,
                "min_data_in_leaf": 5}
        b0 = train(cfgd, Dataset(X, y))
        b1 = train(cfgd, Dataset(X, y, weight=w_hi))
        assert b1.predict(X).mean() > b0.predict(X).mean()

    def test_pred_leaf_and_importance(self, binary_df):
        from mmlspark_tpu.engine.booster import Dataset, train

        X = np.stack(binary_df["features"])[:200]
        y = binary_df["label"][:200]
        booster = train(
            {"objective": "binary", "num_iterations": 5, "num_leaves": 7,
             "min_data_in_leaf": 5},
            Dataset(X, y),
        )
        leaves = booster.predict(X, pred_leaf=True)
        assert leaves.shape == (200, 5)
        assert leaves.max() < 7
        imp = booster.feature_importance()
        assert imp.sum() > 0 and imp.shape == (X.shape[1],)


class TestTrainingMetric:
    def test_is_provide_training_metric_records_per_iteration(self):
        rng = np.random.default_rng(0)
        X = rng.normal(size=(300, 4))
        y = (X[:, 0] > 0).astype(np.float64)
        from mmlspark_tpu.engine.booster import Dataset, train

        b = train(
            dict(objective="binary", num_iterations=5, num_leaves=7,
                 min_data_in_leaf=5, metric="binary_logloss",
                 is_provide_training_metric=True),
            Dataset(X[:200], y[:200]), valid_sets=[Dataset(X[200:], y[200:])],
        )
        assert "training" in b.evals_result and "valid_0" in b.evals_result
        tr = b.evals_result["training"]["binary_logloss"]
        assert len(tr) == 5
        assert tr[-1] < tr[0]  # training loss decreases

    def test_training_metric_never_drives_early_stopping(self):
        rng = np.random.default_rng(1)
        X = rng.normal(size=(300, 4))
        y = (X[:, 0] > 0).astype(np.float64)
        from mmlspark_tpu.engine.booster import Dataset, train

        b = train(
            dict(objective="binary", num_iterations=30, num_leaves=7,
                 min_data_in_leaf=5, early_stopping_round=3,
                 is_provide_training_metric=True),
            Dataset(X[:200], y[:200]), valid_sets=[Dataset(X[200:], y[200:])],
        )
        # early stopping keyed to valid_0 (training loss keeps improving,
        # so stopping at all proves it watched the validation metric)
        assert b.best_iteration >= 0
        assert len(b.evals_result["training"]["binary_logloss"]) == b.num_iterations


class TestWarmStartAndGuards:
    def test_init_model_continued_training(self):
        from mmlspark_tpu.engine.booster import Dataset, train
        from sklearn.metrics import log_loss

        X, y = _toy_xy(600, 6, seed=9)
        ds = Dataset(X, y)
        cfgd = {"objective": "binary", "num_leaves": 7, "min_data_in_leaf": 5,
                "learning_rate": 0.2}
        b10 = train(dict(cfgd, num_iterations=10), ds)
        b_cont = train(dict(cfgd, num_iterations=10), ds, init_model=b10)
        assert b_cont.num_iterations == 20
        # Continuation must actually continue: loss improves over the base
        # model, and the first 10 trees score identically to the base.
        assert (log_loss(y, b_cont.predict(X))
                < log_loss(y, b10.predict(X)) + 1e-9)
        np.testing.assert_allclose(
            b10.predict(X, raw_score=True),
            b_cont.predict(X, raw_score=True, num_iteration=10),
            rtol=1e-5, atol=1e-5,
        )

    def test_init_model_num_class_mismatch_raises(self):
        from mmlspark_tpu.engine.booster import Dataset, train

        X, y = _toy_xy(200, 4, seed=2)
        base = train({"objective": "binary", "num_iterations": 2}, Dataset(X, y))
        import pytest
        with pytest.raises(ValueError, match="models/iteration"):
            train({"objective": "multiclass", "num_class": 3, "num_iterations": 2},
                  Dataset(X, np.zeros_like(y)), init_model=base)

    def test_early_stopping_without_valid_raises(self):
        from mmlspark_tpu.engine.booster import Dataset, train

        X, y = _toy_xy(100, 4, seed=1)
        import pytest
        with pytest.raises(ValueError, match="validation"):
            train({"objective": "binary", "num_iterations": 5,
                   "early_stopping_round": 2}, Dataset(X, y))

    def test_unknown_hist_backend_raises(self):
        from mmlspark_tpu.ops.histogram import build_histogram
        import jax.numpy as jnp
        import pytest

        with pytest.raises(ValueError, match="hist backend"):
            build_histogram(jnp.zeros((2, 4), jnp.int32), jnp.zeros((3, 4)),
                            jnp.ones(4, bool), 4, backend="one_hot")


class TestSaveOverwrite:
    def test_save_refuses_existing_nonempty_dir(self, tmp_path):
        from mmlspark_tpu.core.pipeline import Transformer
        from mmlspark_tpu.core.params import Param
        from mmlspark_tpu.core.registry import register_stage
        import pytest

        @register_stage
        class _T(Transformer):
            value = Param("value", "v", default=1.0, dtype=float)

            def _transform(self, df):
                return df

        target = tmp_path / "occupied"
        target.mkdir()
        (target / "precious.txt").write_text("do not delete")
        with pytest.raises(FileExistsError):
            _T().save(str(target))
        assert (target / "precious.txt").read_text() == "do not delete"
        _T().save(str(target), overwrite=True)
        assert not (target / "precious.txt").exists()


class TestAutoBackendResolution:
    """hist_backend/hist_chunk "auto" defaults resolve at train() time:
    Pallas + one-chunk on a TPU backend, scatter + DEFAULT_CHUNK elsewhere
    — WITHOUT this the user-facing estimators silently trained the slow
    path on TPU (measured 32.6s vs 7.7s at 65k rows)."""

    def test_cpu_resolves_to_scatter_default_chunk(self):
        import numpy as np

        from mmlspark_tpu.engine.booster import Dataset, TrainConfig, train
        from mmlspark_tpu.ops.histogram import DEFAULT_CHUNK

        cfg = TrainConfig.from_params(
            {"objective": "binary", "num_iterations": 2, "num_leaves": 4}
        )
        assert cfg.hist_backend == "auto" and cfg.hist_chunk == 0
        # end to end on the CPU backend: resolution must not error and the
        # model must train (the resolved values live only inside train())
        rng = np.random.default_rng(0)
        X = rng.normal(size=(300, 4))
        y = (X[:, 0] > 0).astype(np.float64)
        b = train({"objective": "binary", "num_iterations": 3,
                   "num_leaves": 4, "min_data_in_leaf": 5}, Dataset(X, y))
        assert np.isfinite(b.predict(X[:10])).all()
        # the stored config records the RESOLVED values (not "auto")
        assert b.config.hist_backend in ("scatter", "pallas")
        assert b.config.hist_chunk > 0
        if __import__("jax").default_backend() != "tpu":
            assert b.config.hist_backend == "scatter"
            assert b.config.hist_chunk == DEFAULT_CHUNK

    def test_explicit_values_respected(self):
        import numpy as np

        from mmlspark_tpu.engine.booster import Dataset, train

        rng = np.random.default_rng(1)
        X = rng.normal(size=(200, 3))
        y = (X[:, 0] > 0).astype(np.float64)
        b = train({"objective": "binary", "num_iterations": 2,
                   "num_leaves": 4, "hist_backend": "scatter",
                   "hist_chunk": 256, "min_data_in_leaf": 5},
                  Dataset(X, y))
        assert b.config.hist_backend == "scatter"
        assert b.config.hist_chunk == 256


class TestMultiMetric:
    """LightGBM comma-separated metric lists (r4): every metric recorded
    per eval set; early stopping = ANY (valid set, metric) pair stalls."""

    def _data(self, seed=21):
        rng = np.random.default_rng(seed)
        X = rng.normal(size=(1500, 6))
        y = (X[:, 0] - 0.6 * X[:, 1]
             + rng.normal(scale=0.5, size=1500) > 0).astype(np.float64)
        return X[:1100], y[:1100], X[1100:], y[1100:]

    def test_comma_separated_metrics_recorded(self):
        X, y, Xv, yv = self._data()
        b = train(dict(objective="binary", num_iterations=6, num_leaves=7,
                       min_data_in_leaf=5, metric="auc,binary_logloss"),
                  Dataset(X, y), valid_sets=[Dataset(Xv, yv)])
        res = b.evals_result["valid_0"]
        assert set(res) == {"auc", "binary_logloss"}
        assert len(res["auc"]) == len(res["binary_logloss"]) == 6
        # each curve matches a single-metric run exactly (same trees)
        b_auc = train(dict(objective="binary", num_iterations=6, num_leaves=7,
                           min_data_in_leaf=5, metric="auc"),
                      Dataset(X, y), valid_sets=[Dataset(Xv, yv)])
        np.testing.assert_allclose(
            res["auc"], b_auc.evals_result["valid_0"]["auc"])

    def test_metric_list_param(self):
        X, y, Xv, yv = self._data()
        b = train(dict(objective="binary", num_iterations=4, num_leaves=7,
                       min_data_in_leaf=5, metric=["binary_error", "auc"]),
                  Dataset(X, y), valid_sets=[Dataset(Xv, yv)])
        assert set(b.evals_result["valid_0"]) == {"binary_error", "auc"}

    def test_any_pair_early_stopping(self):
        # The second valid set is pure noise: its metric stalls early and
        # must trigger the stop even though valid_0 keeps improving —
        # LightGBM's "one metric of one validation data" rule.
        X, y, Xv, yv = self._data()
        rng = np.random.default_rng(99)
        Xn = rng.normal(size=(400, 6))
        yn = rng.integers(0, 2, 400).astype(np.float64)
        b = train(dict(objective="binary", num_iterations=60, num_leaves=15,
                       min_data_in_leaf=5, metric="binary_logloss",
                       early_stopping_round=5, learning_rate=0.3),
                  Dataset(X, y),
                  valid_sets=[Dataset(Xv, yv), Dataset(Xn, yn)],
                  valid_names=["good", "noise"])
        b_single = train(dict(objective="binary", num_iterations=60,
                              num_leaves=15, min_data_in_leaf=5,
                              metric="binary_logloss",
                              early_stopping_round=5, learning_rate=0.3),
                         Dataset(X, y), valid_sets=[Dataset(Xv, yv)],
                         valid_names=["good"])
        # the noise fold stalls almost immediately (random labels), so the
        # ANY-pair rule must stop STRICTLY earlier than watching only the
        # good fold would — equality here would mean the noise set was
        # ignored (the pre-r4 names[0]-only behavior)
        assert b.num_iterations < b_single.num_iterations, (
            b.num_iterations, b_single.num_iterations)
        assert b.num_iterations < 20

    def test_stall_reports_triggering_pair_best(self):
        # LightGBM's early_stopping callback reports the TRIGGERING pair's
        # best iteration/score; when the noise fold (valid index 1) stops
        # the run, best_iteration must be that fold's best — not the
        # still-improving good fold's latest (r4 advisor low #2).
        X, y, Xv, yv = self._data()
        rng = np.random.default_rng(99)
        Xn = rng.normal(size=(400, 6))
        yn = rng.integers(0, 2, 400).astype(np.float64)
        b = train(dict(objective="binary", num_iterations=60, num_leaves=15,
                       min_data_in_leaf=5, metric="binary_logloss",
                       early_stopping_round=5, learning_rate=0.3),
                  Dataset(X, y),
                  valid_sets=[Dataset(Xv, yv), Dataset(Xn, yn)],
                  valid_names=["good", "noise"])
        assert b.num_iterations < 60  # the noise fold stopped the run
        noise_curve = b.evals_result["noise"]["binary_logloss"]
        good_curve = b.evals_result["good"]["binary_logloss"]
        trig_best = int(np.argmin(noise_curve))
        # distinguishing scenario: the good fold's best is NOT the
        # triggering fold's best (else this test can't tell them apart)
        assert int(np.argmin(good_curve)) != trig_best
        assert b.best_iteration == trig_best, (
            b.best_iteration, trig_best, np.argmin(good_curve))

    def test_training_pseudo_valid_never_stops(self):
        # is_provide_training_metric joins the eval loop but must not
        # participate in the ANY-pair stopping rule
        X, y, Xv, yv = self._data()
        b = train(dict(objective="binary", num_iterations=12, num_leaves=7,
                       min_data_in_leaf=5, metric="auc",
                       early_stopping_round=3,
                       is_provide_training_metric=True),
                  Dataset(X, y), valid_sets=[Dataset(Xv, yv)])
        assert "training" in b.evals_result
        assert len(b.evals_result["training"]["auc"]) == b.num_iterations

    def test_first_metric_only(self):
        # the noise metric (auc on a noise fold... here: second metric)
        # must NOT stop training when first_metric_only is set
        X, y, Xv, yv = self._data()
        rng = np.random.default_rng(77)
        Xn = rng.normal(size=(400, 6))
        yn = rng.integers(0, 2, 400).astype(np.float64)
        base = dict(objective="binary", num_iterations=40, num_leaves=15,
                    min_data_in_leaf=5, metric="binary_logloss",
                    early_stopping_round=5, learning_rate=0.3)
        any_pair = train(dict(base), Dataset(X, y),
                         valid_sets=[Dataset(Xv, yv), Dataset(Xn, yn)])
        # first_metric_only still watches ALL valid sets (LightGBM), so to
        # isolate the metric dimension, make the NOISE the second METRIC
        fmo = train(dict(base, metric="binary_logloss,binary_error",
                         first_metric_only=True),
                    Dataset(X, y), valid_sets=[Dataset(Xv, yv)])
        both = train(dict(base, metric="binary_logloss,binary_error"),
                     Dataset(X, y), valid_sets=[Dataset(Xv, yv)])
        # with only the first metric watched, fmo runs at least as long as
        # the two-metric ANY-pair run (binary_error is a coarser/noisier
        # curve that tends to stall earlier)
        assert fmo.num_iterations >= both.num_iterations
        assert any_pair.num_iterations < 40  # noise fold stops the run

    def test_metric_none_disables_eval(self):
        # LightGBM metric="None": valid sets are ignored, nothing recorded
        X, y, Xv, yv = self._data()
        b = train(dict(objective="binary", num_iterations=4, num_leaves=7,
                       min_data_in_leaf=5, metric="None"),
                  Dataset(X, y), valid_sets=[Dataset(Xv, yv)])
        assert b.evals_result == {}
        assert b.num_iterations == 4
        with pytest.raises(ValueError, match="early stopping needs"):
            train(dict(objective="binary", num_iterations=4, num_leaves=7,
                       metric="None", early_stopping_round=2),
                  Dataset(X, y), valid_sets=[Dataset(Xv, yv)])


class TestLeafTableAccess:
    """The fit reads its (K, L) leaf table with no per-row gather."""

    @pytest.mark.parametrize("L", [7, 63, 255])
    @pytest.mark.parametrize("K", [1, 3])
    def test_leaf_delta_is_the_gather_to_the_bit(self, K, L):
        import jax.numpy as jnp

        from mmlspark_tpu.engine.booster import _leaf_delta
        from mmlspark_tpu.engine.tree import _empty_tree

        rng = np.random.default_rng(100 * K + L)
        leaf_value = rng.normal(size=(K, L)).astype(np.float32)
        leaf_value[:, ::5] *= 1e-30  # denormal-range and tiny values too
        leaf_ids = rng.integers(0, L, size=(K, 5000)).astype(np.int32)
        tree = _empty_tree(L - 1, L, 4)._replace(
            leaf_value=jnp.asarray(leaf_value)
        )
        got = np.asarray(_leaf_delta(tree, jnp.asarray(leaf_ids)))
        want = np.take_along_axis(leaf_value, leaf_ids, axis=1)
        assert got.dtype == np.float32
        np.testing.assert_array_equal(got.view(np.uint32), want.view(np.uint32))

    @staticmethod
    def _totals_case(n, L, seed):
        rng = np.random.default_rng(seed)
        vals = rng.normal(size=(3, n)).astype(np.float32)
        vals[2] = 1.0  # the count channel: in-bag rows ...
        vals[:, rng.random(n) < 0.2] = 0.0  # ... zero-weight rows are all 0
        leaf_ids = rng.integers(0, L + 3, size=n).astype(np.int32)  # ids >= L drop
        return vals, leaf_ids

    @pytest.mark.parametrize("rows", ["below", "equal", "ragged", "two_and_ragged"])
    def test_chunked_leaf_totals_match_the_scatter(self, rows):
        import jax
        import jax.numpy as jnp

        from mmlspark_tpu.engine import tree as tr

        c, L = tr._LEAF_TOTALS_CHUNK, 63
        n = {"below": 5000, "equal": c, "ragged": c + 777,
             "two_and_ragged": 2 * c + 12345}[rows]
        vals, leaf_ids = self._totals_case(n, L, seed=n % 1000)
        totals = jax.jit(tr._leaf_totals, static_argnums=(2, 3))
        got = np.asarray(totals(jnp.asarray(vals), jnp.asarray(leaf_ids), L, True))
        want = np.asarray(totals(jnp.asarray(vals), jnp.asarray(leaf_ids), L, False))
        keep = leaf_ids < L
        exact = np.stack([
            np.bincount(leaf_ids[keep], weights=v[keep].astype(np.float64),
                        minlength=L)
            for v in vals
        ])
        np.testing.assert_array_equal(got[2], want[2])  # counts: exact
        np.testing.assert_array_equal(got[2], exact[2])
        scale = np.abs(vals[:2]).sum(axis=1, keepdims=True) / L
        assert np.max(np.abs(got[:2] - want[:2]) / scale) < 1e-6
        assert np.max(np.abs(got[:2] - exact[:2]) / scale) < 1e-6

    @staticmethod
    def _row_ops(jaxpr, n):
        """Names of the gather / scatter-add equations, at any depth, one of
        whose operands has ``n`` rows."""
        import jax

        found = []
        for eqn in jaxpr.eqns:
            if eqn.primitive.name in ("gather", "scatter-add") and any(
                n in getattr(v.aval, "shape", ()) for v in eqn.invars
            ):
                found.append(eqn.primitive.name)
            for sub in jax.core.jaxprs_in_params(eqn.params):
                found += TestLeafTableAccess._row_ops(sub, n)
        return found

    @pytest.mark.parametrize("chunks", [1, 3])
    def test_tpu_resolved_tail_and_delta_index_no_table_per_row(self, chunks):
        import jax
        import jax.numpy as jnp

        from mmlspark_tpu.engine import tree as tr
        from mmlspark_tpu.engine.booster import _leaf_delta

        n, F, B, L = chunks * tr._LEAF_TOTALS_CHUNK, 4, 17, 63
        tail = jax.make_jaxpr(
            lambda v, i: tr._leaf_totals(v, i, L, onehot=True)
        )(jnp.zeros((3, n), jnp.float32), jnp.zeros(n, jnp.int32))
        assert self._row_ops(tail.jaxpr, n) == []
        # the control: the CPU's form of the same sums is the scatter-add
        scatter = jax.make_jaxpr(
            lambda v, i: tr._leaf_totals(v, i, L, onehot=False)
        )(jnp.zeros((3, n), jnp.float32), jnp.zeros(n, jnp.int32))
        assert set(self._row_ops(scatter.jaxpr, n)) == {"scatter-add"}
        delta = jax.make_jaxpr(_leaf_delta)(
            tr._empty_tree(L - 1, L, B)._replace(
                leaf_value=jnp.zeros((2, L), jnp.float32)
            ),
            jnp.zeros((2, n), jnp.int32),
        )
        assert self._row_ops(delta.jaxpr, n) == []
        # and the whole windowed grower, as the booster resolves it on a TPU
        def grower_ops(onehot):
            cfg = tr.GrowConfig(num_bins=B, num_leaves=L, split_batch=8,
                                hist_backend="pallas", onehot_stats=onehot)
            return self._row_ops(jax.make_jaxpr(
                lambda *a: tr.grow_tree_depthwise(cfg, *a)
            )(jnp.zeros((n, F), jnp.uint8), jnp.zeros(n, jnp.float32),
              jnp.zeros(n, jnp.float32), jnp.zeros(n, jnp.float32),
              jnp.ones(F, bool)).jaxpr, n)

        assert grower_ops(True) == []
        assert grower_ops(False) == ["scatter-add"]

    def test_backend_switch_trains_the_same_model(self):
        """``onehot_stats`` is a backend switch, not a semantics change:
        the windowed grower's leaf values and counts under the contraction
        are the scatter's at this (small, fixed summation order) scale."""
        import jax.numpy as jnp

        from mmlspark_tpu.engine import tree as tr

        rng = np.random.default_rng(5)
        n, F, B = 1500, 6, 33
        args = (jnp.asarray(rng.integers(0, B - 1, size=(n, F))),
                jnp.asarray(rng.normal(size=n).astype(np.float32)),
                jnp.ones(n, jnp.float32),
                jnp.asarray((rng.random(n) < 0.8).astype(np.float32)),
                jnp.ones(F, bool))
        common = dict(num_bins=B, num_leaves=15, min_data_in_leaf=5,
                      split_batch=8)
        t_oh, ids_oh = tr.grow_tree_depthwise(
            tr.GrowConfig(**common, onehot_stats=True), *args)
        t_sc, ids_sc = tr.grow_tree_depthwise(
            tr.GrowConfig(**common, onehot_stats=False), *args)
        np.testing.assert_array_equal(np.asarray(ids_oh), np.asarray(ids_sc))
        np.testing.assert_array_equal(
            np.asarray(t_oh.leaf_count), np.asarray(t_sc.leaf_count))
        np.testing.assert_allclose(
            np.asarray(t_oh.leaf_value), np.asarray(t_sc.leaf_value),
            rtol=1e-5, atol=1e-7)

    def test_sharded_contraction_sums_across_shards(self):
        """Under ``reduce_scatter`` on the CPU's fake devices each shard
        contracts its own rows and ``psum_axes`` adds the partials: the
        tree's counts are the whole set's."""
        import jax
        import jax.numpy as jnp
        from jax.sharding import Mesh, PartitionSpec as P

        from mmlspark_tpu.engine import tree as tr
        from mmlspark_tpu.parallel.mesh import DATA_AXIS

        D = 4
        rng = np.random.default_rng(9)
        n, F, B, L = 4096, 8, 33, 15
        bins = rng.integers(0, B - 1, size=(n, F)).astype(np.uint8)
        grad = rng.normal(size=n).astype(np.float32)
        hess = np.ones(n, np.float32)
        bag = (rng.random(n) < 0.9).astype(np.float32)
        common = dict(num_bins=B, num_leaves=L, min_data_in_leaf=5,
                      split_batch=1, onehot_stats=True)
        mesh = Mesh(np.asarray(jax.devices()[:D]), (DATA_AXIS,))
        spec = tr.Tree(*([P()] * len(tr.Tree._fields)))
        cfg = tr.GrowConfig(**common, axis_name=DATA_AXIS,
                            hist_merge="reduce_scatter")
        sharded = jax.jit(jax.shard_map(
            lambda *a: tr.grow_tree_depthwise(cfg, *a), mesh=mesh,
            in_specs=(P(DATA_AXIS, None), P(DATA_AXIS), P(DATA_AXIS),
                      P(DATA_AXIS), P(None)),
            out_specs=(spec, P(DATA_AXIS)), check_vma=False,
        ))
        args = (jnp.asarray(bins), jnp.asarray(grad), jnp.asarray(hess),
                jnp.asarray(bag), jnp.ones(F, bool))
        t_sh, ids_sh = sharded(*args)
        t_1, ids_1 = tr.grow_tree_depthwise(tr.GrowConfig(**common), *args)
        assert float(np.asarray(t_sh.leaf_count).sum()) == float(bag.sum())
        np.testing.assert_array_equal(np.asarray(ids_sh), np.asarray(ids_1))
        np.testing.assert_array_equal(
            np.asarray(t_sh.leaf_count), np.asarray(t_1.leaf_count))
        np.testing.assert_allclose(
            np.asarray(t_sh.leaf_value), np.asarray(t_1.leaf_value),
            rtol=1e-5, atol=1e-7)


class TestScanDispatchIters:
    def test_chunked_dispatch_is_bitwise_identical(self):
        """scan_dispatch_iters caps iterations per device dispatch; the
        scan state carries across chunks, so chunking is pure dispatch
        granularity — bitwise-identical models (the workaround for
        remote links that kill very long dispatches, BASELINE.md r5)."""
        rng = np.random.default_rng(8)
        X = rng.normal(size=(1200, 6))
        y = (X[:, 0] - 0.4 * X[:, 1] > 0).astype(np.float64)
        base = dict(objective="binary", num_iterations=12, num_leaves=15,
                    min_data_in_leaf=5, max_bin=63)
        p_full = train(base, Dataset(X, y)).predict(X)
        p_chunk = train(dict(base, scan_dispatch_iters=5),
                        Dataset(X, y)).predict(X)
        np.testing.assert_array_equal(p_full, p_chunk)
        # composes with eval/early stopping
        b = train(dict(base, scan_dispatch_iters=2, metric="auc",
                       early_stopping_round=3),
                  Dataset(X[:900], y[:900]),
                  valid_sets=[Dataset(X[900:], y[900:])])
        b2 = train(dict(base, metric="auc", early_stopping_round=3),
                   Dataset(X[:900], y[:900]),
                   valid_sets=[Dataset(X[900:], y[900:])])
        assert b.best_iteration == b2.best_iteration
