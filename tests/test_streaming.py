"""Out-of-core streaming ingestion (ISSUE 10): shard loaders, mergeable
quantile sketches, the unified binning authority, nibble packing, and
end-to-end streamed training.

Gates, from strongest to weakest:

1. exact-mode sketches reproduce the host ``BinMapper`` edges BIT-FOR-BIT
   (shared ``numeric_uppers_from_distinct``), so streamed training is
   bitwise-identical to in-memory training (model string equality);
2. approximate (spilled) sketches keep their declared ``rank_epsilon``
   contract — actual CDF error never exceeds the bound — and e2e AUC
   stays within 1e-3 of the host-binned run;
3. peak host residency during ingest stays O(chunk), not O(dataset).
"""

import gc
import os
import pickle
import tracemalloc

import numpy as np
import pytest

from mmlspark_tpu import obs
from mmlspark_tpu.data import (
    DatasetSketch,
    NpySource,
    RowGroupSource,
    chunk_stream,
    merge_sketch_states,
    stream_fit_binning,
    stream_ingest,
    train_streaming,
    write_row_group_shards,
)
from mmlspark_tpu.engine.booster import Dataset, TrainConfig, train
from mmlspark_tpu.ops.binning import BinningAuthority


def _make_xy(n=4000, F=8, cat_col=3, nan_frac=0.03, seed=0):
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(n, F)).astype(np.float32)
    X[:, cat_col] = rng.integers(0, 12, n)
    if nan_frac:
        X[rng.random((n, F)) < nan_frac] = np.nan
        X[:, cat_col] = np.where(
            np.isnan(X[:, cat_col]), np.nan, X[:, cat_col]
        )
    y = (np.nan_to_num(X[:, 0]) + rng.normal(size=n) * 0.5 > 0)
    return X, y.astype(np.float64)


def _auc(y, s):
    order = np.argsort(s, kind="stable")
    ranks = np.empty(len(s), np.float64)
    ranks[order] = np.arange(1, len(s) + 1)
    # midranks for ties
    for v in np.unique(s):
        m = s == v
        ranks[m] = ranks[m].mean()
    pos = y > 0
    n1, n0 = pos.sum(), (~pos).sum()
    return (ranks[pos].sum() - n1 * (n1 + 1) / 2) / (n1 * n0)


# ------------------------------------------------------------- loaders


class TestLoaders:
    def test_row_group_chunks_cover_rows_in_order(self, tmp_path):
        X, y = _make_xy(n=1000, F=4)
        src = RowGroupSource(write_row_group_shards(
            str(tmp_path / "rg"), X, y, rows_per_group=170))
        chunks = list(chunk_stream(src, 256))
        assert len(chunks) == 4  # 1000/256 → chunk boundaries ≠ group ones
        assert [c.start for c in chunks] == [0, 256, 512, 768]
        got = np.concatenate([c.X for c in chunks])
        assert np.array_equal(got, X, equal_nan=True)
        gy = np.concatenate([c.y for c in chunks])
        np.testing.assert_array_equal(gy, y.astype(np.float32))

    def test_npy_source_roundtrip_and_label_mismatch(self, tmp_path):
        X, y = _make_xy(n=100, F=3, cat_col=1, nan_frac=0.0)
        np.save(tmp_path / "x.npy", X)
        np.save(tmp_path / "y.npy", y)
        src = NpySource([str(tmp_path / "x.npy")],
                        label_paths=[str(tmp_path / "y.npy")])
        got = np.concatenate([c.X for c in chunk_stream(src, 33)])
        assert np.array_equal(got, X, equal_nan=True)
        np.save(tmp_path / "y.npy", y[:50])
        with pytest.raises(ValueError, match="label shard"):
            list(chunk_stream(src, 33))


# ------------------------------------------------------------- sketches


class TestSketch:
    def test_exact_mode_edges_bitwise_equal_host_fit(self):
        X, _ = _make_xy(n=4000, F=6, cat_col=2)
        host = BinningAuthority.fit(
            X.astype(np.float64), max_bin=63, categorical_features=(2,),
        ).mapper
        sk = DatasetSketch(6, max_bin=63, categorical_features=(2,))
        for start in range(0, len(X), 700):  # chunked, uneven tail
            sk.update(X[start:start + 700])
        assert sk.is_exact and sk.rank_epsilon == 0.0
        bm = sk.to_bin_mapper()
        for f in range(6):
            np.testing.assert_array_equal(
                bm.upper_bounds[f], host.upper_bounds[f])
        np.testing.assert_array_equal(bm.cat_maps[2], host.cat_maps[2])

    def test_state_roundtrip_and_merge_match_single_pass(self):
        X, _ = _make_xy(n=3000, F=5, cat_col=4, seed=7)
        full = DatasetSketch(5, max_bin=31, categorical_features=(4,))
        full.update(X)
        a = DatasetSketch(5, max_bin=31, categorical_features=(4,))
        b = DatasetSketch(5, max_bin=31, categorical_features=(4,))
        a.update(X[:1300])
        b.update(X[1300:])
        merged = merge_sketch_states([a.to_state(), b.to_state()])
        assert merged.n_rows == 3000
        bm_m, bm_f = merged.to_bin_mapper(), full.to_bin_mapper()
        for f in range(5):
            np.testing.assert_array_equal(
                bm_m.upper_bounds[f], bm_f.upper_bounds[f])
        np.testing.assert_array_equal(bm_m.cat_maps[4], bm_f.cat_maps[4])

    def test_spilled_sketch_cdf_error_within_declared_epsilon(self):
        rng = np.random.default_rng(11)
        col = rng.normal(size=50_000).astype(np.float32)
        sk = DatasetSketch(1, max_bin=255, exact_budget=512,
                           compactor_cap=256)
        for start in range(0, len(col), 4096):
            sk.update(col[start:start + 4096, None])
        assert not sk.is_exact
        eps = sk.rank_epsilon
        assert 0.0 < eps < 0.1
        # actual CDF deviation of the sketch's weighted support vs truth
        distinct, weights = sk.features[0].weighted_distinct()
        approx_cdf = np.cumsum(weights) / weights.sum()
        true_cdf = np.searchsorted(np.sort(col), distinct, side="right") \
            / float(len(col))
        assert np.max(np.abs(approx_cdf - true_cdf)) <= eps

    def test_merge_rejects_mismatched_configs(self):
        a = DatasetSketch(3, max_bin=63)
        b = DatasetSketch(3, max_bin=255)
        with pytest.raises(ValueError):
            a.merge(b)


# ------------------------------------------------------- nibble packing


class TestNibblePacking:
    def test_roundtrip_even_and_odd_rows(self):
        from mmlspark_tpu.ops.binpack import pack_rows, packed_rows, \
            unpack_rows

        rng = np.random.default_rng(3)
        for n in (10, 11, 1):
            b = rng.integers(0, 16, size=(n, 5)).astype(np.uint8)
            p = pack_rows(b)
            assert p.shape == (packed_rows(n), 5)
            np.testing.assert_array_equal(unpack_rows(p, n), b)

    def test_roundtrip_on_device(self):
        import jax.numpy as jnp

        from mmlspark_tpu.ops.binpack import pack_rows, unpack_rows

        rng = np.random.default_rng(4)
        b = rng.integers(0, 16, size=(9, 3)).astype(np.uint8)
        out = np.asarray(unpack_rows(pack_rows(jnp.asarray(b)), 9))
        np.testing.assert_array_equal(out, b)


# ------------------------------------------------- streamed training


class TestStreamedTraining:
    PARAMS = dict(objective="binary", num_iterations=8, num_leaves=7,
                  max_bin=63, categorical_feature=[3], seed=1)

    def test_e2e_bitwise_identical_to_host_binned(self, tmp_path):
        X, y = _make_xy()
        src = RowGroupSource(write_row_group_shards(
            str(tmp_path / "rg"), X, y, rows_per_group=900))
        bst, ds = train_streaming(
            self.PARAMS, src, chunk_rows=1024, exact_budget=32768,
            return_dataset=True)
        host = train(self.PARAMS, Dataset(X.astype(np.float64), y))
        assert bst.save_model_string() == host.save_model_string()
        np.testing.assert_array_equal(
            bst.predict(X.astype(np.float64)),
            host.predict(X.astype(np.float64)))
        assert ds.X is None  # raw features never fully host-resident

    def test_e2e_nibble_packed_bitwise_and_half_cache(self, tmp_path):
        X, y = _make_xy(n=3000)
        params = dict(self.PARAMS, max_bin=15)
        src = RowGroupSource(write_row_group_shards(
            str(tmp_path / "rg"), X, y, rows_per_group=800))
        b_pk, ds_pk = train_streaming(
            params, src, chunk_rows=512, exact_budget=32768,
            return_dataset=True)
        b_un, ds_un = train_streaming(
            params, src, chunk_rows=512, exact_budget=32768,
            pack="never", return_dataset=True)
        assert ds_pk.packed and not ds_un.packed
        assert ds_pk.binned_cache_nbytes * 2 == ds_un.binned_cache_nbytes
        host = train(params, Dataset(X.astype(np.float64), y))
        assert b_pk.save_model_string() == b_un.save_model_string()
        assert b_pk.save_model_string() == host.save_model_string()

    def test_e2e_forced_sketch_mode_auc_within_1e3(self, tmp_path):
        X, y = _make_xy(n=20_000, F=6, cat_col=5, seed=3)
        params = dict(self.PARAMS, categorical_feature=[5],
                      num_iterations=10)
        src = RowGroupSource(write_row_group_shards(
            str(tmp_path / "rg"), X, y, rows_per_group=4096))
        # tiny budget/cap force every numeric feature to spill
        bst = train_streaming(params, src, chunk_rows=4096,
                              exact_budget=256, compactor_cap=128)
        host = train(params, Dataset(X.astype(np.float64), y))
        Xh = X.astype(np.float64)
        auc_s = _auc(y, bst.predict(Xh))
        auc_h = _auc(y, host.predict(Xh))
        assert auc_h > 0.7  # the task is learnable at all
        assert abs(auc_s - auc_h) <= 1e-3

    def test_fitted_mapper_rejects_different_binning_config(self, tmp_path):
        X, y = _make_xy(n=600, F=4, cat_col=1)
        src = RowGroupSource(write_row_group_shards(
            str(tmp_path / "rg"), X, y, rows_per_group=300))
        authority, _ = stream_fit_binning(
            src, max_bin=63, categorical_features=(1,),
            chunk_rows=256, exact_budget=32768)
        ds = stream_ingest(src, authority, chunk_rows=256)
        with pytest.raises(ValueError, match="max_bin"):
            ds.fitted_mapper(TrainConfig.from_params(
                {"max_bin": 255, "categorical_feature": [1]}))

    def test_streamed_dataset_refuses_pickling(self, tmp_path):
        X, y = _make_xy(n=400, F=3, cat_col=1)
        src = RowGroupSource(write_row_group_shards(
            str(tmp_path / "rg"), X, y, rows_per_group=200))
        authority, _ = stream_fit_binning(
            src, max_bin=15, chunk_rows=128, exact_budget=32768)
        ds = stream_ingest(src, authority, chunk_rows=128)
        with pytest.raises(TypeError, match="device-resident"):
            pickle.dumps(ds)


# -------------------------------------------- memory + observability


class TestMemoryAndObs:
    def test_peak_host_memory_o_chunk_not_o_dataset(self, tmp_path):
        F, chunk_rows = 16, 8192

        def peak_for(n, name):
            rng = np.random.default_rng(9)
            X = rng.normal(size=(n, F)).astype(np.float32)
            y = (X[:, 0] > 0).astype(np.float64)
            src = RowGroupSource(write_row_group_shards(
                str(tmp_path / name), X, y, rows_per_group=16384))
            assert n // chunk_rows > 1  # a real multi-chunk stream
            del X, y
            gc.collect()
            tracemalloc.start()
            authority, sketch = stream_fit_binning(
                src, max_bin=63, chunk_rows=chunk_rows,
                exact_budget=2048, compactor_cap=1024)
            ds = stream_ingest(src, authority, chunk_rows=chunk_rows)
            _, peak = tracemalloc.get_traced_memory()
            tracemalloc.stop()
            assert ds.num_rows == n and not sketch.is_exact
            del ds, authority, sketch
            gc.collect()
            return peak

        # warm pass first: lazy imports + jit tracing allocate MBs once,
        # and must not be billed to the pipeline under measurement
        peak_for(32_768, "warm")
        p_small = peak_for(32_768, "small")
        p_big = peak_for(262_144, "big")
        big_x_bytes = 262_144 * F * 4  # 16 MiB of f32 features
        delta_x = (262_144 - 32_768) * F * 4
        # growing the dataset 8× may only grow host peak by the O(8
        # bytes/row) label vector + sketch log-depth — NOT by the O(n·F·4)
        # a host materialization would add (the in-memory path holds the
        # f32 frame plus its f64 cast: ≥ 3× big_x_bytes)
        assert p_big - p_small < delta_x // 3, (p_small, p_big, delta_x)
        assert p_big < big_x_bytes * 3 // 4, (p_big, big_x_bytes)

    def test_ingest_counters_spans_and_report(self, tmp_path):
        from tools.obs import build_report

        X, y = _make_xy(n=2000, F=4, cat_col=2, seed=5)
        src = RowGroupSource(write_row_group_shards(
            str(tmp_path / "rg"), X, y, rows_per_group=700))
        params = dict(objective="binary", num_iterations=3, num_leaves=4,
                      max_bin=15, categorical_feature=[2], seed=0)
        export = str(tmp_path / "obs.jsonl")
        obs.enable(export)
        obs.reset()  # drop counters leaked by earlier suite tests
        try:
            train_streaming(params, src, chunk_rows=512,
                            exact_budget=32768)
            snap = obs.snapshot()
        finally:
            obs.disable()
            obs.reset()
        counters = snap["counters"]
        # two streaming passes (sketch + ingest) × ⌈2000/512⌉ chunks
        assert counters["ingest.chunks"] == 8
        assert counters["ingest.bytes"] == 2 * X.nbytes
        assert counters["ingest.buffer_stall_ns"] > 0
        assert snap["gauges"]["ingest.sketch_rank_epsilon"] == 0.0
        spans = snap["spans"]
        for name in ("train.binning", "train.binning.sketch",
                     "train.binning.merge", "train.binning.device_bin"):
            assert spans[name]["count"] == 1, name
        # the offline report surfaces the same breakdown from the export
        rep = build_report(export)
        for name in ("train.binning", "train.binning.sketch",
                     "train.binning.merge", "train.binning.device_bin"):
            assert name in rep["spans"], name


# -------------------------------------------------- pipelined ingest


class TestPipelinedIngest:
    """The 3-stage decode → upload → device-step pipeline (ISSUE 20):
    overlap must be REAL (≥2 chunks in flight), tunable
    (``MMLSPARK_TPU_INGEST_DEPTH``), bitwise-invisible to the model,
    and drain cleanly on mid-stream errors."""

    def _src(self, tmp_path, n=6000, F=6, name="rg", seed=11):
        X, y = _make_xy(n=n, F=F, cat_col=3, seed=seed)
        return RowGroupSource(write_row_group_shards(
            str(tmp_path / name), X, y, rows_per_group=1500)), X, y

    def test_pipeline_keeps_chunks_in_flight(self, tmp_path):
        src, _, _ = self._src(tmp_path)
        authority, _ = stream_fit_binning(
            src, max_bin=63, chunk_rows=512, exact_budget=32768)
        ds = stream_ingest(src, authority, chunk_rows=512)
        st = ds.ingest_stats
        # the steady-ingest serialization fix: ≥2 chunks concurrently in
        # the pipeline (queued, uploading, or awaiting collection), not
        # the old upload→block→step lockstep
        assert st["max_in_flight"] >= 2, st
        assert st["depth"] == 2 and st["overlap"] is True
        assert 0.0 <= st["overlap_ratio"] <= 1.0
        for k in ("decode_s", "upload_s", "step_s", "wall_s"):
            assert st[k] >= 0.0, (k, st)

    def test_ingest_depth_env_knob(self, tmp_path, monkeypatch):
        from mmlspark_tpu.data.loader import default_ingest_depth

        monkeypatch.setenv("MMLSPARK_TPU_INGEST_DEPTH", "3")
        assert default_ingest_depth() == 3
        monkeypatch.setenv("MMLSPARK_TPU_INGEST_DEPTH", "0")
        assert default_ingest_depth() == 1  # floor: a real pipeline
        monkeypatch.setenv("MMLSPARK_TPU_INGEST_DEPTH", "banana")
        assert default_ingest_depth() == 2  # unparseable -> default
        monkeypatch.delenv("MMLSPARK_TPU_INGEST_DEPTH")
        assert default_ingest_depth() == 2

        src, _, _ = self._src(tmp_path)
        authority, _ = stream_fit_binning(
            src, max_bin=63, chunk_rows=512, exact_budget=32768)
        monkeypatch.setenv("MMLSPARK_TPU_INGEST_DEPTH", "4")
        ds = stream_ingest(src, authority, chunk_rows=512)
        assert ds.ingest_stats["depth"] == 4
        ds1 = stream_ingest(src, authority, chunk_rows=512, depth=1)
        assert ds1.ingest_stats["depth"] == 1  # explicit beats env
        assert np.array_equal(
            np.asarray(ds._binned_dev), np.asarray(ds1._binned_dev))

    def test_overlap_vs_blocking_bitwise_parity(self, tmp_path):
        src, _, _ = self._src(tmp_path)
        authority, _ = stream_fit_binning(
            src, max_bin=63, chunk_rows=700, exact_budget=32768)
        a = stream_ingest(src, authority, chunk_rows=700, overlap=True)
        b = stream_ingest(src, authority, chunk_rows=700, overlap=False)
        assert a.ingest_stats["overlap"] and not b.ingest_stats["overlap"]
        assert np.array_equal(
            np.asarray(a._binned_dev), np.asarray(b._binned_dev))
        assert np.array_equal(a._occupancy, b._occupancy)
        assert np.array_equal(a._sample, b._sample)
        assert np.array_equal(a.label, b.label)

    def test_overlap_parity_packed_8dev_mesh(self, tmp_path):
        # nibble-packed uint8 cache (max_bin=15) trained over the full
        # 8-virtual-device mesh: the pipeline rotation must stay
        # invisible under donation + packing + shard_map
        import jax

        from mmlspark_tpu.parallel.mesh import default_mesh

        if len(jax.devices()) < 8:
            pytest.skip("needs the 8-virtual-device session")
        src, _, _ = self._src(tmp_path, n=4096, F=8, name="rg8", seed=3)
        params = dict(objective="binary", num_iterations=4, num_leaves=7,
                      max_bin=15, categorical_feature=[3], seed=1)
        mesh = default_mesh()
        bst_a, ds_a = train_streaming(
            params, src, chunk_rows=512, exact_budget=32768, mesh=mesh,
            overlap=True, return_dataset=True)
        bst_b, ds_b = train_streaming(
            params, src, chunk_rows=512, exact_budget=32768, mesh=mesh,
            overlap=False, return_dataset=True)
        assert ds_a.packed and ds_b.packed
        assert bst_a.save_model_string() == bst_b.save_model_string()

    def test_mid_stream_error_propagates_and_drains(self, tmp_path):
        # a shard source that dies mid-stream: the error must surface to
        # the caller (not deadlock the stages) and both worker threads
        # must be reaped
        import threading

        src, X, y = self._src(tmp_path, name="rgerr")

        class DyingSource:
            num_rows = src.num_rows
            num_features = src.num_features

            def iter_shards(self):
                it = src.iter_shards()
                yield next(it)
                yield next(it)
                raise OSError("shard storage vanished mid-stream")

        authority, _ = stream_fit_binning(
            src, max_bin=63, chunk_rows=512, exact_budget=32768)
        before = {t.ident for t in threading.enumerate()}
        with pytest.raises(OSError, match="vanished"):
            stream_ingest(DyingSource(), authority, chunk_rows=512)
        deadline = 50
        while deadline:
            alive = [t for t in threading.enumerate()
                     if t.ident not in before and t.is_alive()]
            if not alive:
                break
            import time
            time.sleep(0.1)
            deadline -= 1
        assert deadline, f"pipeline threads leaked: {alive}"

    def test_stacked_prefetcher_close_order_no_deadlock(self):
        # the shutdown contract: closing DOWNSTREAM first must never
        # deadlock even with full queues on both stages
        from mmlspark_tpu.data.loader import ChunkPrefetcher

        def slow_items():
            for i in range(100):
                yield i

        inner = ChunkPrefetcher(slow_items(), depth=2, count_chunks=False,
                                feed_steps=False, name="inner")
        outer = ChunkPrefetcher(iter(inner), depth=2, count_chunks=False,
                                feed_steps=False, name="outer")
        it = iter(outer)
        assert next(it) == 0  # both stages running, queues filling
        outer.close()
        inner.close()
        outer._thread.join(timeout=5)
        inner._thread.join(timeout=5)
        assert not outer._thread.is_alive()
        assert not inner._thread.is_alive()


# ------------------------------------------------------------ mesh leg


class TestMeshStreaming:
    @pytest.mark.parametrize("hist_merge", ["allreduce", "reduce_scatter"])
    def test_mesh_streamed_matches_mesh_host_binned(self, tmp_path,
                                                    hist_merge):
        import jax

        from mmlspark_tpu.parallel.mesh import default_mesh

        if len(jax.devices()) < 2:
            pytest.skip("needs a multi-device mesh")
        X, y = _make_xy(n=2048, F=8, cat_col=3, seed=2)
        params = dict(objective="binary", num_iterations=5, num_leaves=7,
                      max_bin=63, categorical_feature=[3], seed=1,
                      hist_merge=hist_merge)
        src = RowGroupSource(write_row_group_shards(
            str(tmp_path / "rg"), X, y, rows_per_group=600))
        mesh = default_mesh()
        bst = train_streaming(params, src, chunk_rows=512,
                              exact_budget=32768, mesh=mesh)
        host = train(params, Dataset(X.astype(np.float64), y), mesh=mesh)
        assert bst.save_model_string() == host.save_model_string()
