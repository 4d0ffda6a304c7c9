"""Byte-tier bin packing (17-256 bins) and the fused bin+occupancy kernel.

ISSUE 11 tentpoles (b) and (c): past the nibble tier the binned cache and
the transposed histogram working set ride 1-byte indices through the
default max_bin=255 (ops/binpack.py byte tier), and the streamed ingest
fuses binning with the occupancy tally in one kernel pass
(ops/pallas_binhist.py).  Everything here is a bitwise claim: the byte
tier and the fused kernel must change LAYOUT, never results — including
grower splits over the 8-device mesh under both hist merge strategies.
"""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from mmlspark_tpu.engine.booster import Dataset, train
from mmlspark_tpu.ops.binning import BinningAuthority
from mmlspark_tpu.ops.binpack import (
    BYTE_MAX_BINS,
    PACK_MAX_BINS,
    can_pack_bytes,
    hist_transpose,
    pack_bytes,
    unpack_bytes,
)
from mmlspark_tpu.ops.device_binning import bin_rows_device
from mmlspark_tpu.ops.histogram import build_histogram
from mmlspark_tpu.ops.pallas_binhist import bin_occ_rows


class TestByteTier:
    def test_tier_boundaries(self):
        assert PACK_MAX_BINS == 16 and BYTE_MAX_BINS == 256
        assert can_pack_bytes(PACK_MAX_BINS + 1)  # where nibbles end
        assert can_pack_bytes(BYTE_MAX_BINS)
        assert not can_pack_bytes(0)
        assert not can_pack_bytes(BYTE_MAX_BINS + 1)

    def test_roundtrip_17_through_256_bins(self):
        rng = np.random.default_rng(0)
        for num_bins in (17, 100, 255, 256):
            bins = rng.integers(0, num_bins, size=(101, 7)).astype(np.int32)
            packed = pack_bytes(bins)
            assert packed.dtype == np.uint8
            assert packed.nbytes == bins.size  # 1 byte per index, 4x cut
            np.testing.assert_array_equal(unpack_bytes(packed), bins)

    def test_pack_bytes_range_checked_on_host(self):
        with pytest.raises(ValueError):
            pack_bytes(np.array([[256]], np.int64))
        with pytest.raises(ValueError):
            pack_bytes(np.array([[-1]], np.int64))

    def test_pack_bytes_traced_path(self):
        bins = jnp.arange(12, dtype=jnp.int32).reshape(3, 4)
        out = jax.jit(pack_bytes)(bins)
        assert out.dtype == jnp.uint8
        np.testing.assert_array_equal(np.asarray(out), np.asarray(bins))

    def test_hist_transpose_picks_tier_by_num_bins(self):
        bins = jnp.zeros((5, 3), jnp.int32)
        byte = hist_transpose(bins, BYTE_MAX_BINS)
        wide = hist_transpose(bins, BYTE_MAX_BINS + 1)
        assert byte.dtype == jnp.uint8 and byte.shape == (3, 5)
        assert wide.dtype == jnp.int32 and wide.shape == (3, 5)

    @pytest.mark.parametrize("backend", ["scatter", "pallas"])
    def test_hist_bitwise_uint8_vs_int32_working_set(self, backend):
        rng = np.random.default_rng(1)
        n, F, B = 257, 5, 255
        bins = rng.integers(0, B, size=(n, F)).astype(np.int64)
        vals = jnp.asarray(
            rng.normal(size=(3, n)).astype(np.float32))
        mask = jnp.asarray(rng.random(n) < 0.8)
        byte_t = hist_transpose(jnp.asarray(bins), B)
        int_t = jnp.asarray(bins, jnp.int32).T
        assert byte_t.dtype == jnp.uint8
        h8 = build_histogram(byte_t, vals, mask, B, backend=backend)
        h32 = build_histogram(int_t, vals, mask, B, backend=backend)
        np.testing.assert_array_equal(np.asarray(h8), np.asarray(h32))


def _mixed_frame(n=333, F=7, seed=2):
    """Rows exercising every binning edge: NaNs, categoricals with
    non-integral and unseen values, constant and heavy-tail columns."""
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(n, F)).astype(np.float64)
    X[:, 1] = rng.integers(0, 9, size=n)  # categorical
    X[::7, 1] = 40.0  # category unseen rarely enough to stay in the map
    X[:, 4] = rng.integers(0, 5, size=n)  # categorical
    X[3::11, 4] += 0.25  # non-integral cat values truncate toward zero
    X[::13, 0] = np.nan
    X[:, 2] = 1.5  # constant column
    X[:, 3] = np.exp(X[:, 3] * 3)  # heavy tail
    return X


class TestFusedBinOcc:
    """Interpret-mode parity for ops/pallas_binhist vs the shared
    device binner (the contract the kernel docstring points here for)."""

    @pytest.mark.parametrize("bm", [64, 1024])
    def test_fused_bitwise_matches_unfused_plus_tally(self, bm):
        X = _mixed_frame()
        n, F = X.shape
        authority = BinningAuthority.fit(
            X, max_bin=63, categorical_features=[1, 4])
        binner = authority.device_binner()
        B = int(authority.num_bins)
        rows = jnp.asarray(X, jnp.float32)

        ref = np.asarray(bin_rows_device(
            binner.arrays, rows,
            missing_bin=binner.missing_bin, n_bounds=binner.n_bounds))
        occ_ref = np.zeros((F, B), np.int32)
        np.add.at(occ_ref, (np.arange(F)[None, :], ref), 1)

        bins_u8, occ = bin_occ_rows(
            binner.arrays, rows, missing_bin=binner.missing_bin,
            n_bounds=binner.n_bounds, num_bins=B, bm=bm)
        assert bins_u8.dtype == jnp.uint8 and bins_u8.shape == (n, F)
        np.testing.assert_array_equal(np.asarray(bins_u8), ref)
        np.testing.assert_array_equal(np.asarray(occ), occ_ref)

    def test_fused_at_byte_tier_ceiling(self):
        # max_bin=255 -> num_bins=256 incl. the missing bin: the largest
        # bin id must survive the uint8 store
        rng = np.random.default_rng(3)
        X = rng.normal(size=(300, 3)).astype(np.float64)
        X[::5, 0] = np.nan
        authority = BinningAuthority.fit(X, max_bin=255)
        binner = authority.device_binner()
        B = int(authority.num_bins)
        rows = jnp.asarray(X, jnp.float32)
        ref = np.asarray(bin_rows_device(
            binner.arrays, rows,
            missing_bin=binner.missing_bin, n_bounds=binner.n_bounds))
        bins_u8, occ = bin_occ_rows(
            binner.arrays, rows, missing_bin=binner.missing_bin,
            n_bounds=binner.n_bounds, num_bins=B)
        np.testing.assert_array_equal(np.asarray(bins_u8), ref)
        assert int(np.asarray(occ).sum()) == rows.shape[0] * rows.shape[1]


class TestMeshSplitParity:
    """The byte-tier hist working set feeds the grower on every backend;
    forcing the pre-ISSUE-11 int32 layout must reproduce every split
    bitwise — over the 8-device mesh, under both hist merge strategies."""

    @pytest.mark.parametrize("merge", ["allreduce", "reduce_scatter"])
    def test_splits_bitwise_uint8_vs_int32(self, merge, monkeypatch):
        rng = np.random.default_rng(4)
        n, F = 1024, 8
        X = rng.normal(size=(n, F))
        y = (X[:, 0] + 0.5 * X[:, 1] * X[:, 2]
             + rng.normal(scale=0.3, size=n) > 0).astype(np.float64)
        params = dict(
            objective="binary", num_iterations=4, num_leaves=15,
            tree_learner="data", hist_merge=merge, min_data_in_leaf=4,
        )
        byte_model = train(dict(params), Dataset(X, y))
        ref = byte_model.save_model_string()

        import mmlspark_tpu.engine.tree as tree_mod

        monkeypatch.setattr(
            tree_mod, "hist_transpose",
            lambda bins, num_bins: bins.astype(jnp.int32).T,
        )
        int32_model = train(dict(params), Dataset(X, y))
        assert int32_model.save_model_string() == ref
        np.testing.assert_array_equal(
            byte_model.predict(X), int32_model.predict(X))
