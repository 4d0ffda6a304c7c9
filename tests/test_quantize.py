"""Quantized training (ISSUE 9): int16 gradient buckets, int32
histogram accumulation, integer-wire merge, f32 winner refinement.

Layers:
1. wire-plan unit tests — shift sizing and the overflow guard,
2. quantization primitives — SR exactness, determinism, bounds,
3. resolve_auto_config — every hist_merge × hist_quantize
   combination,
4. end-to-end training — AUC parity vs f32, bitwise run-to-run
   determinism, categoricals, adversarial gradient magnitudes, and
   reduce_scatter-vs-allreduce consistency on the 8-device mesh.
"""

import dataclasses

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from mmlspark_tpu import obs
from mmlspark_tpu.engine.booster import (
    Dataset,
    TrainConfig,
    resolve_auto_config,
    train,
)
from mmlspark_tpu.ops.histogram import (
    COUNT_SCALE,
    QMAX,
    HistQuantize,
    build_histogram,
    quantize_channel_scales,
    quantize_hist_vals,
    quantize_wire_plan,
)


def _make_binary(n=4096, F=16, seed=0):
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(n, F))
    logits = X[:, 0] * 1.5 - X[:, 1] + 0.5 * X[:, 2] * X[:, 3]
    y = (logits + rng.normal(scale=0.5, size=n) > 0).astype(np.float64)
    return X, y


def _auc(y, p):
    order = np.argsort(p)
    ranks = np.empty(len(p))
    ranks[order] = np.arange(1, len(p) + 1)
    pos = y > 0
    n1, n0 = pos.sum(), (~pos).sum()
    return (ranks[pos].sum() - n1 * (n1 + 1) / 2) / (n1 * n0)


# ------------------------------------------------------------- wire plan


class TestWirePlan:
    def test_no_shift_when_worst_case_fits(self):
        # 100 rows × 127 ≪ 2^14: nothing to shift on an int16 wire
        assert quantize_wire_plan(100, "int16") == 0
        assert quantize_wire_plan(100, "int32") == 0

    def test_shift_grows_with_rows_and_shrinks_with_cap(self):
        n = 1 << 20  # n·QMAX needs 27 bits
        s16 = quantize_wire_plan(n, "int16")
        s32 = quantize_wire_plan(n, "int32")
        assert s16 == (n * QMAX).bit_length() - 14
        assert s32 == 0  # 27 bits fit the int32 wire's 30-bit cap
        # shifted worst case honors the cap (round-half-up slop included)
        assert (n * QMAX) >> s16 <= 2 ** 14

    def test_overflow_guard_trips_not_wraps(self):
        # ceil(n/D)·QMAX ≥ 2³¹ → a silent int32 wrap if it were allowed;
        # the plan refuses statically instead
        with pytest.raises(ValueError, match="overflow guard"):
            quantize_wire_plan(2 ** 25, "int16")
        # the same rows spread over shards are fine again
        assert quantize_wire_plan(2 ** 25, "int16", num_shards=8) > 0

    def test_unknown_wire_rejected(self):
        with pytest.raises(ValueError, match="int16|int32"):
            quantize_wire_plan(100, "int8")


# ------------------------------------------------------- SR quantization


class TestStochasticRounding:
    def test_bounds_and_dtype(self):
        vals = jnp.asarray(
            np.random.default_rng(0).normal(size=(3, 512)), jnp.float32
        )
        scales = jnp.asarray([0.01, 0.01, COUNT_SCALE], jnp.float32)
        q = quantize_hist_vals(vals, scales, jax.random.PRNGKey(0))
        assert q.dtype == jnp.int16
        assert int(jnp.max(jnp.abs(q))) <= QMAX

    def test_count_channel_exact(self):
        # an in-bag row is exactly 1.0 → exactly 64 buckets → exactly 1.0
        # back, regardless of the random draw (SR is exact on integers)
        vals = jnp.stack([
            jnp.zeros(64), jnp.zeros(64),
            jnp.ones(64, jnp.float32),
        ])
        scales = jnp.asarray([1.0, 1.0, COUNT_SCALE], jnp.float32)
        q = quantize_hist_vals(vals, scales, jax.random.PRNGKey(7))
        assert int(jnp.min(q[2])) == int(jnp.max(q[2])) == 64
        np.testing.assert_array_equal(
            np.asarray(q[2], np.float64) * COUNT_SCALE, np.ones(64)
        )

    def test_seeded_determinism_and_unbiasedness(self):
        vals = jnp.asarray(
            np.random.default_rng(1).normal(size=(3, 4096)), jnp.float32
        )
        scales = jnp.asarray([0.05, 0.05, COUNT_SCALE], jnp.float32)
        key = jax.random.PRNGKey(3)
        q1 = quantize_hist_vals(vals, scales, key)
        q2 = quantize_hist_vals(vals, scales, key)
        np.testing.assert_array_equal(np.asarray(q1), np.asarray(q2))
        # E[q·scale] = v: the dequantized SUM tracks the true sum far
        # tighter than worst-case rounding (CLT over 4096 draws)
        deq = np.asarray(q1, np.float64) * np.asarray(scales)[:, None]
        true = np.asarray(vals, np.float64)
        err = np.abs(deq.sum(axis=1) - true.sum(axis=1))
        assert np.all(err < 4096 * float(scales[0]) * 0.05)

    def test_channel_scales_cover_bagged_max(self):
        g = jnp.asarray([-3.0, 2.0, 0.5], jnp.float32)
        h = jnp.asarray([0.1, 0.2, 0.9], jnp.float32)
        bag = jnp.asarray([1.0, 1.0, 0.0], jnp.float32)  # row 2 out of bag
        s = quantize_channel_scales(g, h, bag)
        assert s.shape == (2,)
        assert float(s[0]) == pytest.approx(3.0 / QMAX)
        assert float(s[1]) == pytest.approx(0.2 / QMAX)
        # all-zero channel → scale 1.0, never a divide-by-zero
        z = quantize_channel_scales(jnp.zeros(3), jnp.zeros(3), bag)
        np.testing.assert_array_equal(np.asarray(z), [1.0, 1.0])

    def test_quantized_histogram_matches_manual_dequant(self):
        # single device: the quantized build must equal scale × integer
        # bin sums of the SAME buckets — no hidden float accumulation
        rng = np.random.default_rng(5)
        n, F, B = 512, 4, 16
        bins = jnp.asarray(rng.integers(0, B, size=(F, n)), jnp.int32)
        vals = jnp.asarray(rng.normal(size=(3, n)), jnp.float32)
        scales = jnp.asarray([0.02, 0.02, COUNT_SCALE], jnp.float32)
        key = jax.random.PRNGKey(11)
        q = quantize_hist_vals(vals, scales, key)
        hq = HistQuantize("int16", 0, scales)
        out = build_histogram(bins, q, jnp.ones(n, bool), B, quantize=hq)
        manual = np.zeros((3, F, B), np.int64)
        qn = np.asarray(q, np.int64)
        bn = np.asarray(bins)
        for f in range(F):
            for c in range(3):
                np.add.at(manual[c, f], bn[f], qn[c])
        # dequantization is int32 total × f32 scale — mirror it exactly
        np.testing.assert_array_equal(
            np.asarray(out),
            manual.astype(np.float32)
            * np.asarray(scales, np.float32)[:, None, None],
        )


# ----------------------------------------------- resolve_auto_config


class TestResolveRules:
    def _resolve(self, **kw):
        cfg = TrainConfig(tree_learner="data", grow_policy="depthwise",
                          **kw)
        return resolve_auto_config(
            cfg, n=1000, backend="cpu", num_devices=8, num_features=64
        )

    def test_every_wire_combination(self):
        # hist_merge × hist_quantize: every pair resolves
        for merge in ("auto", "allreduce", "reduce_scatter"):
            for quant in ("off", "on", "int16", "int32"):
                r = self._resolve(hist_merge=merge, hist_quantize=quant)
                expect = "int16" if quant == "on" else quant
                assert r.hist_quantize == expect
                if merge != "auto":
                    assert r.hist_merge == merge

    def test_on_resolves_to_int16(self):
        assert self._resolve(hist_quantize="on").hist_quantize == "int16"

    def test_unknown_quantize_value_rejected(self):
        with pytest.raises(ValueError, match="hist_quantize"):
            self._resolve(hist_quantize="int8")

    def test_quantize_rejects_voting_and_feature_learners(self):
        for tl in ("voting", "feature"):
            cfg = TrainConfig(tree_learner=tl, hist_quantize="on")
            with pytest.raises(ValueError, match="hist_quantize"):
                resolve_auto_config(cfg, n=1000, backend="cpu",
                                    num_devices=8, num_features=64)

    def test_off_stays_off(self):
        assert self._resolve(hist_quantize="off").hist_quantize == "off"


# --------------------------------------------------- end-to-end training


_COMMON = dict(objective="binary", num_iterations=10, num_leaves=31,
               learning_rate=0.2, seed=11, verbosity=0)


class TestQuantizedTraining:
    def test_auc_parity_with_f32(self):
        X, y = _make_binary()
        f32 = train(dict(_COMMON), Dataset(X, y))
        qnt = train(dict(_COMMON, hist_quantize="on"), Dataset(X, y))
        a_f, a_q = _auc(y, f32.predict(X)), _auc(y, qnt.predict(X))
        assert a_f > 0.85
        assert abs(a_f - a_q) < 1e-3

    def test_same_seed_bitwise_identical_forest(self):
        # the SR key stream is derived from (seed, iteration, class):
        # two runs with identical params reproduce the forest BITWISE
        X, y = _make_binary(n=2048, F=8, seed=3)
        p = dict(_COMMON, hist_quantize="int16")
        m1 = train(p, Dataset(X, y)).save_model_string()
        m2 = train(p, Dataset(X, y)).save_model_string()
        assert m1 == m2

    def test_off_path_matches_param_absent(self):
        # hist_quantize="off" must be the EXACT default path — not a
        # third code path that happens to be close
        X, y = _make_binary(n=2048, F=8, seed=4)
        base = train(dict(_COMMON), Dataset(X, y)).save_model_string()
        off = train(dict(_COMMON, hist_quantize="off"),
                    Dataset(X, y)).save_model_string()
        assert base == off

    def test_categoricals_under_quantize(self):
        rng = np.random.default_rng(9)
        n = 4096
        cat = rng.integers(0, 12, size=n)
        num = rng.normal(size=(n, 3))
        effect = np.where(cat % 3 == 0, 2.0, -1.0)
        y = (effect + num[:, 0] + rng.normal(scale=0.5, size=n) > 0)
        X = np.column_stack([cat.astype(np.float64), num])
        p = dict(_COMMON, categorical_feature=[0])
        f32 = train(p, Dataset(X, y.astype(np.float64)))
        qnt = train(dict(p, hist_quantize="on"),
                    Dataset(X, y.astype(np.float64)))
        # the categorical feature must actually be split on, and parity
        # must hold through the cat-split refinement path
        assert "cat_threshold" in qnt.save_model_string()
        a_f = _auc(y, f32.predict(X))
        a_q = _auc(y, qnt.predict(X))
        assert a_f > 0.8
        assert abs(a_f - a_q) < 1e-3

    def test_adversarial_gradient_magnitudes_stay_finite(self):
        # huge-magnitude regression targets stress the per-iteration
        # max-abs scales; the forest must stay finite (no silent wrap)
        rng = np.random.default_rng(13)
        n = 2048
        X = rng.normal(size=(n, 6))
        y = 1e6 * X[:, 0] + 1e5 * rng.standard_cauchy(size=n)
        b = train(dict(objective="regression", num_iterations=8,
                       num_leaves=15, learning_rate=0.1, seed=5,
                       verbosity=0, hist_quantize="on"),
                  Dataset(X, y))
        pred = b.predict(X)
        assert np.all(np.isfinite(pred))
        # it also has to LEARN: beat the constant-mean baseline
        assert np.mean((y - pred) ** 2) < np.mean((y - y.mean()) ** 2)

    def test_obs_gauges_and_wire_counter(self):
        X, y = _make_binary(n=2048, F=8, seed=6)
        obs.enable()
        try:
            train(dict(_COMMON, num_iterations=3, tree_learner="data",
                       hist_quantize="on"), Dataset(X, y))
            snap = obs.snapshot()
        finally:
            obs.disable()
            obs.reset()
        gauges = set(snap.get("gauges", {}))
        assert any(k.startswith("train.grad_scale") for k in gauges)
        assert any(k.startswith("train.hess_scale") for k in gauges)
        counters = snap.get("counters", {})
        qb = [v for k, v in counters.items()
              if k.startswith("hist.quantized_bytes")]
        assert qb and qb[0] > 0


class TestQuantizedDistributed:
    def test_rs_vs_allreduce_bitwise_same_grower(self):
        # integer partial sums are associative: with the grower pinned
        # (depthwise runs the windowed grower under BOTH merges), the
        # quantized merge is exact and the forests match bitwise
        X, y = _make_binary(n=4096, F=16, seed=2)
        p = dict(_COMMON, tree_learner="data", grow_policy="depthwise",
                 hist_quantize="on")
        ar = train(dict(p, hist_merge="allreduce"), Dataset(X, y))
        rs = train(dict(p, hist_merge="reduce_scatter"), Dataset(X, y))
        assert ar.save_model_string() == rs.save_model_string()
        np.testing.assert_array_equal(ar.predict(X), rs.predict(X))

    def test_mesh_auc_parity_and_int32_wire(self):
        X, y = _make_binary(n=4096, F=16, seed=8)
        p = dict(_COMMON, tree_learner="data", grow_policy="depthwise")
        f32 = train(p, Dataset(X, y))
        q16 = train(dict(p, hist_quantize="int16"), Dataset(X, y))
        q32 = train(dict(p, hist_quantize="int32"), Dataset(X, y))
        a_f = _auc(y, f32.predict(X))
        assert a_f > 0.85
        assert abs(a_f - _auc(y, q16.predict(X))) < 1e-3
        assert abs(a_f - _auc(y, q32.predict(X))) < 1e-3

    def test_mesh_run_to_run_determinism(self):
        X, y = _make_binary(n=4096, F=16, seed=12)
        p = dict(_COMMON, num_iterations=5, tree_learner="data",
                 grow_policy="depthwise", hist_quantize="on")
        m1 = train(p, Dataset(X, y)).save_model_string()
        m2 = train(p, Dataset(X, y)).save_model_string()
        assert m1 == m2

    def test_lossguide_quantized_cross_merge_drift(self):
        # lossguide resolves to DIFFERENT growers per merge strategy
        # (exact-sequence vs windowed) — same contract as f32: score
        # drift, not bitwise identity (see dryrun gates)
        X, y = _make_binary(n=4096, F=16, seed=14)
        p = dict(_COMMON, tree_learner="data", grow_policy="lossguide",
                 hist_quantize="on")
        ar = train(dict(p, hist_merge="allreduce"), Dataset(X, y))
        rs = train(dict(p, hist_merge="reduce_scatter"), Dataset(X, y))
        assert abs(_auc(y, ar.predict(X)) - _auc(y, rs.predict(X))) < 1e-3


class TestGrowConfigStatics:
    def test_quantize_fields_are_cache_key_material(self):
        # hist_quantize/quantize_shift are STATIC grower config: two
        # configs differing only there must not share a trace-cache slot
        from mmlspark_tpu.engine.tree import GrowConfig

        a = GrowConfig(num_leaves=31, num_bins=32, hist_quantize="off")
        b = dataclasses.replace(a, hist_quantize="int16", quantize_shift=2)
        assert a != b
        assert not a.quantize_active
        assert b.quantize_active
