"""Quantized training at a stated number of levels (ISSUE 34): LightGBM's
``use_quantized_grad`` / ``num_grad_quant_bins`` / ``quant_train_renew_leaf``
/ ``stochastic_rounding`` through ``train()`` and the facade, the levels and
the int32 headroom channel by channel, and the engine held to a plain
implementation of the stated equations.

The plain implementation (``plain_*`` below) is the configuration file's
``equations`` in ``jax.numpy`` float32 and numpy integers: scales
``max|g| / (bins/2)`` and ``max h / bins``, buckets ``floor(v/scale + u)``
with the program's drawn ``u`` handed over, integer sums, dequantize, and the
float32 re-accumulation of a winner's column.
"""

import contextlib
import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mmlspark_tpu import obs
from mmlspark_tpu.engine.booster import (
    Dataset,
    TrainConfig,
    resolve_auto_config,
    train,
)
from mmlspark_tpu.ops.histogram import (
    DEFAULT_LEVELS,
    HistQuantize,
    build_histogram_by_leaf,
    quantize_channel_scales,
    quantize_draw,
    quantize_hist_vals,
    quantize_levels,
    quantize_scales3,
    quantize_wire_plan,
)

CELL_ROWS = 39_845_888  # criteo_quant_train_1chip's training rows


# ---- the plain implementation ----------------------------------------------
def plain_levels(bins):
    """Largest bucket of gradient, hessian, count: LightGBM's rule where the
    bins are given, 127 a side and the count's 64 where they are not."""
    return (127, 127, 64) if bins is None else (bins // 2, bins, 1)


def plain_scales(g, h, levels):
    return np.array(
        [np.float32(np.abs(g).max()) / np.float32(levels[0]),
         np.float32(np.abs(h).max()) / np.float32(levels[1]),
         np.float32(1.0) / np.float32(levels[2])], np.float32,
    )


def plain_buckets(vals, scales, u, levels):
    x = jnp.asarray(vals, jnp.float32) / jnp.asarray(scales, jnp.float32)[:, None]
    top = np.asarray(levels, np.float32)[:, None]
    return np.clip(np.floor(np.asarray(x + u)), -top, top).astype(np.int64)


def plain_sums(bins, q, leaf, W, B):
    """Integer sums (3, W, F, B) of the buckets by leaf slot, column and bin."""
    F, n = bins.shape
    out = np.zeros((3, W, F, B), np.int64)
    keep = (leaf >= 0) & (leaf < W)
    for f in range(F):
        for c in range(3):
            np.add.at(out[c, :, f, :], (leaf[keep], bins[f, keep]), q[c, keep])
    return out


def _rows(n=3000, F=5, B=32, W=4, seed=0):
    rng = np.random.default_rng(seed)
    bins = rng.integers(0, B, size=(F, n)).astype(np.int32)
    p = rng.uniform(0.02, 0.98, size=n).astype(np.float32)
    y = (rng.uniform(size=n) < 0.3).astype(np.float32)
    g, h = p - y, p * (1 - p)
    leaf = rng.integers(-1, W + 1, size=n).astype(np.int32)  # some rows parked
    vals = np.stack([g, h, np.ones(n, np.float32)]).astype(np.float32)
    return bins, vals, leaf, W, B


BINS = [pytest.param(4, id="4"), pytest.param(16, id="16"), pytest.param(None, id="absent=127")]


# ---- levels by parameter, against the plain implementation -------------------
@pytest.mark.parametrize("bins", BINS)
def test_levels_follow_the_parameter(bins):
    assert quantize_levels(bins or 0) == plain_levels(bins)
    assert quantize_levels(0) == DEFAULT_LEVELS


@pytest.mark.parametrize("bins", BINS)
def test_scales_are_the_stated_ones(bins):
    _, vals, _, _, _ = _rows()
    levels = quantize_levels(bins or 0)
    got = quantize_scales3(
        quantize_channel_scales(jnp.asarray(vals[0]), jnp.asarray(vals[1]), jnp.ones(vals.shape[1]), levels), levels,
    )
    np.testing.assert_array_equal(np.asarray(got), plain_scales(vals[0], vals[1], plain_levels(bins)))


@pytest.mark.parametrize("bins", BINS)
def test_buckets_and_integer_sums_equal_the_plain_ones_to_the_bit(bins):
    bins_m, vals, leaf, W, B = _rows()
    levels = quantize_levels(bins or 0)
    scales = plain_scales(vals[0], vals[1], plain_levels(bins))
    key = jax.random.PRNGKey(34)
    q = quantize_hist_vals(jnp.asarray(vals), jnp.asarray(scales), key, levels)
    u = quantize_draw(key, vals.shape)  # the program's draw, handed over
    want_q = plain_buckets(vals, scales, u, plain_levels(bins))
    np.testing.assert_array_equal(np.asarray(q, np.int64), want_q)
    assert np.abs(want_q[0]).max() <= plain_levels(bins)[0] and want_q[1].min() >= 0
    assert set(np.unique(want_q[2])) == {plain_levels(bins)[2]}  # the count's bucket, on every row
    want = plain_sums(bins_m, want_q, leaf, W, B)
    # scales of one: the build's float32 result IS its int32 sums
    raw = build_histogram_by_leaf(
        jnp.asarray(bins_m), q, jnp.asarray(leaf), W, B,
        quantize=HistQuantize("int16", 0, jnp.ones(3, jnp.float32)),
    )
    np.testing.assert_array_equal(np.asarray(raw).astype(np.int64), want)
    deq = build_histogram_by_leaf(
        jnp.asarray(bins_m), q, jnp.asarray(leaf), W, B,
        quantize=HistQuantize("int16", 0, jnp.asarray(scales)),
    )
    np.testing.assert_array_equal(np.asarray(deq), want.astype(np.float32) * scales[:, None, None, None])
    # the count channel dequantizes to the rows themselves, whatever its bucket
    keep = (leaf >= 0) & (leaf < W)
    np.testing.assert_array_equal(np.asarray(deq)[2].sum(axis=(0, 2))[0], keep.sum())


@pytest.mark.parametrize("bins", [4, None])
def test_pallas_bucket_build_equals_the_plain_sums(bins):
    # the chip's path, interpreted: int16 row values through the float product
    from mmlspark_tpu.ops.pallas_hist import pallas_hist_by_leaf_nibble_chunk

    bins_m, vals, leaf, W, B = _rows(n=2048)
    levels = quantize_levels(bins or 0)
    scales = plain_scales(vals[0], vals[1], plain_levels(bins))
    q = quantize_hist_vals(jnp.asarray(vals), jnp.asarray(scales), jax.random.PRNGKey(5), levels)
    got = pallas_hist_by_leaf_nibble_chunk(
        jnp.asarray(bins_m).astype(jnp.uint8), q, jnp.asarray(leaf), W, B, precision="default",
    )
    assert got.dtype == jnp.int32
    np.testing.assert_array_equal(np.asarray(got, np.int64), plain_sums(bins_m, np.asarray(q, np.int64), leaf, W, B))


def test_round_to_nearest_without_the_draw():
    _, vals, _, _, _ = _rows()
    levels = quantize_levels(4)
    scales = plain_scales(vals[0], vals[1], levels)
    q = quantize_hist_vals(jnp.asarray(vals), jnp.asarray(scales), jax.random.PRNGKey(0), levels, stochastic=False)
    np.testing.assert_array_equal(np.asarray(q, np.int64), plain_buckets(vals, scales, np.float32(0.5), levels))


def test_refined_column_is_the_float32_sum_of_the_rows():
    # the winner's column re-accumulated from the float32 rows: what the
    # recorded gain and threshold come from, free of the buckets' rounding
    bins_m, vals, leaf, W, B = _rows()
    ref = build_histogram_by_leaf(jnp.asarray(bins_m[:1]), jnp.asarray(vals), jnp.asarray(leaf), W, B)
    want = np.zeros((3, W, 1, B))
    keep = (leaf >= 0) & (leaf < W)
    for c in range(3):
        np.add.at(want[c, :, 0, :], (leaf[keep], bins_m[0, keep]), vals[c, keep].astype(np.float64))
    np.testing.assert_allclose(np.asarray(ref), want, rtol=2e-6, atol=1e-6)


@pytest.mark.parametrize("bins", [1, 128, -4])
def test_level_count_out_of_range_is_refused(bins):
    with pytest.raises(ValueError, match="num_grad_quant_bins"):
        quantize_levels(bins)
    with pytest.raises(ValueError, match="num_grad_quant_bins"):
        resolve_auto_config(
            TrainConfig(num_grad_quant_bins=bins), n=100, backend="cpu", num_devices=1, num_features=4,
        )


# ---- the int32 headroom, channel by channel ----------------------------------
def test_wire_plan_passes_the_cell_at_four_levels():
    assert quantize_wire_plan(CELL_ROWS, "int16", levels=quantize_levels(4)) == (CELL_ROWS * 4).bit_length() - 14
    assert quantize_wire_plan(CELL_ROWS, "int32", levels=quantize_levels(4)) == 0


@pytest.mark.parametrize(
    "levels, channel",
    [
        pytest.param(quantize_levels(0), "gradient", id="127-a-side"),
        pytest.param((2, 127, 1), "hessian", id="hessian-alone"),
        pytest.param((2, 4, 64), "count", id="count-bucket-64"),
    ],
)
def test_wire_plan_refuses_the_cell_and_names_channel_and_parameter(levels, channel):
    with pytest.raises(ValueError, match=f"overflow guard.*{channel} channel.*num_grad_quant_bins"):
        quantize_wire_plan(CELL_ROWS, "int16", levels=levels)


def test_wire_plan_refuses_exactly_where_int32_ends():
    n = 2 ** 31 // 4  # n x 4 = 2**31: the first row count that does not fit
    with pytest.raises(ValueError, match="overflow guard"):
        quantize_wire_plan(n, "int32", levels=quantize_levels(4))
    # one row fewer fits: 31 bits, one over the int32 wire's cap of 30
    assert quantize_wire_plan(n - 1, "int32", levels=quantize_levels(4)) == 1
    # the same rows over shards fit again
    assert quantize_wire_plan(n, "int32", num_shards=2, levels=quantize_levels(4)) == 2


# ---- LightGBM's names through train() and the facade --------------------------
def _binary(n=2048, F=8, seed=4):
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(n, F))
    y = (X[:, 0] * 1.5 - X[:, 1] + rng.normal(scale=0.5, size=n) > 0).astype(np.float64)
    return X, y


_COMMON = dict(objective="binary", num_iterations=4, num_leaves=15, learning_rate=0.2, seed=11, verbosity=0)


@pytest.mark.parametrize("bins", [4, 16])
def test_upstream_names_equal_hist_quantize_at_the_same_levels(bins):
    X, y = _binary()
    up = train(dict(_COMMON, use_quantized_grad=True, num_grad_quant_bins=bins), Dataset(X, y))
    own = train(dict(_COMMON, hist_quantize="on", num_grad_quant_bins=bins), Dataset(X, y))
    both = train(dict(_COMMON, use_quantized_grad=True, hist_quantize="int16", num_grad_quant_bins=bins,
                      quant_train_renew_leaf=True, stochastic_rounding=True), Dataset(X, y))
    assert up.config.hist_quantize == "int16" and up.config.num_grad_quant_bins == bins
    assert up.save_model_string() == own.save_model_string() == both.save_model_string()
    # another level count is another model: the parameter reaches the arithmetic
    other = train(dict(_COMMON, use_quantized_grad=True, num_grad_quant_bins=bins * 2), Dataset(X, y))
    assert other.save_model_string() != up.save_model_string()


def test_use_quantized_grad_false_is_the_float_path():
    X, y = _binary()
    off = train(dict(_COMMON, use_quantized_grad=False, num_grad_quant_bins=4), Dataset(X, y))
    assert off.config.hist_quantize == "off"
    assert off.save_model_string() == train(dict(_COMMON), Dataset(X, y)).save_model_string()


@pytest.mark.parametrize(
    "params, match",
    [
        pytest.param(dict(use_quantized_grad=True, hist_quantize="off"), "disagree", id="on-and-off"),
        pytest.param(dict(use_quantized_grad=False, hist_quantize="on"), "disagree", id="off-and-on"),
        pytest.param(dict(use_quantized_grad=True, quant_train_renew_leaf=False), "quant_train_renew_leaf", id="renew-leaf-false"),
        pytest.param(dict(hist_quantize="on", num_grad_quant_bins=300), "num_grad_quant_bins", id="bins-300"),
    ],
)
def test_train_refuses(params, match):
    X, y = _binary(n=256)
    with pytest.raises(ValueError, match=match):
        train(dict(_COMMON, **params), Dataset(X, y))


def test_stochastic_rounding_false_changes_the_rounding_and_not_the_run_to_run_result():
    X, y = _binary()
    p = dict(_COMMON, use_quantized_grad=True, num_grad_quant_bins=4, stochastic_rounding=False)
    a, b = (train(p, Dataset(X, y)).save_model_string() for _ in range(2))
    assert a == b
    assert a != train(dict(p, stochastic_rounding=True), Dataset(X, y)).save_model_string()


def test_facade_hands_the_upstream_names_to_the_engine(binary_df):
    from mmlspark_tpu.models.lightgbm import LightGBMClassifier

    small = dict(numIterations=4, numLeaves=7, minDataInLeaf=5, parallelism="serial")
    up = LightGBMClassifier(useQuantizedGrad=True, numGradQuantBins=4, **small).fit(binary_df)
    own = LightGBMClassifier(histQuantize="on", numGradQuantBins=4, **small).fit(binary_df)
    plain = LightGBMClassifier(**small).fit(binary_df)
    cfg = up.getBooster().config
    assert (cfg.hist_quantize, cfg.num_grad_quant_bins, cfg.use_quantized_grad) == ("int16", 4, True)
    assert up.getBooster().save_model_string() == own.getBooster().save_model_string()
    assert up.getBooster().save_model_string() != plain.getBooster().save_model_string()
    with pytest.raises(ValueError, match="disagree"):
        LightGBMClassifier(useQuantizedGrad=True, histQuantize="off", **small).fit(binary_df)
    with pytest.raises(ValueError, match="quant_train_renew_leaf"):
        LightGBMClassifier(useQuantizedGrad=True, quantTrainRenewLeaf=False, **small).fit(binary_df)


# ---- absent levels: the parent's model, to the bit ----------------------------
# sha256 of the model strings that commit 1d0684d (the parent of the PR that
# made the levels a parameter) gives for these three fits
_PARENT = {
    "lossguide_on": (dict(hist_quantize="on"), "f8e19bef3880213b90e315bf3669c7ef5c7f2dbe9378993c0146e5363247856f"),
    "window_int32": (dict(hist_quantize="int32", split_batch=4), "8a60470dc3cc50d3d9a6a9cca3fb1773f91408d4c824ddd35e9fc2b29a12d40b"),
    "depthwise": (dict(hist_quantize="on", grow_policy="depthwise"), "db6bbda7285313ce3a978a9ac172faa07a3519bd80051516b8db61dd8ee811a9"),
}


def _pinned_rows(n):
    """The rows of the fits whose model strings are pinned: column 7 categorical."""
    rng = np.random.default_rng(0)
    X = rng.normal(size=(n, 8))
    X[:, 7] = rng.integers(0, 12, size=n)
    y = ((X[:, 0] * 1.5 - X[:, 1] + np.where(X[:, 7] % 3 == 0, 1.0, -0.5) + rng.normal(scale=.5, size=n)) > 0).astype(float)
    return X, y


@pytest.mark.parametrize("name", sorted(_PARENT))
def test_levels_absent_give_the_parents_model_string(name):
    X, y = _pinned_rows(4096)
    extra, want = _PARENT[name]
    m = train(dict(objective="binary", num_iterations=5, num_leaves=15, learning_rate=0.2, seed=11, verbosity=0,
                   categorical_feature=[7], **extra), Dataset(X, y)).save_model_string()
    assert hashlib.sha256(m.encode()).hexdigest() == want


# ---- what a fit counts and says ---------------------------------------------
@contextlib.contextmanager
def _recording():
    obs.reset()
    obs.flight.reset()
    obs.enable()
    try:
        yield
    finally:
        obs.disable()
        obs.reset()


@pytest.fixture(scope="module")
def counted():
    X, y = _binary()
    with _recording():
        before = dict(obs.snapshot()["counters"])
        windowed = train(dict(_COMMON, num_leaves=15, use_quantized_grad=True, num_grad_quant_bins=4, split_batch=4), Dataset(X, y))
        mid = dict(obs.snapshot()["counters"])
        gauges = dict(obs.snapshot()["gauges"])  # the next fit's scales take their place
        train(dict(_COMMON, hist_quantize="int32"), Dataset(X, y))
        after = dict(obs.snapshot()["counters"])
        train(dict(_COMMON), Dataset(X, y))
        floats = dict(obs.snapshot()["counters"])
        spans = obs.flight.spans("booster.program")
    rise = lambda a, b: {k: b[k] - a.get(k, 0.0) for k in b if b[k] != a.get(k, 0.0)}  # noqa: E731
    return {"windowed": rise(before, mid), "lossguide": rise(mid, after), "float": rise(after, floats),
            "spans": [s["attrs"] for s in spans], "gauges": gauges, "booster": windowed, "y": y}


def test_fit_counts_its_levels_once(counted):
    w, lg = counted["windowed"], counted["lossguide"]
    assert [w[f"train.quant_levels{{channel={c}}}"] for c in ("grad", "hess", "count")] == [2, 4, 1]
    assert [lg[f"train.quant_levels{{channel={c}}}"] for c in ("grad", "hess", "count")] == [127, 127, 64]
    assert not any(k.startswith("train.quant") for k in counted["float"])


def test_fit_counts_its_passes_from_the_growers_program(counted):
    from mmlspark_tpu.engine.tree import GrowConfig, full_tree_passes

    iters = _COMMON["num_iterations"]
    # 15 leaves at 4 splits a pass: 1, 2, 4, 4, 3 -> five passes and the root's build
    passes = full_tree_passes(GrowConfig(num_bins=256, num_leaves=15, split_batch=4))
    assert passes == 5
    # the CPU's default backend sums by scatter-add: bucket builds of int16 values, float32 refinement
    bucket, refine = "hist.passes{body=scatter,scope=quant_hist,vals=i16}", "hist.passes{body=scatter,scope=quant_refine,vals=f32}"
    w = counted["windowed"]
    assert w[bucket] == iters * (passes + 1)
    assert w[refine] == iters * passes
    assert w["train.quant_refine_cols"] == iters * passes * 4  # a window's four slots a pass
    lg = counted["lossguide"]  # one split a step: 14 steps, a build and a refined column each, and the root
    assert lg[bucket] == iters * 15
    assert lg[refine] == lg["train.quant_refine_cols"] == iters * 14


def test_program_span_says_levels_and_wire(counted):
    a, b, c = counted["spans"]
    assert (a["quant_levels"], a["quant_wire"]) == ("2x4x1", "int16")
    assert (b["quant_levels"], b["quant_wire"]) == ("127x127x64", "int32")
    assert "quant_levels" not in c and "quant_wire" not in c
    # the CPU's default backend sums by scatter-add: no kernel body is reached
    assert a["quant_bucket_body"] == b["quant_bucket_body"] == "scatter" and "quant_bucket_body" not in c
    assert not any(k.startswith("hist.passes{body=") and "body=scatter" not in k for k in counted["windowed"])


def test_first_iterations_scales_are_lightgbms(counted):
    # iteration 0 of binary log loss: every gradient is p0 or p0 - 1 and every
    # hessian p0 (1 - p0), so the scales are known in closed form
    p0 = np.float32(counted["y"].mean())
    g = counted["gauges"]
    key = lambda name: next(k for k in g if k.startswith(name) and "it=0" in k)  # noqa: E731
    np.testing.assert_allclose(g[key("train.grad_scale")], max(p0, 1 - p0) / 2, rtol=1e-6)
    np.testing.assert_allclose(g[key("train.hess_scale")], p0 * (1 - p0) / 4, rtol=1e-6)


# ---- which kernel body the bucket builds reach (ISSUE 35) ----------------------
# sha256 of the model strings that commit 2fbaca0 (the parent of the PR that
# sent a small window's bucket builds to the factorized body) gives for these
# fits through the interpreted Pallas kernels: integer sums do not depend on
# the body that builds them, so the trees may not move by a bit.  The second
# hash is of the model string without its ``split_gain=`` lines, which is
# still 2fbaca0's at W = 32: the trees (features, thresholds, leaf values,
# counts) are the bucket builds' to decide.  The whole string at W = 32 is the
# factorized body's since it serves the float32 refinement there too: its
# recorded gains are float sums, which another body adds in another order
# (one recorded gain of 0.0163 moves by 3e-5: a difference of large terms).
_BODIES = {
    "window8": (dict(num_leaves=31, split_batch=8), "nibble",
                "61d563703c9d1d03f67a37f69dd9d4b4579c6bda558f5893a2ff0cbee679f39a",
                "e478ad3028432a4dabb114f0ae74a1dd322ad7f2dde198f15518986c0530697b"),
    "depthwise32": (dict(num_leaves=63, grow_policy="depthwise", min_data_in_leaf=5), "nibble",
                    "9e352ff7855ac7bf5cf3bc1bc6416a563ee4a1366f0684935b4d0bd914e52c93",
                    "e2eebf474ea59cb4a0007ea0ef0ac7469abe9429608bf1cbcd09ebc94b9213a4"),
}


@pytest.mark.parametrize("name", sorted(_BODIES))
def test_bucket_builds_take_the_body_their_window_asks_for_and_the_parents_trees(name):
    X, y = _pinned_rows(2048)
    extra, body, want, want_trees = _BODIES[name]
    with _recording():
        m = train(dict(objective="binary", num_iterations=3, learning_rate=0.2, seed=11, verbosity=0, categorical_feature=[7],
                       use_quantized_grad=True, num_grad_quant_bins=4, hist_backend="pallas", max_bin=255, **extra), Dataset(X, y))
        counters = dict(obs.snapshot()["counters"])
        (span,) = (s["attrs"] for s in obs.flight.spans("booster.program"))
    model = m.save_model_string()
    assert hashlib.sha256(model.encode()).hexdigest() == want
    trees = "\n".join(line for line in model.splitlines() if not line.startswith("split_gain="))
    assert hashlib.sha256(trees.encode()).hexdigest() == want_trees
    bodies = {k: v for k, v in counters.items() if k.startswith("hist.passes{") and "scope=quant_hist" in k}
    assert list(bodies) == [f"hist.passes{{body={body},scope=quant_hist,vals=i16}}"] and all(v > 0 for v in bodies.values())
    assert span["quant_bucket_body"] == body
