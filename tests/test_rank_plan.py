"""LambdaRank over a query plan (ops/rank_plan): the gradient against the
all-pairs form and the old padded (G, M, M) form, NDCG by query against the
host metric, the plan's cache on a data set, and the facade."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mmlspark_tpu import obs
from mmlspark_tpu.engine import eval_metrics
from mmlspark_tpu.engine.booster import Dataset, train
from mmlspark_tpu.engine.dist_metrics import get_device_metric, global_group_matrix
from mmlspark_tpu.ops.objectives import LambdaRank
from mmlspark_tpu.ops.rank_plan import build_rank_plan, plan_from_matrix


def _all_pairs_f64(score, y, sizes, K=20, sigma=2.0, label_gain=None):
    """The configuration's equations, pair by pair, in numpy float64;
    returns (grad, hess, each row's query's largest |grad| and hess)."""
    n = int(np.sum(sizes))
    g, h = np.zeros(n), np.zeros(n)
    gmax, hmax = np.zeros(n), np.zeros(n)
    st = 0
    for sz in sizes:
        sl = slice(st, st + sz)
        s = score[sl].astype(np.float64)
        lbl = y[sl].astype(int)
        gain = np.asarray(label_gain, np.float64)[lbl] if label_gain is not None else 2.0 ** lbl - 1.0
        order = np.argsort(-s, kind="stable")
        rank = np.empty(sz, int)
        rank[order] = np.arange(sz)
        d = np.where(rank < K, 1.0 / np.log2(rank + 2.0), 0.0)
        idcg = np.sum(np.sort(gain)[::-1][:K] / np.log2(np.arange(min(K, sz)) + 2.0))
        if idcg > 0:
            gd = gain[:, None] - gain[None, :]
            delta = gd * np.abs(d[:, None] - d[None, :]) / idcg
            rho = 1.0 / (1.0 + np.exp(sigma * (s[:, None] - s[None, :])))
            lam = np.where(gd > 0, -sigma * rho * delta, 0.0)
            hs = np.where(gd > 0, sigma**2 * rho * (1 - rho) * delta, 0.0)
            g[sl] = lam.sum(1) - lam.sum(0)
            h[sl] = hs.sum(1) + hs.sum(0)
        gmax[sl], hmax[sl] = np.abs(g[sl]).max(), h[sl].max()
        st += sz
    return g, np.maximum(h, 1e-9), gmax, hmax


def _padded_form(obj, idx, valid, score, y):
    """The gradient as ops/objectives.LambdaRank computed it before the plan:
    one padded (G, M) matrix, every tensor (G, M, M).  Kept as an oracle."""
    idx, valid = jnp.asarray(idx), jnp.asarray(valid)
    s, lbl = score[idx], y[idx]
    gain = obj._gains(lbl) * valid
    order_ideal = jnp.argsort(jnp.where(valid, -gain, jnp.inf), axis=1)
    sorted_gain = jnp.take_along_axis(gain, order_ideal, axis=1)
    pos = jnp.arange(gain.shape[1])
    disc = 1.0 / jnp.log2(pos + 2.0)
    idcg = jnp.sum(sorted_gain * disc * (pos < obj.max_position), axis=1, keepdims=True)
    inv_idcg = jnp.where(idcg > 0, 1.0 / jnp.maximum(idcg, 1e-12), 0.0)
    order = jnp.argsort(jnp.where(valid, -s, jnp.inf), axis=1)
    ranks = jnp.argsort(order, axis=1)
    item_disc = jnp.where(ranks < obj.max_position, disc[ranks], 0.0)
    sd = s[:, :, None] - s[:, None, :]
    gd = gain[:, :, None] - gain[:, None, :]
    dd = item_disc[:, :, None] - item_disc[:, None, :]
    pair_valid = valid[:, :, None] & valid[:, None, :] & (gd > 0)
    delta_ndcg = jnp.abs(gd * dd) * inv_idcg[:, :, None]
    sig = jax.nn.sigmoid(-obj.sigmoid * sd)
    lam = -obj.sigmoid * sig * delta_ndcg * pair_valid
    hs = obj.sigmoid**2 * sig * (1.0 - sig) * delta_ndcg * pair_valid
    g_item = jnp.sum(lam, axis=2) - jnp.sum(lam, axis=1)
    h_item = jnp.sum(hs, axis=2) + jnp.sum(hs, axis=1)
    n = score.shape[0]
    grad = jnp.zeros(n, score.dtype).at[idx.reshape(-1)].add(jnp.where(valid, g_item, 0.0).reshape(-1))
    hess = jnp.zeros(n, score.dtype).at[idx.reshape(-1)].add(jnp.where(valid, h_item, 0.0).reshape(-1))
    return grad, jnp.maximum(hess, 1e-9)


def _ragged(seed, queries=40, longest=300, tied=True):
    rng = np.random.default_rng(seed)
    sizes = np.concatenate([[1, 2, longest, 19, 20, 21, 8, 9, 7], rng.integers(1, longest, queries)])
    n = int(sizes.sum())
    score = rng.normal(size=n).astype(np.float32)
    if tied:
        score = np.round(score, 1)  # many equal scores: ranks fall back on row order
    y = rng.integers(0, 5, n).astype(np.float32)
    y[: sizes[:3].sum()] = 0.0  # queries with no relevant row (sizes 1, 2 and the longest)
    return sizes, score, y


CASES = {
    "ragged_tied": dict(seed=0),
    "ragged_distinct": dict(seed=1, tied=False),
    "all_scores_equal": dict(seed=2, zero=True),
    "short_cut": dict(seed=3, K=5),
    "cut_beyond_longest": dict(seed=4, longest=24, K=40),
    "label_gain": dict(seed=5, label_gain=[0.0, 1.0, 3.0, 7.0, 20.0]),
    "weights": dict(seed=6, weights=True),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_gradient_equals_all_pairs(case):
    c = dict(CASES[case])
    sizes, score, y = _ragged(c.pop("seed"), longest=c.pop("longest", 300), tied=c.pop("tied", True))
    if c.pop("zero", False):
        score[:] = 0.0
    K, gains = c.pop("K", 20), c.pop("label_gain", None)
    w = np.random.default_rng(9).uniform(0.5, 2.0, len(y)).astype(np.float32) if c.pop("weights", False) else None
    obj = LambdaRank(max_position=K, label_gain=gains).set_groups(sizes)
    g, h = jax.jit(obj.grad_hess)(jnp.asarray(score), jnp.asarray(y), None if w is None else jnp.asarray(w))
    g0, h0, gmax, hmax = _all_pairs_f64(score, y, sizes, K=K, label_gain=gains)
    if w is not None:
        g0, h0, gmax, hmax = g0 * w, h0 * w, gmax * w, hmax * w
    # within 1e-5 of the larger of the value and the query's largest
    assert np.max(np.abs(np.asarray(g) - g0) / np.maximum(np.maximum(np.abs(g0), gmax), 1e-30)) < 1e-5
    assert np.max(np.abs(np.asarray(h) - h0) / np.maximum(np.maximum(h0, hmax), 1e-9)) < 1e-5
    assert np.abs(g0).max() > 0.1  # the case has something to get wrong


@pytest.mark.parametrize("seed,K", [(0, 20), (7, 3), (8, 64)])
def test_gradient_equals_the_padded_form(seed, K):
    sizes, score, y = _ragged(seed, queries=20, longest=90)
    obj = LambdaRank(max_position=K).set_groups(sizes)
    idx, valid = global_group_matrix(sizes, 0, int(sizes.max()))
    g, h = obj.grad_hess(jnp.asarray(score), jnp.asarray(y), None)
    g0, h0 = _padded_form(obj, idx, valid, jnp.asarray(score), jnp.asarray(y))
    np.testing.assert_allclose(np.asarray(g), np.asarray(g0), rtol=1e-5, atol=1e-5 * float(jnp.abs(g0).max()))
    np.testing.assert_allclose(np.asarray(h), np.asarray(h0), rtol=1e-5, atol=1e-5 * float(h0.max()))


def test_plan_buckets_by_length_and_maps_rows_back():
    sizes = np.array([3, 9, 300, 16, 17, 1, 256])
    plan = build_rank_plan(sizes)
    assert plan.shape_key == (((2, 8), (2, 16), (1, 32), (1, 256), (1, 512)), int(sizes.sum()))
    assert plan.queries == 7
    assert plan.pair_terms(20) == int(np.sum(np.minimum(20, sizes) * sizes))
    assert plan.pair_slots(20) == 2 * 8 * 8 + 2 * 16 * 16 + 20 * 32 + 20 * 256 + 20 * 512
    # each row's slot holds that row: scatter the row numbers through the buckets
    flat = np.full(sum(g * m for g, m in plan.shape_key[0]) + 1, -1)
    base = 0
    for start, size, pos in plan.buckets:
        for g in range(len(start)):
            flat[base + g * len(pos) : base + g * len(pos) + size[g]] = start[g] + np.arange(size[g])
        base += len(start) * len(pos)
    np.testing.assert_array_equal(flat[plan.inv], np.arange(sizes.sum()))


def test_plan_from_matrix_with_offsets_and_gaps():
    # two processes' blocks of 8 padded rows: queries (2, 3) at 0 and (4,) at 8
    idx0, valid0 = global_group_matrix(np.array([2, 3]), 0, 4)
    idx1, valid1 = global_group_matrix(np.array([4]), 8, 4)
    plan = plan_from_matrix(np.concatenate([idx0, idx1]), np.concatenate([valid0, valid1]))
    spare = 3 * 8
    np.testing.assert_array_equal(plan.inv, [0, 1, 8, 9, 10, spare, spare, spare, 16, 17, 18, 19])
    with pytest.raises(ValueError, match="contiguous"):
        plan_from_matrix(np.array([[0, 2]]), np.array([[True, True]]))


@pytest.mark.parametrize("k", [1, 5, 10, 400])
def test_ndcg_on_the_plan_equals_the_host_metric(k):
    sizes, score, y = _ragged(11)
    ev = get_device_metric(f"ndcg@{k}", group_sizes=sizes)
    aux = tuple(jnp.asarray(a) for a in ev.aux_host())
    st = jax.jit(lambda s, lbl, *a: ev.stats(s[None, :], lbl, None, None, *a))(jnp.asarray(score), jnp.asarray(y), *aux)
    want = eval_metrics.ndcg_at(k)(y, score, group_sizes=sizes)
    assert ev.finalize(np.asarray(st)) == pytest.approx(want, rel=1e-5)


def _rank_set(seed, queries=60):
    rng = np.random.default_rng(seed)
    sizes = rng.integers(3, 40, queries)
    n = int(sizes.sum())
    X = rng.normal(size=(n, 6))
    y = np.clip(np.round(X[:, 0] + 0.5 * X[:, 1] + 0.3 * rng.normal(size=n) + 1.0), 0, 4)
    return X, y, sizes


def test_second_train_builds_and_sends_no_plan():
    X, y, sizes = _rank_set(0)
    ds = Dataset(X, y, group=sizes)
    params = dict(objective="lambdarank", num_iterations=2, num_leaves=7, min_data_in_leaf=2, verbosity=-1)
    obs.enable()
    try:
        def fit():
            before = obs.snapshot()["counters"]
            train(params, ds)
            after = obs.snapshot()["counters"]
            span = [s for s in obs.flight.spans("booster.rank_plan")][-1]
            return span, {k: after.get(k, 0.0) - before.get(k, 0.0) for k in after}

        first, sent1 = fit()
        plan_bytes = sum(a.nbytes for a in jax.tree_util.tree_leaves(build_rank_plan(sizes).host_arrays()))
        second, sent2 = fit()
    finally:
        obs.disable()
    assert first["attrs"]["cache_hit"] is False and second["attrs"]["cache_hit"] is True
    assert first["parent"] == "booster.prepare"
    assert first["attrs"]["queries"] == len(sizes) and first["attrs"]["buckets"] == 4  # widths 8, 16, 32, 64
    # the first fit also sends the binned matrix (a byte a cell) and five bytes
    # a row of labels and mask (the init scores are made on the device); the
    # second sends nothing
    assert sent1["train.upload_bytes"] == plan_bytes + X.size + 5 * len(y)
    assert sent2["train.upload_bytes"] == 0
    plan = build_rank_plan(sizes)
    for fit_counts in (sent1, sent2):
        assert fit_counts["rank.queries"] == 2 * len(sizes)
        assert fit_counts["rank.pair_slots"] == 2 * plan.pair_slots(20)
        assert fit_counts["rank.pair_terms"] == 2 * plan.pair_terms(20)
    # other sizes on the same data set: a new plan
    ds.group = np.roll(sizes, 1)
    obs.enable()
    try:
        train(params, ds)
        assert obs.flight.spans("booster.rank_plan")[-1]["attrs"]["cache_hit"] is False
    finally:
        obs.disable()


def test_state_key_is_the_plans_shapes_not_its_bytes():
    sizes = np.array([5, 9, 30, 30])
    a = LambdaRank().set_groups(sizes)
    b = LambdaRank().set_groups(sizes[::-1].copy())  # other queries, the same buckets
    assert a.state_key() == b.state_key() == (((1, 8), (1, 16), (2, 32)), 74)
    assert LambdaRank().state_key() is None


@pytest.mark.parametrize("iterations", [1, 4])
def test_ranker_fit_through_the_facade_learns(iterations):
    from mmlspark_tpu import DataFrame, LightGBMRanker

    X, y, sizes = _rank_set(3, queries=80)
    qid = np.repeat(np.arange(len(sizes)), sizes)
    df = DataFrame({"features": list(X), "label": y, "query": qid})
    model = LightGBMRanker(
        numIterations=iterations, numLeaves=7, minDataInLeaf=2, groupCol="query", verbosity=-1,
    ).fit(df)
    scores = np.asarray(model.transform(df)["prediction"], np.float64)
    ndcg = eval_metrics.ndcg_at(10)(y, scores, group_sizes=sizes)
    base = eval_metrics.ndcg_at(10)(y, np.zeros_like(scores), group_sizes=sizes)
    assert ndcg > base + 0.05
    if iterations == 4:
        one = eval_metrics.ndcg_at(10)(
            y,
            np.asarray(
                LightGBMRanker(numIterations=1, numLeaves=7, minDataInLeaf=2, groupCol="query", verbosity=-1)
                .fit(df).transform(df)["prediction"], np.float64,
            ),
            group_sizes=sizes,
        )
        assert ndcg > one  # it rises over four iterations
