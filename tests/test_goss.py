"""Gradient one-side sampling (GOSS): the exact-count selector against a plain
stable-``argsort`` selection, a fit through the compacted path against a plain
``numpy`` GOSS, and the traced fit's program (no sort of a row-long array, the
histogram calls over the sample's rows alone)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mmlspark_tpu.engine import booster as booster_mod
from mmlspark_tpu.engine.booster import (
    GOSS_TAG, Dataset, goss_amplification, goss_counts, goss_rows, goss_sample, train,
)


def _plain_weights(s, valid, u, k_top, k_rest, amp):
    """The sample by stable argsorts: the k_top valid rows of largest s, then
    the k_rest of the other valid rows of smallest u, ties to the lower row."""
    rows = np.flatnonzero(valid)
    top = rows[np.argsort(-s[rows], kind="stable")][:k_top]
    left = np.setdiff1d(rows, top)
    rest = left[np.argsort(u[left], kind="stable")][:k_rest]
    w = np.zeros(len(s), np.float32)
    w[top], w[rest] = 1.0, amp
    return w


def _grads(kind, K, n, rng):
    if kind == "equal":
        return np.full((K, n), 0.5, np.float32)
    if kind == "two":
        return rng.choice(np.float32([0.25, -0.75]), size=(K, n))
    if kind == "few":  # a handful of values, as trees of a few leaves leave them
        return rng.choice(np.float32([0.1, -0.2, 0.3, -0.4, 0.05]), size=(K, n))
    return rng.normal(size=(K, n)).astype(np.float32)


@pytest.mark.parametrize("kind", ["equal", "two", "few", "normal"])
@pytest.mark.parametrize("n, a, b, K, holes", [
    (1000, 0.2, 0.1, 1, False),
    (4099, 0.3, 0.2, 1, True),
    (777, 0.4, 0.5, 3, False),
    (2048, 0.05, 0.9, 1, True),
])
def test_selector_matches_stable_argsort(kind, n, a, b, K, holes):
    rng = np.random.default_rng(n + K)
    pad = n // 5 + 3
    g = np.concatenate([_grads(kind, K, n, rng), np.full((K, pad), 9.0, np.float32)], axis=1)
    valid = np.arange(n + pad) < n  # padded rows last, with the largest |g| of all
    if holes:  # a process-local layout: invalid rows among the valid ones
        perm = rng.permutation(n + pad)
        g, valid = g[:, perm], valid[perm]
    k_top, k_rest = goss_counts(n, a, b)
    amp = goss_amplification(a, b)
    key = jax.random.PRNGKey(n)
    w = np.asarray(goss_sample(jnp.asarray(g), jnp.asarray(valid), key, k_top, k_rest, amp))
    s = np.asarray(jnp.sum(jnp.abs(jnp.asarray(g)), axis=0))
    u = np.asarray(jax.random.uniform(jax.random.fold_in(key, GOSS_TAG), valid.shape))
    plain = _plain_weights(s, valid, u, k_top, k_rest, amp)
    np.testing.assert_array_equal(w, plain)
    assert (w == 1.0).sum() == k_top and (w == np.float32(amp)).sum() == k_rest
    assert not w[~valid].any()


def test_counts_and_buffer_rows():
    assert goss_counts(132_120_576, 0.2, 0.1) == (26_424_115, 13_212_057)
    assert goss_counts(10, 0.01, 0.1) == (1, 1)  # at least one top row
    assert goss_counts(10, 0.6, 0.6) == (6, 4)  # the rest takes what is left
    assert goss_amplification(0.2, 0.1) == 8.0
    assert goss_rows(39_636_172, 2_097_152) == 19 * 2_097_152  # whole chunks
    assert goss_rows(2457, 2_097_152) == 2457  # one chunk: the sample itself


@pytest.mark.parametrize("n, rows, share", [
    (5000, 2048, 0.3),  # a ragged last block, padding past the sample
    (4096, 4096, 1.0),  # every row taken: the windows carry whole blocks
    (10240, 700, 0.05),  # sparse: many steps add nothing
    (3000, 100, 0.5),  # more rows taken than the buffer holds: the first rows kept
])
def test_streaming_compaction_matches_the_gathers(n, rows, share):
    """The Pallas kernel (interpreted here) against XLA's scatter and
    gathers, to the bit: the sample in row order, then rows of weight 0."""
    from mmlspark_tpu.engine.booster import goss_compact

    rng = np.random.default_rng(n)
    bins = jnp.asarray(rng.integers(0, 256, (n, 7)).astype(np.uint8))
    w = np.where(rng.random(n) < share, rng.choice(np.float32([1.0, 8.0]), n), 0.0).astype(np.float32)
    g, h = jnp.asarray(rng.normal(size=(2, n)), jnp.float32), jnp.asarray(rng.random((2, n)), jnp.float32)
    plain = goss_compact(bins, g, h, jnp.asarray(w), rows, backend="scatter")
    kernel = goss_compact(bins, g, h, jnp.asarray(w), rows, backend="pallas")
    for a, b in zip(plain, kernel):
        assert a.shape == b.shape and a.dtype == b.dtype
    held = min(int((w > 0).sum()), rows)
    np.testing.assert_array_equal(np.asarray(kernel[0])[:held], np.asarray(plain[0])[:held])
    for a, b in zip(plain[1:3], kernel[1:3]):
        np.testing.assert_array_equal(np.asarray(b)[:, :held], np.asarray(a)[:, :held])
    np.testing.assert_array_equal(np.asarray(kernel[3]), np.asarray(plain[3]))  # weights 0 past the sample
    assert not np.asarray(kernel[0])[held:].any()


def _data(n, F, seed):
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(n, F))
    X[:, 0] = rng.integers(0, 4, n)  # few distinct values: ties in the bins
    logit = X[:, 1] - 0.5 * X[:, 2] + 0.3 * X[:, 0]
    y = (rng.random(n) < 1 / (1 + np.exp(-logit))).astype(np.float32)
    return X, y


def test_goss_fit_matches_plain_numpy_goss():
    """Each tree's leaf counts are the plain sample's rows in its leaves, to
    the row, and its leaf values the plain sample's amplified sums, the
    gradients teacher-forced on the fit's own float32 trees."""
    X, y = _data(5003, 6, seed=7)
    params = dict(objective="binary", boosting="goss", num_iterations=4, num_leaves=7, min_data_in_leaf=5,
                  top_rate=0.2, other_rate=0.1, learning_rate=0.3, boost_from_average=False)
    b = train(params, Dataset(X, y))
    host = b._host_trees()
    T, L = host.leaf_value.shape[0], host.leaf_value.shape[-1]
    leaves = np.asarray(b.predict(X, pred_leaf=True)).reshape(len(y), T)
    k_top, k_rest = goss_counts(len(y), 0.2, 0.1)
    amp = goss_amplification(0.2, 0.1)
    root = jax.random.PRNGKey(3 + 7919 * 0)  # the engine's bagging_seed and seed
    score = jnp.zeros(len(y), jnp.float32)
    for t in range(T):
        p = jax.nn.sigmoid(score)
        g, h = np.asarray(p - y), np.asarray(p * (1.0 - p))
        gkey = jax.random.split(jax.random.fold_in(root, t))[0]
        u = np.asarray(jax.random.uniform(jax.random.fold_in(gkey, GOSS_TAG), (len(y),)))
        w = _plain_weights(np.abs(g), np.ones(len(y), bool), u, k_top, k_rest, amp)
        lv = np.asarray(host.leaf_value[t, 0], np.float32)
        for leaf in range(int(host.num_leaves[t, 0])):
            at = (leaves[:, t] == leaf) & (w > 0)
            assert host.leaf_count[t, 0, leaf] == at.sum()
            G, H = float(np.sum(g[at] * w[at], dtype=np.float64)), float(np.sum(h[at] * w[at], dtype=np.float64))
            # the plain (gbdt) fit's own leaves read up to 4.5e-5 from this
            # float64 sum on this data; a rest row at weight 1 reads 0.1 and more
            np.testing.assert_allclose(lv[leaf], -G / H * 0.3, rtol=5e-4, atol=1e-7)
        assert host.leaf_count[t, 0, :L].sum() == k_top + k_rest
        score = score + jnp.asarray(lv)[leaves[:, t]]


def test_sample_counts_follow_each_fit_s_rows():
    # the counts are baked into the fit's program: a second data set of
    # another size under the same parameters must not reuse the first's
    params = dict(objective="binary", boosting="goss", num_iterations=2, num_leaves=7, min_data_in_leaf=5)
    for n in (3001, 4500, 3001):
        host = train(dict(params), Dataset(*_data(n, 4, seed=n)))._host_trees()
        np.testing.assert_array_equal(host.leaf_count.sum(axis=-1), sum(goss_counts(n, 0.2, 0.1)))


def test_mesh_draws_the_same_sample():
    """Over a mesh the sample rides the bag weights; it is the same exact
    sample, so every leaf of every tree holds the same rows as on one device."""
    X, y = _data(4096, 6, seed=11)
    params = dict(objective="binary", boosting="goss", num_iterations=3, num_leaves=7, min_data_in_leaf=5,
                  top_rate=0.3, other_rate=0.2)
    serial = train(dict(params), Dataset(X, y))._host_trees()
    dist = train(dict(params, tree_learner="data"), Dataset(X, y))._host_trees()
    np.testing.assert_array_equal(serial.leaf_count, dist.leaf_count)
    np.testing.assert_array_equal(serial.split_feat, dist.split_feat)


def _eqns(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _eqns(sub)


def test_traced_fit_sorts_no_row_array_and_builds_over_the_sample(monkeypatch):
    from mmlspark_tpu import obs
    from mmlspark_tpu.ops.pallas_hist import WRAPPERS

    noted = []
    monkeypatch.setattr(obs.device, "note_program", lambda label, same, fn, args: noted.append((label, fn, args)))
    X, y = _data(8192, 5, seed=3)
    n = len(y)
    params = dict(objective="binary", boosting="goss", num_iterations=2, num_leaves=7, min_data_in_leaf=5,
                  top_rate=0.1, other_rate=0.05, hist_backend="pallas")
    booster_mod._SCAN_CACHE.clear()
    train(params, Dataset(X, y))
    (_, fn, args), = [e for e in noted if e[0] == "booster.fit"]
    jaxpr = jax.make_jaxpr(fn)(*args).jaxpr
    m = sum(goss_counts(n, 0.1, 0.05))
    sorts = [e for e in _eqns(jaxpr) if e.primitive.name == "sort"]
    assert all(max(v.aval.shape) < n for e in sorts for v in e.invars), [e.invars for e in sorts]
    calls = [e for e in _eqns(jaxpr) if e.primitive.name in ("jit", "pjit") and e.params.get("name") in WRAPPERS]
    assert calls
    # (F, rows): the sample's m rows alone, a small fit's one chunk padded
    # to whole row blocks by the wrapper (2,048 here), never the n rows
    shapes = {e.invars[0].aval.shape for e in calls}
    assert all(f == 5 and m <= rows < n // 2 for f, rows in shapes), (shapes, m)


def test_ledger_counts_the_sample_rows():
    from mmlspark_tpu import obs

    X, y = _data(4096, 5, seed=5)
    params = dict(objective="binary", boosting="goss", num_iterations=2, num_leaves=7, min_data_in_leaf=5,
                  top_rate=0.2, other_rate=0.1)
    booster_mod._SCAN_CACHE.clear()
    obs.reset()
    obs.enable()
    try:
        train(params, Dataset(X, y))
        counters = obs.snapshot()["counters"]
    finally:
        obs.disable()
    k_top, k_rest = goss_counts(4096, 0.2, 0.1)
    assert counters["goss.top_rows"] == 2 * k_top and counters["goss.rest_rows"] == 2 * k_rest
    assert counters["goss.sample_rows"] == 2 * (k_top + k_rest)
    rowcols = sum(v for k, v in counters.items() if k.startswith("hist.rowcols{"))
    passes = sum(v for k, v in counters.items() if k.startswith("hist.passes{"))
    assert rowcols == passes * (k_top + k_rest) * 5
