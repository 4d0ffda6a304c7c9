"""The root histograms of a multiclass iteration's K trees built together,
eight classes to a pass (``ops/histogram.py`` ``build_class_root_histograms``,
``ops/pallas_hist.py`` ``_pallas_hist_class_roots``), and each class's
windowed grower handed its own: every root equals the one the class's grower
builds for itself to the bit, on both backends, and so does every tree and
every fit."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mmlspark_tpu.engine import booster as booster_mod
from mmlspark_tpu.engine.booster import Dataset, train
from mmlspark_tpu.engine.tree import (
    GrowConfig,
    class_root_histograms,
    class_roots_active,
    grow_tree_depthwise,
)
from mmlspark_tpu.ops.histogram import build_class_root_histograms, build_histogram_by_leaf

B = 256


def _rows(K, n, F, seed):
    rng = np.random.default_rng(seed)
    bins = rng.integers(0, B, size=(n, F)).astype(np.uint8)
    grad = rng.normal(size=(K, n)).astype(np.float32)
    hess = rng.uniform(0.01, 1.0, size=(K, n)).astype(np.float32)
    # a third of the rows out of the bag, the rest weighted
    bag = ((rng.uniform(size=n) < 0.67) * rng.uniform(0.5, 2.0, size=n)).astype(np.float32)
    return [jnp.asarray(x) for x in (bins, grad, hess, bag)]


@pytest.mark.parametrize("backend", ["scatter", "pallas"])
@pytest.mark.parametrize("K", [5, 8, 100])  # one ragged group, one whole group, a ragged last group
def test_class_roots_equal_each_class_s_own_root_to_the_bit(K, backend):
    F, n, chunk = 7, 4096, 2048  # two histogram chunks
    bins, grad, hess, bag = _rows(K, n, F, seed=K)
    bins_t = bins.T
    kw = dict(backend=backend, chunk=chunk, precision="default")

    @jax.jit
    def together(g, h, w):
        return build_class_root_histograms(bins_t, g, h, w, B, **kw)

    @jax.jit
    def one_by_one(g, h, w):
        def root(gh):
            vals = jnp.stack([gh[0] * w, gh[1] * w, (w > 0).astype(jnp.float32)])
            return build_histogram_by_leaf(bins_t, vals, jnp.zeros(n, jnp.int32), 8, B, **kw)[:, 0]
        return jax.lax.map(root, (g, h))

    got, want = np.asarray(together(grad, hess, bag)), np.asarray(one_by_one(grad, hess, bag))
    assert got.shape == (K, 3, F, B)
    np.testing.assert_array_equal(got, want)
    assert got[:, 2].sum() == K * F * np.count_nonzero(np.asarray(bag))  # every in-bag row once a class and column


@pytest.mark.parametrize("backend", ["scatter", "pallas"])
def test_a_grower_handed_its_root_grows_the_tree_it_grows_alone(backend):
    K, n, F = 12, 8192, 6
    bins, grad, hess, bag = _rows(K, n, F, seed=1)
    grad = grad + 0.5 * (np.asarray(bins[:, 0])[None, :] > 128)  # something to split on
    cfg = GrowConfig(
        num_bins=B, num_leaves=15, split_batch=8, min_data_in_leaf=5, hist_backend=backend,
        hist_chunk=4096, hist_precision="default",
    )
    fmask = jnp.ones((K, F), bool)
    assert class_roots_active(cfg, K) and not class_roots_active(cfg, 1)

    @jax.jit
    def rooted(g, h):
        roots = class_root_histograms(cfg, bins, g, h, bag)
        return jax.lax.map(
            lambda a: grow_tree_depthwise(cfg, bins, a[0], a[1], bag, a[2], root_hist=a[3]), (g, h, fmask, roots)
        )

    @jax.jit
    def alone(g, h):
        return jax.lax.map(lambda a: grow_tree_depthwise(cfg, bins, a[0], a[1], bag, a[2]), (g, h, fmask))

    (tree_r, ids_r), (tree_a, ids_a) = rooted(grad, hess), alone(grad, hess)
    assert int(np.min(np.asarray(tree_a.num_leaves))) > 1
    for name in tree_a._fields:
        np.testing.assert_array_equal(np.asarray(getattr(tree_r, name)), np.asarray(getattr(tree_a, name)), err_msg=name)
    np.testing.assert_array_equal(np.asarray(ids_r), np.asarray(ids_a))


def test_the_roots_are_built_together_where_each_tree_s_own_root_takes_the_factorized_body():
    """On the Pallas backend every window's root takes the factorized body,
    whose tile the class roots' body is: they replace a tree's own root at
    any window and bin count, and equal it to the bit at a window of 32
    slots (depthwise at 63 leaves) and at 64 bins.  Lossguide fits build
    each root in its own tree."""
    cfg = GrowConfig(num_bins=B, num_leaves=31, split_batch=8, hist_backend="pallas")
    assert class_roots_active(cfg, 100)
    assert class_roots_active(GrowConfig(num_bins=64, num_leaves=31, split_batch=8, hist_backend="pallas"), 100)
    wide = GrowConfig(num_bins=B, num_leaves=63, grow_policy="depthwise", hist_backend="pallas")
    assert wide.level_window == 32 and class_roots_active(wide, 100)
    assert class_roots_active(GrowConfig(num_bins=64, num_leaves=31, split_batch=8), 100)  # scatter: any shape
    assert not class_roots_active(GrowConfig(num_bins=B, num_leaves=31, hist_backend="pallas"), 100)  # lossguide
    K, n, F = 5, 2048, 6
    bins, grad, hess, bag = _rows(K, n, F, seed=2)
    for W, num_bins in ((32, B), (8, 64)):
        bins_t = bins.T % num_bins
        kw = dict(backend="pallas", precision="default")
        roots = np.asarray(build_class_root_histograms(bins_t, grad, hess, bag, num_bins, **kw))
        for k in range(K):
            vals = jnp.stack([grad[k] * bag, hess[k] * bag, (bag > 0).astype(jnp.float32)])
            own = build_histogram_by_leaf(bins_t, vals, jnp.zeros(n, jnp.int32), W, num_bins, **kw)[:, 0]
            np.testing.assert_array_equal(roots[k], np.asarray(own))


@pytest.mark.parametrize("extra, engaged", [
    (dict(hist_backend="pallas"), True),
    (dict(), True),
    (dict(tree_learner="data", hist_merge="reduce_scatter"), True),  # a row-sharded mesh: the histogram build merges
    (dict(tree_learner="voting"), False),  # local histograms and votes: each tree its own root
    (dict(use_quantized_grad=True), False),
], ids=["pallas", "scatter", "data-reduce_scatter", "voting", "quantized"])
def test_a_multiclass_fit_grows_the_same_trees_with_its_roots_built_together(monkeypatch, extra, engaged):
    rng = np.random.default_rng(5)
    n, F, K = 4096, 6, 5
    X = rng.normal(size=(n, F))
    y = (np.floor((X[:, 0] + rng.normal(scale=0.5, size=n)) * 2) % K).astype(np.float64)
    params = dict(objective="multiclass", num_class=K, num_iterations=2, num_leaves=15, split_batch=8,
                  min_data_in_leaf=5, bagging_fraction=0.7, bagging_freq=1, seed=1, verbosity=0, **extra)
    asked = []
    monkeypatch.setattr(booster_mod, "class_roots_active", lambda cfg, k: asked.append(class_roots_active(cfg, k)) or asked[-1])
    booster_mod._SCAN_CACHE.clear()
    together = train(params, Dataset(X, y))._host_trees()
    monkeypatch.setattr(booster_mod, "class_roots_active", lambda cfg, k: False)
    booster_mod._SCAN_CACHE.clear()
    alone = train(params, Dataset(X, y))._host_trees()
    assert any(asked) == engaged
    assert np.asarray(alone.leaf_value).shape[:2] == (2, K)
    for name in alone._fields:
        np.testing.assert_array_equal(np.asarray(getattr(together, name)), np.asarray(getattr(alone, name)), err_msg=name)
