"""The replay scorer (``engine/tree.py`` ``_replay_leaf_ids`` /
``predict_tree_binned``) against a plain numpy walk of the same ``Tree``.

Routing is discrete and the leaf value is picked, not computed: leaf ids
must be equal and values equal to the bit.  The scorer runs under
``jax.vmap`` over the class axis everywhere it is called
(``Booster._forest_fn``, ``train()``'s validation scoring), so every case
is also walked as a stack of K = 3 trees.  The last tests hold the
mechanism itself: no per-row gather from a small table.
"""

import zlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mmlspark_tpu.engine.tree import (
    Tree,
    _replay_leaf_ids,
    predict_tree_binned,
)

N, F, L = 257, 5, 31  # rows, columns, leaves (S = L - 1 split steps)
WORD_EDGES = (0, 31, 32, 63, 64, 254, 255)  # bins beside the 32-bit words' edges


def _numpy_walk(tree, bins, num_bins):
    """Row by row, step by step: the semantics the scorer must keep."""
    t = Tree(*[np.asarray(a) for a in tree])
    leaf = np.zeros(bins.shape[0], np.int32)
    for i, row in enumerate(np.asarray(bins).astype(np.int64)):
        at = 0
        for s in range(t.split_leaf.shape[0]):
            if t.split_leaf[s] < 0 or at != t.split_leaf[s]:
                continue
            b = row[t.split_feat[s]]
            if t.split_cat[s]:
                left = bool(t.cat_threshold[s][b])
            elif b == num_bins - 1:
                left = bool(t.default_left[s])
            else:
                left = b <= t.split_bin[s]
            if not left:
                at = s + 1
        leaf[i] = at
    return leaf, t.leaf_value[leaf]


def _random_tree(rng, num_bins, kind, members="random", inactive=(), edges=False):
    """A seeded tree whose every step splits a leaf that exists by then.
    ``kind``: which steps are categorical; ``members``: their sets;
    ``inactive``: steps recorded as no-ops (``split_leaf`` -1); ``edges``:
    one-bin sets and thresholds on the 32-bit words' edges."""
    S = L - 1
    edge_bins = [b for b in WORD_EDGES if b < num_bins]
    split_leaf = np.array([rng.integers(0, s + 1) for s in range(S)], np.int32)
    split_leaf[list(inactive)] = -1
    is_cat = {
        "numeric": np.zeros(S, bool),
        "categorical": np.ones(S, bool),
        "mixed": rng.random(S) < 0.5,
    }[kind]
    if edges:
        split_bin = rng.choice(edge_bins, size=S).astype(np.int32)
        sets = np.zeros((S, num_bins), bool)
        sets[np.arange(S), rng.choice(edge_bins, size=S)] = True
    else:
        split_bin = rng.integers(0, num_bins - 1, size=S).astype(np.int32)
        sets = {
            "random": rng.random((S, num_bins)) < 0.5,
            "empty": np.zeros((S, num_bins), bool),
            "full": np.ones((S, num_bins), bool),
        }[members]
    leaf_value = rng.normal(size=L).astype(np.float32)
    leaf_value[rng.integers(0, L)] = -0.0  # a sum of zeros would lose the sign
    return Tree(
        split_leaf=split_leaf,
        split_feat=rng.integers(0, F, size=S).astype(np.int32),
        split_bin=split_bin,
        default_left=rng.random(S) < 0.5,
        split_cat=is_cat,
        cat_threshold=sets & is_cat[:, None],
        split_gain=np.zeros(S, np.float32),
        leaf_value=leaf_value,
        leaf_count=np.zeros(L, np.float32),
        num_leaves=np.int32(L),
    )


def _random_bins(rng, num_bins, edges=False):
    dtype = np.uint8 if num_bins <= 256 else np.int32  # as BinMapper.transform
    bins = rng.integers(0, num_bins, size=(N, F))
    if edges:
        bins = rng.choice([b for b in WORD_EDGES if b < num_bins], size=(N, F))
    bins[rng.random((N, F)) < 0.15] = num_bins - 1  # rows in the missing bin
    return bins.astype(dtype)


def _stack(trees):
    """Tree arrays (K, ...), as ``_forest_fn`` hands them to ``vmap``."""
    return Tree(*[jnp.stack([jnp.asarray(a) for a in field]) for field in zip(*trees)])


CASES = {
    f"{kind}-{num_bins}": dict(num_bins=num_bins, kind=kind)
    for num_bins in (16, 255, 256, 300)
    for kind in ("numeric", "categorical", "mixed")
} | {
    "inactive-steps": dict(num_bins=256, kind="mixed", inactive=(3, 7, 8, 29)),
    "all-inactive": dict(num_bins=256, kind="mixed", inactive=tuple(range(L - 1))),
    "empty-sets-255": dict(num_bins=255, kind="categorical", members="empty"),
    "empty-sets-256": dict(num_bins=256, kind="categorical", members="empty"),
    "full-sets-255": dict(num_bins=255, kind="categorical", members="full"),
    "full-sets-256": dict(num_bins=256, kind="categorical", members="full"),
    "word-edges-categorical": dict(num_bins=256, kind="categorical", edges=True),
    "word-edges-mixed": dict(num_bins=256, kind="mixed", edges=True),
    "word-edges-33-bins": dict(num_bins=33, kind="categorical", edges=True),
}


@pytest.mark.parametrize("stacked", [False, True], ids=["one-tree", "vmap-K3"])
@pytest.mark.parametrize("name", CASES)
def test_replay_matches_numpy_walk(name, stacked):
    case = dict(CASES[name])
    num_bins = case.pop("num_bins")
    rng = np.random.default_rng(zlib.crc32(name.encode()))
    bins = _random_bins(rng, num_bins, edges=case.get("edges", False))
    trees = [_random_tree(rng, num_bins, **case) for _ in range(3 if stacked else 1)]
    want = [_numpy_walk(t, bins, num_bins) for t in trees]
    stack, rows = _stack(trees), jnp.asarray(bins)
    if stacked:  # rows shared by the K trees
        ids = jax.jit(jax.vmap(lambda t: _replay_leaf_ids(t, rows, num_bins)))(stack)
        vals = jax.jit(jax.vmap(lambda t: predict_tree_binned(t, rows, num_bins)))(stack)
    else:
        tree = Tree(*[a[0] for a in stack])
        ids = jax.jit(_replay_leaf_ids, static_argnums=2)(tree, rows, num_bins)[None]
        vals = jax.jit(predict_tree_binned, static_argnums=2)(tree, rows, num_bins)[None]
    assert ids.dtype == jnp.int32 and vals.dtype == jnp.float32
    for k, (want_ids, want_vals) in enumerate(want):
        assert np.array_equal(np.asarray(ids[k]), want_ids)
        assert np.array_equal(np.asarray(vals[k]).view(np.uint32), want_vals.view(np.uint32))
    if "members" not in case and len(case.get("inactive", ())) < L - 1:
        assert len({int(i) for w, _ in want for i in w}) > 3, "the case reaches too few leaves"


# ---- the mechanism: no per-row read of a small table -----------------------
def _row_gathers_from_tables(jaxpr, n):
    """Gathers that yield a value per row from an operand with no row axis
    (a table); a column taken from the (n, F) matrix has one and is fine."""
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "gather":
            operand, out = eqn.invars[0].aval.shape, eqn.outvars[0].aval.shape
            if n in out and n not in operand:
                found.append((operand, out))
        for sub in jax.core.jaxprs_in_params(eqn.params):
            found += _row_gathers_from_tables(sub, n)
    return found


@pytest.mark.parametrize("stacked", [False, True], ids=["one-tree", "vmap-K3"])
def test_scorer_has_no_row_gather_from_a_table(stacked):
    num_bins = 256
    rng = np.random.default_rng(5)
    bins = jnp.asarray(_random_bins(rng, num_bins))
    stack = _stack([_random_tree(rng, num_bins, "categorical") for _ in range(3)])

    def scorer(predict):
        if stacked:
            return jax.make_jaxpr(jax.vmap(lambda t: predict(t, bins, num_bins)))(stack)
        return jax.make_jaxpr(lambda t: predict(t, bins, num_bins))(Tree(*[a[0] for a in stack]))

    assert _row_gathers_from_tables(scorer(predict_tree_binned).jaxpr, N) == []

    def with_tables(tree, bins, num_bins):  # what the guard is there to catch
        col = bins[:, tree.split_feat[0]].astype(jnp.int32)
        return tree.leaf_value[jnp.where(tree.cat_threshold[0][col], 1, 2)]

    tables = {operand[-1] for operand, _ in _row_gathers_from_tables(scorer(with_tables).jaxpr, N)}
    assert tables == {num_bins, L}
