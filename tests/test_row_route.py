"""The windowed grower's rows moved by one read of each row block
(``ops/pallas_route.py``, through the interpreted kernel on the CPU) against
the slot loop (``engine/tree.py`` ``_route_slots``): the leaf ids equal to
the bit, and a whole fit's trees equal in every field."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mmlspark_tpu.engine import tree as tree_mod
from mmlspark_tpu.engine.booster import route_ledger
from mmlspark_tpu.engine.tree import GrowConfig, PassSplits, grow_tree_depthwise, route_in_block
from mmlspark_tpu.ops.pallas_route import MAX_COLS

B, L, W = 256, 63, 8


def _pass(rng, F, n, kind, k, dleft="mixed", base=24):
    """A pass as the grower makes it: ``k`` of the W gain-ranked slots split
    (leaves below ``base``, their right children ``base``, ``base + 1``, ...),
    the other slots' leaves anywhere in ``[0, L)``; rows in leaves below
    ``base`` and, a sixth of them, parked at or above it; a tenth of the
    bins the missing bin."""
    bins = rng.integers(0, B - 1, size=(F, n))
    bins[rng.uniform(size=(F, n)) < 0.1] = B - 1
    order = np.concatenate([rng.permutation(base)[:k], rng.permutation(np.arange(base, L))[: W - k]])
    slot_leaves = order[:W]
    selected = np.zeros(L, bool)
    selected[slot_leaves[:k]] = True
    new_id = np.full(L, L, np.int32)  # an unselected leaf's step is the scratch slot
    new_id[slot_leaves[:k]] = base + np.arange(k)
    is_cat = {"numeric": np.zeros(L, bool), "categorical": np.ones(L, bool), "mixed": rng.uniform(size=L) < 0.5}[kind]
    left = {"on": np.ones(L, bool), "off": np.zeros(L, bool), "mixed": rng.uniform(size=L) < 0.5}[dleft]
    members = rng.uniform(size=(L, B)) < 0.4
    members[:, B - 1] = False  # the missing bin is never a member
    members &= (selected & is_cat)[:, None]
    ids = rng.integers(0, base, size=n)
    parked = rng.uniform(size=n) < 1 / 6
    ids[parked] = rng.integers(base, L + 8, size=parked.sum())
    splits = PassSplits(
        jnp.asarray(rng.integers(0, F, size=L), jnp.int32), jnp.asarray(rng.integers(0, B, size=L), jnp.int32),
        jnp.asarray(left), jnp.asarray(is_cat), jnp.asarray(selected), jnp.asarray(new_id),
        None if kind == "numeric" else jnp.asarray(members),
    )
    return jnp.asarray(bins, jnp.uint8), jnp.asarray(ids, jnp.int32), jnp.asarray(slot_leaves, jnp.int32), splits


def _both(bins, ids, slot_leaves, splits, **blocks):
    want = tree_mod._route_slots(bins, ids, slot_leaves, splits, B)
    got = tree_mod._route_block(bins, ids, slot_leaves, splits, B, **blocks)
    return np.asarray(got), np.asarray(want)


@pytest.mark.parametrize("kind", ["numeric", "categorical", "mixed"])
@pytest.mark.parametrize("F", [22, 39, MAX_COLS - 1])
def test_block_route_equals_the_slot_loop(kind, F):
    rng = np.random.default_rng(F + len(kind))
    bins, ids, slot_leaves, splits = _pass(rng, F, 3000, kind, k=W)
    got, want = _both(bins, ids, slot_leaves, splits)
    np.testing.assert_array_equal(got, want)
    moved = want != np.asarray(ids)
    assert moved.any() and (~moved).any()
    assert (want[moved] >= 24).all()  # every move lands on a new right child


@pytest.mark.parametrize("dleft", ["on", "off"])
@pytest.mark.parametrize("kind", ["numeric", "mixed"])
def test_the_missing_bin_goes_the_default_way(kind, dleft):
    rng = np.random.default_rng(7)
    bins, ids, slot_leaves, splits = _pass(rng, 39, 2000, kind, k=W, dleft=dleft)
    got, want = _both(bins, ids, slot_leaves, splits)
    np.testing.assert_array_equal(got, want)
    # the rows of a numeric split leaf whose bin is missing: all stay (left) or all move
    l0 = int(slot_leaves[0])
    rows = (np.asarray(ids) == l0) & (np.asarray(bins)[int(splits.feat[l0])] == B - 1)
    if not bool(splits.is_cat[l0]) and rows.any():
        assert (got[rows] == l0).all() == (dleft == "on")


@pytest.mark.parametrize("k", [0, 1, 5])
def test_unselected_slots_and_fewer_than_w_splits_move_nothing_of_theirs(k):
    rng = np.random.default_rng(k)
    bins, ids, slot_leaves, splits = _pass(rng, 39, 2000, "mixed", k=k)
    got, want = _both(bins, ids, slot_leaves, splits)
    np.testing.assert_array_equal(got, want)
    idle = np.isin(np.asarray(ids), np.asarray(slot_leaves[k:]))
    assert idle.any() and (got[idle] == np.asarray(ids)[idle]).all()
    assert (got != np.asarray(ids)).any() == (k > 0)


def test_ids_parked_at_or_above_base_stay():
    rng = np.random.default_rng(3)
    bins, ids, slot_leaves, splits = _pass(rng, 22, 2000, "mixed", k=W)
    got, want = _both(bins, ids, slot_leaves, splits)
    np.testing.assert_array_equal(got, want)
    parked = np.asarray(ids) >= 24
    assert parked.any() and (got[parked] == np.asarray(ids)[parked]).all()


def test_a_ragged_last_block():
    rng = np.random.default_rng(11)
    n = 5000  # four blocks of 1,024 rows and a ragged fifth
    bins, ids, slot_leaves, splits = _pass(rng, 39, n, "mixed", k=W)
    got, want = _both(bins, ids, slot_leaves, splits, bm=1024, rm=512)
    np.testing.assert_array_equal(got, want)
    assert (want[4096:] != np.asarray(ids)[4096:]).any()  # the ragged block's rows moved too


def test_the_block_route_engages_by_backend_and_columns():
    matrix = lambda F, dtype=jnp.uint8: jax.ShapeDtypeStruct((F, 4096), dtype)  # noqa: E731
    pallas, scatter = GrowConfig(num_bins=B, hist_backend="pallas"), GrowConfig(num_bins=B, hist_backend="scatter")
    assert route_in_block(pallas, matrix(39))
    assert route_in_block(pallas, matrix(MAX_COLS))
    assert not route_in_block(pallas, matrix(MAX_COLS + 1))
    assert not route_in_block(pallas, matrix(220))
    assert not route_in_block(pallas, matrix(39, jnp.int32))  # past the byte tier: the slot loop
    assert not route_in_block(scatter, matrix(39))


def test_a_fit_of_more_than_256_bins_keeps_the_slot_loop():
    """Past the byte tier the grower's matrix is int32, and its program
    holds no call of the route body at any width."""
    rng = np.random.default_rng(2)
    n, F, bins_n = 2048, 39, 512
    bins = jnp.asarray(rng.integers(0, bins_n, size=(n, F)), jnp.int32)
    cfg = GrowConfig(num_bins=bins_n, num_leaves=15, split_batch=8, min_data_in_leaf=5, hist_backend="pallas")
    vals = [jnp.asarray(rng.normal(size=n), jnp.float32), jnp.ones(n, jnp.float32), jnp.ones(n, jnp.float32)]
    jaxpr = jax.make_jaxpr(lambda *a: grow_tree_depthwise(cfg, *a))(bins, *vals, jnp.ones(F, bool))
    routes = route_ledger(jaxpr)
    assert routes["block"] == 0 and routes["slots"] > 0


def test_a_whole_fit_grows_the_same_trees_with_either_route(monkeypatch):
    """``grow_tree_depthwise`` on the Pallas interpret backend at F = 39 with
    26 categorical columns: the tree and the leaf ids equal in every field
    with the block route and with the slot loop."""
    rng = np.random.default_rng(5)
    n, F = 4096, 39
    bins = rng.integers(0, 200, size=(n, F)).astype(np.uint8)
    bins[rng.uniform(size=(n, F)) < 0.05] = B - 1
    signal = np.sin(bins[:, 0] / 20.0) + (bins[:, 20] % 7 == 3) - (bins[:, 30] % 5 == 1) + 0.3 * rng.normal(size=n)
    grad = jnp.asarray(-signal, jnp.float32)
    hess = jnp.ones(n, jnp.float32)
    bag = jnp.ones(n, jnp.float32)
    cfg = GrowConfig(
        num_bins=B, num_leaves=31, split_batch=8, min_data_in_leaf=5, hist_backend="pallas",
        categorical_features=tuple(range(13, F)),
    )
    args = (jnp.asarray(bins), grad, hess, bag, jnp.ones(F, bool))

    def fit():  # a function of its own each time: jax caches a trace by the function
        grow = lambda *a: grow_tree_depthwise(cfg, *a)  # noqa: E731
        return "_pallas_route" in str(jax.make_jaxpr(grow)(*args)), jax.jit(grow)(*args)

    engaged, block = fit()
    assert engaged
    monkeypatch.setattr(tree_mod, "_route_block", tree_mod._route_slots)
    engaged, slots = fit()
    assert not engaged
    (tree_b, ids_b), (tree_s, ids_s) = block, slots
    for name, a, b in zip(tree_b._fields, tree_b, tree_s):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b), err_msg=name)
    np.testing.assert_array_equal(np.asarray(ids_b), np.asarray(ids_s))
    assert int(tree_b.num_leaves) == 31
    used = np.asarray(tree_b.split_leaf) >= 0
    cat = np.asarray(tree_b.split_cat)[used]
    assert cat.any() and (~cat).any()  # categorical and numeric splits both moved rows
