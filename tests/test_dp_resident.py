"""The four-chip data-parallel retrain at a few thousand rows: a resident,
already row-sharded ``StreamedDataset`` trained with the benchmark cell's own
parameter set (63 leaves, 26 categorical columns of 39, ``split_batch`` 8,
lossguide, ``tree_learner=data``, ``hist_merge`` auto) on 4 of the 8 virtual
devices, its holdout scored sharded, and the executed merge counters.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from mmlspark_tpu import obs
from mmlspark_tpu.data.streaming import StreamedDataset
from mmlspark_tpu.engine import booster as booster_mod
from mmlspark_tpu.engine.booster import train
from mmlspark_tpu.engine.tree import GrowConfig, full_tree_passes
from mmlspark_tpu.ops.binning import BinningAuthority
from mmlspark_tpu.ops.histogram import build_histogram_by_leaf
from mmlspark_tpu.parallel import distributed
from mmlspark_tpu.parallel.mesh import DATA_AXIS, default_mesh

N, F, D = 4096, 39, 4
CAT = tuple(range(13, F))
PARAMS = dict(
    objective="binary", num_leaves=63, learning_rate=0.1, min_data_in_leaf=20, max_bin=255,
    categorical_feature=CAT, split_batch=8, predict_backend="scan", num_iterations=2,
)
STRUCTURE = ("split_leaf", "split_feat", "split_bin", "default_left", "split_cat", "cat_threshold", "num_leaves")


@pytest.fixture(scope="module")
def mesh():
    return default_mesh(D)


@pytest.fixture(scope="module")
def data():
    """Criteo's shape in small: 13 count columns with missing values, 26
    categorical ones, and labels balanced to the row, so that the first
    tree's gradients are +-0.5 and its hessians 0.25."""
    rng = np.random.default_rng(27)
    X = np.floor(np.exp(rng.normal(size=(N, F))))
    X[:, 13:] = rng.integers(0, 40, size=(N, 26))
    X[rng.random((N, F)) < 0.1] = np.nan
    score = np.nan_to_num(X[:, 0]) + (np.nan_to_num(X[:, 20]) % 2) + rng.normal(size=N)
    y = np.zeros(N)
    y[np.argsort(score)[N // 2:]] = 1.0
    authority = BinningAuthority.fit(X, max_bin=255, categorical_features=CAT, seed=0)
    return authority, authority.mapper.transform(X).astype(np.uint8), y


def _resident(data, sharding=None):
    authority, bins, y = data
    dev = jnp.asarray(bins) if sharding is None else jax.device_put(bins, sharding)
    return StreamedDataset(authority=authority, binned_dev=dev, packed=False, num_rows=N, num_features=F, label=y)


@pytest.fixture(scope="module")
def fits(data, mesh):
    """One serial fit, then two data-parallel fits of one resident sharded
    data set (the second runs the cached program), with what each counted."""
    obs.reset()
    obs.flight.reset()  # the ring keeps spans while obs is off: earlier tests' fits are in it
    obs.enable()
    try:
        counters = [dict(obs.snapshot()["counters"])]
        serial = train(PARAMS, _resident(data))
        counters.append(dict(obs.snapshot()["counters"]))
        ds = _resident(data, NamedSharding(mesh, P(DATA_AXIS, None)))
        first = train({**PARAMS, "tree_learner": "data"}, ds, mesh=mesh)
        counters.append(dict(obs.snapshot()["counters"]))
        second = train({**PARAMS, "tree_learner": "data"}, ds, mesh=mesh)
        counters.append(dict(obs.snapshot()["counters"]))
        spans = obs.flight.spans()
    finally:
        obs.disable()
    rises = [
        {k: after[k] - before.get(k, 0.0) for k in after if after[k] != before.get(k, 0.0)}
        for before, after in zip(counters, counters[1:])
    ]
    return {"serial": serial, "first": first, "second": second, "ds": ds, "rises": rises, "spans": spans}


# ---- (i) the sharded resident fit grows the serial fit's trees --------------
def test_resolves_to_the_cells_path(fits):
    cfg = fits["first"].config
    assert (cfg.tree_learner, cfg.hist_merge, cfg.split_batch, cfg.grow_policy) == ("data", "reduce_scatter", 8, "lossguide")
    assert fits["serial"].config.hist_merge == "allreduce"


@pytest.mark.parametrize("field", STRUCTURE)
def test_first_tree_is_the_serial_fits(fits, field):
    # exact: with balanced labels the init score is 0, every gradient +-0.5 and
    # every hessian 0.25, so each partial sum is exact in float32 and the order
    # in which four shards add up cannot move a gain or a category's rank
    s, d = fits["serial"]._host_trees(), fits["first"]._host_trees()
    np.testing.assert_array_equal(np.asarray(getattr(s, field))[0], np.asarray(getattr(d, field))[0])


def test_first_tree_splits_on_categories_and_fills_its_leaves(fits):
    t = fits["first"]._host_trees()
    assert int(np.asarray(t.num_leaves)[0, 0]) == 63
    assert np.asarray(t.split_cat)[0].any() and not np.asarray(t.split_cat)[0].all()
    assert (np.asarray(t.split_feat)[0] < F).all()  # the slot the merge pads in is never chosen
    np.testing.assert_array_equal(np.asarray(t.leaf_count)[0].sum(axis=-1), [N])


def test_leaf_values_and_predictions_within_the_reduce_scatter_tolerance(fits, data):
    s, d = fits["serial"]._host_trees(), fits["first"]._host_trees()
    np.testing.assert_allclose(np.asarray(d.leaf_value)[0], np.asarray(s.leaf_value)[0], rtol=1e-6, atol=1e-7)
    np.testing.assert_array_equal(np.asarray(d.leaf_count)[0], np.asarray(s.leaf_count)[0])
    # from the second tree on gradients are arbitrary floats, a near-tie may
    # flip with the summation order, and the gate is TestReduceScatterMerge's
    bins = jnp.asarray(data[1])
    ps, pd = (np.asarray(b._raw_scores_binned(bins)) for b in (fits["serial"], fits["first"]))
    assert np.mean(np.abs(ps - pd)) < 1e-3


def test_no_binned_byte_leaves_the_host(fits):
    # labels as float32, the mask as bool: five bytes a row, sent by the first
    # fit of a data set and kept on the devices with it (the init scores, a
    # constant, are made on the devices)
    assert fits["rises"][1]["train.upload_bytes"] == N * (4 + 1)
    assert "train.upload_bytes" not in fits["rises"][2]  # the second fit sends nothing at all
    assert fits["rises"][1]["train.row_state{result=miss}"] == 1 and fits["rises"][2].get("train.row_state{result=hit}") == 1
    ds = fits["ds"]
    (kept,) = ds._dev_bins_cache.values()
    assert kept is ds._binned_dev  # neither fetched, padded nor placed again
    assert kept.shape == (N, F)


def test_spans_say_where_the_rows_live(fits):
    by_name = {}
    for s in fits["spans"]:
        by_name.setdefault(s["name"], []).append(s["attrs"])
    assert [(a["devices"], a["sharded"]) for a in by_name["booster.upload"]] == [(1, False), (D, True), (D, True)]
    assert [(a["devices"], a["hist_merge"]) for a in by_name["booster.program"]] == [
        (1, "none"), (D, "reduce_scatter"), (D, "reduce_scatter"),
    ]
    assert [a["scan_cache_hit"] for a in by_name["booster.program"]] == [False, False, True]


# ---- (ii) the shards' histograms add up to the whole -------------------------
def test_shard_histograms_add_up_to_the_serial_histogram(data, mesh):
    _, bins, y = data
    rng = np.random.default_rng(5)
    W, B = 8, 256
    leaf = rng.integers(-2, W, size=N).astype(np.int32)  # some rows parked outside the window
    vals = np.stack([y - 0.5, np.full(N, 0.25), np.ones(N)]).astype(np.float32)
    hist = lambda b, v, l, **kw: build_histogram_by_leaf(  # noqa: E731
        jnp.asarray(b.T), jnp.asarray(v), jnp.asarray(l), W, B, backend="scatter", **kw
    )
    whole = np.asarray(hist(bins, vals, leaf))
    rows = N // D
    parts = [np.asarray(hist(bins[c * rows:(c + 1) * rows], vals[:, c * rows:(c + 1) * rows], leaf[c * rows:(c + 1) * rows])) for c in range(D)]
    np.testing.assert_array_equal(sum(parts)[2], whole[2])  # counts, exactly
    np.testing.assert_array_equal(sum(parts), whole)  # +-0.5 and 0.25 add exactly too
    assert 0 < parts[0][2].sum() < whole[2].sum()

    # and through the program's own merge: each chip receives its feature
    # slice of the whole, 39 columns scattered as 40 with the last slot empty
    merged = jax.shard_map(
        lambda b, v, l: build_histogram_by_leaf(
            b.T, v, l, W, B, backend="scatter", axis_name=DATA_AXIS, merge="reduce_scatter",
        ),
        mesh=mesh, in_specs=(P(DATA_AXIS, None), P(None, DATA_AXIS), P(DATA_AXIS)), out_specs=P(None, None, DATA_AXIS, None),
        check_vma=False,
    )(jnp.asarray(bins), jnp.asarray(vals), jnp.asarray(leaf))
    merged = np.asarray(merged)
    assert merged.shape == (3, W, 40, B)
    np.testing.assert_array_equal(merged[:, :, :F], whole)
    assert not merged[:, :, F:].any()


# ---- (iii) the sharded holdout ------------------------------------------------
def test_sharded_holdout_scores_equal_single_device_to_the_bit(fits, data, mesh):
    booster, bins = fits["first"], data[1][:2048]
    one = booster._raw_scores_binned(jnp.asarray(bins))
    sharded = booster._raw_scores_binned(jax.device_put(bins, NamedSharding(mesh, P(DATA_AXIS, None))))
    assert len(sharded.sharding.device_set) == D and not sharded.sharding.is_fully_replicated
    np.testing.assert_array_equal(np.asarray(one), np.asarray(sharded))


# ---- (iv) the executed merge counters ------------------------------------------
def _merge(rise, counter):
    return {k.split("op=")[1].rstrip("}"): v for k, v in rise.items() if k.startswith(counter + "{")}


def test_merge_counters_count_what_ran(fits):
    serial, first, second = fits["rises"]
    assert not _merge(serial, "train.merge_bytes") and not _merge(serial, "train.merge_calls")
    iters, passes = PARAMS["num_iterations"], 10  # 1, 2, 4, then 8 splits a pass: 63 leaves in 10
    # the root's histogram and one window a pass: 3 channels x 8 slots x 10 of
    # the 40 scattered columns x 256 bins of float32
    site = 3 * 8 * 10 * 256 * 4
    got = _merge(first, "train.merge_bytes")
    assert got["reduce_scatter"] == site * (1 + passes) * iters
    assert _merge(first, "train.merge_calls")["reduce_scatter"] == (1 + passes) * iters
    assert got["all_gather"] == D * 5 * 63 * 4 * passes * iters  # the winner exchange, once a pass
    assert set(got) == {"reduce_scatter", "all_gather", "psum"}
    # the second fit ran the cached program: it traced nothing and counts the same
    assert fits["spans"] and _merge(second, "train.merge_bytes") == got
    assert _merge(second, "train.merge_calls") == _merge(first, "train.merge_calls")
    assert not any(k.startswith("collective.") for k in second)
    # the trace-time counters ticked once a site in the first fit, whatever ran
    assert first["collective.calls{name=reduce_scatter}"] == 2
    assert first["collective.bytes{name=reduce_scatter}"] == 2 * site


def test_merge_counters_stay_off_with_recording_off(data, mesh):
    before = dict(obs.snapshot()["counters"])
    train({**PARAMS, "tree_learner": "data", "num_iterations": 1}, _resident(data, NamedSharding(mesh, P(DATA_AXIS, None))), mesh=mesh)
    assert dict(obs.snapshot()["counters"]) == before


# ---- the pieces ---------------------------------------------------------------
@pytest.mark.parametrize("leaves, batch, passes", [(63, 8, 10), (63, 0, 6), (15, 1, 14), (2, 8, 1), (31, 4, 9)])
def test_full_tree_passes(leaves, batch, passes):
    assert full_tree_passes(GrowConfig(num_bins=256, num_leaves=leaves, split_batch=batch)) == passes


def test_collective_ledger_reads_loops_and_branches(mesh):
    from jax import lax

    def local(x):  # x: (8, 4) a shard
        a = lax.psum(x, DATA_AXIS)  # 128 bytes, once
        b, _ = lax.scan(lambda c, _: (c + lax.psum_scatter(x, DATA_AXIS, scatter_dimension=0, tiled=True), None), jnp.zeros((2, 4)), None, length=3)
        c = lax.while_loop(lambda s: s[0] < 5, lambda s: (s[0] + 1, s[1] + lax.all_gather(x, DATA_AXIS).sum(0)), (0, x))[1]
        d = lax.cond(x[0, 0] > 0, lambda: lax.psum(x[:2], DATA_AXIS), lambda: x[:2])
        return a.sum() + b.sum() + c.sum() + d.sum()

    f = jax.shard_map(local, mesh=mesh, in_specs=P(DATA_AXIS, None), out_specs=P(), check_vma=False)
    ledger = distributed.collective_ledger(jax.make_jaxpr(f)(jnp.ones((32, 4))), while_trips=7)
    assert ledger == {
        "psum": (2, 128 + 32),  # the plain one and the branch that has one
        "reduce_scatter": (3, 3 * 32),  # a scan multiplies by its length
        "all_gather": (7, 7 * 4 * 128),  # a while loop by the trips it is told
    }


@pytest.mark.parametrize("cols, scattered", [(39, 40), (40, 40), (5, 8)])
def test_psum_scatter_pads_the_histogram_not_the_rows(mesh, cols, scattered):
    x = np.arange(D * 2 * cols, dtype=np.float32).reshape(D * 2, cols)
    out = jax.shard_map(
        lambda a: distributed.device_psum_scatter(a, DATA_AXIS, scatter_dimension=1),
        mesh=mesh, in_specs=P(DATA_AXIS, None), out_specs=P(None, DATA_AXIS), check_vma=False,
    )(jnp.asarray(x))
    assert out.shape == (2, scattered)
    np.testing.assert_array_equal(np.asarray(out)[:, :cols], x.reshape(D, 2, cols).sum(axis=0))
    assert not np.asarray(out)[:, cols:].any()


def test_merge_ledger_is_kept_in_the_cached_programs_entry(fits):
    ledgers = [notes.get("merge_ledger") for _, notes in booster_mod._SCAN_CACHE.values()]
    assert any(ledger and "reduce_scatter" in ledger for ledger in ledgers)


def test_full_tree_passes_is_the_growers_own_trip_count(data, monkeypatch):
    """``full_tree_passes`` restates the windowed grower's schedule: held
    here to the trips its ``while_loop`` makes for a tree that fills up."""
    from jax import lax

    from mmlspark_tpu.engine import tree as tree_mod

    trips = [0]

    class CountingLax:  # tree.py's ``lax``, its one while_loop counted; no other module sees it
        def __getattr__(self, name):
            return getattr(lax, name)

        @staticmethod
        def while_loop(cond, body, init):
            def counted(carry):
                jax.debug.callback(lambda: trips.__setitem__(0, trips[0] + 1))
                return body(carry)

            return lax.while_loop(cond, counted, init)

    monkeypatch.setattr(tree_mod, "lax", CountingLax())
    _, bins, y = data
    cfg = GrowConfig(
        num_bins=256, num_leaves=63, min_data_in_leaf=20, split_batch=8, grow_policy="lossguide",
        categorical_features=CAT, max_cat_threshold=255, cat_value_bins=255,
    )
    grad = jnp.asarray(0.5 - y, jnp.float32)
    tree, _ = jax.jit(lambda b, g: tree_mod.grow_tree_auto(cfg, b, g, jnp.full(N, 0.25), jnp.ones(N), jnp.ones(F, bool)))(jnp.asarray(bins), grad)
    jax.effects_barrier()
    assert int(tree.num_leaves) == 63
    assert trips[0] == full_tree_passes(cfg) == 10
