"""A fit's per-row state (labels, weights, row mask, init scores) is made once
for a data set and kept on the device beside its binned matrix
(``booster._fit_row_state``, ``Dataset._row_state_cache``): a second
``train()`` finds it and sends nothing; whatever the arrays depend on misses
when it changes; a write into the host arrays it was made from raises.
"""

import pickle

import numpy as np
import pytest

import jax.numpy as jnp

from mmlspark_tpu import obs
from mmlspark_tpu.data.streaming import StreamedDataset
from mmlspark_tpu.engine.booster import Dataset, train
from mmlspark_tpu.ops.binning import BinningAuthority
from mmlspark_tpu.parallel.mesh import default_mesh

N, F = 600, 5
PARAMS = dict(objective="binary", num_iterations=3, num_leaves=7, min_data_in_leaf=4, verbosity=-1)


def _arrays(seed=0):
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(N, F))
    y = (X[:, 0] + 0.5 * X[:, 1] + 0.3 * rng.normal(size=N) > 0.4).astype(np.float64)
    return X, y, rng.uniform(0.5, 2.0, size=N), 0.1 * rng.normal(size=N)


def _plain(y=None, weight=None, init_score=None):
    X, y0, _, _ = _arrays()
    return Dataset(X, (y0 if y is None else y).copy(), weight=weight, init_score=init_score)


def _streamed(y=None, weight=None, init_score=None):
    X, y0, _, _ = _arrays()
    authority = BinningAuthority.fit(X, max_bin=255, seed=0)
    ds = StreamedDataset(
        authority=authority, binned_dev=jnp.asarray(authority.mapper.transform(X).astype(np.uint8)), packed=False,
        num_rows=N, num_features=F, label=(y0 if y is None else y).copy(), weight=weight,
    )
    ds.init_score = init_score
    return ds


@pytest.fixture
def counted():
    """``fit(params, ds, **kw)`` -> ``(booster, what the fit counted)``."""
    obs.reset()
    obs.flight.reset()
    obs.enable()

    def fit(params, ds, **kw):
        before = dict(obs.snapshot()["counters"])
        booster = train(params, ds, **kw)
        after = obs.snapshot()["counters"]
        rise = {k: after[k] - before.get(k, 0.0) for k in after}
        return booster, {
            "sent": rise.get("train.upload_bytes", 0.0),
            "hit": rise.get("train.row_state{result=hit}", 0.0),
            "miss": rise.get("train.row_state{result=miss}", 0.0),
            "rows_cached": obs.flight.spans("booster.upload")[-1]["attrs"]["rows_cached"],
        }

    yield fit
    obs.disable()
    obs.reset()


def _held(ds):
    (entry,) = ds._row_state_cache.values()  # one entry, replaced and never added to
    return entry[1]


@pytest.mark.parametrize("make", [_plain, _streamed], ids=["Dataset", "StreamedDataset"])
def test_second_fit_sends_nothing_and_grows_the_same_model(counted, make):
    ds = make()
    first, c1 = counted(PARAMS, ds)
    # the labels (4 bytes a row) and the row mask (1); the init scores are made on the device
    assert (c1["hit"], c1["miss"], c1["rows_cached"]) == (0, 1, False) and c1["sent"] >= 5 * N
    state = _held(ds)
    second, c2 = counted(PARAMS, ds)
    assert c2 == {"sent": 0, "hit": 1, "miss": 0, "rows_cached": True}
    again = _held(ds)
    assert all(a is b for a, b in zip(state, again))  # the very arrays the first fit made
    assert second.save_model_string() == first.save_model_string()
    assert train(PARAMS, make()).save_model_string() == first.save_model_string()


def _other_labels(ds):
    y = ds.label.copy()
    y[:40] = 1.0 - y[:40]
    ds.label = y
    return {}, dict(y=y)


def _other_weight(ds):
    ds.weight = ds.weight[::-1].copy()
    return {}, dict(weight=ds.weight.copy())


def _other_init_score(ds):
    ds.init_score = -ds.init_score
    return {}, dict(init_score=ds.init_score.copy())


MISSES = {
    "new_label": _other_labels,
    "new_weight": _other_weight,
    "new_init_score": _other_init_score,
    "is_unbalance": lambda ds: ({"is_unbalance": True}, {}),
    "scale_pos_weight": lambda ds: ({"scale_pos_weight": 3.0}, {}),
    "objective": lambda ds: ({"objective": "regression"}, {}),
    "objective_param": lambda ds: ({"sigmoid": 2.0}, {}),
    "boosting_rf": lambda ds: ({"boosting": "rf", "bagging_freq": 1, "bagging_fraction": 0.7}, {}),
    "mesh": lambda ds: ({"tree_learner": "data", "mesh": default_mesh(8)}, {}),
    "hist_chunk": lambda ds: ({"hist_chunk": 256}, {}),  # 600 rows pad to three chunks: n_pad 0 -> 168
}


@pytest.mark.parametrize("what", list(MISSES))
def test_a_fit_that_depends_on_something_else_misses(counted, what):
    _, _, w, s = _arrays()
    # a set with an init score never folds the label mean in: the cases that
    # turn on the init score's own settings start without one
    with_init = what in ("new_init_score", "new_weight", "new_label")
    base = dict(weight=w.copy(), init_score=s.copy() if with_init else None)
    ds = _plain(**base)
    _, c0 = counted(PARAMS, ds)
    assert c0["miss"] == 1
    changed, arrays = MISSES[what](ds)
    mesh = changed.pop("mesh", None)
    params = {**PARAMS, **changed}
    got, c1 = counted(params, ds, mesh=mesh)
    assert (c1["hit"], c1["miss"], c1["rows_cached"]) == (0, 1, False) and c1["sent"] > 0
    assert len(ds._row_state_cache) == 1  # the old entry went: the device holds one copy
    fresh = train(params, _plain(**{**base, **arrays}), mesh=mesh)
    assert got.save_model_string() == fresh.save_model_string()
    # the entry now stands for the new arrays and settings: the next such fit finds it
    _, c2 = counted(params, ds, mesh=mesh)
    assert (c2["hit"], c2["sent"]) == (1, 0)


@pytest.mark.parametrize("name", ["label", "weight", "init_score"])
@pytest.mark.parametrize("make", [_plain, _streamed], ids=["Dataset", "StreamedDataset"])
def test_a_write_into_a_held_array_raises(make, name):
    _, _, w, s = _arrays()
    ds = make(weight=w, init_score=s)  # float64 arrays are taken as they are: the caller's own
    assert getattr(ds, name).flags.writeable
    train(PARAMS, ds)
    with pytest.raises(ValueError, match="read-only"):
        getattr(ds, name)[3] = 0.5
    if name != "label":
        with pytest.raises(ValueError, match="read-only"):
            (w if name == "weight" else s)[3] = 0.5
    # the way to change one: a new array, which the next fit sees
    setattr(ds, name, getattr(ds, name) * 1.0)
    getattr(ds, name)[3] = 0.5


def test_init_model_on_a_hit_is_init_model_on_a_miss(counted):
    ds = _plain()
    base, _ = counted(PARAMS, ds)
    on_miss, c1 = counted(PARAMS, ds, init_model=base)  # no bias folding under a warm start: a new entry
    on_hit, c2 = counted(PARAMS, ds, init_model=base)
    assert (c1["miss"], c2["hit"], c2["sent"]) == (1, 1, 0)
    held = _held(ds)
    # the entry holds the init score (0.0 under a warm start), not the scores with the old forest's added
    assert held.init_scores is None and held.init == 0.0
    assert on_hit.num_iterations == 2 * PARAMS["num_iterations"]
    assert on_hit.save_model_string() == on_miss.save_model_string()
    fresh = train(PARAMS, _plain(), init_model=base)
    assert on_hit.save_model_string() == fresh.save_model_string()


def test_a_pickled_data_set_comes_back_with_no_entry(counted):
    ds = _plain()
    first, _ = counted(PARAMS, ds)
    assert ds._row_state_cache
    back = pickle.loads(pickle.dumps(ds))
    assert back._row_state_cache == {} and back._dev_bins_cache == {}
    again, c = counted(PARAMS, back)
    assert (c["hit"], c["miss"]) == (0, 1)
    assert again.save_model_string() == first.save_model_string()


def test_a_multiclass_fit_hits_and_matches(counted):
    rng = np.random.default_rng(5)
    X = rng.normal(size=(N, F))
    y = np.clip(np.round(X[:, 0] + X[:, 1] + 1.0), 0, 2)
    params = dict(PARAMS, objective="multiclass", num_class=3)
    ds = Dataset(X, y)
    first, c1 = counted(params, ds)
    second, c2 = counted(params, ds)
    assert _held(ds).init_scores is None and np.shape(_held(ds).init) == (3,)
    assert (c1["miss"], c2["hit"], c2["sent"]) == (1, 1, 0)
    assert second.save_model_string() == first.save_model_string()
    assert train(params, Dataset(X, y.copy())).save_model_string() == first.save_model_string()


def test_a_multi_controller_fit_keeps_nothing(counted):
    # its label statistics are collectives every process enters in every fit
    ds = _plain()
    params = dict(PARAMS, tree_learner="data")
    _, c1 = counted(params, ds, process_local=True)
    _, c2 = counted(params, ds, process_local=True)
    assert ds._row_state_cache == {} and ds.label.flags.writeable
    assert (c1["miss"], c2["miss"], c2["hit"], c2["rows_cached"]) == (1, 1, 0, False)
    assert c2["sent"] >= 9 * N


def test_a_set_init_score_is_held_and_outlives_the_fits(counted):
    """With an ``init_score`` the entry holds the ``(K, n)`` scores, and the
    program, which overwrites the carry that goes in, gets a copy of them."""
    _, _, _, s = _arrays()
    ds = _plain(init_score=s)
    first, c1 = counted(PARAMS, ds)
    second, c2 = counted(PARAMS, ds)
    held = _held(ds).init_scores
    assert (c1["miss"], c2["hit"], c2["sent"]) == (1, 1, 0)
    assert not held.is_deleted()
    np.testing.assert_array_equal(np.asarray(held)[0, :N], s.astype(np.float32))
    assert second.save_model_string() == first.save_model_string()


@pytest.mark.parametrize("exported", [False, True], ids=["jit", "exported"])
def test_the_program_overwrites_the_scores_it_starts_from(monkeypatch, tmp_path, exported):
    """The whole-run program takes over the fit's first scores, made on the
    device for it alone: no second ``(K, n)`` buffer beside its result.
    Through the trace cache's exported program too."""
    import mmlspark_tpu.engine.booster as bo
    from mmlspark_tpu.core import trace_cache as tc

    made = []
    real = bo._constant_rows
    monkeypatch.setattr(bo, "_constant_rows", lambda *a: made.append(real(*a)) or made[-1])
    if exported:
        monkeypatch.delenv("MMLSPARK_TPU_NO_TRACE_CACHE", raising=False)
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "traces"))
        monkeypatch.setattr(bo, "_TRACE_CACHE_MIN_WORK", 0)
        tc._EXP_MEMO.clear()
    else:
        monkeypatch.setenv("MMLSPARK_TPU_NO_TRACE_CACHE", "1")
    params = dict(PARAMS, objective="multiclass", num_class=3)
    X, y, _, _ = _arrays()
    ds = Dataset(X, np.clip(np.round(X[:, 0] + 1.0), 0, 2))
    first = train(params, ds)
    second = train(params, ds)
    assert len(made) == 2 and all(a.is_deleted() for a in made)
    assert second.save_model_string() == first.save_model_string()
    if exported:
        assert list((tmp_path / "traces").glob("*.jaxexp"))

