"""CPU tests of the multiclass cell's own files: the data module
``data/expedia.py``, the traffic kind ``train_loop_multiclass``, the cell's
declaration and the ``class_*`` readers.  The end-to-end cases run the
runner with its look for a chip skipped, at 8,192 rows."""

import argparse
import json

import numpy as np
import pytest

from benchmark import run as runner
from benchmark.data import expedia
from benchmark.dataset import seed_key
from benchmark.metrics import _class, class_grad_share_pct, class_tree_s, class_update_share_pct
from benchmark.traffic import train_loop_multiclass

CELL = "expedia_multiclass_train_1chip"
TINY = {"rows": 8192, "holdout_rows": 4096, "chunk_rows": 4096, "bin_sample_rows": 4096}
READERS = (class_grad_share_pct, class_update_share_pct, class_tree_s)


@pytest.fixture()
def tiny_cell(monkeypatch):
    orig = runner.load_cell

    def load(name):
        bench, cell, cfg, workload = orig(name)
        cfg = dict(cfg, **TINY)
        cfg["params"] = dict(cfg["params"], num_leaves=7)
        return bench, cell, cfg, workload

    monkeypatch.setattr(runner, "load_cell", load)


def _args(**kw):
    return argparse.Namespace(**dict(dict(workload=CELL, seed=2**31 + 13, seconds=0.0, trace=0), **kw))


def test_chunks_are_a_function_of_the_key_and_index():
    import jax

    chunk = jax.jit(expedia.chunk, static_argnums=2)
    X, y = chunk(seed_key(2**31 + 13), 3, 4096)
    X2, y2 = chunk(seed_key(2**31 + 13), 3, 4096)
    X3, _ = chunk(seed_key(2**31 + 13), 4, 4096)
    X, y = np.asarray(X), np.asarray(y)
    np.testing.assert_array_equal(X, np.asarray(X2))
    np.testing.assert_array_equal(y, np.asarray(y2))
    assert not np.array_equal(X, np.asarray(X3), equal_nan=True)
    assert X.shape == (4096, expedia.NUM_FEATURES) == (4096, 22)
    assert set(np.unique(y)) <= set(range(expedia.NUM_CLASSES)) and len(np.unique(y)) > 50
    cats = X[:, list(expedia.CATEGORICAL)]
    assert np.all(cats == np.floor(cats)) and np.all(cats < np.asarray(expedia.CAT_CARD))
    missing = np.isnan(X).mean(axis=0)
    assert 0.3 < missing[expedia.NUMERIC.index("orig_destination_distance")] < 0.42
    assert missing[list(expedia.CATEGORICAL)].max() == 0.0


def test_cell_is_declared_with_its_files():
    bench, cell, cfg, workload = runner.load_cell(CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == ("expedia_hotel_multiclass", workload["kind"], 1)
    assert workload["kind"] == "train_loop_multiclass" and workload["iterations_per_fit"] == 2
    p = cfg["params"]
    assert (p["objective"], p["num_class"], p["num_leaves"], p["predict_backend"]) == ("multiclass", 100, 31, "scan")
    assert (cfg["num_features"], cfg["num_numeric"], cfg["num_categorical"]) == (22, 11, 11)
    assert len(cfg["columns"]["numeric"]) == 11 and cfg["columns"]["categorical"] == list(expedia.CATEGORICAL_NAMES)
    assert cfg["rows"] % cfg["chunk_rows"] == 0 and cfg["holdout_rows"] % cfg["chunk_rows"] == 0
    assert cfg["rows"] <= cfg["published"]["rows_per_chip"] and cfg["published"]["chips"] == 8
    names = {m["name"] for m in runner.metrics_for(bench, CELL, "per_layer", {"train_rowiters_per_s", "peak_hbm_gb", "setup_s"})}
    assert names == {"class_grad_share_pct", "class_update_share_pct", "class_tree_s",
                     "device_idle_pct", "train_step_mfu_pct", "warm_cache_misses", "program_reserved_gb"}


def test_run_end_to_end(tiny_cell):
    from mmlspark_tpu import obs

    obs.reset()
    out = runner.run(_args(), need_chip=False)
    assert out["correct"] is True, out["check"]
    assert out["check"]["leaf_count_gap"]["value"] == 0.0 and out["check"]["holdout_score_gap"]["value"] == 0.0
    assert out["observed"]["holdout_logloss_gap"] < 1e-5
    # set-up's warm fit, counted with the counters on: 100 trees an iteration
    assert obs.snapshot()["counters"]["train.class_trees"] == 2 * 100
    json.dumps(out)


def test_fp8_control_is_not_correct(tiny_cell):
    out = runner.run(_args(), need_chip=False, variant="fp8")
    assert out["correct"] is False, out["check"]


def test_faults_are_the_cell_s():
    assert set(train_loop_multiclass.FAULTS) == {
        "ova_gradient", "class_shift", "hess_halved",
        "state_unchanged", "answer_altered", "holdout_tree_dropped", "half_batch",
    }
    assert list(train_loop_multiclass.FAULTS)[-1] == "half_batch"  # it spends the data set


@pytest.mark.parametrize("fault", list(train_loop_multiclass.FAULTS))
def test_planted_fault_is_not_correct(tiny_cell, fault):
    out = runner.run(_args(), need_chip=False, traffic_overrides=train_loop_multiclass.FAULTS[fault])
    assert out["correct"] is False, out["check"]


def test_a_fault_leaves_the_sound_gradient_behind(tiny_cell):
    from mmlspark_tpu.engine import booster
    from mmlspark_tpu.ops import objectives

    sound = objectives.Multiclass.grad_hess
    runner.run(_args(), need_chip=False, traffic_overrides=train_loop_multiclass.FAULTS["class_shift"])
    assert objectives.Multiclass.grad_hess is sound and not booster._SCAN_CACHE


# ---- the readers ----------------------------------------------------------------
def _ctx(classes=True):
    ops = {  # names as the v5e's trace gives them, at small sizes
        "_pallas_hist_by_leaf_nibble.9 f32[1,48,2816]": 2.0,
        "fusion.12 f32[100,8192]": 0.5,
        "compare_select_fusion.6 f32[100,8192]": 0.25,
        "add.7 f32[100,8192]": 0.125,
        "fusion.33 s32[1,4096]": 0.125,
    }
    regions = {"booster.fit": {
        ("_pallas_hist_by_leaf_nibble.9", "f32[1,48,2816]"): "hist_build",
        ("fusion.12", "f32[100,8192]"): "class_grad", ("compare_select_fusion.6", "f32[100,8192]"): "class_update",
        ("add.7", "f32[100,8192]"): "class_update", ("fusion.33", "s32[1,4096]"): "replay_step",
    }}
    after = {"train.class_trees": 3 * 2 * 100.0} if classes else {}
    before = {k: v / 3 for k, v in after.items()}  # set-up's fit once, the window's two fits twice more
    return {
        "trace": {"op_s": ops, "busy_s": 3.0, "window_s": 3.5}, "regions": regions, "rows": 8192, "cols": 22,
        "cfg": {"chunk_rows": 4096}, "window": {"attempted": 2, "iterations": 4}, "device_kind": "TPU v5 lite",
        "window_counters": after, "setup_counters": before,
    }


def test_readers_read_a_small_trace():
    ctx = _ctx()
    assert _class.class_trees(ctx) == 400
    assert class_grad_share_pct.read(ctx) == pytest.approx(100.0 * 0.5 / 3.0)
    assert class_update_share_pct.read(ctx) == pytest.approx(100.0 * 0.375 / 3.0)
    assert class_tree_s.read(ctx) == pytest.approx(3.0 / 400)


def test_a_program_that_counts_no_class_trees_reads_nothing():
    # a fit of one tree an iteration, or a parent from before the counter
    for reader in READERS:
        assert reader.read(_ctx(classes=False)) is None
    ctx = {**_ctx(), "trace": None}
    assert class_grad_share_pct.read(ctx) is None and class_update_share_pct.read(ctx) is None
