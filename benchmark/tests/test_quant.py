"""CPU tests of the quantized-training cell's own files: the traffic kind
``train_loop_quant``, ``reference_quant`` and the ``quant_*`` readers.  The
end-to-end cases run the runner with its look for a chip skipped, at a few
thousand rows, with the Pallas kernels interpreted."""

import argparse
import json

import numpy as np
import pytest

from benchmark import peaks, reference_quant
from benchmark import run as runner
from benchmark.metrics import _quant, quant_hist_roofline_pct, quant_hist_share_pct, quant_levels, quant_refine_share_pct, quant_round_share_pct

CELL = "criteo_quant_train_1chip"
TINY = {"rows": 8192, "holdout_rows": 4096, "chunk_rows": 4096, "bin_sample_rows": 4096}


@pytest.fixture()
def tiny_cell(monkeypatch):
    orig = runner.load_cell

    def load(name):
        bench, cell, cfg, workload = orig(name)
        cfg = dict(cfg, **TINY)
        cfg["params"] = dict(cfg["params"], num_leaves=7, hist_backend="pallas", hist_precision="highest")
        return bench, cell, cfg, workload

    monkeypatch.setattr(runner, "load_cell", load)


def _args(**kw):
    return argparse.Namespace(**dict(dict(workload=CELL, seed=2**31 + 7, seconds=0.0, trace=0), **kw))


def test_cell_is_declared_with_its_files():
    bench, cell, cfg, workload = runner.load_cell(CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == ("criteo_kaggle_quant", workload["kind"], 1)
    p = cfg["params"]
    assert (p["use_quantized_grad"], p["num_grad_quant_bins"], p["hist_quantize"]) == (True, 4, "on")
    assert cfg["rows"] % cfg["chunk_rows"] == 0 and cfg["holdout_rows"] % cfg["chunk_rows"] == 0
    assert cfg["rows"] + cfg["holdout_rows"] <= cfg["published"]["rows"]
    # the float Criteo cell's recipe, and nothing but the gradients' arithmetic beside it
    _, _, base, _ = runner.load_cell("criteo_train_1chip")
    assert {k: v for k, v in p.items() if k in base["params"]} == base["params"]
    assert set(p) - set(base["params"]) == {"use_quantized_grad", "num_grad_quant_bins", "hist_quantize"}
    names = {m["name"] for m in runner.metrics_for(bench, CELL, "per_layer", {"train_rowiters_per_s", "peak_hbm_gb", "setup_s"})}
    assert {"quant_hist_share_pct", "quant_hist_roofline_pct", "quant_refine_share_pct", "quant_round_share_pct", "quant_levels",
            "device_idle_pct", "train_step_mfu_pct", "warm_cache_misses", "program_reserved_gb"} == names


def test_run_end_to_end(tiny_cell):
    from mmlspark_tpu import obs

    obs.reset()
    out = runner.run(_args(), need_chip=False)
    assert out["correct"] is True, out["check"]
    assert set(out["metrics"]) == {"train_rowiters_per_s", "peak_hbm_gb", "setup_s"}
    assert {"quant_choice_gap", "quant_choice_own", "split_choice_gap", "holdout_logloss"} <= set(out["observed"]) | set(out["check"])
    # set-up's warm fit, counted with the counters on: the configuration's levels
    counters = obs.snapshot()["counters"]
    assert [counters[f"train.quant_levels{{channel={c}}}"] for c in ("grad", "hess", "count")] == [2, 4, 1]
    json.dumps(out)


def test_fp8_control_is_not_correct(tiny_cell):
    out = runner.run(_args(), need_chip=False, variant="fp8")
    assert out["correct"] is False, out["check"]


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch", "answer_altered", "holdout_tree_dropped"])
def test_planted_fault_is_not_correct(tiny_cell, fault):
    from benchmark.traffic import train_loop_quant

    out = runner.run(_args(), need_chip=False, traffic_overrides=train_loop_quant.FAULTS[fault])
    assert out["correct"] is False, out["check"]


def test_fewer_levels_fault_fits_at_two_bins(tiny_cell):
    from benchmark.traffic import train_loop_quant
    from mmlspark_tpu import obs

    assert list(train_loop_quant.FAULTS)[-1] == "half_batch"  # it spends the data set
    obs.reset()
    out = runner.run(_args(), need_chip=False, traffic_overrides=train_loop_quant.FAULTS["fewer_levels"])
    counters = obs.snapshot()["counters"]
    assert [counters[f"train.quant_levels{{channel={c}}}"] for c in ("grad", "hess", "count")] == [1, 2, 1]
    assert "quant_choice_gap" in set(out["observed"]) | set(out["check"])


# ---- the reference's own pieces ---------------------------------------------
def test_levels_of_the_configuration():
    assert reference_quant.levels_of({"num_grad_quant_bins": 4}) == (2, 4)
    assert reference_quant.levels_of({"num_grad_quant_bins": 2}) == (1, 2)


def test_best_within_a_column():
    # column 1 holds the planted threshold (gain 128), column 0 none
    hist = np.zeros((2, 8, 3))
    hist[:, :, 1] = 25.0
    hist[:, :, 2] = 100.0
    hist[1, :4, 0], hist[1, 4:, 0] = -20.0, 20.0
    is_cat = np.array([False, False])
    params = {"min_data_in_leaf": 20}
    assert reference_quant._best_within(hist, 1, is_cat, params) == pytest.approx(128.0)
    assert reference_quant._best_within(hist, 0, is_cat, params) == pytest.approx(0.0)


# ---- the readers ----------------------------------------------------------------
ROWS, CHUNK = 8192, 4096


def _ctx(quantized=True, bucket="s16"):
    ops = {  # names as the v5e's trace gives them (my chip run, PR 34), at small sizes
        "_pallas_hist_by_leaf.15 s32[1,24,10240]": 4.0,
        "_pallas_hist_by_leaf.14 s32[1,24,10240]": 0.5,
        f"constant_dynamic-slice_fusion.31 {bucket}[3,{CHUNK}]": 0.25,
        "_pallas_hist_by_leaf_nibble.4 f32[1,48,1024]": 1.0,
        f"constant_dynamic-slice_fusion.29 f32[3,{CHUNK}]": 0.125,
        f"constant_dynamic-slice_fusion.28 s32[1,{CHUNK}]": 0.0625,
        f"pad.456 s32[8,{CHUNK}]": 0.03125,
        f"dynamic-slice_convert_fusion.9 (s32[1,{ROWS}]": 0.03125,
        f"pad_maximum_fusion.6 (f32[3,{ROWS}]": 1.0,
        f"pad.457 u8[40,{CHUNK}]": 0.5,  # the bins' own copies, the row masks, the leaf delta, the
        f"dynamic_slice.891 s32[{CHUNK}]": 0.25,  # leaf ids' slices and the scorer: the float fit's too
        f"compare_reduce_fusion.46 pred[{ROWS}]": 2.0,
        f"compare_select_fusion.131 f32[1,{ROWS}]": 1.0,
        "fusion.33 s32[1,6144]": 0.25,
    }
    after = {"train.quant_levels{channel=grad}": 6.0, "train.quant_levels{channel=hess}": 12.0, "train.quant_levels{channel=count}": 3.0}
    before = {k: v / 3 for k, v in after.items()}  # set-up's fit counted once, the window's two fits twice more
    return {
        "trace": {"op_s": ops, "busy_s": 10.0, "window_s": 11.0}, "rows": ROWS, "cols": 39, "cfg": {"chunk_rows": CHUNK},
        "window": {"attempted": 2, "iterations": 4}, "device_kind": "TPU v5 lite",
        "window_counters": after if quantized else {}, "setup_counters": before if quantized else {},
    }


def test_readers_split_the_traced_seconds_by_what_an_op_produces():
    ctx = _ctx()
    assert _quant.split_seconds(ctx) == {"bucket": 4.75, "refine": 1.25, "round": 1.0}
    assert quant_hist_share_pct.read(ctx) == pytest.approx(47.5)
    assert quant_refine_share_pct.read(ctx) == pytest.approx(12.5)
    assert quant_round_share_pct.read(ctx) == pytest.approx(10.0)
    assert quant_levels.read(ctx) == 4.0
    assert _quant.levels(ctx) == {"grad": 2.0, "hess": 4.0, "count": 1.0}


def test_roofline_counts_the_buckets_at_their_width():
    assert _quant.least_work(1000, 10, 2) == {"bytes": 16_000, "ops": 20_000}
    assert _quant.least_work(1000, 10, 1) == {"bytes": 13_000, "ops": 20_000}
    peak = peaks.peaks("TPU v5 lite")
    assert _quant.floor_seconds({"bytes": 819e9, "ops": 1.0}, peak) == (pytest.approx(1.0), "hbm_bytes")
    assert _quant.floor_seconds({"bytes": 1.0, "ops": 393e12}, peak) == (pytest.approx(1.0), "int8_ops")
    for bucket, width in (("s16", 2), ("s8", 1)):
        ctx = _ctx(bucket=bucket)
        assert _quant.value_bytes(ctx) == width
        least = (ROWS * 39 + ROWS * 3 * width) / 819e9
        assert quant_hist_roofline_pct.read(ctx) == pytest.approx(100.0 * least * 4 / 4.75)


def test_a_program_that_counts_no_levels_reads_nothing():
    # a float fit, or a parent from before the counter: every reader is silent
    ctx = _ctx(quantized=False)
    for reader in (quant_hist_share_pct, quant_hist_roofline_pct, quant_refine_share_pct, quant_round_share_pct, quant_levels):
        assert reader.read(ctx) is None
    assert quant_hist_share_pct.read({**_ctx(), "trace": None}) is None
