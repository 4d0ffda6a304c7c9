"""CPU tests of the GOSS cell's own files: the traffic kind
``train_loop_goss``, ``reference_goss`` and the ``goss_*`` readers.  The
end-to-end cases run the runner with its look for a chip skipped, at 8,192
rows, with the Pallas kernels interpreted."""

import argparse
import json

import numpy as np
import pytest

from benchmark import peaks, reference_goss
from benchmark import run as runner
from benchmark.metrics import (
    _goss, goss_compact_roofline_pct, goss_hist_rowcol_frac, goss_hist_s_per_iter, goss_share_pct,
)
from benchmark.traffic import train_loop_goss

CELL = "criteo_goss_train_1chip"
TINY = {"rows": 8192, "holdout_rows": 4096, "chunk_rows": 4096, "bin_sample_rows": 4096}
READERS = (goss_share_pct, goss_compact_roofline_pct, goss_hist_rowcol_frac, goss_hist_s_per_iter)


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
    return argparse.Namespace(**dict(dict(workload=CELL, seed=2**31 + 11, seconds=0.0, trace=0), **kw))


def test_cell_is_declared_with_its_files():
    bench, cell, cfg, workload = runner.load_cell(CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == ("criteo_goss_share", workload["kind"], 1)
    p = cfg["params"]
    assert (p["boosting"], p["top_rate"], p["other_rate"]) == ("goss", 0.2, 0.1)
    assert cfg["rows"] % cfg["chunk_rows"] == 0 and cfg["holdout_rows"] % cfg["chunk_rows"] == 0
    assert cfg["rows"] + cfg["holdout_rows"] <= cfg["published"]["rows_per_chip_v5e32"]
    # the file's sample is what the counts give at its rows
    top, rest = reference_goss.counts(cfg["rows"], p)
    assert (top, rest, top + rest) == (cfg["sample"]["top_rows"], cfg["sample"]["rest_rows"], cfg["sample"]["sample_rows"])
    assert float(reference_goss.amplification(p)) == cfg["sample"]["amplification"]
    # the float Criteo cell's recipe, and nothing but the sampling beside it
    _, _, base, _ = runner.load_cell("criteo_train_1chip")
    assert {k: v for k, v in p.items() if k in base["params"]} == base["params"]
    assert set(p) - set(base["params"]) == {"boosting", "top_rate", "other_rate"}
    names = {m["name"] for m in runner.metrics_for(bench, CELL, "per_layer", {"train_rowiters_per_s", "peak_hbm_gb", "setup_s"})}
    assert names == {"goss_share_pct", "goss_compact_roofline_pct", "goss_hist_rowcol_frac", "goss_hist_s_per_iter",
                     "device_idle_pct", "train_step_mfu_pct", "warm_cache_misses", "program_reserved_gb"}


def test_run_end_to_end(tiny_cell):
    from mmlspark_tpu import obs

    obs.reset()
    out = runner.run(_args(), need_chip=False)
    assert out["correct"] is True, out["check"]
    assert out["check"]["leaf_count_gap"]["value"] == 0.0
    assert set(out["metrics"]) == {"train_rowiters_per_s", "peak_hbm_gb", "setup_s"}
    assert out["observed"]["sample_rows"] == sum(reference_goss.counts(TINY["rows"], {"top_rate": 0.2, "other_rate": 0.1}))
    # set-up's warm fit, counted with the counters on: the sample of each of its iterations
    assert obs.snapshot()["counters"]["goss.sample_rows"] == 2 * out["observed"]["sample_rows"]
    json.dumps(out)


def test_fp8_control_is_not_correct(tiny_cell):
    out = runner.run(_args(), need_chip=False, variant="fp8")
    assert out["correct"] is False, out["check"]


def test_faults_are_the_issue_s():
    assert set(train_loop_goss.FAULTS) == {
        "amp_dropped", "rest_bernoulli", "top_by_random",
        "state_unchanged", "answer_altered", "holdout_tree_dropped", "half_batch",
    }
    assert list(train_loop_goss.FAULTS)[-1] == "half_batch"  # it spends the data set


@pytest.mark.parametrize("fault", list(train_loop_goss.FAULTS))
def test_planted_fault_is_not_correct(tiny_cell, fault):
    out = runner.run(_args(), need_chip=False, traffic_overrides=train_loop_goss.FAULTS[fault])
    assert out["correct"] is False, out["check"]


def test_a_fault_leaves_the_sound_sampler_behind(tiny_cell):
    from mmlspark_tpu.engine import booster

    sound = booster.goss_sample
    runner.run(_args(), need_chip=False, traffic_overrides=train_loop_goss.FAULTS["amp_dropped"])
    assert booster.goss_sample is sound and not booster._SCAN_CACHE


# ---- the reference's own pieces ---------------------------------------------
def test_sample_weights_by_stable_argsort():
    import jax.numpy as jnp

    params = {"top_rate": 0.25, "other_rate": 0.25}
    s = jnp.asarray([0.5, 0.9, 0.5, 0.1, 0.9, 0.5, 0.2, 0.5], jnp.float32)
    w = np.asarray(reference_goss.sample_weights(s, params, t=0))
    # top: the two 0.9s; rest: two of the other six, by the draw
    assert list(np.flatnonzero(w == 1.0)) == [1, 4]
    assert (w == 3.0).sum() == 2 and (w == 0.0).sum() == 4
    u = np.asarray(reference_goss.rest_draw(params, 0, 8))
    others = [i for i in range(8) if i not in (1, 4)]
    assert sorted(np.flatnonzero(w == 3.0)) == sorted(sorted(others, key=lambda i: (u[i], i))[:2])
    # ties in s go to the lower row
    w = np.asarray(reference_goss.sample_weights(jnp.full(8, 0.5, jnp.float32), params, t=0))
    assert list(np.flatnonzero(w == 1.0)) == [0, 1]


def test_counts_of_the_configuration():
    assert reference_goss.counts(132_120_576, {"top_rate": 0.2, "other_rate": 0.1}) == (26_424_115, 13_212_057)
    assert reference_goss.amplification({"top_rate": 0.2, "other_rate": 0.1}) == np.float32(8.0)


# ---- the readers ----------------------------------------------------------------
ROWS, COLS, SAMPLE = 8192, 39, 2457


def _ctx(goss=True):
    ops = {  # names as the v5e's trace gives them, at small sizes
        "_pallas_hist_by_leaf_nibble.9 f32[1,48,4992]": 2.0,
        "gather.269 u8[4096,39]": 0.25,
        "scatter.214 s32[4096]": 0.125,
        "fusion.12 u32[8192]": 0.5,
        "fusion.33 s32[1,8192]": 0.375,
        "compare_select_fusion.6 f32[1,8192]": 0.75,
    }
    regions = {"booster.fit": {
        ("_pallas_hist_by_leaf_nibble.9", "f32[1,48,4992]"): "hist_build",
        ("gather.269", "u8[4096,39]"): "goss_compact", ("scatter.214", "s32[4096]"): "goss_compact",
        ("fusion.12", "u32[8192]"): "goss_select", ("fusion.33", "s32[1,8192]"): "goss_route",
        ("compare_select_fusion.6", "f32[1,8192]"): "leaf_delta",
    }}
    after = {"hist.passes{body=nibble,scope=hist_build,vals=f32}": 44.0,
             "hist.rowcols{body=nibble,scope=hist_build,vals=f32}": 44.0 * 4096 * COLS}
    if goss:
        after |= {"goss.sample_rows": 3 * 2 * SAMPLE, "goss.top_rows": 3 * 2 * 1638, "goss.rest_rows": 3 * 2 * 819}
    before = {k: v / 3 for k, v in after.items()}  # set-up's fit once, the window's two fits twice more
    return {
        "trace": {"op_s": ops, "busy_s": 4.0, "window_s": 4.5}, "regions": regions, "rows": ROWS, "cols": COLS,
        "cfg": {"chunk_rows": 4096}, "window": {"attempted": 2, "iterations": 4}, "device_kind": "TPU v5 lite",
        "window_counters": after, "setup_counters": before,
    }


def test_readers_read_a_small_trace():
    ctx = _ctx()
    assert _goss.sample_rows(ctx) == SAMPLE
    assert goss_share_pct.read(ctx) == pytest.approx(100.0 * (0.375 + 0.5 + 0.375) / 4.0)
    least = (ROWS * COLS + SAMPLE * COLS + 12 * SAMPLE) / peaks.peaks("TPU v5 lite")["hbm_bytes_per_s"]
    assert goss_compact_roofline_pct.read(ctx) == pytest.approx(100.0 * least * 4 / 0.375)
    assert goss_hist_rowcol_frac.read(ctx) == pytest.approx(4096 / ROWS)
    assert goss_hist_s_per_iter.read(ctx) == pytest.approx(2.0 / 4)


def test_a_program_without_the_sampler_reads_nothing_of_it():
    # a fit without GOSS, or a parent from before the counters: the sample's
    # readers are silent; the histogram readers read what every program has
    ctx = _ctx(goss=False)
    assert goss_share_pct.read(ctx) is None and goss_compact_roofline_pct.read(ctx) is None
    assert goss_hist_rowcol_frac.read(ctx) == pytest.approx(0.5)
    ctx = {**_ctx(), "trace": None}
    assert goss_share_pct.read(ctx) is None and goss_compact_roofline_pct.read(ctx) is None
    assert goss_hist_s_per_iter.read(ctx) is None
