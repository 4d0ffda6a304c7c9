"""CPU tests of the benchmark's own code: ``python -m pytest benchmark/tests -q``.

Nothing here touches libtpu: the end-to-end cases run the runner with its look
for a chip skipped, at a few thousand rows, with the Pallas kernels interpreted.
"""

import argparse
import json
import os

import numpy as np
import pytest

from benchmark import peaks, reference, trace
from benchmark import run as runner

HERE = os.path.dirname(os.path.abspath(__file__))


# ---- trace reduction --------------------------------------------------------
def _events():
    with open(os.path.join(HERE, "trace_small.json")) as f:
        return [tuple(e) for e in json.load(f)]


def test_busy_union_and_gaps():
    ops = [("a", 0, 10), ("b", 5, 10), ("c", 30, 5), ("d", 35, 5)]
    busy, gaps = trace.busy_union(ops)
    assert busy == 15 + 10
    assert gaps == [(15, 30)]


def test_leaf_ops_drop_enclosing_control_flow():
    ops = [("while", 0, 100), ("fusion.1", 0, 40), ("_hist_kernel", 50, 30), ("copy", 120, 5)]
    assert sorted(n for n, _, _ in trace.leaf_ops(ops)) == ["_hist_kernel", "copy", "fusion.1"]


def test_reduce_recorded_trace():
    ev = _events()
    red = trace.reduce(ev, window_ns=4_000_000_000)
    dev = [e for e in ev if e[0].startswith(trace.DEVICE_PLANE) and e[1] == trace.OPS_LINE]
    busy, _ = trace.busy_union(sorted(((n, s, d) for _, _, n, s, d in dev), key=lambda e: e[1]))
    assert red["busy_s"] == pytest.approx(busy / 1e9)
    assert red["window_s"] == pytest.approx(4.0)
    hist = trace.seconds_of(red["op_s"], ("_pallas_hist",))
    by_hand = sum(d for _, _, n, _, d in dev if "_pallas_hist" in n) / 1e9
    assert hist == pytest.approx(by_hand) and hist > 0
    assert len(red["device_ops"]) <= 10 and len(red["idle_gaps"]) <= 10
    assert sum(red["op_s"].values()) <= red["busy_s"] * (1 + 1e-9)
    # the recorded while loop encloses its body's ops and is not counted itself
    assert not any(name.startswith("while.197") for name in red["op_s"])
    # the longest gap is the host's work before the fit's dispatch, which lies
    # before the first device op and between no two
    assert red["idle_gaps"][0] == ["booster.train", pytest.approx(0.2828, abs=1e-3)]


def test_short_name_keeps_op_and_shape():
    line = "%fusion.4 = f32[3,63]{0,1:T(8,128)S(1)} fusion(s32[8]{0} %x), kind=kCustom"
    assert trace.short_name(line) == "fusion.4 f32[3,63]"
    assert trace.short_name("booster.train") == "booster.train"


def test_reduce_without_device_plane_reads_nothing():
    assert trace.reduce([("/host:CPU", "python", "f", 0, 10)], 100) == {}


# ---- least work and peaks -----------------------------------------------------
def test_least_work_by_hand():
    # 1,000 rows x 10 columns: 10,000 binned bytes + 8,000 of gradient and hessian
    assert peaks.hist_least_work(1000, 10) == {"bytes": 18_000, "ops": 20_000}
    assert peaks.step_least_work(1000, 10) == {"bytes": 26_000, "ops": 20_000}
    t, binds = peaks.floor_seconds({"bytes": 819e9, "ops": 1.0}, peaks.peaks("TPU v5 lite"))
    assert t == pytest.approx(1.0) and binds == "hbm_bytes"
    t, binds = peaks.floor_seconds({"bytes": 1.0, "ops": 197e12}, peaks.peaks("TPU v5 lite"))
    assert t == pytest.approx(1.0) and binds == "flops"


def test_unknown_device_kind_is_an_error():
    with pytest.raises(ValueError):
        peaks.peaks("TPU v9 imaginary")


# ---- data -----------------------------------------------------------------------
def test_generator_is_a_function_of_the_seed():
    import jax

    from benchmark.data import criteo as data
    from benchmark.dataset import seed_key

    gen = jax.jit(data.chunk, static_argnums=2)
    big = 2**31 + 12345  # the driver's seeds pass 32 signed bits
    Xa, ya = gen(seed_key(big), 1, 512)
    Xb, yb = gen(seed_key(big), 1, 512)
    Xc, _ = gen(seed_key(big + 1), 1, 512)
    Xd, _ = gen(seed_key(big), 2, 512)
    assert Xa.shape == (512, data.NUM_FEATURES)
    np.testing.assert_array_equal(np.asarray(Xa), np.asarray(Xb))
    np.testing.assert_array_equal(np.asarray(ya), np.asarray(yb))
    assert not np.array_equal(np.asarray(Xa), np.asarray(Xc), equal_nan=True)
    assert not np.array_equal(np.asarray(Xa), np.asarray(Xd), equal_nan=True)


# ---- the reference's own pieces ---------------------------------------------------
def test_floor32_is_the_largest_float32_not_above():
    a = np.array([0.1, 1.0, 1e-30, 123456.789, np.inf])
    f = reference.floor32(a)
    assert (f.astype(np.float64) <= a).all()
    assert (np.nextafter(f[:-1], np.float32(np.inf)).astype(np.float64) > a[:-1]).all()


def test_best_split_finds_a_planted_threshold():
    # two numeric columns over 8 bins (the last holds missing values), equal
    # totals; column 1 changes sign after bin 3: gain 80^2/100 * 2 = 128
    hist = np.zeros((2, 8, 3))
    hist[:, :, 1] = 25.0
    hist[:, :, 2] = 100.0
    hist[1, :4, 0], hist[1, 4:, 0] = -20.0, 20.0
    gain, d = reference.best_split(hist, np.array([False, False]), {"min_data_in_leaf": 20})
    assert (d["feat"], d["bin"]) == (1, 3)
    assert gain == pytest.approx(128.0)
    assert reference.eval_split(hist, d, {"min_data_in_leaf": 20}) == pytest.approx(gain)


@pytest.mark.parametrize("seed", [3, 2**31 + 99])
def test_plain_edge_fit_agrees_with_the_programs(seed):
    import jax

    from benchmark import dataset
    from benchmark.data import criteo as data

    cfg = {"bin_sample_rows": 20000, "chunk_rows": 32768, "max_bin": 255}
    key = dataset.seed_key(seed)
    mapper = dataset.fit_authority(cfg, data, key).mapper
    X, _ = jax.jit(data.chunk, static_argnums=2)(key, 0, cfg["chunk_rows"])
    edges = reference.fit_edges(np.asarray(X[: cfg["bin_sample_rows"]]), data.CATEGORICAL, cfg["max_bin"])
    for f in range(data.NUM_FEATURES):
        theirs = mapper.cat_maps[f] if f in data.CATEGORICAL else reference.floor32(mapper.upper_bounds[f])
        np.testing.assert_array_equal(edges.rows[f], np.asarray(theirs, np.float32))


# ---- the runner, end to end ---------------------------------------------------------
TINY = {"rows": 8192, "holdout_rows": 4096, "chunk_rows": 4096, "bin_sample_rows": 4096}


@pytest.fixture()
def tiny_cells(monkeypatch):
    orig = runner.load_cell

    def load(name):
        bench, cell, cfg, workload = orig(name)
        cfg = dict(cfg, **TINY)
        cfg["params"] = dict(cfg["params"], num_leaves=7, hist_backend="pallas", hist_precision="highest")
        if not cfg["holdout_rows"]:  # a cell with no holdout has no limit for its scores
            workload = dict(workload, limits={k: v for k, v in workload["limits"].items() if k != "holdout_score_gap"})
        return bench, cell, cfg, workload

    monkeypatch.setattr(runner, "load_cell", load)


def _args(workload, **kw):
    base = dict(workload=workload, seed=2**31 + 7, seconds=0.0, trace=0)
    return argparse.Namespace(**dict(base, **kw))


@pytest.mark.parametrize("holdout_rows", [4096, 0])
def test_run_end_to_end(tiny_cells, monkeypatch, holdout_rows):
    monkeypatch.setitem(TINY, "holdout_rows", holdout_rows)
    out = runner.run(_args("criteo_train_1chip"), need_chip=False)
    assert list(out)[:5] == ["correct", "attempted", "failed", "metrics", "device"]
    assert list(out)[-1] == "check"
    assert out["correct"] is True, out["check"]
    assert set(out["metrics"]) == {"train_rowiters_per_s", "peak_hbm_gb", "setup_s"}
    assert out["metrics"]["train_rowiters_per_s"]["value"] > 0
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= set(out["device"])
    for c in out["check"].values():
        assert c["value"] <= c["limit"]
    assert ("holdout_logloss" in out["observed"]) == bool(holdout_rows)
    json.dumps(out)


def test_fp8_control_is_not_correct(tiny_cells):
    out = runner.run(_args("criteo_train_1chip"), need_chip=False, variant="fp8")
    assert out["correct"] is False, out["check"]


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch", "answer_altered", "holdout_tree_dropped"])
def test_planted_fault_is_not_correct(tiny_cells, fault):
    from benchmark.traffic import train_loop

    out = runner.run(
        _args("criteo_train_1chip"), need_chip=False,
        traffic_overrides=train_loop.FAULTS[fault],
    )
    assert out["correct"] is False, out["check"]


def test_a_metric_is_reported_in_its_own_cells_only():
    bench = {"per_layer": [
        {"name": "a", "moves": "x"}, {"name": "b", "moves": "x", "workloads": ["other"]}, {"name": "c", "moves": "y"},
    ]}
    assert [m["name"] for m in runner.metrics_for(bench, "cell", "per_layer", {"x"})] == ["a"]
    assert [m["name"] for m in runner.metrics_for(bench, "other", "per_layer", {"x", "y"})] == ["a", "b", "c"]


def test_no_chip_is_refused():
    with pytest.raises(SystemExit):
        runner.require_chips(1)
