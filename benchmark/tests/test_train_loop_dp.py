"""CPU tests of the data-parallel traffic kind and of the merge layer's three
readers: the kind end to end on four virtual devices at 8,192 rows a device
with the kernels interpreted, every planted fault, and the readers on a small
trace with collective ops in it (``trace_dp_small.json``: two chips' planes in
the form ``trace.load`` gives, the ops under the names the v5e's trace of the
cell gave them in PR 27, durations in round numbers)."""

import argparse
import importlib
import json
import os

import jax
import pytest

from benchmark import run as runner
from benchmark import trace
from benchmark.metrics import _merge

HERE = os.path.dirname(os.path.abspath(__file__))
CELL = "criteo_train_4chip"
TINY = {"rows": 8192, "holdout_rows": 4096, "chunk_rows": 4096, "bin_sample_rows": 4096}


@pytest.fixture()
def tiny_cell(monkeypatch):
    orig = runner.load_cell

    def load(name):
        bench, cell, cfg, workload = orig(name)
        cfg = dict(cfg, **TINY, chips=jax.device_count())  # 4 here; 8 when run beside tests/
        # the cell's own path: Pallas kernels (interpreted), eight splits a pass
        cfg["params"] = dict(cfg["params"], hist_backend="pallas", hist_precision="highest", split_batch=8, num_leaves=15)
        return bench, cell, cfg, dict(workload, iterations_per_fit=2)

    monkeypatch.setattr(runner, "load_cell", load)


def _args(**kw):
    return argparse.Namespace(**dict(dict(workload=CELL, seed=2**31 + 27, seconds=0.0, trace=0), **kw))


def test_cell_is_declared_as_the_issue_names_it():
    bench, cell, cfg, workload = runner.load_cell(CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == ("criteo_gbdt_dp4", "train_loop_dp", 4)
    assert (workload["kind"], workload["iterations_per_fit"]) == ("train_loop_dp", 4)
    chunk = cfg["chunk_rows"]
    # the issue's one fallback: 10 + 5 chunks a chip, the same 15 resident chunks as 12 + 3
    assert (cfg["rows"] // chunk, cfg["holdout_rows"] // chunk, cfg["chips"]) == (10, 5, 4)
    assert cfg["global_rows"] == cfg["rows"] * 4 == 83_886_080
    assert cfg["global_holdout_rows"] == cfg["holdout_rows"] * 4 == 41_943_040
    one = json.load(open(os.path.join(runner.ROOT, "benchmark", "configs", "criteo_gbdt.json")))
    assert {k: v for k, v in cfg["params"].items() if k != "tree_learner"} == one["params"]
    assert cfg["params"]["tree_learner"] == "data"
    for k in ("num_features", "num_numeric", "num_categorical", "max_bin", "bin_sample_rows", "chunk_rows"):
        assert cfg[k] == one[k]
    reported = {m["name"] for m in runner.metrics_for(bench, CELL, "per_layer", {"train_rowiters_per_s", "peak_hbm_gb", "setup_s"})}
    assert reported == {
        "device_idle_pct", "train_step_mfu_pct", "warm_cache_misses", "program_reserved_gb",
        "hist_merge_share_pct", "hist_merge_ici_pct", "hist_merge_mb_per_iter",
    }


def test_the_reference_is_given_the_hosts_counts():
    from benchmark.traffic import train_loop_dp

    _, _, cfg, _ = runner.load_cell(CELL)
    g = train_loop_dp.global_cfg(cfg)
    assert (g["rows"], g["holdout_rows"]) == (83_886_080, 41_943_040)
    assert cfg["rows"] == 20_971_520  # a copy: the readers still see a chip's rows


def test_run_end_to_end(tiny_cell):
    out = runner.run(_args(), need_chip=False)
    assert out["correct"] is True, out["check"]
    assert set(out["metrics"]) == {"train_rowiters_per_s", "peak_hbm_gb", "setup_s"}
    assert out["device"]["count"] == jax.device_count()
    assert out["check"]["leaf_count_gap"]["value"] == 0 and out["check"]["holdout_score_gap"]["value"] == 0
    assert "holdout_logloss" in out["observed"]
    json.dumps(out)


def test_set_up_places_each_chips_chunks_on_it_and_resolves_the_merge(tiny_cell):
    import numpy as np

    from benchmark import dataset
    from benchmark.data import criteo
    from benchmark.traffic import train_loop_dp

    _, _, cfg, workload = runner.load_cell(CELL)
    seed = 2**31 + 5
    state, _ = train_loop_dp.setup(cfg, workload, seed)
    D = jax.device_count()
    assert state["resolved"]["hist_merge"] == "reduce_scatter" and state["resolved"]["devices"] == D
    ds, holdout = state["ds"], state["holdout"]
    assert ds.num_rows == D * 8192 and holdout["bins"].shape == (D * 4096, 39)
    assert len(ds._binned_dev.sharding.device_set) == D and not ds._binned_dev.sharding.is_fully_replicated
    # the labels are the stream's, chunk by chunk: chip c holds training chunks 2c, 2c+1 and holdout chunk 2D+c
    gen = jax.jit(criteo.chunk, static_argnums=2)
    key = dataset.seed_key(seed)
    for k in (0, 3, 7):
        np.testing.assert_array_equal(ds.label[k * 4096:(k + 1) * 4096], np.asarray(gen(key, k, 4096)[1]))
    np.testing.assert_array_equal(np.asarray(holdout["label"])[2 * 4096:3 * 4096], np.asarray(gen(key, 2 * D + 2, 4096)[1]))
    assert state["cfg"]["rows"] == D * 8192  # the reference's copy


def test_fp8_control_is_not_correct(tiny_cell):
    out = runner.run(_args(), need_chip=False, variant="fp8")
    assert out["correct"] is False, out["check"]


@pytest.mark.parametrize("fault", ["state_unchanged", "answer_altered", "holdout_tree_dropped", "shard_lost", "half_batch"])
def test_planted_fault_is_not_correct(tiny_cell, fault):
    from benchmark.traffic import train_loop_dp

    assert list(train_loop_dp.FAULTS)[-2:] == ["shard_lost", "half_batch"]  # the two that spend the data set come last
    out = runner.run(_args(), need_chip=False, traffic_overrides=train_loop_dp.FAULTS[fault])
    assert out["correct"] is False, out["check"]
    if fault == "shard_lost":  # a quarter of every leaf counts the wrong rows: orders of magnitude over the limit
        assert out["check"]["leaf_count_gap"]["value"] > 1e3 * out["check"]["leaf_count_gap"]["limit"]


def test_a_program_without_the_merge_ledger_is_refused_at_once(monkeypatch):
    from benchmark.traffic import train_loop_dp
    from mmlspark_tpu.parallel import distributed

    monkeypatch.delattr(distributed, "collective_ledger")
    with pytest.raises(SystemExit, match="collective_ledger"):
        train_loop_dp.setup({}, {}, 1)


# ---- the merge layer's readers ----------------------------------------------
def _ctx(events, counters=None):
    red = trace.reduce(events, window_ns=12_000)
    return {
        "trace": red, "window": {"iterations": 2, "attempted": 1}, "cols": 39, "device_kind": "TPU v5 lite",
        "device": {"count": 4}, "cfg": {"max_bin": 255, "params": {"num_leaves": 63}},
        "setup_counters": {"train.merge_bytes{op=psum}": 100.0},
        "window_counters": counters if counters is not None else {
            "train.merge_bytes{op=psum}": 100.0 + 4e6, "train.merge_bytes{op=reduce_scatter}": 6e6, "train.merge_calls{op=psum}": 80.0,
        },
    }


def _read(name, ctx):
    return importlib.import_module(f"benchmark.metrics.{name}").read(ctx)


def _events():
    with open(os.path.join(HERE, "trace_dp_small.json")) as f:
        return [tuple(e) for e in json.load(f)]


def test_readers_on_a_trace_with_collectives(capsys):
    ctx = _ctx(_events())
    # a chip's mean: two passes of reduce_scatter 300 / 320, all-gather 100 and psum 100 ns, and the loss's all-reduce
    collective_s = (2 * (300 + 100 + 100) + 100 + 2 * (320 + 100 + 100) + 100) / 2 / 1e9
    assert _merge.collective_seconds(ctx) == pytest.approx(collective_s)
    assert _read("hist_merge_share_pct", ctx) == pytest.approx(100 * collective_s / ctx["trace"]["busy_s"])
    least = 2 * 39 * 256 * 4 * 62 * 3 / 4
    assert _merge.merge_least_bytes(39, 256, 63, 4) == least
    assert _read("hist_merge_ici_pct", ctx) == pytest.approx(100 * (least * 2 / 200e9) / collective_s)
    assert _read("hist_merge_mb_per_iter", ctx) == pytest.approx((4e6 + 6e6) / 2 / 1e6)
    assert "reduce_scatter_bytes=6e+06" in capsys.readouterr().err
    # the while loop that encloses the ops is not itself counted
    assert not any(n.startswith("while") for n in ctx["trace"]["op_s"])


@pytest.mark.parametrize("name", ["hist_merge_share_pct", "hist_merge_ici_pct", "hist_merge_mb_per_iter"])
def test_readers_read_nothing_where_there_is_nothing(name):
    with open(os.path.join(HERE, "trace_small.json")) as f:  # one chip's trace: no collective in it
        one_chip = [tuple(e) for e in json.load(f)]
    assert _read(name, _ctx(one_chip, counters={"train.upload_bytes": 1.0})) is None
    assert _read(name, {"trace": None, "window": {"iterations": 2}, "device": {"count": 4}, "setup_counters": {}, "window_counters": {}}) is None


def test_an_unknown_chips_interconnect_is_an_error():
    ctx = dict(_ctx(_events()), device_kind="TPU v9 imaginary")
    with pytest.raises(ValueError, match="interconnect"):
        _read("hist_merge_ici_pct", ctx)
