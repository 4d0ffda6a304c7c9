"""CPU tests of the readers of the device's seconds by region
(``benchmark/metrics/_regions.py`` and the eleven metrics of ISSUE 36) over the
two recorded traces, with a handed-in region map and counters: seconds by
region sum to the busy seconds, a key two programs put in different regions is
unscoped, a map that finds under 90 % of the busy seconds gives ``None``, and
so does a program without the map or the counters (never 0)."""

import importlib
import json
import os

import pytest

from benchmark import trace
from benchmark.metrics import _regions

HERE = os.path.dirname(os.path.abspath(__file__))
REGION_METRICS = ["hist_region_s_per_iter", "hist_pass_ns_per_rowcol", "chunk_copy_s_per_iter", "row_route_s_per_iter",
                  "tree_logic_s_per_iter", "replay_device_s_per_eval", "unscoped_device_pct"]
COUNTER_METRICS = ["hist_passes_per_tree", "hist_mxu_flops_per_rowcol", "hist_vpu_elems_per_rowcol", "scorer_retrace_s"]


def read(name, ctx):
    return importlib.import_module(f"benchmark.metrics.{name}").read(ctx)


def _reduced(name):
    with open(os.path.join(HERE, name)) as f:
        return trace.reduce([tuple(e) for e in json.load(f)], window_ns=4_000_000_000)


def _region_of(op: str) -> str:
    """A region for every op of the recorded traces, by what its name says."""
    for part, region in (("_pallas_hist", "hist_build"), ("pad", "chunk_copy"), ("dynamic-slice", "chunk_copy"),
                         ("dynamic_slice", "chunk_copy"), ("reduce", "row_route"), ("select", "leaf_delta"),
                         ("scatter", "leaf_stats"), ("psum", "hist_merge"), ("all-", "hist_merge")):
        if part in op:
            return region
    return "split_scan" if hash(op) % 3 else None  # some ops carry no scope


def ctx_for(name, **over):
    red = _reduced(name)
    fit = {tuple(op.split(" ", 1)): _region_of(op) for op in red["op_s"]}
    setup = {"hist.passes{body=nibble,scope=hist_build,vals=f32}": 22.0, "hist.rowcols{body=nibble,scope=hist_build,vals=f32}": 2.0e9,
             "hist.mxu_flops{body=nibble,scope=hist_build,vals=f32}": 2.0e9 * 12288, "hist.vpu_elems{body=nibble,scope=hist_build,vals=f32}": 2.0e9 * 347,
             "jit.trace_s": 3.0, "jit.trace_s{span=booster.score_binned}": 1.0, "jit.trace_s{span=booster.program}": 2.0}
    window = {k: 2 * v for k, v in setup.items()} | {"jit.trace_s": 3.15, "jit.trace_s{span=booster.score_binned}": 1.125,
                                                     "jit.trace_s{span=booster.program}": 2.025}
    return {"trace": red, "regions": {"booster.fit:0": fit}, "setup_counters": setup, "window_counters": window,
            "window": {"iterations": 2, "eval_s": [0.2]}} | over


@pytest.mark.parametrize("name", ["trace_small.json", "trace_dp_small.json"])
def test_seconds_by_region_sum_to_busy_and_every_metric_reads(name, capsys):
    ctx = ctx_for(name)
    table = _regions.seconds(ctx)
    assert sum(table.values()) == pytest.approx(sum(ctx["trace"]["op_s"].values()))
    assert sum(table.values()) <= ctx["trace"]["busy_s"] * (1 + 1e-9)  # the recorded loops' bodies are cut short
    assert table["hist_build"] == pytest.approx(trace.seconds_of(ctx["trace"]["op_s"], ("_pallas_hist",)))
    values = {m: read(m, ctx) for m in REGION_METRICS + COUNTER_METRICS}
    assert all(v is not None for v in values.values())
    assert values["hist_region_s_per_iter"] == pytest.approx(table["hist_build"] / 2)
    assert values["hist_pass_ns_per_rowcol"] == pytest.approx(1e9 * table["hist_build"] / 2.0e9)
    assert values["unscoped_device_pct"] == pytest.approx(100 * table["unscoped"] / ctx["trace"]["busy_s"])
    assert values["tree_logic_s_per_iter"] == pytest.approx(sum(table.get(r, 0) for r in ("split_scan", "leaf_stats", "leaf_delta")) / 2)
    assert (values["hist_passes_per_tree"], values["hist_mxu_flops_per_rowcol"], values["hist_vpu_elems_per_rowcol"]) == (11, 12288, 347)
    assert values["scorer_retrace_s"] == pytest.approx(0.125)
    said = capsys.readouterr().err
    assert said.count("regions coverage=1 ") == 1  # the table is made and printed once a run
    assert "scorer_retrace_s booster.program=0.025 booster.score_binned=0.125" in said


def test_a_key_with_two_regions_is_unscoped():
    ctx = ctx_for("trace_small.json")
    kernel = next(op for op in ctx["trace"]["op_s"] if "_pallas_hist" in op)
    ctx["regions"]["booster.scorer:1"] = {tuple(kernel.split(" ", 1)): "replay_step"}
    alone = _regions.seconds(ctx_for("trace_small.json"))
    table = _regions.seconds(ctx)
    assert table["unscoped"] == pytest.approx(alone["unscoped"] + ctx["trace"]["op_s"][kernel])
    assert "replay_step" not in table
    # the same region from two programs is no conflict
    ctx = ctx_for("trace_small.json")
    ctx["regions"]["booster.fit:1"] = dict(ctx["regions"]["booster.fit:0"])
    assert _regions.seconds(ctx) == pytest.approx(alone)


def test_coverage_under_nine_tenths_gives_none_and_says_so(capsys):
    ctx = ctx_for("trace_small.json")
    ctx["regions"]["booster.fit:0"] = {k: r for k, r in ctx["regions"]["booster.fit:0"].items() if "_pallas_hist" not in k[0]}
    assert [read(m, ctx) for m in REGION_METRICS] == [None] * len(REGION_METRICS)
    assert "regions coverage=0." in capsys.readouterr().err
    assert all(read(m, ctx) is not None for m in COUNTER_METRICS)  # the counts still print


@pytest.mark.parametrize("name", REGION_METRICS + COUNTER_METRICS)
def test_a_program_without_the_map_or_the_counters_gives_none(name):
    ctx = ctx_for("trace_small.json", regions=None, setup_counters={"jit.trace_s": 1.0}, window_counters={"jit.trace_s": 2.0})
    assert read(name, ctx) is None


def test_a_real_fit_leaves_what_the_readers_need():
    """The program's own map and counters, on the CPU: no device trace here, so
    the trace is made of the fit's own instructions, a second each."""
    import jax.numpy as jnp
    import numpy as np

    from mmlspark_tpu import obs
    from mmlspark_tpu.engine.booster import Dataset, train

    rng = np.random.default_rng(0)
    X = rng.normal(size=(2048, 6))
    y = (X[:, 0] + rng.normal(size=2048) > 0).astype(np.float64)
    obs.reset()
    obs.enable()
    try:
        setup = dict(obs.snapshot()["counters"])
        model = train(dict(objective="binary", num_iterations=2, num_leaves=15, split_batch=4, hist_chunk=1024, verbosity=0, predict_backend="scan"), Dataset(X, y))
        model._raw_scores_binned(jnp.asarray(model.bin_mapper.transform(X)))
        window = dict(obs.snapshot()["counters"])
        maps = obs.device.regions()
    finally:
        obs.disable()
        obs.reset()
    ops = {" ".join(k): 1.0 for m in maps.values() for k in m}
    ctx = {"trace": {"op_s": ops, "busy_s": float(len(ops))}, "setup_counters": setup, "window_counters": window,
           "window": {"iterations": 2, "eval_s": [0.1]}, "regions": maps}
    values = {m: read(m, ctx) for m in REGION_METRICS + COUNTER_METRICS}
    assert all(v is not None for v in values.values()), values
    assert values["hist_mxu_flops_per_rowcol"] == 0  # the scatter backend issues none
    assert values["replay_device_s_per_eval"] > 0 and values["row_route_s_per_iter"] > 0 and values["chunk_copy_s_per_iter"] > 0
