"""CPU tests of the per-layer metrics that read the program's own spans and
counters (``benchmark/metrics/_program.py``): each reader on a hand-made span
list and counter pair, ``None`` where its span or counter is absent, and all
five on what a real (tiny) fit and evaluation leave behind."""

import importlib

import numpy as np
import pytest

NAMES = ["fit_host_lead_s", "fit_upload_mb", "scorer_dispatch_s", "window_retrace_s", "setup_program_load_s"]


def read(name, ctx):
    return importlib.import_module(f"benchmark.metrics.{name}").read(ctx)


def span(sid, name, start_ms, end_ms, parent_id=None, **attrs):
    return {
        "id": sid, "name": name, "start_ns": start_ms * 1_000_000, "end_ns": end_ms * 1_000_000,
        "parent": None, "parent_id": parent_id, "thread": "MainThread", "attrs": attrs,
    }


def hand_made():
    """A warm fit (ids 0-3, set-up's) then the window's fit and evaluation."""
    spans = [
        span(0, "booster.train", 0, 900), span(1, "booster.prepare", 0, 400, 0), span(2, "booster.upload", 400, 800, 0),
        span(3, "booster.score_binned", 950, 1950, built=True),
        span(10, "booster.train", 2000, 2400),
        span(11, "booster.prepare", 2000, 2050, 10), span(12, "booster.binning", 2010, 2020, 11),
        span(13, "booster.upload", 2050, 2200, 10, bytes=9_000_000), span(14, "booster.program", 2200, 2270, 10),
        span(15, "booster.scan_dispatch", 2270, 2280, 10), span(16, "booster.collect", 2280, 2400, 10),
        span(17, "booster.quality_baseline", 2400, 2500),
        span(18, "booster.score_binned", 2600, 2725, built=True),
    ]
    setup = {"train.upload_bytes": 20e6, "jit.trace_s": 3.5, "jit.traces": 400.0, "jit.backend_s": 4.25, "jit.lower_s": 1.0,
             "predict.scorer_builds": 1.0}
    window = {"train.upload_bytes": 38e6, "jit.trace_s": 3.625, "jit.traces": 405.0, "jit.backend_s": 4.3, "jit.lower_s": 1.05,
              "predict.scorer_builds": 3.0}
    return {"spans": spans, "setup_counters": setup, "window_counters": window, "window": {"attempted": 2}}


@pytest.mark.parametrize("name, value", [
    ("fit_host_lead_s", 0.05 + 0.15 + 0.07),  # the window's fit's three phases, not the warm fit's
    ("fit_upload_mb", 9.0),  # 18 MB over the window's two fits
    ("scorer_dispatch_s", 0.125),  # the last call's span
    ("window_retrace_s", 0.125),
    ("setup_program_load_s", 4.25),
])
def test_reader_on_hand_made_records(name, value, capsys):
    assert read(name, hand_made()) == pytest.approx(value)
    said = capsys.readouterr().err
    if name == "fit_host_lead_s":
        assert "prepare_s=0.05 upload_s=0.15 program_s=0.07" in said
    if name == "window_retrace_s":
        assert "jit.traces=5 predict.scorer_builds=2" in said


@pytest.mark.parametrize("name", NAMES)
def test_reader_finds_nothing_in_a_program_without_the_span_or_counter(name):
    """The parent commit: older spans only, older counters only."""
    ctx = hand_made()
    ctx["spans"] = [s for s in ctx["spans"] if s["name"] in ("booster.train", "booster.binning", "booster.scan_dispatch")]
    ctx["setup_counters"] = {"jit_cache.hit": 80.0}
    ctx["window_counters"] = {"jit_cache.hit": 82.0}
    assert read(name, ctx) is None


def test_reader_without_the_programs_reader_reads_nothing(monkeypatch):
    from mmlspark_tpu.obs import flight

    monkeypatch.delattr(flight, "spans")
    ctx = {k: v for k, v in hand_made().items() if k != "spans"}
    assert read("fit_host_lead_s", ctx) is None and read("scorer_dispatch_s", ctx) is None


def test_all_five_read_what_a_real_fit_and_evaluation_leave():
    """What ``run.py`` does around a traced window, at a few thousand rows:
    counters snapshotted after set-up and after the window, spans from the
    program's reader in this process."""
    import json
    import os

    from benchmark.traffic import train_loop
    from mmlspark_tpu import obs

    root = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    with open(os.path.join(root, "benchmark", "configs", "criteo_gbdt.json")) as f:
        cfg = json.load(f)
    cfg.update(rows=8192, holdout_rows=4096, chunk_rows=4096, bin_sample_rows=4096)
    cfg["params"] = dict(cfg["params"], num_leaves=7, hist_backend="pallas", hist_precision="highest")
    with open(os.path.join(root, "benchmark", "workloads", "criteo_train_1chip.json")) as f:
        workload = json.load(f)
    obs.enable()
    try:
        state, _ = train_loop.setup(cfg, workload, seed=2**31 + 11)
        setup_counters = dict(obs.snapshot()["counters"])
        result = train_loop.window(state, 0.0, max_fits=1)
        ctx = {"window": result, "setup_counters": setup_counters, "window_counters": dict(obs.snapshot()["counters"])}
        values = {name: read(name, ctx) for name in NAMES}
    finally:
        obs.disable()
        obs.reset()
    assert all(v is not None for v in values.values()), values
    rows = state["ds"].num_rows
    assert values["fit_upload_mb"] == rows * (4 + 4 + 1) / 1e6  # labels, init scores, the mask; the matrix is resident
    assert values["fit_host_lead_s"] > 0 and values["scorer_dispatch_s"] > 0
    assert values["window_retrace_s"] > 0  # a new Booster's scorer traces again
    assert values["setup_program_load_s"] > 0
    assert ctx["window_counters"]["predict.scorer_builds"] - setup_counters["predict.scorer_builds"] == 1
    assert np.isfinite(list(values.values())).all()
