"""The program's span record and its reader, and the spans and counters a fit
and the binned scorer leave (ISSUE 25).  Nothing here asserts a wall time.

1. one record ``(name, start_ns, end_ns, parent, attrs)`` on one clock:
   ``obs.flight.spans()`` pairs the ring's begin/end events, enabled and
   disabled alike, and the exported JSONL line carries the same stamps;
2. a fit's phase spans tile ``booster.train`` (fused scan and legacy loop);
3. ``train.upload_bytes``, ``predict.scorer_builds`` and ``jit.traces`` count
   where the bytes, the builds and the retraces happen.
"""

import json
import threading

import numpy as np
import pytest

from mmlspark_tpu import obs
from mmlspark_tpu.obs import flight, tracing


@pytest.fixture(autouse=True)
def _clean_obs_state():
    obs.disable()
    obs.reset()
    flight.reset()
    yield
    obs.disable()
    obs.reset()
    tracing.close_exporter()
    flight.reset()


@pytest.fixture(params=["enabled", "disabled"])
def mode(request):
    if request.param == "enabled":
        obs.enable()
    return request.param


def _inside(child, parent):
    return parent["start_ns"] <= child["start_ns"] <= child["end_ns"] <= parent["end_ns"]


# ---------------------------------------------------------------- the reader


def test_reader_pairs_nested_spans_of_two_interleaved_threads(mode):
    """Two threads, each ``outer > mid > leaf`` twice over, forced to take
    turns: every record's parent is the enclosing span of ITS thread and
    the child lies inside it."""
    turn = threading.Barrier(2, timeout=30)

    def work(tag):
        with obs.span("outer", who=tag):
            for i in range(2):
                turn.wait()
                with obs.span("mid", who=tag, i=i):
                    turn.wait()
                    with obs.span("leaf", who=tag, i=i):
                        turn.wait()

    threads = [threading.Thread(target=work, args=(t,), name=f"w-{t}") for t in "ab"]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=60)
        assert not t.is_alive()

    recs = flight.spans()
    by_id = {r["id"]: r for r in recs}
    assert len(by_id) == len(recs) == 2 * (1 + 2 + 2)
    for r in recs:
        assert r["end_ns"] >= r["start_ns"]
        if r["name"] == "outer":
            assert r["parent"] is None and r["parent_id"] is None
            continue
        up = by_id[r["parent_id"]]
        assert up["name"] == r["parent"] == {"mid": "outer", "leaf": "mid"}[r["name"]]
        assert up["thread"] == r["thread"] and up["attrs"]["who"] == r["attrs"]["who"]
        assert _inside(r, up)
    # the two threads really interleaved: each outer began before the other ended
    a, b = (r for r in recs if r["name"] == "outer")
    assert a["start_ns"] < b["end_ns"] and b["start_ns"] < a["end_ns"]
    assert [r["name"] for r in flight.spans("leaf")] == ["leaf"] * 4


def test_pairing_drops_an_end_without_its_begin_and_a_span_still_open():
    events = [
        (5, "se", "evicted", None, "t"),  # its sb fell out of the ring
        (10, "sb", "train", {"rows": 4}, "t"),
        (12, "sb", "open", None, "t"),  # never ends
        (20, "sb", "phase", None, "t"),
        (30, "se", "phase", {"bytes": 7}, "t"),
        (40, "span", "iter", {"dur_s": 5e-9, "it": 0}, "t"),  # pre-measured
        (50, "se", "train", None, "t"),
        (15, "ctr", "some.counter", None, "t"),  # not a span event
    ]
    recs = flight.pair_spans(sorted(events, key=lambda e: e[0]))
    assert [(r["name"], r["start_ns"], r["end_ns"]) for r in recs] == [
        ("phase", 20, 30), ("iter", 35, 40), ("train", 10, 50),
    ]
    phase, it, train = recs
    assert phase["attrs"] == {"bytes": 7} and phase["parent"] == "open"
    assert it["attrs"] == {"it": 0}
    assert train["attrs"] == {"rows": 4} and train["parent"] is None


def test_exported_record_and_ring_agree_on_one_clock(tmp_path):
    path = tmp_path / "run.jsonl"
    obs.enable(str(path))
    with obs.span("outer"):
        with obs.span("inner", k=1) as sp:
            pass
    obs.disable()
    lines = [json.loads(x) for x in path.read_text().splitlines()]
    exported = {r["name"]: r for r in lines if r["kind"] == "span"}
    ring = {r["name"]: r for r in flight.spans()}
    for name in ("outer", "inner"):
        e, r = exported[name], ring[name]
        assert (e["start_ns"], e["end_ns"]) == (r["start_ns"], r["end_ns"])
        assert e["dur_s"] == (e["end_ns"] - e["start_ns"]) / 1e9
    assert (sp.start_ns, sp.end_ns) == (ring["inner"]["start_ns"], ring["inner"]["end_ns"])
    assert exported["inner"]["parent"] == ring["inner"]["parent"] == "outer"


def test_late_attrs_ride_the_end_event(mode):
    with obs.span("upload", cached=False) as sp:
        sp.set(bytes=12)
        sp.set(chunks=3)
    (rec,) = flight.spans("upload")
    assert rec["attrs"] == {"cached": False, "bytes": 12, "chunks": 3}


def test_a_bound_request_id_joins_the_attrs_of_every_span_under_it(mode):
    with obs.bind_trace("t-1", request_id="req-9"):
        with obs.span("serve.batch", rows=2):
            with obs.span("predict"):
                pass
    with obs.span("unbound"):
        pass
    recs = {r["name"]: r["attrs"] for r in flight.spans()}
    assert recs["serve.batch"] == {"trace_id": "t-1", "request_id": "req-9", "rows": 2}
    assert recs["predict"] == {"trace_id": "t-1", "request_id": "req-9"}
    assert recs["unbound"] == {}


def test_the_timeline_tool_pairs_through_the_programs_reader(monkeypatch):
    import tools.obs as tool

    calls = []
    real = flight.pair_spans
    monkeypatch.setattr(flight, "pair_spans", lambda ev: calls.append(1) or real(ev))
    ev = lambda wall, kind, name, detail=None: {  # noqa: E731
        "rank": 0, "wall": wall, "ev": kind, "name": name, "thread": "m", "detail": detail, "src": "flight"}
    spans = tool._pair_flight_spans([
        ev(100.0, "sb", "booster.train"), ev(100.5, "collective_end", "psum", {"dur_s": 0.25}),
        ev(101.0, "se", "booster.train"),
    ])
    assert calls == [1]
    got = {s["name"]: s for s in spans}
    assert got["booster.train"]["dur_s"] == pytest.approx(1.0)
    assert got["collective.psum"]["start"] == pytest.approx(100.25)


# ----------------------------------------------------------- a fit's phases

PHASES = ["booster.prepare", "booster.upload", "booster.program", "booster.collect"]


def _data(n=256, f=4, seed=0):
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(n, f))
    y = (X[:, 0] + 0.25 * rng.normal(size=n) > 0).astype(np.float64)
    return X, y


def _params(**kw):
    return {"objective": "binary", "num_iterations": 3, "num_leaves": 7, "min_data_in_leaf": 4, **kw}


@pytest.mark.parametrize("path", ["fused", "legacy"])
def test_a_fit_leaves_one_of_each_phase_under_one_train_span(mode, path, monkeypatch):
    from mmlspark_tpu.engine import booster as bo

    params = _params()
    if path == "legacy":  # DART off the scan: the per-iteration Python loop
        monkeypatch.setattr(bo, "_DART_SCAN_MAX_ELS", 0)
        params = _params(boosting="dart", drop_rate=0.5)
    X, y = _data()
    bo.train(params, bo.Dataset(X, label=y))

    recs = flight.spans()
    (fit,) = [r for r in recs if r["name"] == "booster.train"]
    kids = sorted((r for r in recs if r["parent_id"] == fit["id"]), key=lambda r: r["start_ns"])
    dispatches = [] if path == "legacy" else ["booster.scan_dispatch"]
    assert [k["name"] for k in kids if k["name"].startswith("booster.") and k["name"] != "booster.iteration"] == (
        PHASES[:3] + dispatches + PHASES[3:]
    )
    for k in kids:
        assert _inside(k, fit)
    for before, after in zip(kids, kids[1:]):  # in order, none overlapping
        assert before["end_ns"] <= after["start_ns"]
    got = {k["name"]: k["attrs"] for k in kids}
    assert got["booster.prepare"] == {"rows": 256, "features": 4}
    assert got["booster.upload"]["bins_cached"] is False and got["booster.upload"]["bytes"] > 0
    assert got["booster.collect"] == {"iters": 3}
    if path == "fused":
        assert set(got["booster.program"]) == {"xs_cache_hit", "scan_cache_hit", "devices", "hist_merge"}
        assert (got["booster.program"]["devices"], got["booster.program"]["hist_merge"]) == (1, "none")  # no mesh
    # binning stays, as the prepare phase's child
    (binning,) = [r for r in recs if r["name"] == "booster.binning"]
    assert binning["parent"] == "booster.prepare"
    # nothing outside the fit claims it as parent
    assert all(r["parent_id"] != fit["id"] or _inside(r, fit) for r in recs)


def test_upload_bytes_by_hand_and_less_the_matrix_on_a_second_fit():
    from mmlspark_tpu.engine.booster import Dataset, train

    obs.enable()
    n, f = 256, 4
    X, y = _data(n, f)
    ds = Dataset(X, label=y)
    vX, vy = _data(64, f, seed=1)
    valid = Dataset(vX, label=vy)

    def sent():
        return obs.snapshot()["counters"].get("train.upload_bytes", 0.0)

    booster = train(_params(), ds, valid_sets=[valid])
    first = sent()
    bins = ds.binned(booster.bin_mapper)
    vbins = valid.binned(booster.bin_mapper)
    rows = n * 4 + n * 1 + n * 4  # labels float32, the valid mask, init scores float32
    valid_rows = vbins.nbytes + 64 * 4  # its binned matrix and its float32 scores
    assert first == bins.nbytes + rows + valid_rows
    (up,) = flight.spans("booster.upload")
    one_device = {"devices": 1, "sharded": False}  # where the binned matrix lives (PR 27)
    assert up["attrs"] == {"bins_cached": False, "rows_cached": False, "bytes": first, **one_device}

    train(_params(), ds, valid_sets=[valid])  # the same Dataset: its matrix and its row state are resident
    assert sent() - first == valid_rows
    assert flight.spans("booster.upload")[-1]["attrs"] == {
        "bins_cached": True, "rows_cached": True, "bytes": valid_rows, **one_device,
    }


def test_scorer_builds_once_for_a_new_booster(mode):
    import jax.numpy as jnp

    from mmlspark_tpu.engine.booster import Dataset, train

    X, y = _data()
    ds = Dataset(X, label=y)
    booster = train(_params(predict_backend="scan"), ds)
    bins = jnp.asarray(ds.binned(booster.bin_mapper))

    def builds():
        return obs.snapshot()["counters"].get("predict.scorer_builds", 0.0)

    before = builds()
    flight.reset()
    a = booster._raw_scores_binned(bins)
    first = builds() - before
    b = booster._raw_scores_binned(bins)
    second = builds() - before - first
    if mode == "enabled":
        assert (first, second) == (1, 0)
    one, two = flight.spans("booster.score_binned")
    assert one["attrs"] == {"backend": "scan", "rows": 256, "trees": 3, "built": True, "devices": 1, "sharded": False}
    assert two["attrs"]["built"] is False
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_jit_traces_rise_on_a_new_shape_and_not_on_a_repeat():
    import jax
    import jax.numpy as jnp

    from mmlspark_tpu.core import jit_cache

    jit_cache._listen_for_cache_events()
    obs.enable()

    def counters():
        c = obs.snapshot()["counters"]
        return c.get("jit.traces", 0.0), c.get("jit.trace_s", 0.0), c.get("device.compile_events{kind=trace}", 0.0)

    f = jax.jit(lambda x: jnp.tanh(x) * 2.0 + x.sum())  # tanh, sum: jitted functions traced INSIDE f's trace
    x, x2 = jnp.ones((17, 3)), jnp.ones((19, 3))  # made first: making an array can trace too
    base = counters()
    f(x).block_until_ready()
    new_shape = counters()
    f(x).block_until_ready()
    assert counters() == new_shape  # a repeat takes the fast path: no trace
    assert new_shape[0] - base[0] == 1  # f's own trace, not one per function inside it
    assert new_shape[2] - base[2] == 1
    assert new_shape[1] > base[1]
    f(x2).block_until_ready()
    assert counters()[0] - new_shape[0] == 1
    assert obs.snapshot()["counters"].get("jit.lower_s", 0.0) > 0
    assert obs.snapshot()["counters"].get("jit.backend_s", 0.0) > 0


def test_train_wall_gauge_is_read_off_the_spans_own_stamps():
    from mmlspark_tpu.engine.booster import Dataset, train

    obs.enable()
    X, y = _data()
    train(_params(), Dataset(X, label=y))
    recs = {r["name"]: r for r in flight.spans()}
    fit, baseline = recs["booster.train"], recs["booster.quality_baseline"]
    gauges = obs.snapshot()["gauges"]
    assert gauges["booster.train_wall_s"] == (baseline["end_ns"] - fit["start_ns"]) / 1e9
    assert gauges["booster.rows_per_s"] == 256 * 3 / gauges["booster.train_wall_s"]
