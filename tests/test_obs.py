"""mmlspark_tpu.obs — the rank-aware tracing + metrics subsystem (ISSUE 2).

Layers:
1. registry/span unit behavior (labels, percentiles, nesting, reset),
2. the near-zero-overhead-when-disabled contract (micro-bench + a budget
   check against a real tiny train),
3. end-to-end: tiny train with obs enabled round-trips through the JSONL
   export and ``tools.obs report`` with per-iteration booster spans,
   cache counters, and a native-call timer,
4. the collective watchdog fires a rank-stamped diagnostic on a seeded
   stuck collective,
5. rank tagging: per-rank export files under a multi-process harness,
6. instrumented serving: latency histogram + malformed/oversized counters.
"""

import json
import logging
import os
import socket
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.request
from datetime import datetime

import numpy as np
import pytest

from mmlspark_tpu import obs
from mmlspark_tpu.obs import flight, metrics, tracing

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _clean_obs_state():
    """Every test starts and ends disabled, empty, with no exporter, and
    with empty (but armed) flight-recorder rings."""
    obs.disable()
    obs.reset()
    flight.reset()
    yield
    obs.disable()
    obs.reset()
    tracing.close_exporter()
    flight.reset()


def _tiny_train(n_iter=4, seed=0):
    from mmlspark_tpu.engine.booster import Dataset, train

    rng = np.random.default_rng(seed)
    X = rng.normal(size=(256, 4))
    y = (X[:, 0] + 0.25 * rng.normal(size=256) > 0).astype(np.float64)
    params = {
        "objective": "binary",
        "num_iterations": n_iter,
        "num_leaves": 7,
        "min_data_in_leaf": 4,
    }
    return train(params, Dataset(X, label=y))


# ------------------------------------------------------------- registry


class TestRegistry:
    def test_counters_gauges_labels(self):
        r = metrics.Registry()
        r.inc("c")
        r.inc("c", 2.5)
        r.inc("c", 1, status=200)
        r.gauge("g", 7)
        r.gauge("g", 9)  # last write wins
        snap = r.snapshot()
        assert snap["counters"]["c"] == 3.5
        assert snap["counters"]["c{status=200}"] == 1.0
        assert snap["gauges"]["g"] == 9.0

    def test_label_named_name_does_not_collide(self):
        # inc/gauge/observe take name positionally-only so a label literally
        # called "name" (the watchdog uses one) can't shadow it
        r = metrics.Registry()
        r.inc("collective.stuck", name="host_allgather")
        assert r.snapshot()["counters"][
            "collective.stuck{name=host_allgather}"] == 1.0

    def test_histogram_summary(self):
        r = metrics.Registry()
        for v in range(100):
            r.observe("h", v / 100.0)
        h = r.snapshot()["histograms"]["h"]
        assert h["count"] == 100
        assert h["min"] == 0.0 and h["max"] == 0.99
        assert abs(h["p50"] - 0.5) < 0.05
        assert h["p95"] >= h["p50"] >= h["min"]

    def test_span_aggregates_and_reset(self):
        r = metrics.Registry()
        r.observe_span("s", 0.5)
        r.observe_span("s", 1.5)
        s = r.snapshot()["spans"]["s"]
        assert s["count"] == 2 and s["total_s"] == 2.0
        assert s["mean_s"] == 1.0 and s["max_s"] == 1.5
        r.reset()
        assert r.snapshot()["spans"] == {}


# ------------------------------------------------- enable/disable + spans


class TestSpans:
    def test_disabled_is_noop(self):
        assert not obs.enabled()
        # with the flight recorder ALSO disarmed, the pre-flight contract
        # holds exactly: one shared null context, zero allocation
        flight.set_armed(False)
        try:
            s1, s2 = obs.span("a"), obs.span("b", it=1)
            assert s1 is s2
            with s1:
                pass
        finally:
            flight.set_armed(True)
        # armed (the default): disabled-mode calls ring blackbox events
        # but never touch the metric registry
        with obs.span("a"):
            pass
        obs.inc("x")
        obs.gauge("y", 1)
        obs.observe("z", 1.0)
        obs.record_span("w", 0.1)
        snap = obs.snapshot()
        assert snap["enabled"] is False
        assert snap["counters"] == {} and snap["spans"] == {}
        assert flight.ring_stats()["total_events"] >= 4  # sb+se+ctr+span

    def test_enabled_records_nesting(self):
        obs.enable()
        with obs.span("outer"):
            time.sleep(0.01)
            with obs.span("inner"):
                pass
        snap = obs.snapshot()
        assert snap["enabled"] is True
        assert snap["spans"]["outer"]["count"] == 1
        assert snap["spans"]["outer"]["total_s"] >= 0.01
        assert "inner" in snap["spans"]

    def test_jsonl_export_round_trip(self, tmp_path):
        path = str(tmp_path / "run.jsonl")
        obs.enable(path)
        obs.inc("some.counter", 3)
        with obs.span("outer", kind="t"):
            with obs.span("inner"):
                pass
        obs.disable()  # flushes the final snapshot record + closes
        recs = [json.loads(l) for l in open(path) if l.strip()]
        spans = [r for r in recs if r["kind"] == "span"]
        by_name = {r["name"]: r for r in spans}
        assert by_name["inner"]["depth"] == 1
        assert by_name["inner"]["parent"] == "outer"
        assert by_name["outer"]["depth"] == 0
        assert by_name["outer"]["parent"] is None
        assert by_name["outer"]["attrs"] == {"kind": "t"}
        snaps = [r for r in recs if r["kind"] == "snapshot"]
        assert len(snaps) == 1
        assert snaps[0]["snapshot"]["counters"]["some.counter"] == 3.0

        # ...and the reader side agrees
        from tools.obs import build_report

        rep = build_report(path)
        assert rep["spans"]["inner"]["count"] == 1
        assert rep["snapshots"]["0"]["counters"]["some.counter"] == 3.0

    def test_malformed_export_lines_are_skipped(self, tmp_path):
        path = tmp_path / "torn.jsonl"
        good = json.dumps({"kind": "span", "name": "ok", "dur_s": 1.0,
                           "rank": 0})
        path.write_text(good + "\n{\"kind\": \"span\", \"na\n")
        from tools.obs import build_report

        rep = build_report(str(path))
        assert rep["spans"] == {"ok": {
            "count": 1, "total_s": 1.0, "max_s": 1.0, "mean_s": 1.0,
            "ranks": [0]}}


# ------------------------------------------------------ overhead contract


class TestDisabledOverhead:
    def test_per_call_cost_is_sub_microsecond_scale(self):
        assert not obs.enabled()
        n = 20_000
        # warm
        for _ in range(1000):
            with obs.span("overhead.probe"):
                pass
        t0 = time.perf_counter()
        for _ in range(n):
            with obs.span("overhead.probe", it=0):
                pass
            obs.inc("overhead.probe")
            obs.observe("overhead.probe_s", 0.0)
        per_call = (time.perf_counter() - t0) / (3 * n)
        # loose: a disabled entry point is one flag check; anything over
        # 20µs/call means the fast path grew real work
        assert per_call < 20e-6, f"disabled obs call costs {per_call * 1e6:.2f}µs"

    def test_train_overhead_budget_under_2_percent(self):
        # Count the instrumentation events a real train emits (enabled run),
        # then bound disabled-mode cost: events x per-call disabled cost must
        # stay under 2% of the train wall.  Loose by construction — both
        # sides are measured on this machine, and the budget uses the
        # *enabled* event count against the *disabled* per-call cost.
        _tiny_train()  # warm compile caches so wall is steady-state
        obs.enable()
        obs.reset()
        _tiny_train()
        snap = obs.snapshot()
        events = sum(s["count"] for s in snap["spans"].values())
        # counters that carry a quantity (nanoseconds, bytes sent, seconds
        # of tracing, the histogram ledger's passes, row-columns, flops and
        # element operations) are one event per `inc`, not `value` events
        events += sum(
            1 if k.endswith(("_bytes", "_s")) or k.startswith(("hist.", "jit.trace_s{")) else v
            for k, v in snap["counters"].items() if ".ns" not in k
        )
        # step telemetry rides the same budget: every histogram sample
        # (train.step_*_s et al) is one more enabled-mode event, and the
        # enabled run must actually have produced step records or the
        # event count understates what the telemetry costs
        events += sum(h["count"] for h in snap["histograms"].values())
        from mmlspark_tpu.obs import steps

        assert steps.records(), "enabled train produced no step records"
        obs.disable()
        obs.reset()

        t0 = time.perf_counter()
        _tiny_train()
        train_wall = time.perf_counter() - t0

        n = 20_000
        t0 = time.perf_counter()
        for _ in range(n):
            with obs.span("p"):
                pass
        per_call = (time.perf_counter() - t0) / n
        budget = 0.02 * train_wall
        cost = events * per_call
        assert cost < budget, (
            f"{events:.0f} events x {per_call * 1e6:.2f}µs = {cost * 1e3:.2f}ms"
            f" exceeds 2% of train wall ({budget * 1e3:.2f}ms)"
        )


# --------------------------------------------------- end-to-end tiny train


class TestTrainRoundTrip:
    def test_export_carries_booster_cache_and_native_signals(self, tmp_path):
        path = str(tmp_path / "train.jsonl")
        obs.enable(path)
        obs.reset()
        booster = _tiny_train(n_iter=5)
        snap = obs.snapshot()
        obs.disable()

        spans = snap["spans"]
        assert spans["booster.train"]["count"] == 1
        assert "booster.binning" in spans
        assert spans["booster.iteration"]["count"] >= booster.num_iterations
        # cache instrumentation saw the train
        assert any(k.startswith("jit_cache.") for k in snap["counters"])
        # at least one timed native ctypes call (binner fit/transform)
        native = [k for k in snap["counters"] if k.startswith("native.calls")]
        assert native, snap["counters"].keys()
        assert any(k.startswith("native.ns") for k in snap["counters"])
        # wall/throughput gauges
        assert snap["gauges"]["booster.train_wall_s"] > 0
        assert snap["gauges"]["booster.rows_per_s"] > 0

        # reader side: report aggregates the same run
        from tools.obs import build_report

        rep = build_report(path)
        assert rep["spans"]["booster.iteration"]["count"] >= 5
        last = rep["snapshots"]["0"]
        assert any(k.startswith("native.calls") for k in last["counters"])

    def test_report_cli_json(self, tmp_path, capsys):
        path = str(tmp_path / "cli.jsonl")
        obs.enable(path)
        with obs.span("x"):
            pass
        obs.disable()
        from tools.obs.__main__ import main

        assert main(["report", path, "--json"]) == 0
        rep = json.loads(capsys.readouterr().out)
        assert rep["spans"]["x"]["count"] == 1

        assert main(["report", str(tmp_path / "missing.jsonl")]) == 2


# -------------------------------------------------------------- watchdog


class TestWatchdog:
    def test_barks_on_seeded_stuck_collective(self, caplog):
        with caplog.at_level(logging.WARNING, logger="mmlspark_tpu"):
            with obs.collective_watchdog("seeded", timeout_s=0.05):
                time.sleep(0.2)
        stuck = [r for r in caplog.records
                 if "stuck in collective seeded" in r.getMessage()]
        assert stuck, [r.getMessage() for r in caplog.records]
        # rank-stamped: the message leads with this process's rank
        assert stuck[0].getMessage().startswith(
            f"rank {obs.process_index()}: ")
        # completion line reports the hang is over
        assert any("collective seeded completed" in r.getMessage()
                   for r in caplog.records)
        # the stuck counter records even with metrics disabled — it's the
        # diagnostic you need precisely when you didn't enable obs
        snap = obs.snapshot()
        assert snap["counters"]["collective.stuck{name=seeded}"] >= 1

    def test_silent_on_fast_collective(self, caplog):
        with caplog.at_level(logging.WARNING, logger="mmlspark_tpu"):
            with obs.collective_watchdog("quick", timeout_s=5.0):
                pass
        assert not caplog.records

    def test_zero_timeout_disables(self, caplog):
        with caplog.at_level(logging.WARNING, logger="mmlspark_tpu"):
            with obs.collective_watchdog("off", timeout_s=0):
                time.sleep(0.05)
        assert not caplog.records

    def test_records_metrics_when_enabled(self):
        obs.enable()
        with obs.collective_watchdog("host_allgather", timeout_s=60):
            pass
        snap = obs.snapshot()
        assert snap["counters"]["collective.calls{name=host_allgather}"] == 1
        assert snap["histograms"][
            "collective.duration_s{name=host_allgather}"]["count"] == 1
        assert snap["spans"]["collective.host_allgather"]["count"] == 1


# ---------------------------------------------------------- rank tagging


_CHILD = """\
import json
from mmlspark_tpu import obs
with obs.span("child.work"):
    pass
print(json.dumps({"rank": obs.process_index(),
                  "path": obs.export_path()}))
obs.disable()
"""


class TestRankTagging:
    def test_env_rank_stamps_snapshot_and_spans(self, tmp_path, monkeypatch):
        monkeypatch.setenv("MMLSPARK_TPU_PROCESS_ID", "3")
        obs.reset()  # drops the cached rank so the env var is re-read
        path = str(tmp_path / "r.jsonl")
        obs.enable(path)
        with obs.span("tagged"):
            pass
        snap = obs.snapshot()
        obs.disable()
        assert snap["process_index"] == 3
        recs = [json.loads(l) for l in open(path) if l.strip()]
        assert all(r["rank"] == 3 for r in recs)

    def test_multiprocess_harness_per_rank_files(self, tmp_path):
        # Two real processes share one MMLSPARK_TPU_OBS base path; each must
        # write its own .rank<R> file (no interleaving) and the report must
        # merge both.  obs imports no heavy deps, so the children are fast.
        base = str(tmp_path / "mp.jsonl")
        procs = []
        for rank in range(2):
            env = dict(
                os.environ,
                MMLSPARK_TPU_OBS=base,
                MMLSPARK_TPU_PROCESS_ID=str(rank),
                MMLSPARK_TPU_NUM_PROCESSES="2",
                PYTHONPATH=REPO,
            )
            procs.append(subprocess.Popen(
                [sys.executable, "-c", _CHILD], env=env, cwd=REPO,
                stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
            ))
        outs = []
        for p in procs:
            out, err = p.communicate(timeout=60)
            assert p.returncode == 0, err
            outs.append(json.loads(out.strip().splitlines()[-1]))
        assert {o["rank"] for o in outs} == {0, 1}
        assert sorted(o["path"] for o in outs) == [
            base + ".rank0", base + ".rank1"]

        from tools.obs import build_report, discover_files

        assert discover_files(base) == [base + ".rank0", base + ".rank1"]
        rep = build_report(base)
        assert rep["ranks"] == [0, 1]
        assert rep["spans"]["child.work"]["count"] == 2
        assert rep["spans"]["child.work"]["ranks"] == [0, 1]
        assert set(rep["snapshots"]) == {"0", "1"}


# ------------------------------------------------------------- serving


def _post(host, port, payload):
    req = urllib.request.Request(
        f"http://{host}:{port}/", data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"}, method="POST",
    )
    with urllib.request.urlopen(req, timeout=30) as r:
        return r.status, r.read()


def _wait_counter(key, value=1, timeout=5.0):
    """Counters increment on the handler thread after the reply bytes are
    already on the wire — poll until the EXPECTED count lands (existence
    alone races: the first request creates the key while later ones are
    still mid-increment)."""
    deadline = time.time() + timeout
    while time.time() < deadline:
        snap = obs.snapshot()
        if snap["counters"].get(key, 0) >= value:
            return snap
        time.sleep(0.01)
    return obs.snapshot()


class TestServingInstrumentation:
    def _echo_server(self):
        from mmlspark_tpu.io.http.serving import HTTPServer, serve_transformer

        server = HTTPServer("127.0.0.1", 0).start()
        stop = threading.Event()

        def transform(df):
            rows = df.collect()
            for row in rows:
                body = (row["request"].get("entity") or {}).get("content")
                row["response"] = json.loads(body.decode()) if body else {}
            return df.withColumn("response", [r["response"] for r in rows])

        t = threading.Thread(
            target=serve_transformer, args=(server, transform, stop),
            daemon=True,
        )
        t.start()
        return server, stop

    def test_latency_histogram_and_status_counters(self):
        obs.enable()
        server, stop = self._echo_server()
        try:
            for i in range(3):
                status, body = _post(server.host, server.port, {"v": i})
                assert status == 200
            snap = _wait_counter("http.requests{status=200}", value=3)
            assert snap["counters"]["http.requests{status=200}"] == 3
            h = snap["histograms"]["http.request_latency_s"]
            assert h["count"] == 3 and h["max"] > 0
            assert "http.queue_depth" in snap["gauges"]
        finally:
            stop.set()
            server.stop()

    def test_malformed_content_length_counted(self):
        obs.enable()
        server, stop = self._echo_server()
        try:
            # urllib won't emit a bogus Content-Length; speak raw HTTP
            with socket.create_connection(
                    (server.host, server.port), timeout=10) as s:
                s.sendall(b"POST / HTTP/1.1\r\nHost: x\r\n"
                          b"Content-Length: banana\r\n\r\n")
                reply = s.recv(4096)
            assert b"400" in reply.split(b"\r\n", 1)[0]
            snap = _wait_counter("http.requests{status=400}")
            assert snap["counters"]["http.malformed"] == 1
            assert snap["counters"]["http.requests{status=400}"] == 1
        finally:
            stop.set()
            server.stop()

    def test_oversized_entity_counted(self, monkeypatch):
        from mmlspark_tpu.io.http import serving

        monkeypatch.setattr(serving, "_MAX_ENTITY_BYTES", 64)
        obs.enable()
        server, stop = self._echo_server()
        try:
            with pytest.raises(urllib.error.HTTPError) as ei:
                _post(server.host, server.port, {"pad": "x" * 256})
            assert ei.value.code == 413
            snap = _wait_counter("http.requests{status=413}")
            assert snap["counters"]["http.oversized"] == 1
            assert snap["counters"]["http.requests{status=413}"] == 1
        finally:
            stop.set()
            server.stop()


# ------------------------------------------------- satellites: timer, meta


class TestSatellites:
    def test_timer_records_obs_spans_and_keeps_lastTimings(self):
        from mmlspark_tpu.core.frame import DataFrame
        from mmlspark_tpu.stages import DropColumns, Timer

        obs.enable()
        df = DataFrame({"a": [1.0], "b": [2.0]})
        t = Timer(logToScala=False).setStage(DropColumns(cols=["b"]))
        out = t.transform(df)
        assert out.columns == ["a"]
        assert len(t.lastTimings) == 1  # the pre-obs API survives
        snap = obs.snapshot()
        assert snap["spans"]["stage.transform"]["count"] == 1

    def test_pipeline_metadata_saved_at_iso8601(self, tmp_path):
        from mmlspark_tpu.stages import DropColumns

        p = str(tmp_path / "stage")
        DropColumns(cols=["b"]).save(p)
        meta = json.load(open(os.path.join(p, "metadata.json")))
        # machine twin stays; the human twin parses as tz-aware ISO-8601
        assert isinstance(meta["timestamp"], float)
        dt = datetime.fromisoformat(meta["saved_at"])
        assert dt.tzinfo is not None
        assert abs(dt.timestamp() - meta["timestamp"]) < 2.0

    def test_env_init_enables_and_exports(self, tmp_path):
        # MMLSPARK_TPU_OBS=<path> at import time enables + exports, and the
        # atexit hook lands the final snapshot without an explicit disable()
        path = str(tmp_path / "envinit.jsonl")
        child = (
            "from mmlspark_tpu import obs\n"
            "assert obs.enabled()\n"
            "with obs.span('env.work'):\n"
            "    pass\n"
        )
        env = dict(os.environ, MMLSPARK_TPU_OBS=path, PYTHONPATH=REPO)
        r = subprocess.run(
            [sys.executable, "-c", child], env=env, cwd=REPO,
            capture_output=True, text=True, timeout=60,
        )
        assert r.returncode == 0, r.stderr
        recs = [json.loads(l) for l in open(path) if l.strip()]
        kinds = [r["kind"] for r in recs]
        assert "span" in kinds and kinds[-1] == "snapshot"


# ------------------------------------------- step telemetry (ISSUE 17)


class TestStepTelemetry:
    def test_train_emits_attributed_step_records(self, tmp_path):
        from mmlspark_tpu.obs import steps

        path = str(tmp_path / "steps.jsonl")
        obs.enable(path)
        _tiny_train(n_iter=5)
        recs = steps.records()
        assert recs, "enabled train produced no step records"
        kinds = {r["kind"] for r in recs}
        assert kinds & {"scan", "legacy"}, kinds
        # attribution closes: compute + collective + stall == wall (the
        # parts are derived by subtraction and clamping, so equality is
        # by construction — 10% covers float split across derived steps)
        for r in recs:
            parts = r["compute_s"] + r["collective_s"] + r["ingest_stall_s"]
            assert abs(parts - r["wall_s"]) <= 0.1 * r["wall_s"] + 1e-9, r
        snap = obs.snapshot()
        assert any(k.startswith("train.steps{") for k in snap["counters"])
        assert any(k.startswith("train.step_wall_s")
                   for k in snap["histograms"])
        obs.disable()

        # export + report round-trip: records land as kind=step lines and
        # the report folds them into the steps section
        from tools.obs import build_report

        rep = build_report(path)
        assert rep["step_records"], "no step lines in the export"
        total = sum(s["count"] for s in rep["steps"].values())
        assert total == len(recs)

    def test_streaming_multichunk_attribution_sums(self, tmp_path):
        from mmlspark_tpu.data import (
            RowGroupSource,
            train_streaming,
            write_row_group_shards,
        )
        from mmlspark_tpu.obs import steps

        rng = np.random.default_rng(7)
        X = rng.normal(size=(3000, 4)).astype(np.float32)
        y = (X[:, 0] + 0.25 * rng.normal(size=3000) > 0).astype(np.float64)
        src = RowGroupSource(write_row_group_shards(
            str(tmp_path / "rg"), X, y, rows_per_group=800))
        params = {"objective": "binary", "num_iterations": 4,
                  "num_leaves": 7, "max_bin": 63, "seed": 1}
        obs.enable()
        train_streaming(params, src, chunk_rows=512, exact_budget=32768)
        recs = steps.records()
        ingest = [r for r in recs if r["kind"] == "ingest"]
        assert len(ingest) >= 3, "expected a multi-chunk ingest"
        # each chunk's attribution parts must sum to its wall within 10%
        for r in ingest:
            parts = r["compute_s"] + r["collective_s"] + r["ingest_stall_s"]
            assert abs(parts - r["wall_s"]) <= 0.1 * r["wall_s"] + 1e-9, r
        # training steps rode along too (streamed train ends in the same
        # fused-scan/legacy loop as the in-memory path)
        assert {r["kind"] for r in recs} & {"scan", "legacy"}

    def test_straggler_gauges_from_fabricated_peers(self, monkeypatch):
        import jax

        from mmlspark_tpu.obs import steps

        obs.enable()
        st = steps.begin()  # one completed step so a mark exists
        steps.end(st, "legacy", 0)
        monkeypatch.setattr(jax, "process_count", lambda: 2)
        anchor_ts = time.time()
        anchor_mono = time.monotonic_ns() / 1e9
        # same anchor on both ranks, rank 1's mark 300ms later — exactly
        # the shape the receiver-side offset reconstruction expects
        peers = [
            [0.0, 100.0, anchor_ts, anchor_mono],
            [1.0, 100.3, anchor_ts, anchor_mono],
        ]
        monkeypatch.setattr(steps, "_exchange_marks",
                            lambda epoch, row, nproc: peers)
        steps._check_straggler()
        snap = obs.snapshot()
        skew = snap["gauges"]["train.straggler_skew_ms{rank=1}"]
        assert abs(skew - 300.0) < 0.01, skew
        assert snap["gauges"]["train.straggler_skew_ms{rank=0}"] == 0.0
        assert snap["counters"]["train.straggler_events{rank=1}"] == 1.0

    def test_straggler_silent_below_threshold(self, monkeypatch):
        import jax

        from mmlspark_tpu.obs import steps

        obs.enable()
        st = steps.begin()
        steps.end(st, "legacy", 0)
        monkeypatch.setattr(jax, "process_count", lambda: 2)
        anchor_ts = time.time()
        anchor_mono = time.monotonic_ns() / 1e9
        peers = [
            [0.0, 100.0, anchor_ts, anchor_mono],
            [1.0, 100.01, anchor_ts, anchor_mono],  # 10ms < 50ms default
        ]
        monkeypatch.setattr(steps, "_exchange_marks",
                            lambda epoch, row, nproc: peers)
        steps._check_straggler()
        snap = obs.snapshot()
        assert not any("straggler" in k for k in snap["gauges"])
        assert not any("straggler" in k for k in snap["counters"])

    def test_ingest_steps_never_drive_the_exchange(self, monkeypatch):
        # The PR 1 deadlock class: ingest chunk counts are per-rank
        # data-dependent (round-robin shards × row-dependent chunking),
        # so an ingest-driven cadence would have ranks executing
        # different numbers of collectives — one blocking forever in a
        # gather no peer enters.  Only lockstep training kinds may fire.
        import jax

        from mmlspark_tpu.obs import steps

        obs.enable()
        monkeypatch.setattr(steps, "_STRAGGLER_EVERY", 1)
        monkeypatch.setattr(jax, "process_count", lambda: 2)
        calls = []

        def fake_exchange(epoch, row, nproc):
            calls.append((epoch, list(row)))
            return [list(row)]

        monkeypatch.setattr(steps, "_exchange_marks", fake_exchange)
        for i in range(5):
            steps.end(steps.begin(), "ingest", i)
        assert not calls, "data-dependent ingest steps entered a collective"
        steps.end(steps.begin(), "legacy", 0)
        assert len(calls) == 1, "training step did not drive the exchange"

    def test_exchange_wait_not_attributed_as_collective_wait(
            self, monkeypatch):
        # A fast rank blocks in the exchange for the laggard's full
        # delay; feeding that wait to note_collective would inflate
        # train.step_collective_s exactly when a straggler exists.  The
        # exchange rides the coordination-service KV store — never a
        # watchdog-wrapped collective — so its wait must leave the
        # attribution accumulator untouched, while an ordinary
        # collective on the same thread still feeds.
        import jax

        from mmlspark_tpu.obs import steps
        from mmlspark_tpu.obs.watchdog import collective_watchdog

        obs.enable()
        steps.end(steps.begin(), "legacy", 0)  # a mark exists
        monkeypatch.setattr(jax, "process_count", lambda: 2)

        def slow_exchange(epoch, row, nproc):
            time.sleep(0.01)  # the laggard shows up 10ms late
            return [list(row), [1.0, row[1] + 0.3, row[2], row[3]]]

        monkeypatch.setattr(steps, "_exchange_marks", slow_exchange)
        before = steps._collective_wait_ns
        steps._check_straggler()
        assert steps._collective_wait_ns == before, (
            "straggler exchange's own wait fed the step attribution")
        # an ordinary collective on the same thread still feeds
        with collective_watchdog("host_allgather", timeout_s=0):
            time.sleep(0.001)
        assert steps._collective_wait_ns > before

    def test_exchange_marks_kv_roundtrip(self, monkeypatch):
        # The exchange transport against a fake coordination-service
        # client: publish-then-collect, previous-epoch cleanup, and the
        # timeout path degrading to a skipped round (never a hang).
        import jax
        from jax._src import distributed as jax_distributed

        from mmlspark_tpu.obs import steps

        class _FakeClient:
            def __init__(self):
                self.store: dict = {}
                self.deleted: list = []

            def key_value_set(self, key, value):
                assert key not in self.store, key
                self.store[key] = value

            def blocking_key_value_get(self, key, timeout_ms):
                if key not in self.store:
                    raise TimeoutError(key)  # peer never published
                return self.store[key]

            def key_value_delete(self, key):
                self.deleted.append(key)
                self.store.pop(key, None)

        fake = _FakeClient()
        monkeypatch.setattr(jax_distributed.global_state, "client", fake)
        monkeypatch.setattr(steps, "_prev_kv_key", None)
        pfx = steps._KV_PREFIX
        fake.key_value_set(f"{pfx}/6/1", "1.0,100.3,5.0,4.0")
        rows = steps._exchange_marks(6, [0.0, 100.0, 5.0, 4.0], 2)
        assert sorted(r[0] for r in rows) == [0.0, 1.0]
        assert [r for r in rows if r[0] == 1.0][0][1] == 100.3
        assert not fake.deleted  # first round: nothing to clean up yet
        # the next round retires this rank's previous key
        fake.key_value_set(f"{pfx}/12/1", "1.0,200.3,5.0,4.0")
        steps._exchange_marks(12, [0.0, 200.0, 5.0, 4.0], 2)
        assert fake.deleted == [f"{pfx}/6/0"]
        # a peer that never publishes → bounded timeout swallowed by
        # _check_straggler's best-effort guard, no gauges emitted
        obs.enable()
        steps.end(steps.begin(), "legacy", 0)
        monkeypatch.setattr(jax, "process_count", lambda: 2)
        steps._check_straggler()
        snap = obs.snapshot()
        assert not any("straggler" in k for k in snap["gauges"])

    def test_zero_live_bytes_is_a_reading(self, monkeypatch):
        from mmlspark_tpu.obs import device

        obs.enable()

        class _FakeJax:
            _arrays: list = []

            @staticmethod
            def local_devices():
                return []  # no memory_stats anywhere

            @classmethod
            def live_arrays(cls):
                return cls._arrays

        monkeypatch.setitem(sys.modules, "jax", _FakeJax)
        # first poll before any arrays exist: 0.0 is a valid READING,
        # not absence of signal
        s = device.poll(force=True)
        assert s is not None and s["live_buffer_bytes"] == 0.0

        class _Buf:
            nbytes = 1024

        _FakeJax._arrays = [_Buf()]
        s2 = device.poll(force=True)
        assert s2 is not None and s2["live_buffer_bytes"] == 1024.0

    def test_device_gauges_polled_at_step_boundaries(self):
        obs.enable()
        _tiny_train(n_iter=4)
        snap = obs.snapshot()
        # CPU has no memory_stats() but does expose live_arrays(); either
        # signal satisfies the poll contract (TPU/GPU adds hbm_* gauges)
        assert any(k.startswith("device.") for k in snap["gauges"]), (
            snap["gauges"].keys())
        # compile-event counters fired during the (cold or warm) train
        from mmlspark_tpu.obs import device

        sec = device.summary(snap)
        assert "memory" in sec and sec["memory"]
