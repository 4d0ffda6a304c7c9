"""Serving benchmark: seed fixed-batch loop vs the mmlspark_tpu.serve engine.

Gives serving a perf trajectory like training has (BENCH-style JSON):

- **baseline** — the seed ``serve_transformer`` micro-batch loop: drain
  whatever is queued, predict the UNPADDED batch.  Under variable request
  sizes every novel total-row-count is a fresh XLA compile, so the loop
  stalls for tens-to-hundreds of ms at a time.
- **dynamic**  — :class:`mmlspark_tpu.serve.ServingApp`: deadline-aware
  batching padded to pre-warmed bucket shapes, so the steady state never
  compiles.  A hot-swap fires mid-run (the acceptance gate is zero 5xx
  across it).
- **overload** — an open-loop phase at 2× the measured dynamic throughput
  against a deliberately small admission envelope, to exercise load
  shedding (shed rate = 429s / attempts; 5xx must stay zero).

Both phases serve the same model from the same saved directory and the
same traffic shape (closed-loop clients, variable instances/request).

Usage::

    JAX_PLATFORMS=cpu python -m tools.bench_serving [--smoke] [--json PATH]
        [--duration S] [--clients N] [--seed K]

``--smoke`` shrinks the run for CI and exits non-zero unless the serving
invariants hold (zero 5xx incl. across the swap, non-empty /metrics).

``--shift`` runs the model-quality drift scenario instead of the
baseline/overload phases: steady traffic drawn from the training
distribution (the drift monitor must stay silent), then covariate-shifted
traffic (+3σ on every feature — the monitor must raise a drift alarm,
drop a flight-recorder dump, and surface the alarm on /driftz and
Prometheus).  Monitor cost is measured report-only by re-running the
steady phase with the monitor disabled.  With ``--smoke`` the drift
invariants are hard-asserted for CI.

``--cold`` runs the replica cold-to-ready scenario (ISSUE 11) instead:
two fresh replica PROCESSES share one initially-empty jit-cache dir.
Replica A pays the bucket compiles and persists the ``aot-*``
executables; replica B — the steady-state "new replica joins the
fleet" case — deserializes them.  Per leg the JSON records
``proc_to_ready_s`` (parent wall: process spawn → first ``/readyz``
200, so interpreter + imports are in) and ``app_ready_s`` (child wall:
replica main entry → prewarmed-ready, the part model/compile work
scales).  With ``--smoke`` the mechanism is hard-asserted (both legs
ready, warm leg hit the AOT artifacts); the sub-second warm
``app_ready_s`` target is recorded and enforced like the ingest gate —
hard on accelerators, advisory on ``backend: cpu``.

``--fleet`` runs the multi-model fleet scenario (ISSUE 13) instead:
phase 1 builds a :class:`~mmlspark_tpu.serve.CoResidentGroup` of 4
tenants and measures ONE mixed-batch super-table dispatch against 4
sequential per-model dispatches at an equal row budget (per-model
outputs must stay bitwise-identical; the >=2x aggregate-throughput gate
is hard on accelerators, advisory on cpu), and records the measured
fp16/int8 leaf-table AUC drift.  Phase 2 spawns a
:class:`~mmlspark_tpu.serve.FleetRouter` with two replica PROCESSES
each co-hosting 3 tenants, runs per-tenant closed-loop traffic through
the router, fires a rolling hot-swap of one tenant mid-window, and
gates on zero 5xx plus the unswapped tenants' p99 staying within 20%
of steady state.  The report is emitted as a ``SERVE_FLEET`` JSON line.
"""

from __future__ import annotations

import argparse
import json
import os
import random
import socket
import subprocess
import sys
import tempfile
import threading
import time
import urllib.error
import urllib.request

import numpy as np

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

N_FEATURES = 4
MAX_INSTANCES = 24  # per request; keeps baseline shape-space honest


# --------------------------------------------------------------------------
# HTTP helpers
# --------------------------------------------------------------------------
def _post(url: str, payload: dict, timeout: float = 30.0):
    """(status, latency_s); urllib errors map to their status or 599."""
    data = json.dumps(payload).encode()
    req = urllib.request.Request(
        url, data=data, headers={"Content-Type": "application/json"},
        method="POST",
    )
    t0 = time.perf_counter()
    try:
        with urllib.request.urlopen(req, timeout=timeout) as r:
            r.read()
            return r.status, time.perf_counter() - t0
    except urllib.error.HTTPError as e:
        e.read()
        return e.code, time.perf_counter() - t0
    except (urllib.error.URLError, OSError):
        return 599, time.perf_counter() - t0


def _pct(sorted_vals, p):
    if not sorted_vals:
        return 0.0
    i = min(len(sorted_vals) - 1, int(round(p * (len(sorted_vals) - 1))))
    return sorted_vals[i]


class _LoadResult:
    def __init__(self):
        self.lock = threading.Lock()
        self.latencies = []
        self.statuses = {}

    def record(self, status, latency):
        with self.lock:
            self.statuses[status] = self.statuses.get(status, 0) + 1
            if status == 200:
                self.latencies.append(latency)

    def summary(self, wall_s):
        lat = sorted(self.latencies)
        n_ok = len(lat)
        total = sum(self.statuses.values())
        # 599 is this client's own transport-error sentinel (reset/refused
        # under churn), not a server response — report it separately so
        # the zero-5xx gate only trips on genuine server errors.
        fivexx = sum(v for k, v in self.statuses.items() if 500 <= k < 599)
        shed = self.statuses.get(429, 0)
        return {
            "requests": total,
            "ok": n_ok,
            "shed": shed,
            "fivexx": fivexx,
            "transport_errors": self.statuses.get(599, 0),
            "statuses": {str(k): v for k, v in sorted(self.statuses.items())},
            "wall_s": round(wall_s, 3),
            "throughput_rps": round(n_ok / wall_s, 1) if wall_s else 0.0,
            "shed_rate": round(shed / total, 4) if total else 0.0,
            "p50_ms": round(_pct(lat, 0.50) * 1e3, 2),
            "p95_ms": round(_pct(lat, 0.95) * 1e3, 2),
            "p99_ms": round(_pct(lat, 0.99) * 1e3, 2),
        }


def _closed_loop(url, duration_s, clients, seed, feature_rng):
    """Each client fires back-to-back requests with 1..MAX_INSTANCES rows."""
    res = _LoadResult()
    stop_at = time.monotonic() + duration_s

    def worker(wid):
        rng = random.Random(seed * 1000 + wid)
        while time.monotonic() < stop_at:
            k = rng.randint(1, MAX_INSTANCES)
            rows = feature_rng.normal(size=(k, N_FEATURES)).tolist()
            res.record(*_post(url, {"instances": rows}))

    threads = [
        threading.Thread(target=worker, args=(i,), daemon=True)
        for i in range(clients)
    ]
    t0 = time.monotonic()
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=duration_s + 60)
    return res.summary(time.monotonic() - t0)


def _open_loop(url, duration_s, target_rps, workers, seed, feature_rng):
    """Paced arrivals at ``target_rps`` split across a worker pool; a
    worker that falls >1 s behind schedule skips (client saturated) so
    the measurement stays open-loop."""
    res = _LoadResult()
    t0 = time.monotonic()
    skipped = [0]

    def worker(wid):
        rng = random.Random(seed * 7777 + wid)
        j = wid
        while True:
            sched = t0 + j / target_rps
            j += workers
            now = time.monotonic()
            if sched - t0 > duration_s:
                return
            if now < sched:
                time.sleep(sched - now)
            elif now - sched > 1.0:
                with res.lock:
                    skipped[0] += 1
                continue
            k = rng.randint(1, MAX_INSTANCES)
            rows = feature_rng.normal(size=(k, N_FEATURES)).tolist()
            res.record(*_post(url, {"instances": rows}, timeout=10.0))

    threads = [
        threading.Thread(target=worker, args=(i,), daemon=True)
        for i in range(workers)
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=duration_s + 60)
    out = res.summary(time.monotonic() - t0)
    out["target_rps"] = round(target_rps, 1)
    out["client_skipped"] = skipped[0]
    return out


class _ShiftedRng:
    """Feature source for the drift phase: the same normal draws the
    closed-loop clients use, displaced by ``shift`` on every feature."""

    def __init__(self, rng, shift):
        self._rng = rng
        self._shift = float(shift)

    def normal(self, size=None):
        return self._rng.normal(size=size) + self._shift


# --------------------------------------------------------------------------
# fixtures
# --------------------------------------------------------------------------
def _train_and_save(tmp, seed):
    from mmlspark_tpu.core.frame import DataFrame
    from mmlspark_tpu.models.lightgbm import LightGBMRegressor

    rng = np.random.default_rng(seed)
    X = rng.normal(size=(400, N_FEATURES))
    y = X[:, 0] * 2.0 + np.sin(X[:, 1]) + 0.1 * rng.normal(size=400)
    model = LightGBMRegressor(
        numIterations=8, numLeaves=8, minDataInLeaf=4
    ).fit(DataFrame({"features": list(X), "label": y}))
    path = os.path.join(tmp, f"model_v{seed}")
    model.save(path)
    return path


def _seed_loop_server(model_path, batch_size=64):
    """The seed serving shape: HTTPServer + serve_transformer, predicting
    each micro-batch at its natural (unpadded) row count."""
    from mmlspark_tpu.io.http.serving import HTTPServer, serve_transformer
    from mmlspark_tpu.models.lightgbm import LightGBMRegressionModel

    booster = LightGBMRegressionModel.load(model_path).getBooster()

    def transform(batch):
        rows = batch.collect()
        feats, counts = [], []
        for r in rows:
            body = (r["request"].get("entity") or {}).get("content")
            inst = np.asarray(json.loads(body.decode())["instances"])
            feats.append(inst)
            counts.append(len(inst))
        X = np.concatenate(feats, axis=0)
        preds = booster.predict(X)  # unpadded: every new shape compiles
        out, off = [], 0
        for k in counts:
            out.append({"predictions": preds[off:off + k].tolist()})
            off += k
        return batch.withColumn("response", out)

    server = HTTPServer().start()
    stop = threading.Event()
    thread = threading.Thread(
        target=serve_transformer, args=(server, transform, stop, batch_size),
        daemon=True,
    )
    thread.start()
    return server, stop, thread


# --------------------------------------------------------------------------
# drift scenario (--shift)
# --------------------------------------------------------------------------
def _drift_counts(monitor, route):
    d = monitor.describe()["routes"].get(route, {})
    counts = d.get("alarm_counts") or {}
    return {
        "drift": counts.get("feature_drift", 0) + counts.get("score_drift", 0),
        "by_kind": dict(counts),
        "feature_excess_psi_max": (d.get("feature_drift") or {}).get(
            "excess_psi_max", 0.0),
        "score_excess_psi": (d.get("score_drift") or {}).get(
            "excess_psi", 0.0),
    }


def _run_shift(args, tmp, report) -> int:
    from mmlspark_tpu import obs
    from mmlspark_tpu.serve import ServingApp

    flight_dir = os.path.join(tmp, "flight")
    os.environ["MMLSPARK_TPU_OBS_FLIGHT_DIR"] = flight_dir
    # every drift alarm should dump, even back-to-back in a short run
    os.environ["MMLSPARK_TPU_OBS_FLIGHT_MIN_INTERVAL_S"] = "0"

    v1 = _train_and_save(tmp, args.seed)
    obs.reset()
    app = ServingApp(max_wait_ms=10.0).start()
    app.add_model("bench", path=v1)
    url = f"{app.url}/models/bench/predict"
    if app.monitor is None:
        print("[serving] --shift needs the quality monitor "
              "(unset MMLSPARK_TPU_SERVE_MONITOR)", file=sys.stderr)
        app.stop()
        return 1

    # ---- steady phase: training-distribution traffic, monitor silent ---
    steady = _closed_loop(
        url, args.duration, args.clients, args.seed,
        np.random.default_rng(args.seed + 1),
    )
    deadline = time.monotonic() + 5.0
    while time.monotonic() < deadline and app.monitor._pending.qsize():
        time.sleep(0.2)
    time.sleep(1.5)  # one monitor eval tick past the last ingest
    steady["quality"] = _drift_counts(app.monitor, "bench")
    report["steady"] = steady
    print(f"[serving] steady: {steady['throughput_rps']} rps  "
          f"p50={steady['p50_ms']}ms  "
          f"excess_psi={steady['quality']['feature_excess_psi_max']:.3f}  "
          f"drift_alarms={steady['quality']['drift']}")

    # ---- shifted phase: +3σ covariate shift, alarm must fire -----------
    shifted = _closed_loop(
        url, args.duration, args.clients, args.seed + 99,
        _ShiftedRng(np.random.default_rng(args.seed + 2), 3.0),
    )
    deadline = time.monotonic() + 15.0
    while time.monotonic() < deadline:
        if app.monitor.alarm_count("bench") > steady["quality"]["drift"]:
            break
        time.sleep(0.5)
    shifted["quality"] = _drift_counts(app.monitor, "bench")
    report["shifted"] = shifted
    print(f"[serving] shifted (+3σ): {shifted['throughput_rps']} rps  "
          f"excess_psi={shifted['quality']['feature_excess_psi_max']:.3f}  "
          f"drift_alarms={shifted['quality']['drift']}")

    # ---- surfacing: /driftz, Prometheus, flight dump -------------------
    with urllib.request.urlopen(app.url + "/driftz", timeout=10) as r:
        driftz = json.loads(r.read().decode())
    with urllib.request.urlopen(
        app.url + "/metrics?format=prometheus", timeout=10
    ) as r:
        prom_body = r.read().decode()
    report["driftz"] = driftz
    report["prometheus_has_quality"] = (
        "mmlspark_tpu_quality_feature_psi_max" in prom_body
    )
    try:
        dumps = sorted(os.listdir(flight_dir))
    except OSError:
        dumps = []
    report["flight_dumps"] = dumps
    # the quality.*/slo.* series land under "obs" so the report feeds
    # ``python -m tools.obs drift <this json>`` directly
    report["obs"] = obs.snapshot()
    app.stop()

    # ---- monitor overhead, report-only ---------------------------------
    obs.reset()
    bare = ServingApp(max_wait_ms=10.0, monitor=False).start()
    bare.add_model("bench", path=v1)
    no_monitor = _closed_loop(
        f"{bare.url}/models/bench/predict",
        args.duration, args.clients, args.seed,
        np.random.default_rng(args.seed + 1),
    )
    bare.stop()
    report["no_monitor"] = no_monitor
    if no_monitor["p50_ms"]:
        report["monitor_p50_overhead_pct"] = round(
            100.0 * (steady["p50_ms"] - no_monitor["p50_ms"])
            / no_monitor["p50_ms"], 1,
        )
        print(f"[serving] monitor p50 overhead: "
              f"{report['monitor_p50_overhead_pct']}% "
              f"({no_monitor['p50_ms']}ms -> {steady['p50_ms']}ms)")

    out = json.dumps(report, indent=2, default=str)
    print(out)
    if args.json_path:
        with open(args.json_path, "w") as f:
            f.write(out)

    if args.smoke:
        failures = []
        if steady["fivexx"] or shifted["fivexx"]:
            failures.append("drift phases saw 5xx responses")
        if not (steady["ok"] and shifted["ok"]):
            failures.append("a drift phase served zero requests")
        if steady["quality"]["drift"]:
            failures.append(
                "drift alarm fired on UNSHIFTED traffic "
                f"(kinds {steady['quality']['by_kind']})"
            )
        if shifted["quality"]["drift"] < 1:
            failures.append(
                "no drift alarm on +3σ shifted traffic "
                f"(excess_psi="
                f"{shifted['quality']['feature_excess_psi_max']:.3f})"
            )
        if not dumps:
            failures.append("drift alarm produced no flight-recorder dump")
        if not report["prometheus_has_quality"]:
            failures.append("quality gauges missing from Prometheus export")
        if driftz.get("status") != "ok" or "bench" not in (
            driftz.get("routes") or {}
        ):
            failures.append("/driftz did not report the bench route")
        if failures:
            print("[serving] SHIFT SMOKE FAILED: " + "; ".join(failures),
                  file=sys.stderr)
            return 1
        print("[serving] shift smoke OK")
    return 0


# --------------------------------------------------------------------------
# replica cold-to-ready scenario (--cold)
# --------------------------------------------------------------------------
def _run_replica(args) -> int:
    """Child leg of ``--cold``: ONE serving replica in this fresh
    process.  Everything a real replica pays before taking traffic —
    jax import, app construction, model load, bucket prewarm — lands
    inside ``app_ready_s``; the parent polls /readyz for the outside
    view.  Blocks until killed."""
    t0 = time.perf_counter()
    from mmlspark_tpu.serve import ServingApp

    # register BEFORE start: /readyz flips 200 only once start() has
    # prewarmed every bucket, so the parent's poll can't beat the warm
    app = ServingApp(port=args.port, max_wait_ms=10.0)
    app.add_model("bench", path=args.replica)
    app.start()
    app_ready_s = time.perf_counter() - t0
    print(json.dumps({"port": app.port,
                      "app_ready_s": round(app_ready_s, 3)}), flush=True)
    try:
        while True:
            time.sleep(1.0)
    except KeyboardInterrupt:
        pass
    return 0


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _spawn_replica_leg(model_path: str, timeout_s: float = 180.0) -> dict:
    """Spawn one replica process and wait for /readyz 200; returns the
    leg record (ready walls + the replica's AOT counters)."""
    from mmlspark_tpu.core.env import refuse_child_on_held_chip

    # the parent trained the model in-process: fine on the CPU, but on a
    # chip it would hold the device the replica needs
    refuse_child_on_held_chip("bench_serving --cold")
    port = _free_port()
    t0 = time.perf_counter()
    proc = subprocess.Popen(
        [sys.executable, "-m", "tools.bench_serving",
         "--replica", model_path, "--port", str(port)],
        cwd=_REPO_ROOT, stdout=subprocess.PIPE, stderr=subprocess.PIPE,
        text=True,
    )
    url = f"http://127.0.0.1:{port}"
    ready = False
    try:
        deadline = t0 + timeout_s
        while time.perf_counter() < deadline:
            if proc.poll() is not None:
                break
            try:
                with urllib.request.urlopen(url + "/readyz", timeout=2) as r:
                    if r.status == 200:
                        ready = True
                        break
            except (urllib.error.URLError, OSError):
                pass
            time.sleep(0.01)
        proc_to_ready_s = time.perf_counter() - t0
        if not ready:
            proc.terminate()
            _, err = proc.communicate(timeout=30)
            return {"error": f"replica never became ready: {err[-2000:]}"}
        child = json.loads(proc.stdout.readline())
        with urllib.request.urlopen(url + "/metrics", timeout=10) as r:
            counters = json.loads(r.read().decode()).get("counters", {})
        return {
            "proc_to_ready_s": round(proc_to_ready_s, 3),
            "app_ready_s": child["app_ready_s"],
            "aot_hits": int(counters.get("jit_cache.aot_hits", 0)),
            "aot_misses": int(counters.get("jit_cache.aot_misses", 0)),
        }
    finally:
        if proc.poll() is None:
            proc.terminate()
            proc.wait(timeout=30)


def _run_cold(args, tmp, report) -> int:
    import jax

    backend = jax.default_backend()
    model_path = _train_and_save(tmp, args.seed)
    cold = {"backend": backend}
    for leg in ("cold_cache", "warm_from_disk"):
        cold[leg] = _spawn_replica_leg(model_path)
        if "error" in cold[leg]:
            print(f"[serving] cold {leg}: {cold[leg]['error']}",
                  file=sys.stderr)
            report["cold"] = cold
            print(json.dumps(report, indent=2, default=str))
            return 1
        print(f"[serving] cold {leg:<15} proc_to_ready="
              f"{cold[leg]['proc_to_ready_s']:.2f}s  app_ready="
              f"{cold[leg]['app_ready_s']:.2f}s  "
              f"(aot hits={cold[leg]['aot_hits']} "
              f"misses={cold[leg]['aot_misses']})")
    warm = cold["warm_from_disk"]
    cold["gate_warm_ready_lt_1s"] = warm["app_ready_s"] < 1.0
    # sub-second ready is a device-compile claim; on cpu the record is
    # honest but advisory (same policy as the ingest bench gate)
    cold["gate_enforced"] = backend != "cpu"
    report["cold"] = cold
    out = json.dumps(report, indent=2, default=str)
    print(out)
    if args.json_path:
        with open(args.json_path, "w") as f:
            f.write(out)

    failures = []
    if warm["aot_hits"] < 1:
        failures.append("warm replica never hit the AOT artifact cache")
    if warm["aot_misses"] > cold["cold_cache"]["aot_misses"]:
        failures.append("warm replica missed more AOT artifacts than the "
                        "cache-cleared one")
    if not cold["gate_warm_ready_lt_1s"]:
        msg = (f"warm replica app_ready {warm['app_ready_s']:.2f}s >= 1s "
               f"(cold_cache {cold['cold_cache']['app_ready_s']:.2f}s)")
        if cold["gate_enforced"]:
            failures.append(msg)
        else:
            print(f"[serving] cold gate advisory on backend=cpu: {msg} "
                  "(recorded, not enforced)")
    if failures:
        print("[serving] COLD FAILED: " + "; ".join(failures),
              file=sys.stderr)
        return 1
    print("[serving] cold-to-ready OK"
          + (" (smoke)" if args.smoke else ""))
    return 0


# --------------------------------------------------------------------------
# fleet scenario (--fleet): co-resident super-table + replica router
# --------------------------------------------------------------------------
def _train_fleet_models(tmp, seed, n_models):
    """``n_models`` small regressors with DIFFERENT feature widths (the
    co-resident group must pad narrower tenants) sharing one rng stream.
    Returns [(name, path, facade_model, X, y), ...]."""
    from mmlspark_tpu.core.frame import DataFrame
    from mmlspark_tpu.models.lightgbm import LightGBMRegressor

    rng = np.random.default_rng(seed)
    out = []
    for i in range(n_models):
        f = N_FEATURES + i  # 4, 5, 6, 7, ...
        X = rng.normal(size=(300, f))
        y = X[:, 0] * (1.5 + i) + np.sin(X[:, 1]) + 0.1 * rng.normal(size=300)
        model = LightGBMRegressor(
            numIterations=8, numLeaves=8, minDataInLeaf=4
        ).fit(DataFrame({"features": list(X), "label": y}))
        path = os.path.join(tmp, f"tenant{i}_v1")
        model.save(path)
        out.append((f"t{i}", path, model, X, y))
    return out


def _coresident_phase(models, bucket, rounds, report):
    """In-process micro-bench: ONE mixed-batch dispatch through the
    super-table vs M sequential per-model dispatches, equal row budget,
    plus the bitwise per-model parity check and the quantized-leaf AUC
    drift measurements."""
    from mmlspark_tpu.serve.coresident import (
        CoResidentGroup, quantization_auc_drift,
    )
    from mmlspark_tpu.serve.monitor import find_booster

    boosters = [(name, find_booster(m)) for name, _, m, _, _ in models]
    group = CoResidentGroup(boosters)
    M = len(models)
    k = bucket // M  # rows per tenant; equal total budget both paths
    f_max = group.feature_dim
    rng = np.random.default_rng(1234)

    # mixed batch: tenant i owns rows [i*k, (i+1)*k), zero-padded right
    X_mixed = np.zeros((bucket, f_max), np.float64)
    mids = np.zeros(bucket, np.int32)
    per_model = []
    for i, (name, _, m, X, _) in enumerate(models):
        f = X.shape[1]
        rows = rng.normal(size=(k, f))
        X_mixed[i * k:(i + 1) * k, :f] = rows
        mids[i * k:(i + 1) * k] = group.model_id(name)
        per_model.append((name, find_booster(m), rows))

    # parity: each tenant's finalized slice must be bitwise-identical to
    # its STANDALONE predict_padded at the same bucket width
    out = group.predict_mixed(X_mixed, mids)
    parity = True
    for i, (name, booster, rows) in enumerate(per_model):
        K = int(booster.num_class)
        padded = np.zeros((bucket, rows.shape[1]))
        padded[:k] = rows
        want = np.asarray(booster.predict_padded(padded, k), np.float32)
        got = out[i * k:(i + 1) * k, :K]
        if K == 1:
            got = got[:, 0]
        if not np.array_equal(got, want):
            parity = False
            print(f"[serving] fleet parity BROKEN for {name}: "
                  f"max|d|={np.abs(got - want).max()}", file=sys.stderr)

    # timed rounds (both paths warmed by the calls above / below)
    seq_inputs = [
        (booster, np.ascontiguousarray(rows)) for _, booster, rows in per_model
    ]
    for booster, rows in seq_inputs:  # warm the (k, F) standalone programs
        booster.predict_padded(rows, k)
    t0 = time.perf_counter()
    for _ in range(rounds):
        group.predict_mixed(X_mixed, mids)
    co_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    for _ in range(rounds):
        for booster, rows in seq_inputs:
            booster.predict_padded(rows, k)
    seq_s = time.perf_counter() - t0

    total_rows = bucket * rounds
    speedup = seq_s / co_s if co_s else 0.0
    co = {
        "models": M,
        "bucket_rows": bucket,
        "rows_per_tenant": k,
        "rounds": rounds,
        "parity_bitwise": parity,
        "co_resident_rows_per_s": round(total_rows / co_s, 1),
        "sequential_rows_per_s": round(total_rows / seq_s, 1),
        "dispatches_co": rounds,
        "dispatches_seq": rounds * M,
        "speedup_vs_sequential": round(speedup, 2),
        "gate_speedup_ge_2x": speedup >= 2.0,
        "supertable": group.describe(),
    }

    # quantized-leaf gate: measured AUC drift, recorded alongside
    _, _, m0, X0, y0 = models[0]
    labels = (y0 > np.median(y0)).astype(int)
    co["quantization"] = {
        dt: quantization_auc_drift(find_booster(m0), X0, labels, dt)
        for dt in ("f16", "int8")
    }
    report["coresident"] = co
    print(f"[serving] co-resident {M} models @ {bucket} rows: "
          f"{co['co_resident_rows_per_s']} rows/s (1 dispatch) vs "
          f"{co['sequential_rows_per_s']} rows/s ({M} dispatches) = "
          f"{co['speedup_vs_sequential']}x  parity={parity}")
    return co


def _fleet_traffic(router_url, tenants, duration_s, clients_per_tenant,
                   seed):
    """Closed-loop per-tenant traffic through the router; one
    _LoadResult per tenant so p50/p99 stay attributable."""
    results = {name: _LoadResult() for name, _ in tenants}
    stop_at = time.monotonic() + duration_s
    threads = []

    def worker(name, f, wid):
        rng = random.Random(seed * 131 + hash(name) % 1000 + wid)
        frng = np.random.default_rng(seed * 17 + wid)
        url = f"{router_url}/models/{name}/predict"
        while time.monotonic() < stop_at:
            k = rng.randint(1, 8)
            rows = frng.normal(size=(k, f)).tolist()
            results[name].record(*_post(url, {"instances": rows},
                                        timeout=30.0))

    t0 = time.monotonic()
    for name, f in tenants:
        for wid in range(clients_per_tenant):
            t = threading.Thread(target=worker, args=(name, f, wid),
                                 daemon=True)
            t.start()
            threads.append(t)
    for t in threads:
        t.join(timeout=duration_s + 120)
    wall = time.monotonic() - t0
    return {name: res.summary(wall) for name, res in results.items()}


def _run_fleet(args, tmp, report) -> int:
    import jax

    from mmlspark_tpu.serve.router import FleetRouter

    backend = jax.default_backend()
    report["backend"] = backend
    gate_enforced = backend != "cpu"  # perf gates advisory on cpu CI
    report["gate_enforced"] = gate_enforced

    # ---- phase 1: co-resident super-table vs sequential dispatch -------
    n_models = 4
    models = _train_fleet_models(tmp, args.seed, n_models)
    bucket = 256 if args.smoke else 512
    rounds = 10 if args.smoke else 40
    co = _coresident_phase(models, bucket, rounds, report)

    # ---- phase 2: router + 2 replica processes, rolling swap ----------
    tenant_specs = [(name, path) for name, path, _, _, _ in models[:3]]
    tenants = [(name, X.shape[1]) for name, _, _, X, _ in models[:3]]
    swap_tenant = tenant_specs[0][0]
    v2_path = os.path.join(tmp, "tenant0_v2")
    models[0][2].save(v2_path)  # same model re-saved = a new version dir

    router = FleetRouter(port=0, health_interval_s=0.5)
    fleet = {"replicas": [], "swap_tenant": swap_tenant}
    try:
        for _ in range(2):
            t0 = time.perf_counter()
            h = router.spawn_replica(tenant_specs, group=True)
            fleet["replicas"].append({
                "replica_id": h.replica_id,
                "url": h.url,
                "spawn_to_ready_s": round(time.perf_counter() - t0, 2),
            })
            print(f"[serving] fleet replica {h.replica_id} ready at {h.url} "
                  f"({fleet['replicas'][-1]['spawn_to_ready_s']}s)")
        router.start()
        clients = max(1, min(2, args.clients))

        # steady window: per-tenant baseline latencies
        steady = _fleet_traffic(router.url, tenants, args.duration,
                                clients, args.seed)
        fleet["steady"] = steady

        # swap window: same traffic, rolling hot-swap of ONE tenant fired
        # mid-window through the router (drain-aware, one replica at a time)
        swap_result = {}

        def swapper():
            time.sleep(args.duration * 0.25)
            t0 = time.perf_counter()
            status, lat = _post(
                f"{router.url}/admin/swap",
                {"model": swap_tenant, "path": v2_path}, timeout=600.0,
            )
            swap_result["status"] = status
            swap_result["wall_s"] = round(time.perf_counter() - t0, 3)

        swap_thread = threading.Thread(target=swapper, daemon=True)
        swap_thread.start()
        during = _fleet_traffic(router.url, tenants, args.duration,
                                clients, args.seed + 5)
        swap_thread.join(timeout=600)
        fleet["during_swap"] = during
        fleet["swap"] = swap_result

        with urllib.request.urlopen(router.url + "/fleetz", timeout=10) as r:
            fleet["fleetz"] = json.loads(r.read().decode())
    finally:
        fleet["router_stop_clean"] = router.stop(drain_s=10.0)

    # gates: zero 5xx anywhere; unswapped tenants' p99 within 20% of
    # their steady-state p99 while the swap rolled through the fleet
    fivexx = sum(s["fivexx"] for s in fleet["steady"].values()) + sum(
        s["fivexx"] for s in fleet["during_swap"].values()
    )
    fleet["fivexx_total"] = fivexx
    p99_ok = True
    p99_detail = {}
    for name, _ in tenants:
        if name == swap_tenant:
            continue
        base = fleet["steady"][name]["p99_ms"]
        swapped = fleet["during_swap"][name]["p99_ms"]
        # sub-ms floor: at cpu-CI latencies a 20% band is noise
        within = swapped <= max(1.2 * base, base + 1.0)
        p99_detail[name] = {"steady_p99_ms": base, "swap_p99_ms": swapped,
                            "within_20pct": within}
        p99_ok = p99_ok and within
    fleet["gate_zero_5xx"] = fivexx == 0
    fleet["gate_p99_within_20pct"] = p99_ok
    fleet["p99_by_tenant"] = p99_detail
    report["fleet"] = fleet
    for name, _ in tenants:
        s, d = fleet["steady"][name], fleet["during_swap"][name]
        print(f"[serving] fleet tenant {name}: steady "
              f"{s['throughput_rps']} rps p99={s['p99_ms']}ms | swap-window "
              f"p99={d['p99_ms']}ms 5xx={s['fivexx'] + d['fivexx']}")
    print(f"[serving] rolling swap of {swap_tenant}: "
          f"status={fleet['swap'].get('status')} "
          f"wall={fleet['swap'].get('wall_s')}s  fleet 5xx={fivexx}")

    out = json.dumps(report, indent=2, default=str)
    print(out)
    print("SERVE_FLEET " + json.dumps(report, default=str))
    if args.json_path:
        with open(args.json_path, "w") as f:
            f.write(out)

    failures = []
    advisories = []
    if not co["parity_bitwise"]:
        failures.append("co-resident per-model outputs not bitwise-identical")
    if not co["gate_speedup_ge_2x"]:
        msg = (f"co-resident speedup {co['speedup_vs_sequential']}x < 2x "
               "vs sequential dispatch")
        (failures if gate_enforced else advisories).append(msg)
    if fleet["swap"].get("status") != 200:
        failures.append(
            f"rolling swap failed: status={fleet['swap'].get('status')}"
        )
    if fivexx:
        failures.append(f"fleet traffic saw {fivexx} 5xx responses")
    if not all(s["ok"] for s in fleet["steady"].values()):
        failures.append("a tenant served zero steady-state requests")
    if not all(s["ok"] for s in fleet["during_swap"].values()):
        failures.append("a tenant served zero requests during the swap")
    if not p99_ok:
        msg = f"unswapped-tenant p99 left the 20% band: {p99_detail}"
        (failures if gate_enforced else advisories).append(msg)
    if not fleet["router_stop_clean"]:
        failures.append("router drain did not complete cleanly")
    for msg in advisories:
        print(f"[serving] fleet gate advisory on backend={backend}: {msg} "
              "(recorded, not enforced)")
    if failures and args.smoke:
        print("[serving] FLEET SMOKE FAILED: " + "; ".join(failures),
              file=sys.stderr)
        return 1
    if failures:
        print("[serving] fleet gates failed: " + "; ".join(failures),
              file=sys.stderr)
        return 1
    print("[serving] fleet OK" + (" (smoke)" if args.smoke else ""))
    return 0


# --------------------------------------------------------------------------
# main
# --------------------------------------------------------------------------
def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--duration", type=float, default=8.0,
                    help="seconds per closed-loop phase")
    ap.add_argument("--clients", type=int, default=8)
    ap.add_argument("--overload-duration", type=float, default=4.0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--json", dest="json_path", default=None,
                    help="also write the report to this path")
    ap.add_argument("--smoke", action="store_true",
                    help="short CI run + hard-assert serving invariants")
    ap.add_argument("--no-baseline", action="store_true",
                    help="skip the seed-loop phase")
    ap.add_argument("--shift", action="store_true",
                    help="run the drift scenario (steady then +3σ shifted "
                         "traffic) instead of the baseline/overload phases")
    ap.add_argument("--cold", action="store_true",
                    help="run the replica cold-to-ready scenario (two "
                         "fresh processes over one jit-cache dir) instead "
                         "of the baseline/overload phases")
    ap.add_argument("--fleet", action="store_true",
                    help="run the fleet scenario (ISSUE 13): co-resident "
                         "super-table vs sequential dispatch, then a "
                         "router + 2 replica processes sustaining a "
                         "rolling hot-swap under multi-tenant traffic")
    ap.add_argument("--replica", metavar="MODEL_PATH", default=None,
                    help=argparse.SUPPRESS)  # internal: one replica child
    ap.add_argument("--port", type=int, default=0, help=argparse.SUPPRESS)
    args = ap.parse_args(argv)
    if args.replica:
        return _run_replica(args)
    if args.smoke:
        args.duration = min(args.duration, 2.5)
        args.overload_duration = min(args.overload_duration, 2.0)
        args.clients = min(args.clients, 6)

    tmp = tempfile.mkdtemp(prefix="bench_serving_")
    # fresh compile cache so neither phase rides a previous run's warmth
    # (set before jax is imported; the replica children inherit it)
    from tools import empty_cache_dir

    os.environ["JAX_COMPILATION_CACHE_DIR"] = empty_cache_dir(
        "jit_cache_serving")

    from mmlspark_tpu import obs
    from mmlspark_tpu.serve import ServingApp

    obs.enable()
    report = {
        "bench": ("serving-drift" if args.shift
                  else "serving-cold" if args.cold
                  else "serving-fleet" if args.fleet else "serving"),
        "config": {
            "duration_s": args.duration,
            "clients": args.clients,
            "max_instances": MAX_INSTANCES,
            "n_features": N_FEATURES,
            "smoke": args.smoke,
        },
    }
    if args.shift:
        return _run_shift(args, tmp, report)
    if args.cold:
        return _run_cold(args, tmp, report)
    if args.fleet:
        return _run_fleet(args, tmp, report)
    feature_rng = np.random.default_rng(args.seed + 1)
    v1 = _train_and_save(tmp, args.seed)
    v2 = _train_and_save(tmp, args.seed + 1)

    # ---- phase 1: seed fixed-batch loop --------------------------------
    if not args.no_baseline:
        server, stop, thread = _seed_loop_server(v1)
        base_url = f"http://{server.host}:{server.port}/"
        report["baseline"] = _closed_loop(
            base_url, args.duration, args.clients, args.seed, feature_rng
        )
        stop.set()
        thread.join(timeout=10)
        server.stop()
        print(f"[serving] baseline (seed loop): "
              f"{report['baseline']['throughput_rps']} rps  "
              f"p99={report['baseline']['p99_ms']}ms")

    # ---- phase 2: dynamic batcher + hot-swap ---------------------------
    obs.reset()  # isolate the dynamic phase's batch histogram
    app = ServingApp(max_wait_ms=10.0).start()
    app.add_model("bench", path=v1)  # re-baselines the ready jit snapshot
    jit_at_ready = app.jit_counters_at_ready()

    swap_result = {}

    def swapper():
        time.sleep(args.duration / 2)
        t0 = time.perf_counter()
        app.swap_model("bench", path=v2)
        swap_result["swap_wall_s"] = round(time.perf_counter() - t0, 3)

    swap_thread = threading.Thread(target=swapper, daemon=True)
    swap_thread.start()
    dyn_url = f"{app.url}/models/bench/predict"
    dynamic = _closed_loop(
        dyn_url, args.duration, args.clients, args.seed, feature_rng
    )
    swap_thread.join(timeout=60)
    from mmlspark_tpu.core.jit_cache import cache_counters

    jit_after = cache_counters()
    snap = obs.snapshot()
    dynamic["batch_rows_hist"] = snap["histograms"].get("serve.batch_rows", {})
    dynamic["batches_by_bucket"] = {
        k: v for k, v in snap["counters"].items() if k.startswith("serve.batches")
    }
    dynamic["swap"] = {
        **swap_result,
        "swaps": snap["counters"].get("serve.swaps{model=bench}", 0),
        "fivexx_during_run": dynamic["fivexx"],
    }
    # prewarm proof: serving traffic after ready never reaches the
    # compilation cache — the only lookups after the ready baseline are
    # the swap's own pre-flip warm compiles (one per bucket, done BEFORE
    # v2 takes traffic, so no request ever waits on them).
    swap_warm_budget = len(app.buckets) if swap_result else 0
    dynamic["jit_cache"] = {
        "at_ready": jit_at_ready,
        "after_run": jit_after,
        "lookups_after_ready": (
            jit_after["miss"] + jit_after["hit"]
            - jit_at_ready["miss"] - jit_at_ready["hit"]
        ),
        "swap_warm_budget": swap_warm_budget,
    }
    report["dynamic"] = dynamic
    print(f"[serving] dynamic batcher: {dynamic['throughput_rps']} rps  "
          f"p99={dynamic['p99_ms']}ms  5xx={dynamic['fivexx']} "
          f"(swap mid-run: {swap_result.get('swap_wall_s')}s)")

    # ---- phase 3: open-loop overload vs a small admission envelope -----
    app.stop()
    obs.reset()
    overload_app = ServingApp(
        max_wait_ms=10.0, max_queue_depth=8, max_inflight=8
    ).start()
    overload_app.add_model("bench", path=v1)
    target = max(50.0, 2.0 * dynamic["throughput_rps"])
    overload = _open_loop(
        f"{overload_app.url}/models/bench/predict",
        args.overload_duration, target,
        workers=min(64, max(32, args.clients * 4)),
        seed=args.seed, feature_rng=feature_rng,
    )
    overload_snap = obs.snapshot()
    overload["admission"] = {
        k: v for k, v in overload_snap["counters"].items()
        if k.startswith("serve.admission")
    }
    overload_app.stop()
    report["overload"] = overload
    print(f"[serving] overload @2x: shed_rate={overload['shed_rate']} "
          f"5xx={overload['fivexx']} "
          f"({overload['requests']} attempts at {overload['target_rps']} rps)")

    # ---- metrics endpoint sanity (CI gate) -----------------------------
    check_app = ServingApp().start()
    check_app.add_model("bench", path=v1)
    with urllib.request.urlopen(check_app.url + "/metrics", timeout=10) as r:
        metrics_body = json.loads(r.read().decode())
    with urllib.request.urlopen(
        check_app.url + "/metrics?format=prometheus", timeout=10
    ) as r:
        prom_body = r.read().decode()
        prom_ctype = r.headers.get("Content-Type", "")
    check_app.stop()
    report["metrics_nonempty"] = bool(metrics_body.get("counters"))
    report["prometheus_nonempty"] = (
        "# TYPE" in prom_body and prom_ctype.startswith("text/plain")
    )

    if "baseline" in report and report["baseline"]["throughput_rps"]:
        report["speedup_vs_seed"] = round(
            report["dynamic"]["throughput_rps"]
            / report["baseline"]["throughput_rps"], 2,
        )
        print(f"[serving] dynamic/seed throughput: "
              f"{report['speedup_vs_seed']}x")

    out = json.dumps(report, indent=2, default=str)
    print(out)
    if args.json_path:
        with open(args.json_path, "w") as f:
            f.write(out)

    if args.smoke:
        failures = []
        if report["dynamic"]["fivexx"]:
            failures.append(f"dynamic phase saw {report['dynamic']['fivexx']} 5xx")
        if report["overload"]["fivexx"]:
            failures.append(f"overload phase saw {report['overload']['fivexx']} 5xx")
        if not report["dynamic"]["ok"]:
            failures.append("dynamic phase served zero requests")
        if not report["metrics_nonempty"]:
            failures.append("/metrics snapshot was empty")
        if not report["prometheus_nonempty"]:
            failures.append("/metrics?format=prometheus was empty or "
                            "mis-typed")
        if report["dynamic"]["swap"]["swaps"] < 1:
            failures.append("hot-swap did not complete")
        jc = report["dynamic"]["jit_cache"]
        if jc["lookups_after_ready"] > jc["swap_warm_budget"]:
            failures.append(
                "serving traffic reached the compile cache "
                f"({jc['lookups_after_ready']} lookups after ready, "
                f"swap warm budget {jc['swap_warm_budget']}) — prewarm broken"
            )
        if failures:
            print("[serving] SMOKE FAILED: " + "; ".join(failures),
                  file=sys.stderr)
            return 1
        print("[serving] smoke OK")
    return 0


if __name__ == "__main__":
    sys.exit(main())
