"""Closed-loop bench: drift → retrain → shadow → promote → rollback, e2e.

Drives one :class:`mmlspark_tpu.serve.ServingApp` with an attached
:class:`mmlspark_tpu.loop.RetrainController` through the full
continuous-training story, using the same traffic generator as
``bench_serving --shift``:

1. **steady**   — training-distribution traffic; the monitor and the
   controller must both stay silent (no alarms, no retrains).
2. **shifted**  — +3σ covariate shift on every feature.  The drift alarm
   must fire, the controller must warm-refit the champion on fresh
   (shifted-distribution) shards, shadow the candidate under mirrored
   live traffic, and auto-promote it — with ZERO 5xx throughout, since
   every stage (mirror tap, registry swap, probation) rides the live
   path.  After promotion the route's excess PSI must fall back below
   ``MMLSPARK_TPU_QUALITY_PSI_ALERT``: the loop actually corrected the
   drift it paged on.
3. **poisoned** — the fresh-shard provider is swapped for shards drawn
   from the WRONG distribution and a manual ``POST /admin/retrain``
   fires.  The resulting candidate is drifted against live traffic by
   construction; the promotion gate must reject it
   (``loop.promotions_rejected``) and the champion must keep serving,
   untouched.
4. **rollback** — with the promoted champion still inside its probation
   window, a synthetic SLO burn (a batch of 5xx statuses injected
   straight into the monitor, never through HTTP — the zero-5xx gate
   stays honest) must auto-roll the route back to the PINNED previous
   version: a pointer flip, not a cold load, asserted by the
   ``serve.models_loaded`` counter not moving.

The report is written as ``LOOP_BENCH.json`` (schema- and gate-checked
by ``tools.bench_ratchet``).  ``--smoke`` shrinks the run for CI and
exits non-zero unless every gate holds.

Usage::

    JAX_PLATFORMS=cpu python -m tools.bench_loop [--smoke] [--json PATH]
        [--duration S] [--clients N] [--seed K]
"""

from __future__ import annotations

import argparse
import json
import os
import random
import sys
import tempfile
import threading
import time
import urllib.request

import numpy as np

from tools.bench_serving import (
    MAX_INSTANCES,
    N_FEATURES,
    _closed_loop,
    _drift_counts,
    _LoadResult,
    _post,
    _ShiftedRng,
    _train_and_save,
)

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

#: covariate displacement for the shifted phase (matches bench_serving)
SHIFT = 3.0


# --------------------------------------------------------------------------
# traffic
# --------------------------------------------------------------------------
class _Pump:
    """Open-ended closed-loop traffic: like ``_closed_loop`` but running
    until stopped, so the bench can hold traffic while it polls the
    controller for promotion/rollback progress."""

    def __init__(self, url, clients, seed, feature_rng):
        self.res = _LoadResult()
        self._url = url
        self._seed = seed
        self._rng = feature_rng
        self._stop = threading.Event()
        self._t0 = time.monotonic()
        self._threads = [
            threading.Thread(target=self._work, args=(i,), daemon=True)
            for i in range(clients)
        ]
        for t in self._threads:
            t.start()

    def _work(self, wid):
        rng = random.Random(self._seed * 1000 + wid)
        while not self._stop.is_set():
            k = rng.randint(1, MAX_INSTANCES)
            rows = self._rng.normal(size=(k, N_FEATURES)).tolist()
            self.res.record(*_post(self._url, {"instances": rows},
                                   timeout=10.0))

    def stop(self) -> dict:
        self._stop.set()
        for t in self._threads:
            t.join(timeout=30)
        return self.res.summary(time.monotonic() - self._t0)


def _wait(pred, timeout_s, interval_s=0.25):
    """Poll ``pred`` until truthy or timeout; returns the last value."""
    deadline = time.monotonic() + timeout_s
    while True:
        v = pred()
        if v or time.monotonic() >= deadline:
            return v
        time.sleep(interval_s)


# --------------------------------------------------------------------------
# fixtures
# --------------------------------------------------------------------------
def _label(X, rng):
    return X[:, 0] * 2.0 + np.sin(X[:, 1]) + 0.1 * rng.normal(size=len(X))


def _write_shards(tmp, name, center, rows, seed):
    """A labeled row-group shard container centered at ``center`` — the
    'fresh traffic window' a retrain appends trees from."""
    from mmlspark_tpu.data.loader import write_row_group_shards

    rng = np.random.default_rng(seed)
    X = rng.normal(size=(rows, N_FEATURES)) + center
    y = _label(X, rng)
    path = os.path.join(tmp, name)
    write_row_group_shards(path, X, y, rows_per_group=512)
    return path


def _counter(snapshot, prefix) -> float:
    """Sum of obs counters whose key starts with ``prefix`` (label-blind:
    keys render as ``name{k=v,...}``)."""
    return float(sum(
        v for k, v in snapshot.get("counters", {}).items()
        if k == prefix or k.startswith(prefix + "{")
    ))


# --------------------------------------------------------------------------
# the scenario
# --------------------------------------------------------------------------
def run(args) -> int:
    tmp = tempfile.mkdtemp(prefix="bench_loop_")
    os.environ["MMLSPARK_TPU_OBS_FLIGHT_DIR"] = os.path.join(tmp, "flight")
    os.environ["MMLSPARK_TPU_OBS_FLIGHT_MIN_INTERVAL_S"] = "0"
    from tools import empty_cache_dir

    os.environ.setdefault(
        "JAX_COMPILATION_CACHE_DIR", empty_cache_dir("jit_cache_loop")
    )

    from mmlspark_tpu import obs
    from mmlspark_tpu.data.loader import RowGroupSource
    from mmlspark_tpu.loop import LoopConfig, RetrainController
    from mmlspark_tpu.obs.quality import quality_env_config
    from mmlspark_tpu.serve import ServingApp

    qcfg = quality_env_config()
    report: dict = {
        "bench": "serve_loop",
        "backend": "cpu" if os.environ.get(
            "JAX_PLATFORMS", "").startswith("cpu") else (
            os.environ.get("JAX_PLATFORMS") or "default"),
        "config": {
            "duration_s": args.duration,
            "clients": args.clients,
            "seed": args.seed,
            "psi_alert": qcfg["psi_alert"],
            "min_rows": qcfg["min_rows"],
        },
    }

    v1 = _train_and_save(tmp, args.seed)
    shift_shards = _write_shards(
        tmp, "shards_shift", SHIFT, 3000, args.seed + 11)
    poison_shards = _write_shards(
        tmp, "shards_poison", -SHIFT, 2000, args.seed + 12)
    provider = {"source": RowGroupSource(shift_shards)}

    obs.reset()
    app = ServingApp(max_wait_ms=10.0).start()
    app.add_model("bench", path=v1)
    url = f"{app.url}/models/bench/predict"
    if app.monitor is None:
        print("[loop] bench_loop needs the quality monitor "
              "(unset MMLSPARK_TPU_SERVE_MONITOR)", file=sys.stderr)
        app.stop()
        return 1

    cfg = LoopConfig(
        cooldown_s=600.0,        # one retrain per alarm storm in-run
        queue_depth=4,
        append_trees=16,
        shadow_sample=1.0,
        min_shadow_rows=256,
        shadow_timeout_s=90.0,
        psi_margin=0.0,
        latency_ratio=50.0,      # CPU-jitter headroom; not the story here
        probation_s=600.0,       # rollback leg runs inside this window
        poll_interval_s=0.1,
        workdir=os.path.join(tmp, "loop"),
    )
    controller = RetrainController(
        app, lambda name: provider["source"], config=cfg)
    app.attach_loop(controller)

    failures = []

    # ---- phase 1: steady — loop must stay closed and silent -------------
    steady = _closed_loop(
        url, args.duration, args.clients, args.seed,
        np.random.default_rng(args.seed + 1),
    )
    _wait(lambda: not app.monitor._pending.qsize(), 5.0, 0.2)
    time.sleep(1.5)  # one monitor eval tick past the last ingest
    steady["quality"] = _drift_counts(app.monitor, "bench")
    report["steady"] = steady
    steady_quiet = (
        steady["quality"]["drift"] == 0
        and _counter(obs.snapshot(), "loop.retrains") == 0
    )
    print(f"[loop] steady: {steady['throughput_rps']} rps  "
          f"alarms={steady['quality']['drift']}  quiet={steady_quiet}")

    # ---- phase 2: shifted — alarm → retrain → shadow → promote ----------
    v1_version = app.registry.get("bench").version
    pump = _Pump(url, args.clients, args.seed + 99,
                 _ShiftedRng(np.random.default_rng(args.seed + 2), SHIFT))
    promoted_mv = _wait(
        lambda: (app.registry.get("bench").version > v1_version
                 and app.registry.get("bench")),
        timeout_s=args.phase_timeout,
    )
    # the promotion's register_route replaces the route's monitor state
    # (fresh baseline, fresh alarm counts) — the cumulative obs counter
    # is the signal that survives the flip
    alarm_fired = _counter(obs.snapshot(), "quality.drift_alarms") > 0

    # drift must RECOVER on the promoted model: fresh baseline, live
    # excess PSI back under the paging threshold at full warm-up depth
    def _recovered():
        m = app.monitor.route_metrics("bench")
        if not m or not promoted_mv:
            return None
        drifts = [v for v in (m.get("feature_excess_psi_max"),
                              m.get("score_excess_psi")) if v is not None]
        warm = (m.get("feature_live_rows") or 0) >= qcfg["min_rows"]
        if warm and drifts and max(drifts) < qcfg["psi_alert"]:
            return m
        return None

    recovery = (
        _wait(_recovered, timeout_s=args.phase_timeout)
        if promoted_mv else None
    )
    shifted = pump.stop()
    shifted["quality"] = _drift_counts(app.monitor, "bench")
    report["shifted"] = shifted
    report["recovery"] = {
        "recovered": bool(recovery),
        "excess_psi": (
            max(v for v in (recovery.get("feature_excess_psi_max"),
                            recovery.get("score_excess_psi"))
                if v is not None) if recovery else None
        ),
        "live_rows": recovery.get("feature_live_rows") if recovery else None,
        "psi_alert": qcfg["psi_alert"],
        "promoted_version": promoted_mv.version if promoted_mv else None,
    }
    # bool-or-None → the JSON schema wants a number; pin the miss to -1
    if report["recovery"]["excess_psi"] is None:
        report["recovery"]["excess_psi"] = -1.0
    promoted = bool(promoted_mv)
    print(f"[loop] shifted: alarms={shifted['quality']['by_kind']}  "
          f"promoted={promoted} "
          f"(v{promoted_mv.version if promoted_mv else '?'})  "
          f"recovered={bool(recovery)} "
          f"excess_psi={report['recovery']['excess_psi']}")

    # ---- phase 3: poisoned challenger must never promote ----------------
    provider["source"] = RowGroupSource(poison_shards)
    champion_version = app.registry.get("bench").version
    snap_before = obs.snapshot()
    n_decisions = len(controller.status()["decisions"])
    pump = _Pump(url, args.clients, args.seed + 7,
                 _ShiftedRng(np.random.default_rng(args.seed + 3), SHIFT))
    status, _lat = _post(f"{app.url}/admin/retrain", {"model": "bench"})
    decided = _wait(
        lambda: (len(controller.status()["decisions"]) > n_decisions
                 and controller.status()["decisions"][-1]),
        timeout_s=args.phase_timeout,
    )
    poisoned_traffic = pump.stop()
    snap_after = obs.snapshot()
    decision = dict(decided["decision"]) if decided else None
    version_unchanged = (
        app.registry.get("bench").version == champion_version)
    rejected_counted = (
        _counter(snap_after, "loop.promotions_rejected")
        > _counter(snap_before, "loop.promotions_rejected")
    )
    poisoned_rejected = bool(
        decided and not decision["promote"]
        and version_unchanged and rejected_counted
    )
    report["poisoned"] = {
        "admin_status": status,
        "decision": decision,
        "version_unchanged": version_unchanged,
        "rejected_counted": rejected_counted,
        "traffic": poisoned_traffic,
    }
    print(f"[loop] poisoned: admin={status}  "
          f"decision={decision and decision['reason']}  "
          f"champion_untouched={version_unchanged}")

    # ---- phase 4: SLO burn inside probation → auto-rollback -------------
    models_loaded_before = _counter(obs.snapshot(), "serve.models_loaded")
    burn_version = app.registry.get("bench").version
    pump = _Pump(url, args.clients, args.seed + 8,
                 _ShiftedRng(np.random.default_rng(args.seed + 4), SHIFT))
    # synthetic burn: 5xx statuses injected into the monitor's SLO
    # tracker, NOT served over HTTP — clients keep seeing 200s, which is
    # exactly what makes the zero-5xx gate meaningful across a rollback
    app.monitor.submit("bench", burn_version,
                       statuses=[500] * 600, latencies=[0.01] * 600)
    rolled_mv = _wait(
        lambda: (app.registry.get("bench").version == v1_version
                 and app.registry.get("bench")),
        # without a promotion there is no probation to roll back from —
        # don't burn the full deadline on a leg that cannot progress
        timeout_s=args.phase_timeout if promoted_mv else 5.0,
    )
    rollback_traffic = pump.stop()
    models_loaded_after = _counter(obs.snapshot(), "serve.models_loaded")
    rollbacks_counted = _counter(obs.snapshot(), "loop.rollbacks") >= 1
    rollback_ok = bool(rolled_mv) and rollbacks_counted
    rollback_pin = (
        bool(rolled_mv) and models_loaded_after == models_loaded_before
    )
    report["rollback"] = {
        "restored_version": rolled_mv.version if rolled_mv else -1,
        "rolled_back": bool(rolled_mv),
        "rollbacks_counted": rollbacks_counted,
        "models_loaded_before": models_loaded_before,
        "models_loaded_after": models_loaded_after,
        "traffic": rollback_traffic,
    }
    print(f"[loop] rollback: restored="
          f"v{rolled_mv.version if rolled_mv else '?'}  "
          f"pin_flip_only={rollback_pin}")

    # ---- surfacing -------------------------------------------------------
    try:
        with urllib.request.urlopen(app.url + "/loopz", timeout=10) as r:
            report["loopz"] = json.loads(r.read().decode())
    except Exception as e:  # surfaced as a gate below
        report["loopz"] = {"error": repr(e)}
    report["obs"] = obs.snapshot()
    app.stop()

    fivexx = sum(
        phase.get("fivexx", 0)
        for phase in (steady, shifted, poisoned_traffic, rollback_traffic)
    )
    served = all(
        phase.get("ok", 0) > 0
        for phase in (steady, shifted, poisoned_traffic, rollback_traffic)
    )
    report["gates"] = {
        "zero_5xx": fivexx == 0 and served,
        "steady_quiet": bool(steady_quiet),
        "alarm_fired": bool(alarm_fired),
        "promoted": promoted,
        "psi_recovered": bool(recovery),
        "poisoned_rejected": poisoned_rejected,
        "rollback_ok": rollback_ok,
        "rollback_pin": rollback_pin,
        "loopz_ok": report["loopz"].get("status") in ("ok", "degraded"),
    }

    out = json.dumps(report, indent=2, default=str)
    if args.json_path:
        with open(args.json_path, "w") as f:
            f.write(out)
    print(out if not args.smoke else json.dumps(report["gates"], indent=1))

    if args.smoke:
        failures = [g for g, ok in report["gates"].items() if not ok]
        if failures:
            print("[loop] LOOP SMOKE FAILED: " + ", ".join(failures),
                  file=sys.stderr)
            return 1
        print("[loop] loop smoke OK")
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="python -m tools.bench_loop")
    ap.add_argument("--smoke", action="store_true",
                    help="CI mode: shrink the run, hard-assert the gates")
    ap.add_argument("--json", dest="json_path", default=None,
                    help="write the LOOP_BENCH report here")
    ap.add_argument("--duration", type=float, default=None,
                    help="steady-phase seconds (default 6 smoke, 15 full)")
    ap.add_argument("--clients", type=int, default=4)
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--phase-timeout", type=float, default=None,
                    help="per-leg progress deadline (default 120)")
    args = ap.parse_args(argv)
    if args.duration is None:
        args.duration = 6.0 if args.smoke else 15.0
    if args.phase_timeout is None:
        args.phase_timeout = 120.0
    return run(args)


if __name__ == "__main__":
    sys.exit(main())
