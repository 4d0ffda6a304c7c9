"""Batch-predict throughput benchmark: scan baseline vs the packed forest.

Gives inference a perf trajectory like training and serving have
(BENCH-style JSON).  One trained forest is scored through each traversal
backend at three batch sizes (request-sized, micro-batch, bulk):

- **scan**   — the seed per-tree replay scan (``lax.scan`` over T trees);
  the baseline every other backend is gated against.
- **packed** — the ISSUE-5 device-resident SoA node table with
  depth-stepped forest-parallel traversal (engine/forest.py).
- **pallas_interpret** — the Pallas VMEM kernel run through the
  interpreter (the only way to execute it on CPU; numbers are a
  correctness leg, NOT a perf claim — the compiled kernel needs a TPU).

Per (backend, batch) cell the bench reports the COLD call (fresh booster
clone: node-table pack + upload + XLA compile, exactly what a serving
process pays once) and the STEADY distribution (p50/p99 latency and
rows/s over warm repeats).  Every backend's output is checked BITWISE
against scan on the same batch — a speedup at different numerics never
counts.

The run ends with a COLD-START phase (ISSUE 11): the trained booster is
pickled, then scored by two fresh subprocesses sharing one empty
jit-cache dir.  Process A ("cleared") pays the full trace+compile and
persists the ``aot-*`` executable; process B ("from_disk") deserializes
it — its first-predict wall is the new ``cold_from_disk_ms`` field.
Gate (full run and ``--cold-smoke``): ``cold_from_disk_ms`` ≤ 1/10 of
the cleared cold, outputs bitwise-identical across the process
boundary.  ``--smoke``'s tiny forest compiles too fast to clear 10×
honestly, so smoke asserts the mechanism (AOT hit, bitwise, faster
than cleared) and leaves the ratio to ``--cold-smoke`` — the CI
cold-start job, which trains a serving-sized forest and hard-asserts
the 10× gate and nothing else.

Usage::

    JAX_PLATFORMS=cpu python -m tools.bench_predict [--smoke] [--json PATH]
        [--batches 8,512,65536] [--iters N] [--seed K]

``--smoke`` shrinks the run for CI and exits non-zero unless every
backend matches scan bitwise and completes at every batch size.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import pickle
import subprocess
import sys
import tempfile
import time

import numpy as np

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

DEFAULT_BATCHES = (8, 512, 65536)
# interpret-mode pallas executes grid cells sequentially through the
# interpreter; bulk batches would take minutes on CPU for a number that
# means nothing (the compiled kernel is the TPU artifact).
PALLAS_INTERPRET_MAX_BATCH = 512


def _pct(sorted_vals, p):
    if not sorted_vals:
        return 0.0
    i = min(len(sorted_vals) - 1, int(round(p * (len(sorted_vals) - 1))))
    return sorted_vals[i]


def _train_booster(n_rows, n_features, n_iter, num_leaves, seed):
    from mmlspark_tpu.core.frame import DataFrame
    from mmlspark_tpu.models.lightgbm import LightGBMRegressor

    rng = np.random.default_rng(seed)
    X = rng.normal(size=(n_rows, n_features))
    y = (
        X[:, 0] * 2.0
        + np.sin(X[:, 1] * 3.0)
        + np.where(X[:, 2] > 0.3, 1.5, -0.5)
        + 0.1 * rng.normal(size=n_rows)
    )
    model = LightGBMRegressor(
        numIterations=n_iter, numLeaves=num_leaves, minDataInLeaf=4
    ).fit(DataFrame({"features": list(X), "label": y}))
    return model.getBooster()


def _clone_with_backend(booster, backend):
    """Fresh booster (pickle round-trip drops every device cache) pinned
    to one traversal backend — the cold call then pays the full
    pack/upload/compile cost a new serving process would."""
    b = pickle.loads(pickle.dumps(booster))
    b.config = dataclasses.replace(b.config, predict_backend=backend)
    return b


def _bench_cell(booster, backend, X, reps):
    """One (backend, batch) measurement: cold first call, then the warm
    steady-state latency distribution."""
    b = _clone_with_backend(booster, backend)
    n = X.shape[0]
    t0 = time.perf_counter()
    first = b.predict(X)
    cold_s = time.perf_counter() - t0
    times = []
    for _ in range(reps):
        t0 = time.perf_counter()
        b.predict(X)
        times.append(time.perf_counter() - t0)
    times.sort()
    p50 = _pct(times, 0.50)
    return first, {
        "backend": backend,
        "batch": n,
        "cold_ms": round(cold_s * 1e3, 2),
        "p50_ms": round(p50 * 1e3, 3),
        "p99_ms": round(_pct(times, 0.99) * 1e3, 3),
        "rows_per_s": round(n / p50, 1) if p50 else 0.0,
        "reps": reps,
    }


def _run_cold_child(args) -> int:
    """Child leg of the cold-start phase: load the pickled booster in
    THIS fresh process, time the first padded predict on the packed
    backend (the serving cold path), dump the scores for the parent's
    bitwise check, and report the AOT counters so the parent can tell a
    deserialize-warm from a recompile."""
    from mmlspark_tpu import obs
    from mmlspark_tpu.core.jit_cache import enable_compile_cache

    obs.enable()
    obs.reset()
    with open(args.cold_child, "rb") as fh:
        b = pickle.loads(fh.read())
    b.config = dataclasses.replace(b.config, predict_backend="packed")
    enable_compile_cache()
    rng = np.random.default_rng(7)
    X = rng.normal(size=(args.bucket, b.num_features)).astype(np.float32)
    t0 = time.perf_counter()
    out = b.predict_padded(X, args.bucket)
    cold_ms = (time.perf_counter() - t0) * 1e3
    np.save(args.out_npy, out)
    c = obs.snapshot()["counters"]
    print(json.dumps({
        "cold_ms": round(cold_ms, 2),
        "aot_hits": int(c.get("jit_cache.aot_hits", 0)),
        "aot_misses": int(c.get("jit_cache.aot_misses", 0)),
    }))
    return 0


def _cold_start_phase(booster, bucket: int):
    """Two-subprocess cold-start measurement over one shared (initially
    empty) jit-cache dir: leg "cleared" = cache-cleared cold (compiles +
    persists the AOT artifact), leg "from_disk" = a second process
    deserializing it.  Returns the PREDICT_BENCH ``cold_start`` cell."""
    from mmlspark_tpu.core.env import refuse_child_on_held_chip
    from tools import empty_cache_dir

    # the steady phase ran in THIS process: on a chip it holds the device
    # the cold children need
    refuse_child_on_held_chip("bench_predict cold-start phase")
    cell = {"bucket": int(bucket), "backend": "packed"}
    with tempfile.TemporaryDirectory(prefix="bench_cold_") as td:
        pkl = os.path.join(td, "booster.pkl")
        with open(pkl, "wb") as fh:
            fh.write(pickle.dumps(booster))
        env = dict(os.environ)
        env["JAX_COMPILATION_CACHE_DIR"] = empty_cache_dir("jit_cache_predict")
        outs = {}
        for leg in ("cleared", "from_disk"):
            out_npy = os.path.join(td, leg + ".npy")
            t0 = time.perf_counter()
            r = subprocess.run(
                [sys.executable, "-m", "tools.bench_predict",
                 "--cold-child", pkl, "--bucket", str(bucket),
                 "--out-npy", out_npy],
                env=env, cwd=_REPO_ROOT, capture_output=True, text=True,
                timeout=600,
            )
            proc_total_s = time.perf_counter() - t0
            if r.returncode != 0:
                cell["error"] = f"{leg} child failed: {r.stderr[-2000:]}"
                return cell
            child = json.loads(r.stdout.strip().splitlines()[-1])
            child["proc_total_s"] = round(proc_total_s, 2)
            outs[leg] = np.load(out_npy)
            cell[leg] = child
            print(f"[predict] cold-start {leg:<9} first predict "
                  f"{child['cold_ms']:>8.1f}ms  (process total "
                  f"{proc_total_s:.1f}s, aot hits={child['aot_hits']} "
                  f"misses={child['aot_misses']})",
                  file=sys.stderr, flush=True)
        cell["cleared_cold_ms"] = cell["cleared"]["cold_ms"]
        cell["cold_from_disk_ms"] = cell["from_disk"]["cold_ms"]
        cell["speedup"] = round(
            cell["cleared_cold_ms"] / cell["cold_from_disk_ms"], 2
        ) if cell["cold_from_disk_ms"] else 0.0
        cell["bitwise_across_processes"] = bool(
            np.array_equal(outs["cleared"], outs["from_disk"])
        )
        print(f"[predict] cold-start: cleared {cell['cleared_cold_ms']}ms "
              f"-> from-disk {cell['cold_from_disk_ms']}ms "
              f"({cell['speedup']}x, bitwise="
              f"{cell['bitwise_across_processes']})",
              file=sys.stderr, flush=True)
    return cell


def _cold_cell_failures(cell, require_10x: bool):
    """Shared gate logic for the cold-start cell; returns failure strings."""
    fails = []
    if "error" in cell:
        return [cell["error"]]
    if not cell["bitwise_across_processes"]:
        fails.append("cold-start legs diverge bitwise across processes")
    if cell["from_disk"]["aot_hits"] < 1:
        fails.append("from-disk leg never hit the AOT artifact cache")
    if require_10x:
        if cell["speedup"] < 10.0:
            fails.append(
                f"warm-from-disk cold {cell['cold_from_disk_ms']}ms not "
                f"10x under cleared {cell['cleared_cold_ms']}ms "
                f"({cell['speedup']}x)"
            )
    elif cell["cold_from_disk_ms"] >= cell["cleared_cold_ms"]:
        fails.append("warm-from-disk cold not faster than cache-cleared")
    return fails


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--batches", default=None,
                    help="comma-separated batch sizes "
                         f"(default {','.join(map(str, DEFAULT_BATCHES))})")
    ap.add_argument("--iters", type=int, default=200,
                    help="trees in the benchmark forest")
    ap.add_argument("--leaves", type=int, default=31)
    ap.add_argument("--features", type=int, default=32)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--json", dest="json_path", default=None,
                    help="also write the report to this path")
    ap.add_argument("--smoke", action="store_true",
                    help="short CI run + hard-assert bitwise parity")
    ap.add_argument("--no-pallas", action="store_true",
                    help="skip the pallas_interpret correctness leg")
    ap.add_argument("--cold-smoke", action="store_true",
                    help="CI cold-start job: only the two-subprocess "
                         "cold-start phase, hard-asserting the 10x gate")
    ap.add_argument("--cold-bucket", type=int, default=8,
                    help="bucket shape for the cold-start phase")
    ap.add_argument("--cold-child", metavar="PICKLE", default=None,
                    help=argparse.SUPPRESS)  # internal subprocess leg
    ap.add_argument("--bucket", type=int, default=8, help=argparse.SUPPRESS)
    ap.add_argument("--out-npy", default=None, help=argparse.SUPPRESS)
    args = ap.parse_args(argv)

    if args.cold_child:
        return _run_cold_child(args)

    if args.cold_smoke:
        # serving-sized forest: enough compile work that the 10x ratio
        # measures the AOT deserialize win, not process noise
        print("[predict] cold-smoke: training 60x63 forest ...",
              file=sys.stderr, flush=True)
        booster = _train_booster(
            n_rows=2048, n_features=args.features, n_iter=60,
            num_leaves=63, seed=args.seed,
        )
        cell = _cold_start_phase(booster, args.cold_bucket)
        report = {"bench": "predict-cold-smoke", "cold_start": cell}
        out = json.dumps(report, indent=2)
        print(out)
        if args.json_path:
            with open(args.json_path, "w") as f:
                f.write(out)
        failures = _cold_cell_failures(cell, require_10x=True)
        if failures:
            print("[predict] COLD SMOKE FAILED: " + "; ".join(failures),
                  file=sys.stderr)
            return 1
        print("[predict] cold smoke OK", file=sys.stderr)
        return 0

    if args.smoke:
        args.iters = min(args.iters, 20)
        args.features = min(args.features, 16)
        batches = (8, 512, 4096)
    else:
        batches = DEFAULT_BATCHES
    if args.batches:
        batches = tuple(int(b) for b in args.batches.split(","))

    print(f"[predict] training forest: {args.iters} trees x "
          f"{args.leaves} leaves on {args.features} features ...",
          file=sys.stderr, flush=True)
    booster = _train_booster(
        n_rows=1024 if args.smoke else 4096,
        n_features=args.features,
        n_iter=args.iters,
        num_leaves=args.leaves,
        seed=args.seed,
    )

    report = {
        "bench": "predict",
        "config": {
            "iters": args.iters,
            "leaves": args.leaves,
            "features": args.features,
            "batches": list(batches),
            "smoke": args.smoke,
        },
        "results": [],
    }
    rng = np.random.default_rng(args.seed + 1)
    failures = []

    for n in batches:
        X = rng.normal(size=(n, args.features))
        reps = 50 if n <= 64 else (20 if n <= 4096 else 5)
        if args.smoke:
            reps = min(reps, 10)
        backends = ["scan", "packed"]
        if not args.no_pallas and n <= PALLAS_INTERPRET_MAX_BATCH:
            backends.append("pallas_interpret")
        ref = None
        cells = {}
        for backend in backends:
            out, cell = _bench_cell(booster, backend, X, reps)
            if backend == "scan":
                ref = out
                cell["bitwise_vs_scan"] = True
            else:
                cell["bitwise_vs_scan"] = bool(np.array_equal(ref, out))
                if not cell["bitwise_vs_scan"]:
                    failures.append(
                        f"{backend} diverges from scan at batch {n} "
                        f"(maxdiff {np.max(np.abs(ref - out)):.3e})"
                    )
            report["results"].append(cell)
            cells[backend] = cell
            print(f"[predict] batch={n:<6} {backend:<17} "
                  f"cold={cell['cold_ms']:>8.1f}ms  "
                  f"p50={cell['p50_ms']:>8.2f}ms  "
                  f"p99={cell['p99_ms']:>8.2f}ms  "
                  f"{cell['rows_per_s']:>12,.0f} rows/s  "
                  f"bitwise={cell['bitwise_vs_scan']}",
                  file=sys.stderr, flush=True)
        if cells["scan"]["p50_ms"] and cells["packed"]["p50_ms"]:
            report.setdefault("speedup_vs_scan", {})[str(n)] = round(
                cells["scan"]["p50_ms"] / cells["packed"]["p50_ms"], 2
            )

    top = str(max(batches))
    if top in report.get("speedup_vs_scan", {}):
        report["speedup_bulk"] = report["speedup_vs_scan"][top]
        print(f"[predict] packed/scan steady speedup at {top}: "
              f"{report['speedup_bulk']}x", file=sys.stderr, flush=True)

    # ---- cold-start phase: cache-cleared vs warm-from-disk subprocesses
    report["cold_start"] = _cold_start_phase(booster, args.cold_bucket)
    # smoke forests compile too fast for an honest 10x; the full bench
    # and --cold-smoke (the CI job's serving-sized forest) gate the ratio
    failures.extend(
        _cold_cell_failures(report["cold_start"], require_10x=not args.smoke)
    )

    out = json.dumps(report, indent=2)
    print(out)
    if args.json_path:
        with open(args.json_path, "w") as f:
            f.write(out)

    if args.smoke:
        for cell in report["results"]:
            if cell["rows_per_s"] <= 0:
                failures.append(
                    f"{cell['backend']} at batch {cell['batch']} "
                    "reported zero throughput"
                )
        if failures:
            print("[predict] SMOKE FAILED: " + "; ".join(failures),
                  file=sys.stderr)
            return 1
        print("[predict] smoke OK", file=sys.stderr)
    elif failures:
        print("[predict] PARITY FAILED: " + "; ".join(failures),
              file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
