"""Measure the sharded-program AOT trace cache: multi-host cold fit.

r4 verdict next #1's done bar: on a 2-process process_local run, a fresh
process's cold fit on WARM caches must be far closer to steady than the
~15 s-class Python-tracing tax the meshless path measured (BASELINE.md r4
decomposition).  Tracing cost is a host-side Python cost — independent of
the backend — so this measures it on the virtual-CPU 2-process topology
(the only multi-controller topology this environment can run): the same
bench-class program shape (50 iters, 63 leaves, data-parallel scan with
early-stopping OFF) over small rows, cold-cache round vs warm-cache round,
train()-call wall per process.

Run: python tools/bench_trace_cache_mesh.py
"""

import json
import os
import socket
import subprocess
import sys
import tempfile
import textwrap

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)  # run as a script: make ``tools`` importable

_WORKER = textwrap.dedent("""
    import json, sys, time
    sys.path.insert(0, {repo!r})
    import numpy as np
    from mmlspark_tpu.spark_bridge import barrier_context_from_task_infos
    from mmlspark_tpu.parallel.distributed import (
        global_mesh, initialize_distributed,
    )
    import mmlspark_tpu.engine.booster as bo
    from mmlspark_tpu.ops.binning import distributed_fit

    bo._TRACE_CACHE_MIN_WORK = 0
    pid = int(sys.argv[1]); port = sys.argv[2]

    rng = np.random.default_rng(600 + pid)
    n = 4096
    X = rng.normal(size=(n, 32))
    y = (X[:, 0] - 0.4 * X[:, 1]
         + rng.normal(scale=0.3, size=n) > 0).astype(np.float64)

    ctx = barrier_context_from_task_infos(
        ["127.0.0.1:" + port, "127.0.0.1:0"], pid,
        coordinator_port=int(port))
    initialize_distributed(ctx)
    bm = distributed_fit(X, max_bin=255)
    params = dict(objective="binary", num_iterations=50, num_leaves=63,
                  min_data_in_leaf=5, tree_learner="data")
    mesh = global_mesh()
    ds = bo.Dataset(X, y)
    ds.binned(bm)

    t0 = time.perf_counter()
    b = bo.train(params, ds, bin_mapper=bm, mesh=mesh, process_local=True)
    np.asarray(b.trees.num_leaves)
    cold = time.perf_counter() - t0
    t0 = time.perf_counter()
    b = bo.train(params, ds, bin_mapper=bm, mesh=mesh, process_local=True)
    np.asarray(b.trees.num_leaves)
    steady = time.perf_counter() - t0
    print(json.dumps({{"pid": pid, "cold_s": round(cold, 2),
                       "steady_s": round(steady, 2)}}))
""")


def run_round(cache_dir):
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    with tempfile.TemporaryDirectory() as td:
        script = os.path.join(td, "w.py")
        with open(script, "w") as f:
            f.write(_WORKER.format(repo=REPO))
        env = {
            "PATH": "/usr/bin:/bin:/usr/local/bin", "HOME": "/root",
            "JAX_PLATFORMS": "cpu", "PYTHONDONTWRITEBYTECODE": "1",
            "JAX_COMPILATION_CACHE_DIR": cache_dir,
        }
        procs = [
            subprocess.Popen(
                [sys.executable, script, str(pid), str(port)],
                stdout=subprocess.PIPE, stderr=subprocess.PIPE,
                text=True, env=env,
            )
            for pid in range(2)
        ]
        out = []
        for p in procs:
            o, e = p.communicate(timeout=900)
            if p.returncode != 0:
                raise SystemExit(f"worker failed:\n{e[-3000:]}")
            out.append(json.loads(o.strip().splitlines()[-1]))
        return out


def main():
    from tools import empty_cache_dir

    cdir = empty_cache_dir("jit_cache_trace_mesh")  # traces + XLA entries
    r1 = run_round(cdir)  # cold caches: pays trace + compile
    r2 = run_round(cdir)  # fresh processes, warm caches
    r3 = run_round(cdir)  # repeat (cache-hit variance)
    for tag, r in [("cold-caches", r1), ("warm-caches", r2),
                   ("warm-caches-2", r3)]:
        print(json.dumps({"round": tag, "per_process": r}))
    worst_warm = max(x["cold_s"] for x in r2 + r3)
    steady = min(x["steady_s"] for x in r2 + r3)
    print(json.dumps({
        "metric": "2-process process_local fresh-process cold fit, warm caches",
        "worst_warm_cold_s": worst_warm,
        "steady_s": steady,
        "ratio": round(worst_warm / steady, 2),
        "cold_cache_cold_s": max(x["cold_s"] for x in r1),
    }))


if __name__ == "__main__":
    main()
