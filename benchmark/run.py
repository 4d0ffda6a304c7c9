"""One run of one benchmark cell.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Everything that belongs to one configuration, traffic kind or per-layer metric
is found by name from ``BENCHMARK.json``: ``configs/<config>.json``,
``data/<data>.py``, ``workloads/<cell>.json``, ``traffic/<kind>.py``,
``metrics/<metric>.py``.  See ``benchmark/README.md``.
"""

import time

_T0 = time.perf_counter()

import argparse
import importlib
import json
import os
import shutil
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

TRACE_DIR = os.path.join(ROOT, ".bench_trace")


def load_cell(name: str):
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = next((w for w in bench["workloads"] if w["name"] == name), None)
    if cell is None:
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json")
    entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    with open(os.path.join(ROOT, entry["file"])) as f:
        cfg = json.load(f)
    with open(os.path.join(ROOT, "benchmark", "workloads", name + ".json")) as f:
        workload = json.load(f)
    return bench, cell, cfg, workload


def metrics_for(bench, cell_name, group, reported):
    """The metrics of ``group`` that this cell reports."""
    out = []
    for m in bench[group]:
        if "workloads" in m and cell_name not in m["workloads"]:
            continue
        if group == "per_layer" and m["moves"] not in reported:
            continue
        out.append(m)
    return out


def require_chips(chips: int):
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < chips:
        raise SystemExit(f"cell needs {chips} TPU chip(s); JAX found {len(devs)} x {devs[0].platform}")


def device_record():
    """The device as JAX reports it: ``memory_peak_bytes`` is the fullest
    chip's ``peak_bytes_in_use``.  The TPU runtime counts the space it reserves
    for compiled programs' temporaries apart (``peak_bytes_reserved``); that
    goes beside it and is read by the per-layer metric ``program_reserved_gb``."""
    import jax

    devs = jax.devices()
    fullest = max((d.memory_stats() or {} for d in devs), key=lambda s: s.get("peak_bytes_in_use", 0))
    print("memory_stats " + json.dumps(fullest), file=sys.stderr)
    return {
        "platform": devs[0].platform, "kind": devs[0].device_kind, "count": len(devs),
        "memory_peak_bytes": int(fullest.get("peak_bytes_in_use", 0)),
        "memory_reserved_peak_bytes": int(fullest.get("peak_bytes_reserved", 0)),
    }


def run(args, need_chip=True, traffic_overrides=None, variant=None):
    """The whole of a run; returns the result object (``main`` prints it).
    The last three arguments are the tests': no look for a chip, a fault
    planted in the timed path, the reference judged in the program's place."""
    bench, cell, cfg, workload = load_cell(args.workload)
    if need_chip:
        require_chips(cell["chips"])
    chip_s = time.perf_counter() - _T0  # interpreter start to the chip's answer
    from mmlspark_tpu import obs
    from mmlspark_tpu.core.jit_cache import enable_compile_cache

    enable_compile_cache()  # JAX_COMPILATION_CACHE_DIR if set, else <checkout>/.jax_cache
    obs.enable()  # counters only: no file is written
    traffic = importlib.import_module(f"benchmark.traffic.{workload['kind']}")
    import_s = time.perf_counter() - _T0 - chip_s
    state, timings = traffic.setup(cfg, workload, args.seed, **(traffic_overrides or {}))
    timings = {"chip_s": chip_s, "import_s": import_s, **timings}
    setup_counters = dict(obs.snapshot().get("counters", {}))
    setup_s = time.perf_counter() - _T0
    print("setup " + json.dumps({"setup_s": setup_s, **timings, "resolved": state.get("resolved")}), file=sys.stderr)

    trace_red = None
    if args.trace:
        import jax

        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(TRACE_DIR, profiler_options=opts)
        t0 = time.perf_counter_ns()
        try:
            result = traffic.window(state, args.seconds, max_fits=1)
        finally:
            window_ns = time.perf_counter_ns() - t0
            jax.profiler.stop_trace()
    else:
        obs.disable()
        result = traffic.window(state, args.seconds)
    window_counters = dict(obs.snapshot().get("counters", {}))
    device = device_record()
    traffic.free(state)

    if args.trace:
        from benchmark import trace

        events = trace.load(TRACE_DIR)
        trace_red = trace.reduce(events, window_ns)
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        if not trace_red or trace_red["busy_s"] <= 0:
            raise SystemExit("the traced window shows no operation on the device")
        device["busy_s"], device["window_s"] = trace_red["busy_s"], trace_red["window_s"]

    values = dict(result["end_to_end"])
    values["setup_s"] = setup_s
    values["peak_hbm_gb"] = device["memory_peak_bytes"] / 1e9
    e2e = metrics_for(bench, cell["name"], "end_to_end", None)
    if args.trace:
        ctx = {
            "trace": trace_red, "window": result, "cfg": cfg, "device": device, "device_kind": device["kind"],
            "rows": int(cfg["rows"]), "cols": int(cfg["num_features"]),
            "setup_counters": setup_counters, "window_counters": window_counters,
        }
        metrics = {}
        for m in metrics_for(bench, cell["name"], "per_layer", {e["name"] for e in e2e}):
            v = importlib.import_module(f"benchmark.metrics.{m['name']}").read(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]} for m in e2e}

    t0 = time.perf_counter()
    numbers = traffic.check(state, result, variant=variant)
    check = {k: {"value": v, "limit": lim} for k, (v, lim) in numbers.items()}
    correct = all(c["value"] <= c["limit"] for c in check.values())
    print(f"reference {time.perf_counter() - t0:.1f} s; fits {['%.3f' % f for f in result['fit_s']]}", file=sys.stderr)
    out = {
        "correct": bool(correct), "attempted": result["attempted"], "failed": result["failed"],
        "metrics": metrics, "device": device,
    }
    if trace_red:
        out["breakdown"] = {"device_ops": trace_red["device_ops"], "idle_gaps": trace_red["idle_gaps"]}
    out["observed"] = result.get("observed", {})
    out["check"] = check
    return out


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    out = run(args)
    for k, c in out["check"].items():
        print(f"check {k} {c['value']:.6g} limit {c['limit']:.6g}", file=sys.stderr)
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
