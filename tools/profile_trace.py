"""Device-op breakdown of one steady bench train via jax.profiler.

``jax.profiler.start_trace`` writes ``*.trace.json.gz`` with per-HLO
device durations + Python source attribution — this script runs one warm
bench-config train under the profiler and prints the top device ops with
their source lines.  This is the tool behind BASELINE.md's r3
"profiler-driven pass" numbers.

Usage (on the TPU): python tools/profile_trace.py
"""

import collections
import glob
import gzip
import json
import os
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _short_source(s: str, width: int = 44) -> str:
    """Fit a ``path/to/file.py:line`` ref into ``width`` columns keeping
    the ``file.py:line`` TAIL intact.

    The old ``s[-44:]`` left-trim chopped the front of the path mid-word
    (``/root/repo/...`` → ``oot/repo/...``), which broke clickable
    file:line refs in the report.  Shorten by dropping LEADING directories
    wholesale (marking the elision with ``…/``) so whatever remains is a
    real openable suffix of the path.
    """
    if len(s) <= width:
        return s
    parts = s.split("/")
    # keep as many trailing components as fit after the "…/" marker
    for i in range(1, len(parts)):
        tail = "…/" + "/".join(parts[i:])
        if len(tail) <= width:
            return tail
    # even the basename overflows: right-align it, still tail-exact
    return "…" + s[-(width - 1):]


def main():
    import jax

    from bench import MAX_BIN, bench_config, make_catmix_data, make_data
    from mmlspark_tpu.engine.booster import Dataset, train
    from mmlspark_tpu.ops.binning import BinMapper

    if "catmix" in sys.argv[1:]:
        X, y, cat_idx = make_catmix_data()
        params = bench_config(cat_idx)  # headline config + compile cache
        bm = BinMapper(
            max_bin=MAX_BIN, categorical_features=tuple(cat_idx)
        ).fit(X)
    else:
        params = bench_config()  # numeric config + compile cache
        X, y = make_data()
        bm = BinMapper(max_bin=MAX_BIN).fit(X)
    ds = Dataset(X, y)
    ds.binned(bm)
    train(params, ds, bin_mapper=bm)  # warm

    trace_dir = tempfile.mkdtemp(prefix="mmlspark_tpu_trace_")
    jax.profiler.start_trace(trace_dir)
    t0 = time.perf_counter()
    train(params, ds, bin_mapper=bm)
    wall = time.perf_counter() - t0
    jax.profiler.stop_trace()
    print(f"traced steady train: {wall:.2f}s  (trace: {trace_dir})")

    traces = sorted(glob.glob(f"{trace_dir}/**/*.trace.json.gz", recursive=True))
    if not traces:
        raise SystemExit(
            f"no *.trace.json.gz under {trace_dir} — the profiler wrote "
            "nothing (or only xplane.pb) on this platform/jax version"
        )
    path = traces[-1]
    with gzip.open(path) as fh:
        tr = json.load(fh)
    pids = {
        e["pid"]: e["args"].get("name", "")
        for e in tr["traceEvents"]
        if e.get("ph") == "M" and e.get("name") == "process_name"
    }
    dur, cnt, src = collections.Counter(), collections.Counter(), {}
    total = 0
    for e in tr["traceEvents"]:
        if e.get("ph") == "X" and "TPU" in pids.get(e.get("pid"), ""):
            name = e["name"]
            dur[name] += e.get("dur", 0)
            cnt[name] += 1
            s = (e.get("args") or {}).get("source")
            if s:
                src[name] = s
            if name.startswith("jit_"):
                total += e.get("dur", 0)
    print(f"device total (jit programs): {total/1e6:.3f}s of {wall:.2f}s wall")
    for name, d in dur.most_common(20):
        print(
            f"{d/1e6:8.3f}s x{cnt[name]:<5} {name[:52]:52} "
            f"{_short_source(src.get(name, ''))}"
        )


if __name__ == "__main__":
    main()
