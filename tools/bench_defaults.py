"""Defaults-decision table: what the auto-resolved default config costs.

Measures, on the bench shapes (criteo-schema catmix + all-numeric), the
combinations the r5 auto-resolution chooses between:

    grow            split_batch   hist_precision
    lossguide_exact 1-at-a-time   highest (f32)   <- pre-r5 engine default
    lossguide       8 (auto)      highest (f32)
    lossguide       8 (auto)      default (bf16)  <- r5 engine default on TPU
    lossguide       12            default (bf16)  (r5-mid candidate, k-sweep)

reporting steady wall-clock and train-AUC so the default's quality cost is
a committed number, not an assertion (r4 verdict weak #1 / next #2: "decide
the hist_precision default with a committed AUC-delta table").

Each (dataset, config) cell runs in its OWN subprocess, one after the
other: a client process that crashes cannot recover its device state, so
isolation turns a crash into one "crashed" cell instead of a lost table.
The parent never touches JAX — each child has the chip to itself.

Run on the real chip:  python tools/bench_defaults.py
Output: a markdown table on stdout (paste into BASELINE.md) and one JSON
line per cell on stderr.
"""

import json
import os
import subprocess
import sys

sys.path.insert(0, ".")

_CELL = r"""
import json, sys, time
sys.path.insert(0, ".")
import numpy as np
from bench import MAX_BIN, auc, bench_config, make_catmix_data, make_data
from mmlspark_tpu.engine.booster import Dataset, train
from mmlspark_tpu.ops.binning import BinMapper

dname, extra = sys.argv[1], json.loads(sys.argv[2])
if dname == "catmix":
    X, y, cat_idx = make_catmix_data()
    cats = tuple(cat_idx)
else:
    X, y = make_data()
    cats = ()
bm = BinMapper(max_bin=MAX_BIN, categorical_features=cats).fit(X)
ds = Dataset(X, y)
ds.binned(bm)
params = dict(bench_config(cats), **extra)
walls = []
booster = None
for i in range(3):  # run 0 = compile; best of the next 2
    t0 = time.perf_counter()
    booster = train(params, ds, bin_mapper=bm)
    np.asarray(booster.trees.num_leaves)  # sync (device forest)
    w = time.perf_counter() - t0
    if i:
        walls.append(w)
a = auc(y[:100_000], booster.predict(X[:100_000]))
print(json.dumps(dict(wall_s=round(min(walls), 3), auc=round(a, 5),
                      runs=[round(w, 3) for w in walls])))
"""

CONFIGS = [
    ("exact/f32 (pre-r5 default)",
     dict(grow_policy="lossguide_exact", hist_precision="highest")),
    ("batched8/f32",
     dict(split_batch=8, hist_precision="highest")),
    ("batched8/bf16 (r5 default)",
     dict(split_batch=8, hist_precision="default")),
    ("batched12/bf16",
     dict(split_batch=12, hist_precision="default")),
]


def main():
    rows = []
    for dname in ("catmix", "numeric"):
        for cname, extra in CONFIGS:
            r = subprocess.run(
                [sys.executable, "-c", _CELL, dname, json.dumps(extra)],
                capture_output=True, text=True, timeout=900,
                cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            )
            if r.returncode != 0:
                rec = dict(dataset=dname, config=cname, crashed=True,
                           tail=r.stderr.strip().splitlines()[-1:])
            else:
                rec = dict(dataset=dname, config=cname,
                           **json.loads(r.stdout.strip().splitlines()[-1]))
            rows.append(rec)
            print(json.dumps(rec), file=sys.stderr, flush=True)

    print("| dataset | config | steady wall (s) | train-AUC | dAUC vs exact/f32 |")
    print("|---|---|---|---|---|")
    base = {r["dataset"]: r.get("auc") for r in rows if "pre-r5" in r["config"]}
    for r in rows:
        if r.get("crashed"):
            print(f"| {r['dataset']} | {r['config']} | crashed | — | — |")
            continue
        b = base.get(r["dataset"])
        d = f"{r['auc'] - b:+.5f}" if b is not None else "—"
        print(f"| {r['dataset']} | {r['config']} | {r['wall_s']} "
              f"| {r['auc']} | {d} |")


if __name__ == "__main__":
    main()
