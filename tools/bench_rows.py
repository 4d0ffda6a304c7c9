"""Row-scaling / HBM-capacity envelope on one chip (r4 verdict next #3).

The north star is Criteo-1TB on v5e-32 — O(100M) rows per chip — but
nothing had ever measured training beyond 262k rows.  This sweeps the
criteo-schema shape at 1M/2M/4M rows on the real chip at ENGINE DEFAULTS,
reporting steady s/iter, device peak memory, and the resolved statics
(no form of the fit changes with the rows since PR 30: the leaf delta is
`_leaf_lookup`'s select form and the leaf statistics a one-hot
contraction over `tree._LEAF_TOTALS_CHUNK` rows at a time, at every n).

Each cell runs in its own subprocess, one after the other (a cell that
runs out of device memory takes only its own process down; the parent never
touches JAX, so each child has the chip to itself).  A crashed cell is
recorded and the sweep exits non-zero.

Run: python tools/bench_rows.py [--out F] [rows ...]

Cell results stream to stdout AND to ``--out`` (default
``bench_out/rows_out.jsonl``, an ignored scratch directory — bench
scratch never lands in the repo root where it reads as a committed
ledger).  The file is written atomically at the end of the sweep.
"""

import json
import os
import subprocess
import sys

_CELL = r"""
import json, sys, time
sys.path.insert(0, ".")
import numpy as np

N = int(sys.argv[1])
ITERS = int(sys.argv[2])

rng = np.random.default_rng(11)
N_NUM, N_CAT = 13, 26
Xn = rng.normal(size=(N, N_NUM)).astype(np.float32)
cards = rng.integers(4, 200, size=N_CAT)
Xc = np.column_stack([rng.integers(0, c, size=N) for c in cards])
logits = (Xn @ (rng.normal(size=N_NUM) * 0.5).astype(np.float32)
          + 0.8 * (Xc[:, 0] % 5 == 2) - 0.6 * (Xc[:, 1] % 7 == 3))
y = (logits + rng.logistic(size=N).astype(np.float32) > 0).astype(np.float64)
X = np.column_stack([Xn.astype(np.float64), Xc.astype(np.float64)])
del Xn, Xc, logits

from mmlspark_tpu.engine.booster import Dataset, train
from mmlspark_tpu.ops.binning import BinMapper
import jax

cats = tuple(range(N_NUM, N_NUM + N_CAT))
t0 = time.perf_counter()
bm = BinMapper(max_bin=255, categorical_features=cats).fit(X)
ds = Dataset(X, y)
ds.binned(bm)
bin_s = time.perf_counter() - t0

params = dict(objective="binary", num_iterations=ITERS, num_leaves=63,
              max_bin=255, min_data_in_leaf=20, learning_rate=0.1,
              categorical_feature=list(cats))
walls = []
b = None
for i in range(3):
    t0 = time.perf_counter()
    b = train(params, ds, bin_mapper=bm)
    np.asarray(b.trees.num_leaves)
    w = time.perf_counter() - t0
    if i:
        walls.append(w)
mem = {}
try:
    ms = jax.local_devices()[0].memory_stats() or {}
    mem = {k: int(v) for k, v in ms.items()
           if k in ("bytes_in_use", "peak_bytes_in_use", "bytes_limit")}
except Exception:
    pass
rc = b.config
print(json.dumps(dict(
    rows=N, iters=ITERS, bin_s=round(bin_s, 2),
    steady_s=round(min(walls), 3),
    s_per_iter=round(min(walls) / ITERS, 4),
    onehot_stats=jax.default_backend() == "tpu",
    hist_chunk=rc.hist_chunk, split_batch=rc.split_batch,
    mem=mem,
)))
"""


def _write_atomic(path, lines):
    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
    tmp = path + ".new"
    try:
        with open(tmp, "w") as f:
            f.write("".join(ln + "\n" for ln in lines))
        os.replace(tmp, path)
    except BaseException:
        try:
            os.remove(tmp)
        except OSError:
            pass
        raise


def main():
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    argv = sys.argv[1:]
    out_path = os.path.join(repo, "bench_out", "rows_out.jsonl")
    if "--out" in argv:
        i = argv.index("--out")
        out_path = argv[i + 1]
        argv = argv[:i] + argv[i + 2:]
    rows = [int(a) for a in argv] or [1 << 20, 1 << 21, 1 << 22]
    lines = []
    crashed = 0
    for n in rows:
        iters = 20
        r = subprocess.run(
            [sys.executable, "-c", _CELL, str(n), str(iters)],
            capture_output=True, text=True, timeout=1800, cwd=repo,
        )
        if r.returncode != 0:
            crashed += 1
            line = json.dumps(dict(rows=n, crashed=True,
                                   tail=r.stderr.strip().splitlines()[-1:]))
        else:
            line = r.stdout.strip().splitlines()[-1]
        print(line, flush=True)
        lines.append(line)
    _write_atomic(out_path, lines)
    return 1 if crashed else 0


if __name__ == "__main__":
    sys.exit(main())
