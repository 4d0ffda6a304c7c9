"""Multi-chip scaling evidence: weak scaling + collective-bytes accounting.

VERDICT r3 #4: nothing measured how the data-parallel/voting collectives
scale.  This tool produces the table BASELINE.md commits:

1. **Weak scaling** over 1→8 virtual CPU devices (fixed rows/device):
   steady train wall for ``tree_learner=data`` vs ``voting`` vs data with
   the quantized integer wire (``hist_quantize``), plus AUC so
   wire-precision tradeoffs are quality-gated.  Virtual CPU devices share
   one core, so WALL numbers measure collective/overhead growth (the
   shape of the curve), not real ICI speedup — the BYTES are the part
   that predicts v5e-32 behavior.
2. **Measured collective bytes**: every ``lax.psum`` / ``psum_scatter`` /
   ``all_gather`` the training program actually traces is recorded as the
   bytes each device RECEIVES from that call site (result shape × dtype —
   a tracing shim, so the numbers come from the real program, not a hand
   formula).  Each in-loop site executes once per grower pass, so the
   traced bytes ARE the per-pass wire volume.  For the bench-shape
   depthwise config the dominant term is the histogram merge: 3·W·F·B
   floats/pass under ``hist_merge="allreduce"`` vs the 3·W·F/D·B slice +
   a (D, 5, L) candidate all-gather under ``"reduce_scatter"`` (ISSUE 4),
   vs the elected top-2k slices (3·W·2k·B) + votes for voting-parallel.
   The ``data`` mode runs the AUTO-resolved default (asserted to be
   reduce_scatter on a real mesh — the benchmarked configuration IS the
   default configuration); ``data_allreduce`` pins the old merge so the
   comms ledger records the measured ratio.  Every traced call is also
   split per link tier (``axis_bytes``: intra-host vs inter-host, via
   ``parallel.distributed.axis_scope`` — ISSUE 14): on the flat 1-D mesh
   every byte is "inter"; the ``data_hier`` mode (D>=4) re-runs training
   on a (2 hosts × D/2) ``mesh2d`` pod with the hierarchical merge, whose
   inter column carries only the (D,5,L) winner exchange + the elected
   column's refinement histogram.
3. **psum vs psum_scatter microbench** on a histogram-shaped array — the
   transport-level bound for the reduce-scatter merge.

Usage:  python tools/bench_scaling.py            # full table (spawns children)
        python tools/bench_scaling.py --out F    # also write rows to F
                                                 # (atomic: F.new + rename,
                                                 # temp removed on failure)
        python tools/bench_scaling.py --child D  # one device count (internal)
"""

import json
import os
import subprocess
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

ROWS_PER_DEV = 32_768
F = 64
B = 256
ITERS = 10
LEAVES = 63
TOP_K = 8


def _log(*a):
    print(*a, file=sys.stderr, flush=True)


class CollectiveRecorder:
    """Tracing shim over lax.psum / lax.psum_scatter / lax.all_gather:
    records the bytes each device RECEIVES per traced call site (result
    shape × dtype — psum: the full reduced array; psum_scatter: the 1/D
    slice; all_gather: the D-fold result).  Numbers reflect the REAL
    program's collectives (anything the grower adds or removes shows up
    here unprompted)."""

    def __init__(self):
        self.calls = []

    def _record(self, kind, out, axis_name):
        import jax

        from mmlspark_tpu.parallel.distributed import axis_scope

        scope = axis_scope(axis_name)
        for leaf in jax.tree_util.tree_leaves(out):
            if not hasattr(leaf, "shape"):
                continue  # psum of a Python scalar constant-folds to an
                # int (the axis-size idiom) — no bytes move
            self.calls.append((kind, tuple(leaf.shape), str(leaf.dtype),
                               int(np.prod(leaf.shape)) * leaf.dtype.itemsize,
                               scope))

    def __enter__(self):
        from jax import lax

        self._lax = lax
        self._psum, self._ag = lax.psum, lax.all_gather
        self._pscat = lax.psum_scatter

        def psum(x, axis_name, **kw):
            out = self._psum(x, axis_name, **kw)
            self._record("psum", out, axis_name)
            return out

        def all_gather(x, axis_name, **kw):
            out = self._ag(x, axis_name, **kw)
            self._record("all_gather", out, axis_name)
            return out

        def psum_scatter(x, axis_name, **kw):
            out = self._pscat(x, axis_name, **kw)
            self._record("reduce_scatter", out, axis_name)
            return out

        self._lax.psum, self._lax.all_gather = psum, all_gather
        self._lax.psum_scatter = psum_scatter
        return self

    def __exit__(self, *exc):
        self._lax.psum, self._lax.all_gather = self._psum, self._ag
        self._lax.psum_scatter = self._pscat

    def summary(self):
        out = {}
        for kind, shape, dtype, nbytes, _scope in self.calls:
            key = f"{kind}{list(shape)}:{dtype}"
            ent = out.setdefault(key, {"bytes": nbytes, "traced_calls": 0})
            ent["traced_calls"] += 1
        return out

    def total_bytes(self):
        """Σ received-bytes over every traced call — the per-pass wire
        volume of the in-loop sites plus one-off setup collectives."""
        return int(sum(c[3] for c in self.calls))

    def axis_bytes(self):
        """Per-link-tier split of :meth:`total_bytes` (ISSUE 14): every
        call's axis argument classified by
        :func:`mmlspark_tpu.parallel.distributed.axis_scope` — "intra"
        bytes ride a host's fast links on the 2D ``mesh2d`` pod, "inter"
        bytes cross the slow data axis.  On a flat 1-D mesh every
        collective runs over the data axis, so everything is "inter"."""
        out = {"inter": 0, "intra": 0}
        for _, _, _, nbytes, scope in self.calls:
            out[scope] = out.get(scope, 0) + nbytes
        return out


def make_data(n, seed=0):
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(n, F)).astype(np.float32)
    w = rng.normal(size=F) * (rng.random(F) < 0.4)
    logits = X @ w + 0.5 * X[:, 0] * X[:, 1]
    y = (logits + rng.logistic(size=n) > 0).astype(np.float64)
    return X.astype(np.float64), y


def _auc(y, p):
    from mmlspark_tpu.engine.eval_metrics import auc

    return float(auc(y, p))


def run_child(n_dev: int):
    # The virtual device count must be set BEFORE jax initializes a backend.
    # The collective-bytes ledger reads the PYTHON trace — an AOT
    # trace-cache replay skips tracing and would record zero collectives,
    # so the bench always re-traces (the compile cache still applies).
    os.environ["MMLSPARK_TPU_NO_TRACE_CACHE"] = "1"
    import jax

    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_num_cpu_devices", n_dev)
    assert jax.device_count() == n_dev, jax.device_count()

    from mmlspark_tpu import obs
    from mmlspark_tpu.engine.booster import Dataset, train
    from mmlspark_tpu.ops.binning import BinMapper
    from mmlspark_tpu.parallel.mesh import default_mesh

    obs.enable()  # per-phase breakdowns ride along in the JSON row
    n = ROWS_PER_DEV * n_dev  # weak scaling: fixed rows per device
    X, y = make_data(n)
    bm = BinMapper(max_bin=B - 1).fit(X)
    ds = Dataset(X, y)
    ds.binned(bm)
    mesh = default_mesh() if n_dev > 1 else None
    base = dict(
        objective="binary", num_iterations=ITERS, num_leaves=LEAVES,
        max_bin=B - 1, min_data_in_leaf=20, grow_policy="depthwise",
        top_k=TOP_K,
    )
    results = {
        "n_devices": n_dev, "rows": n,
        "mesh_shape": [n_dev] if n_dev > 1 else [],
        "modes": {},
    }
    # "data" is the AUTO default path (resolves to reduce_scatter on a
    # real mesh — asserted below the same way bench.py pins the other
    # auto knobs); "data_allreduce" pins the pre-ISSUE-4 merge so the
    # comms ledger records the measured bytes ratio on identical trees.
    modes = [("data", dict(tree_learner="data"), None),
             ("data_allreduce", dict(tree_learner="data",
                                     hist_merge="allreduce"), None),
             # ISSUE 9: int16 gradient buckets + integer merge wire — the
             # recorder shows the hist merge riding int16 (half the f32
             # bytes) and the AUC column quality-gates the quantization
             ("data_quantize", dict(tree_learner="data",
                                    hist_quantize="int16"), None),
             ("voting", dict(tree_learner="voting"), None)]
    if n_dev >= 4 and n_dev % 2 == 0:
        # ISSUE 14: the same devices as a (2 hosts × n/2) mesh2d pod —
        # the intra/inter columns show the hierarchical merge keeping the
        # histogram bulk on the fast feature axis and shipping only the
        # winner exchange + elected-column refinement across hosts.
        from mmlspark_tpu.parallel.mesh import mesh2d

        modes.insert(1, ("data_hier",
                         dict(tree_learner="data",
                              hist_merge="hierarchical"),
                         mesh2d(2, n_dev // 2)))
    if n_dev == 1:
        modes = [("data", dict(tree_learner="serial"), None)]
    for name, extra, mesh_over in modes:
        params = dict(base, **extra)
        m_use = mesh_over if mesh_over is not None else mesh
        with CollectiveRecorder() as rec:
            booster = train(params, ds, bin_mapper=bm, mesh=m_use)  # trace
        if name == "data" and n_dev > 1:
            # The benchmarked default IS the default configuration: a bare
            # tree_learner="data" run must land on the reduce-scatter
            # merge at this mesh/feature shape without opt-in knobs.
            assert booster.config.hist_merge == "reduce_scatter", \
                booster.config.hist_merge
        t0 = time.perf_counter()
        booster = train(params, ds, bin_mapper=bm, mesh=m_use)
        wall = time.perf_counter() - t0
        results["modes"][name] = {
            "steady_wall_s": round(wall, 3),
            "auc": round(_auc(y, booster.predict(X)), 5),
            "hist_merge": booster.config.hist_merge,
            "comm_traced_bytes": rec.total_bytes(),
            "axis_bytes": rec.axis_bytes(),
            "collectives": rec.summary(),
        }

    # psum vs psum_scatter microbench on a histogram-shaped array
    if n_dev > 1:
        import jax.numpy as jnp
        from jax.sharding import PartitionSpec as P

        W = (LEAVES + 1) // 2 + 2  # the level window the grower uses
        shape = (3, W, F, B)
        h = jnp.ones((n_dev,) + shape, jnp.float32)

        def timed(fn, *args):
            fn(*args)[0].block_until_ready() if isinstance(fn(*args), tuple) \
                else fn(*args).block_until_ready()
            t0 = time.perf_counter()
            for _ in range(5):
                r = fn(*args)
                jax.tree_util.tree_leaves(r)[0].block_until_ready()
            return (time.perf_counter() - t0) / 5

        psum_f = jax.jit(jax.shard_map(
            lambda x: jax.lax.psum(x[0], "data"), mesh=mesh,
            in_specs=P("data"), out_specs=P()))
        scat_f = jax.jit(jax.shard_map(
            lambda x: jax.lax.psum_scatter(
                x[0], "data", scatter_dimension=3, tiled=True),
            mesh=mesh, in_specs=P("data"), out_specs=P(None, None, None, "data")))
        results["microbench"] = {
            "shape": list(shape),
            "psum_s": round(timed(psum_f, h), 5),
            "psum_scatter_s": round(timed(scat_f, h), 5),
        }
    results["obs"] = obs.snapshot()
    print(json.dumps(results))


def _write_atomic(path, rows):
    """Write ``rows`` as JSON to ``path`` via a ``.new`` temp file.

    The temp file is removed on any failure so an aborted run never
    leaves a stray ``<path>.new`` in the tree (and a half-written file
    never shadows the committed artifact).
    """
    tmp = path + ".new"
    try:
        with open(tmp, "w") as fh:
            json.dump(rows, fh, indent=1)
            fh.write("\n")
        os.replace(tmp, path)
    except BaseException:
        try:
            os.remove(tmp)
        except OSError:
            pass
        raise


def main(out_path=None):
    rows = []
    for d in (1, 2, 4, 8):
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        env.pop("JAX_NUM_CPU_DEVICES", None)
        try:
            proc = subprocess.run(
                [sys.executable, os.path.abspath(__file__), "--child", str(d)],
                env=env, capture_output=True, text=True, timeout=2700,
            )
        except subprocess.TimeoutExpired:
            _log(f"child D={d} timed out")
            continue
        if proc.returncode != 0:
            _log(f"child D={d} failed:\n{proc.stderr[-3000:]}")
            continue
        rows.append(json.loads(proc.stdout.strip().splitlines()[-1]))
        _log(f"D={d} done")
    print(json.dumps(rows, indent=1))
    if out_path:
        _write_atomic(out_path, rows)
    # Human summary table
    _log("\nD  rows    mode            wall(s)  AUC     merge           "
         "comm/pass  inter/intra      dominant collective")
    for r in rows:
        for mode, m in r["modes"].items():
            # Dominant term = the largest single traced collective (the
            # histogram merge in every mode; keyed psum[...] under
            # allreduce, reduce_scatter[...] under the ISSUE-4 merge).
            hist_key = max(
                m["collectives"],
                key=lambda k: m["collectives"][k]["bytes"],
                default="-",
            )
            hb = m["collectives"].get(hist_key, {}).get("bytes", 0)
            ab = m.get("axis_bytes", {})
            _log(f"{r['n_devices']}  {r['rows']:>7} {mode:<15} "
                 f"{m['steady_wall_s']:>7} {m['auc']:.4f} "
                 f"{m['hist_merge']:<15} "
                 f"{m['comm_traced_bytes']/1e6:>7.2f}MB  "
                 f"{ab.get('inter', 0)/1e6:.2f}/{ab.get('intra', 0)/1e6:.2f}MB  "
                 f"{hb/1e6:.2f} MB ({hist_key})")
        if "microbench" in r:
            mb = r["microbench"]
            _log(f"   microbench {mb['shape']}: psum={mb['psum_s']}s "
                 f"psum_scatter={mb['psum_scatter_s']}s")


if __name__ == "__main__":
    if len(sys.argv) >= 3 and sys.argv[1] == "--child":
        run_child(int(sys.argv[2]))
    elif len(sys.argv) >= 3 and sys.argv[1] == "--out":
        main(out_path=sys.argv[2])
    else:
        main()
