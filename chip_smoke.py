#!/usr/bin/env python3
"""Does the system still start on the chip?  ``python3 chip_smoke.py``

Drives the main path once through the entry points a user calls —
``LightGBMClassifier.fit`` → ``model.transform`` → ``ServingApp`` — at the
full width of the headline model (the Criteo-schema classifier of
``bench.py``: 262,144 rows × 13 numeric + 26 categorical, 255 bins, 63
leaves; depth cut to 10 iterations, weights from the data seed), checks
what comes out by the repo's own means, and prints as the LAST line of
stdout one JSON object naming the device as JAX reports it.

A chip belongs to one process at a time, and the persistent caches are only
proven by a second process.  So this file is two things:

- the **driver** (no argument): never imports JAX.  It runs the two legs
  below one after the other as child processes, relays their output, stops
  them at the time limit, and prints the result line only if both passed.
- one **leg** (``--leg cold|warm``): ONE process that holds the chip and runs
  the phases ``device → train → score → serve → kernels → mesh → cache``.
  ``cold`` runs everything; ``warm`` re-runs train/score/serve in a fresh
  process and must find the first leg's work on disk: persistent-cache hits,
  ``trace_cache.hit``, an ``aot-*`` executable loaded ``from_disk`` that
  answers a request, and not one new compile.

Any phase that fails prints ``phase=<name> FAILED …`` and ends the run with a
non-zero exit code and no result line.  Without a TPU the device phase fails
at once: nothing here sets ``JAX_PLATFORMS`` or falls back.  Times printed are
smoke timings for the log, not metrics.

``--rehearse-cpu`` is the debugging aid for a sandbox without a chip: tiny
sizes, Pallas interpreted, every line it prints and its result line say it is
NOT a chip result.
"""

from __future__ import annotations

import argparse
import json
import os
import signal
import subprocess
import sys
import time

TIME_LIMIT_S = 1150  # the contract allows 1200 s, compilation included

# (rows, scored rows, iterations, leaves, rows for the kernel parity checks)
FULL = dict(rows=262_144, score_rows=100_000, iters=10, leaves=63,
            kernel_rows=262_144)
REHEARSAL = dict(rows=8_192, score_rows=2_048, iters=3, leaves=15,
                 kernel_rows=2_048)


class SmokeFailure(Exception):
    pass


def check(cond, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


class phase:
    """``with phase("train") as p: ...; p.note(k=v)`` prints exactly one
    ``phase=<name> ok|FAILED`` line.  A failure is re-raised — nothing
    continues after a failed phase."""

    def __init__(self, name: str, tag: str):
        self.name, self.tag, self.notes = name, tag, []

    def note(self, **kv) -> None:
        self.notes += [f"{k}={v}" for k, v in kv.items()]

    def __enter__(self):
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, exc_type, exc, tb):
        dt = time.perf_counter() - self.t0
        if exc_type is None:
            print(f"phase={self.name} ok {self.tag}smoke_s={dt:.1f} "
                  + " ".join(self.notes), flush=True)
        else:
            print(f"phase={self.name} FAILED {self.tag}smoke_s={dt:.1f} "
                  f"{exc_type.__name__}: {exc}", flush=True)
        return False


# ---------------------------------------------------------------------------
# driver: no JAX in this process
# ---------------------------------------------------------------------------
def drive(rehearse: bool) -> int:
    deadline = time.monotonic() + TIME_LIMIT_S
    reports = {}
    for leg in ("cold", "warm"):
        cmd = [sys.executable, os.path.abspath(__file__), "--leg", leg]
        if rehearse:
            cmd.append("--rehearse-cpu")
        proc = subprocess.Popen(
            cmd, stdout=subprocess.PIPE, text=True, start_new_session=True,
        )
        timer = _kill_at(proc, deadline)
        last = ""
        for line in proc.stdout:
            sys.stdout.write(line)
            sys.stdout.flush()
            last = line.strip() or last
        rc = proc.wait()
        timer.cancel()
        if rc != 0:
            print(f"chip_smoke: leg={leg} exited with code {rc}; no result",
                  file=sys.stderr, flush=True)
            return rc if rc > 0 else 1
        reports[leg] = json.loads(last)
    device = reports["cold"]["device"]
    if reports["warm"]["device"] != device:
        print("chip_smoke: the two legs saw different devices", file=sys.stderr)
        return 1
    result = {"ok": not rehearse, "device": device}
    if rehearse:
        result["rehearsal"] = "cpu - not a chip result"
    print(json.dumps(result), flush=True)
    return 0


def _kill_at(proc, deadline):
    """Stop the leg (its whole process group) at the time limit."""
    import threading

    def kill():
        print("chip_smoke: time limit reached, stopping the leg",
              file=sys.stderr, flush=True)
        try:
            os.killpg(proc.pid, signal.SIGKILL)
        except ProcessLookupError:
            pass

    t = threading.Timer(max(1.0, deadline - time.monotonic()), kill)
    t.daemon = True
    t.start()
    return t


# ---------------------------------------------------------------------------
# one leg: one process, holds the chip
# ---------------------------------------------------------------------------
def run_leg(leg: str, rehearse: bool) -> int:
    tag = "REHEARSAL-cpu-not-a-chip-result " if rehearse else ""
    sz = REHEARSAL if rehearse else FULL

    with phase("device", tag) as p:
        import jax

        dev = jax.devices()[0]
        device = {"platform": dev.platform, "kind": dev.device_kind,
                  "count": len(jax.devices())}
        check(
            dev.platform == "tpu" or rehearse,
            f"needs a TPU; JAX found platform={dev.platform!r} "
            f"kind={dev.device_kind!r} count={len(jax.devices())}",
        )
        import jaxlib
        from importlib import metadata

        from mmlspark_tpu import native, obs
        from mmlspark_tpu.core import jit_cache

        obs.enable()
        check(jit_cache.enable_compile_cache(), "compile cache did not enable")
        import shutil

        binner = "native" if native.get_binner_lib() is not None else "numpy"
        check(
            binner == "native" or shutil.which("g++") is None
            or os.environ.get("MMLSPARK_TPU_NO_NATIVE"),
            "g++ is installed but the native binner did not build",
        )
        p.note(leg=leg, **device, jax=jax.__version__,
               jaxlib=jaxlib.__version__, libtpu=metadata.version("libtpu"),
               cache_dir=jit_cache.cache_dir(), binner=binner)
    on_chip = dev.platform == "tpu"
    if rehearse:
        # tiny fits are below the work threshold that turns the trace
        # cache on; the rehearsal still wants to walk that path
        import mmlspark_tpu.engine.booster as _bo

        _bo._TRACE_CACHE_MIN_WORK = 0

    import numpy as np

    import bench  # the repo's benchmark module: data makers + AUC

    k = sz["score_rows"]
    with phase("train", tag) as p:
        import dataclasses

        from mmlspark_tpu.engine.booster import Dataset, train

        Xc, yc, cat_idx = bench.make_catmix_data()
        model_c = fit(sz, Xc, yc, cat_idx)
        bc = model_c.getBooster()
        rc = bc.config
        p.note(hist_backend=rc.hist_backend, split_batch=rc.split_batch,
               hist_precision=rc.hist_precision)
        if on_chip:
            check(rc.hist_backend == "pallas", f"hist_backend={rc.hist_backend}")
            check(rc.split_batch == 8, f"split_batch={rc.split_batch}")
            check(rc.hist_precision == "default",
                  f"hist_precision={rc.hist_precision}")
        pred_c = bc.predict(Xc[:k])
        check(np.isfinite(pred_c).all(), "NaN/inf in train-set predictions")
        auc = bench.auc(yc[:k], pred_c)
        p.note(auc=round(auc, 5))
        if leg == "cold":
            # the lax reference ON THIS DEVICE: same resolved config and
            # bins, histograms by XLA scatter-add instead of the kernels
            ref = train(
                dict(dataclasses.asdict(rc), hist_backend="scatter"),
                Dataset(Xc[: sz["rows"]], yc[: sz["rows"]]),
                bin_mapper=bc.bin_mapper,
            )
            auc_ref = bench.auc(yc[:k], ref.predict(Xc[:k]))
            p.note(auc_scatter=round(auc_ref, 5))
            check(abs(auc - auc_ref) <= 0.005,
                  f"AUC {auc:.5f} vs scatter reference {auc_ref:.5f}")

    with phase("score", tag) as p:
        import pickle

        T = bc.num_iterations
        out = model_c.transform(frame(Xc, yc, k))
        prob = np.stack(out["probability"])
        check(prob.shape == (k, 2) and np.isfinite(prob).all(),
              f"categorical transform: shape {prob.shape}")
        check(np.array_equal(prob[:, 1], pred_c), "transform != predict")
        be_c = bc._resolved_predict_backend(T)
        check(be_c == "packed", f"categorical forest resolved to {be_c}")

        Xn, yn = bench.make_data()
        model_n = fit(sz, Xn, yn, ())
        bn = model_n.getBooster()
        be_n = bn._resolved_predict_backend(T)
        p.note(criteo_predict_backend=be_c, numeric_predict_backend=be_n)
        if on_chip:
            check(be_n == "pallas", f"numeric forest resolved to {be_n}")
        prob_n = np.stack(model_n.transform(frame(Xn, yn, k))["probability"])
        check(prob_n.shape == (k, 2) and np.isfinite(prob_n).all(),
              f"numeric transform: shape {prob_n.shape}")
        packed = pickle.loads(pickle.dumps(bn))
        packed.config = dataclasses.replace(bn.config, predict_backend="packed")
        # tests/test_packed_forest.py: every backend scores bitwise-equal
        check(np.array_equal(prob_n[:, 1], packed.predict(Xn[:k])),
              f"{be_n} replay != packed traversal")
        p.note(numeric_auc=round(bench.auc(yn[:k], prob_n[:, 1]), 5))

    with phase("serve", tag) as p:
        answered = serve_phase(
            {"numeric": (model_n, Xn), "criteo": (model_c, Xc)}
        )
        p.note(**answered)

    if leg == "cold":
        with phase("kernels", tag) as p:
            p.note(**kernels_phase(sz, bn, Xn))

        if len(jax.devices()) == 1:
            print(f"phase=mesh skipped (1 device) {tag}", flush=True)
        else:
            with phase("mesh", tag) as p:
                p.note(**mesh_phase(sz, Xc, yc, cat_idx, pred_c, on_chip))

    with phase("cache", tag) as p:
        c = obs.snapshot()["counters"]
        jc = jit_cache.cache_counters()
        tc = {n: int(c.get(f"trace_cache.{n}", 0))
              for n in ("hit", "miss", "memo_hit", "off")}
        p.note(
            xla_hit=int(jc["hit"]), xla_miss=int(jc["miss"]),
            aot_hits=int(jc["aot_hits"]), aot_misses=int(jc["aot_misses"]),
            aot_bytes=int(jc["aot_bytes"]), pruned=int(jc["pruned"]),
            **{f"trace_{n}": v for n, v in tc.items()},
            cache_mb=round(_dir_mb(jit_cache.cache_dir()), 1),
        )
        check(tc["off"] == 0, "trace cache turned itself off for a program")
        if leg == "warm":
            check(jc["hit"] > 0, "second process: no persistent-cache hit")
            check(jc["miss"] == 0,
                  f"second process compiled {int(jc['miss'])} programs anew")
            check(tc["hit"] > 0 and tc["miss"] == 0,
                  f"second process re-traced: {tc}")
            check(jc["aot_hits"] > 0 and jc["aot_misses"] == 0,
                  "second process: serving executables not loaded from disk")
    print(json.dumps({"leg": leg, "device": device}), flush=True)
    return 0


def frame(X, y, rows, partitions=1):
    from mmlspark_tpu import DataFrame

    return DataFrame({"features": list(X[:rows]), "label": y[:rows]},
                     num_partitions=partitions)


def fit(sz, X, y, cat_idx, partitions=1):
    """The user's call: everything but size and schema at its default."""
    from mmlspark_tpu import LightGBMClassifier

    clf = LightGBMClassifier(
        numIterations=sz["iters"], numLeaves=sz["leaves"], maxBin=255,
        categoricalSlotIndexes=list(cat_idx) or None,
    )
    return clf.fit(frame(X, y, sz["rows"], partitions))


def _dir_mb(path: str) -> float:
    with os.scandir(path) as it:
        return sum(e.stat().st_size for e in it if e.is_file()) / (1 << 20)


def serve_phase(models: dict) -> dict:
    """``ServingApp`` in this process: every route pre-warmed, a few
    ``POST /models/<name>/predict`` over loopback equal to offline
    ``predict`` on the same float32 rows, ``/readyz`` 200, a clean stop —
    and the traffic reaches the compile cache not once."""
    import urllib.request

    import numpy as np

    from mmlspark_tpu.core.jit_cache import cache_counters
    from mmlspark_tpu.serve import ServingApp

    def call(url, payload=None):
        data = None if payload is None else json.dumps(payload).encode()
        req = urllib.request.Request(
            url, data=data, headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=120) as r:
            return r.status, json.loads(r.read().decode())

    app = ServingApp(port=0)
    wire, want = {}, {}
    for name, (model, X) in models.items():
        app.add_model(name, model=model)
        wire[name] = X[:40].astype(np.float32)  # the serving wire is float32
        want[name] = model.getBooster().predict(wire[name].astype(np.float64))
    before = cache_counters()
    app.start()  # pre-warms every bucket of every route
    at_ready = app.jit_counters_at_ready()
    # serving executables + packed forests: loaded from disk / built anew
    answered = {"aot_from_disk": int(at_ready["aot_hits"] - before["aot_hits"]),
                "aot_built": int(at_ready["aot_misses"] - before["aot_misses"])}
    try:
        status, ready = call(f"{app.url}/readyz")
        check(status == 200 and ready["ready"], f"/readyz said {status}")
        for name, rows in wire.items():
            url = f"{app.url}/models/{name}/predict"
            _, one = call(url, {"features": rows[0].tolist()})
            _, many = call(url, {"instances": rows.tolist()})
            got = np.asarray([one["prediction"]] + many["predictions"])
            check(
                np.array_equal(
                    got, np.concatenate([want[name][:1], want[name]])),
                f"route {name}: served answers differ from offline predict",
            )
            answered[f"{name}_requests"] = 2
        after = cache_counters()
        lookups = sum(after[n] - at_ready[n] for n in ("hit", "miss"))
        check(lookups == 0, f"traffic reached the compile cache: {after}")
    finally:
        clean = app.stop()
    check(clean, "ServingApp.stop() did not drain cleanly")
    return answered


def kernels_phase(sz: dict, booster, X) -> dict:
    """Every Pallas entry point of ``ops/`` that the fits above did not
    already run: compiled once at a real shape and held to its lax
    reference at the tolerance the unit tests state."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from mmlspark_tpu.ops import histogram as H
    from mmlspark_tpu.ops import pallas_hist as PH

    n, F, B = sz["kernel_rows"], 39, 256
    rng = np.random.default_rng(11)
    bins_t = jnp.asarray(rng.integers(0, B, size=(F, n), dtype=np.uint8))
    vals = jnp.asarray(rng.normal(size=(3, n)), jnp.float32)
    qvals = jnp.asarray(rng.integers(-127, 128, size=(3, n)), jnp.int16)
    mask = jnp.ones(n, bool)
    done = {}

    def close(got, ref, what, tol):
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=tol, atol=tol, err_msg=what)
        done[what] = "ok"

    def equal(got, ref, what):  # integer kernels are exact by contract
        check(np.array_equal(np.asarray(got), np.asarray(ref)), what)
        done[what] = "ok"

    # W=8 is the default lossguide window (nibble kernel), W=32 the plain
    # by-leaf kernel of depthwise growth
    for W, name in ((8, "by_leaf_nibble"), (32, "by_leaf")):
        leaf = jnp.asarray(rng.integers(-1, W + 1, size=n), jnp.int32)
        got, ref = (
            H.build_histogram_by_leaf(bins_t, vals, leaf, W, B, backend=be,
                                      chunk=n)
            for be in ("pallas", "scatter")
        )
        close(got, ref, name, 1e-5)  # test_gbdt_engine.py TestByLeafKernels
        equal(
            PH.pallas_hist_by_leaf_chunk(
                bins_t, qvals, leaf, W, B, precision="default"),
            H._scatter_hist_by_leaf_chunk(bins_t, qvals, leaf, W, B),
            f"by_leaf_int_W{W}",
        )
    got, ref = (
        H.build_histogram(bins_t, vals, mask, B, backend=be, chunk=n)
        for be in ("pallas", "scatter")
    )
    close(got, ref, "hist", 1e-4)  # ...::test_pallas_matches_scatter
    equal(
        PH.pallas_hist_chunk(bins_t, qvals, B, precision="default"),
        H._scatter_hist_chunk(bins_t, qvals, B),
        "hist_int",
    )

    # the streamed ingest's fused bin+occupancy kernel
    from mmlspark_tpu.ops.device_binning import bin_rows_device
    from mmlspark_tpu.ops.pallas_binhist import bin_occ_rows

    db = booster.device_binner()
    rows = jnp.asarray(X[: min(n, 65_536)], jnp.float32)
    kw = dict(missing_bin=db.missing_bin, n_bounds=db.n_bounds)
    ref_bins = np.asarray(bin_rows_device(db.arrays, rows, **kw))
    occ_ref = np.zeros((rows.shape[1], B), np.int32)
    np.add.at(occ_ref, (np.arange(rows.shape[1])[None, :], ref_bins), 1)
    bins_u8, occ = jax.jit(
        lambda a, r: bin_occ_rows(a, r, num_bins=B, **kw)
    )(db.arrays, rows)
    equal(bins_u8, ref_bins, "bin_occ_bins")
    equal(occ, occ_ref, "bin_occ_tally")

    # the multi-model replay kernel: two forests in one launch, each
    # bitwise its own packed traversal
    from mmlspark_tpu.engine.booster import Dataset, train
    from mmlspark_tpu.ops import pallas_predict as PP

    m = min(n, 16_384)
    y2 = (X[:m, 0] + X[:m, 3] > 0).astype(np.float64)
    small = train(dict(objective="binary", num_iterations=3, num_leaves=15),
                  Dataset(X[:m], y2))
    fleet, rows_bins, want = [], [], []
    for b in (booster, small):
        T = b.num_iterations
        fleet.append((b._host_trees(), b.tree_weights, T,
                      b.bin_mapper.num_bins))
        bb = jnp.asarray(b.bin_mapper.transform(X[:1024]))
        rows_bins.append(bb)
        want.append(np.asarray(b._raw_scores_dispatch(bb, T, "packed")))
    mpf = PP.build_multi_pallas_forest(fleet)
    mid = jnp.asarray(np.repeat([0, 1], 1024), jnp.int32)
    got = np.asarray(PP.multi_pallas_raw_scores(
        mpf, jnp.concatenate(rows_bins), mid))
    equal(got[:, :1024], want[0], "multi_replay_model0")
    equal(got[:, 1024:], want[1], "multi_replay_model1")

    # the stacked trainer's TPU-only one-hot contractions
    from mmlspark_tpu.engine.multi_train import (
        MultiTrainJob,
        fit_shared_mapper,
        multi_train,
    )

    params = dict(objective="binary", num_iterations=3, num_leaves=15)
    sets = []
    for j in range(2):
        Xj = X[j * 2048:(j + 1) * 2048]
        sets.append(
            Dataset(Xj, (Xj[:, 0] + Xj[:, j + 1] > 0).astype(np.float64)))
    shared = fit_shared_mapper(sets, params)
    stacked = multi_train([MultiTrainJob(params, ds) for ds in sets], shared)
    for j, (ds, b) in enumerate(zip(sets, stacked)):
        alone = train(params, ds, bin_mapper=shared)
        equal(b.predict(ds.X), alone.predict(ds.X), f"multi_train_model{j}")
    return done


def mesh_phase(sz, X, y, cat_idx, pred_one_chip, on_chip) -> dict:
    """The same fit, data-parallel over every device of the host: a
    D-partition DataFrame makes the facade build the mesh.  The data must
    really live on all the devices — code that has only seen virtual CPU
    devices may leave everything on device 0."""
    import dataclasses
    import gc

    import jax
    import numpy as np

    from mmlspark_tpu.engine.booster import Dataset, train
    from mmlspark_tpu.parallel.mesh import default_mesh

    devs = jax.devices()
    D = len(devs)

    def mem(key):
        return [(d.memory_stats() or {}).get(key, 0) for d in devs]

    peak0 = mem("peak_bytes_in_use")
    b = fit(sz, X, y, cat_idx, partitions=D).getBooster()
    peak1 = mem("peak_bytes_in_use")
    # (off the chip the scatter backend keeps exact lossguide growth, and
    # auto never moves that onto the windowed reduce-scatter grower)
    check(b.config.hist_merge == "reduce_scatter" or not on_chip,
          f"hist_merge resolved to {b.config.hist_merge}")
    k = len(pred_one_chip)
    diff = np.abs(b.predict(X[:k]) - pred_one_chip)
    # the gate __graft_entry__.dryrun_multichip holds the mesh path to
    check(diff.mean() < 1e-3,
          f"mean prediction drift vs the one-chip model {diff.mean():.2e}")
    if on_chip:
        # device 0 carries the high-water mark of the earlier phases; the
        # others have held nothing yet, so the fit must raise their peaks
        check(all(p1 > p0 for p0, p1 in zip(peak0[1:], peak1[1:])),
              f"a device never held data: peaks {peak0} -> {peak1}")
    # where the binned matrix and the scores live: the engine call the
    # facade just made, repeated with the Dataset in hand
    gc.collect()
    used0 = mem("bytes_in_use")
    ds = Dataset(X[: sz["rows"]], y[: sz["rows"]])
    b2 = train(dataclasses.asdict(b.config), ds, mesh=default_mesh(D),
               bin_mapper=b.bin_mapper)
    (bins_dev,) = ds._dev_bins_cache.values()
    scores = b2._raw_scores_binned(bins_dev)
    jax.block_until_ready(scores)
    used1 = mem("bytes_in_use")
    if on_chip:
        check(all(u1 > u0 for u0, u1 in zip(used0, used1)),
              f"a device holds no shard: bytes_in_use {used0} -> {used1}")
    for what, a in (("bins", bins_dev), ("scores", scores)):
        check(a.sharding.device_set == set(devs),
              f"{what} live on {sorted(d.id for d in a.sharding.device_set)}")
    check(len({str(s.index) for s in bins_dev.addressable_shards}) == D,
          "the binned matrix is replicated, not row-sharded")
    return dict(
        devices=D, hist_merge=b.config.hist_merge,
        drift_mean=f"{diff.mean():.1e}", drift_max=f"{diff.max():.1e}",
        bins_shard=bins_dev.sharding.spec, scores_shard=scores.sharding.spec,
        held_mb="/".join(f"{(u1 - u0) / 2**20:.1f}"
                         for u0, u1 in zip(used0, used1)),
        peak_mb="/".join(str(p >> 20) for p in peak1),
    )


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--leg", choices=("cold", "warm"),
                    help="run one leg in this process (the driver does)")
    ap.add_argument("--rehearse-cpu", action="store_true",
                    help="debugging aid: tiny sizes on the CPU; NOT a chip "
                         "result")
    args = ap.parse_args()
    if not args.leg:
        return drive(args.rehearse_cpu)
    try:
        return run_leg(args.leg, args.rehearse_cpu)
    except SmokeFailure:
        return 1  # its phase line says why; any other exception keeps its traceback


if __name__ == "__main__":
    sys.exit(main())
