"""Benchmark: streamed out-of-core ingestion vs the host binning pass.

Prints ONE JSON line and (without ``--smoke``) writes it to
``INGEST_BENCH.json``:
    {"metric": ..., "value": N, "unit": "s", "host_total_s": N, ...}

Shape: the r5 (2026-07-30) all-numeric bench config — 262,144 rows x 64
f32 features, max_bin=255 — whose HOST binning cost that run reported as
~1.12 s (fit 0.73 + transform 0.39).  The streamed path replaces both with:

- a chunked SKETCH pass (host, mergeable KLL — paid once per dataset,
  overlapped with shard I/O by the prefetch thread), and
- a DEVICE-BIN ingest pass (raw f32 chunks upload double-buffered and bin
  on device through the BinningAuthority's double-single boundary table).

The headline ``value`` is the STEADY ingest wall (second run, jit warm) —
the recurring cost of re-binning a dataset through the device path, the
like-for-like replacement for the host fit+transform the LightGBM
protocol pays at Dataset construction.  GATE (ISSUE 10, scoped by ISSUE
11): steady ingest ≤ 0.5× the SAME-PROCESS host fit+transform wall.
The ratio is a DEVICE-vs-host claim, so it hard-gates only on
accelerator backends; on ``backend: cpu`` (this box — the "device" path
is XLA:CPU racing tuned numpy) it is recorded honestly but advisory
(``gate_enforced: false``).  The nibble-packed max_bin=15 leg rides
along to show the halved cache footprint, and the 255-bin BYTE-TIER
gate (ISSUE 11) asserts the histogram working set — the transposed
(F, n) matrix every hist pass consumes — stays 1 byte/index, ≤ half
(in fact ¼) of the int32 layout it replaced, with a timed hist pass
over it (``ingest.hist`` span).

Since ISSUE 20 the streamed leg runs the 3-stage pipelined ingest
(decode → upload → device-step, ``data/streaming.py``): the record
carries the pipeline telemetry (``overlap_ratio``, ``max_in_flight``,
per-stage walls under ``pipeline``) plus a serial comparator leg
(``overlap=False`` — same kernels, no overlap) isolating the
pipelining win, and the cpu trend gate in ``tools/bench_ratchet.py``
holds the steady wall below the pre-pipeline 3.61 s record
(``r17_steady_s``).

Timing protocol: best-of-2 for the host legs, cold + steady for the
streamed legs (cold pays jit compile and is reported separately).  obs is
enabled for the streamed run; the final snapshot (ingest.* counters,
train.binning.* spans) embeds under ``"obs"`` so
``python -m tools.obs report INGEST_BENCH.json`` shows the breakdown.

``--smoke``: 16,384 x 16 in-CI shape — asserts the pipeline runs
multi-chunk and the gate fields exist, never the perf ratio (CI machines
are not the bench box).
"""

import argparse
import json
import os
import sys
import tempfile
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

N_ROWS = 262_144
N_FEATURES = 64
MAX_BIN = 255
CHUNK_ROWS = 32_768
R05_HOST_BINNING_S = 1.12  # r5 bench, numeric: fit 0.73 + transform 0.39
# ISSUE-10 record for the same leg, for cross-run context: the host legs
# (unchanged pure-numpy code) calibrate box drift between records.
R10_STEADY_S = 2.52
R10_HOST_TOTAL_S = 1.179
# ISSUE-17 record (pre-pipelined ingest): the 3-stage overlap rework
# (ISSUE 20) must improve on this — the cpu trend gate in bench_ratchet
# holds the steady wall below it.
R17_STEADY_S = 3.61
R17_HOST_TOTAL_S = 1.666


def _log(*a):
    print(*a, file=sys.stderr, flush=True)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="tiny CI shape; no perf gate")
    ap.add_argument("--out", default=None,
                    help="output JSON path (default INGEST_BENCH.json "
                         "next to the repo root; '-' for stdout only)")
    ns = ap.parse_args(argv)

    n_rows = 16_384 if ns.smoke else N_ROWS
    n_feat = 16 if ns.smoke else N_FEATURES
    chunk_rows = 4096 if ns.smoke else CHUNK_ROWS

    import jax

    from mmlspark_tpu import obs
    from mmlspark_tpu.data import (
        RowGroupSource,
        stream_fit_binning,
        stream_ingest,
        write_row_group_shards,
    )
    from mmlspark_tpu.ops.binning import BinningAuthority

    _log(f"[ingest] backend={jax.default_backend()} "
         f"devices={len(jax.devices())} rows={n_rows} features={n_feat}")

    rng = np.random.default_rng(0)
    X = rng.normal(size=(n_rows, n_feat)).astype(np.float32)

    with tempfile.TemporaryDirectory() as td:
        src = RowGroupSource(write_row_group_shards(
            os.path.join(td, "rg"), X,
            (X[:, 0] > 0).astype(np.float64), rows_per_group=65_536))
        n_chunks = -(-n_rows // chunk_rows)
        assert n_chunks > 1, "bench must exercise a multi-chunk stream"

        # -- host leg: the binning pass the streamed path replaces ------
        Xh = X.astype(np.float64)
        fit_runs, tr_runs = [], []
        for _ in range(2):
            t0 = time.perf_counter()
            authority_h = BinningAuthority.fit(Xh, max_bin=MAX_BIN)
            fit_runs.append(time.perf_counter() - t0)
            t0 = time.perf_counter()
            authority_h.bin_host(Xh)
            tr_runs.append(time.perf_counter() - t0)
        host_fit_s, host_tr_s = min(fit_runs), min(tr_runs)
        host_total_s = host_fit_s + host_tr_s
        _log(f"[ingest] host binning: fit={host_fit_s:.2f}s "
             f"transform={host_tr_s:.2f}s total={host_total_s:.2f}s "
             f"(r5 reference {R05_HOST_BINNING_S:.2f}s)")

        # -- streamed leg ----------------------------------------------
        obs.enable()
        t0 = time.perf_counter()
        authority, sketch = stream_fit_binning(
            src, max_bin=MAX_BIN, chunk_rows=chunk_rows)
        sketch_s = time.perf_counter() - t0
        t0 = time.perf_counter()
        ds = stream_ingest(src, authority, chunk_rows=chunk_rows)
        ingest_cold_s = time.perf_counter() - t0
        t0 = time.perf_counter()
        ds = stream_ingest(src, authority, chunk_rows=chunk_rows)
        ingest_steady_s = time.perf_counter() - t0
        unpacked_bytes = ds.binned_cache_nbytes
        pipeline = dict(ds.ingest_stats)
        _log(f"[ingest] streamed: sketch={sketch_s:.2f}s "
             f"(rank_eps={sketch.rank_epsilon:.2e}) "
             f"cold={ingest_cold_s:.2f}s (incl. compile) "
             f"steady={ingest_steady_s:.2f}s "
             f"overlap={pipeline.get('overlap_ratio', 0):.2f} "
             f"in_flight={pipeline.get('max_in_flight', 0)}")

        # -- serial comparator: same kernels, overlap disabled — the
        # pipelining win in isolation (steady wall vs steady wall)
        t0 = time.perf_counter()
        stream_ingest(src, authority, chunk_rows=chunk_rows, overlap=False)
        ingest_serial_s = time.perf_counter() - t0
        _log(f"[ingest] serial (overlap=False) steady: "
             f"{ingest_serial_s:.2f}s")

        # -- byte-tier hist phase (ISSUE 11): the transposed working set
        # every hist pass consumes must ride 1-byte indices at 255 bins,
        # ≤ half the int32 layout it replaced (it is actually ¼).
        import jax.numpy as jnp

        from mmlspark_tpu.ops.binpack import hist_transpose
        from mmlspark_tpu.ops.histogram import build_histogram

        B = int(authority.num_bins)
        bins_t = jax.jit(hist_transpose, static_argnums=1)(
            ds.binned(authority.mapper), B)
        assert bins_t.dtype == jnp.uint8, bins_t.dtype
        byte_ws_bytes = int(bins_t.nbytes)
        int32_ws_bytes = 4 * n_rows * n_feat
        assert 2 * byte_ws_bytes <= int32_ws_bytes
        vals = jnp.ones((3, n_rows), jnp.float32)
        rmask = jnp.ones(n_rows, bool)

        def hist_once():
            build_histogram(bins_t, vals, rmask, B).block_until_ready()

        hist_once()  # warm the jit
        with obs.span("ingest.hist", rows=n_rows, features=n_feat):
            t0 = time.perf_counter()
            hist_once()
            hist_steady_s = time.perf_counter() - t0
        _log(f"[ingest] hist pass over byte-tier cache: "
             f"{hist_steady_s:.2f}s  working set {byte_ws_bytes} B "
             f"(int32 equiv {int32_ws_bytes} B)")

        # -- packed leg: max_bin=15 halves the device cache ------------
        authority15, _ = stream_fit_binning(
            src, max_bin=15, chunk_rows=chunk_rows)
        ds15 = stream_ingest(src, authority15, chunk_rows=chunk_rows)
        packed_bytes = ds15.binned_cache_nbytes
        assert ds15.packed and 2 * packed_bytes <= unpacked_bytes + n_feat
        _log(f"[ingest] cache bytes: unpacked={unpacked_bytes} "
             f"packed(max_bin=15)={packed_bytes}")
        snap = obs.snapshot()
        obs.disable()
        obs.reset()

    backend = jax.default_backend()
    speedup = host_total_s / ingest_steady_s if ingest_steady_s else 0.0
    gate_ok = ingest_steady_s <= 0.5 * host_total_s
    # device-vs-host ratio: hard gate on accelerators only (advisory on
    # cpu, where the comparator isn't measuring what the gate claims)
    gate_enforced = backend != "cpu" and not ns.smoke
    out = {
        "metric": (
            f"streamed ingest steady wall, {n_rows // 1000}kx{n_feat} f32 "
            f"max_bin={MAX_BIN} chunk={chunk_rows} ({n_chunks} chunks, "
            "device-bin + donated cache update; host fit+transform is the "
            "replaced pass)"
        ),
        "value": round(ingest_steady_s, 3),
        "unit": "s",
        "host_fit_s": round(host_fit_s, 3),
        "host_transform_s": round(host_tr_s, 3),
        "host_total_s": round(host_total_s, 3),
        "r05_host_binning_s": R05_HOST_BINNING_S,
        "r10_steady_s": R10_STEADY_S,
        "r10_host_total_s": R10_HOST_TOTAL_S,
        "r17_steady_s": R17_STEADY_S,
        "r17_host_total_s": R17_HOST_TOTAL_S,
        "sketch_s": round(sketch_s, 3),
        "ingest_cold_s": round(ingest_cold_s, 3),
        "ingest_serial_s": round(ingest_serial_s, 3),
        "overlap_ratio": round(float(pipeline.get("overlap_ratio", 0.0)), 3),
        "pipeline_depth": int(pipeline.get("depth", 0)),
        "max_in_flight": int(pipeline.get("max_in_flight", 0)),
        "pipeline": {
            k: (round(v, 4) if isinstance(v, float) else v)
            for k, v in pipeline.items()
        },
        "vs_host_binning": round(speedup, 3),
        "gate_steady_le_half_host": gate_ok,
        "gate_enforced": gate_enforced,
        "hist_steady_s": round(hist_steady_s, 3),
        "byte_hist_working_set_bytes": int(byte_ws_bytes),
        "int32_hist_working_set_bytes": int(int32_ws_bytes),
        "gate_byte_ws_le_half_int32": bool(2 * byte_ws_bytes <= int32_ws_bytes),
        "rank_epsilon": float(sketch.rank_epsilon),
        "backend": backend,
        "devices": len(jax.devices()),
        "unpacked_cache_bytes": int(unpacked_bytes),
        "packed_cache_bytes": int(packed_bytes),
        "smoke": bool(ns.smoke),
        "obs": snap,
    }
    line = json.dumps(out)
    print(line)
    if ns.out != "-":
        dest = ns.out or os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            "INGEST_BENCH.json")
        if not ns.smoke or ns.out:
            with open(dest, "w") as fh:
                fh.write(line + "\n")
            _log(f"[ingest] wrote {dest}")
    if not ns.smoke and not gate_ok:
        if gate_enforced:
            _log("[ingest] GATE FAILED: steady ingest "
                 f"{ingest_steady_s:.2f}s > 0.5 x host {host_total_s:.2f}s")
            return 1
        _log("[ingest] gate advisory on backend=cpu: steady ingest "
             f"{ingest_steady_s:.2f}s > 0.5 x host {host_total_s:.2f}s "
             "(recorded, not enforced)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
