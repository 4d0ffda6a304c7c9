"""Measure row-compaction economics for the by-leaf histogram pass.

The windowed grower's per-pass cost is invariant in n (parked rows still
burn matmul FLOPs).  Row compaction gathers only the rows whose leaf is in
the window into a compact buffer (static bucket sizes n, n/2, n/4, n/8)
and runs the factorized kernel on the bucket.  This sweep measures, at the
bench shape, (a) the full-n kernel, (b) compaction overhead (mask → cumsum
→ inverse permutation scatter → gather) + kernel at each bucket, so the
integration decision is data-driven.

Run on the real TPU: python tools/sweep_compact.py
"""

import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp

from mmlspark_tpu.ops.pallas_hist import pallas_hist_by_leaf_nibble_chunk

N, F, B, W = 262_144, 64, 256, 12
REPS = 20


def _time(fn, *args):
    out = fn(*args)
    np.asarray(out[:1, :1, :1, :1])  # compile + settle
    t0 = time.perf_counter()
    for _ in range(REPS):
        out = fn(*args)
    np.asarray(out[:1, :1, :1, :1])
    return (time.perf_counter() - t0) / REPS


def compact_then_hist(bins_t, vals, leaf, n_buf: int):
    """Compaction + kernel at a STATIC bucket size n_buf."""
    n = leaf.shape[0]
    mask = (leaf >= 0) & (leaf < W)
    pos = jnp.cumsum(mask)  # 1-based position among active rows
    dest = jnp.where(mask, pos - 1, n_buf)  # inactive → dump slot
    dest = jnp.minimum(dest, n_buf)  # overflow rows also dumped
    inv = jnp.full((n_buf + 1,), n, dtype=jnp.int32)
    inv = inv.at[dest].set(jnp.arange(n, dtype=jnp.int32), mode="drop")
    take = inv[:n_buf]  # compact slot -> source row (n = "no row")
    # Out-of-range gather indices clamp to the last row; park those rows by
    # leaf=W below instead of padding the arrays.
    bins_c = jnp.take(bins_t, take, axis=1, fill_value=0, mode="fill")
    vals_c = jnp.take(vals, take, axis=1, fill_value=0.0, mode="fill")
    leaf_c = jnp.where(take < n, jnp.take(leaf, jnp.minimum(take, n - 1)), W)
    return pallas_hist_by_leaf_nibble_chunk(
        bins_c, vals_c, leaf_c, W, B, precision="default"
    )


def main():
    rng = np.random.default_rng(0)
    bins_t = jnp.asarray(rng.integers(0, B - 1, size=(F, N)), dtype=jnp.int32)
    vals = jnp.asarray(rng.normal(size=(3, N)), dtype=jnp.float32)
    print(f"backend={jax.default_backend()} n={N} F={F} B={B} W={W}", flush=True)

    full = jax.jit(
        lambda b, v, l: pallas_hist_by_leaf_nibble_chunk(
            b, v, l, W, B, precision="default"
        )
    )

    for frac in (1.0, 0.5, 0.25, 0.125):
        leaf_np = np.where(
            rng.random(N) < frac, rng.integers(0, W, size=N), -1
        ).astype(np.int32)
        leaf = jnp.asarray(leaf_np)
        t_full = _time(full, bins_t, vals, leaf)
        print(f"active={frac:5.3f}  full-n kernel: {t_full*1e3:7.2f} ms", flush=True)
        for n_buf in (N, N // 2, N // 4, N // 8):
            n_act = int((leaf_np >= 0).sum())
            if n_act > n_buf:
                continue  # bucket too small for this fraction
            fn = jax.jit(
                lambda b, v, l, nb=n_buf: compact_then_hist(b, v, l, nb)
            )
            t_c = _time(fn, bins_t, vals, leaf)
            # correctness spot-check vs full kernel
            ref = np.asarray(full(bins_t, vals, leaf))
            got = np.asarray(fn(bins_t, vals, leaf))
            err = float(np.abs(ref - got).max())
            print(
                f"          compact->bucket {n_buf:>7}: {t_c*1e3:7.2f} ms"
                f"  (max|Δ|={err:.2e})",
                flush=True,
            )


if __name__ == "__main__":
    main()
