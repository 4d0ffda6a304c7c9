"""Micro-sweep the by-leaf Pallas kernel block sizes at the bench shape.

Times pallas_hist_by_leaf_chunk directly at (262144 rows, 64 features,
B=256, W=12) for candidate (bm, bf, rm) blockings.  Chained async calls +
one tiny fetch per timing.
"""

import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp

from mmlspark_tpu.ops.pallas_hist import pallas_hist_by_leaf_chunk

N, F, B, W = 262_144, 64, 256, 12
REPS = 20


def main():
    rng = np.random.default_rng(0)
    bins_t = jnp.asarray(rng.integers(0, B - 1, size=(F, N)), dtype=jnp.int32)
    vals = jnp.asarray(rng.normal(size=(3, N)), dtype=jnp.float32)
    leaf = jnp.asarray(rng.integers(-1, W, size=(N,)), dtype=jnp.int32)
    print(f"backend={jax.default_backend()} shape n={N} F={F} B={B} W={W}", flush=True)

    configs = [
        ("default bm=16384 bf=32 rm=1024", dict(bm=16384, bf=32, rm=1024)),
        ("bf=64 rm=1024 bm=16384", dict(bm=16384, bf=64, rm=1024)),
        ("bf=32 rm=2048 bm=16384", dict(bm=16384, bf=32, rm=2048)),
        ("bf=64 rm=2048 bm=16384", dict(bm=16384, bf=64, rm=2048)),
        ("bf=32 rm=1024 bm=8192", dict(bm=8192, bf=32, rm=1024)),
        ("bf=64 rm=512  bm=16384", dict(bm=16384, bf=64, rm=512)),
    ]
    for name, kw in configs:
        try:
            fn = jax.jit(lambda b, v, l, kw=kw: pallas_hist_by_leaf_chunk(
                b, v, l, W, B, precision="default", **kw))
            out = fn(bins_t, vals, leaf)
            np.asarray(out[:1, :1, :1, :1])  # compile+run once
            t0 = time.perf_counter()
            for _ in range(REPS):
                out = fn(bins_t, vals, leaf)
            np.asarray(out[:1, :1, :1, :1])
            dt = (time.perf_counter() - t0) / REPS * 1e3
            print(f"{name}: {dt:.2f} ms/pass", flush=True)
        except Exception as e:
            print(f"{name}: FAILED {type(e).__name__}: {str(e)[:120]}", flush=True)


def nibble():
    from mmlspark_tpu.ops.pallas_hist import pallas_hist_by_leaf_nibble_chunk

    rng = np.random.default_rng(0)
    bins_t = jnp.asarray(rng.integers(0, B - 1, size=(F, N)), dtype=jnp.int32)
    vals = jnp.asarray(rng.normal(size=(3, N)), dtype=jnp.float32)
    leaf = jnp.asarray(rng.integers(-1, W, size=(N,)), dtype=jnp.int32)
    for name, kw in [
        ("nibble bf=32 rm=1024", dict(bm=16384, bf=32, rm=1024)),
        ("nibble bf=64 rm=1024", dict(bm=16384, bf=64, rm=1024)),
        ("nibble bf=32 rm=2048", dict(bm=16384, bf=32, rm=2048)),
    ]:
        try:
            fn = jax.jit(lambda b, v, l, kw=kw: pallas_hist_by_leaf_nibble_chunk(
                b, v, l, W, B, precision="default", **kw))
            out = fn(bins_t, vals, leaf)
            np.asarray(out[:1, :1, :1, :1])
            t0 = time.perf_counter()
            for _ in range(REPS):
                out = fn(bins_t, vals, leaf)
            np.asarray(out[:1, :1, :1, :1])
            dt = (time.perf_counter() - t0) / REPS * 1e3
            print(f"{name}: {dt:.2f} ms/pass", flush=True)
        except Exception as e:
            print(f"{name}: FAILED {type(e).__name__}: {str(e)[:150]}", flush=True)


if __name__ == "__main__":
    main()
    nibble()
