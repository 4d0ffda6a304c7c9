"""Criteo-schema rows made on the device from a seed.

The distribution is ``tools/gen_criteo_shards.py``'s (13 ``floor(lognormal)``
count columns with 4-45 % NaN, 26 zipf-ish categorical columns of
cardinality 16..2**18 whose ids are hashed into [0, 2**24) so every value
is f32-exact, 0-30 % NaN, a logistic label over log counts and id parity),
rewritten for ``jax.random`` so 50 M rows take a second on the chip where
the host generator takes minutes.  Departures: a 32-bit mixer stands in for
splitmix64 (no 64-bit integers on the device), and the label weights come
from ``jax.random`` instead of numpy's generator.
"""

import jax
import jax.numpy as jnp
import numpy as np

NUM_INT = 13
NUM_CAT = 26
NUM_FEATURES = NUM_INT + NUM_CAT
CATEGORICAL = tuple(range(NUM_INT, NUM_FEATURES))

_INT_SIGMA = np.linspace(0.8, 2.4, NUM_INT).astype(np.float32)
_INT_MISS = np.linspace(0.04, 0.45, NUM_INT).astype(np.float32)
_CAT_CARD = np.resize(
    np.unique(np.geomspace(16, 2 ** 18, NUM_CAT).astype(np.int64)), NUM_CAT
).astype(np.float32)
_CAT_MISS = np.linspace(0.0, 0.30, NUM_CAT).astype(np.float32)
_CAT_SALT = ((np.arange(NUM_CAT, dtype=np.uint32) + 1) * 0x9E3779B1).astype(np.uint32)


def _mix32(x):
    # murmur3's 32-bit finalizer
    x = x ^ (x >> 16)
    x = x * jnp.uint32(0x85EBCA6B)
    x = x ^ (x >> 13)
    x = x * jnp.uint32(0xC2B2AE35)
    return x ^ (x >> 16)


def chunk(key, index, rows: int):
    """Chunk ``index`` of the seed's stream: ``(X (rows, 39) f32, y (rows,) f32)``."""
    wkey = jax.random.fold_in(key, 10007)
    w_int = 0.6 * jax.random.normal(jax.random.fold_in(wkey, 0), (NUM_INT,))
    w_cat = 0.9 * jax.random.normal(jax.random.fold_in(wkey, 1), (NUM_CAT,))
    kz, km, ku, kc, ky = jax.random.split(jax.random.fold_in(key, index), 5)

    z = jax.random.normal(kz, (rows, NUM_INT))
    ints = jnp.floor(jnp.exp(z * _INT_SIGMA))
    ints = jnp.where(jax.random.uniform(km, (rows, NUM_INT)) < _INT_MISS, jnp.nan, ints)

    u = jax.random.uniform(ku, (rows, NUM_CAT))
    bucket = jnp.floor(u ** 3 * _CAT_CARD).astype(jnp.uint32)
    cats = (_mix32(bucket + _CAT_SALT) & jnp.uint32(0xFFFFFF)).astype(jnp.float32)
    cats = jnp.where(jax.random.uniform(kc, (rows, NUM_CAT)) < _CAT_MISS, jnp.nan, cats)

    xi = jnp.nan_to_num(jnp.log1p(ints), nan=0.0)
    parity = jnp.mod(jnp.nan_to_num(cats, nan=0.0), 2.0)
    logits = -1.0 + xi @ (w_int * 0.25) + parity @ (w_cat * 0.15)
    y = (jax.random.uniform(ky, (rows,)) < jax.nn.sigmoid(logits)).astype(jnp.float32)
    return jnp.concatenate([ints, cats], axis=1), y
