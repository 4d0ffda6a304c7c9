"""Rows and queries at Istella LETOR's shape, made from a seed.

The source (istella.ai/data/letor-dataset; Dato et al., ACM TOIS 2016) has
10,454,629 query-document rows in 33,018 queries, 220 numeric features and
relevance grades 0-4; there is no network here, so nothing of it is read and
everything below is ``assumed`` in the configuration's file:

- query sizes: a seeded log-normal of mean 315.5 and log-sigma 0.5, clipped to
  1...2,048, then nudged so that they sum to the split's rows exactly;
- columns: non-negative and without missing values, ``exp(sigma z + mu)`` of a
  normal ``z``, every fourth column floored to whole counts, three columns in
  four zero-inflated (5-60 % zeros);
- grades: thresholds of ``r = w . z[label columns] + 0.6 noise`` with a seeded
  unit ``w`` over eight continuous columns that are not zero-inflated, so ``r`` is normal
  with variance 1.36 and the shares are those of ``GRADE_SHARES`` in
  expectation (96 % grade 0).  Monotone in eight features plus noise: trees
  keep finding splits down to 255 leaves.

``chunk`` is pure ``jax.numpy``, a function of the key and the chunk index
alone; the test split's chunks follow ``HOLDOUT_FIRST_CHUNK``.
"""

import math
from statistics import NormalDist

import jax
import jax.numpy as jnp
import numpy as np

NUM_FEATURES = 220
CATEGORICAL = ()
HOLDOUT_FIRST_CHUNK = 1 << 20  # the test split is a stream of its own

SIZE_MEAN, SIZE_LOG_SIGMA, SIZE_MAX = 315.5, 0.5, 2048
GRADE_SHARES = (0.96, 0.02, 0.012, 0.006, 0.002)  # grades 0...4
LABEL_COLUMNS = tuple(range(3, NUM_FEATURES, 28))  # eight columns
NOISE = 0.6

_F = np.arange(NUM_FEATURES)
_SIGMA = np.linspace(0.4, 1.6, NUM_FEATURES).astype(np.float32)
_MU = (np.linspace(0.0, 3.0, NUM_FEATURES)[(_F * 37) % NUM_FEATURES]).astype(np.float32)
_COUNT = _F % 4 == 1
_ZERO_SHARE = np.where(_F % 4 == 3, 0.0, np.linspace(0.05, 0.6, NUM_FEATURES)[(_F * 53) % NUM_FEATURES]).astype(np.float32)
assert all(_ZERO_SHARE[f] == 0.0 for f in LABEL_COLUMNS)
_THRESHOLDS = np.asarray(
    [NormalDist(0.0, math.sqrt(1.0 + NOISE * NOISE)).inv_cdf(p) for p in np.cumsum(GRADE_SHARES)[:-1]], np.float32
)


def query_sizes(seed: int, queries: int, rows: int, split: int = 0) -> np.ndarray:
    """``queries`` sizes in 1...``SIZE_MAX`` that sum to ``rows`` exactly:
    int64, a function of the seed and the split (0 train, 1 test) alone."""
    rng = np.random.default_rng([int(seed), int(split), 0x15E11A])
    mu = math.log(SIZE_MEAN) - SIZE_LOG_SIGMA**2 / 2
    sizes = np.clip(np.rint(rng.lognormal(mu, SIZE_LOG_SIGMA, queries)), 1, SIZE_MAX).astype(np.int64)
    if not queries <= rows <= queries * SIZE_MAX:
        raise ValueError(f"{queries} queries of 1...{SIZE_MAX} rows cannot hold {rows} rows")
    while (gap := rows - int(sizes.sum())) != 0:
        # spread the gap over the queries that have room, one row each
        room = np.flatnonzero(sizes < SIZE_MAX if gap > 0 else sizes > 1)
        pick = rng.choice(room, min(abs(gap), len(room)), replace=False)
        sizes[pick] += 1 if gap > 0 else -1
    return sizes


def chunk(key, index, rows: int):
    """Chunk ``index`` of the seed's stream: ``(X (rows, 220) f32, y (rows,) f32)``."""
    w = jax.random.normal(jax.random.fold_in(key, 10007), (len(LABEL_COLUMNS),))
    w = jnp.abs(w) / jnp.linalg.norm(w)
    kz, ku, ky = jax.random.split(jax.random.fold_in(key, index), 3)
    z = jax.random.normal(kz, (rows, NUM_FEATURES))
    x = jnp.exp(z * _SIGMA + _MU)
    x = jnp.where(_COUNT, jnp.floor(x), x)
    x = jnp.where(jax.random.uniform(ku, (rows, NUM_FEATURES)) < _ZERO_SHARE, 0.0, x)
    r = z[:, jnp.asarray(LABEL_COLUMNS)] @ w + NOISE * jax.random.normal(ky, (rows,))
    y = jnp.sum(r[:, None] > _THRESHOLDS[None, :], axis=1).astype(jnp.float32)
    return x, y
