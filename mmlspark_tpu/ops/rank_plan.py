"""Query plan for ranking: a data set's queries bucketed by length.

A ranking objective and a by-query metric both need each query's rows side
by side.  Public ranking sets are skewed (Istella LETOR: 1 to over 1,000
documents a query, mean 316), so one padded ``(queries, longest)`` matrix is
mostly padding and anything quadratic in its width does not fit a chip.  The
plan puts every query into the bucket of the next power of two at or above
its length (8 at least), so a bucket's ``(G_b, M_b)`` matrix is under half
padding, and it is built once for a data set, in vectorised numpy, from the
query sizes alone.

On the device a plan is a pytree of small arrays (``device_arrays``): per
bucket the first row and the length of each query plus ``arange(M_b)``, which
carries the bucket's width in its shape, and ``inv``, for every row its slot in
the buckets' concatenated ``(G_b * M_b)`` layouts (rows of no query point at
one spare slot that stays zero).  A query's rows are contiguous, so a bucket's
matrix is ``G_b`` slices of the row vector and needs no stored index matrix.
"""

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

MIN_WIDTH = 8


@dataclasses.dataclass(frozen=True)
class RankPlan:
    """Host form of a plan.  ``buckets[b]`` = ``(start, size, pos)``: int32
    ``(G_b,)`` first row and length of the bucket's queries, in query order,
    and ``arange(M_b)``; ``inv``: int32 ``(rows,)``."""

    buckets: Tuple[Tuple[np.ndarray, np.ndarray, np.ndarray], ...]
    inv: np.ndarray

    @property
    def queries(self) -> int:
        return sum(len(size) for _, size, _ in self.buckets)

    @property
    def shape_key(self) -> Tuple:
        """What a program traced over this plan's arrays depends on:
        ``(((G_b, M_b), ...), rows)``."""
        return (tuple((len(size), len(pos)) for _, size, pos in self.buckets), len(self.inv))

    def pair_slots(self, k: int) -> int:
        """Pair terms the gradient forms: sum of ``G_b * min(k, M_b) * M_b``."""
        return sum(g * min(k, m) * m for g, m in self.shape_key[0])

    def pair_terms(self, k: int) -> int:
        """Pair terms that are real: sum over queries of ``min(k, M_q) * M_q``."""
        return int(sum(np.sum(np.minimum(k, size.astype(np.int64)) * size) for _, size, _ in self.buckets))

    def host_arrays(self):
        return self.buckets, self.inv

    def device_arrays(self, put=jnp.asarray):
        return jax.tree_util.tree_map(put, self.host_arrays())


def build_rank_plan(sizes, starts=None, num_rows: Optional[int] = None) -> RankPlan:
    """The plan of queries of ``sizes`` rows each.  ``starts``: each query's
    first row (default: the queries tile the rows in order); ``num_rows``: the
    length of the row vector (default: the end of the last query)."""
    sizes = np.asarray(sizes, np.int64).reshape(-1)
    if starts is None:
        starts = np.cumsum(sizes) - sizes
    starts = np.asarray(starts, np.int64).reshape(-1)
    end = int((starts + sizes).max()) if len(sizes) else 0
    num_rows = end if num_rows is None else int(num_rows)
    if num_rows < end or num_rows >= 2**31 - 1:
        raise ValueError(f"queries end at row {end}, outside a row vector of {num_rows}")
    width = np.maximum(MIN_WIDTH, 2 ** np.ceil(np.log2(np.maximum(sizes, 1))).astype(np.int64))
    slot0 = np.zeros(len(sizes), np.int64)  # each query's first slot in the flat layout
    base = 0
    buckets = []
    for m in np.unique(width):  # a dozen buckets at most, never a loop over queries
        sel = np.flatnonzero(width == m)
        slot0[sel] = base + np.arange(len(sel)) * m
        base += len(sel) * m
        buckets.append((starts[sel].astype(np.int32), sizes[sel].astype(np.int32), np.arange(m, dtype=np.int32)))
    if base >= 2**31 - 1:
        raise ValueError(f"{base} bucket slots do not fit an int32 index")
    within = np.arange(int(sizes.sum())) - np.repeat(np.cumsum(sizes) - sizes, sizes)
    inv = np.full(num_rows, base, np.int32)  # slot ``base`` is the spare zero
    inv[np.repeat(starts, sizes) + within] = np.repeat(slot0, sizes) + within
    return RankPlan(tuple(buckets), inv)


def plan_from_matrix(idx, valid, num_rows: Optional[int] = None) -> RankPlan:
    """The plan of a padded ``(G, M)`` row-index and validity pair whose rows
    are contiguous queries (``engine/dist_metrics.assemble_global_groups``)."""
    idx, valid = np.asarray(idx), np.asarray(valid, bool)
    if idx.size == 0:
        return build_rank_plan(np.zeros(0, np.int64), num_rows=num_rows)
    sizes = valid.sum(axis=1)
    starts = idx[:, 0].astype(np.int64)
    pos = np.arange(idx.shape[1])
    if not (np.array_equal(valid, pos < sizes[:, None]) and np.array_equal(idx[valid], (starts[:, None] + pos)[valid])):
        raise ValueError("a group matrix must hold each query's rows contiguously, left-aligned")
    return build_rank_plan(sizes, starts, num_rows)


def by_bucket(arrays, rows: Tuple):
    """Yield ``(row vectors as (G_b, M_b) matrices, valid (G_b, M_b), pos)``
    per bucket of the plan's device ``arrays``: row ``g`` of a matrix is the
    ``M_b`` entries of the row vector from query ``g``'s first row on."""
    buckets, _ = arrays
    widest = max(pos.shape[0] for _, _, pos in buckets)
    rows = [jnp.concatenate([x, jnp.zeros((widest,), x.dtype)]) for x in rows]  # a slice may pass the end
    for start, size, pos in buckets:
        valid = pos[None, :] < size[:, None]
        cut = jax.vmap(lambda s, x: lax.dynamic_slice(x, (s,), pos.shape), in_axes=(0, None))
        yield tuple(cut(start, x) for x in rows), valid, pos


def score_key(s, valid):
    """What ``lax.top_k`` ranks a bucket's rows by: the score, padding last.
    top_k puts the lower index first among equals, which is the stable
    descending order, but it tells -0.0 from 0.0: both become 0.0 here."""
    return jnp.where(valid, jnp.where(s == 0, 0.0, s), -jnp.inf)


def to_rows(arrays, per_bucket, num_rows: int):
    """Per-bucket ``(C, G_b, M_b)`` values back onto the rows: ``(C, num_rows)``.
    Rows of no query (padding) read zero."""
    _, inv = arrays
    c = per_bucket[0].shape[0]
    flat = jnp.concatenate([v.reshape(c, -1) for v in per_bucket] + [jnp.zeros((c, 1), per_bucket[0].dtype)], axis=1)
    spare = flat.shape[1] - 1
    if num_rows > inv.shape[0]:
        inv = jnp.concatenate([inv, jnp.full((num_rows - inv.shape[0],), spare, inv.dtype)])
    return flat[:, inv[:num_rows]]


def ndcg_sum(arrays, score, label, k: int):
    """Sum over the plan's queries of NDCG@k (gain ``2**label - 1``, ties in
    row order, a query with no relevant row counts 1)."""
    with jax.named_scope("rank_ndcg"):
        total = jnp.float32(0.0)
        for (s, lbl), valid, pos in by_bucket(arrays, (score, label)):
            kb = min(k, pos.shape[0])
            gain = jnp.where(valid, 2.0 ** lbl - 1.0, 0.0)
            _, best = lax.top_k(score_key(s, valid), kb)
            disc = 1.0 / jnp.log2(jnp.arange(kb) + 2.0)
            dcg = jnp.sum(jnp.take_along_axis(gain, best, axis=1) * disc, axis=1)
            idcg = jnp.sum(lax.top_k(gain, kb)[0] * disc, axis=1)
            total += jnp.sum(jnp.where(idcg > 0, dcg / jnp.maximum(idcg, 1e-30), 1.0))
        return total
