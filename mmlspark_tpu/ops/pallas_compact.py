"""Stream compaction on the MXU: the rows of weight > 0 of a ``(F, n)``
uint8 matrix and its ``(R, n)`` float32 row values (weight last), gathered
in row order into buffers of their own, with no gather, no scatter and no
sort.

A TPU gathers one index at a time, 30-45 ns however few bytes it fetches
(a 39-byte row or a 4-byte value alike), so an XLA gather of 40 M sampled
rows takes seconds.  This kernel streams the rows instead.  Each grid step
reads ``SUB`` consecutive rows; the prefetched scalar ``off[s]`` is the
number of sampled rows before them.  Their ranks within the step come
from a doubling scan of the take mask across the lanes (ten rolls), and a
one-hot ``(WIN, SUB)`` matmul places each sampled row at its rank inside a
window of ``WIN`` output lanes that starts at the 128-lane tile holding rank
``off[s]``.  The window is merged with what the previous step left in the
same lanes (a VMEM carry, shifted by whole tiles) and written to HBM by
one DMA per output; a later step rewrites the lanes it shares with this
one, and the DMAs run one after the other, so the last write of every lane
holds all of its rows.  A step makes one default-precision pass of the
MXU: the bins and the four bytes of each float32 row value (bit-cast to
int32) are stacked into one bfloat16 operand, every one an integer below
256 and so exact, and every output lane sums at most one row, so the
placed bytes are exact in float32 and put back together give each value
to the bit (-0.0, subnormals and every exponent included).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

SUB = 1024  # rows a grid step reads (n / SUB prefetched offsets must fit SMEM)
WIN = SUB + 128  # output lanes a step reaches: its ranks start anywhere in a tile
_NT = (((1,), (1,)), ((), ()))  # contract the lanes of both operands


def _compact_kernel(off_ref, bins_ref, vals_ref, _zb, _zv, ob_ref, ov_ref, acc, winb, winv, sem, *, w: int):
    s = pl.program_id(0)
    off = off_ref[s]
    a = pl.multiple_of((off // 128) * 128, 128)
    prev = jnp.where(s == 0, 0, (off_ref[jnp.maximum(s - 1, 0)] // 128) * 128)
    d = pl.multiple_of(a - prev, 128)  # how far the window moved, < WIN

    @pl.when(s == 0)
    def _init():
        acc[...] = jnp.zeros_like(acc)

    vals = vals_ref[...]  # (Rp, SUB) f32: row w the weight, rows past it the ragged block's
    take = (vals[w : w + 1, :] > 0).astype(jnp.int32)
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, SUB), 1)
    prefix, k = take, 1
    while k < SUB:  # (1, SUB): sampled rows up to and including each
        prefix = prefix + jnp.where(lane >= k, pltpu.roll(prefix, k, 1), 0)
        k *= 2
    dest = jnp.where(take > 0, (off - a) + prefix - 1, -1)  # lane in the window; -1 for none
    onehot = (jax.lax.broadcasted_iota(jnp.int32, (WIN, SUB), 0) == dest).astype(jnp.bfloat16)
    Fp, Rp = bins_ref.shape[0], vals.shape[0]
    bits = jax.lax.bitcast_convert_type(vals, jnp.int32)
    planes = [bins_ref[...].astype(jnp.int32)] + [(bits >> (8 * b)) & 255 for b in range(4)]
    if acc.shape[0] > Fp + 4 * Rp:  # the operand in whole bfloat16 sublane tiles
        planes.append(jnp.zeros((acc.shape[0] - Fp - 4 * Rp, SUB), jnp.int32))
    lhs = jnp.concatenate(planes).astype(jnp.float32).astype(jnp.bfloat16)
    placed = jax.lax.dot_general(lhs, onehot, _NT, preferred_element_type=jnp.float32)  # (M, WIN)
    nb = acc[:, pl.ds(d, WIN)] + placed
    acc[:, :WIN] = nb
    acc[:, WIN:] = jnp.zeros_like(nb)
    q = nb.astype(jnp.int32)
    winb[...] = q[:Fp].astype(jnp.uint8)
    byte = [q[Fp + b * Rp : Fp + (b + 1) * Rp] << (8 * b) for b in range(4)]
    # a row past the weight holds what its ragged block read
    winv[...] = jax.lax.bitcast_convert_type(byte[0] | byte[1] | byte[2] | byte[3], jnp.float32)
    out_b = pltpu.make_async_copy(winb, ob_ref.at[:, pl.ds(a, WIN)], sem.at[0])
    out_v = pltpu.make_async_copy(winv, ov_ref.at[:, pl.ds(a, WIN)], sem.at[1])
    out_b.start()
    out_v.start()
    out_b.wait()
    out_v.wait()


@functools.partial(jax.jit, static_argnames=("rows", "interpret"))
def _pallas_compact(bins_t, vals, rows: int, interpret: bool):
    F, n = bins_t.shape
    R = vals.shape[0]
    # blocks, windows and the outputs' rows whole sublane tiles: the DMA of a
    # window slices the output's rows, which must align to 8; the blocks read
    # past the arrays' last row (ragged), and those rows are sliced off
    Fp, Rp = -(-F // 8) * 8, -(-R // 8) * 8
    pad = -n % SUB
    if pad:  # small fits: rows of weight 0 are never taken
        bins_t = jnp.pad(bins_t, ((0, 0), (0, pad)))
        vals = jnp.pad(vals, ((0, 0), (0, pad)))
    steps = (n + pad) // SUB
    counts = (vals[-1] > 0).reshape(steps, SUB).sum(axis=1, dtype=jnp.int32)
    # sampled rows before each step's block; past ``rows`` (more rows taken
    # than the buffer holds) the windows stay in bounds and land past the end
    off = jnp.minimum(jnp.cumsum(counts) - counts, rows)
    lanes = rows + WIN  # a window starting at the last rank stays inside
    M = -(-(Fp + 4 * Rp) // 16) * 16  # the bins' rows and four byte rows a value row
    any_ = pl.BlockSpec(memory_space=pl.ANY)
    grid = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(steps,),
        in_specs=[
            pl.BlockSpec((Fp, SUB), lambda s, off: (0, s)),
            pl.BlockSpec((Rp, SUB), lambda s, off: (0, s)),
            any_, any_,
        ],
        out_specs=[any_, any_],
        scratch_shapes=[
            pltpu.VMEM((M, 2 * WIN), jnp.float32),
            pltpu.VMEM((Fp, WIN), jnp.uint8), pltpu.VMEM((Rp, WIN), jnp.float32),
            pltpu.SemaphoreType.DMA((2,)),
        ],
    )
    out_b, out_v = pl.pallas_call(
        functools.partial(_compact_kernel, w=R - 1),
        grid_spec=grid,
        out_shape=[jax.ShapeDtypeStruct((Fp, lanes), jnp.uint8), jax.ShapeDtypeStruct((Rp, lanes), jnp.float32)],
        # the outputs start as zeros: lanes past the last sampled row stay 0
        input_output_aliases={3: 0, 4: 1},
        interpret=interpret,
    )(off, bins_t, vals, jnp.zeros((Fp, lanes), jnp.uint8), jnp.zeros((Rp, lanes), jnp.float32))
    return out_b[:F, :rows], out_v[:R, :rows]


def compact_rows(bins_t, vals, rows: int):
    """``(bins (F, rows) uint8, vals (R, rows) float32)``: the columns of
    ``bins_t`` (F, n) and ``vals`` (R, n) whose last row of ``vals`` is
    positive, in order, then zeros.  At most ``rows`` of them."""
    return _pallas_compact(bins_t, vals, rows, interpret=jax.default_backend() != "tpu")
