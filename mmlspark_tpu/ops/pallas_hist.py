"""Pallas TPU kernels for gradient-histogram construction.

The TPU-native analog of LightGBM's CUDA histogram kernels (reference native
component N1, SURVEY.md §2.9: upstream ``src/treelearner/cuda/`` /
``kernels/`` — [REF-EMPTY]; shipped prebuilt in the ``lightgbmlib`` jar).
CUDA's approach — per-thread-block shared-memory scatter-adds — does not map
to the TPU's vector/matrix units, so the kernel reformulates histogramming
as a contraction (SURVEY.md §7.4.2):

    hist[c, f, b] = Σ_rows vals[c, row] * onehot_f[b, row]

i.e. per feature a (channels, rows) × (rows, B) matmul that lands on the
MXU, with the one-hot tile materialized **only in VMEM** (never HBM).  The
grid iterates row-blocks innermost so each feature block's output tile stays
resident in VMEM and accumulates across row blocks — the standard Pallas
reduction pattern.

Layout choices (TPU tiling wants the last dim lane-sized):
- bins arrive transposed as (F, rows) so a block is (bf, bm) with rows on
  the 128-lane axis; the dtype is uint8 through the byte tier
  (``num_bins ≤ 256``, ``ops/binpack.py``) and every kernel widens to
  int32 immediately after the block load — 1-byte indices in HBM and on
  the DMA, int32 only in VMEM;
- vals arrive channel-major (3, rows) — rows on lanes — float32, or the
  int16 buckets of quantized training: all three bodies serve both (see
  "Value dtype" below);
- bin one-hots are built PER FEATURE as clean 2-D (B, rows) iota-compares:
  a fused (bf, B, rows)→(bf·B, rows) one-hot needs a Mosaic lane relayout
  that traced at ~10x the matmul cost;
- outputs keep channels/leaves on sublanes and (feature-block · B) on
  lanes; the unflatten to engine layout happens outside the kernel.

VMEM budget per grid cell (by-leaf defaults bm=8192, bf=8, rm=1024):
one-hot (256, 1024) f32 = 1 MiB + rhs/out tiles ≪ 16 MiB/core.

Reading in place (ISSUE 37): a call is handed the growers' arrays WHOLE —
the (F, n) matrix, the (3, n) row values, the leaf ids — and the index of
the row chunk it is to sum as a prefetched scalar, which its grid's
``index_map``s add to the row-block index (:func:`_chunk_grid`).  No chunk
is sliced out and no column padded: a block is as tall as the matrix where
the columns fit one block, else the last of ``cdiv(F, bf)`` blocks is
ragged, and what it reads past column ``F`` lands in output columns the
wrapper drops (a bin of any value indexes no row of a one-hot it should
not).  Only rows that are no whole number of ``bm`` blocks are still
sliced and padded (:func:`_place_chunk`): padded row values must be zero,
which a ragged row block's are not.

Distributed merge layout (ISSUE 4): the engine keeps features CONTIGUOUS
on the feature axis of the kernel's output, so the reduce-scatter merge
(``ops/histogram.py::merge_shard_histograms``) can ``psum_scatter`` that
axis tiled — block i of the feature axis lands merged on mesh shard i
with no re-layout between the kernel and the collective.  Feature padding
for ``F % D != 0`` (the mesh's D, not a block's height) happens host-side
before binning, so the merge never sees a feature axis it cannot tile.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from mmlspark_tpu.ops.histogram import _is_bucket, _row_chunk

_PRECISIONS = {
    "highest": jax.lax.Precision.HIGHEST,
    "default": jax.lax.Precision.DEFAULT,
}


def _round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


def _pow2_floor(x: int) -> int:
    return 1 << (max(x, 1).bit_length() - 1)


# ---------------------------------------------------------------------------
# Value dtype (ISSUE 9 — quantized training).  One body per kernel serves
# both, in all three kernels (plain, by-leaf, factorized by-leaf): the
# dtype of ``vals`` is static at trace time and picks the accumulator, so
# the float build's traced body holds no integer op.
#
# Integer ``vals`` are the int16 buckets of ops.histogram.quantize_hist_vals.
# Layout note, int accumulator tile: the row values arrive as an int16
# (3, bm) tile (sublane-padded to 16; HALF the per-row-block DMA of the
# f32 build) and the grid-resident output tile is **int32** with the
# same (3·L on sublanes, bf·B on lanes) orientation as the float build.
# The per-row-block contraction itself stays an f32 MXU matmul — there is
# no native int32 MXU path to lower to, and none is needed for exactness:
# both operands are small integers (one-hot ∈ {0,1}, |vals| at most a
# channel's largest bucket, never over QMAX = 127 — 2 for the gradients of
# LightGBM's default num_grad_quant_bins=4 — exact even as bf16 under
# precision="default"), so every partial sum is
# an integer ≤ bm·QMAX ≈ 2.1M ≪ 2²⁴, exactly representable in the f32
# accumulator; the cast to int32 after each row block is therefore exact,
# and int32 grid accumulation across row blocks is associative — the
# whole build is bit-reproducible regardless of precision mode, chunking,
# merge order, or body: the factorized kernel's operands are the same
# one-hots and buckets with the bin one-hot split in two (hi into M, lo
# on N), its sub-block sums the same whole numbers ≤ rm·QMAX, and its
# un-factoring a reshape, so a small window's bucket build takes it like
# the float build (ops/histogram.py routes by shape alone).  headroom:
# rows × largest bucket < 2³¹ per shard is attested statically by
# ops.histogram.quantize_wire_plan before any kernel runs.
# ---------------------------------------------------------------------------
def _tile_dtype(vals_dtype):
    """The dtype row values cross the DMA in: int16 buckets, else f32."""
    return jnp.int16 if _is_bucket(vals_dtype) else jnp.float32


def _chunk_grid(F: int, bf: int, bm: int, chunk: int, by_leaf: bool, out_block: tuple):
    """The grid of one call over whole arrays: ``cdiv(F, bf)`` column blocks
    by the ``chunk // bm`` row blocks of chunk ``c[0]``, the one prefetched
    scalar: row block ``i`` of the chunk is block ``c[0] · (chunk // bm) + i``
    of the array, read where it lies.  ``out_block`` is a column block's
    ``(1, M, lanes)`` tile of the output, resident across the row blocks."""
    k = chunk // bm

    def rows(j, i, c):
        return 0, c[0] * k + i

    in_specs = [pl.BlockSpec((bf, bm), lambda j, i, c: (j, c[0] * k + i)), pl.BlockSpec((3, bm), rows)]
    if by_leaf:
        in_specs.append(pl.BlockSpec((1, bm), rows))
    return pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1, grid=(pl.cdiv(F, bf), k), in_specs=in_specs,
        out_specs=pl.BlockSpec(out_block, lambda j, i, c: (j, 0, 0)),
    )


def _place_chunk(bins_t, rows, i, chunk: int, bm: int):
    """What a wrapper is called with to sum rows ``[i·chunk, (i+1)·chunk)``:
    ``(bins_t, rows, c, chunk)``.  ``rows`` are the per-row ``(array, pad
    value)`` pairs, rows last.  A chunk of whole ``bm`` blocks stays where
    it lies and ``c`` carries its index.  Any other (small fits) is cut out
    and padded to whole blocks, ``c`` 0: padded rows must carry zero values
    and a parked leaf id, which the rows a ragged block would read in their
    place do not."""
    n = bins_t.shape[1]
    pad = (-chunk) % bm
    if not pad:
        return bins_t, [x for x, _ in rows], jnp.asarray(i, jnp.int32).reshape(1), chunk

    def cut(x, fill):
        x = x if chunk == n else _row_chunk(x, i, chunk, 1)
        return jnp.pad(x, ((0, 0), (0, pad)), constant_values=fill)

    with jax.named_scope("chunk_copy"):
        return cut(bins_t, 0), [cut(x, fill) for x, fill in rows], jnp.asarray([0], jnp.int32), chunk + pad


def _hist_kernel(chunk_ref, bins_ref, vals_ref, out_ref, *, num_bins: int, precision):
    """One (feature-block j, row-block i) cell: out[j] += vals·onehotᵀ.
    ``chunk_ref`` is the prefetched chunk index, the ``index_map``s' alone."""
    i = pl.program_id(1)  # row block (innermost → accumulation is safe)
    quant = _is_bucket(vals_ref.dtype)
    # bins arrive uint8 at ≤256 bins (byte tier, ops/binpack.py) — the
    # HBM→VMEM DMA moves 1 byte/index; widen to int32 IN VMEM only.
    bins = bins_ref[...].astype(jnp.int32)  # (bf, bm)
    vals = vals_ref[...]  # (3, bm) f32 | int16 buckets
    if quant:
        vals = vals.astype(jnp.float32)
    bf, bm = bins.shape
    # Per-feature 2-D one-hot over bins, rows on lanes — VMEM only.
    # Precision: HIGHEST = f32 passes (scatter-add-exact numerics — the
    # MXU's bf16-multiply default loses ~1e-3 per element, which can flip
    # near-tied split gains); DEFAULT = bf16 multiplies with f32
    # accumulation, ~4x throughput (the one-hot operand is exact either
    # way).  Chosen by GrowConfig.hist_precision.
    iota_b = jax.lax.broadcasted_iota(jnp.int32, (num_bins, bm), 0)
    parts = []
    for f in range(bf):
        oh_f = (iota_b == bins[f, :][None, :]).astype(jnp.float32)
        parts.append(
            jax.lax.dot_general(
                vals, oh_f,
                dimension_numbers=(((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
                precision=precision,
            )  # (3, B) — of buckets: integer-valued, exact in f32
        )
    part = jnp.concatenate(parts, axis=1)  # (3, bf·B)
    if quant:
        part = part.astype(jnp.int32)  # exact (see "Value dtype")

    @pl.when(i == 0)
    def _init():
        out_ref[...] = part[None, :, :]

    @pl.when(i > 0)
    def _acc():
        out_ref[...] += part[None, :, :]


def _hist_kernel_work(M: int, N: int, bf: int, bm: int, rm: int, quant: bool):
    """``(MXU flops, VPU element operations)`` one grid cell of
    :func:`_hist_kernel` issues, as its body stands: ``M`` = 3 channels, ``N``
    = bins, and no inner loop (``rm`` = ``bm``).  Every element of every
    elementwise array the body builds counts once (widenings, compares,
    converts, products, accumulator adds); iotas, slices and concatenations
    do not."""
    rows = bf * bm  # the widened bins
    per_col = 2 * N * bm  # the bin one-hot: a compare and its convert
    tile = M * bf * N  # the accumulator tile: its add, and of buckets its cast
    vpu = rows + bf * per_col + tile * (2 if quant else 1) + (3 * bm if quant else 0)
    return 2 * M * N * bm * bf, vpu


@functools.partial(
    jax.jit, static_argnames=("num_bins", "bm", "bf", "chunk", "interpret", "precision")
)
def _pallas_hist(
    bins_t, vals, c, num_bins: int, bm: int, bf: int, chunk: int, interpret: bool, precision: str
):
    """(3, F, B) of chunk ``c[0]``'s ``chunk`` rows of the whole ``(F, n)``
    ``bins_t`` and ``(3, n)`` ``vals`` (:func:`_chunk_grid`)."""
    F = bins_t.shape[0]
    blocks = pl.cdiv(F, bf)
    kernel = functools.partial(
        _hist_kernel, num_bins=num_bins, precision=_PRECISIONS[precision]
    )
    out = pl.pallas_call(
        kernel,
        # Output layout (F/bf, 3, bf·B): feature-block leading so the block
        # shape's last two dims (3, bf·B) satisfy TPU tiling by equalling
        # the array dims; the bin unflatten happens outside the kernel.
        grid_spec=_chunk_grid(F, bf, bm, chunk, False, (1, 3, bf * num_bins)),
        # headroom: the int32 grid accumulator of a bucket build — rows ×
        # largest bucket per shard is attested statically by
        # ops.histogram.quantize_wire_plan before kernels run
        out_shape=jax.ShapeDtypeStruct(
            (blocks, 3, bf * num_bins),
            jnp.int32 if _is_bucket(vals.dtype) else jnp.float32,
        ),
        interpret=interpret,
    )(c, bins_t, vals)
    # a ragged last block's columns past F are dropped here
    return out.transpose(1, 0, 2).reshape(3, blocks * bf, num_bins)[:, :F]


def pallas_hist_chunk(
    bins_t, vals, num_bins: int, bm: int = 4096, bf: int = 32,
    precision: str = "highest", i=0, chunk: Optional[int] = None,
) -> jnp.ndarray:
    """(F, n) int bins + (3, n) vals → (3, F, B) of rows ``[i·chunk,
    (i+1)·chunk)`` (all of them with no ``chunk``), same contract as the
    scatter chunk builder in :mod:`mmlspark_tpu.ops.histogram`: float32
    sums of float ``vals``, int32 sums of int16 bucket ``vals``.

    ``bins_t`` is the growers' (F, n) matrix — uint8 through the byte
    tier (``num_bins ≤ 256``), int32 past it.  The kernel widens per VMEM
    block, so uint8 input quarters the per-pass bins DMA.

    The chunk is read in place (:func:`_chunk_grid`).  Up to ``bf``
    columns are one block as tall as the matrix, more are ``bf``-tall
    blocks, the last ragged; only a chunk that is no whole number of row
    blocks is cut out and padded (:func:`_place_chunk`).
    """
    backend = jax.default_backend()
    if backend not in ("cpu", "tpu"):
        # The sequential-innermost-grid accumulation is a TPU contract; on
        # GPU Pallas lowers via Triton with parallel grid cells and the
        # out_ref accumulation would race.
        raise NotImplementedError(
            f"hist_backend='pallas' supports tpu (compiled) and cpu "
            f"(interpret) backends, not {backend!r}; use 'scatter'"
        )
    F, n = bins_t.shape
    chunk = n if chunk is None else chunk
    vals = vals.astype(_tile_dtype(vals.dtype))
    # VMEM guard: the kernel's iota/one-hot tiles are (num_bins, bm); the
    # defaults were swept at B=256, so scale bm down for bigger bin counts.
    # Powers of two / 128-multiples only: Pallas requires 128-aligned
    # trailing block dims (an 8-aligned guard broke num_bins like 712).
    bm = min(bm, _pow2_floor(max(512, bm * 256 // num_bins)))
    bm = min(bm, _round_up(chunk, 128))
    bf = min(bf, F)  # a block as tall as the matrix, or a multiple of 8
    bins_t, (vals,), c, chunk = _place_chunk(bins_t, [(vals, 0)], i, chunk, bm)
    return _pallas_hist(
        bins_t, vals, c, num_bins, bm, bf, chunk, backend == "cpu", precision
    )


# ---------------------------------------------------------------------------
# Per-leaf histograms (depthwise grower): hist[c, l, f, b] in one data pass.
#
# Contraction per feature: out[(c·L+l), b] = Σ_r rhs[r, c·L+l] · onehot[b, r]
# where rhs[r, c·L+l] = vals[c, r] · (leaf[r] == l).  The leaf axis
# multiplies the matmul's tiny channel dimension up to 3·L — at the
# depthwise window W=32 that is M=96, which feeds the MXU properly.
# ---------------------------------------------------------------------------
def _hist_leaf_kernel(
    chunk_ref, bins_ref, vals_ref, leaf_ref, out_ref, *,
    num_bins: int, num_leaves: int, rm: int, precision,
):
    """One (feature-block j, row-block i) cell (``chunk_ref``: the
    prefetched chunk index, the ``index_map``s' alone).

    The row block (bm) is deliberately LARGE with an in-kernel
    accumulation loop over ``rm``-row sub-blocks: VMEM tiles are bounded by
    ``rm`` while the grid stays coarse — at bm=rm the grid overhead of ~8k
    tiny cells dominated the pass.  ``rm`` is also the matmul contraction
    length: small rm left the MXU latency-bound (65k tiny matmuls at
    rm=256 traced ~10x slower than rm=1024).
    """
    i = pl.program_id(1)  # row block, innermost → accumulation is safe
    bf, bm = bins_ref.shape
    quant = _is_bucket(vals_ref.dtype)

    def sub(s, acc):
        sl = pl.ds(s * rm, rm)
        # uint8 at ≤256 bins: 1-byte DMA, widened in VMEM (see _hist_kernel)
        bins = bins_ref[:, sl].astype(jnp.int32)  # (bf, rm)
        vals = vals_ref[:, sl]  # (3, rm) f32 | int16 buckets
        if quant:
            vals = vals.astype(jnp.float32)
        leaf = leaf_ref[0, sl]  # (rm,) int32
        # Leaf-masked values, channel-major columns: rhs[r, c·L + l] =
        # vals[c, r] · (leaf[r] == l).  Three lane-dim concats because
        # Mosaic cannot lane-merge a trailing (L, 3) pair.  Rows parked
        # outside [0, num_leaves) (out-of-bag/padding/windowed-out) match
        # no slot → 0.
        iota_l = jax.lax.broadcasted_iota(jnp.int32, (rm, num_leaves), 1)
        oh_leaf = (iota_l == leaf[:, None]).astype(jnp.float32)
        rhs = jnp.concatenate(
            [oh_leaf * vals[c, :][:, None] for c in range(3)], axis=1
        )  # (rm, 3·L)
        iota_b = jax.lax.broadcasted_iota(jnp.int32, (num_bins, rm), 0)
        parts = []
        for f in range(bf):
            oh_f = (iota_b == bins[f, :][None, :]).astype(jnp.float32)
            parts.append(
                jax.lax.dot_general(
                    rhs, oh_f,
                    dimension_numbers=(((0,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32,
                    precision=precision,
                )  # (3·L, B)
            )
        # Output (3·L, bf·B): the small 3·L axis on SUBLANES (pads to a
        # multiple of 8) and the big bf·B axis on lanes — the transposed
        # orientation padded 3·L up to 256 lanes and blew the 16M VMEM
        # budget through the grid-resident accumulator tile.
        part = jnp.concatenate(parts, axis=1)  # (3·L, bf·B)
        if quant:
            # integer-valued f32 partial sums ≤ rm·QMAX ≪ 2²⁴ → exact cast
            part = part.astype(jnp.int32)
        return acc + part

    part = jax.lax.fori_loop(
        0, bm // rm, sub,
        # headroom: bm·QMAX ≪ 2³¹ per block of buckets; the cross-block
        # int32 total is bounded by quantize_wire_plan's static rows × bucket check
        jnp.zeros(
            (3 * num_leaves, bf * num_bins),
            jnp.int32 if quant else jnp.float32,
        ),
    )

    @pl.when(i == 0)
    def _init():
        out_ref[...] = part[None]

    @pl.when(i > 0)
    def _acc():
        out_ref[...] += part[None]


def _hist_leaf_kernel_work(M: int, N: int, bf: int, bm: int, rm: int, quant: bool):
    """As :func:`_hist_kernel_work`, of :func:`_hist_leaf_kernel`: ``M`` =
    3·L, ``N`` = bins; a sub-block of ``rm`` rows builds the leaf one-hot
    (compare, convert), its three products, a bin one-hot a column, and adds
    its ``(M, bf·N)`` part to the carry; the cell adds the carry to the
    output tile."""
    L = M // 3
    sub = bf * rm + 5 * L * rm + bf * 2 * N * rm + M * bf * N * (2 if quant else 1)
    sub += 3 * rm if quant else 0
    return 2 * M * N * bm * bf, (bm // rm) * sub + M * bf * N


@functools.partial(
    jax.jit,
    static_argnames=(
        "num_leaves", "num_bins", "bm", "bf", "rm", "chunk", "interpret", "precision"
    ),
)
def _pallas_hist_by_leaf(
    bins_t, vals, leaf_ids, c, num_leaves, num_bins, bm, bf, rm, chunk, interpret, precision
):
    """(3, L, F, B) of chunk ``c[0]``'s ``chunk`` rows of the whole ``(F,
    n)`` ``bins_t``, ``(3, n)`` ``vals`` and ``(1, n)`` ``leaf_ids``
    (:func:`_chunk_grid`)."""
    F = bins_t.shape[0]
    blocks = pl.cdiv(F, bf)
    kernel = functools.partial(
        _hist_leaf_kernel, num_bins=num_bins, num_leaves=num_leaves, rm=rm,
        precision=_PRECISIONS[precision],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=_chunk_grid(F, bf, bm, chunk, True, (1, num_leaves * 3, bf * num_bins)),
        # headroom: the int32 grid accumulator of a bucket build — rows ×
        # largest bucket per shard is attested statically by
        # ops.histogram.quantize_wire_plan before kernels run
        out_shape=jax.ShapeDtypeStruct(
            (blocks, num_leaves * 3, bf * num_bins),
            jnp.int32 if _is_bucket(vals.dtype) else jnp.float32,
        ),
        compiler_params=_by_leaf_compiler_params(num_leaves, bf, num_bins),
        interpret=interpret,
    )(c, bins_t, vals, leaf_ids)
    # (F/bf, 3·L, bf·B) channel-major → (3, L, F, B); a ragged last block's
    # columns past F are dropped here
    out = out.reshape(blocks, 3, num_leaves, bf, num_bins)
    return out.transpose(1, 2, 0, 3, 4).reshape(3, num_leaves, blocks * bf, num_bins)[:, :, :F]


# Largest by-leaf accumulator (3·W · bf·B f32 elements) the v5e compiler
# fits in its default 16 MiB of scoped VMEM next to the other tiles:
# W=32, bf=48, B=256 (4.5 MiB) compiles with uint8 bins; one step past it
# — the same tile with 4-byte bins, or B=512 at bf≥32 — is refused
# ("Scoped allocation with size 16.41M and limit 16.00M exceeded scoped
# vmem limit").
_ACC_BUDGET_ELS = 3 * 32 * 48 * 256


def _by_leaf_compiler_params(num_leaves: int, bf: int, num_bins: int):
    """``None`` (the compiler's defaults) wherever the accumulator is
    inside the budget ``_prep_by_leaf_chunk`` sizes ``bf`` to.  Only when
    even the minimum block bf=8 is over it (W·B > 49,152: ≥249-leaf
    depthwise windows at ≥512 bins) is the scoped-VMEM limit raised to
    hold the tile's four live copies."""
    acc_els = 3 * num_leaves * bf * num_bins
    if acc_els <= _ACC_BUDGET_ELS:
        return None
    return pltpu.CompilerParams(vmem_limit_bytes=16 * acc_els + (8 << 20))


def _prep_by_leaf_chunk(
    bins_t, vals, leaf_ids, num_leaves: int, num_bins: int,
    bm: int, bf: int, rm: int, i=0, chunk: Optional[int] = None,
):
    """Shared wrapper prep for the by-leaf kernels: backend check, block
    choice, the chunk's place (:func:`_place_chunk`).  Returns
    (bins_t, vals, leaf_row, c, chunk, bm, bf, rm, interpret): the call's
    operands, ``bins_t`` the very matrix wherever the chunk is whole row
    blocks.  Float ``vals`` go in as f32, integer buckets as int16 (the row
    values DMA at half width)."""
    F, n = bins_t.shape  # integer, uint8 through the byte tier
    chunk = n if chunk is None else chunk
    bm, bf, rm, interpret = _by_leaf_blocks(F, num_leaves, num_bins, bm, bf, rm, chunk)
    vals = vals.astype(_tile_dtype(vals.dtype))
    leaf_row = leaf_ids.astype(jnp.int32)[None, :]  # (1, n): lane-friendly
    # padded rows park at leaf == num_leaves → no one-hot slot
    bins_t, (vals, leaf_row), c, chunk = _place_chunk(
        bins_t, [(vals, 0), (leaf_row, num_leaves)], i, chunk, bm
    )
    return bins_t, vals, leaf_row, c, chunk, bm, bf, rm, interpret


def _by_leaf_blocks(F: int, num_leaves: int, num_bins: int, bm: int, bf: int, rm: int, chunk: int):
    """The by-leaf bodies' ``(bm, bf, rm, interpret)`` for ``F`` columns,
    ``num_leaves`` slots and a chunk of ``chunk`` rows, after the backend
    check."""
    backend = jax.default_backend()
    if backend not in ("cpu", "tpu"):
        raise NotImplementedError(
            f"hist_backend='pallas' supports tpu/cpu backends, not {backend!r}"
        )
    # Feature blocks.  No column is padded: a block is as tall as the
    # matrix (always a legal block dimension: 39 on the criteo schema, the
    # refinement's 1) wherever that fits, else a multiple of 8 (legal for
    # uint8 as for int32 bins: Mosaic pads the 8-bit (32, 128) tile itself)
    # with the last block ragged.  A ragged block is looped over whole, so
    # the choice is the FEWEST COLUMNS COMPUTED, blocks × height: bf=32 on
    # F=220 (the istella schema) computes 224 where bf=48 computes 240; on
    # F=136 (the MSLR schema) it computes 160 where bf=48 computes 144.  The
    # cap is VMEM:
    # the grid-resident (3·W, bf·B) f32 accumulator, its loop carry and the
    # double-buffered output block share the 16 MiB scoped default with the
    # bins block — see _ACC_BUDGET_ELS.  Ties prefer the LARGER block
    # (fewer grid steps amortize the per-block leaf-side rhs build).
    cap = min(48, max(8, _ACC_BUDGET_ELS // (3 * num_leaves * num_bins) // 8 * 8))
    if F <= cap:
        bf = F
    else:
        bf = min(
            {c for c in (bf, 24, 40, 48) if c <= cap} or {cap},
            key=lambda c: (_round_up(F, c), -c),
        )
    # VMEM guard: (num_bins, rm) one-hot tiles were swept at B=256.  rm
    # must stay a power of two ≥ 256: pl.ds offsets need 128 alignment and
    # the in-kernel loop needs rm | bm (an 8-aligned guard silently dropped
    # rows on the interpret path for num_bins like 304).
    rm = min(rm, _pow2_floor(max(256, rm * 256 // num_bins)))
    bm = min(bm, _round_up(chunk, rm))
    rm = min(rm, bm)
    return bm, bf, rm, backend == "cpu"


def pallas_hist_by_leaf_chunk(
    bins_t, vals, leaf_ids, num_leaves: int, num_bins: int,
    bm: int = 16384, bf: int = 32, rm: int = 1024, precision: str = "highest",
    i=0, chunk: Optional[int] = None,
) -> jnp.ndarray:
    """(F, n) bins + (3, n) vals + (n,) leaf ids → (3, L, F, B) of rows
    ``[i·chunk, (i+1)·chunk)``, read in place: float32 sums of float
    ``vals``, int32 sums of int16 bucket ``vals`` (see
    :func:`pallas_hist_chunk`).

    ``rm`` bounds the VMEM one-hot tile AND sets the matmul contraction
    length; ``bm`` is the DMA/grid granularity.  Defaults from a traced
    sweep at 262k×64×256/W=32 on v5e: bf=32 amortizes the per-sub-block
    leaf-side rhs build over 4x more matmul work (10.3 → 6.0 ms/pass);
    bf=64 and bm=32k×rm=2k blow the remote-compile VMEM budget.
    """
    bins_t, vals, leaf_row, c, chunk, bm, bf, rm, interp = _prep_by_leaf_chunk(
        bins_t, vals, leaf_ids, num_leaves, num_bins, bm, bf, rm, i, chunk
    )
    return _pallas_hist_by_leaf(
        bins_t, vals, leaf_row, c, num_leaves, num_bins, bm, bf, rm, chunk,
        interp, precision,
    )


# ---------------------------------------------------------------------------
# Factorized (hi/lo) by-leaf kernel for SMALL leaf windows.
#
# At small W the plain kernel's matmul M = 3·W starves the MXU (W=12 →
# M=36/128 ≈ 28% utilization, with the N axis already full at B=256).
# Factoring the bin axis as bin = hi·LO + lo moves the hi part into M:
#
#     out[(c,l,hi), (f,lo)] = Σ_r vals[c,r]·1[leaf_r=l]·1[hi_rf=hi]·1[lo_rf=lo]
#
# i.e. per feature a (rm, 3·W·H) × (rm, LO) contraction with M = 3·W·H and
# N = LO = 128 — identical FLOPs to the plain kernel (M·N invariant), twice
# the MXU utilization at W≤16, and the (B, rm) one-hot build shrinks to
# (W·H, rm) + (LO, rm).  Only pays when W is small: at W=32 the plain
# kernel is already M-saturated and the per-feature lhs build dominates.
# Nothing in it is float-specific: int16 buckets go through the same
# products into an int32 accumulator (see "Value dtype"), where the plain
# kernel at W=8 took 2.57x the time a row and pass (PERF.md §5).
# ---------------------------------------------------------------------------
_NIBBLE_LO = 128


def _hist_leaf_nibble_kernel(
    chunk_ref, bins_ref, vals_ref, leaf_ref, out_ref, *,
    num_bins: int, num_leaves: int, rm: int, precision,
):
    i = pl.program_id(1)  # row block, innermost → accumulation is safe
    bf, bm = bins_ref.shape
    H = (num_bins + _NIBBLE_LO - 1) // _NIBBLE_LO
    M = 3 * num_leaves * H
    quant = _is_bucket(vals_ref.dtype)

    def sub(s, acc):
        sl = pl.ds(s * rm, rm)
        # uint8 at ≤256 bins: 1-byte DMA, widened in VMEM (the >>/& bit
        # ops below need the widening anyway — hi spans [0, 2) at B=256)
        bins = bins_ref[:, sl].astype(jnp.int32)  # (bf, rm)
        vals = vals_ref[:, sl]  # (3, rm) f32 | int16 buckets
        if quant:
            vals = vals.astype(jnp.float32)
        leaf = leaf_ref[0, sl]  # (rm,) int32
        # All operands keep ROWS ON LANES (rm trailing) — mixed-orientation
        # tiles with a 24-wide trailing dim crashed the Mosaic compile.
        iota_key = jax.lax.broadcasted_iota(
            jnp.int32, (num_leaves * H, rm), 0
        )
        iota_lo = jax.lax.broadcasted_iota(jnp.int32, (_NIBBLE_LO, rm), 0)
        parts = []
        for f in range(bf):
            hi = bins[f, :] >> 7  # LO = 128
            lo = bins[f, :] & (_NIBBLE_LO - 1)
            # parked rows (leaf outside [0, W)) produce keys outside the
            # iota range → all-zero one-hot rows
            key = leaf * H + hi
            oh_key = (iota_key == key[None, :]).astype(jnp.float32)  # (WH, rm)
            lhs = jnp.concatenate(
                [oh_key * vals[c, :][None, :] for c in range(3)], axis=0
            )  # (3·W·H, rm)
            oh_lo = (iota_lo == lo[None, :]).astype(jnp.float32)  # (LO, rm)
            parts.append(
                jax.lax.dot_general(
                    lhs, oh_lo,
                    dimension_numbers=(((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32,
                    precision=precision,
                )  # (3·W·H, LO)
            )
        part = jnp.concatenate(parts, axis=1)  # (M, bf·LO)
        if quant:
            # integer-valued f32 partial sums ≤ rm·QMAX ≪ 2²⁴ → exact cast
            part = part.astype(jnp.int32)
        return acc + part

    part = jax.lax.fori_loop(
        0, bm // rm, sub,
        # headroom: bm·QMAX ≪ 2³¹ per block of buckets; the cross-block
        # int32 total is bounded by quantize_wire_plan's static rows × bucket check
        jnp.zeros((M, bf * _NIBBLE_LO), jnp.int32 if quant else jnp.float32),
    )

    @pl.when(i == 0)
    def _init():
        out_ref[...] = part[None]

    @pl.when(i > 0)
    def _acc():
        out_ref[...] += part[None]


def _hist_leaf_nibble_kernel_work(M: int, N: int, bf: int, bm: int, rm: int, quant: bool):
    """As :func:`_hist_kernel_work`, of :func:`_hist_leaf_nibble_kernel`:
    ``M`` = 3·W·H, ``N`` = LO.  A row and column: ``hi``, ``lo``, the key's
    multiply and add, the key one-hot (W·H compares and converts), its three
    products and the ``lo`` one-hot (LO compares and converts); a sub-block
    adds its ``(M, bf·LO)`` part to the carry, the cell the carry to the
    output tile."""
    WH = M // 3
    sub = bf * rm + bf * (4 + 5 * WH + 2 * N) * rm + M * bf * N * (2 if quant else 1)
    sub += 3 * rm if quant else 0
    return 2 * M * N * bm * bf, (bm // rm) * sub + M * bf * N


@functools.partial(
    jax.jit,
    static_argnames=(
        "num_leaves", "num_bins", "bm", "bf", "rm", "chunk", "interpret", "precision"
    ),
)
def _pallas_hist_by_leaf_nibble(
    bins_t, vals, leaf_ids, c, num_leaves, num_bins, bm, bf, rm, chunk, interpret, precision
):
    """As :func:`_pallas_hist_by_leaf`, through the factorized body."""
    F = bins_t.shape[0]
    blocks = pl.cdiv(F, bf)
    H = (num_bins + _NIBBLE_LO - 1) // _NIBBLE_LO
    M = 3 * num_leaves * H
    kernel = functools.partial(
        _hist_leaf_nibble_kernel, num_bins=num_bins, num_leaves=num_leaves,
        rm=rm, precision=_PRECISIONS[precision],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=_chunk_grid(F, bf, bm, chunk, True, (1, M, bf * _NIBBLE_LO)),
        # headroom: the int32 grid accumulator of a bucket build, as in
        # _pallas_hist_by_leaf (quantize_wire_plan's static check)
        out_shape=jax.ShapeDtypeStruct(
            (blocks, M, bf * _NIBBLE_LO),
            jnp.int32 if _is_bucket(vals.dtype) else jnp.float32,
        ),
        compiler_params=_by_leaf_compiler_params(
            num_leaves, bf, H * _NIBBLE_LO
        ),
        interpret=interpret,
    )(c, bins_t, vals, leaf_ids)
    # (F/bf, 3·W·H, bf·LO) → (3, W, F, H·LO) → slice the real columns (a
    # ragged last block's run past F) and the real bin range
    out = out.reshape(blocks, 3, num_leaves, H, bf, _NIBBLE_LO)
    out = out.transpose(1, 2, 0, 4, 3, 5).reshape(
        3, num_leaves, blocks * bf, H * _NIBBLE_LO
    )
    return out[:, :, :F, :num_bins]


def pallas_hist_by_leaf_nibble_chunk(
    bins_t, vals, leaf_ids, num_leaves: int, num_bins: int,
    bm: int = 16384, bf: int = 32, rm: int = 1024, precision: str = "highest",
    i=0, chunk: Optional[int] = None,
) -> jnp.ndarray:
    """Factorized-bin variant of :func:`pallas_hist_by_leaf_chunk` — same
    contract, float32 sums of float ``vals`` and int32 sums of int16
    bucket ``vals``, intended for small windows (see module comment
    above).  The hi/lo factoring is a one-hot split and its un-factoring a
    reshape: of buckets it gives the plain kernel's int32 sums bit for
    bit."""
    bins_t, vals, leaf_row, c, chunk, bm, bf, rm, interp = _prep_by_leaf_chunk(
        bins_t, vals, leaf_ids, num_leaves, num_bins, bm, bf, rm, i, chunk
    )
    return _pallas_hist_by_leaf_nibble(
        bins_t, vals, leaf_row, c, num_leaves, num_bins, bm, bf, rm, chunk,
        interp, precision,
    )


# ---------------------------------------------------------------------------
# Class roots: the root histograms of a multiclass iteration's K trees, the
# factorized body's tile with a CLASS in each slot.  At the root every row is
# in leaf 0 of every class's tree and only the row values differ, so eight
# classes share one pass: slot w of group p is class 8p + w, its values read
# out of the (K, n) gradient and hessian where they lie, an (8, bm) block of
# each through the index map, and weighted by the shared bag in the body:
#
#     lhs[(c, h, w), r] = 1[hi_rf = h] · v[c, w, r]
#     v[0] = g·bag, v[1] = h·bag, v[2] = 1[bag > 0]
#
# The same products as the nibble body's slot 0 (its one-hot times the same
# float32 values), so each class's sums are the per-class root's; its rows
# are ordered (channel, half, slot) so that every piece is a whole (8, rm)
# sublane tile.  Groups are the grid's leading axis; a ragged last group's
# slots read past K and are dropped by the wrapper.  Its own body beside
# the nibble body: the operands differ, and the nibble body's compiled
# program is every other fit's.
# ---------------------------------------------------------------------------
_CLASS_SLOTS = 8


def _class_roots_kernel(
    chunk_ref, bins_ref, grad_ref, hess_ref, bag_ref, out_ref, *,
    num_bins: int, rm: int, precision,
):
    i = pl.program_id(2)  # row block, innermost → accumulation is safe
    bf, bm = bins_ref.shape
    H = (num_bins + _NIBBLE_LO - 1) // _NIBBLE_LO
    G = grad_ref.shape[0]

    def sub(s, acc):
        sl = pl.ds(s * rm, rm)
        bins = bins_ref[:, sl].astype(jnp.int32)  # (bf, rm), see _hist_kernel
        bag = bag_ref[:, sl]  # (1, rm)
        in_bag = jnp.broadcast_to((bag > 0).astype(jnp.float32), (G, rm))
        vals = (grad_ref[:, sl] * bag, hess_ref[:, sl] * bag, in_bag)  # (G, rm) each
        iota_lo = jax.lax.broadcasted_iota(jnp.int32, (_NIBBLE_LO, rm), 0)
        parts = []
        for f in range(bf):
            hi = bins[f, :] >> 7  # LO = 128
            lo = bins[f, :] & (_NIBBLE_LO - 1)
            halves = [(hi == h).astype(jnp.float32)[None, :] for h in range(H)]
            lhs = jnp.concatenate(
                [half * v for v in vals for half in halves], axis=0
            )  # (3·H·G, rm)
            oh_lo = (iota_lo == lo[None, :]).astype(jnp.float32)  # (LO, rm)
            parts.append(
                jax.lax.dot_general(
                    lhs, oh_lo,
                    dimension_numbers=(((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32,
                    precision=precision,
                )  # (3·H·G, LO)
            )
        return acc + jnp.concatenate(parts, axis=1)  # (M, bf·LO)

    part = jax.lax.fori_loop(
        0, bm // rm, sub, jnp.zeros((3 * H * G, bf * _NIBBLE_LO), jnp.float32)
    )

    @pl.when(i == 0)
    def _init():
        out_ref[...] = part[None, None]

    @pl.when(i > 0)
    def _acc():
        out_ref[...] += part[None, None]


def _class_roots_kernel_work(M: int, N: int, bf: int, bm: int, rm: int, quant: bool):
    """As :func:`_hist_kernel_work`, of :func:`_class_roots_kernel`: ``M`` =
    3·H·G, ``N`` = LO.  A sub-block widens its bins, weighs the G slots'
    gradients and hessians by the bag and takes the in-bag flag (compare,
    convert); a column takes ``hi``, ``lo``, H half masks (compare,
    convert), their products with the three channels' G rows and the ``lo``
    one-hot (LO compares and converts); the sub-block adds its ``(M,
    bf·LO)`` part to the carry, the cell the carry to the output tile.
    Float values only (``quant`` is always False)."""
    G = _CLASS_SLOTS
    H = M // (3 * G)
    sub = bf * rm + 2 * G * rm + 2 * rm + bf * (2 + 2 * H + M + 2 * N) * rm + M * bf * N
    return 2 * M * N * bm * bf, (bm // rm) * sub + M * bf * N


@functools.partial(
    jax.jit,
    static_argnames=("num_bins", "bm", "bf", "rm", "chunk", "interpret", "precision"),
)
def _pallas_hist_class_roots(
    bins_t, grad, hess, bag, c, num_bins, bm, bf, rm, chunk, interpret, precision
):
    """(K, 3, F, B) root histograms of chunk ``c[0]``'s ``chunk`` rows of the
    whole ``(F, n)`` ``bins_t``, ``(K, n)`` ``grad`` and ``hess`` and ``(1,
    n)`` ``bag``: ⌈K/8⌉ groups of eight classes on the grid's leading axis,
    each a pass over the chunk's bins."""
    F = bins_t.shape[0]
    K = grad.shape[0]
    G = _CLASS_SLOTS
    groups, blocks, k = pl.cdiv(K, G), pl.cdiv(F, bf), chunk // bm
    H = (num_bins + _NIBBLE_LO - 1) // _NIBBLE_LO
    M = 3 * H * G
    kernel = functools.partial(
        _class_roots_kernel, num_bins=num_bins, rm=rm, precision=_PRECISIONS[precision],
    )
    vals_spec = pl.BlockSpec((G, bm), lambda p, j, i, c: (p, c[0] * k + i))
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(groups, blocks, k),
            in_specs=[
                pl.BlockSpec((bf, bm), lambda p, j, i, c: (j, c[0] * k + i)),
                vals_spec, vals_spec,
                pl.BlockSpec((1, bm), lambda p, j, i, c: (0, c[0] * k + i)),
            ],
            out_specs=pl.BlockSpec((1, 1, M, bf * _NIBBLE_LO), lambda p, j, i, c: (p, j, 0, 0)),
        ),
        out_shape=jax.ShapeDtypeStruct((groups, blocks, M, bf * _NIBBLE_LO), jnp.float32),
        compiler_params=_by_leaf_compiler_params(G, bf, H * _NIBBLE_LO),
        interpret=interpret,
    )(c, bins_t, grad, hess, bag)
    # (P, F/bf, 3·H·G, bf·LO) → (P·G, 3, F, H·LO) → the real classes, columns
    # (a ragged last block's run past F) and bin range
    out = out.reshape(groups, blocks, 3, H, G, bf, _NIBBLE_LO)
    out = out.transpose(0, 4, 2, 1, 5, 3, 6).reshape(
        groups * G, 3, blocks * bf, H * _NIBBLE_LO
    )
    return out[:K, :, :F, :num_bins]


def pallas_hist_class_roots_chunk(
    bins_t, grad, hess, bag, num_bins: int,
    bm: int = 16384, bf: int = 32, rm: int = 1024, precision: str = "highest",
    i=0, chunk: Optional[int] = None,
) -> jnp.ndarray:
    """(F, n) bins + (K, n) gradients and hessians + (n,) bag weights →
    (K, 3, F, B) of rows ``[i·chunk, (i+1)·chunk)``, read in place: each
    class's root histogram of ``(g·bag, h·bag, 1[bag > 0])``, float32 sums,
    equal to the nibble body's slot 0 at eight slots over the same values.
    Blocks as :func:`_by_leaf_blocks` chooses them for eight slots."""
    F, n = bins_t.shape
    chunk = n if chunk is None else chunk
    bm, bf, rm, interp = _by_leaf_blocks(F, _CLASS_SLOTS, num_bins, bm, bf, rm, chunk)
    bag_row = bag[None, :]  # (1, n): lane-friendly
    # padded rows carry a zero bag: no value and no count
    bins_t, (grad, hess, bag_row), c, chunk = _place_chunk(
        bins_t, [(grad, 0), (hess, 0), (bag_row, 0)], i, chunk, bm
    )
    return _pallas_hist_class_roots(
        bins_t, grad, hess, bag_row, c, num_bins, bm, bf, rm, chunk, interp, precision,
    )


# ---------------------------------------------------------------------------
# What a traced call issues (the booster's histogram work ledger).  The
# wrappers by the name their ``jit`` equation carries: the body's label and
# the function beside it that states a grid cell's work.
# ---------------------------------------------------------------------------
WRAPPERS = {
    "_pallas_hist": ("plain", _hist_kernel_work),
    "_pallas_hist_by_leaf": ("by_leaf", _hist_leaf_kernel_work),
    "_pallas_hist_by_leaf_nibble": ("nibble", _hist_leaf_nibble_kernel_work),
    "_pallas_hist_class_roots": ("class_root", _class_roots_kernel_work),
}


def call_work(eqn) -> dict:
    """One call of a wrapper of :data:`WRAPPERS`, read off its ``jit``
    equation: ``body``, ``quant`` (int16 bucket values), ``passes`` (the
    passes over the chunk's rows it makes: the grid's axes before the column
    and row axes, the class roots' groups; 1 for the others), ``rowcols``
    (rows × columns the call reads: its grid's cells times a block),
    ``mxu_flops`` and ``vpu_elems``.  All of it is the inner
    ``pallas_call``'s: the operands are the growers' whole arrays, the grid
    is one chunk's.  Its blocks and result give ``bf``, ``bm``, ``M`` and
    ``N``, and the length of the body's loop over sub-blocks gives ``rm``."""
    body, work = WRAPPERS[eqn.params["name"]]
    quant = _is_bucket(eqn.invars[1].aval.dtype)
    (call,) = (e for e in eqn.params["jaxpr"].eqns if e.primitive.name == "pallas_call")
    grid = call.params["grid_mapping"]
    bf, bm = (int(b.block_size) for b in grid.block_mappings[0].block_shape)
    M, lanes = call.params["out_avals"][0].shape[-2:]
    loops = [e.params["length"] for e in call.params["jaxpr"].eqns if e.primitive.name == "scan"]
    rm = bm // int(loops[0]) if loops else bm
    flops, elems = work(M, lanes // bf, bf, bm, rm, quant)
    cells = math.prod(int(d) for d in grid.grid)
    return {
        "body": body, "quant": quant, "passes": math.prod(int(d) for d in grid.grid[:-2]),
        "rowcols": cells * bf * bm, "mxu_flops": cells * flops, "vpu_elems": cells * elems,
    }
