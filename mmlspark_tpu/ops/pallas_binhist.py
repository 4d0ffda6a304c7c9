"""Fused Pallas bin+occupancy kernel for the streamed ingest step.

ISSUE 11 tentpole (c): the streamed ingest
(:func:`mmlspark_tpu.data.streaming.stream_ingest`) used to run device
binning and the occupancy tally as SEPARATE dispatches, so every binned
chunk round-tripped HBM between the two.  This kernel computes, in one
pass over a raw f32 chunk:

- the uint8 bin ids (written once, straight into the chunk's cache
  slot), and
- the exact per-feature bin-occupancy histogram ``occ[f, b]``
  (grid-accumulated in VMEM — the binned rows are consumed for the
  tally while still in registers/VMEM, never re-read from HBM).

Semantics are EXACTLY those of
:func:`mmlspark_tpu.ops.device_binning.bin_rows_device` (the shared
binning authority): double-single f64-exact boundary compares,
categorical exact-match with trunc-toward-zero, NaN → missing bin.  The
kernel replaces the branchless binary search (log₂P predicated GATHER
steps — gathers are the expensive part on TPU) with an O(P)
**count-below** loop:

    pos[r, f] = Σ_p  (hi[p,f] < v) | ((hi[p,f] == v) & (lo[p,f] < 0))

which is pure vector compares — every operand keeps features on the
128-lane axis, so each of the P iterations is one (bm, F) VPU op and no
relayout or gather ever lowers.  The categorical hit test folds into the
same loop: boundaries are sorted, so "some table entry equals v
exactly" ⟺ "the entry at the insertion point equals v", and the pad
entries (+inf) can never produce a finite-v hit.

Layout: rows arrive row-major (bm, F_pad) — features lane-padded to a
128 multiple — and the boundary table arrives TRANSPOSED (P, F_pad), so
the per-iteration boundary row broadcasts along sublanes with no
transpose.  The uint8 bins block satisfies the int8 (32, 128) min tile
(``bm ≥ 32``); the (B, F_pad) int32 occupancy block accumulates across
the sequential row grid (TPU contract — same pattern as
``ops/pallas_hist.py``).

Backends: tpu (compiled) and cpu (interpret, parity tests only —
``tests/test_binpack_bytes.py``); the streamed ingest uses the XLA path
on cpu where interpret mode would be slower than what it replaces.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


def _bin_occ_kernel(
    rows_ref, hi_ref, lo_ref, iscat_ref, bins_ref, occ_ref, *,
    n_rows: int, n_bounds: int, num_bins: int, missing_bin: int,
):
    """One row-block: bins out + occupancy accumulated across the grid."""
    i = pl.program_id(0)  # row block (sequential → accumulation is safe)
    v_raw = rows_ref[...]  # (bm, Fp) f32
    bm, Fp = v_raw.shape
    ic = iscat_ref[...] != 0  # (1, Fp)
    # host cat matching truncates toward zero (see device_binning)
    v = jnp.where(ic, jnp.trunc(v_raw), v_raw)

    def p_body(p, carry):
        pos, hit = carry
        h = hi_ref[pl.ds(p, 1), :]  # (1, Fp): broadcasts along sublanes
        l = lo_ref[pl.ds(p, 1), :]
        # f64-exact "boundary < v" via the double-single pair
        below = (h < v) | ((h == v) & (l < 0))
        # exact-match hit anywhere ⟺ hit at the insertion point (sorted
        # table); +inf pads can't hit a finite v.  Carried as 0/1 int32:
        # Mosaic cannot legalize an scf.for that carries a bool vector.
        hit = hit | ((h == v) & (l == 0)).astype(jnp.int32)
        return pos + below.astype(jnp.int32), hit

    # headroom: pos counts boundaries below v, so it is bounded by
    # n_bounds ≤ BYTE_MAX_BINS = 256 ≪ 2³¹ (cf. ops.histogram.
    # quantize_wire_plan for the histogram-side int32 audit)
    pos, hit = jax.lax.fori_loop(
        0, n_bounds, p_body,
        (jnp.zeros((bm, Fp), jnp.int32), jnp.zeros((bm, Fp), jnp.int32)),
    )
    hit = (hit != 0) & jnp.isfinite(v)
    bins = jnp.where(ic, jnp.where(hit, pos, missing_bin), pos)
    bins = jnp.where(jnp.isnan(v_raw), missing_bin, bins)
    bins_ref[...] = bins.astype(jnp.uint8)

    # padded tail rows of the last block must not tally
    gr = i * bm + jax.lax.broadcasted_iota(jnp.int32, (bm, Fp), 0)
    valid = gr < n_rows

    @pl.when(i == 0)
    def _init():
        # headroom: occ tallies at most n_rows per (bin, feature); one
        # streamed chunk is ≪ 2³¹ rows (the int32 limit), same bound
        # ops.histogram.quantize_wire_plan attests for histogram counts
        occ_ref[...] = jnp.zeros((num_bins, Fp), jnp.int32)

    def occ_body(b, _):
        m = (bins == b) & valid
        cnt = jnp.sum(m.astype(jnp.int32), axis=0, keepdims=True)  # (1, Fp)
        occ_ref[pl.ds(b, 1), :] += cnt
        return 0

    jax.lax.fori_loop(0, num_bins, occ_body, 0)


@functools.partial(
    jax.jit,
    static_argnames=(
        "n_rows", "n_bounds", "num_bins", "missing_bin", "bm", "interpret"
    ),
)
def _bin_occ(
    rows_p, hi_t, lo_t, iscat_row,
    n_rows: int, n_bounds: int, num_bins: int, missing_bin: int,
    bm: int, interpret: bool,
):
    n_pad, Fp = rows_p.shape
    kernel = functools.partial(
        _bin_occ_kernel, n_rows=n_rows, n_bounds=n_bounds,
        num_bins=num_bins, missing_bin=missing_bin,
    )
    return pl.pallas_call(
        kernel,
        grid=(n_pad // bm,),
        in_specs=[
            pl.BlockSpec((bm, Fp), lambda i: (i, 0)),
            pl.BlockSpec((n_bounds, Fp), lambda i: (0, 0)),
            pl.BlockSpec((n_bounds, Fp), lambda i: (0, 0)),
            pl.BlockSpec((1, Fp), lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((bm, Fp), lambda i: (i, 0)),
            pl.BlockSpec((num_bins, Fp), lambda i: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n_pad, Fp), jnp.uint8),
            # headroom: per-cell occupancy ≤ n_rows per chunk ≪ 2³¹
            # (ops.histogram.quantize_wire_plan audits the same bound)
            jax.ShapeDtypeStruct((num_bins, Fp), jnp.int32),
        ],
        interpret=interpret,
    )(rows_p, hi_t, lo_t, iscat_row)


def bin_occ_rows(
    a, rows, *, missing_bin: int, n_bounds: int, num_bins: int,
    bm: int = 1024,
):
    """(n, F) raw f32 rows → ``(bins_u8 (n, F), occ (F, B) int32)`` in one
    fused kernel pass.

    ``a`` is a :class:`~mmlspark_tpu.ops.device_binning.DeviceBinnerArrays`
    pytree; results are bitwise-identical to ``bin_rows_device`` followed
    by an ``occ.at[f, bin].add(1)`` tally (parity-tested in interpret
    mode).  Trace-time body — callable from inside other jitted programs
    (the streamed ingest step).
    """
    backend = jax.default_backend()
    if backend not in ("cpu", "tpu"):
        raise NotImplementedError(
            f"fused bin+occ kernel supports tpu (compiled) and cpu "
            f"(interpret) backends, not {backend!r}"
        )
    rows = jnp.asarray(rows, jnp.float32)
    n, F = rows.shape
    Fp = _round_up(max(F, 1), 128)
    # VMEM guard: the (bm, Fp) f32 row tile + int32 pos + bool hit stay
    # ≈ 9 bytes/elem; default bm=1024 at Fp=128 is ~1.2 MiB.  The uint8
    # bins block wants the int8 (32, 128) min tile → bm ≥ 32.
    bm = max(32, min(bm, _round_up(n, 32)))
    n_pad = _round_up(n, bm)
    pad_f = Fp - F
    if pad_f or n_pad != n:
        rows = jnp.pad(rows, ((0, n_pad - n), (0, pad_f)))
    # table transposed (P, Fp): the p-loop reads (1, Fp) boundary rows
    # that broadcast along sublanes — no per-iteration relayout.  Pad
    # features with +inf boundaries (never "below", never a finite hit).
    hi_t = jnp.pad(a.hi.T, ((0, 0), (0, pad_f)), constant_values=jnp.inf)
    lo_t = jnp.pad(a.lo.T, ((0, 0), (0, pad_f)))
    iscat_row = jnp.pad(
        a.iscat.astype(jnp.int32)[None, :], ((0, 0), (0, pad_f))
    )
    bins_p, occ = _bin_occ(
        rows, hi_t, lo_t, iscat_row,
        n_rows=n, n_bounds=n_bounds, num_bins=num_bins,
        missing_bin=missing_bin, bm=bm, interpret=backend == "cpu",
    )
    return bins_p[:n, :F], occ[:, :F].T
