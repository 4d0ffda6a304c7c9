"""Gradient-histogram construction — the GBDT hot loop.

TPU-native replacement for LightGBM's histogram construction (reference
native component N1, SURVEY.md §2.9: upstream C++ ``src/treelearner/*`` and
its CUDA kernels, shipped prebuilt in the ``lightgbmlib`` jar — [REF-EMPTY]).

Two interchangeable backends build the same CHANNEL-MAJOR histogram of
``(Σgrad, Σhess, Σcount)``:

- ``build_histogram``          → ``(3, F, B)``
- ``build_histogram_by_leaf``  → ``(3, L, F, B)``

Channel-major layout is a TPU tiling decision: every downstream consumer
(cumsums, split gains) then operates on arrays whose MINOR axis is the
bin axis (lane-sized), instead of a trailing size-3 channel axis that
wastes ~97% of each 8×128 vector tile.  ``vals`` arrives as ``(3, n)`` for
the same reason, and the bins as the growers' ``(F, n)`` matrix — uint8
through the byte tier (``num_bins ≤ 256``, ``ops/binpack.py``), int32
past it: rows on the lane axis, which is what the kernels read.

Backends:

- ``scatter``  — ``jnp...at[].add`` scatter-add.  Reference semantics; the
  backend used on the CPU test mesh (it slices, transposes and widens per
  chunk).
- ``pallas``   — one Pallas body (``mmlspark_tpu.ops.pallas_hist``): the
  histogram as a one-hot × values matmul on the MXU, with the one-hot
  tile living in VMEM only.  The chip's path; interpreted on a CPU.

Every chunk body sums in the accumulator its ``vals`` ask for, read from
their dtype at trace time: float32, or int32 for the int16 buckets of
quantized training (gradient, hessian and in-bag count each rounded to its
own number of integer levels: "Quantized accumulation" below).

Both are row-chunked with ``lax.scan`` so peak memory is bounded by the chunk,
not the dataset (HBM holds only the uint8 binned matrix — SURVEY.md §7.2).
The scan walks the chunk INDEX and hands every chunk function the whole
bins, ``vals`` and leaf ids with it: ``fn(bins, *rows, i=i, chunk=chunk)``.
How chunk ``i`` is reached is the backend's own business.  The Pallas
wrappers offset their grid by it and read the chunk's blocks where they
lie, so a pass moves no byte that no histogram needs (PERF.md §6 PR 37);
the scatter functions slice it out (``_chunk_slices``).  Nothing is
re-laid-out to put chunks on a leading axis: for the ``(F, n)`` matrix
that is a copy of the whole data set on every histogram pass, and a
compile that follows the row count (PERF.md §6 PR 28).
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax import lax

# Default rows per scan chunk; callers pad row counts to a multiple.
DEFAULT_CHUNK = 16_384


def _row_chunk(x, i, size: int, axis: int):
    """Rows ``[i·size, (i+1)·size)`` of ``x`` along its row ``axis``."""
    return lax.dynamic_slice_in_dim(x, i * size, size, axis=axis)


def _chunk_slices(arrays, i, chunk: Optional[int]):
    """Chunk ``i`` of each ``(array, row axis)``, a copy each (the device
    region ``chunk_copy``); with no ``chunk`` the arrays themselves.  How
    the scatter functions reach a chunk."""
    if chunk is None:
        return [x for x, _ in arrays]
    with jax.named_scope("chunk_copy"):
        return [_row_chunk(x, i, chunk, axis) for x, axis in arrays]


# ---------------------------------------------------------------------------
# Quantized accumulation (ISSUE 9 — LightGBM quantized training,
# "Quantized Training of Gradient Boosting Decision Trees", NeurIPS 2022)
# ---------------------------------------------------------------------------
# Per-row gradients, hessians and the in-bag count are rounded to signed
# integer buckets, each channel up to its own LARGEST BUCKET (its "level"),
# with per-iteration max-abs scales and seeded stochastic rounding;
# histograms then accumulate as int32 adds and cross the mesh on an integer
# wire.  The levels are static and come from the configuration
# (:func:`quantize_levels`):
#
# - ``num_grad_quant_bins`` given (LightGBM's parameter, default 4 there):
#   gradients in ``[-bins/2, bins/2]`` with scale ``max|g| / (bins/2)``,
#   hessians in ``[0, bins]`` with scale ``max h / bins`` (LightGBM's
#   gradient_discretizer rule), and the count's bucket 1: the count is
#   ``1[w > 0]``, always 0 or 1, so its bucket holds it exactly at scale 1.
# - absent: ``DEFAULT_LEVELS``, 127 a side for gradients and hessians
#   (QMAX: every row value one int8 of information) and the count's bucket
#   64 at the fixed scale 2⁻⁶ (COUNT_SCALE) — what the engine did before
#   the levels became a parameter, kept so that those fits, their integer
#   wire's dynamic shift included, give the same model to the bit.
#
# The row array is int16 whatever the levels (scatter/matmul convenience).
# The int32 accumulator holds ``rows × largest bucket`` (every row of a shard
# in one bin at full magnitude, and that worst case is REAL: iteration 0 of
# binary logloss has every |grad| equal), so :func:`quantize_wire_plan`
# refuses a fit where that product, channel by channel, reaches 2³¹.
QMAX = 127

# The default count channel: an in-bag row quantizes to exactly
# 1/COUNT_SCALE = 64 and dequantizes to exactly 1.0 (64 · 2⁻⁶ is exact in
# f32).  Whatever the count's bucket (64 here, 1 under
# ``num_grad_quant_bins``), its scale is the bucket's reciprocal, a power of
# two, so quantized leaf counts are EXACT and ``count >= min_data_in_leaf``
# comparisons can never flip versus the f32 path.
COUNT_SCALE = 2.0 ** -6

# Largest bucket of (gradient, hessian, count) with no level count given.
DEFAULT_LEVELS = (QMAX, QMAX, int(1 / COUNT_SCALE))
_CHANNELS = ("gradient", "hessian", "count")


def quantize_levels(num_grad_quant_bins: int = 0) -> tuple:
    """Largest bucket of ``(gradient, hessian, count)`` for LightGBM's
    ``num_grad_quant_bins`` (0 = not given: ``DEFAULT_LEVELS``)."""
    bins = int(num_grad_quant_bins)
    if bins == 0:
        return DEFAULT_LEVELS
    if not 2 <= bins <= QMAX:
        raise ValueError(
            f"num_grad_quant_bins must lie in [2, {QMAX}] (LightGBM's "
            f"default is 4), got {num_grad_quant_bins!r}"
        )
    return (bins // 2, bins, 1)


class HistQuantize(NamedTuple):
    """Static plan + scales for one quantized histogram build.

    ``wire``   — ``"int16"`` | ``"int32"``: dtype of the cross-shard merge.
    ``shift``  — static rounding right-shift applied to local int32
                 partial sums before the wire (0 when the worst-case sum
                 already fits; see :func:`quantize_wire_plan`).
    ``scales`` — ``(3,)`` f32 per-channel dequantization scales
                 (grad, hess, count), each its own: :func:`quantize_scales3`.
    """

    wire: str
    shift: int
    scales: jnp.ndarray


def quantize_wire_plan(n_rows: int, wire: str, num_shards: int = 1,
                       levels: tuple = DEFAULT_LEVELS) -> int:
    """Static integer-wire plan: the pre-merge right-shift for ``wire``.

    The worst-case bin total of a channel is ``n_rows × its largest
    bucket`` (every row in one bin at max magnitude; ``levels`` holds the
    three).  The plan guarantees, by construction:

    - the LOCAL int32 accumulator never wraps: ``ceil(n/D) × bucket <
      2³¹`` for each channel (raises ``ValueError`` naming the channel
      otherwise — quantize is unsupported at that scale rather than
      silently wrong);
    - the WIRE value fits its dtype: partial sums are right-shifted by
      ``s`` with round-half-up, so each shifted magnitude is at most
      ``(n·bucket)/2^s + 1/2`` and the D-shard sum stays under
      ``2^cap + D/2`` with cap = 14 (int16) / 30 (int32) — comfortably
      inside the signed range.  Dequantization multiplies by ``2^s``.

    The returned shift is a STATIC CEILING: the merge itself
    (:func:`merge_shard_histograms_quantized`) sizes the wire shift
    dynamically from the observed max partial, which on real data is
    far smaller — this function's job is the overflow guard and the
    attested worst-case bound.
    """
    if wire not in ("int16", "int32"):
        raise ValueError(
            f"unknown quantize wire {wire!r}; expected int16|int32"
        )
    n_local = -(-int(n_rows) // max(int(num_shards), 1))
    for channel, bucket in zip(_CHANNELS, levels):
        if n_local * int(bucket) >= 2 ** 31:
            raise ValueError(
                f"hist_quantize overflow guard: {n_local} rows/shard × the "
                f"{channel} channel's largest bucket {bucket} exceeds the "
                "int32 accumulator's headroom (2³¹); set num_grad_quant_bins "
                f"(LightGBM's default is 4) below {2 ** 31 // n_local + 1} "
                "or shard the rows further"
            )
    cap_bits = 14 if wire == "int16" else 30
    return max(0, (int(n_rows) * max(int(b) for b in levels)).bit_length() - cap_bits)


def quantize_channel_scales(grad, hess, bag_weight,
                            levels: tuple = DEFAULT_LEVELS) -> jnp.ndarray:
    """Per-iteration (grad, hess) quantization scales for ONE class:
    max-abs over the bagged batch divided by the channel's largest bucket
    (LightGBM quantized training's per-iteration gradient scale:
    ``max|g| / (bins/2)`` and ``max h / bins`` under
    ``num_grad_quant_bins``).  Zero-gradient batches get scale 1.0 so
    dequantization never divides by zero."""
    gmax = jnp.max(jnp.abs(grad * bag_weight))
    hmax = jnp.max(jnp.abs(hess * bag_weight))
    one = jnp.float32(1.0)
    return jnp.stack([
        jnp.where(gmax > 0, gmax / levels[0], one),
        jnp.where(hmax > 0, hmax / levels[1], one),
    ]).astype(jnp.float32)


def quantize_scales3(qscale, levels: tuple = DEFAULT_LEVELS) -> jnp.ndarray:
    """``(3,)`` dequantization scales: the iteration's (grad, hess) scales
    and the count's, the reciprocal of its bucket (a power of two)."""
    return jnp.concatenate([
        qscale.astype(jnp.float32),
        jnp.asarray([1.0 / levels[2]], jnp.float32),
    ])


def quantize_draw(key, shape) -> jnp.ndarray:
    """The stochastic rounding's ``u ~ U[0, 1)``, one a value."""
    return jax.random.uniform(key, shape, dtype=jnp.float32)


def quantize_hist_vals(vals, scales, key, levels: tuple = (QMAX, QMAX, QMAX),
                       stochastic: bool = True) -> jnp.ndarray:
    """Round ``vals`` (3, n) f32 to int16 buckets, channel ``c`` clipped to
    ``±levels[c]`` (the largest bucket of gradient, hessian and count:
    :func:`quantize_levels`; the count channel holds ``1[w > 0]``; with no
    levels given every channel is clipped at QMAX).

    ``q = floor(v / scale + u)`` with ``u ~ U[0, 1)`` — unbiased
    (E[q·scale] = v), and EXACT whenever ``v/scale`` is integral, which
    the count channel always is (its scale is its bucket's reciprocal).
    Seeded by ``key``: the same (seed, iteration, class) key reproduces
    the same buckets bitwise, making quantized training run-to-run
    deterministic.  ``stochastic=False`` (LightGBM's
    ``stochastic_rounding=false``) rounds to nearest, ``u = 1/2``."""
    x = vals / scales[:, None]
    u = quantize_draw(key, vals.shape) if stochastic else jnp.float32(0.5)
    # clip: f32 division rounding can land x a hair above the largest bucket
    top = jnp.asarray(levels, jnp.float32)[:, None]
    return jnp.clip(jnp.floor(x + u), -top, top).astype(jnp.int16)


def merge_shard_histograms(
    hist: jnp.ndarray,
    axis_name: str,
    merge: str = "allreduce",
    feature_axis: int = 1,
) -> jnp.ndarray:
    """Cross-shard histogram merge — the one collective of the
    data-parallel learner.

    - ``"allreduce"``: every device receives ALL F features' merged bins
      (the reference's socket allreduce, SURVEY.md §3.1/§5.8 N2).
    - ``"reduce_scatter"``: each device receives the merged histogram of
      only its contiguous ``F/D`` feature slice — LightGBM's data-parallel
      Reduce-Scatter merge (Ke et al., NeurIPS 2017): split finding then
      runs per-slice and a tiny per-leaf winner all-gather elects the
      global best, cutting received bytes per device per pass from
      ``3·F·B`` floats to ``3·F·B/D``.  A ``feature_axis`` the mesh axis
      size does not divide is zero-padded by ``device_psum_scatter``.
    - ``"hierarchical"`` (ISSUE 14, 2D pod mesh): ``axis_name`` is the
      ``(slow, fast)`` axis tuple and the merge psum_scatters over the
      FAST intra-host axis ONLY — each device receives its host's merged
      ``F/d`` feature slice without a single byte crossing the slow
      inter-host axis.  The grower then elects candidates from the
      host-local slices and sends only the tiny winner exchange plus the
      winning columns' exact refinement over the full mesh (engine/tree
      ``_exchange_best`` + the f32 refinement pass), shrinking inter-host
      bytes by ~the feature-shard factor versus a flat merge.

    - ``"allreduce_exact"``: allreduce semantics with a BITWISE
      process-layout-invariant f32 sum (per-axis all_gather + fixed-order
      local reduce, :func:`~mmlspark_tpu.parallel.distributed.device_psum_exact`).
      Costs a host-count wire amplification on the slow axis, so it is
      reserved for the tiny winner-refinement columns whose values are
      recorded in the model (the multihost bitwise-parity gate,
      tools/multihost_smoke.py).

    All delegate to the watchdog-wrapped device collectives in
    :mod:`mmlspark_tpu.parallel.distributed`, so call counts and received
    bytes land in the obs ``collective.*`` ledger (split per axis tier
    under ``collective.axis_bytes``).
    """
    from mmlspark_tpu.parallel.distributed import (
        device_psum,
        device_psum_exact,
        device_psum_scatter,
    )

    if merge == "hierarchical":
        if not isinstance(axis_name, (tuple, list)) or len(axis_name) < 2:
            raise ValueError(
                "hierarchical merge needs the (slow, fast) axis tuple of "
                f"the 2D mesh, got axis_name={axis_name!r}"
            )
        op = functools.partial(
            device_psum_scatter,
            axis_name=axis_name[-1],  # fast intra-host axis only
            scatter_dimension=feature_axis,
            tiled=True,
        )
    elif merge == "reduce_scatter":
        op = functools.partial(
            device_psum_scatter,
            axis_name=axis_name,
            scatter_dimension=feature_axis,
            tiled=True,
        )
    elif merge == "allreduce":
        op = functools.partial(device_psum, axis_name=axis_name)
    elif merge == "allreduce_exact":
        op = functools.partial(device_psum_exact, axis_name=axis_name)
    else:
        raise ValueError(
            f"unknown hist_merge {merge!r}; expected "
            "allreduce|allreduce_exact|reduce_scatter|hierarchical"
        )
    return op(hist)


def merge_shard_histograms_quantized(
    hist: jnp.ndarray,
    axis_name: str,
    merge: str,
    wire: str,
    shift: int,
    feature_axis: int = 1,
) -> jnp.ndarray:
    """Integer-wire histogram merge: the quantized twin of
    :func:`merge_shard_histograms`.

    The wire shift is sized DYNAMICALLY per merge: a scalar ``pmax`` of
    the largest local ``|partial|`` agrees a global bit length, and the
    shift is just what squeezes the D-shard sum under the wire cap.  On
    real data the largest bin magnitude sits far below the static worst
    case ``rows × largest bucket``, so the int16 wire usually ships at shift
    0–3 where the static plan would demand ~7 — enough rounding noise to
    corrupt split selection (the AUC-parity gates in
    ``tests/test_quantize.py`` fail on the static plan at 16k rows).
    ``shift`` (the static ceiling from :func:`quantize_wire_plan`) is
    retained in the plan/cache key; the dynamic shift never exceeds it
    by more than 1 and both independently guarantee wire safety.

    The shift is round-half-up in exact integer arithmetic and the
    reduce is an integer sum, so the merge is associative and the merged
    result is bitwise identical under either strategy.  Wire bytes land
    under ``hist.quantized_bytes`` via the int collective wrappers.
    Returns the merged histogram as f32 WITH the ``2^s`` shift already
    folded back in — the caller only applies the channel scales.
    """
    from mmlspark_tpu.parallel.distributed import (
        device_psum_int,
        device_psum_scatter_int,
    )

    if merge == "reduce_scatter":
        op = functools.partial(
            device_psum_scatter_int,
            axis_name=axis_name,
            scatter_dimension=feature_axis,
            tiled=True,
        )
    elif merge in ("allreduce", "allreduce_exact"):
        # integer sums are associative-exact, so the "exact" variant is
        # the plain integer allreduce — no gather amplification needed
        op = functools.partial(device_psum_int, axis_name=axis_name)
    else:
        # hierarchical quantized merges are rejected up front: the
        # hierarchical grower's election runs on HOST-LOCAL statistics and
        # its refinement pass is already exact f32, so an integer wire
        # underneath would compound two approximations (resolve_auto_config
        # forbids the config combination before training starts).
        raise ValueError(
            f"unknown hist_merge {merge!r}; expected allreduce|reduce_scatter"
        )
    num_shards = int(lax.psum(1, axis_name))
    d_bits = max(num_shards - 1, 0).bit_length()
    cap_bits = 14 if wire == "int16" else 30
    # global max |partial| → bit length → minimal safe shift: every
    # shard's shifted magnitude is ≤ 2^(bl-s) + 1/2, so the D-shard sum
    # stays under 2^(d_bits+bl-s) + D/2 ≤ 2^cap + D/2 — in range for
    # int16 (cap 14) / int32 (cap 30)
    m = lax.pmax(jnp.max(jnp.abs(hist)), axis_name)
    bit_len = jnp.int32(32) - lax.clz(m)
    s = jnp.maximum(bit_len + jnp.int32(d_bits - cap_bits), 0)
    # round-half-up on signed int32 (arithmetic >> floors, so adding
    # half the divisor first rounds); s == 0 adds nothing
    half = jnp.where(s > 0, jnp.left_shift(jnp.int32(1),
                                           jnp.maximum(s - 1, 0)), 0)
    hist = jnp.right_shift(hist + half, s)
    if wire == "int16":
        # headroom: the dynamic shift above sized the D-shard sum under
        # 2^14 + D/2, comfortably inside int16
        hist = hist.astype(jnp.int16)
    merged = op(hist).astype(jnp.float32)
    # exp2 of a small integer is exact in f32 — the shifted-off scale
    # folds back without rounding
    return merged * jnp.exp2(s.astype(jnp.float32))


def _is_bucket(vals_dtype) -> bool:
    """True for integer (quantized bucket) row values: int32 accumulation."""
    return jnp.issubdtype(vals_dtype, jnp.integer)


def _scatter_hist_chunk(bins, vals, num_bins: int, i=0, chunk: Optional[int] = None):
    """(F, n) int bins, (3, n) vals → (3, F, B) of rows ``[i·chunk,
    (i+1)·chunk)`` (all of them with no ``chunk``) via scatter-add: float32
    sums, or int32 sums of int16 bucket ``vals``.  headroom: a chunk is
    rows of one shard, and quantize_wire_plan refuses a fit whose rows ×
    largest bucket, channel by channel, reach 2³¹."""
    bins_c, vals_c = _chunk_slices([(bins, 1), (vals, 1)], i, chunk)
    F, C = bins_c.shape
    acc = jnp.int32 if _is_bucket(vals_c.dtype) else jnp.float32
    idx = bins_c.T.astype(jnp.int32) + jnp.arange(F, dtype=jnp.int32)[None, :] * num_bins
    flat = jax.vmap(
        lambda v: jnp.zeros(F * num_bins, acc).at[idx.reshape(-1)].add(
            jnp.broadcast_to(v.astype(acc)[:, None], (C, F)).reshape(-1)
        )
    )(vals_c)
    return flat.reshape(3, F, num_bins)


def _scatter_hist_by_leaf_chunk(bins, vals, leaf_ids, num_leaves: int, num_bins: int,
                                i=0, chunk: Optional[int] = None):
    """(F, n) bins + (3, n) vals + (n,) leaf ids → (3, L, F, B) of rows
    ``[i·chunk, (i+1)·chunk)`` (all of them with no ``chunk``) scatter-add:
    float32 sums, or int32 sums of int16 bucket ``vals``.  headroom:
    rows × largest bucket stays inside int32 (quantize_wire_plan).

    Rows parked outside ``[0, num_leaves)`` (including NEGATIVE ids from the
    windowed depthwise pass) are routed to a scratch slot and sliced off —
    negative flat indices would otherwise WRAP in ``.at[].add``.
    """
    bins_c, vals_c, leaf_c = _chunk_slices([(bins, 1), (vals, 1), (leaf_ids, 0)], i, chunk)
    F, C = bins_c.shape
    acc = jnp.int32 if _is_bucket(vals_c.dtype) else jnp.float32
    leaf_c = leaf_c.astype(jnp.int32)
    parked = (leaf_c < 0) | (leaf_c >= num_leaves)
    leaf_c = jnp.where(parked, num_leaves, leaf_c)
    base = leaf_c[:, None] * (F * num_bins)
    idx = base + jnp.arange(F, dtype=jnp.int32)[None, :] * num_bins + bins_c.T.astype(jnp.int32)
    flat = jax.vmap(
        lambda v: jnp.zeros((num_leaves + 1) * F * num_bins, acc)
        .at[idx.reshape(-1)]
        .add(jnp.broadcast_to(v.astype(acc)[:, None], (C, F)).reshape(-1))
    )(vals_c)
    return flat.reshape(3, num_leaves + 1, F, num_bins)[:, :num_leaves]


def _chunked_hist(fn, acc0, bins, rows, chunk: int, axis_name, merge: str,
                  quantize: Optional[HistQuantize]):
    """What both builders do with their chunk function ``fn(bins, *rows,
    i=, chunk=)``: sum it over the row chunks of the (F, n) ``bins`` into
    ``acc0``, merge the sum across shards, dequantize.

    ``rows`` holds the per-row arrays in ``fn``'s argument order.  Every
    call is handed all of them whole, and the chunk's index: reaching rows
    ``[i·chunk, (i+1)·chunk)`` is ``fn``'s to do, each backend its own way
    (module text).  The loop itself touches no array but its accumulator.
    """
    n = bins.shape[1]
    if quantize is not None and not _is_bucket(acc0.dtype):
        raise ValueError(
            "quantize needs the int16 buckets of quantize_hist_vals as vals"
        )
    if n <= chunk:
        hist = fn(bins, *rows)
    else:
        if n % chunk != 0:
            raise ValueError(f"row count {n} not a multiple of chunk {chunk}")

        def body(acc, i):
            return acc + fn(bins, *rows, i=i, chunk=chunk), None

        hist, _ = lax.scan(body, acc0, jnp.arange(n // chunk))
    feature_axis = acc0.ndim - 2  # (3, F, B) | (3, L, F, B)
    if axis_name is not None:
        if quantize is not None:
            hist = merge_shard_histograms_quantized(
                hist, axis_name, merge=merge, wire=quantize.wire,
                shift=quantize.shift, feature_axis=feature_axis,
            )
        else:
            hist = merge_shard_histograms(
                hist, axis_name, merge=merge, feature_axis=feature_axis,
            )
    if quantize is not None:
        # dequantize ONCE post-merge (the merge already folded back its
        # dynamic wire shift; serial hists are plain int32 sums)
        hist = hist.astype(jnp.float32) * quantize.scales.reshape(
            (3,) + (1,) * (acc0.ndim - 1)
        )
    return hist


def build_histogram(
    bins: jnp.ndarray,
    vals: jnp.ndarray,
    mask: jnp.ndarray,
    num_bins: int,
    backend: str = "scatter",
    chunk: int = DEFAULT_CHUNK,
    axis_name: Optional[str] = None,
    precision: str = "highest",
    merge: str = "allreduce",
    quantize: Optional[HistQuantize] = None,
) -> jnp.ndarray:
    """Histogram of ``vals`` (3, n) over (feature, bin) of the (F, n)
    ``bins``, rows gated by ``mask``; returns (3, F, B) — or (3, F/D, B),
    this shard's merged feature slice, under ``merge="reduce_scatter"``.

    With ``quantize`` set, ``vals`` must arrive as int16 buckets from
    :func:`quantize_hist_vals`; accumulation is int32, the cross-shard
    merge rides the integer wire, and the returned histogram is
    DEQUANTIZED f32 — downstream gain math is unchanged.

    ``bins`` is the growers' (F, n) integer matrix — uint8 through the
    byte tier (``num_bins ≤ 256``, ``ops/binpack.py``), int32 past it:
    growers hoist the transpose out of their per-pass loop (pallas wants
    rows on the lane axis and widens per VMEM block; the scatter fallback
    transposes back and widens per chunk, it is the small-scale/test
    path).

    On the ``pallas`` backend this is :func:`build_histogram_by_leaf` at
    one slot, the rows in ``mask`` in slot 0 and the others parked at 1:
    the one Pallas body (``ops/pallas_hist.py``) serves both builders.

    When ``axis_name`` is set (running inside ``shard_map`` over row shards),
    the result is ``psum``-med across the mesh axis — this single line is the
    replacement for LightGBM's socket allreduce of histograms
    (``LGBM_NetworkInit`` + recursive-halving allreduce; SURVEY.md §3.1,
    §5.8 native component N2).
    """
    if quantize is None:
        vals = vals.astype(jnp.float32)
    vals = jnp.where(mask[None, :], vals, vals.dtype.type(0))
    if backend == "pallas":
        return build_histogram_by_leaf(
            bins, vals, (~mask).astype(jnp.int32), 1, num_bins, backend, chunk, axis_name, precision, merge, quantize,
        )[:, 0]
    if backend != "scatter":
        raise ValueError(
            f"unknown hist backend {backend!r}; expected scatter|pallas"
        )
    # headroom: bin sums of buckets fit the int32 accumulator while rows ×
    # largest bucket < 2³¹ a shard — guarded statically by quantize_wire_plan
    acc0 = jnp.zeros(
        (3, bins.shape[0], num_bins), jnp.int32 if _is_bucket(vals.dtype) else jnp.float32
    )
    fn = functools.partial(_scatter_hist_chunk, num_bins=num_bins)
    return _chunked_hist(
        fn, acc0, bins, [vals], chunk, axis_name, merge, quantize
    )


def build_histogram_by_leaf(
    bins: jnp.ndarray,
    vals: jnp.ndarray,
    leaf_ids: jnp.ndarray,
    num_leaves: int,
    num_bins: int,
    backend: str = "scatter",
    chunk: int = DEFAULT_CHUNK,
    axis_name: Optional[str] = None,
    precision: str = "highest",
    merge: str = "allreduce",
    quantize: Optional[HistQuantize] = None,
) -> jnp.ndarray:
    """Per-leaf histograms in ONE pass over the (F, n) ``bins``:
    (3, L, F, B) — or (3, L, F/D, B), this shard's merged feature slice,
    under ``merge="reduce_scatter"``.  With ``quantize`` set, ``vals``
    must be int16 buckets; the result is the DEQUANTIZED f32 histogram
    (see :func:`build_histogram`, also for the layout of ``bins``).

    The depthwise grower's workhorse (SURVEY.md §7.4.2): one pass histograms
    every leaf slot in ``[0, num_leaves)`` together.  Rows to exclude
    (out of bag / padding / other leaves — e.g. the windowed new-children
    pass, which passes ``leaf_ids - base``) must arrive with ``leaf_ids``
    outside ``[0, num_leaves)`` (any parked value, including negatives) or
    zeroed ``vals``.  With ``axis_name``, the result is psum-med
    across the mesh — the same single-collective structure as
    :func:`build_histogram`.

    The ``pallas`` backend takes the factorized (hi/lo) body at every
    window and bin count (``ops/pallas_hist.py``
    ``pallas_hist_by_leaf_nibble_chunk``).
    """
    F = bins.shape[0]
    if quantize is None:
        vals = vals.astype(jnp.float32)
    quant = _is_bucket(vals.dtype)
    if backend == "pallas":
        from mmlspark_tpu.ops.pallas_hist import pallas_hist_by_leaf_nibble_chunk

        fn = functools.partial(
            pallas_hist_by_leaf_nibble_chunk,
            num_leaves=num_leaves, num_bins=num_bins, precision=precision,
        )
    elif backend == "scatter":
        fn = functools.partial(
            _scatter_hist_by_leaf_chunk, num_leaves=num_leaves, num_bins=num_bins
        )
    else:
        raise ValueError(
            f"unknown hist backend {backend!r}; expected scatter|pallas"
        )
    # headroom: bin sums of buckets fit the int32 accumulator while rows ×
    # largest bucket < 2³¹ a shard — guarded statically by quantize_wire_plan
    acc0 = jnp.zeros(
        (3, num_leaves, F, num_bins), jnp.int32 if quant else jnp.float32
    )
    return _chunked_hist(
        fn, acc0, bins, [vals, leaf_ids], chunk, axis_name, merge, quantize,
    )


def _scatter_class_roots_chunk(bins, grad, hess, bag, num_bins: int, i=0,
                               chunk: Optional[int] = None):
    """(F, n) bins + (K, n) gradients and hessians + (n,) bag weights →
    (K, 3, F, B) of rows ``[i·chunk, (i+1)·chunk)``: each class's root
    histogram of ``(g·bag, h·bag, 1[bag > 0])``, the scatter-add of
    :func:`_scatter_hist_chunk` class by class."""
    bins_c, grad_c, hess_c, bag_c = _chunk_slices(
        [(bins, 1), (grad, 1), (hess, 1), (bag, 0)], i, chunk
    )
    in_bag = (bag_c > 0).astype(jnp.float32)
    return lax.map(
        lambda gh: _scatter_hist_chunk(
            bins_c, jnp.stack([gh[0] * bag_c, gh[1] * bag_c, in_bag]), num_bins
        ),
        (grad_c, hess_c),
    )


def build_class_root_histograms(
    bins: jnp.ndarray,
    grad: jnp.ndarray,
    hess: jnp.ndarray,
    bag: jnp.ndarray,
    num_bins: int,
    backend: str = "scatter",
    chunk: int = DEFAULT_CHUNK,
    axis_name: Optional[str] = None,
    precision: str = "highest",
    merge: str = "allreduce",
) -> jnp.ndarray:
    """The root histograms of a multiclass iteration's K trees in one go:
    ``(K, 3, F, B)`` — or ``(K, 3, F/D, B)`` under
    ``merge="reduce_scatter"`` — where class ``k``'s is
    :func:`build_histogram_by_leaf` of ``(grad[k]·bag, hess[k]·bag,
    1[bag > 0])`` with every row in leaf 0, sum for sum.

    The ``pallas`` backend puts eight classes in the slots of one pass
    (``ops/pallas_hist.py`` ``pallas_hist_class_roots_chunk``), reading the
    ``(K, n)`` gradients and hessians where they lie: ⌈K/8⌉ passes over
    the ``(F, n)`` ``bins`` where the classes' own roots make K.  Float
    values only; the chunk loop and the merge across shards are
    :func:`build_histogram_by_leaf`'s."""
    K, F = grad.shape[0], bins.shape[0]
    if backend == "pallas":
        from mmlspark_tpu.ops.pallas_hist import pallas_hist_class_roots_chunk

        fn = functools.partial(pallas_hist_class_roots_chunk, num_bins=num_bins, precision=precision)
    elif backend == "scatter":
        fn = functools.partial(_scatter_class_roots_chunk, num_bins=num_bins)
    else:
        raise ValueError(
            f"unknown hist backend {backend!r}; expected scatter|pallas"
        )
    acc0 = jnp.zeros((K, 3, F, num_bins), jnp.float32)
    rows = [grad.astype(jnp.float32), hess.astype(jnp.float32), bag.astype(jnp.float32)]
    return _chunked_hist(fn, acc0, bins, rows, chunk, axis_name, merge, None)
