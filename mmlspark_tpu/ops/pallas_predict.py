"""Pallas TPU kernel for fused forest inference (ISSUE 5 pallas variant).

The lax packed path (:mod:`mmlspark_tpu.engine.forest`) is depth-stepped:
each level is one HBM gather over all (rows × trees) cursors.  This
kernel instead keeps a ROW TILE'S BINS RESIDENT IN VMEM and walks every
tree over that tile **in-register**, accumulating the weighted leaf sum
into a (K, bm) accumulator that only touches HBM once per tile — the
FIL-style "block per row batch" shape, reformulated for the TPU:

- bins arrive transposed (F, n) int32 so a block is (F, bm) with rows on
  the 128-lane axis; one DMA per tile, every split of every tree then
  reads its feature row via a SCALAR dynamic slice
  (``bins_ref[pl.ds(f, 1), :]``) — vector gathers don't lower on TPU, so
  the kernel replays the grower's split list (leaf-id relabelling)
  instead of chasing node pointers;
- per-tree split metadata (feat/threshold/split-leaf/default-left,
  (TT, S) int32) and weights live in **SMEM** — scalars steering control
  flow and slice offsets, the blessed Pallas TPU pattern;
- leaf values (TT, L) f32 sit in VMEM; the per-row leaf value is a
  one-hot (L, bm) contraction on the MXU at HIGHEST precision — exact
  f32 (products are v·1 and v·0), with one documented caveat: a leaf
  value of **-0.0** comes out as +0.0 (the +0·v terms of the sum are
  +0.0 and (+0.0) + (-0.0) = +0.0).  This only perturbs raw scores when
  an accumulator is itself ±0.0 at that tree — the parity suite pins the
  behaviour;
- the class accumulation uses ``jnp.where(iota_k == k, acc + w·v, acc)``
  NOT additive masking (adding a masked 0 column would flip -0.0 the
  same way), so per class the f32 add sequence is exactly the scan
  path's serial ``acc + w·v`` in tree order — bitwise parity.

Numeric splits only: categorical membership tables are (S, B) bool per
tree and blow the SMEM budget; forests with cat splits resolve to the
lax packed path (the documented fallback + parity oracle).  Backends:
TPU compiled, CPU via the interpreter (tests/parity); anything else
raises — same contract as :mod:`mmlspark_tpu.ops.pallas_hist`.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


# SMEM is 1 MiB per core on v5e and the compiler keeps ~1 KiB for itself
# (XLA:TPU's words at the edge: "Used 1.00M of 1.00M smem. Exceeded smem
# capacity by 1.1K").  A 2-D 32-bit SMEM array is padded to (8, 128)
# words, so a (TT, S) table costs far more than TT·S entries — the
# budget is on the PADDED bytes of every scalar table together.  Above
# it the resolver falls back to the lax packed path.
SMEM_BUDGET_BYTES = (1 << 20) - 16 * 1024


def _smem_table_bytes(rows: int, cols: int) -> int:
    """Bytes one (rows, cols) 32-bit table occupies in SMEM."""
    return _round_up(max(rows, 1), 8) * _round_up(max(cols, 1), 128) * 4


class PallasForest(NamedTuple):
    """Device arrays + statics for the replay kernel (host-built once)."""

    feat: jnp.ndarray    # (TT, S) int32
    thr: jnp.ndarray     # (TT, S) int32
    sleaf: jnp.ndarray   # (TT, S) int32 (-1 = inactive step)
    dleft: jnp.ndarray   # (TT, S) int32 (0/1)
    weight: jnp.ndarray  # (TT, 1) float32 (per-iteration weight, expanded)
    leafv: jnp.ndarray   # (TT, Lp) float32 (L padded to a lane multiple)
    num_trees: int       # T
    num_class: int       # K
    num_steps: int       # S
    num_leaves: int      # Lp
    nbytes: int


def pallas_supported(num_trees: int, num_class: int, num_steps: int,
                     has_cats: bool) -> bool:
    """Can this forest run on the kernel?  (numeric-only + SMEM budget:
    four (TT, S) split tables and the (TT, 1) weights)"""
    tt = num_trees * num_class
    smem = 4 * _smem_table_bytes(tt, num_steps) + _smem_table_bytes(tt, 1)
    return (not has_cats) and smem <= SMEM_BUDGET_BYTES


def build_pallas_forest(host_trees, tree_weights, T: int) -> PallasForest:
    """Flatten (T, K, ...) replay arrays into the kernel's (TT, ...) SMEM/
    VMEM layout.  Trees are t-major, k-minor (idx = t·K + k) so the
    per-class add order matches the scan path exactly."""
    sl = np.asarray(host_trees.split_leaf)[:T]   # (T, K, S)
    T_, K, S = sl.shape
    lv = np.asarray(host_trees.leaf_value)[:T]   # (T, K, L)
    L = lv.shape[-1]
    Lp = _round_up(max(L, 1), 128)
    leafv = np.zeros((T * K, Lp), np.float32)
    leafv[:, :L] = lv.reshape(T * K, L)
    w = np.repeat(np.asarray(tree_weights[:T], np.float32), K)[:, None]
    arrays = dict(
        feat=np.asarray(host_trees.split_feat)[:T].reshape(T * K, S).astype(np.int32),
        thr=np.asarray(host_trees.split_bin)[:T].reshape(T * K, S).astype(np.int32),
        sleaf=sl.reshape(T * K, S).astype(np.int32),
        dleft=np.asarray(host_trees.default_left)[:T].reshape(T * K, S).astype(np.int32),
        weight=w,
        leafv=leafv,
    )
    nbytes = sum(a.nbytes for a in arrays.values())
    return PallasForest(
        **{k: jnp.asarray(v) for k, v in arrays.items()},
        num_trees=T, num_class=K, num_steps=S, num_leaves=Lp, nbytes=nbytes,
    )


def _predict_kernel(bins_ref, leafv_ref, feat_ref, thr_ref, sleaf_ref,
                    dleft_ref, w_ref, out_ref, *, TT: int, K: int, S: int,
                    L: int, num_bins: int):
    """One row tile: replay all TT trees over the resident (F, bm) bins."""
    bm = bins_ref.shape[1]
    iota_k = lax.broadcasted_iota(jnp.int32, (K, bm), 0)
    iota_l = lax.broadcasted_iota(jnp.int32, (L, bm), 0)

    def tree_body(idx, acc):
        def step_body(s, leaf):
            f = feat_ref[idx, s]
            sleaf = sleaf_ref[idx, s]
            thr = thr_ref[idx, s]
            dl = dleft_ref[idx, s]
            fcol = bins_ref[pl.ds(f, 1), :]          # (1, bm) int32
            # direction as an int32 select: Mosaic has no select over
            # bool vectors ("Unsupported target bitwidth for truncation"
            # i8 → i1 on v5e), so the missing-bin default (scalar 1 - dl)
            # and the threshold compare meet as 0/1 integers
            go_right = jnp.where(
                fcol == num_bins - 1, 1 - dl, (fcol > thr).astype(jnp.int32)
            )
            # rows sitting in the split leaf that go right take the new
            # leaf id s+1 (LightGBM leaf relabelling); inactive steps
            # have sleaf == -1 and never match
            move = (leaf == sleaf) & (go_right == 1)
            return jnp.where(move, s + 1, leaf)

        leaf = lax.fori_loop(0, S, step_body, jnp.zeros((1, bm), jnp.int32))
        one_hot = (iota_l == leaf).astype(jnp.float32)   # (L, bm)
        lv = leafv_ref[pl.ds(idx, 1), :]                 # (1, L)
        val = lax.dot_general(
            lv, one_hot,
            dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=lax.Precision.HIGHEST,
        )                                                # (1, bm)
        contrib = w_ref[idx, 0] * val
        k = idx % K
        # where (not additive masking): preserves the scan path's exact
        # per-class f32 add sequence incl. signed zeros
        return jnp.where(iota_k == k, acc + contrib, acc)

    out_ref[...] = lax.fori_loop(
        0, TT, tree_body, jnp.zeros((K, bm), jnp.float32)
    )


@functools.partial(jax.jit, static_argnames=(
    "TT", "K", "S", "L", "num_bins", "bm", "interpret"))
def _pallas_predict(bins_t, leafv, feat, thr, sleaf, dleft, weight, *,
                    TT: int, K: int, S: int, L: int, num_bins: int,
                    bm: int, interpret: bool):
    F, n = bins_t.shape
    kernel = functools.partial(
        _predict_kernel, TT=TT, K=K, S=S, L=L, num_bins=num_bins
    )
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    return pl.pallas_call(
        kernel,
        grid=(n // bm,),
        in_specs=[
            pl.BlockSpec((F, bm), lambda i: (0, i)),   # bins tile (VMEM)
            pl.BlockSpec(memory_space=pltpu.VMEM),     # leaf values
            smem, smem, smem, smem, smem,              # scalar metadata
        ],
        out_specs=pl.BlockSpec((K, bm), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((K, n), jnp.float32),
        interpret=interpret,
    )(bins_t, leafv, feat, thr, sleaf, dleft, weight)


def pallas_raw_scores(pf: PallasForest, bins, num_bins: int,
                      bm: int = 2048, interpret: bool = False) -> jnp.ndarray:
    """(n, F) binned matrix → (K, n) raw scores, bitwise-equal to the scan
    path (modulo the documented -0.0 leaf-value caveat)."""
    backend = jax.default_backend()
    if backend not in ("cpu", "tpu"):
        raise NotImplementedError(
            f"predict_backend='pallas' supports tpu (compiled) and cpu "
            f"(interpret) backends, not {backend!r}; use 'packed'"
        )
    n, F = bins.shape
    bins_t = bins.astype(jnp.int32).T            # (F, n): rows on lanes
    bm = min(bm, _round_up(max(n, 1), 128))
    pad_r = (-n) % bm
    pad_f = (-F) % 8                             # int32 sublane multiple
    if pad_r or pad_f:
        bins_t = jnp.pad(bins_t, ((0, pad_f), (0, pad_r)))
    out = _pallas_predict(
        bins_t, pf.leafv, pf.feat, pf.thr, pf.sleaf, pf.dleft, pf.weight,
        TT=pf.num_trees * pf.num_class, K=pf.num_class, S=pf.num_steps,
        L=pf.num_leaves, num_bins=num_bins, bm=bm,
        interpret=interpret or backend == "cpu",
    )
    return out[:, :n]


# ---------------------------------------------------------------------------
# Multi-model co-resident kernel (ISSUE 13): one launch, mixed batch
# ---------------------------------------------------------------------------
class MultiPallasForest(NamedTuple):
    """N models' replay tables concatenated tree-major plus the SMEM
    model-offset table: per tree row its model id, class slot, and the
    model's missing-bin sentinel.  One launch replays the whole fleet
    over a mixed tile; per-row masking keeps foreign trees inert."""

    feat: jnp.ndarray    # (TTtot, S) int32
    thr: jnp.ndarray     # (TTtot, S) int32
    sleaf: jnp.ndarray   # (TTtot, S) int32 (-1 = inactive step)
    dleft: jnp.ndarray   # (TTtot, S) int32
    weight: jnp.ndarray  # (TTtot, 1) float32
    tmid: jnp.ndarray    # (TTtot, 1) int32 — tree row -> model id
    tcls: jnp.ndarray    # (TTtot, 1) int32 — tree row -> class slot
    tnb: jnp.ndarray     # (TTtot, 1) int32 — tree row -> model num_bins
    leafv: jnp.ndarray   # (TTtot, Lp) float32
    num_models: int
    total_trees: int     # TTtot = sum of T_m * K_m
    num_class: int       # Kmax
    num_steps: int       # Smax
    num_leaves: int      # Lp
    nbytes: int


def multi_pallas_supported(parts) -> bool:
    """``parts`` = per-model (T, K, S, has_cats) tuples; the concatenated
    tables — four (TTtot, Smax) split tables and four (TTtot, 1) columns
    (weight, model id, class slot, bin count) — must fit the same SMEM
    budget as the standalone kernel."""
    if any(p[3] for p in parts):
        return False
    s_max = max((p[2] for p in parts), default=0)
    tt_tot = sum(p[0] * p[1] for p in parts)
    smem = 4 * _smem_table_bytes(tt_tot, s_max) + 4 * _smem_table_bytes(tt_tot, 1)
    return smem <= SMEM_BUDGET_BYTES


def build_multi_pallas_forest(models) -> MultiPallasForest:
    """``models`` = list of (host_trees, tree_weights, T, num_bins) per
    model, concatenated model-major / tree-major / class-minor so each
    model's per-class add order matches its standalone scan exactly."""
    per = []
    for host_trees, tree_weights, T, num_bins in models:
        sl = np.asarray(host_trees.split_leaf)[:T]      # (T, K, S)
        _, K, S = sl.shape
        lv = np.asarray(host_trees.leaf_value)[:T]      # (T, K, L)
        w = np.repeat(np.asarray(tree_weights[:T], np.float32), K)[:, None]
        per.append(dict(
            feat=np.asarray(host_trees.split_feat)[:T].reshape(T * K, S),
            thr=np.asarray(host_trees.split_bin)[:T].reshape(T * K, S),
            sleaf=sl.reshape(T * K, S),
            dleft=np.asarray(host_trees.default_left)[:T].reshape(T * K, S),
            weight=w, leafv=lv.reshape(T * K, lv.shape[-1]),
            K=K, S=S, num_bins=num_bins,
        ))
    S = max(p["S"] for p in per)
    L = max(p["leafv"].shape[1] for p in per)
    Lp = _round_up(max(L, 1), 128)
    Kmax = max(p["K"] for p in per)
    tt_tot = sum(p["feat"].shape[0] for p in per)

    def pad_steps(a, fill):
        out = np.full((a.shape[0], S), fill, np.int32)
        out[:, : a.shape[1]] = a
        return out

    feat = np.concatenate([pad_steps(p["feat"], 0) for p in per])
    thr = np.concatenate([pad_steps(p["thr"], 0) for p in per])
    sleaf = np.concatenate([pad_steps(p["sleaf"], -1) for p in per])
    dleft = np.concatenate([pad_steps(p["dleft"], 0) for p in per])
    weight = np.concatenate([p["weight"] for p in per]).astype(np.float32)
    leafv = np.zeros((tt_tot, Lp), np.float32)
    row = 0
    tmid = np.zeros((tt_tot, 1), np.int32)
    tcls = np.zeros((tt_tot, 1), np.int32)
    tnb = np.zeros((tt_tot, 1), np.int32)
    for m, p in enumerate(per):
        tt_m = p["feat"].shape[0]
        leafv[row: row + tt_m, : p["leafv"].shape[1]] = p["leafv"]
        tmid[row: row + tt_m, 0] = m
        tcls[row: row + tt_m, 0] = np.arange(tt_m, dtype=np.int32) % p["K"]
        tnb[row: row + tt_m, 0] = p["num_bins"]
        row += tt_m
    arrays = dict(feat=feat, thr=thr, sleaf=sleaf, dleft=dleft,
                  weight=weight, tmid=tmid, tcls=tcls, tnb=tnb, leafv=leafv)
    nbytes = sum(a.nbytes for a in arrays.values())
    return MultiPallasForest(
        **{k: jnp.asarray(v) for k, v in arrays.items()},
        num_models=len(per), total_trees=tt_tot, num_class=Kmax,
        num_steps=S, num_leaves=Lp, nbytes=nbytes,
    )


def _multi_predict_kernel(bins_ref, mid_ref, leafv_ref, feat_ref, thr_ref,
                          sleaf_ref, dleft_ref, w_ref, tmid_ref, tcls_ref,
                          tnb_ref, out_ref, *, TT: int, K: int, S: int,
                          L: int):
    """One mixed row tile: replay ALL models' trees; a tree's contribution
    lands only on rows whose model-id matches its SMEM offset entry."""
    bm = bins_ref.shape[1]
    iota_k = lax.broadcasted_iota(jnp.int32, (K, bm), 0)
    iota_l = lax.broadcasted_iota(jnp.int32, (L, bm), 0)
    mids = mid_ref[pl.ds(0, 1), :]                   # (1, bm) int32

    def tree_body(idx, acc):
        nb = tnb_ref[idx, 0]

        def step_body(s, leaf):
            f = feat_ref[idx, s]
            sleaf = sleaf_ref[idx, s]
            thr = thr_ref[idx, s]
            dl = dleft_ref[idx, s]
            fcol = bins_ref[pl.ds(f, 1), :]          # (1, bm) int32
            # int32 select, not a bool one (see _predict_kernel)
            go_right = jnp.where(
                fcol == nb - 1, 1 - dl, (fcol > thr).astype(jnp.int32)
            )
            move = (leaf == sleaf) & (go_right == 1)
            return jnp.where(move, s + 1, leaf)

        leaf = lax.fori_loop(0, S, step_body, jnp.zeros((1, bm), jnp.int32))
        one_hot = (iota_l == leaf).astype(jnp.float32)
        lv = leafv_ref[pl.ds(idx, 1), :]
        val = lax.dot_general(
            lv, one_hot,
            dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=lax.Precision.HIGHEST,
        )
        contrib = w_ref[idx, 0] * val
        sel = (iota_k == tcls_ref[idx, 0]) & (mids == tmid_ref[idx, 0])
        return jnp.where(sel, acc + contrib, acc)

    out_ref[...] = lax.fori_loop(
        0, TT, tree_body, jnp.zeros((K, bm), jnp.float32)
    )


@functools.partial(jax.jit, static_argnames=(
    "TT", "K", "S", "L", "bm", "interpret"))
def _multi_pallas_predict(bins_t, mid_row, leafv, feat, thr, sleaf, dleft,
                          weight, tmid, tcls, tnb, *, TT: int, K: int,
                          S: int, L: int, bm: int, interpret: bool):
    F, n = bins_t.shape
    kernel = functools.partial(
        _multi_predict_kernel, TT=TT, K=K, S=S, L=L
    )
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    return pl.pallas_call(
        kernel,
        grid=(n // bm,),
        in_specs=[
            pl.BlockSpec((F, bm), lambda i: (0, i)),   # bins tile (VMEM)
            pl.BlockSpec((1, bm), lambda i: (0, i)),   # row model ids
            pl.BlockSpec(memory_space=pltpu.VMEM),     # leaf values
            smem, smem, smem, smem, smem, smem, smem, smem,
        ],
        out_specs=pl.BlockSpec((K, bm), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((K, n), jnp.float32),
        interpret=interpret,
    )(bins_t, mid_row, leafv, feat, thr, sleaf, dleft, weight, tmid, tcls,
      tnb)


def multi_pallas_raw_scores(mpf: MultiPallasForest, bins, mid,
                            bm: int = 2048,
                            interpret: bool = False) -> jnp.ndarray:
    """(n, F) mixed binned matrix + (n,) model ids → (Kmax, n) raw
    scores; per model bitwise-equal to its standalone kernel output."""
    backend = jax.default_backend()
    if backend not in ("cpu", "tpu"):
        raise NotImplementedError(
            f"multi-model pallas predict supports tpu (compiled) and cpu "
            f"(interpret) backends, not {backend!r}; use 'packed'"
        )
    n, F = bins.shape
    bins_t = bins.astype(jnp.int32).T
    mid_row = mid.astype(jnp.int32)[None, :]         # (1, n)
    bm = min(bm, _round_up(max(n, 1), 128))
    pad_r = (-n) % bm
    pad_f = (-F) % 8
    if pad_r or pad_f:
        bins_t = jnp.pad(bins_t, ((0, pad_f), (0, pad_r)))
    if pad_r:
        # pad rows carry model id -1: no tree matches, they stay zero
        mid_row = jnp.pad(mid_row, ((0, 0), (0, pad_r)),
                          constant_values=-1)
    out = _multi_pallas_predict(
        bins_t, mid_row, mpf.leafv, mpf.feat, mpf.thr, mpf.sleaf,
        mpf.dleft, mpf.weight, mpf.tmid, mpf.tcls, mpf.tnb,
        TT=mpf.total_trees, K=mpf.num_class, S=mpf.num_steps,
        L=mpf.num_leaves, bm=bm, interpret=interpret or backend == "cpu",
    )
    return out[:, :n]
