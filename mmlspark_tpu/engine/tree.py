"""Leaf-wise (best-first) tree growth as a single jitted program.

Reference behavior being reproduced: LightGBM's ``SerialTreeLearner`` /
``DataParallelTreeLearner`` leaf-wise growth (upstream C++
``src/treelearner/serial_tree_learner.cpp`` — [REF-EMPTY]; surfaced in the
reference through ``LGBM_BoosterUpdateOneIter``, SURVEY.md §3.1 hot loop).

TPU-first redesign (SURVEY.md §7.4.1 "Leaf-wise growth under XLA static
shapes"):

- The tree is a **fixed-size array program**: ``max_leaves-1`` split steps
  run in a ``lax.fori_loop``; a ``stopped`` flag masks steps after growth
  ends, so shapes never depend on data.
- Row→leaf assignment is a dense ``leaf_ids`` vector updated in place —
  leaf-id recompute instead of LightGBM's index-array data partitions
  (gather-free; SURVEY.md §7.4.1 "prefer leaf-id recompute").
- Split bookkeeping uses the histogram-subtraction trick: a new right
  child's histogram is built by one pass; the left child's is the
  parent's minus the right's (same trick LightGBM uses).
- Under ``shard_map`` (``axis_name`` set), histograms are ``psum``-med, so
  every shard computes the identical argmax split — the decision path is
  replicated, only the row data is sharded.  This is byte-for-byte the
  "data_parallel" tree learner semantics of the reference
  (SURVEY.md §2 parallelism table).
- Categorical features split by membership sets found with LightGBM's
  sorted-by-gradient-statistic scan (SURVEY.md §7.4.5; upstream
  ``FindBestThresholdCategorical``): categories sorted by
  ``Σgrad/(Σhess+cat_smooth)``, best prefix (both directions) under
  ``max_cat_threshold``, regularized by ``cat_l2``.

Leaf numbering: the root is leaf 0; the split at step ``s`` keeps the left
child in the parent's slot and assigns the right child id ``s+1``.  This is
exactly LightGBM's numbering, which makes the exported model string's
``split_feature``/``leaf_value`` ordering match.
"""

from __future__ import annotations

import contextlib
import dataclasses
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from mmlspark_tpu.ops.binpack import hist_transpose
from mmlspark_tpu.ops.histogram import (
    DEFAULT_LEVELS,
    HistQuantize,
    build_class_root_histograms,
    build_histogram,
    build_histogram_by_leaf,
    factorized_by_leaf,
    quantize_hist_vals,
    quantize_scales3,
)


@dataclasses.dataclass(frozen=True)
class GrowConfig:
    """Static (trace-time) knobs of the grower.

    Field names follow LightGBM config names (the reference's ``TrainParams``
    flattens SparkML params into this vocabulary — SURVEY.md §5.6).
    """

    num_bins: int  # total bins incl. missing bin (= BinMapper.num_bins)
    num_leaves: int = 31
    max_depth: int = -1
    min_data_in_leaf: int = 20
    min_sum_hessian_in_leaf: float = 1e-3
    lambda_l1: float = 0.0
    lambda_l2: float = 0.0
    min_gain_to_split: float = 0.0
    learning_rate: float = 0.1
    hist_backend: str = "scatter"
    hist_chunk: int = 16_384
    # "highest": f32 matmuls (scatter-add-exact numerics).  "default": bf16
    # multiplies with f32 accumulation — ~4x MXU throughput; the one-hot
    # operand is exact in bf16, the grad/hess operand rounds to 8 mantissa
    # bits before accumulation (LightGBM's own histograms are f32 sums of
    # f32 — validate AUC before enabling on a new workload).
    hist_precision: str = "highest"
    axis_name: Optional[str] = None  # set under shard_map for psum
    # Cross-shard histogram merge of the data-parallel learner (depthwise/
    # windowed grower only).  "allreduce": every device receives ALL F
    # features' merged bins per pass (the reference's socket allreduce).
    # "reduce_scatter": each device receives the merged histogram for only
    # its contiguous F/D feature slice (LightGBM's data-parallel
    # Reduce-Scatter merge — Ke et al. NeurIPS 2017), finds best splits
    # for those features locally, and a per-leaf all-gather of (gain,
    # feature, threshold, flags) candidates elects the global best on
    # every shard identically — F·B·3/D received floats per device per
    # pass instead of F·B·3, at the cost of a tiny (D, 5, L) exchange.
    # Requires F to be a multiple of the mesh axis size (the booster
    # right-pads columns and masks the pads out of every candidate
    # search).  Ignored under voting/feature-parallel, which never
    # allreduce full histograms in the first place.
    # "hierarchical" (ISSUE 14): 2D pod-mesh merge — ``axis_name`` is the
    # (slow, fast) tuple, the windowed merge psum_scatters over the FAST
    # intra-host axis only (host-local feature slices), candidates are
    # elected from the host-local statistics, and every pass's winners get
    # the exact f32 refinement re-accumulation over the FULL mesh — so
    # only the (D, 5, L) winner exchange and the winning columns'
    # (3, W, 1, B) refinement cross the slow inter-host axis.  Split
    # SELECTION is host-biased (like voting's local vote) but recorded
    # thresholds/gains/memberships are globally exact.
    hist_merge: str = "allreduce"
    # The fast intra-host axis of the 2D mesh; set (with the tuple
    # ``axis_name``) only under hist_merge="hierarchical".
    feature_axis_name: Optional[str] = None
    grow_policy: str = "lossguide"  # lossguide (LightGBM-exact) | depthwise
    # Categorical membership splits (LightGBM's sorted-category algorithm —
    # SURVEY.md §7.4.5; defaults are LightGBM's cat_smooth/cat_l2/
    # max_cat_threshold).  Static tuple: tracing specializes on it, so the
    # all-numeric case pays zero overhead.
    categorical_features: Tuple[int, ...] = ()
    cat_smooth: float = 10.0
    cat_l2: float = 10.0
    max_cat_threshold: int = 32
    # Static cap on the categorical scan's value-bin axis: the max used
    # bins over the categorical features (from the BinMapper), 0 = B-1.
    # Bins past every cat feature's cardinality are provably unused, so
    # capping shrinks the sorts + prefix contraction with zero effect.
    cat_value_bins: int = 0
    # Voting-parallel (SURVEY.md §2 parallelism table; LightGBM
    # tree_learner=voting): workers keep LOCAL histograms, vote their
    # top_k features per leaf by local gain, and only the globally
    # top-(2·top_k)-voted features' histograms are psum-med for the exact
    # split decision — the bandwidth-reduced data-parallel mode.  Only
    # meaningful under shard_map (axis_name set); depthwise grower only.
    voting: bool = False
    top_k: int = 20
    # Feature-parallel (SURVEY.md §2 parallelism table; LightGBM
    # tree_learner=feature): COLUMNS are sharded across the mesh axis and
    # rows are replicated.  Each shard builds histograms and candidates for
    # only its feature block (no histogram allreduce at all); per-leaf
    # local winners are all-gathered (a few scalars per leaf), every shard
    # elects the identical global winner, and the OWNING shard broadcasts
    # the per-row left/right partition via one psum — exactly LightGBM's
    # "communicate best split, winner broadcasts the row partition"
    # structure.  Same split decisions as serial up to float-summation
    # order: histogramming a narrow column block accumulates in a different
    # order than the full-width build, so gains match only to ulps and a
    # near-tied split can resolve differently (LightGBM's distributed
    # learners have the same property vs its serial learner).  Windowed
    # grower only; numeric features only (a static per-shard categorical
    # set cannot exist in one SPMD program).
    feature_parallel: bool = False
    # k-batched best-first growth (TPU-first generalization): at most
    # ``split_batch`` splits are applied per histogram pass, selected
    # best-first by gain over ALL current leaves.  0 = a full level's worth
    # (the depthwise default); 1 = one split per pass, which reproduces the
    # lossguide grower's split sequence exactly (same argmax ordering)
    # while paying ONE windowed data pass per split instead of the
    # all-rows masked pass of :func:`grow_tree`.  Intermediate k trades a
    # small policy delay (the k-th split is chosen before the first k-1
    # splits' children are scored) for k-fold fewer passes.
    split_batch: int = 0
    # Quantized histogram training (ISSUE 9; LightGBM quantized training,
    # NeurIPS 2022).  "off": the f32 path, bitwise-identical to before the
    # feature existed (all quantize logic is statically gated on this
    # field).  "int16"/"int32": per-row grad/hess quantize to int16
    # buckets with per-iteration max-abs scales + seeded stochastic
    # rounding, histograms accumulate int32, and the cross-shard merge
    # rides an integer wire of this dtype.  Split selection runs on the
    # dequantized totals; each pass's WINNERS get an exact f32
    # refinement re-accumulation, and final leaf values are always
    # computed from raw f32 grad/hess.  resolve_auto_config validates
    # the value ("on" → "int16") and rejects voting/feature-parallel
    # learners before a GrowConfig is ever built.
    hist_quantize: str = "off"
    # Static pre-wire right-shift from ops.histogram.quantize_wire_plan
    # (0 when the worst-case global bin total already fits the wire).
    quantize_shift: int = 0
    # Largest bucket of (gradient, hessian, count), from
    # ops.histogram.quantize_levels(num_grad_quant_bins), and whether the
    # rounding draws (LightGBM's stochastic_rounding) or rounds to nearest.
    quantize_levels: Tuple[int, int, int] = DEFAULT_LEVELS
    quantize_stochastic: bool = True
    # A backend switch for the windowed grower's final per-leaf stats
    # (_leaf_totals): the chunked one-hot contraction, what the booster
    # sets on a TPU, or the scatter-add, which sums in row order and so
    # keeps XLA:CPU's results the same under every process layout.
    onehot_stats: bool = True

    @property
    def num_value_bins(self) -> int:
        return self.num_bins - 1  # last bin is the missing bin

    @property
    def max_steps(self) -> int:
        return self.num_leaves - 1

    @property
    def has_categoricals(self) -> bool:
        return len(self.categorical_features) > 0

    @property
    def voting_active(self) -> bool:
        return self.voting and self.axis_name is not None

    @property
    def feature_parallel_active(self) -> bool:
        return self.feature_parallel and self.axis_name is not None

    @property
    def quantize_active(self) -> bool:
        return self.hist_quantize != "off"

    @property
    def reduce_scatter_active(self) -> bool:
        """Reduce-scatter histogram merging engages only for the plain
        data-parallel learner: voting psums elected slices and
        feature-parallel never merges histograms at all."""
        return (
            self.hist_merge == "reduce_scatter"
            and self.axis_name is not None
            and not self.voting
            and not self.feature_parallel
        )

    @property
    def hierarchical_active(self) -> bool:
        """2D-mesh hierarchical merge (ISSUE 14): ``axis_name`` carries the
        (slow, fast) tuple and ``feature_axis_name`` the fast axis."""
        return (
            self.hist_merge == "hierarchical"
            and self.axis_name is not None
            and self.feature_axis_name is not None
            and not self.voting
            and not self.feature_parallel
        )

    @property
    def refine_active(self) -> bool:
        """The f32 winner-refinement pass: always on for quantized training
        (re-scores quantized winners exactly) and under the hierarchical
        merge (host-local election needs exact global thresholds/gains)."""
        return self.quantize_active or self.hierarchical_active

    @property
    def feature_shard_axis(self):
        """The axis features are sliced over: the fast axis under the
        hierarchical merge, the whole (1-D) mesh axis otherwise."""
        return (
            self.feature_axis_name if self.hierarchical_active
            else self.axis_name
        )

    @property
    def level_window(self) -> int:
        """Static width of the per-pass new-children window (depthwise).

        A pass's split count is bounded by min(current leaves, remaining
        budget) ≤ ⌈num_leaves/2⌉ — if half the budget is already leaves,
        the remaining budget is under half — and the selection logic
        additionally caps the per-pass budget at W itself, so any W ≥ the
        rounded need below fits every pass's new right children.  With
        ``split_batch`` set, the per-pass split count (hence the window) is
        capped at the batch size instead.
        """
        need = max(1, (self.num_leaves + 1) // 2)
        if self.split_batch > 0:
            need = min(need, self.split_batch)
        # Round to a sublane-friendly multiple of 4, not a power of two:
        # the by-leaf kernel's matmul M is 3·W, so a k=12 batch at W=12
        # (M=36) does 25% less work than the old W=16 (M=48).  Tiny
        # windows stay exact — rounding 1→4 would 4x the k=1 (exact
        # lossguide) pass.
        return need if need <= 4 else ((need + 3) // 4) * 4


class Tree(NamedTuple):
    """One grown tree as flat arrays (S = num_leaves-1, L = num_leaves).

    ``cat_threshold[s]`` is the bin-membership mask of categorical split
    ``s`` (bins in the set go LEFT; the missing bin is never a member, so
    missing/unseen categories go right — LightGBM's categorical rule).
    """

    split_leaf: jnp.ndarray  # (S,) int32; leaf id split at step s; -1 = no-op
    split_feat: jnp.ndarray  # (S,) int32
    split_bin: jnp.ndarray  # (S,) int32; bins <= split_bin go left
    default_left: jnp.ndarray  # (S,) bool; missing-bin direction
    split_cat: jnp.ndarray  # (S,) bool; membership (categorical) split?
    cat_threshold: jnp.ndarray  # (S, B) bool; member bins (go left)
    split_gain: jnp.ndarray  # (S,) float32
    leaf_value: jnp.ndarray  # (L,) float32 (includes learning-rate shrinkage)
    leaf_count: jnp.ndarray  # (L,) float32 (bagged row counts)
    num_leaves: jnp.ndarray  # () int32


def _l1_threshold(G, l1):
    return jnp.sign(G) * jnp.maximum(jnp.abs(G) - l1, 0.0)


def _leaf_score(G, H, l1, l2):
    Gt = _l1_threshold(G, l1)
    return (Gt * Gt) / (H + l2 + 1e-15)


def _leaf_output(G, H, l1, l2, lr):
    return -_l1_threshold(G, l1) / (H + l2 + 1e-15) * lr


def _numeric_candidates(cfg: GrowConfig, hists, leaf_stats, feat_mask):
    """Best numeric (threshold, missing-dir) candidate per (leaf, feature).

    hists: (3, L, F, B) channel-major (Σgrad, Σhess, Σcount) — the bin axis
    stays MINOR throughout so every intermediate tiles lane-efficiently (a
    trailing (2, 3) axis pair wasted ~97% of each 8×128 vector tile and
    traced at ~10ms/level).
    Returns (gain (L,F), bin (L,F), default_left (L,F)).
    """
    _, L, F, B = hists.shape
    VB = B - 1
    cumG = jnp.cumsum(hists[0, :, :, :VB], axis=-1)  # (L, F, VB)
    cumH = jnp.cumsum(hists[1, :, :, :VB], axis=-1)
    cumC = jnp.cumsum(hists[2, :, :, :VB], axis=-1)
    missG = hists[0, :, :, B - 1]  # (L, F)
    missH = hists[1, :, :, B - 1]
    missC = hists[2, :, :, B - 1]
    totG = leaf_stats[0][:, None, None]  # (L, 1, 1)
    totH = leaf_stats[1][:, None, None]
    totC = leaf_stats[2][:, None, None]
    # feat_mask may be (F,) shared or (L, F) per-leaf (voting-parallel).
    fm2 = jnp.broadcast_to(feat_mask, (L, F))
    parent = _leaf_score(leaf_stats[0], leaf_stats[1], cfg.lambda_l1, cfg.lambda_l2)

    def direction(dleft):
        # dir 0: missing goes right; dir 1: missing goes left.
        if dleft:
            Gl = cumG + missG[:, :, None]
            Hl = cumH + missH[:, :, None]
            Cl = cumC + missC[:, :, None]
        else:
            Gl, Hl, Cl = cumG, cumH, cumC
        Gr, Hr, Cr = totG - Gl, totH - Hl, totC - Cl
        gain = (
            _leaf_score(Gl, Hl, cfg.lambda_l1, cfg.lambda_l2)
            + _leaf_score(Gr, Hr, cfg.lambda_l1, cfg.lambda_l2)
            - parent[:, None, None]
        )
        valid = (
            (Cl >= cfg.min_data_in_leaf)
            & (Cr >= cfg.min_data_in_leaf)
            & (Hl >= cfg.min_sum_hessian_in_leaf)
            & (Hr >= cfg.min_sum_hessian_in_leaf)
        )
        valid &= fm2[..., None]
        gain = jnp.where(valid, gain, -jnp.inf)  # (L, F, VB)
        t = jnp.argmax(gain, axis=-1)  # (L, F)
        return jnp.take_along_axis(gain, t[..., None], axis=-1)[..., 0], t

    gain0, t0 = direction(False)
    gain1, t1 = direction(True)
    use1 = gain1 > gain0
    return (
        jnp.maximum(gain0, gain1),
        jnp.where(use1, t1, t0).astype(jnp.int32),
        use1,
    )


def _cat_sort_key(cfg: GrowConfig, hist_vb):
    """Ascending sort key over value bins for the categorical scan.

    hist_vb: (3, ..., VB) channel-major.  Unused bins (count 0) key to
    +inf so they sort to the end; the DESCENDING direction is derived
    from the same order as used-block suffixes (no second sort).
    """
    G, H, C = hist_vb[0], hist_vb[1], hist_vb[2]
    used = C > 0
    ratio = G / (H + cfg.cat_smooth)
    return jnp.where(used, ratio, jnp.inf), used


def _cat_candidates(cfg: GrowConfig, hists, leaf_stats, feat_mask):
    """Best categorical membership split per (leaf, feature).

    LightGBM's sorted-category algorithm: sort used bins by
    Σgrad/(Σhess+cat_smooth), scan set-prefixes of both sort directions
    (≤ max_cat_threshold categories in the set), gain regularized by
    lambda_l2 + cat_l2.  ONE ascending argsort serves both directions:
    unused bins park at the end, so the used block is a contiguous prefix
    [0, nuse) of the order and a descending prefix of size p is exactly
    the used-block SUFFIX [nuse-p, nuse) — its sums come from the same
    cumsum (total − shifted prefix) with no second sort.  Returns
    (gain (L,F), k (L,F) prefix-length-1 in the chosen direction,
    descending (L,F) bool).  One-vs-rest small-cardinality mode
    (max_cat_to_onehot) is subsumed by the k=0 prefix candidate.
    """
    _, L, F, B = hists.shape
    # Value-bin axis capped at the max CATEGORICAL cardinality (static,
    # from the BinMapper): bins past it are provably unused for every cat
    # feature (count 0 → sorted last, never in a proper-subset prefix), so
    # the sorts + rank-mask contraction shrink exactly (255 → ~card_max).
    VB = B - 1
    if 0 < cfg.cat_value_bins < VB:
        VB = cfg.cat_value_bins
    hist_vb = hists[:, :, :, :VB]  # (3, L, F, VB)
    # (feat_mask may be (F,) shared or (L, F) per-leaf — see numeric)
    l2 = cfg.lambda_l2 + cfg.cat_l2
    parent = _leaf_score(leaf_stats[0], leaf_stats[1], cfg.lambda_l1, l2)

    key, used = _cat_sort_key(cfg, hist_vb)
    order = jnp.argsort(key, axis=-1)  # (L, F, VB): used block first
    rank = jnp.argsort(order, axis=-1)  # rank of each value bin
    # Sorted-prefix sums WITHOUT the take_along_axis gather + cumsum (both
    # slow TPU lowerings — the gather+cumsum chain was ~0.7s of the 2.5s
    # catmix bench): cum[..., k] = Σ_v hist[..., v]·[rank[v] ≤ k] is ONE
    # MXU contraction against the rank mask.  Precision follows
    # cfg.hist_precision like the histogram kernels: "highest" runs the
    # f32 dot exactly; "default" uses the hi/lo bf16 split (the factorized
    # pallas-kernel idiom) — le is exact 0/1 in bf16, the hist splits into
    # bf16 high + residual for ~2^-16 relative accuracy on the sums.
    le = rank[..., :, None] <= jnp.arange(VB, dtype=rank.dtype)[None, :]

    if cfg.hist_precision == "default":
        le_b = le.astype(jnp.bfloat16)

        def _mm(x):
            return jnp.einsum(
                "clfv,lfvk->clfk", x, le_b,
                preferred_element_type=jnp.float32,
            )

        hi = hist_vb.astype(jnp.bfloat16)
        lo = (hist_vb - hi.astype(jnp.float32)).astype(jnp.bfloat16)
        cum = _mm(hi) + _mm(lo)  # prefix k+1 sums at index k
    else:
        cum = jnp.einsum(
            "clfv,lfvk->clfk", hist_vb, le.astype(jnp.float32),
            precision=jax.lax.Precision.HIGHEST,
        )
    nuse = used.sum(axis=-1)[..., None]  # (L, F, 1)
    k = jnp.arange(VB)[None, None, :]
    fm = jnp.broadcast_to(feat_mask, (L, F))[..., None]

    def best_of(Gl, Hl, Cl, size_l, extra_valid):
        Gr = leaf_stats[0][:, None, None] - Gl
        Hr = leaf_stats[1][:, None, None] - Hl
        Cr = leaf_stats[2][:, None, None] - Cl
        gain = (
            _leaf_score(Gl, Hl, cfg.lambda_l1, l2)
            + _leaf_score(Gr, Hr, cfg.lambda_l1, l2)
            - parent[:, None, None]
        )
        valid = (
            extra_valid
            & (size_l <= cfg.max_cat_threshold)
            & (size_l < nuse)  # proper subset of used bins
            & (size_l >= 1)
            & (Cl >= cfg.min_data_in_leaf)
            & (Cr >= cfg.min_data_in_leaf)
            & (Hl >= cfg.min_sum_hessian_in_leaf)
            & (Hr >= cfg.min_sum_hessian_in_leaf)
            & fm
        )
        gain = jnp.where(valid, gain, -jnp.inf)
        best = jnp.argmax(gain, axis=-1)  # (L, F)
        return (
            jnp.take_along_axis(gain, best[..., None], axis=-1)[..., 0],
            best.astype(jnp.int32),
        )

    # ascending: set = order[0..k], size k+1
    g_asc, k_asc = best_of(
        cum[0], cum[1], cum[2], k + 1, jnp.ones((L, F, VB), bool)
    )
    # descending: set = order[s..nuse), size nuse-s, sums = used-total
    # minus the prefix BEFORE s (shifted cumsum; zero at s=0)
    total_vb = hist_vb.sum(axis=-1)  # (3, L, F) — used bins only (rest 0)
    cumsh = jnp.pad(cum[..., :-1], [(0, 0)] * 3 + [(1, 0)])
    size_d = nuse - k  # set size at start index s=k
    g_desc, s_desc = best_of(
        total_vb[0][..., None] - cumsh[0],
        total_vb[1][..., None] - cumsh[1],
        total_vb[2][..., None] - cumsh[2],
        size_d,
        k >= 1,  # s=0 would be the full used set (not a proper subset)
    )
    use_desc = g_desc > g_asc
    # desc representation: prefix-length-1 in the (derived) descending
    # order = set size - 1 = nuse - s - 1
    k_desc = (nuse[..., 0] - s_desc - 1).astype(jnp.int32)
    return (
        jnp.maximum(g_asc, g_desc),
        jnp.where(use_desc, k_desc, k_asc),
        use_desc,
    )


def _cat_members(cfg: GrowConfig, hist_cb, k_len, descending):
    """Membership mask for a chosen categorical split.

    hist_cb: (3, ..., B) channel-major histogram of the chosen
    (leaf, feature); k_len: prefix length - 1 in the chosen direction;
    descending: direction flag.  Recomputes the identical (stable)
    ascending argsort used by :func:`_cat_candidates` and derives the
    descending rank as ``nuse - 1 - rank`` (used bins only), so the set
    is exactly the winning prefix — deterministic under psum-replicated
    histograms, hence identical on every shard.  Returns (..., B) bool
    (missing bin never a member → missing goes right).
    """
    B = hist_cb.shape[-1]
    VB = B - 1
    if 0 < cfg.cat_value_bins < VB:
        VB = cfg.cat_value_bins  # same static cap as _cat_candidates
    descending = jnp.asarray(descending)
    key, used = _cat_sort_key(cfg, hist_cb[..., :VB])
    order = jnp.argsort(key, axis=-1)
    rank = jnp.argsort(order, axis=-1)
    nuse = used.sum(axis=-1, keepdims=True)
    rank_eff = jnp.where(descending[..., None], nuse - 1 - rank, rank)
    members = (rank_eff <= jnp.asarray(k_len)[..., None]) & used
    pad = [(0, 0)] * (members.ndim - 1) + [(0, B - VB)]
    return jnp.pad(members, pad)  # bins past the cap + missing: False


def _member_lookup(members, col, B: int):
    """``members[col]`` without the gather lowering.

    An (n,)-indexed gather from a (B,)-bool table lowers to ~2.4ms at
    262k rows on v5e; bit-packing the mask into ≤⌈B/32⌉ uint32 words and
    selecting by word index is a handful of n-sized elementwise ops
    (~0.1ms).  ``members``: (B,) bool; ``col``: (n,) int bins."""
    from mmlspark_tpu.ops.pallas_route import member_words

    words = member_words(members)  # (nw,)
    wsel = jnp.zeros_like(col, dtype=jnp.uint32)
    for j in range(words.shape[0]):
        wsel = jnp.where(col >> 5 == j, words[j], wsel)
    return ((wsel >> (col & 31).astype(jnp.uint32)) & 1) > 0


class PassSplits(NamedTuple):
    """One windowed pass's splits, by leaf: what moves its rows."""

    feat: jnp.ndarray  # (L,) int32 split column
    thr: jnp.ndarray  # (L,) int32; bins <= thr go left
    dleft: jnp.ndarray  # (L,) bool; the missing bin goes left
    is_cat: jnp.ndarray  # (L,) bool; membership split
    selected: jnp.ndarray  # (L,) bool; the leaf splits this pass
    new_id: jnp.ndarray  # (L,) int32; its right child
    members: Optional[jnp.ndarray]  # (L, B) bool bins that go left; None with no categoricals


def route_in_block(cfg: GrowConfig, bins_t) -> bool:
    """Whether the windowed grower moves its rows by the one-pass Pallas
    body (:func:`_route_block`) rather than the slot loop: on the Pallas
    backend, over the byte tier's uint8 ``(F, n)`` matrix, up to
    ``ops/pallas_route.MAX_COLS`` columns, where reading a whole row block
    costs less than W one-column passes."""
    from mmlspark_tpu.ops.pallas_route import MAX_COLS

    return cfg.hist_backend == "pallas" and bins_t.dtype == jnp.uint8 and bins_t.shape[0] <= MAX_COLS


def _route_slots(bins_t, leaf_ids, slot_leaves, sp: PassSplits, B: int):
    """``leaf_ids`` after the pass: the slot loop.  Reads the ≤W split
    columns with W dynamic slices and resolves rows against their leaf's
    slot with n-sized selects, where a per-row gather of each row's
    split-column bin out of the (F, n) matrix (a dynamic cross-sublane
    lookup) cost ~2.7ms/pass at the bench shape.  XLA makes one fusion a
    slot over every row.  A moved row's new id is ≥ base > every
    splittable leaf id, so later slots can never re-match it."""
    n = bins_t.shape[1]
    for w in range(slot_leaves.shape[0]):
        l_w = slot_leaves[w]
        col = lax.dynamic_slice(bins_t, (sp.feat[l_w], jnp.int32(0)), (1, n))[0]
        gl_w = jnp.where(col == (B - 1), sp.dleft[l_w], col <= sp.thr[l_w])
        if sp.members is not None:
            memb_w = lax.dynamic_slice(sp.members, (l_w, 0), (1, B))[0]
            gl_w = jnp.where(sp.is_cat[l_w], _member_lookup(memb_w, col, B), gl_w)
        moves_w = (leaf_ids == l_w) & sp.selected[l_w] & ~gl_w
        leaf_ids = jnp.where(moves_w, sp.new_id[l_w], leaf_ids)
    return leaf_ids


def _route_block(bins_t, leaf_ids, slot_leaves, sp: PassSplits, B: int, **blocks):
    """What :func:`_route_slots` returns, to the bit, from one read of each
    row block (``ops/pallas_route.py``; ``blocks``: its ``route_rows``
    block sizes): the slots' splits packed into one small table a pass."""
    from mmlspark_tpu.ops.pallas_route import route_rows, slot_table

    W = slot_leaves.shape[0]
    take = lambda x: x[slot_leaves]  # noqa: E731
    if sp.members is None:
        is_cat, members = jnp.zeros(W, bool), jnp.zeros((W, B), bool)
    else:
        is_cat, members = take(sp.is_cat), take(sp.members)
    table = slot_table(
        slot_leaves, take(sp.feat), take(sp.thr), take(sp.dleft), is_cat,
        jnp.where(take(sp.selected), take(sp.new_id), slot_leaves), members,
    )
    return route_rows(bins_t, leaf_ids, table, **blocks)


def _cat_feat_mask(cfg: GrowConfig, F: int) -> np.ndarray:
    m = np.zeros(F, bool)
    for f in cfg.categorical_features:
        if 0 <= f < F:
            m[f] = True
    return m


@jax.named_scope("split_scan")
def _candidate_matrix(cfg: GrowConfig, hists, leaf_stats, feat_mask):
    """Best candidate per (leaf, feature): (gain, t, d) each (L, F).

    For numeric features ``t`` is the threshold bin and ``d`` the
    missing-left flag; for categorical features ``t`` is the sorted-prefix
    length - 1 and ``d`` the sort direction.  hists is channel-major
    (3, L, F, B); feat_mask is (F,) or per-leaf (L, F).
    """
    _, L, F, B = hists.shape
    gain, t, d = _numeric_candidates(cfg, hists, leaf_stats, feat_mask)
    if cfg.has_categoricals:
        # Run the sorted-category scan over ONLY the static categorical
        # column subset, then scatter back — running it over all F and
        # masking wasted ~F/n_cat of the sort work.
        cat_idx = jnp.asarray(cfg.categorical_features, dtype=jnp.int32)
        hists_cat = jnp.take(hists, cat_idx, axis=2)  # (3, L, nc, B)
        fm = jnp.broadcast_to(feat_mask, (L, F))
        cgain, ck, cdesc = _cat_candidates(
            cfg, hists_cat, leaf_stats, jnp.take(fm, cat_idx, axis=1)
        )
        gain = gain.at[:, cat_idx].set(cgain)
        t = t.at[:, cat_idx].set(ck)
        d = d.at[:, cat_idx].set(cdesc)
    return gain, t, d


def _refine_candidates(cfg: GrowConfig, ref_hist, ref_stats, is_cat_w):
    """Re-score already-CHOSEN (leaf, feature) winners on exact f32 columns
    (ISSUE 9 quantized training's refinement pass).

    ref_hist: (3, W, 1, B) float32 winner-column histograms, one slot per
    refined split; ref_stats: (3, W) exact per-slot totals; is_cat_w: (W,)
    winner-is-categorical flags.  Runs the identical numeric/sorted-category
    candidate math the quantized pass ran — same tie-breaks — but on exact
    operands, so the recorded threshold/direction/gain carry no
    quantization error.  Returns (gain, t, d) each (W,); a slot whose exact
    re-score finds NO valid candidate (quantization flipped a
    min-hessian-type constraint) returns gain=-inf and the caller keeps the
    quantized decision.
    """
    W = ref_hist.shape[1]
    ones = jnp.ones((W, 1), bool)
    g, t, d = _numeric_candidates(cfg, ref_hist, ref_stats, ones)
    gain, t, d = g[:, 0], t[:, 0], d[:, 0]
    if cfg.has_categoricals:
        cg, ck, cdesc = _cat_candidates(cfg, ref_hist, ref_stats, ones)
        gain = jnp.where(is_cat_w, cg[:, 0], gain)
        t = jnp.where(is_cat_w, ck[:, 0], t)
        d = jnp.where(is_cat_w, cdesc[:, 0], d)
    return gain, t, d


def _reduce_candidates(cfg: GrowConfig, gain_m, t_m, d_m):
    """(L, F) candidate matrices → per-leaf best (gain, f, t, d, is_cat)."""
    L, F = gain_m.shape
    f = jnp.argmax(gain_m, axis=1).astype(jnp.int32)  # (L,)
    take = lambda a: jnp.take_along_axis(a, f[:, None], axis=1)[:, 0]  # noqa: E731
    if cfg.has_categoricals:
        is_cat = jnp.asarray(_cat_feat_mask(cfg, F))[f]
    else:
        is_cat = jnp.zeros(L, bool)
    return take(gain_m), f, take(t_m), take(d_m), is_cat


def _leaf_candidates(cfg: GrowConfig, hists, leaf_stats, feat_mask):
    """Best candidate PER LEAF over all features (numeric + categorical).

    Returns per-leaf (gain (L,), feat, t, d, is_cat); leaves with no valid
    candidate get gain=-inf.  hists is channel-major (3, L, F, B).
    """
    gain, t, d = _candidate_matrix(cfg, hists, leaf_stats, feat_mask)
    return _reduce_candidates(cfg, gain, t, d)


def _voting_leaf_candidates(cfg: GrowConfig, hists_local, leaf_stats_local, feat_mask):
    """Per-leaf best split under voting-parallel (LightGBM
    ``tree_learner=voting`` — SURVEY.md §2 parallelism table, §5.8).

    Two rounds per level instead of a full-histogram allreduce:

    1. VOTE — every shard scores candidates on its LOCAL histograms and
       votes its ``top_k`` features per leaf; votes are psum-med and the
       top ``2·top_k``-voted features per leaf are elected (ties broken by
       feature index — deterministic, so every shard elects identically).
    2. EXACT — only the elected features' histogram slices are psum-med
       (``(3, L, 2k, B)`` instead of ``(3, L, F, B)``), and the final
       split decision is computed exactly on those global histograms with
       globally-summed leaf stats.

    Returns (gain (L,), f, t, d, is_cat, hists_sel (3,L,2k,B), sel (L,2k),
    j (L,)) — the elected-histogram block and per-leaf winner column are
    returned so categorical membership sets can be built from GLOBAL
    statistics.
    """
    _, L, F, B = hists_local.shape
    k = min(cfg.top_k, F)
    k2 = min(2 * k, F)

    # Round 1: local candidate gains → per-leaf top-k feature votes.
    vgain, _, _ = _candidate_matrix(cfg, hists_local, leaf_stats_local, feat_mask)
    _, topi = jax.lax.top_k(vgain, k)  # (L, k)
    votes = jnp.zeros((L, F), jnp.float32).at[
        jnp.arange(L)[:, None], topi
    ].add(1.0)
    votes = lax.psum(votes, cfg.axis_name)
    _, sel = jax.lax.top_k(votes, k2)  # (L, k2); stable → replicated

    # Round 2: psum only the elected features' histograms.
    hists_sel = jnp.take_along_axis(
        hists_local, sel[None, :, :, None], axis=2
    )  # (3, L, k2, B)
    hists_sel = lax.psum(hists_sel, cfg.axis_name)  # analyze: ignore[COL004]
    leaf_stats = lax.psum(leaf_stats_local, cfg.axis_name)

    fm = jnp.broadcast_to(feat_mask, (L, F))
    fm_sel = jnp.take_along_axis(fm, sel, axis=1)  # (L, k2)
    gain_s, t_s, d_s = _numeric_candidates(cfg, hists_sel, leaf_stats, fm_sel)
    if cfg.has_categoricals:
        cmask = jnp.asarray(_cat_feat_mask(cfg, F))
        cmask_sel = cmask[sel]  # (L, k2) — dynamic election: no static subset
        cgain, ck, cdesc = _cat_candidates(cfg, hists_sel, leaf_stats, fm_sel)
        gain_s = jnp.where(cmask_sel, cgain, gain_s)
        t_s = jnp.where(cmask_sel, ck, t_s)
        d_s = jnp.where(cmask_sel, cdesc, d_s)
    j = jnp.argmax(gain_s, axis=1).astype(jnp.int32)  # (L,) winner column
    take = lambda a: jnp.take_along_axis(a, j[:, None], axis=1)[:, 0]  # noqa: E731
    f = take(sel).astype(jnp.int32)
    if cfg.has_categoricals:
        is_cat = jnp.asarray(_cat_feat_mask(cfg, F))[f]
    else:
        is_cat = jnp.zeros(L, bool)
    return take(gain_s), f, take(t_s), take(d_s), is_cat, hists_sel, sel, j


def _local_cat_mask(cfg: GrowConfig, F_local: int):
    """Runtime (F_local,) categorical mask of THIS shard's column block
    (feature-parallel column shards and reduce-scatter feature slices are
    both contiguous ascending blocks of ``F_local`` global columns).

    ``cfg.categorical_features`` holds GLOBAL column indices, but one SPMD
    program cannot specialize statically per shard — so the mask is
    computed from ``lax.axis_index`` at run time: local column j is global
    ``shard·F_local + j``, compared against the static set (a handful of
    traced equality ops, no extra operand threading).  Under the
    hierarchical merge the slicing axis is the FAST one (feature blocks
    repeat identically on every host).
    """
    shard = lax.axis_index(cfg.feature_shard_axis)
    gids = shard * F_local + jnp.arange(F_local, dtype=jnp.int32)
    m = jnp.zeros(F_local, bool)
    for c in cfg.categorical_features:
        m = m | (gids == c)
    return m


@jax.named_scope("split_scan")
def _local_candidate_matrix(cfg: GrowConfig, hists, leaf_stats, feat_mask, cmask):
    """(L, F_local) candidate matrices over a LOCAL column block with a
    RUNTIME categorical mask: numeric and sorted-category candidates are
    both computed for every local column and selected per column by
    ``cmask`` (the voting path's dynamic-election technique) — a static
    per-shard column subset cannot exist inside one SPMD program, so
    :func:`_candidate_matrix`'s static take/scatter-back is unusable here.
    """
    gain, t, d = _numeric_candidates(cfg, hists, leaf_stats, feat_mask)
    if cfg.has_categoricals:
        cgain, ck, cdesc = _cat_candidates(cfg, hists, leaf_stats, feat_mask)
        gain = jnp.where(cmask[None, :], cgain, gain)
        t = jnp.where(cmask[None, :], ck, t)
        d = jnp.where(cmask[None, :], cdesc, d)
    return gain, t, d


def _reduce_local_candidates(gain_m, t_m, d_m, cmask):
    """(L, F_local) candidate matrices → per-leaf best, with ``is_cat``
    from the RUNTIME column mask (the static :func:`_reduce_candidates`
    lookup indexes global columns and is wrong for local blocks)."""
    f = jnp.argmax(gain_m, axis=1).astype(jnp.int32)  # (L,) LOCAL index
    take = lambda a: jnp.take_along_axis(a, f[:, None], axis=1)[:, 0]  # noqa: E731
    return take(gain_m), f, take(t_m), take(d_m), cmask[f]


def _fp_leaf_candidates(cfg: GrowConfig, hists, leaf_stats, feat_mask, cmask):
    """Per-leaf best over a feature-parallel LOCAL block (runtime
    categorical mask) — :func:`_local_candidate_matrix` + local reduce."""
    gain, t, d = _local_candidate_matrix(cfg, hists, leaf_stats, feat_mask, cmask)
    return _reduce_local_candidates(gain, t, d, cmask)


def _exchange_best(cfg: GrowConfig, gain_l, f_l, t_l, d_l, ic_l, F_block):
    """Per-leaf winner exchange for the feature-sharded modes
    (feature-parallel column shards, reduce-scatter feature slices).

    All-gathers each shard's per-leaf best (5 scalars per leaf) and
    argmaxes across shards — every shard elects the identical global
    winner from the identical gathered matrix.  Ties pick the lowest
    shard (argmax-first), whose within-shard winner is its lowest local
    index — together the lowest GLOBAL feature index, identical to the
    serial argmax tie-break (both column layouts are contiguous ascending
    blocks of ``F_block`` columns per shard).

    Returns (gain, f_global, t, dleft, is_cat, own, f_local): ``own``
    marks the leaves whose winning feature lives on THIS shard and
    ``f_local`` is its local column there (clipped garbage elsewhere).

    Under the hierarchical merge (2D mesh) the gather spans the FULL
    flattened mesh — every (host, feature-slice) cell proposes its best
    from host-local statistics and the highest gain anywhere wins (the
    ISSUE 14 hierarchical election: this (D, 5, L) exchange is the only
    per-pass collective crossing the slow axis besides the winners'
    refinement columns).  Global feature ids come from the FEATURE-axis
    index (feature slices repeat across hosts), while ``own`` keys on the
    flattened cell index so exactly one device owns each winner.
    """
    from mmlspark_tpu.parallel.distributed import device_all_gather

    ax = cfg.axis_name
    if cfg.hierarchical_active:
        f_shard = lax.axis_index(cfg.feature_axis_name)
        # flattened cell index: gather order is axis-tuple major-to-minor
        shard = lax.axis_index(ax[0]) * lax.psum(1, ax[1]) + f_shard
    else:
        f_shard = shard = lax.axis_index(ax)
    cand = jnp.stack([
        gain_l,
        (f_l + f_shard * F_block).astype(jnp.float32),  # global feature id
        t_l.astype(jnp.float32),
        d_l.astype(jnp.float32),
        ic_l.astype(jnp.float32),
    ])  # (5, L)
    allc = device_all_gather(cand, ax)  # (D, 5, L)
    win_shard = jnp.argmax(allc[:, 0, :], axis=0)  # (L,)

    def take_s(c):
        return jnp.take_along_axis(allc[:, c, :], win_shard[None], axis=0)[0]

    gain = take_s(0)
    f = take_s(1).astype(jnp.int32)  # GLOBAL index (for the record)
    t = take_s(2).astype(jnp.int32)
    dleft = take_s(3) > 0.5
    is_cat = take_s(4) > 0.5
    own = win_shard == shard  # (L,) leaf's winner lives here
    f_local = jnp.clip(f - f_shard * F_block, 0, F_block - 1)
    return gain, f, t, dleft, is_cat, own, f_local


def _best_split(cfg: GrowConfig, hists, leaf_stats, leaf_depth, num_leaves, feat_mask):
    """Global best split over all leaves (lossguide step)."""
    L = hists.shape[1]
    gain, f, t, d, is_cat = _leaf_candidates(cfg, hists, leaf_stats, feat_mask)
    leaf_ok = jnp.arange(L) < num_leaves
    if cfg.max_depth > 0:
        leaf_ok &= leaf_depth < cfg.max_depth
    gain = jnp.where(leaf_ok, gain, -jnp.inf)
    l = jnp.argmax(gain).astype(jnp.int32)
    return gain[l], l, f[l], t[l], d[l], is_cat[l]


def _empty_tree(S: int, L: int, B: int) -> Tree:
    return Tree(
        split_leaf=jnp.full(S, -1, jnp.int32),
        split_feat=jnp.zeros(S, jnp.int32),
        split_bin=jnp.zeros(S, jnp.int32),
        default_left=jnp.zeros(S, bool),
        split_cat=jnp.zeros(S, bool),
        cat_threshold=jnp.zeros((S, B), bool),
        split_gain=jnp.zeros(S, jnp.float32),
        leaf_value=jnp.zeros(L, jnp.float32),
        leaf_count=jnp.zeros(L, jnp.float32),
        num_leaves=jnp.asarray(1, jnp.int32),
    )


# Rows one step of the one-hot leaf totals takes: its (L, c) float32
# operand is 64 MiB at 63 leaves whatever the fit's rows.
_LEAF_TOTALS_CHUNK = 1 << 18


def _leaf_totals(vals: jnp.ndarray, leaf_ids: jnp.ndarray, L: int,
                 onehot: bool) -> jnp.ndarray:
    """Per-leaf float32 sums (3, L) of ``vals`` (3, n); a row whose id lies
    outside [0, L) is dropped.

    ``onehot`` is ``GrowConfig.onehot_stats``.  Set, the sum is the
    contraction ``vals · 1[leaf_ids = l]`` at ``Precision.HIGHEST`` over
    row chunks of ``_LEAF_TOTALS_CHUNK`` (0.15ns a row on v5e at 25M
    rows, against 7.5 for the scatter-add, whose (n, 3) operand the
    compiler pads to 512 bytes a row); a ragged tail is padded with the
    id ``L``.  Unset, the scatter-add, which accumulates in row order.
    """
    if not onehot:
        return jax.vmap(
            lambda v: jnp.zeros(L, jnp.float32).at[leaf_ids].add(
                v, mode="drop"
            )
        )(vals)

    def part(v, ids):
        leaf_oh = (
            ids[None, :] == jnp.arange(L, dtype=jnp.int32)[:, None]
        ).astype(jnp.float32)  # (L, c)
        return lax.dot_general(
            v, leaf_oh, dimension_numbers=(((1,), (1,)), ((), ())),
            precision=lax.Precision.HIGHEST,
        )

    n, c = leaf_ids.shape[0], _LEAF_TOTALS_CHUNK
    if n <= c:
        return part(vals, leaf_ids)
    pad = -n % c
    if pad:
        vals = jnp.pad(vals, ((0, 0), (0, pad)))
        leaf_ids = jnp.pad(leaf_ids, (0, pad), constant_values=L)

    def body(acc, i):
        return acc + part(
            lax.dynamic_slice_in_dim(vals, i * c, c, axis=1),
            lax.dynamic_slice_in_dim(leaf_ids, i * c, c),
        ), None

    return lax.scan(
        body, jnp.zeros((3, L), jnp.float32), jnp.arange((n + pad) // c)
    )[0]


def _quantize_rows(cfg: GrowConfig, vals, qkey, qscale):
    """The quantized growers' row values: ``vals`` (3, n) rounded ONCE a
    tree to int16 buckets at the configuration's levels (the booster
    computes the per-iteration max-abs scales over the GLOBAL batch
    pre-shard), and the plan the builders dequantize by.  They accumulate
    int32 and dequantize right after the merge, so everything downstream of
    a histogram stays f32 and unchanged.  ``(vals, None)`` with
    quantization off."""
    if not cfg.quantize_active:
        return vals, None
    with jax.named_scope("quant_round"):
        scales3 = quantize_scales3(qscale, cfg.quantize_levels)
        if cfg.axis_name is not None:
            # decorrelate the SR draws across shards: with one key every
            # shard would reuse the SAME uniform pattern, correlating
            # rounding errors across shards instead of letting them cancel
            qkey = jax.random.fold_in(qkey, lax.axis_index(cfg.axis_name))
        qvals = quantize_hist_vals(
            vals, scales3, qkey, cfg.quantize_levels, cfg.quantize_stochastic
        )
    return qvals, HistQuantize(cfg.hist_quantize, cfg.quantize_shift, scales3)


def _hist_scope(cfg: GrowConfig):
    """The named scope of a grower's histogram builds: ``quant_hist`` for
    bucket builds, ``hist_build`` for float ones."""
    return jax.named_scope("quant_hist" if cfg.quantize_active else "hist_build")


def _refine_scope(cfg: GrowConfig):
    """``quant_refine`` around quantized training's float32 refinement pass
    (the hierarchical merge's has no scope of its own)."""
    return (
        jax.named_scope("quant_refine") if cfg.quantize_active
        else contextlib.nullcontext()
    )


def grow_tree(
    cfg: GrowConfig,
    bins: jnp.ndarray,  # (n, F) integer bins (uint8/int32)
    grad: jnp.ndarray,  # (n,)
    hess: jnp.ndarray,  # (n,)
    bag_weight: jnp.ndarray,  # (n,) float; 0 = out of bag, GOSS amplification
    feat_mask: jnp.ndarray,  # (F,) bool; feature_fraction sampling
    qkey: Optional[jnp.ndarray] = None,  # PRNG key (stochastic rounding)
    qscale: Optional[jnp.ndarray] = None,  # (2,) grad/hess quantize scales
) -> Tuple[Tree, jnp.ndarray]:
    """Grow one tree (lossguide, one split per step); returns the tree and
    the final per-row leaf ids.

    Jit-safe and shard_map-safe: with ``cfg.axis_name`` set, ``bins``/rows are
    the local shard and all histogram sums are globally reduced.
    """
    n, F = bins.shape
    B, L, S = cfg.num_bins, cfg.num_leaves, cfg.max_steps
    # One transpose per tree (histogram passes want rows on the lane
    # axis); the dtype stays uint8 through the byte tier (B ≤ 256) — the
    # kernels widen per block — so the tree-resident working set is 1
    # byte/index instead of 4 (ops/binpack.py::hist_transpose).
    bins_t = hist_transpose(bins, B)
    in_bag = (bag_weight > 0).astype(jnp.float32)
    vals = jnp.stack(
        [grad * bag_weight, hess * bag_weight, in_bag], axis=0
    ).astype(jnp.float32)  # (3, n) channel-major
    qvals, hq = _quantize_rows(cfg, vals, qkey, qscale)

    def hist(mask):
        with _hist_scope(cfg):
            return build_histogram(
                bins_t, qvals, mask, B,
                backend=cfg.hist_backend, chunk=cfg.hist_chunk, axis_name=cfg.axis_name,
                precision=cfg.hist_precision,
                quantize=hq,
            )

    root_hist = hist(jnp.ones(n, bool))  # (3, F, B)
    hists = jnp.zeros((3, L, F, B), jnp.float32).at[:, 0].set(root_hist)
    # Every feature's bins partition all rows, so feature 0's bin-sum is the
    # leaf total.
    leaf_stats = jnp.zeros((3, L), jnp.float32).at[:, 0].set(
        root_hist[:, 0, :].sum(axis=-1)
    )
    leaf_ids = jnp.zeros(n, jnp.int32)
    leaf_depth = jnp.zeros(L, jnp.int32)
    tree0 = _empty_tree(S, L, B)

    def step(s, carry):
        leaf_ids, hists, leaf_stats, leaf_depth, tree, stopped = carry
        gain, l, f, t, dleft, is_cat = _best_split(
            cfg, hists, leaf_stats, leaf_depth, tree.num_leaves, feat_mask
        )
        if cfg.quantize_active:
            with _refine_scope(cfg):
                # f32 winner refinement (ISSUE 9): quantized histograms picked
                # the winner; its ONE column is re-accumulated exactly and
                # re-scored, so the recorded threshold/gain — and the
                # membership set below — carry no quantization error.  A tiny
                # (3, 1, B) allreduce vs the full quantized pass.
                wcol = lax.dynamic_index_in_dim(bins_t, f, axis=0, keepdims=True)
                ref = build_histogram(
                    wcol, vals, leaf_ids == l, B,
                    backend=cfg.hist_backend, chunk=cfg.hist_chunk,
                    axis_name=cfg.axis_name, precision=cfg.hist_precision,
                    merge="allreduce_exact",  # recorded gains: layout-invariant
                )[:, None]  # (3, 1, 1, B)
                ref_col = ref[:, 0, 0]  # (3, B) exact winner column
                ref_stats = ref_col.sum(axis=-1)[:, None]  # (3, 1)
                rg, rt, rd = _refine_candidates(cfg, ref, ref_stats, is_cat[None])
                ok = rg[0] > -jnp.inf
                gain = jnp.where(ok, rg[0], gain)
                t = jnp.where(ok, rt[0], t)
                dleft = jnp.where(ok, rd[0], dleft)
        do = (gain > cfg.min_gain_to_split) & ~stopped

        fcol = lax.dynamic_index_in_dim(bins_t, f, axis=0, keepdims=False)
        is_missing = fcol == (B - 1)
        goes_left = jnp.where(is_missing, dleft, fcol <= t)
        if cfg.has_categoricals:
            hist_lf = ref_col if cfg.quantize_active else hists[:, l, f]
            members = _cat_members(cfg, hist_lf, t, dleft)  # (B,)
            goes_left = jnp.where(
                is_cat, _member_lookup(members, fcol, B), goes_left
            )
        else:
            members = jnp.zeros(B, bool)
        new_id = s + 1
        move = do & (leaf_ids == l) & ~goes_left
        leaf_ids = jnp.where(move, new_id, leaf_ids)

        right_hist = hist(leaf_ids == new_id)  # zeros when not do (no rows moved)
        dof = do.astype(jnp.float32)
        hists = hists.at[:, new_id].set(right_hist * dof)
        hists = hists.at[:, l].add(-right_hist * dof)
        right_total = right_hist[:, 0, :].sum(axis=-1)
        leaf_stats = leaf_stats.at[:, new_id].set(right_total * dof)
        leaf_stats = leaf_stats.at[:, l].add(-right_total * dof)
        child_depth = leaf_depth[l] + 1
        leaf_depth = leaf_depth.at[new_id].set(jnp.where(do, child_depth, 0))
        leaf_depth = leaf_depth.at[l].set(jnp.where(do, child_depth, leaf_depth[l]))

        tree = tree._replace(
            split_leaf=tree.split_leaf.at[s].set(jnp.where(do, l, -1)),
            split_feat=tree.split_feat.at[s].set(jnp.where(do, f, 0)),
            split_bin=tree.split_bin.at[s].set(jnp.where(do, t, 0)),
            default_left=tree.default_left.at[s].set(do & dleft & ~is_cat),
            split_cat=tree.split_cat.at[s].set(do & is_cat),
            cat_threshold=tree.cat_threshold.at[s].set(members & do & is_cat),
            split_gain=tree.split_gain.at[s].set(jnp.where(do, gain, 0.0)),
            num_leaves=tree.num_leaves + do.astype(jnp.int32),
        )
        return (leaf_ids, hists, leaf_stats, leaf_depth, tree, stopped | ~do)

    carry = (leaf_ids, hists, leaf_stats, leaf_depth, tree0, jnp.asarray(False))
    leaf_ids, hists, leaf_stats, leaf_depth, tree, _ = lax.fori_loop(0, S, step, carry)

    if cfg.quantize_active:
        # Exact f32 leaf totals for the leaf VALUES: the carried stats are
        # dequantized bucket sums, good enough to rank splits but the
        # model's outputs must come from exact sums (AUC/leaf parity).
        leaf_stats = _leaf_totals(vals, leaf_ids, L, onehot=False)
        if cfg.axis_name is not None:
            from mmlspark_tpu.parallel.distributed import psum_axes

            leaf_stats = psum_axes(leaf_stats, cfg.axis_name)
    leaf_value = _leaf_output(
        leaf_stats[0], leaf_stats[1], cfg.lambda_l1, cfg.lambda_l2, cfg.learning_rate
    )
    active = jnp.arange(L) < tree.num_leaves
    tree = tree._replace(
        leaf_value=jnp.where(active, leaf_value, 0.0),
        leaf_count=leaf_stats[2],
    )
    return tree, leaf_ids


def grow_tree_depthwise(
    cfg: GrowConfig,
    bins: jnp.ndarray,
    grad: jnp.ndarray,
    hess: jnp.ndarray,
    bag_weight: jnp.ndarray,
    feat_mask: jnp.ndarray,
    qkey: Optional[jnp.ndarray] = None,
    qscale: Optional[jnp.ndarray] = None,
    root_hist: Optional[jnp.ndarray] = None,
) -> Tuple[Tree, jnp.ndarray]:
    """Level-synchronous growth with windowed new-children histograms.

    The TPU-first answer to SURVEY.md §7.4.2, round 2: per level, ONE
    histogram pass builds only the level's NEW RIGHT CHILDREN — whose ids
    are contiguous ``[base, base+k)`` by construction of the step
    numbering — into a static window of ``level_window`` leaf slots
    (:func:`~mmlspark_tpu.ops.histogram.build_histogram_by_leaf` parks
    every other row outside the one-hot range).  Left children are derived
    by the subtraction trick from the carried per-leaf histogram buffer.
    Compared to round 1's rebuild-all-leaves pass this cuts the one-hot
    matmul's leaf axis from ``num_leaves`` to ``≤ num_leaves/2`` per level
    and skips every row that did not move — the measured pass went from
    77ms to single-digit ms at 262k×64×256 on v5e.

    Split SEMANTICS per level are best-first: all active leaves propose
    their best candidate, and the top-(remaining budget) by gain are
    applied.  On balanced data this matches lossguide's tree; they diverge
    only when the leaf budget runs out mid-level (lossguide can then favor
    a deep chain).  The recorded Tree uses the identical step numbering, so
    prediction replay and model-string export are unchanged.

    ``root_hist``: the tree's merged ``(3, F_loc, B)`` root histogram where
    the caller built it (:func:`class_root_histograms`, a multiclass
    iteration's roots together); the grower then makes no root pass.
    """
    n, F = bins.shape
    B, L, S = cfg.num_bins, cfg.num_leaves, cfg.max_steps
    W = cfg.level_window
    LB = L + W  # hist buffer slots: window writes start at base ≤ S
    # ONE transpose per tree: every histogram pass wants rows on the
    # lane axis (F, n), and re-deriving it per pass cost a ~10s-of-MB
    # relayout each level.  uint8 through the byte tier (B ≤ 256) — see
    # grow_tree / ops/binpack.py::hist_transpose.
    bins_t = hist_transpose(bins, B)  # (F, n)
    in_bag = (bag_weight > 0).astype(jnp.float32)
    vals = jnp.stack(
        [grad * bag_weight, hess * bag_weight, in_bag], axis=0
    ).astype(jnp.float32)  # (3, n) channel-major

    hist_axis, merge_mode = _window_merge(cfg)
    rs = cfg.reduce_scatter_active
    featsliced = rs or cfg.hierarchical_active
    qvals, hq = _quantize_rows(cfg, vals, qkey, qscale)

    def window_hist(win_leaf):
        with _hist_scope(cfg):
            return build_histogram_by_leaf(
                bins_t, qvals, win_leaf, W, B,
                backend=cfg.hist_backend, chunk=cfg.hist_chunk, axis_name=hist_axis,
                precision=cfg.hist_precision,
                merge=merge_mode,
                quantize=hq,
            )

    # Root histogram through the SAME windowed kernel (all rows in slot 0):
    # the plain per-feature kernel's M=3 matmuls cost 2.8ms/pass at the
    # bench shape vs 1.9ms for the factorized windowed kernel, and reusing
    # it drops one compiled kernel from the program.
    if root_hist is None:
        root_hist = window_hist(jnp.zeros(n, jnp.int32))[:, 0]  # (3, F_loc, B)
    # Under reduce_scatter the merged buffer holds only THIS shard's
    # contiguous feature slice: F_loc = F/D is STATIC at trace time
    # (psum_scatter's result shape; device_psum_scatter pads the histogram
    # to a multiple of the axis size).  Every other mode has F_loc == F.
    F_loc = root_hist.shape[1]
    hists0 = jnp.zeros((3, LB, F_loc, B), jnp.float32).at[:, 0].set(root_hist)

    if featsliced:
        from mmlspark_tpu.parallel.distributed import device_psum

        # Feature slices live along the fast axis under hierarchical (the
        # same block layout on every host), the whole mesh axis under
        # reduce_scatter.
        stats_axis = cfg.feature_shard_axis
        rs_shard = lax.axis_index(stats_axis)
        # This shard's slice of the global feature mask + the runtime
        # categorical mask of its column block (global indices cannot be
        # specialized statically per shard in one SPMD program).
        fm_loc = lax.dynamic_slice(feat_mask, (rs_shard * F_loc,), (F_loc,))
        cmask_loc = (
            _local_cat_mask(cfg, F_loc)
            if cfg.has_categoricals
            else jnp.zeros(F_loc, bool)
        )

        def _global_leaf_stats(h):
            # Per-leaf totals summed from GLOBAL feature 0's merged bins on
            # its owning shard (shard 0), broadcast with one tiny (3, nL)
            # psum — identical on every shard AND the same bins-of-feature-0
            # float summation the serial/allreduce paths use, so near-tied
            # gains round the same way (a per-shard local feature's bin-sum
            # or a rows segment-sum would each round DIFFERENTLY, visibly
            # reordering lossguide's gain-ranked split sequence).
            # Hierarchical: the psum stays on the FAST axis, so these are
            # HOST-LOCAL totals — identical across a host's devices, which
            # is all the host-biased election needs; the refinement pass
            # re-derives exact global stats for every winner.
            s = h[:, :, 0, :].sum(axis=-1)  # (3, nL) on shard 0
            return device_psum(
                jnp.where(rs_shard == 0, s, 0.0), stats_axis
            )

    # Incremental candidate cache (serial + data-parallel paths): only the
    # ≤ 2W leaves whose histograms a pass touches (split parents + new
    # children) get their (L, F) candidate rows re-scored — candidates per
    # leaf depend only on that leaf's own histogram, so unchanged rows are
    # bitwise stable.  Kills the full (3·L·F·B) cumsum+argmax chain every
    # pass (L/2W of it is redundant).  Voting re-scores LOCAL candidates
    # against re-psum-ed stats and feature-parallel re-scores local blocks
    # per shard, so both keep the full per-pass compute.  Reduce-scatter
    # keeps the cache — its matrices are (L, F_loc) local slices reduced
    # per shard and exchanged per pass.
    use_cand_cache = not (cfg.voting_active or cfg.feature_parallel_active)
    if use_cand_cache and featsliced:
        stats0 = _global_leaf_stats(hists0[:, :L])
        cand0 = _local_candidate_matrix(
            cfg, hists0[:, :L], stats0, fm_loc, cmask_loc
        )
    elif use_cand_cache:
        stats0 = hists0[:, :L, 0, :].sum(axis=-1)
        cand0 = _candidate_matrix(cfg, hists0[:, :L], stats0, feat_mask)
    else:  # dummy carry slot (shapes must match across the while_loop)
        cand0 = (
            jnp.full((L, F_loc), -jnp.inf, jnp.float32),
            jnp.zeros((L, F_loc), jnp.int32),
            jnp.zeros((L, F_loc), bool),
        )

    # Split-record arrays get one extra scratch slot (index S) that
    # non-selected leaves harmlessly scatter into; trimmed at the end.
    tree0 = _empty_tree(S + 1, L, B)
    leaf_arange = jnp.arange(L, dtype=jnp.int32)

    def cond(carry):
        return ~carry[-1]

    def level(carry):
        leaf_ids, hists, tree, leaf_depth, step, cand, _ = carry
        gain_m, t_m, d_m = cand
        cur_leaves = tree.num_leaves
        if cfg.feature_parallel_active:
            # Per-leaf totals from a segment-sum over the REPLICATED rows:
            # every shard computes bit-identical stats (local feature 0
            # differs per shard, and its different float summation order
            # would skew near-tied gains differently across shards,
            # breaking the lowest-feature tie agreement with serial).
            leaf_stats = _leaf_totals(vals, leaf_ids, L, onehot=False)
        elif not use_cand_cache:
            # feature 0's bins tile all rows → per-leaf totals
            leaf_stats = hists[:, :L, 0, :].sum(axis=-1)  # (3, L)
        if use_cand_cache:
            if featsliced:
                # Local reduce over this shard's feature slice, then the
                # winner exchange: the only per-pass collectives are the
                # windowed reduce-scatter merge, the (D, 5, L) candidate
                # all-gather, and the tiny leaf-stat psum — vs the full
                # (3, W, F, B) allreduce of hist_merge="allreduce".
                # Hierarchical: the scatter + leaf-stat psum ride the fast
                # intra-host axis; ONLY the (D, 5, L) all-gather (and the
                # refinement below) cross the slow axis.
                gain_l, f_l, t_l, d_l, ic_l = _reduce_local_candidates(
                    gain_m, t_m, d_m, cmask_loc
                )
                gain, f, t, dleft, is_cat, xch_own, xch_f_local = (
                    _exchange_best(cfg, gain_l, f_l, t_l, d_l, ic_l, F_loc)
                )
            else:
                gain, f, t, dleft, is_cat = _reduce_candidates(
                    cfg, gain_m, t_m, d_m
                )
        elif cfg.voting_active:
            gain, f, t, dleft, is_cat, hists_sel, sel_feats, sel_j = (
                _voting_leaf_candidates(cfg, hists[:, :L], leaf_stats, feat_mask)
            )
        elif cfg.feature_parallel_active:
            # Candidates over the LOCAL feature block, then the winner
            # exchange: all-gather each shard's per-leaf best (4 scalars
            # per leaf) and argmax across shards.  Ties pick the lowest
            # shard (argmax-first), whose within-shard winner is its lowest
            # local index — together the lowest GLOBAL feature index,
            # identical to the serial argmax tie-break (features are
            # sharded in contiguous ascending blocks).
            if cfg.has_categoricals:
                # runtime per-shard column kinds (a static per-shard set
                # cannot exist in one SPMD program — VERDICT r3 #7)
                fp_cmask = _local_cat_mask(cfg, F_loc)
                gain_l, f_l, t_l, d_l, ic_l = _fp_leaf_candidates(
                    cfg, hists[:, :L], leaf_stats, feat_mask, fp_cmask
                )
            else:
                gain_l, f_l, t_l, d_l, ic_l = _leaf_candidates(
                    cfg, hists[:, :L], leaf_stats, feat_mask
                )
            gain, f, t, dleft, is_cat, xch_own, xch_f_local = (
                _exchange_best(cfg, gain_l, f_l, t_l, d_l, ic_l, F_loc)
            )
        leaf_ok = leaf_arange < cur_leaves
        if cfg.max_depth > 0:
            leaf_ok &= leaf_depth < cfg.max_depth
        gain = jnp.where(leaf_ok, gain, -jnp.inf)
        valid = gain > cfg.min_gain_to_split

        # Best-first selection within the pass, capped by the leaf budget
        # and (with split_batch) the per-pass batch size (level_window
        # never binds below either — see its docstring).
        budget = jnp.minimum(L - cur_leaves, W)
        if cfg.split_batch > 0:
            budget = jnp.minimum(budget, cfg.split_batch)
        order = jnp.argsort(-gain)
        rank = jnp.argsort(order)  # gain-desc rank of each leaf
        selected = valid & (rank < budget)
        k = jnp.sum(selected).astype(jnp.int32)
        # step id per selected leaf, in gain order (0-based among selected)
        sel_rank = (jnp.cumsum(selected[order]) - 1)[rank]
        step_of_leaf = jnp.where(selected, step + sel_rank.astype(jnp.int32), S)
        new_id_of_leaf = (step_of_leaf + 1).astype(jnp.int32)  # right-child ids
        base = step + 1  # first new id this level
        slot_leaves = order[:W].astype(jnp.int32)  # gain-ranked slots

        # -- f32 winner refinement (ISSUE 9 quantized path; ISSUE 14
        # hierarchical merge) ---------------------------------------------
        if cfg.refine_active:
            with _refine_scope(cfg):
                # Approximate statistics picked the level's ≤W winners
                # (quantized histograms, or the hierarchical merge's
                # host-local slices); ONE windowed f32 pass re-accumulates
                # just their winning COLUMNS (composed into a single per-row
                # column: each row reads its own leaf's winning feature) and
                # re-scores them exactly, so recorded thresholds/gains and
                # the membership sets below carry no quantization or
                # host-bias error.  Rides the same small-allreduce structure
                # as the membership owner-broadcast: (3, W, 1, B) ≪ the full
                # (3, W, F, B) pass — and replicates the whole winner column
                # even when the merge itself scatters (rows are sharded,
                # features are not, so every shard holds every column
                # locally).  Under hierarchical this allreduce spans the FULL
                # (slow × fast) mesh: it is, with the winner exchange, the
                # only inter-host traffic of the pass.
                win_col = jnp.zeros(n, jnp.int32)
                for w in range(W):
                    l_w = slot_leaves[w]
                    col_w = lax.dynamic_slice(
                        bins_t, (f[l_w], jnp.int32(0)), (1, n)
                    )[0]
                    win_col = jnp.where(leaf_ids == l_w, col_w, win_col)
                warange_r = jnp.arange(W, dtype=jnp.int32)
                slot_of_leaf = jnp.full(L, W, jnp.int32).at[slot_leaves].set(
                    jnp.where(selected[slot_leaves], warange_r, W)
                )
                row_slot = slot_of_leaf[leaf_ids]  # non-winners park at W
                ref_hist = build_histogram_by_leaf(
                    win_col[None, :], vals, row_slot, W, B,
                    backend=cfg.hist_backend, chunk=cfg.hist_chunk,
                    axis_name=hist_axis, precision=cfg.hist_precision,
                    # exact AND process-layout-invariant: the refined
                    # gains/thresholds are recorded in the model, so their
                    # f32 sum order must not depend on how many processes
                    # the mesh spans (multihost bitwise-parity gate)
                    merge="allreduce_exact",
                )  # (3, W, 1, B) exact winner columns
                stats_w = ref_hist[:, :, 0, :].sum(axis=-1)  # (3, W)
                rg, rt, rd = _refine_candidates(
                    cfg, ref_hist, stats_w, is_cat[slot_leaves]
                )
                ok_w = selected[slot_leaves] & (rg > -jnp.inf)
                gain = gain.at[slot_leaves].set(
                    jnp.where(ok_w, rg, gain[slot_leaves])
                )
                t = t.at[slot_leaves].set(jnp.where(ok_w, rt, t[slot_leaves]))
                dleft = dleft.at[slot_leaves].set(
                    jnp.where(ok_w, rd, dleft[slot_leaves])
                )

        # -- categorical membership sets for the level's winners ----------
        if cfg.has_categoricals:
            if cfg.refine_active:
                # The refined f32 columns already hold GLOBAL statistics
                # for every selected leaf (allreduce merge above): no
                # owner psum, and the membership scan runs on exact
                # operands.  Non-selected leaves gather garbage the
                # ``selected & is_cat`` mask below discards.
                hist_lf = jnp.take(
                    ref_hist[:, :, 0, :],
                    jnp.minimum(slot_of_leaf, W - 1), axis=1,
                )  # (3, L, B)
            elif cfg.voting_active:
                # GLOBAL statistics for the winning feature live in the
                # psum-med elected block, not the local buffer.
                hist_lf = jnp.take_along_axis(
                    hists_sel, sel_j[None, :, None, None], axis=2
                )[:, :, 0]  # (3, L, B)
            elif cfg.feature_parallel_active or rs:
                # The winner's MERGED histogram lives whole on its OWNING
                # shard (feature-parallel: rows replicated ⇒ local
                # histograms are complete; reduce_scatter: the merge
                # already summed the owner's slice across shards); one
                # small psum of the owner's (3, L, B) slice replicates it,
                # so every shard derives the identical membership set —
                # the exchange rides the same owner-broadcast structure as
                # the feature-parallel row partition below.
                from mmlspark_tpu.parallel.distributed import device_psum

                hist_own = jnp.take_along_axis(
                    hists[:, :L], xch_f_local[None, :, None, None], axis=2
                )[:, :, 0]  # (3, L, B)
                hist_lf = device_psum(
                    jnp.where(xch_own[None, :, None], hist_own, 0.0),
                    cfg.axis_name,
                )
            else:
                hist_lf = jnp.take_along_axis(
                    hists[:, :L], f[None, :, None, None], axis=2
                )[:, :, 0]  # (3, L, B)
            members = _cat_members(cfg, hist_lf, t, dleft)  # (L, B)
            members &= (selected & is_cat)[:, None]
        else:
            members = jnp.zeros((L, B), bool)

        # -- per-row moves ------------------------------------------------
        if cfg.feature_parallel_active:
            sel_row = selected[leaf_ids]
            # Only the winner-owning shard can read the split column; it
            # computes the row partition and broadcasts it with one psum —
            # LightGBM feature-parallel's "winner broadcasts the split
            # result" step (its n-bit bitset → an n-vector reduction here).
            f_row = xch_f_local[leaf_ids]
            fcol = jnp.take_along_axis(bins_t, f_row[None, :], axis=0)[0]
            is_missing = fcol == (B - 1)
            gl_local = jnp.where(is_missing, dleft[leaf_ids], fcol <= t[leaf_ids])
            if cfg.has_categoricals:
                # categorical winners route rows by MEMBERSHIP: per-leaf
                # sets bit-packed to (L, ⌈B/32⌉) u32 words, one small-table
                # take per row (the `members` above is already global —
                # psum-ed from the owner — so every shard agrees)
                from mmlspark_tpu.ops.pallas_route import member_words

                words = member_words(members)  # (L, nw)
                nw = words.shape[1]
                wsel = jnp.take(
                    words.reshape(-1),
                    leaf_ids * nw + (fcol >> 5).astype(jnp.int32),
                )
                gl_cat = ((wsel >> (fcol & 31).astype(jnp.uint32)) & 1) > 0
                gl_local = jnp.where(is_cat[leaf_ids], gl_cat, gl_local)
            own_row = xch_own[leaf_ids]
            goes_left = lax.psum(
                jnp.where(own_row, gl_local.astype(jnp.float32), 0.0),
                cfg.axis_name,
            ) > 0.5
            move = sel_row & ~goes_left
            leaf_ids = jnp.where(move, new_id_of_leaf[leaf_ids], leaf_ids)
        else:
            # Only the ≤W window leaves split this pass: their rows move by
            # the ≤W split columns alone (slot_leaves hoisted above — the
            # refinement pass and the candidate cache share the
            # gain-ranked slots).
            splits = PassSplits(
                f, t, dleft, is_cat, selected, new_id_of_leaf,
                members if cfg.has_categoricals else None,
            )
            route = _route_block if route_in_block(cfg, bins_t) else _route_slots
            with jax.named_scope("row_route"):
                leaf_ids = route(bins_t, leaf_ids, slot_leaves, splits, B)

        # -- windowed new-children histograms + parent subtraction --------
        win = window_hist(leaf_ids - base)  # (3, W, F, B); old ids park <0
        hists = lax.dynamic_update_slice(hists, win, (0, base, 0, 0))
        widx = jnp.clip(new_id_of_leaf - base, 0, W - 1)  # (L,)
        sub = jnp.where(selected[None, :, None, None], win[:, widx], 0.0)
        hists = hists.at[:, :L].add(-sub)

        if use_cand_cache:
            # Re-score ONLY the ≤2W leaves whose histograms changed: the
            # split parents (now left children, post-subtraction) and the
            # new right children.  Unselected slots park at LB (gather
            # clipped harmlessly, scatter dropped), so shapes stay static.
            warange = jnp.arange(W, dtype=jnp.int32)
            parent_slots = slot_leaves  # the move loop's gain-ranked slots
            parent_ids = jnp.where(selected[parent_slots], parent_slots, LB)
            child_ids = jnp.where(warange < k, base + warange, LB)
            changed = jnp.concatenate([parent_ids, child_ids])  # (2W,)
            h_ch = jnp.take(hists, jnp.minimum(changed, LB - 1), axis=1)
            if featsliced:
                # Shard-identical per-leaf totals from the merged slices
                # (see _global_leaf_stats); parked slots clip to garbage
                # the mode="drop" scatter below discards.
                stats_ch = _global_leaf_stats(h_ch)  # (3, 2W)
                cg, ct, cd = _local_candidate_matrix(
                    cfg, h_ch, stats_ch, fm_loc, cmask_loc
                )
            else:
                stats_ch = h_ch[:, :, 0, :].sum(axis=-1)  # (3, 2W)
                cg, ct, cd = _candidate_matrix(cfg, h_ch, stats_ch, feat_mask)
            gain_m = gain_m.at[changed].set(cg, mode="drop")
            t_m = t_m.at[changed].set(ct, mode="drop")
            d_m = d_m.at[changed].set(cd, mode="drop")

        # -- record the level's splits (scratch slot S absorbs the rest) --
        tree = tree._replace(
            split_leaf=tree.split_leaf.at[step_of_leaf].set(
                jnp.where(selected, leaf_arange, -1)
            ),
            split_feat=tree.split_feat.at[step_of_leaf].set(f),
            split_bin=tree.split_bin.at[step_of_leaf].set(t),
            default_left=tree.default_left.at[step_of_leaf].set(
                selected & dleft & ~is_cat
            ),
            split_cat=tree.split_cat.at[step_of_leaf].set(selected & is_cat),
            cat_threshold=tree.cat_threshold.at[step_of_leaf].set(members),
            split_gain=tree.split_gain.at[step_of_leaf].set(
                jnp.where(selected, gain, 0.0)
            ),
            num_leaves=cur_leaves + k,
        )
        child_depth = leaf_depth + 1
        # right children (out-of-bounds ids for non-selected are dropped)
        leaf_depth = leaf_depth.at[new_id_of_leaf].set(
            jnp.where(selected, child_depth, 0), mode="drop"
        )
        leaf_depth = jnp.where(selected, child_depth, leaf_depth)

        stop = (k == 0) | (tree.num_leaves >= L)
        return (
            leaf_ids, hists, tree, leaf_depth, step + k,
            (gain_m, t_m, d_m), stop,
        )

    carry = (
        jnp.zeros(n, jnp.int32), hists0, tree0, jnp.zeros(L, jnp.int32),
        jnp.asarray(0, jnp.int32), cand0, jnp.asarray(False),
    )
    leaf_ids, _, tree, leaf_depth, _, _, _ = lax.while_loop(cond, level, carry)

    # Final per-leaf (G, H, count), from the float32 rows and not from the
    # carried histograms, whose sums hold the kernels' bf16 products.
    with jax.named_scope("leaf_stats"):
        leaf_stats = _leaf_totals(vals, leaf_ids, L, cfg.onehot_stats)
    if cfg.axis_name is not None and not cfg.feature_parallel_active:
        # Row-sharded modes sum partial stats; feature-parallel replicates
        # rows, so the local sum is already the global sum.  psum_axes
        # gathers the partials and sums them in fixed program order so
        # the f32 result is process-layout-invariant on the 2D mesh
        # (multihost bitwise parity gate).
        from mmlspark_tpu.parallel.distributed import psum_axes

        leaf_stats = psum_axes(leaf_stats, cfg.axis_name)
    leaf_value = _leaf_output(
        leaf_stats[0], leaf_stats[1], cfg.lambda_l1, cfg.lambda_l2,
        cfg.learning_rate,
    )
    active = leaf_arange < tree.num_leaves
    tree = tree._replace(
        split_leaf=tree.split_leaf[:S],
        split_feat=tree.split_feat[:S],
        split_bin=tree.split_bin[:S],
        default_left=tree.default_left[:S],
        split_cat=tree.split_cat[:S],
        cat_threshold=tree.cat_threshold[:S],
        split_gain=tree.split_gain[:S],
        leaf_value=jnp.where(active, leaf_value, 0.0),
        leaf_count=leaf_stats[2],
    )
    return tree, leaf_ids


def windowed_grower(cfg: GrowConfig) -> bool:
    """Whether :func:`grow_tree_auto` takes the windowed grower:
    split_batch routes lossguide through it too (k best-first splits per
    windowed pass; k=1 reproduces grow_tree's split sequence exactly — see
    GrowConfig.split_batch), and feature-parallel's winner exchange only
    exists there."""
    return (
        cfg.grow_policy == "depthwise"
        or cfg.split_batch > 0
        or cfg.feature_parallel_active
        or cfg.reduce_scatter_active
        or cfg.hierarchical_active
    )


def _window_merge(cfg: GrowConfig):
    """``(axis_name, merge)`` of the windowed grower's histogram builds.

    Under voting-parallel the carried histogram buffer stays LOCAL per
    shard (votes + elected slices are the only collectives); under
    feature-parallel it is local by CONSTRUCTION (each shard owns its
    columns outright — no histogram collective exists in the mode);
    otherwise the histogram builds merge so the buffer is globally replicated
    (hist_merge="allreduce") or feature-sliced per shard
    (hist_merge="reduce_scatter").  Hierarchical (the 2-D pod mesh): the merge
    scatters over the FAST intra-host axis only (the axis is the (slow,
    fast) tuple; the merge routes the scatter to its last element), so the
    carried buffer holds HOST-LOCAL feature slices.  Election is then
    host-biased; the always-on refinement pass restores global exactness
    for the winners."""
    if cfg.voting_active or cfg.feature_parallel_active:
        return None, "allreduce"
    if cfg.hierarchical_active:
        return cfg.axis_name, "hierarchical"
    return cfg.axis_name, "reduce_scatter" if cfg.reduce_scatter_active else "allreduce"


def class_roots_active(cfg: GrowConfig, num_class: int) -> bool:
    """Whether a fit of ``num_class`` trees an iteration builds their roots
    together (:func:`class_root_histograms`) and hands each windowed grower
    its own: float values, the windowed grower, one device or a row-sharded
    mesh whose merge the histogram build performs, and on the Pallas
    backend a window whose own root takes the factorized body (the class
    roots' body is its tile with a class in each slot: the same sums in the
    same order).  Quantized, lossguide, voting and feature-parallel fits,
    and windows or bin counts that take the plain by-leaf body, build each
    root in its own tree."""
    return (
        num_class > 1
        and not cfg.quantize_active
        and windowed_grower(cfg)
        and not (cfg.voting_active or cfg.feature_parallel_active)
        and (cfg.hist_backend != "pallas" or factorized_by_leaf(cfg.level_window, cfg.num_bins))
    )


def class_root_histograms(cfg: GrowConfig, bins, grad, hess, bag_weight):
    """``(K, 3, F_loc, B)``: the merged root histogram of each of the K
    classes' trees, which :func:`grow_tree_depthwise` builds one tree at a
    time as its first windowed pass, in ⌈K/8⌉ passes
    (:func:`~mmlspark_tpu.ops.histogram.build_class_root_histograms`)."""
    axis_name, merge = _window_merge(cfg)
    with _hist_scope(cfg):
        return build_class_root_histograms(
            hist_transpose(bins, cfg.num_bins), grad, hess, bag_weight, cfg.num_bins,
            backend=cfg.hist_backend, chunk=cfg.hist_chunk, axis_name=axis_name,
            precision=cfg.hist_precision, merge=merge,
        )


def grow_tree_auto(cfg: GrowConfig, *args):
    if windowed_grower(cfg):
        return grow_tree_depthwise(cfg, *args)
    return grow_tree(cfg, *args)


def _replay_leaf_ids(tree: Tree, bins: jnp.ndarray, num_bins: int,
                     scope: str = "replay_step") -> jnp.ndarray:
    """Replay a tree's splits over binned rows → per-row leaf ids.

    Split replay keeps prediction gather-free over tree topology: rows start
    in leaf 0 and each recorded split moves the affected rows, mirroring the
    growth procedure exactly (same arithmetic ⇒ train/predict parity): a
    step reads one column and tests set membership with the grower's
    bit-packed :func:`_member_lookup`, never a per-row table entry.  The
    steps run under the named ``scope``: the scorers' ``replay_step``, or a
    GOSS fit's ``goss_route`` (every training row routed through the tree
    grown from the sample).
    """
    n = bins.shape[0]
    S = tree.split_leaf.shape[0]

    def step(s, leaf_ids):
        active = tree.split_leaf[s] >= 0
        # widen the column, not the matrix: a step moves one column's bytes
        fcol = lax.dynamic_index_in_dim(
            bins, tree.split_feat[s], axis=1, keepdims=False
        ).astype(jnp.int32)
        is_missing = fcol == (num_bins - 1)
        goes_left = jnp.where(is_missing, tree.default_left[s], fcol <= tree.split_bin[s])
        goes_left = jnp.where(
            tree.split_cat[s],
            _member_lookup(tree.cat_threshold[s], fcol, num_bins),
            goes_left,
        )
        move = active & (leaf_ids == tree.split_leaf[s]) & ~goes_left
        return jnp.where(move, s + 1, leaf_ids)

    with jax.named_scope(scope):
        return lax.fori_loop(0, S, step, jnp.zeros(n, jnp.int32))


def _leaf_lookup(leaf_value: jnp.ndarray, leaf_ids: jnp.ndarray) -> jnp.ndarray:
    """``leaf_value[leaf_ids]`` without the gather lowering.

    Under the scorers' ``vmap`` over classes the (L,)-table gather is a
    batched gather, ~7.6ns a row on v5e; one compare-and-select pass over
    the rows per leaf is ~0.5ns a row at L=63 and picks the same float32,
    so the result is the gather's to the bit.  Left rolled: unrolled 64
    it saves 1.3ms a tree at 4.19M rows and costs a new scorer ~2ms of
    host time per unrolled leaf before its first dispatch."""

    def pick(leaf, out):
        return jnp.where(leaf_ids == leaf, leaf_value[leaf], out)

    return lax.fori_loop(
        0, leaf_value.shape[0], pick, jnp.zeros(leaf_ids.shape, leaf_value.dtype)
    )


def predict_tree_binned(tree: Tree, bins: jnp.ndarray, num_bins: int) -> jnp.ndarray:
    """Per-row leaf values for one tree over binned rows."""
    return _leaf_lookup(tree.leaf_value, _replay_leaf_ids(tree, bins, num_bins))


def predict_tree_leaf_binned(tree: Tree, bins: jnp.ndarray, num_bins: int) -> jnp.ndarray:
    """Per-row leaf *index* (for ``leafPredictionCol`` — SURVEY.md §2.3.1)."""
    return _replay_leaf_ids(tree, bins, num_bins)


def predict_forest_binned(trees: Tree, bins: jnp.ndarray, num_bins: int) -> jnp.ndarray:
    """Sum of per-tree predictions for stacked trees (leading axis T)."""

    def body(acc, tree):
        return acc + predict_tree_binned(tree, bins, num_bins), None

    init = jnp.zeros(bins.shape[0], jnp.float32)
    out, _ = lax.scan(body, init, trees)
    return out


def full_tree_passes(cfg: GrowConfig) -> int:
    """Trips of the windowed grower's ``while_loop`` for a tree that splits
    every leaf it may: a pass splits at most ``split_batch`` (where set)
    and ``level_window`` of the leaves it holds, up to the leaf budget.  The
    count the program itself does not carry (the loop stops on data); a
    tree whose leaves run out of valid splits makes other passes."""
    leaves, passes = 1, 0
    while leaves < cfg.num_leaves:
        k = min(leaves, cfg.num_leaves - leaves, cfg.level_window)
        if cfg.split_batch > 0:
            k = min(k, cfg.split_batch)
        leaves += k
        passes += 1
    return passes
