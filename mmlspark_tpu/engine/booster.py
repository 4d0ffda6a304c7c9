"""Boosting orchestration: the ``fit`` loop over jitted tree growth.

This module is the TPU-native analog of the reference's per-task native
training loop (SURVEY.md §3.1: ``LGBM_BoosterCreate`` + HOT LOOP of
``LGBM_BoosterUpdateOneIter`` / ``LGBM_BoosterGetEval`` — [REF-EMPTY],
upstream C++ ``src/boosting/gbdt.cpp``).  Differences by design:

- The per-iteration work (objective grad/hess → bagging/GOSS → leaf-wise
  growth → score update) is one jitted JAX program; the Python loop around it
  is control only (early stopping, metric records, DART bookkeeping) —
  mirroring how the reference keeps its loop in Scala but the work native.
- Boosting modes: ``gbdt``, ``rf``, ``dart``, ``goss`` (SURVEY.md §2.3.1
  ``boostingType``).  ``goss`` draws an exact-count sample every iteration
  (:func:`goss_sample`: no sort) and, on one device, grows the tree from
  the sample's rows alone (:func:`goss_compact`), then routes every row
  through it.
- ``boost_from_average`` folds the initial score into tree 0's leaf values
  (LightGBM's ``Tree::AddBias`` behavior) so saved models predict
  identically without a separate init-score field.
"""

from __future__ import annotations

import contextlib
import dataclasses
import itertools
import math
import os
import time
from functools import partial
from typing import Dict, List, NamedTuple, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from mmlspark_tpu import obs
from mmlspark_tpu.engine import eval_metrics
from mmlspark_tpu.engine.tree import (
    GrowConfig,
    Tree,
    _leaf_lookup,
    _replay_leaf_ids,
    grow_tree_auto,
    predict_tree_binned,
    predict_tree_leaf_binned,
)
from mmlspark_tpu.ops.binning import BinMapper
from mmlspark_tpu.ops.histogram import (
    DEFAULT_CHUNK,
    quantize_channel_scales,
    quantize_levels,
    quantize_wire_plan,
)
from mmlspark_tpu.ops.objectives import LambdaRank, Objective, get_objective


@dataclasses.dataclass
class TrainConfig:
    """LightGBM-vocabulary training config.

    Field names follow LightGBM's config strings because the reference's
    ``TrainParams`` serializes SparkML params into exactly that vocabulary
    (SURVEY.md §5.6, §2.3.1) — keeping it preserves the param-surface
    contract ("the native config parser is the last word").
    """

    objective: str = "regression"
    num_iterations: int = 100
    learning_rate: float = 0.1
    num_leaves: int = 31
    max_bin: int = 255
    max_depth: int = -1
    min_data_in_leaf: int = 20
    min_sum_hessian_in_leaf: float = 1e-3
    lambda_l1: float = 0.0
    lambda_l2: float = 0.0
    min_gain_to_split: float = 0.0
    bagging_fraction: float = 1.0
    bagging_freq: int = 0
    bagging_seed: int = 3
    feature_fraction: float = 1.0
    feature_fraction_seed: int = 2
    boosting: str = "gbdt"
    top_rate: float = 0.2
    other_rate: float = 0.1
    drop_rate: float = 0.1
    max_drop: int = 50
    skip_drop: float = 0.5
    drop_seed: int = 4
    num_class: int = 1
    sigmoid: float = 1.0
    alpha: float = 0.9
    fair_c: float = 1.0
    poisson_max_delta_step: float = 0.7
    tweedie_variance_power: float = 1.5
    early_stopping_round: int = 0
    # One metric name, a LightGBM comma-separated list ("auc,binary_logloss"),
    # or a Python list; None = the objective's default metric.
    metric: Optional[Union[str, Sequence[str]]] = None
    # LightGBM first_metric_only: early stopping watches only the FIRST
    # metric (still across every validation set); False = the default
    # ANY-(set, metric)-pair rule.
    first_metric_only: bool = False
    # Record the metric on TRAINING data each iteration under
    # evals_result["training"] (the reference's isProvideTrainingMetric --
    # SURVEY.md 2.3.1/5.5; unlike the reference, the values surface on
    # the booster instead of being trapped in executor logs).
    is_provide_training_metric: bool = False
    is_unbalance: bool = False
    scale_pos_weight: float = 1.0
    boost_from_average: bool = True
    categorical_feature: Sequence[int] = dataclasses.field(default_factory=tuple)
    label_gain: Optional[Sequence[float]] = None
    max_position: int = 20
    seed: int = 0
    tree_learner: str = "serial"
    top_k: int = 20
    # lossguide (auto-batched on TPU — see split_batch) | lossguide_exact
    # (LightGBM's one-split-per-pass sequence, never batched) | depthwise
    grow_policy: str = "lossguide"
    # >0: apply at most this many best-first splits per histogram pass
    # (k-batched growth; 1 = LightGBM-exact lossguide via the windowed
    # grower, ~num_leaves/2 ≈ depthwise).  0 = AUTO: on the TPU pallas
    # lossguide path this resolves to _AUTO_SPLIT_BATCH (histogram passes
    # dominate there and k-batching trades none of the measured AUC —
    # BASELINE.md r5 defaults table); elsewhere it keeps the policy's
    # default (exact lossguide).  -1 = never batch (exact), also spelled
    # grow_policy="lossguide_exact".
    split_batch: int = 0
    # "auto" resolves at train() time: the Pallas MXU kernels on a TPU
    # backend, the XLA scatter builder elsewhere (pallas on CPU means
    # interpret mode — orders of magnitude slower).  Without this, the
    # user-facing estimators silently trained on the slow path on TPU.
    hist_backend: str = "auto"
    # Predict-path traversal backend (ISSUE 5): "packed" = depth-stepped
    # device-resident node table (engine/forest), "pallas" = fused VMEM
    # row-tile kernel (ops/pallas_predict, TPU), "pallas_interpret" = that
    # kernel under the Pallas interpreter on CPU (tests/parity), "scan" =
    # the legacy sequential per-tree lax.scan.  "auto" resolves the same
    # way hist_backend does (pallas on a TPU backend, packed elsewhere) —
    # and is RE-resolved against the backend each predict actually runs
    # on, so a model trained on TPU serves correctly from a CPU process.
    # All backends produce bitwise-identical raw scores (the pallas
    # kernel's one documented -0.0 leaf-value caveat aside).
    predict_backend: str = "auto"
    # 0 = auto: one chunk (the whole padded row count, capped) under the
    # pallas backend — fewer scan steps; DEFAULT_CHUNK for the
    # memory-bound scatter builder.
    hist_chunk: int = 0
    # Histogram / leaf-delta contraction precision: "highest" = f32 MXU
    # passes (scatter-add-exact numerics), "default" = bf16 multiplies with
    # f32 accumulation (~4x MXU throughput; the one-hot operand is exact
    # either way).  "auto" resolves at train() time: bf16 on the TPU pallas
    # path — the measured AUC cost is noise-level (≤1e-3, BASELINE.md r5
    # defaults table) while the wall-clock win is ~2-4x on the hot kernel —
    # f32 everywhere else (CPU dots are f32 regardless; keeping "highest"
    # there preserves scatter-exact parity in the test oracles).
    hist_precision: str = "auto"
    # Cross-shard histogram merge strategy for the data-parallel learner:
    # "allreduce" (every device receives all F×B histogram floats per
    # node — SURVEY §3.1 direct allreduce), "reduce_scatter" (each device
    # receives only the merged histograms for its contiguous 1/D feature
    # slice, finds its local best split, and a tiny per-node candidate
    # allgather selects the global winner — LightGBM/NeurIPS-2017 data-
    # parallel merge, ~D× less wire volume), or "auto" (resolved at
    # train() time by resolve_auto_config from mesh size × feature count:
    # reduce_scatter whenever the mesh has >1 device and enough features
    # to shard, allreduce otherwise).  Ignored by the voting and
    # feature-parallel learners, which have their own comm patterns.
    hist_merge: str = "auto"
    # Quantized training (ISSUE 9; Shi et al., "Quantized Training of
    # Gradient Boosting Decision Trees", NeurIPS 2022; LightGBM 4's
    # use_quantized_grad): "off" (default — bitwise-identical to the
    # pre-quantize path), "int16"/"int32" = round each row's gradient,
    # hessian and in-bag count (1[w > 0]) to integer buckets with
    # per-iteration max-abs scales and seeded stochastic rounding,
    # accumulate histograms as int32, and merge shards over an INTEGER
    # psum/psum_scatter wire of this dtype ("int16" needs attested
    # row-count headroom — ops.histogram.quantize_wire_plan picks the
    # pre-wire shift and refuses a fit whose rows × a channel's largest
    # bucket reach 2³¹; int sums are associative, so allreduce and
    # reduce_scatter merges agree bit-for-bit).  "on" = resolved to
    # "int16" by resolve_auto_config.  How many levels a channel has comes
    # from num_grad_quant_bins below (ops.histogram.quantize_levels):
    # gradients in [-bins/2, bins/2], hessians in [0, bins], the count's
    # bucket 1; not given, 127 a side and the count's bucket 64.
    # Winning splits get an f32 refinement pass, and leaf values come
    # from exact f32 sums, so AUC holds parity with the f32 path.
    hist_quantize: str = "off"
    # LightGBM's names for the same (Parameters.rst).  use_quantized_grad
    # is hist_quantize on or off: from_params sets the one from the other
    # where only one is given, and a disagreement raises.
    # num_grad_quant_bins: the levels (LightGBM's default is 4; 0 = not
    # given, the engine's 127 a side).  quant_train_renew_leaf: leaf values
    # from the exact float32 sums of the rows, which is what this engine
    # always does, so False is refused.  stochastic_rounding=False rounds
    # to nearest.
    use_quantized_grad: Optional[bool] = None
    num_grad_quant_bins: int = 0
    quant_train_renew_leaf: bool = True
    stochastic_rounding: bool = True
    # Histogram resolution of the process_local (device-eval) AUC: its
    # ~1/bins quantization can flip improvement comparisons near a plateau,
    # so distributed early stopping on metric="auc" may stop at a different
    # iteration than a single-controller run — raise to tighten at the
    # cost of a (2*bins,) f32 allreduce per eval (engine/dist_metrics).
    auc_eval_bins: int = 4096
    cat_smooth: float = 10.0
    cat_l2: float = 10.0
    # 0 = auto (UNCAPPED, resolved to max_bin): LightGBM's default cap of
    # 32 bounds the cost of its sequential sorted-category scan — a CPU
    # artifact.  The TPU candidate scan is fully vectorized over every
    # sorted prefix regardless, so the cap buys nothing and costs measured
    # AUC (~0.009 on the criteo-schema bench at 200-ish cardinalities).
    # Set an explicit value (e.g. 32) for LightGBM-matching behavior.
    max_cat_threshold: int = 0
    num_threads: int = 0  # host-side binner threads (0 = auto)
    # Checkpointed boosting (SURVEY.md §5.4 "tree list is a natural
    # incremental checkpoint"): every `checkpoint_every` iterations the
    # model string so far is written atomically to
    # `<checkpoint_dir>/model.txt`; a later train() with the same dir
    # resumes from it (continuation re-bins — thresholds come from the
    # checkpoint's own vocabulary, §5.4 "resume = load tree array + rebin").
    checkpoint_dir: Optional[str] = None
    checkpoint_every: int = 0
    # Cap on boosting iterations per DEVICE DISPATCH (0 = uncapped: the
    # whole run is one scan dispatch when nothing else chunks it).
    # Chunking is pure dispatch granularity — the scan state carries
    # across chunks, so results are identical.  Set it when a very long
    # single dispatch is undesirable: finer chunks bound time-to-first-
    # checkpoint and keep-alive behavior.
    scan_dispatch_iters: int = 0
    verbosity: int = 1

    _ALIASES = {
        "num_boost_round": "num_iterations",
        "n_iter": "num_iterations",
        "num_trees": "num_iterations",
        "num_round": "num_iterations",
        "shrinkage_rate": "learning_rate",
        "eta": "learning_rate",
        "max_leaves": "num_leaves",
        "num_leaf": "num_leaves",
        "min_data": "min_data_in_leaf",
        "min_child_samples": "min_data_in_leaf",
        "min_sum_hessian": "min_sum_hessian_in_leaf",
        "min_child_weight": "min_sum_hessian_in_leaf",
        "reg_alpha": "lambda_l1",
        "reg_lambda": "lambda_l2",
        "sub_row": "bagging_fraction",
        "subsample": "bagging_fraction",
        "subsample_freq": "bagging_freq",
        "sub_feature": "feature_fraction",
        "colsample_bytree": "feature_fraction",
        "boosting_type": "boosting",
        "boost": "boosting",
        "early_stopping_rounds": "early_stopping_round",
        "unbalance": "is_unbalance",
        "application": "objective",
        "loss": "objective",
    }

    @classmethod
    def from_params(cls, params: dict) -> "TrainConfig":
        import warnings

        fields = {f.name for f in dataclasses.fields(cls)}
        kwargs, unknown = {}, []
        for k, v in params.items():
            k = cls._ALIASES.get(k, k)
            if k in fields:
                kwargs[k] = v
            else:
                unknown.append(k)
        if unknown:
            # LightGBM logs "Unknown parameter"; surface typos the same way.
            warnings.warn(f"Unknown training parameter(s) ignored: {sorted(unknown)}")
        if "hist_quantize" not in kwargs and kwargs.get("use_quantized_grad") is not None:
            kwargs["hist_quantize"] = "on" if kwargs["use_quantized_grad"] else "off"
        return cls(**kwargs)

    def objective_params(self) -> dict:
        return {
            "sigmoid": self.sigmoid,
            "alpha": self.alpha,
            "fair_c": self.fair_c,
            "poisson_max_delta_step": self.poisson_max_delta_step,
            "tweedie_variance_power": self.tweedie_variance_power,
            "num_class": self.num_class,
            "label_gain": self.label_gain,
            "max_position": self.max_position,
        }


class Dataset:
    """Training data container (the moral analog of LightGBM's ``Dataset``
    built per executor task from partition rows — SURVEY.md §3.1
    ``generateDataset``).

    Like LightGBM's Dataset — which quantizes features ONCE at construction
    and reuses the binned matrix across every subsequent training call —
    this container caches the fitted :class:`BinMapper` (per bin-config) and
    the binned matrix (per mapper), so repeated ``train()`` calls on the
    same Dataset skip the host binning pass entirely.

    **Held on the device** between fits, one entry each, replaced and never
    added to: the padded binned matrix (``_dev_bins_cache``, keyed by the
    mapper, the padding and the placement), a ranking fit's query plan
    (``_rank_plan_cache``), and the fit's per-row state
    (``_row_state_cache``: labels, weights, row mask and init scores, made
    by ``_fit_row_state``), so a second ``train()`` under the same objective
    settings and placement computes and sends nothing that follows the
    rows.  All three go with the data set, and none enters a pickle.  The
    row state is keyed by the IDENTITY of ``label``, ``weight`` and
    ``init_score``: assigning a new array is seen.  A write into one of
    them cannot be seen, so it is refused: storing the entry makes the three
    arrays read-only (``setflags(write=False)``: a later write raises
    ``ValueError``; a caller who passed float64 arrays handed over those
    very arrays).  What stays unseen: a write through another view of the
    same memory, such as the array ``label`` was sliced from.
    """

    def __init__(
        self,
        X: np.ndarray,
        label: np.ndarray,
        weight: Optional[np.ndarray] = None,
        group: Optional[np.ndarray] = None,
        init_score: Optional[np.ndarray] = None,
    ):
        self.X = np.ascontiguousarray(X, dtype=np.float64)
        self.label = np.asarray(label, dtype=np.float64)
        self.weight = None if weight is None else np.asarray(weight, dtype=np.float64)
        self.group = None if group is None else np.asarray(group, dtype=np.int64)
        self.init_score = (
            None if init_score is None else np.asarray(init_score, dtype=np.float64)
        )
        self.num_rows, self.num_features = self.X.shape
        self._mapper_cache: Dict[Tuple, BinMapper] = {}
        self._bins_cache: Dict[int, np.ndarray] = {}
        self._dev_bins_cache: Dict[Tuple, object] = {}  # padded device copies
        self._rank_plan_cache: Dict[Tuple, object] = {}  # (RankPlan, device arrays)
        self._row_state_cache: Dict[Tuple, object] = {}  # (host arrays keyed on, _RowState)
        self._cache_refs: List[BinMapper] = []  # pin ids used as cache keys

    def __getstate__(self):
        # No cache enters a pickle (Datasets ride inside pickled estimator
        # params in AutoML flows): device arrays don't serialize, binned
        # matrices would bloat the payload, and _bins_cache keys are id()s
        # that a new process would recycle onto unrelated mappers.
        state = dict(self.__dict__)
        state["_mapper_cache"] = {}
        state["_bins_cache"] = {}
        state["_dev_bins_cache"] = {}
        state["_rank_plan_cache"] = {}
        state["_row_state_cache"] = {}
        state["_cache_refs"] = []
        return state

    def fitted_mapper(self, cfg: "TrainConfig") -> BinMapper:
        """The BinMapper for this dataset under ``cfg``'s binning params,
        fit on first use (LightGBM bins at Dataset construction; lazy here
        so ``bin_mapper``-supplying callers never pay it)."""
        # num_threads is host parallelism only — the fitted thresholds are
        # deterministic in the input, so it must not key (or evict) the cache.
        key = (cfg.max_bin, tuple(cfg.categorical_feature), cfg.seed)
        bm = self._mapper_cache.get(key)
        if bm is None:
            # One fit path for every consumer: the full-pass branch of the
            # binning authority (ops/binning.BinningAuthority) — streamed
            # datasets take its from_sketch branch instead.
            from mmlspark_tpu.ops.binning import BinningAuthority

            bm = BinningAuthority.fit(
                self.X,
                max_bin=cfg.max_bin,
                categorical_features=tuple(cfg.categorical_feature),
                seed=cfg.seed,
                threads=cfg.num_threads,
            ).mapper
            self._mapper_cache = {key: bm}  # size-1: sweeps must not pin all
        return bm

    def pin_mapper(self, bin_mapper: BinMapper, cfg: "TrainConfig") -> None:
        """Pin an EXTERNAL mapper as this dataset's fitted mapper under
        ``cfg``'s binning params — the shared-authority hook: a fleet of
        per-tenant datasets binned through one ``BinningAuthority``
        (``engine/multi_train``) pins it here so a standalone ``train()``
        on any of them bins identically to the stacked run."""
        key = (cfg.max_bin, tuple(cfg.categorical_feature), cfg.seed)
        self._mapper_cache = {key: bin_mapper}

    def binned(self, bin_mapper: BinMapper) -> np.ndarray:
        """This dataset's rows under ``bin_mapper``, cached for the MOST
        RECENT mapper instance (mappers are fit-once/immutable by
        contract).  Size-1 on purpose: each entry is a full n×F matrix, and
        a hyperparameter sweep over binning configs must not pin one copy
        per config (the common case — many train() calls, one mapper —
        still always hits)."""
        key = id(bin_mapper)
        bins = self._bins_cache.get(key)
        if bins is None:
            bins = bin_mapper.transform(self.X)
            self._bins_cache = {key: bins}
            self._dev_bins_cache = {}
            self._cache_refs = [bin_mapper]  # keep id() stable while cached
        return bins


def _pad_rows(arr, n_pad: int, value=0):
    # Accepts numpy OR device arrays: a StreamedDataset's binned matrix is
    # already on device, and pulling it to host just to pad would undo the
    # out-of-core ingestion (ING001's whole point).
    if n_pad == 0:
        return arr
    pad_shape = (n_pad,) + arr.shape[1:]
    if isinstance(arr, np.ndarray):
        return np.concatenate(
            [arr, np.full(pad_shape, value, dtype=arr.dtype)], axis=0
        )
    return jnp.concatenate(
        [arr, jnp.full(pad_shape, value, dtype=arr.dtype)], axis=0
    )


def _pad_cols(arr, f_pad: int):
    """Right-pad feature columns with zeros (numpy or device array)."""
    if f_pad == 0:
        return arr
    if isinstance(arr, np.ndarray):
        return np.pad(arr, ((0, 0), (0, f_pad)))
    return jnp.pad(arr, ((0, 0), (0, f_pad)))


# Padding fill per Tree field when concatenating forests whose num_leaves
# budgets differ (warm start): inactive split slots are -1, the rest 0.
_TREE_PAD_FILL = {"split_leaf": -1}


def _concat_forests(old: Tree, new: Tree) -> Tree:
    """Stack two (T, K, ...) tree-array forests along T, padding the
    split/leaf axes to the larger budget."""

    def cat(field: str, a, b):
        a, b = np.asarray(a), np.asarray(b)
        # Budget axis: last for (T, K, S)/(T, K, L) fields, -2 for
        # cat_threshold's (T, K, S, B).  B (bin count) always matches:
        # warm start pins the BinMapper.
        axis = -2 if field == "cat_threshold" else -1
        if a.ndim >= 3 and a.shape[axis] != b.shape[axis]:
            target = max(a.shape[axis], b.shape[axis])
            fill = _TREE_PAD_FILL.get(field, 0)

            def pad(x):
                if x.shape[axis] == target:
                    return x
                widths = [(0, 0)] * x.ndim
                widths[axis % x.ndim] = (0, target - x.shape[axis])
                return np.pad(x, widths, constant_values=fill)

            a, b = pad(a), pad(b)
        return np.concatenate([a, b], axis=0)

    return Tree(*[cat(f, getattr(old, f), getattr(new, f)) for f in Tree._fields])


class Booster:
    """A trained forest: stacked tree arrays + binning state.

    Parity surface: the reference's ``LightGBMBooster`` wrapper
    (UPSTREAM:.../lightgbm/LightGBMBooster.scala — SURVEY.md §2.3: score,
    predictLeaf, saveNativeModel, getFeatureImportances).
    """

    def __init__(
        self,
        trees: Tree,  # arrays with leading (T, K) axes
        tree_weights: np.ndarray,  # (T,)
        bin_mapper: BinMapper,
        config: TrainConfig,
        best_iteration: int = -1,
        average_output: bool = False,
    ):
        self.trees = trees
        self.tree_weights = np.asarray(tree_weights, dtype=np.float64)
        self.bin_mapper = bin_mapper
        self.config = config
        self.best_iteration = best_iteration
        self.average_output = average_output
        self.objective = get_objective(config.objective, **config.objective_params())
        self.evals_result: Dict[str, Dict[str, List[float]]] = {}
        # Training-time reference histograms for the serving drift monitor
        # (plain dict, set by train(); rides pickles, persisted as
        # quality_baseline.json by the model facades' _save_extra).
        self.quality_baseline: Optional[dict] = None
        self._predict_cache: Dict[Tuple, callable] = {}
        # Device-resident predict state, all keyed by T (used iterations)
        # and built at most once per instance: continued training
        # constructs a NEW Booster, so per-instance caching needs no
        # invalidation hook.  None of it enters pickles (__getstate__).
        self._dev_slices: Dict[int, Tuple[Tree, jnp.ndarray]] = {}
        self._packed_forests: Dict[int, object] = {}
        self._pallas_forests: Dict[int, object] = {}
        self._device_binner = None
        self._bin_authority = None
        self._predict_warm: set = set()
        self._aot_execs: Dict[Tuple, object] = {}

    def _host_trees(self) -> Tree:
        """Host (numpy) copy of the forest, materialized LAZILY via ONE
        bit-packed fetch and cached.

        train() keeps the forest device-resident (predict consumes it
        there; fetching + re-uploading cost ~3 RPC latencies per fit on
        remote-dispatch links), so export/pickle/importance paths pull it
        through here instead of per-field ``np.asarray`` (10 fetch RPCs).
        """
        if getattr(self, "_trees_np", None) is None:
            if isinstance(self.trees.split_leaf, np.ndarray):
                self._trees_np = self.trees
            else:
                # cat_threshold planes are ~97% of the packed bits but all
                # False for non-categorical models: trust the config when
                # it declares categoricals; otherwise confirm with one
                # small split_cat fetch (a booster loaded from a model
                # string may carry cat splits its config never mentions).
                has_cats = bool(
                    getattr(self.config, "categorical_feature", ())
                ) or bool(np.asarray(self.trees.split_cat).any())
                self._trees_np = _fetch_tree_chunks([self.trees], has_cats)[0]
        return self._trees_np

    # Boosters ride inside pickled ComplexParams (e.g. a fitted model nested
    # in BestModel/TrainedClassifierModel); the jitted-closure cache and
    # device arrays must not enter the pickle (found by the registry fuzz).
    def __getstate__(self):
        state = dict(self.__dict__)
        state["_predict_cache"] = {}
        state.pop("_native_predictor", None)  # ctypes handle: rebuild lazily
        state.pop("_trees_np", None)
        # device-resident predict caches: rebuild lazily after unpickle
        state["_dev_slices"] = {}
        state["_packed_forests"] = {}
        state["_pallas_forests"] = {}
        state["_device_binner"] = None
        state["_bin_authority"] = None
        state["_predict_warm"] = set()
        state["_aot_execs"] = {}
        state["trees"] = self._host_trees()
        return state

    def __setstate__(self, state):
        self.__dict__.update(state)
        # pickles from before the packed-forest PR lack the predict caches
        self.__dict__.setdefault("_dev_slices", {})
        self.__dict__.setdefault("_packed_forests", {})
        self.__dict__.setdefault("_pallas_forests", {})
        self.__dict__.setdefault("_device_binner", None)
        self.__dict__.setdefault("_bin_authority", None)
        self.__dict__.setdefault("_predict_warm", set())
        self.__dict__.setdefault("_aot_execs", {})
        self.__dict__.setdefault("quality_baseline", None)
        # the pickle carries host arrays (__getstate__): keep them as the
        # _host_trees copy so a fresh process's predict cold never pays a
        # device fetch program for arrays it already had on host
        if isinstance(self.trees.split_leaf, np.ndarray):
            self._trees_np = self.trees
        self.trees = Tree(*[jnp.asarray(a) for a in self.trees])

    # -- introspection ---------------------------------------------------
    @property
    def num_iterations(self) -> int:
        return int(self.trees.split_leaf.shape[0])

    @property
    def num_class(self) -> int:
        return int(self.trees.split_leaf.shape[1])

    @property
    def num_features(self) -> int:
        return self.bin_mapper.num_features

    def _used_iters(self, num_iteration: Optional[int]) -> int:
        if num_iteration is not None and num_iteration > 0:
            return min(num_iteration, self.num_iterations)
        if self.best_iteration >= 0:
            return self.best_iteration + 1
        return self.num_iterations

    # -- prediction ------------------------------------------------------
    def _forest_fn(self, T: int, kind: str):
        key = (T, kind)
        if key not in self._predict_cache:
            obs.inc("predict.scorer_builds")
            nb = self.bin_mapper.num_bins

            if kind == "raw":

                def fn(trees, weights, bins):
                    def per_class(tree_k):
                        def body(acc, tw):
                            tree, w = tw
                            return acc + w * predict_tree_binned(tree, bins, nb), None

                        out, _ = jax.lax.scan(
                            body, jnp.zeros(bins.shape[0], jnp.float32), (tree_k, weights)
                        )
                        return out

                    # trees arrays: (T, K, ...) → vmap over K
                    return jax.vmap(per_class, in_axes=(1,))(trees)  # (K, n)

            else:  # leaf indices

                def fn(trees, weights, bins):
                    def per_class(tree_k):
                        def body(_, tree):
                            return None, predict_tree_leaf_binned(tree, bins, nb)

                        _, leaves = jax.lax.scan(body, None, tree_k)
                        return leaves  # (T, n)

                    return jax.vmap(per_class, in_axes=(1,))(trees)  # (K, T, n)

            self._predict_cache[key] = jax.jit(fn)
        return self._predict_cache[key]

    def _slice_trees(self, T: int) -> Tree:
        return Tree(*[a[:T] for a in self.trees])

    def _dev_forest(self, T: int) -> Tuple[Tree, jnp.ndarray]:
        """Device-resident (trees, weights) slice for the legacy scan
        path, built ONCE per T.  The seed re-sliced the tree arrays and
        re-uploaded the f32 weights on every predict call (the per-call
        forest re-upload bug); repeat predicts now do zero host→device
        model transfer even on the scan backend."""
        cached = self._dev_slices.get(T)
        if cached is None:
            cached = (
                Tree(*[jnp.asarray(a[:T]) for a in self.trees]),
                jnp.asarray(self.tree_weights[:T], dtype=jnp.float32),
            )
            self._dev_slices[T] = cached
        return cached

    def _has_cat_splits(self) -> bool:
        """Does any tree carry a categorical (membership) split?  Gates
        the numeric-only pallas predict kernel."""
        if getattr(self, "_has_cats", None) is None:
            self._has_cats = bool(
                getattr(self.config, "categorical_feature", ())
            ) or bool(np.asarray(self.trees.split_cat).any())
        return self._has_cats

    def _resolved_predict_backend(self, T: int) -> str:
        """The backend THIS predict call runs on: config.predict_backend
        re-resolved against jax.default_backend(), with the pallas kernel
        additionally gated on its numeric-only + SMEM-budget support."""
        from mmlspark_tpu.engine.forest import resolve_predict_backend

        requested = getattr(self.config, "predict_backend", "auto") or "auto"
        resolved = resolve_predict_backend(
            requested, has_cats=self._has_cat_splits()
        )
        if resolved in ("pallas", "pallas_interpret"):
            # deferred: importing the pallas stack costs ~100 ms of pure
            # Python module load — the packed cold path must not pay it
            from mmlspark_tpu.ops.pallas_predict import pallas_supported

            if not pallas_supported(
                T, self.num_class, int(self.trees.split_leaf.shape[-1]), False
            ):
                resolved = "packed"
        return resolved

    def _model_fingerprint(self, T: int) -> str:
        """Content hash of the forest slice actually used at ``T``
        iterations (tree arrays + weights + bin count) — the ``pft-*``
        artifact key half that ties a packed-forest blob to exactly this
        model's bytes."""
        import hashlib

        host = self._host_trees()
        h = hashlib.sha256()
        for field in host:
            a = np.ascontiguousarray(np.asarray(field)[:T])
            h.update(str((a.shape, a.dtype)).encode())
            h.update(a.tobytes())
        w = np.ascontiguousarray(self.tree_weights[:T])
        h.update(w.tobytes())
        h.update(str(int(self.bin_mapper.num_bins)).encode())
        return h.hexdigest()[:32]

    def _packed_forest(self, T: int):
        """Device-resident packed SoA node table (engine/forest), built +
        uploaded once per T and cached.

        Warm-from-disk: the per-tree Python pack loop is ~40 ms for a
        200-tree forest — real money against the millisecond cold-start
        budget — so the host arrays are stashed as a ``pft-*`` jit_cache
        artifact keyed by the model content hash; a second process
        reloads them in ~1 ms and goes straight to the upload.
        """
        from mmlspark_tpu.core import jit_cache as _jc
        from mmlspark_tpu.engine import forest as _forest

        pf = self._packed_forests.get(T)
        if pf is None:
            import pickle

            obs.inc("predict.scorer_builds")

            key = _jc.aot_fingerprint(
                "pft", {"model": self._model_fingerprint(T)}
            )
            data = _jc.load_pft(key)
            if data is not None:
                try:
                    pf = _forest.packed_forest_from_state(data)
                except (pickle.UnpicklingError, EOFError):
                    pf = None  # torn blob: re-pack and overwrite below
            if pf is None:
                pf = _forest.pack_forest(
                    self._host_trees(), self.tree_weights, T,
                    self.bin_mapper.num_bins,
                )
                _jc.save_pft(key, _forest.packed_forest_state(pf))
            self._packed_forests[T] = pf
        return pf

    def _finalize_fn(self, T: int, raw_score: bool):
        """One jitted program for the score post-processing (average
        division + objective link).  Eager op-by-op dispatch here costs
        ~80 ms of first-call compiles that the persistent cache never
        sees — as ONE jitted program it compiles once ever per machine
        and loads from the jax cache in milliseconds on every later
        process, keeping the warm-from-disk predict cold in budget."""
        key = ("finalize", T, bool(raw_score))
        fn = self._predict_cache.get(key)
        if fn is None:
            denom = float(max(T, 1)) if self.average_output else None
            transform = None if raw_score else self.objective.transform

            def _finalize(r):
                if denom is not None:
                    r = r / denom
                return r if transform is None else transform(r)

            fn = jax.jit(_finalize)
            self._predict_cache[key] = fn
        return fn

    def _packed_raw_rows_exec(self, T: int, rows):
        """The compiled resident serving program for one bucket shape —
        disk-first (``jit_cache.load_aot``), tracing + ``save_aot`` only
        on a genuine miss.

        Returns ``(executable, how)`` where ``how`` is ``None`` (already
        resident in this process), ``"from_disk"`` (deserialized — the
        millisecond path), or ``"traced"`` (paid the full lower+compile).
        Weights are runtime arguments, so the artifact key only covers
        shapes/statics: a hot-swapped model with the same forest shape
        reuses the executable outright.
        """
        from mmlspark_tpu.core import jit_cache as _jc
        from mmlspark_tpu.engine import forest as _forest

        # predict-only processes deserve the persistent cache too (the
        # score post-processing programs compile outside the AOT artifact)
        _jc.enable_compile_cache()
        pf = self._packed_forest(T)
        db = self.device_binner()
        ck = (T, tuple(rows.shape))
        exe = self._aot_execs.get(ck)
        if exe is not None:
            return exe, None
        exe, how = _jc.load_or_compile_aot(
            "packed_raw_rows",
            _forest.packed_raw_rows_meta(pf, db),
            (pf.arrays, db.arrays, rows),
            lambda: _forest.lower_packed_raw_rows(pf, db, rows),
        )
        self._aot_execs[ck] = exe
        return exe, how

    def _pallas_forest(self, T: int):
        pf = self._pallas_forests.get(T)
        if pf is None:
            from mmlspark_tpu.ops.pallas_predict import build_pallas_forest

            obs.inc("predict.scorer_builds")
            pf = build_pallas_forest(self._host_trees(), self.tree_weights, T)
            self._pallas_forests[T] = pf
        return pf

    def bin_authority(self):
        """This model's :class:`~mmlspark_tpu.ops.binning.BinningAuthority`
        — the ONE object owning the fitted edges and the f64/f32 decision
        contract.  The serve wire (``predict_padded``), host predict, and
        any re-ingestion all bin through it."""
        from mmlspark_tpu.ops.binning import BinningAuthority

        if getattr(self, "_bin_authority", None) is None:
            self._bin_authority = BinningAuthority(self.bin_mapper)
        return self._bin_authority

    def append_trees(
        self,
        source,
        num_trees: int,
        params: Optional[dict] = None,
        chunk_rows: Optional[int] = None,
        mesh=None,
    ) -> "Booster":
        """Warm-start continuation entry (the closed loop's refit path,
        ISSUE 18): return a NEW booster extending this one by
        ``num_trees`` trees trained on ``source`` — a shard source the
        streamed ingest accepts — binned through THIS booster's
        authority, with the per-iteration RNG continuing at the absolute
        fold_in schedule (tree ``T+k`` draws the key it would have drawn
        in one long run).  ``params`` overrides training params for the
        appended trees (learning_rate decay, say); binning params stay
        pinned by the continuation contract."""
        if num_trees <= 0:
            raise ValueError(f"num_trees must be positive, got {num_trees}")
        from mmlspark_tpu.data.streaming import train_streaming

        base = dataclasses.asdict(self.config)
        base.update(params or {})
        base["num_iterations"] = int(num_trees)
        # binning is pinned by the fitted mapper, which may disagree with
        # the config dataclass (facade-fit mappers carry their own max_bin)
        base["max_bin"] = int(self.bin_mapper.max_bin)
        base["categorical_feature"] = tuple(
            self.bin_mapper.categorical_features
        )
        kwargs = {} if not chunk_rows else {"chunk_rows": int(chunk_rows)}
        return train_streaming(
            base, source, init_model=self, mesh=mesh, **kwargs
        )

    def device_binner(self):
        """Uploaded-once on-device binning state (via the binning
        authority) for the raw-f32-rows serving hot path."""
        if getattr(self, "_device_binner", None) is None:
            self._device_binner = self.bin_authority().device_binner()
        return self._device_binner

    def _raw_scores_dispatch(
        self, bins: jnp.ndarray, T: int, backend: str
    ) -> jnp.ndarray:
        """(K, n) raw scores from a binned matrix on the given backend.
        Every backend runs the identical per-class f32 add sequence
        (trees in serial order), so outputs are bitwise-equal."""
        if backend == "scan":
            trees, weights = self._dev_forest(T)
            fn = self._forest_fn(T, "raw")
            obs.device.note_program(
                "booster.scorer", self.bin_mapper.num_bins, fn, (trees, weights, bins)
            )
            return fn(trees, weights, bins)
        if backend in ("pallas", "pallas_interpret"):
            from mmlspark_tpu.ops.pallas_predict import pallas_raw_scores

            return pallas_raw_scores(
                self._pallas_forest(T), jnp.asarray(bins),
                self.bin_mapper.num_bins,
                interpret=backend == "pallas_interpret",
            )
        from mmlspark_tpu.engine import forest as _forest

        return _forest.packed_raw_scores(
            self._packed_forest(T), jnp.asarray(bins)
        )

    def _raw_scores_binned(
        self, bins: jnp.ndarray, num_iteration: Optional[int] = None
    ) -> jnp.ndarray:
        """(K, n) raw scores from an already-binned matrix (skips the host
        binning pass — used by warm start, which bins once for training and
        reuses the same matrix here)."""
        T = self._used_iters(num_iteration)
        backend = self._resolved_predict_backend(T)
        # `built`: this call has to make its scorer first (a new jitted
        # function that traces again, or a forest to pack) — what
        # `predict.scorer_builds` counts.  The call returns at dispatch, so
        # the span is host time: build, trace, enqueue.
        if backend == "scan":
            built = (T, "raw") not in self._predict_cache
        elif backend in ("pallas", "pallas_interpret"):
            built = T not in self._pallas_forests
        else:
            built = T not in self._packed_forests
        with obs.span(
            "booster.score_binned", backend=backend,
            rows=int(bins.shape[0]), trees=T, built=built, **_placement(bins),
        ):
            raw = self._raw_scores_dispatch(bins, T, backend)
        if self.average_output:
            raw = raw / max(T, 1)
        return raw

    def predict(
        self,
        X: np.ndarray,
        raw_score: bool = False,
        pred_leaf: bool = False,
        num_iteration: Optional[int] = None,
    ) -> np.ndarray:
        """Batch scoring.  Replaces the reference's per-row JNI
        ``LGBM_BoosterPredictForMat`` crossing (SURVEY.md §3.2) with one
        jitted whole-batch program.  Binning stays on the host here (the
        offline float64 contract); the traversal backend is
        ``config.predict_backend`` re-resolved per call — all backends
        score bitwise-identically."""
        # API entry: normalize user input to the host f64 contract
        X = np.asarray(X, dtype=np.float64)  # analyze: ignore[PRED001]
        n = X.shape[0]
        T = self._used_iters(num_iteration)
        backend = self._resolved_predict_backend(T)
        kind = "leaf" if pred_leaf else "raw"
        key = (kind, backend, T, n)
        cold = key not in self._predict_warm
        t0 = time.perf_counter()
        with obs.span(
            "predict", rows=n, backend=backend, cold=cold,
            **obs.trace_attrs(),
        ):
            bins = jnp.asarray(self.bin_mapper.transform(X))
            if pred_leaf:
                if backend == "scan":
                    trees, weights = self._dev_forest(T)
                    leaves = self._forest_fn(T, "leaf")(trees, weights, bins)
                else:
                    from mmlspark_tpu.engine import forest as _forest

                    leaves = _forest.packed_leaf_indices(
                        self._packed_forest(T), bins
                    )
                # API exit: host ndarray is the return contract
                out = np.asarray(leaves)  # analyze: ignore[PRED001]
                K, _, _ = out.shape
                out = out.transpose(2, 1, 0).reshape(n, T * K)
            else:
                raw = self._raw_scores_dispatch(bins, T, backend)
                if self.average_output:
                    raw = raw / max(T, 1)
                if not raw_score:
                    raw = self.objective.transform(raw)
                # API exit: host ndarray is the return contract
                out = np.asarray(raw)  # analyze: ignore[PRED001]
                out = out[0] if out.shape[0] == 1 else out.T
        self._predict_warm.add(key)
        elapsed = time.perf_counter() - t0
        if obs.enabled() and elapsed > 0:
            obs.gauge("predict.rows_per_s", n / elapsed, backend=backend)
        return out

    def predict_padded(
        self,
        X: np.ndarray,
        n_valid: int,
        raw_score: bool = False,
        num_iteration: Optional[int] = None,
    ) -> np.ndarray:
        """Serving entry for padded bucket batches (mmlspark_tpu.serve).

        ``X`` has a FIXED bucket shape (B, F) where only the first
        ``n_valid`` rows are real; the tail is zero padding so repeated
        calls reuse one jitted program per bucket instead of compiling a
        fresh program for every distinct row count (the compile churn
        that kills the naive fixed-batch loop under variable traffic).
        Returns predictions for the real rows only.

        On the packed/pallas backends this is the RESIDENT hot path: the
        batch is shipped as raw **float32** rows and binned on device
        (ops/device_binning — f64-exact boundary compares for every
        f32-representable input), so nothing touches the host BinMapper
        and the model/bin-edge uploads happened once at build time.  The
        f32 row contract is the serving interface (serve/README.md);
        inputs carrying float64 precision beyond f32 round to it here.
        The scan backend keeps the seed's host-binned f64 path.
        """
        T = self._used_iters(num_iteration)
        backend = self._resolved_predict_backend(T)
        if backend == "scan":
            out = self.predict(
                np.asarray(X, dtype=np.float64),  # analyze: ignore[PRED001]
                raw_score=raw_score,
                num_iteration=num_iteration,
            )
            return out[: int(n_valid)]
        # API entry: the serving wire contract is raw f32 rows
        rows = jnp.asarray(
            np.ascontiguousarray(X, dtype=np.float32)  # analyze: ignore[PRED001]
        )
        key = ("padded", backend, T, rows.shape[0], bool(raw_score))
        cold = key not in self._predict_warm
        t0 = time.perf_counter()
        with obs.span(
            "predict", rows=int(n_valid), bucket=int(rows.shape[0]),
            backend=backend, cold=cold, **obs.trace_attrs(),
        ) as sp:
            if backend in ("pallas", "pallas_interpret"):
                from mmlspark_tpu.ops.pallas_predict import pallas_raw_scores

                bins = self.device_binner().transform(rows)
                raw = pallas_raw_scores(
                    self._pallas_forest(T), bins, self.bin_mapper.num_bins,
                    interpret=backend == "pallas_interpret",
                )
            else:
                # AOT-resident hot path: disk-deserialized executable when
                # a prior process compiled this bucket shape, traced (and
                # persisted) otherwise.  The span's ``cold`` attr upgrades
                # from a bool to "from_disk"/"traced" so obs can tell a
                # millisecond deserialize-warm from a full-compile warm.
                exe, how = self._packed_raw_rows_exec(T, rows)
                if how is not None:
                    try:
                        sp.attrs["cold"] = how
                    except (AttributeError, TypeError):
                        pass
                pf = self._packed_forests[T]
                raw = exe(pf.arrays, self.device_binner().arrays, rows)
            raw = self._finalize_fn(T, raw_score)(raw)
            # API exit: host ndarray is the return contract
            out = np.asarray(raw)  # analyze: ignore[PRED001]
            out = out[0] if out.shape[0] == 1 else out.T
        self._predict_warm.add(key)
        elapsed = time.perf_counter() - t0
        if obs.enabled() and elapsed > 0:
            obs.gauge(
                "predict.rows_per_s", int(n_valid) / elapsed, backend=backend
            )
        return out[: int(n_valid)]

    def prewarm_predict(
        self, batch_sizes: Sequence[int], raw_score: bool = False
    ) -> None:
        """Warm the predict program for each serving bucket shape up
        front, so a serving process answers its first real request
        without a compile stall.  On the packed backend this
        deserializes ``aot-*`` executables from the jit_cache dir when a
        prior process compiled the same shapes (milliseconds per bucket
        — the replica warm-from-disk path, serve/README.md); only
        genuinely new shapes pay a trace+compile, and those are
        persisted for the next replica."""
        from mmlspark_tpu.core.jit_cache import enable_compile_cache

        enable_compile_cache()
        F = self.num_features
        for b in batch_sizes:
            with obs.span("serve.prewarm", bucket=int(b)):
                self.predict_padded(
                    np.zeros((int(b), F)), 1, raw_score=raw_score
                )

    def feature_importance(self, importance_type: str = "split") -> np.ndarray:
        """Split-count or total-gain importances (parity:
        ``LightGBMBooster.getFeatureImportances`` — SURVEY.md §2.3)."""
        host = self._host_trees()
        feats = np.asarray(host.split_feat).reshape(-1)
        active = np.asarray(host.split_leaf).reshape(-1) >= 0
        F = self.num_features
        out = np.zeros(F)
        if importance_type == "split":
            np.add.at(out, feats[active], 1.0)
        else:
            gains = np.asarray(host.split_gain).reshape(-1)
            np.add.at(out, feats[active], gains[active])
        return out

    # -- persistence (LightGBM text format lives in ops/model_string) ----
    def save_model_string(self, num_iteration: Optional[int] = None) -> str:
        from mmlspark_tpu.ops.model_string import booster_to_string

        return booster_to_string(self, num_iteration)

    @staticmethod
    def from_model_string(s: str) -> "Booster":
        from mmlspark_tpu.ops.model_string import booster_from_string

        return booster_from_string(s)

    def native_predictor(self):
        """Host-side C++ single-row scorer over this model (serving path).

        The XLA ``predict`` is right for batched DataFrame scoring but
        pays a dispatch round-trip per call; HTTP serving of one request
        wants the native walker (~µs/row) — the reference's
        ``LGBM_BoosterPredictForMatSingleRow`` parity (SURVEY.md §3.2,
        §7.1(c)).  Falls back to a Python walker without a toolchain."""
        from mmlspark_tpu.native.predictor import NativePredictor

        if getattr(self, "_native_predictor", None) is None:
            self._native_predictor = NativePredictor(self.save_model_string())
        return self._native_predictor


# ---------------------------------------------------------------------------
# Sampling helpers (bagging / GOSS / feature_fraction)
# ---------------------------------------------------------------------------
def _bag_weights(key, cfg: TrainConfig, valid_mask):
    """Per-row bag weight of LightGBM's bagging for this iteration: 1 on the
    rows drawn (each valid row with probability ``bagging_fraction``), 0
    elsewhere.  GOSS draws its own sample from the iteration's gradients
    (:func:`goss_sample`)."""
    frac = cfg.bagging_fraction
    if frac < 1.0:
        u = jax.random.uniform(key, valid_mask.shape)
        return (valid_mask & (u < frac)).astype(jnp.float32)
    return valid_mask.astype(jnp.float32)


# ---------------------------------------------------------------------------
# Gradient one-side sampling (GOSS: Ke et al. 2017, Algorithm 2)
# ---------------------------------------------------------------------------
GOSS_TAG = 0x6055  # folded into an iteration's sampling key for the rest's draw


def goss_counts(n_valid: int, top_rate: float, other_rate: float) -> Tuple[int, int]:
    """``(top, rest)``: the rows of a GOSS sample of ``n_valid`` rows, fixed
    by the row count alone: ``⌊top_rate·n⌋`` (at least one) of largest
    gradient, and ``⌊other_rate·n⌋`` of the others."""
    top = min(max(1, math.floor(top_rate * n_valid)), n_valid)
    return top, min(math.floor(other_rate * n_valid), n_valid - top)


def goss_amplification(top_rate: float, other_rate: float) -> float:
    """The weight of a rest row, ``(1 - top_rate) / other_rate`` in float32."""
    return float(np.float32((1.0 - top_rate) / max(other_rate, 1e-12)))


def _largest(keys, eligible, k):
    """The ``k`` eligible rows of largest ``uint32`` key, ties to the lower
    row index, with no sort: the k-th largest key is found bit by bit from
    32 counts over the rows, and the rows AT it are taken in row order by a
    running count."""

    def bit(i, t):
        cand = t | (jnp.uint32(0x80000000) >> i.astype(jnp.uint32))
        return jnp.where(jnp.sum(eligible & (keys >= cand)) >= k, cand, t)

    t = jax.lax.fori_loop(0, 32, bit, jnp.uint32(0))
    above = eligible & (keys > t)
    at = eligible & (keys == t)
    return above | (at & (jnp.cumsum(at) <= k - jnp.sum(above)))


def goss_sample(grad, valid_mask, key, k_top: int, k_rest: int, amp: float):
    """One iteration's GOSS weights, ``(n,)`` float32: 1 on the ``k_top``
    valid rows of largest ``Σ_k |g_k|`` (``grad`` is ``(K, n)``), ``amp``
    on ``k_rest`` of the other valid rows, those of smallest
    ``u = uniform(fold_in(key, GOSS_TAG))``, 0 elsewhere; ties go to the
    lower row index on both sides, so the counts are exact."""
    with jax.named_scope("goss_select"):
        s = jnp.sum(jnp.abs(grad), axis=0)  # >= 0: its bits order as its values
        top = _largest(jax.lax.bitcast_convert_type(s, jnp.uint32), valid_mask, k_top)
        u = jax.random.uniform(jax.random.fold_in(key, GOSS_TAG), valid_mask.shape)
        rest = _largest(~jax.lax.bitcast_convert_type(u, jnp.uint32), valid_mask & ~top, k_rest)
        return jnp.where(top, 1.0, jnp.where(rest, amp, 0.0)).astype(jnp.float32)


def goss_rows(m: int, chunk: int) -> int:
    """Rows of the buffer a sample of ``m`` rows is gathered into: whole
    histogram chunks where it spans more than one (the chunk loop's shape)."""
    return m if m <= chunk else -(-m // chunk) * chunk


def goss_compact(bins, grad, hess, bag, rows: int, backend: str = "scatter"):
    """The sample (rows of weight > 0) gathered in row order into buffers of
    ``rows`` rows: ``(bins (rows, F), grad (K, rows), hess (K, rows), bag
    (rows,))``; the buffer's rows past the sample hold weight 0.  On the
    ``pallas`` backend one streaming kernel does it
    (``ops/pallas_compact.py``: a TPU gathers at 30-45 ns an index);
    elsewhere XLA's scatter and gathers."""
    with jax.named_scope("goss_compact"):
        if backend == "pallas":
            from mmlspark_tpu.ops.pallas_compact import compact_rows

            K = grad.shape[0]
            bins_c, vals_c = compact_rows(
                bins.T, jnp.concatenate([grad, hess, bag[None, :]]), rows
            )
            return bins_c.T, vals_c[:K], vals_c[K : 2 * K], vals_c[2 * K]
        n = bag.shape[0]
        take = bag > 0
        rows_n = jnp.arange(n, dtype=jnp.int32)
        # each row its own slot: the sample's in order, the others past the end
        slot = jnp.where(take, jnp.cumsum(take) - 1, rows + rows_n)
        idx = jnp.zeros(rows, jnp.int32).at[slot].set(rows_n, mode="drop", unique_indices=True)
        held = jnp.arange(rows) < jnp.sum(take)
        idx = jnp.where(held, idx, n - 1)  # the pad reads the last row: idx stays sorted

        def gather(x, axis):
            return jnp.take(x, idx, axis=axis, mode="clip", indices_are_sorted=True)

        return (
            gather(bins, 0), gather(grad, 1), gather(hess, 1),
            jnp.where(held, gather(bag, 0), 0.0),
        )


def _feature_mask(key, F: int, fraction: float):
    if fraction >= 1.0:
        return jnp.ones(F, bool)
    k = max(1, int(math.ceil(F * fraction)))
    u = jax.random.uniform(key, (F,))
    order = jnp.argsort(-u)
    rank = jnp.argsort(order)
    return rank < k


def _leaf_delta(tree: Tree, leaf_ids: jnp.ndarray) -> jnp.ndarray:
    """delta[k] = leaf_value[k][leaf_ids[k]] for the (K, L) leaf values
    and (K, n) leaf ids of one iteration's trees: the float32 the stored
    model holds, on every backend and at every ``n`` (the gather lowering
    cost 8.0ns a row on v5e, ``_leaf_lookup``'s select form ~0.5ns).
    Its device region is ``leaf_delta``, ``class_update`` where K > 1."""
    with jax.named_scope("class_update" if tree.leaf_value.shape[0] > 1 else "leaf_delta"):
        return jax.vmap(_leaf_lookup)(tree.leaf_value, leaf_ids)


def _class_scope(K: int, name: str):
    """``jax.named_scope(name)`` round what a fit of K > 1 trees an
    iteration adds (``class_grad``: the objective's (K, n) gradient;
    ``class_update``: the K-row score update); a K = 1 fit's program is
    left as it was."""
    return jax.named_scope(name) if K > 1 else contextlib.nullcontext()


# ---------------------------------------------------------------------------
# Packed single-fetch transfers.  Every array fetched pays one
# device→host round trip, so a pytree headed for the host is first packed
# device-side into ONE uint32 vector — numeric fields bitcast, bool fields
# bit-packed 32× (cat_threshold is 97% of a chunk's bits) — fetched once,
# and unpacked with numpy views.  (What this saves on a local chip: not
# measured.)
# ---------------------------------------------------------------------------
@jax.jit
def _pack_u32(pt):
    parts = []
    for a in jax.tree_util.tree_leaves(pt):
        if a.dtype == jnp.bool_:
            flat = a.ravel()
            flat = jnp.pad(flat, (0, (-flat.size) % 32))
            w = flat.reshape(-1, 32).astype(jnp.uint32)
            parts.append(
                (w << jnp.arange(32, dtype=jnp.uint32)[None, :]).sum(
                    axis=1, dtype=jnp.uint32
                )
            )
        else:
            parts.append(jax.lax.bitcast_convert_type(a, jnp.uint32).ravel())
    return jnp.concatenate(parts) if parts else jnp.zeros((0,), jnp.uint32)


def fetch_packed(pt):
    """``jax.device_get(pt)`` via one packed uint32 transfer (bit-exact)."""
    leaves, treedef = jax.tree_util.tree_flatten(pt)
    if any(a.dtype != jnp.bool_ and a.dtype.itemsize != 4 for a in leaves):
        return jax.device_get(pt)  # e.g. x64 arrays: not 32-bit packable
    packed = np.asarray(_pack_u32(pt))
    out, off = [], 0
    for a in leaves:
        n = a.size
        if a.dtype == jnp.bool_:
            nw = (n + 31) // 32
            bits = np.unpackbits(
                packed[off : off + nw].view(np.uint8), bitorder="little"
            )[:n]
            out.append(bits.astype(bool).reshape(a.shape))
            off += nw
        else:
            out.append(
                packed[off : off + n]
                .view(np.dtype(a.dtype.name))
                .reshape(a.shape)
            )
            off += n
    return jax.tree_util.tree_unflatten(treedef, out)


def _fetch_tree_chunks(chunks: List[Tree], has_cats: bool) -> List[Tree]:
    """One packed fetch for a whole list of stacked-Tree chunks; without
    categoricals the all-False ``cat_threshold`` planes (the bulk of the
    bits) are dropped device-side and rebuilt host-side."""
    if not has_cats:
        shapes = [c.cat_threshold.shape for c in chunks]
        slim = [c._replace(cat_threshold=jnp.zeros((0,), bool)) for c in chunks]
        fetched = fetch_packed(slim)
        return [
            c._replace(cat_threshold=np.zeros(s, bool))
            for c, s in zip(fetched, shapes)
        ]
    return fetch_packed(chunks)


# ---------------------------------------------------------------------------
# The training loop
# ---------------------------------------------------------------------------
_PARALLEL_LEARNERS = (
    "data", "data_parallel", "voting", "voting_parallel",
    "feature", "feature_parallel",
)

# Jitted whole-run scan programs cached ACROSS train() calls (bounded FIFO):
# jax.jit caches per function object, so without this every fit (AutoML
# candidate, CV fold, steady-state run) re-traces the scan body for seconds.
# An entry is ``(program, notes)``; the notes are :func:`_grow_ledgers`'.
_SCAN_CACHE: Dict[Tuple, tuple] = {}
_SCAN_CACHE_MAX = 16

# Device copies of the packed per-iteration xs (keys/bag-keys/iteration
# index) cached across train() calls: the array derives deterministically
# from (seed, bagging config, iteration range), so repeated fits (CV
# folds, AutoML candidates, benches) reuse the same xs bytes instead of
# uploading them again.  (What this saves on a local chip: not measured.)
_XS_CACHE: Dict[Tuple, object] = {}
_XS_CACHE_MAX = 8

# DART's scan path carries a (num_iterations, K, n) per-tree prediction
# buffer; beyond this element budget it falls back to the legacy
# per-iteration loop (tests monkeypatch this to force the legacy path).
_DART_SCAN_MAX_ELS = 128_000_000

# The AOT trace cache engages only for programs big enough that tracing
# hurts (rows × iterations): exporting costs one extra serialize per
# first-ever program, which would tax small fits/test suites for no win.
_TRACE_CACHE_MIN_WORK = 1 << 21

# split_batch="auto" (0) resolution on the TPU pallas lossguide path.
# Swept on BOTH bench shapes (262k rows, 63 leaves, BASELINE.md r5
# defaults table + k-sweep): k=8 matches k=12's wall inside run variance
# (catmix 1.33 vs 1.34 s; numeric 1.37 vs 1.34 s) while recovering
# +2e-4 (numeric) to +7e-4 (catmix) train-AUC — halving the batching
# trade vs exact lossguide.  Larger k is strictly worse (k=16: 1.46 s
# AND -1.5e-3 AUC; k=24: 2.24 s), smaller k pays wall (k=6: 1.65 s).
_AUTO_SPLIT_BATCH = 8


def resolve_auto_config(
    cfg: "TrainConfig",
    n: int,
    backend: str,
    *,
    num_devices: int = 1,
    num_features: int = 0,
    num_bins: int = 0,
) -> "TrainConfig":
    """Resolve every "auto" knob to the value train() will run with.

    The default configuration IS the benchmarked configuration (r4 verdict
    weak #1): a bare ``train(params, ds)`` / facade ``fit()`` must land on
    the headline path without opt-in knobs, and anything quality-affecting
    the auto picks is measured in BASELINE.md's r5 defaults table.  Pure
    function of (cfg, row count, jax backend, mesh/feature geometry) so
    the facade tests can assert the resolution without TPU hardware.

    ``num_devices``/``num_features``/``num_bins`` feed the ``hist_merge``
    resolution (mesh size × feature count); callers that never reach the
    distributed grower may omit them (defaults resolve to "allreduce").
    """
    if cfg.hist_backend == "auto":
        cfg = dataclasses.replace(
            cfg,
            hist_backend="pallas" if backend == "tpu" else "scatter",
        )
    if cfg.predict_backend == "auto":
        # Same shape as hist_backend: the fused Pallas kernel on TPU, the
        # depth-stepped packed-node-table path elsewhere.  Predict-time
        # code re-resolves against jax.default_backend() again
        # (engine/forest.resolve_predict_backend) so a TPU-trained config
        # degrades gracefully on a CPU serving host.
        cfg = dataclasses.replace(
            cfg,
            predict_backend="pallas" if backend == "tpu" else "packed",
        )
    if cfg.hist_chunk == 0:
        if cfg.hist_backend == "pallas":
            # One chunk when it fits (fewer scan steps; the kernel's grid
            # streams row blocks anyway).  Beyond 4M rows, 2M chunks when
            # the multiple-of-chunk padding stays ≤ 12.5%, else 1M —
            # measured at 8M rows (BASELINE.md r5 envelope): 2M chunks
            # 0.93 s/iter vs 1.11 (one 4M-chunk pair) vs 1.24 (1M chunks).
            if n <= (1 << 22):
                auto_chunk = 1 << 22
            elif ((-n) % (1 << 21)) <= n // 8:
                auto_chunk = 1 << 21
            else:
                auto_chunk = 1 << 20
        else:
            auto_chunk = DEFAULT_CHUNK
        cfg = dataclasses.replace(cfg, hist_chunk=auto_chunk)
    if cfg.grow_policy == "lossguide_exact":
        # Explicit spelling for LightGBM's one-split-per-pass sequence,
        # immune to the TPU auto-batching below.
        cfg = dataclasses.replace(cfg, grow_policy="lossguide", split_batch=-1)
    if (
        cfg.split_batch == 0
        and cfg.grow_policy == "lossguide"
        and cfg.hist_backend == "pallas"
        and cfg.tree_learner not in ("feature", "feature_parallel")
    ):
        # Auto-batching: on TPU the histogram pass dominates and k-batched
        # best-first growth cuts passes ~6x at no measured AUC cost
        # (BASELINE.md r5 defaults table).  Feature-parallel keeps the
        # exact sequence: its winner exchange is per-split.
        cfg = dataclasses.replace(cfg, split_batch=_AUTO_SPLIT_BATCH)
    if cfg.split_batch < 0:
        cfg = dataclasses.replace(cfg, split_batch=0)
    if cfg.hist_precision == "auto":
        cfg = dataclasses.replace(
            cfg,
            hist_precision=(
                "default" if cfg.hist_backend == "pallas" else "highest"
            ),
        )
    if cfg.hist_merge not in (
        "auto", "allreduce", "reduce_scatter", "hierarchical"
    ):
        raise ValueError(
            f"hist_merge must be 'auto', 'allreduce', 'reduce_scatter' or "
            f"'hierarchical', got {cfg.hist_merge!r}"
        )
    if cfg.hist_merge == "hierarchical":
        # The 2D-mesh merge only steers the plain data-parallel learner:
        # voting and feature-parallel own their comm patterns, and the
        # quantized integer wire under a host-biased election would stack
        # two approximations (the hierarchical refinement is already the
        # exact-f32 correction) — reject rather than silently degrade.
        if cfg.tree_learner in (
            "voting", "voting_parallel", "feature", "feature_parallel"
        ):
            raise ValueError(
                "hist_merge='hierarchical' requires the data-parallel "
                f"learner; got tree_learner={cfg.tree_learner!r}"
            )
        if cfg.hist_quantize != "off":
            raise ValueError(
                "hist_merge='hierarchical' and hist_quantize are mutually "
                "exclusive: the hierarchical merge already refines winners "
                "in exact f32, so pick ONE wire-reduction strategy"
            )
    if cfg.hist_merge == "auto":
        # Reduce-scatter wins whenever there is a mesh to scatter over and
        # enough features that every device owns a non-degenerate slice
        # (≥2 features/device keeps the per-slice split scan worthwhile;
        # below that the candidate-exchange overhead eats the wire saving).
        # Voting and feature-parallel learners own their comm patterns —
        # the knob only steers the plain data-parallel merge.  The winner
        # exchange lives in the WINDOWED grower, so auto only flips when
        # that grower is already the resolved path (depthwise or a
        # positive split_batch — note split_batch resolved above): pushing
        # an exact-sequence lossguide run (split_batch=0) into the
        # windowed grower can flip near-tie split ORDER (the documented
        # k-batching trade), which auto must never do behind the user's
        # back.  Explicit hist_merge="reduce_scatter" still opts in.
        use_rs = (
            num_devices > 1
            and num_features >= 2 * num_devices
            and (cfg.grow_policy == "depthwise" or cfg.split_batch > 0)
            and cfg.tree_learner
            not in ("voting", "voting_parallel", "feature", "feature_parallel")
        )
        cfg = dataclasses.replace(
            cfg, hist_merge="reduce_scatter" if use_rs else "allreduce"
        )
    if cfg.hist_quantize not in ("off", "on", "int16", "int32"):
        raise ValueError(
            f"hist_quantize must be 'off', 'on', 'int16' or 'int32', got "
            f"{cfg.hist_quantize!r}"
        )
    if (
        cfg.use_quantized_grad is not None
        and bool(cfg.use_quantized_grad) != (cfg.hist_quantize != "off")
    ):
        raise ValueError(
            f"use_quantized_grad={cfg.use_quantized_grad!r} and "
            f"hist_quantize={cfg.hist_quantize!r} disagree: they are two "
            "names of one switch, give one or make them agree"
        )
    if not cfg.quant_train_renew_leaf:
        raise ValueError(
            "quant_train_renew_leaf=false is not supported: leaf values "
            "always come from the exact float32 sums of the rows' gradients"
        )
    quantize_levels(cfg.num_grad_quant_bins)  # raises outside [2, 127]
    if cfg.hist_quantize != "off":
        if cfg.tree_learner in (
            "voting", "voting_parallel", "feature", "feature_parallel"
        ):
            # Voting merges elected SLICES and feature-parallel never
            # merges histograms at all — neither carries the full-histogram
            # wire the integer path compresses, and their winner exchanges
            # assume f32 local histograms.
            raise ValueError(
                f"hist_quantize is not supported with tree_learner="
                f"{cfg.tree_learner!r}; use the data-parallel or serial "
                "learner"
            )
        if cfg.hist_quantize == "on":
            cfg = dataclasses.replace(cfg, hist_quantize="int16")
    return cfg


# Jitted device-side chunk stackers, cached by (chunk count, kept,
# has-bias) — a fresh jax.jit per train() call would retrace every fit,
# and the bias VALUES enter as a traced argument (each CV fold's label
# mean differs; baking it into the closure would recompile per fit).
_STACK_CACHE: Dict[Tuple, callable] = {}
_STACK_CACHE_MAX = 16


def _stack_chunks_device(chunks: List[Tree], kept: int, bias) -> Tree:
    """Concatenate per-chunk tree stacks, truncate to ``kept`` iterations,
    and fold the boost_from_average bias into stored tree 0 — all in ONE
    device program, output left device-resident (see Booster._host_trees).
    ``bias``: (K,) float32 or None."""
    key = (len(chunks), kept, bias is None)
    fn = _STACK_CACHE.get(key)
    if fn is None:

        def stack(bias_a, *chs):
            t = jax.tree_util.tree_map(
                lambda *xs: jnp.concatenate(xs, axis=0)[:kept], *chs
            )
            if bias_a is not None:
                lv = t.leaf_value  # (T, K, L)
                active = (
                    jnp.arange(lv.shape[-1])[None, :]
                    < t.num_leaves[0][:, None]
                )
                lv0 = jnp.where(active, lv[0] + bias_a.reshape(-1, 1), 0.0)
                t = t._replace(leaf_value=lv.at[0].set(lv0))
            return t

        fn = jax.jit(stack)
        if len(_STACK_CACHE) >= _STACK_CACHE_MAX:
            _STACK_CACHE.pop(next(iter(_STACK_CACHE)))
        _STACK_CACHE[key] = fn
    return fn(None if bias is None else jnp.asarray(bias), *chunks)


def _dart_drop_schedule(rng, cfg: "TrainConfig") -> np.ndarray:
    """(T, T) mask: row ``it`` marks the trees dropped at iteration ``it``.

    The drop decisions consume only host RNG — one uniform for the skip
    check (only once trees exist), one vector draw for the mask, one
    integer draw only when the mask came up empty — so the whole schedule
    precomputes, shared by the scan and legacy paths.
    """
    T = cfg.num_iterations
    rows = np.zeros((T, T), np.float32)
    for it in range(T):
        if it > 0 and rng.random() >= cfg.skip_drop:
            m = rng.random(it) < cfg.drop_rate
            idx = np.nonzero(m)[0][: cfg.max_drop]
            if idx.size == 0:
                idx = np.array([int(rng.integers(it))])
            rows[it, idx] = 1.0
    return rows


def _hashable(v):
    if isinstance(v, (list, tuple, np.ndarray)):
        return tuple(np.asarray(v).ravel().tolist())
    return v


# Config fields the jitted scan body does NOT close over: excluding them
# lets e.g. per-run checkpoint directories or different iteration counts
# reuse the compiled program (scan length retraces by shape anyway).
_CACHE_KEY_EXCLUDE = frozenset(
    {"num_iterations", "checkpoint_dir", "checkpoint_every", "verbosity",
     "metric", "early_stopping_round", "scan_dispatch_iters",
     "predict_backend"}
)


def _cfg_cache_key(cfg: TrainConfig) -> Tuple:
    return tuple(
        (f.name, _hashable(getattr(cfg, f.name)))
        for f in dataclasses.fields(cfg)
        if f.name not in _CACHE_KEY_EXCLUDE
    )


def _mesh_cache_key(mesh):
    if mesh is None:
        return None
    return (
        tuple(d.id for d in mesh.devices.flat),
        mesh.devices.shape,
        tuple(mesh.axis_names),
    )


def _host_replay_scores(booster: "Booster", bins: np.ndarray) -> np.ndarray:
    """Transformed scores for a binned sample, computed ENTIRELY on the
    host with a numpy mirror of :func:`_replay_leaf_ids`.

    Used only for the training-time quality baseline: routing the sample
    through the jitted predict path would add one XLA compile per
    ``train()`` call, which hundreds of test-tier fits cannot afford.
    The replay arithmetic is the same (rows start in leaf 0; each
    recorded split moves its rows), so the score histogram matches what
    serving will produce modulo f32-vs-f64 accumulation."""
    trees = booster._host_trees()
    T = booster._used_iters(None)
    K = booster.num_class
    nb = int(booster.bin_mapper.num_bins)
    weights = np.asarray(booster.tree_weights, np.float64)
    split_leaf = np.asarray(trees.split_leaf)
    split_feat = np.asarray(trees.split_feat)
    split_bin = np.asarray(trees.split_bin)
    default_left = np.asarray(trees.default_left)
    split_cat = np.asarray(trees.split_cat)
    cat_threshold = np.asarray(trees.cat_threshold)
    leaf_value = np.asarray(trees.leaf_value, np.float64)
    n = bins.shape[0]
    S = split_leaf.shape[2]
    bins = bins.astype(np.int64)
    raw = np.zeros((K, n), np.float64)
    for t in range(T):
        for k in range(K):
            leaf = np.zeros(n, np.int64)
            for s in range(S):
                sl = int(split_leaf[t, k, s])
                if sl < 0:
                    continue
                fcol = bins[:, int(split_feat[t, k, s])]
                if split_cat[t, k, s]:
                    goes_left = cat_threshold[t, k, s].astype(bool)[fcol]
                else:
                    goes_left = np.where(
                        fcol == nb - 1,
                        bool(default_left[t, k, s]),
                        fcol <= int(split_bin[t, k, s]),
                    )
                move = (leaf == sl) & ~goes_left
                leaf[move] = s + 1
            raw[k] += weights[t] * leaf_value[t, k][leaf]
    if booster.average_output:
        raw = raw / max(T, 1)
    # the objective's own transform (eager, no jit) for serving parity
    out = np.asarray(booster.objective.transform(jnp.asarray(raw, jnp.float32)))
    return out[0] if out.shape[0] == 1 else out.T


def _capture_quality_baseline(
    booster: "Booster", train_set: Dataset
) -> Optional[dict]:
    """Training-time reference for the serve-path drift monitor
    (``mmlspark_tpu/obs/quality.py``): per-feature bin occupancy from the
    already-binned training matrix plus a score histogram over a capped
    host-replayed sample.  Disabled via ``MMLSPARK_TPU_QUALITY_BASELINE=0``."""
    gate = os.environ.get("MMLSPARK_TPU_QUALITY_BASELINE", "").strip().lower()
    if gate in ("0", "false", "off"):
        return None
    from mmlspark_tpu.obs import quality

    cap = int(float(os.environ.get(
        "MMLSPARK_TPU_QUALITY_SCORE_SAMPLE", "4096") or 4096))
    specs_fn = getattr(train_set, "quality_feature_specs", None)
    if specs_fn is not None:
        # Streamed dataset: occupancy was tallied chunk-by-chunk on device
        # during ingest and the score sample was capped at collection time
        # — the full binned matrix NEVER materializes on host here.
        features = specs_fn(booster.bin_mapper)
        if features is None:
            return None
        sample0 = train_set.quality_binned_sample(cap)
        score = None
        class_mix = None
        if cap > 0 and sample0 is not None and len(sample0):
            preds = _host_replay_scores(booster, sample0)
            score = quality.score_spec_from_scores(
                quality.ScoreDriftTracker.scores_of(preds)
            )
            if preds.ndim == 2 and preds.shape[1] > 1:
                class_mix = np.bincount(
                    np.argmax(preds, axis=1), minlength=preds.shape[1]
                ).astype(float).tolist()
        return quality.QualityBaseline(
            features, score=score, class_mix=class_mix,
            n_rows=train_set.num_rows,
        ).to_dict()

    bins = np.asarray(train_set.binned(booster.bin_mapper))
    features = quality.feature_specs_from_binned(bins, booster.bin_mapper)
    score = None
    class_mix = None
    if cap > 0 and len(bins):
        sample = bins
        if len(bins) > cap:
            idx = np.random.default_rng(0).choice(len(bins), cap, replace=False)
            sample = bins[idx]
        preds = _host_replay_scores(booster, sample)
        score = quality.score_spec_from_scores(
            quality.ScoreDriftTracker.scores_of(preds)
        )
        if preds.ndim == 2 and preds.shape[1] > 1:
            class_mix = np.bincount(
                np.argmax(preds, axis=1), minlength=preds.shape[1]
            ).astype(float).tolist()
    return quality.QualityBaseline(
        features, score=score, class_mix=class_mix, n_rows=len(bins)
    ).to_dict()


def train(
    params: dict,
    train_set: Dataset,
    valid_sets: Sequence[Dataset] = (),
    valid_names: Optional[Sequence[str]] = None,
    bin_mapper: Optional[BinMapper] = None,
    init_model: Optional[Booster] = None,
    mesh=None,
    process_local: bool = False,
) -> Booster:
    """Training entry — single-device or data-parallel over a device mesh.

    With ``mesh`` set (or ``tree_learner`` in data/voting modes, which builds
    a default mesh over all visible devices), rows are sharded over the
    mesh's ``"data"`` axis and the grower runs under ``shard_map`` with
    per-shard histograms merged across the axis — the direct replacement
    for the reference's ``LGBM_NetworkInit`` + socket histogram allreduce
    (SURVEY.md §3.1, §5.8 N2).  How they merge is ``hist_merge``:
    ``"allreduce"`` ``psum``s the full (3, F, B) stack so every shard then
    computes an identical best split (exactly LightGBM's
    ``tree_learner=data`` semantics), while ``"reduce_scatter"`` (the
    ``"auto"`` pick on real meshes) scatters merged histograms over
    contiguous feature slices — each shard scans only its F/D features and
    a tiny per-node candidate allgather elects the identical global winner
    on every shard (LightGBM's reduce-scatter data-parallel merge, Ke et
    al. 2017; ~D× less wire volume).  Either way tree growth stays
    replicated: the decision inputs are bit-identical across shards.

    ``process_local=True`` is the MULTI-CONTROLLER ingestion contract
    (SURVEY.md §3.1 ``generateDataset``, §7.3.4): ``train_set`` holds ONLY
    this process's partition rows — exactly as the reference's per-task
    native Dataset holds only the partition — and the global row-sharded
    arrays are assembled with ``jax.make_array_from_process_local_data``,
    so no process ever materializes another's rows.  Label statistics that
    the serial path reads from the full label vector (boost_from_average
    seed, is_unbalance pos/neg) come from tiny summed-stat allgathers; pass
    a ``bin_mapper`` fit by :func:`mmlspark_tpu.ops.binning.distributed_fit`
    so thresholds agree across processes.  Every process must call train()
    collectively (SPMD) and receives the identical replicated Booster.
    """
    phases = _Phases()
    with obs.span(
        "booster.train", process_local=bool(process_local)
    ) as sp_train:
        try:
            booster = _train_impl(
                params, train_set, valid_sets, valid_names,
                bin_mapper, init_model, mesh, process_local, phases,
            )
        finally:
            phases.close()
    sp_last = sp_train
    if booster.quality_baseline is None:
        try:
            with obs.span("booster.quality_baseline") as sp_last:
                booster.quality_baseline = _capture_quality_baseline(
                    booster, train_set
                )
        except Exception:
            obs.get_logger("mmlspark_tpu.engine").warning(
                "quality baseline capture failed; serving drift monitor "
                "will run reference-less for this model", exc_info=True,
            )
    if isinstance(sp_train, obs.Span):
        # the spans' own stamps: the fit's start to the baseline's end
        wall = (sp_last.end_ns - sp_train.start_ns) / 1e9
        obs.gauge("booster.train_wall_s", wall)
        try:
            # StreamedDataset has X=None by design; row count still exists
            n_rows = (
                int(train_set.num_rows) if train_set.X is None
                else int(np.shape(train_set.X)[0])
            )
        except Exception:
            n_rows = 0
        if n_rows and wall > 0:
            # Throughput as row-iterations/s over THIS process's partition
            # (multiply by process count for the global rate under
            # process_local ingestion).
            obs.gauge(
                "booster.rows_per_s", n_rows * booster.num_iterations / wall
            )
    return booster


class _Phases:
    """A fit's consecutive host phases as ``obs`` spans, all children of
    ``booster.train`` (``booster.prepare``, ``.upload``, ``.program``,
    ``.collect``; ``booster.scan_dispatch`` stands between the last two on
    its own): entering one closes the one before it, so the phases tile
    the fit's host time and an idle gap in a device trace lies in a named
    one.  Host code only — never entered inside a traced function."""

    def __init__(self):
        self._open = None

    def enter(self, name: str, **attrs):
        self.close()
        self._open = obs.span(name, **attrs)
        return self._open.__enter__()

    def close(self) -> None:
        sp, self._open = self._open, None
        if sp is not None:
            sp.__exit__(None, None, None)


class _Uploads:
    """What a fit sends to the device, counted at the send: called on each
    array on its way there, a host array's ``nbytes`` go into
    ``train.upload_bytes`` (and ``bytes``, for ``booster.upload``'s attr);
    one already resident counts 0."""

    def __init__(self):
        self.bytes = 0

    def __call__(self, a):
        if isinstance(a, np.ndarray):
            self.bytes += a.nbytes
            obs.inc("train.upload_bytes", float(a.nbytes))
        return a


def _placer(mesh, process_local: bool):
    """``put(array, spec)``: how this fit places an array on its devices.
    Off a mesh an uncommitted array on the default device; under a mesh a
    ``NamedSharding`` of ``spec``; multi-controller, the global array
    stitched from each process's (padded) partition, so no host ever sees
    another's rows."""
    if mesh is None:
        return lambda a, spec: jnp.asarray(a)
    if process_local:
        from mmlspark_tpu.parallel.distributed import make_global_array

        return lambda a, spec: make_global_array(mesh, spec, a)
    from jax.sharding import NamedSharding

    return lambda a, spec: jax.device_put(a, NamedSharding(mesh, spec))


class _RowState(NamedTuple):
    """What a fit holds for each training row beside the binned matrix, on
    the device, and the objective's init score it was made with."""

    y: object  # (n + n_pad,) float32 labels
    w: object  # (n + n_pad,) float32 weights after is_unbalance / scale_pos_weight, or None
    valid_mask: object  # (n + n_pad,) bool, False on padding
    init_scores: object  # (K, n + n_pad) float32: init score (+ the set's init_score), before any init_model
    init: object  # the init score itself: a float, or (K,) for K > 1


def _fit_row_state(
    train_set, obj, cfg: TrainConfig, *, n: int, n_pad: int, use_bfa: bool,
    process_local: bool, placement: Tuple, put, row_spec, krow_spec,
    sent: "_Uploads",
) -> Tuple[_RowState, bool]:
    """``(row state, found)`` for a fit of ``train_set``: made once for a
    data set and kept on the device with its binned matrix
    (``_row_state_cache``, one entry), so a later fit on the same host
    arrays, placement and objective settings touches no array of ``n``
    elements and sends nothing.  The key holds what the arrays depend on;
    the entry pins the host arrays it was made from, which keeps their ids
    from being recycled, and makes them read-only, so a write into them
    between fits raises instead of being trained past (see ``Dataset``).
    Nothing donates these buffers; a fit that did would need a copy here.

    Multi-controller fits always build: their label statistics are
    collectives that every process must enter in every fit."""
    label, weight, init_score = srcs = (train_set.label, train_set.weight, train_set.init_score)
    K = obj.num_model_per_iteration
    key = (
        tuple(id(a) for a in srcs), n, n_pad, placement, K, cfg.objective,
        tuple((k, _hashable(v)) for k, v in cfg.objective_params().items()),
        # what enters the weights, and what decides the init score
        (cfg.is_unbalance, cfg.scale_pos_weight) if cfg.objective == "binary" else None,
        use_bfa,
    )
    held = None if process_local else train_set._row_state_cache.get(key)
    obs.inc("train.row_state", 1.0, result="miss" if held is None else "hit")
    if held is not None:
        return held[1], True
    train_set._row_state_cache = {}  # the old entry's arrays go before the new ones come

    y = _pad_rows(label, n_pad)
    valid_mask_np = np.concatenate([np.ones(n, bool), np.zeros(n_pad, bool)])

    # ---- weights (is_unbalance / scale_pos_weight) ---------------------
    w = weight
    if cfg.objective == "binary":
        pn = np.asarray([float((label > 0).sum()), float((label <= 0).sum())])
        if process_local:
            from mmlspark_tpu.parallel.distributed import host_allgather

            pn = host_allgather(pn).sum(axis=0)
        pos, neg = max(pn[0], 1.0), max(pn[1], 1.0)
        spw = neg / pos if cfg.is_unbalance else cfg.scale_pos_weight
        if spw != 1.0:
            base = np.ones(n) if w is None else np.asarray(w, dtype=np.float64)
            w = np.where(label > 0, base * spw, base)
    w_np = None if w is None else _pad_rows(np.asarray(w, dtype=np.float64), n_pad)

    # ---- init score ----------------------------------------------------
    if use_bfa and process_local:
        # Seed from SUMMED sufficient statistics (one tiny allgather) —
        # the global label vector never exists on any host.
        from mmlspark_tpu.parallel.distributed import host_allgather

        stats = host_allgather(obj.init_score_stats(label, weight)).sum(axis=0)
        init = obj.init_score_from_stats(stats)
    elif use_bfa:
        init = obj.init_score(label, weight)
    else:
        init = np.zeros(K) if K > 1 else 0.0
    init_arr = np.broadcast_to(np.asarray(init, dtype=np.float32).reshape(-1, 1), (K, n + n_pad)).copy()
    if init_score is not None:
        init_arr = init_arr + _pad_rows(init_score.astype(np.float32), n_pad).reshape(1, -1)

    state = _RowState(
        y=put(sent(y.astype(np.float32)), row_spec),
        w=None if w_np is None else put(sent(w_np.astype(np.float32)), row_spec),
        valid_mask=put(sent(valid_mask_np), row_spec),
        init_scores=put(sent(init_arr), krow_spec),
        init=init,
    )
    if not process_local:
        train_set._row_state_cache = {key: (srcs, state)}
        for a in srcs:
            if isinstance(a, np.ndarray):
                a.setflags(write=False)
    return state, False


def _train_impl(
    params: dict,
    train_set: Dataset,
    valid_sets: Sequence[Dataset],
    valid_names: Optional[Sequence[str]],
    bin_mapper: Optional[BinMapper],
    init_model: Optional[Booster],
    mesh,
    process_local: bool,
    phases: _Phases,
) -> Booster:
    """Body of :func:`train` — see its docstring.  Split out so the
    ``booster.train`` obs span wraps every return path; ``phases`` are its
    children (:class:`_Phases`), closed by the caller."""
    import warnings

    from mmlspark_tpu.core.jit_cache import enable_compile_cache

    sp_prepare = phases.enter("booster.prepare")

    # Library-level persistent compile cache (SURVEY.md §3.1: the reference
    # has no compile step to beat — a user's FIRST fit must not pay full
    # XLA freight every process).  No-op if the user opted out/configured
    # their own.
    enable_compile_cache()

    cfg = params if isinstance(params, TrainConfig) else TrainConfig.from_params(params)
    if cfg.boosting == "dart" and cfg.early_stopping_round > 0:
        # Later DART iterations rescale earlier trees, so a truncated-at-
        # best-iteration model cannot reproduce the selected metric.
        # LightGBM forbids the combination for the same reason.
        raise ValueError("early stopping is not available in dart mode")
    if cfg.boosting == "rf" and not (cfg.bagging_freq > 0 and cfg.bagging_fraction < 1.0):
        # Without bagging every RF tree would be identical (LightGBM raises
        # the equivalent config check).
        raise ValueError(
            "boosting='rf' requires bagging_freq > 0 and bagging_fraction < 1"
        )
    if cfg.early_stopping_round > 0 and not valid_sets:
        # LightGBM: "For early stopping, at least one dataset ... is required".
        raise ValueError(
            "early_stopping_round > 0 requires at least one validation set"
        )
    obj = get_objective(cfg.objective, **cfg.objective_params())
    K = obj.num_model_per_iteration

    # ---- checkpoint recovery (SURVEY.md §5.3/§5.4) ---------------------
    # The resume source is a PICKLE (exact Booster state, including the
    # fitted BinMapper — a model-string round trip would collapse
    # never-yet-split features to a single bin); model.txt is mirrored
    # alongside for interop/inspection.  dart cannot warm-start (drop
    # bookkeeping) and rf cannot continue (averaged output), so neither
    # checkpoints.
    #
    # TRUST MODEL: checkpoint_dir must be as trusted as the code itself —
    # ``pickle.load`` executes whatever the file says (same stance as
    # torch.load or the reference's JVM deserialization).  Point it at a
    # per-job private directory, never a shared/world-writable one; for an
    # interchange-safe artifact use the mirrored model.txt +
    # BinMapper.to_dict(), which are data-only.
    ckpt_path = ckpt_txt = None
    requested_total = cfg.num_iterations
    from_ckpt = False
    if (
        cfg.checkpoint_dir
        and cfg.checkpoint_every > 0
        and cfg.boosting not in ("dart", "rf")
    ):
        import os

        os.makedirs(cfg.checkpoint_dir, exist_ok=True)
        ckpt_path = os.path.join(cfg.checkpoint_dir, "checkpoint.pkl")
        ckpt_txt = os.path.join(cfg.checkpoint_dir, "model.txt")
        if init_model is None and os.path.exists(ckpt_path):
            # Digest-verified load (ISSUE 14 elasticity): a torn, partial,
            # or bit-rotted snapshot answers None and the run self-heals
            # by training from scratch — a surviving-host resume must
            # never die on the artifact the dead host half-wrote.
            from mmlspark_tpu.parallel.elastic import load_checkpoint

            init_model = load_checkpoint(ckpt_path)
            if init_model is not None and not hasattr(init_model, "_used_iters"):
                # digest-valid but wrong payload (operator copied some
                # other pickle in): same self-healing as corruption
                warnings.warn(
                    f"checkpoint {ckpt_path!r} does not hold a Booster "
                    f"(got {type(init_model).__name__}); training from "
                    "scratch"
                )
                init_model = None
            from_ckpt = init_model is not None
        if from_ckpt:
            # Count the trees continuation will actually replay/keep
            # (_used_iters: an early-stopped snapshot contributes only
            # best_iteration+1 trees).
            done = init_model._used_iters(None)
            if done >= cfg.num_iterations:
                # Honor the REQUESTED size: truncate rather than silently
                # returning a bigger forest than asked for, preserving the
                # early-stopping cutoff when it survives the truncation.
                T = cfg.num_iterations
                bi = init_model.best_iteration
                return Booster(
                    trees=init_model._slice_trees(T),
                    tree_weights=init_model.tree_weights[:T],
                    bin_mapper=init_model.bin_mapper,
                    config=cfg,
                    best_iteration=bi if 0 <= bi < T else -1,
                )
            if getattr(init_model, "_ckpt_completed_for", -1) >= cfg.num_iterations:
                # The prior run FINISHED this request (early stopping just
                # truncated the forest below num_iterations).  Rerunning
                # must be stable: return the completed snapshot as-is
                # instead of training past the recorded stopping point.
                return init_model
            cfg = dataclasses.replace(cfg, num_iterations=cfg.num_iterations - done)

    # ---- warm start (continued training; the reference's `modelString`
    # param — SURVEY.md §2.3.1, §5.4) -----------------------------------
    if init_model is not None:
        if init_model.num_class != K:
            raise ValueError(
                f"init_model has {init_model.num_class} models/iteration, "
                f"objective {cfg.objective!r} needs {K}"
            )
        if init_model.average_output:
            raise ValueError("continued training from an rf booster is not supported")
        if cfg.boosting in ("rf", "dart"):
            # rf would average the old forest's contribution away; dart
            # would drop/rescale trees it did not train.
            raise ValueError(
                f"continued training with boosting={cfg.boosting!r} is not supported"
            )
        if bin_mapper is not None and bin_mapper is not init_model.bin_mapper:
            # Elastic resume (ISSUE 14): the survivor re-supplies the
            # shared binning authority while the recovered checkpoint
            # carries its own unpickled copy — same thresholds, different
            # object.  Structural equality keeps the continuation safe;
            # a genuinely different mapper still hard-fails.
            if not (
                from_ckpt
                and bin_mapper.to_dict() == init_model.bin_mapper.to_dict()
            ):
                raise ValueError(
                    "bin_mapper cannot be overridden when init_model is "
                    "set; continuation replays old trees, which pins "
                    "their thresholds"
                )
        # New trees must be replayed over the same thresholds as the old
        # ones (one BinMapper per booster), so continuation pins the mapper.
        bin_mapper = init_model.bin_mapper

    # ---- mesh (data-parallel tree learner) -----------------------------
    hierarchical_req = cfg.hist_merge == "hierarchical"
    if mesh is None and hierarchical_req:
        # 2D (data × feature) pod mesh: hosts on the slow axis, each
        # host's devices on the fast axis (ISSUE 14).
        from mmlspark_tpu.parallel.mesh import mesh2d

        mesh = mesh2d()
    elif mesh is None and (
        process_local or cfg.tree_learner in _PARALLEL_LEARNERS
    ):
        from mmlspark_tpu.parallel.mesh import default_mesh

        mesh = default_mesh()
    from mmlspark_tpu.parallel.mesh import (
        DATA_AXIS,
        FEATURE_AXIS,
        ROW_AXES,
        is_mesh_2d,
        mesh_axis_size,
        mesh_num_devices,
    )

    if hierarchical_req and not is_mesh_2d(mesh):
        raise ValueError(
            "hist_merge='hierarchical' needs the 2D (data × feature) mesh "
            "— build one with parallel.mesh.mesh2d(); got axes "
            f"{tuple(mesh.axis_names) if mesh is not None else None}"
        )

    D = mesh_num_devices(mesh)
    d_feat = mesh_axis_size(mesh, FEATURE_AXIS)

    if cfg.tree_learner in ("feature", "feature_parallel") and process_local:
        # LightGBM's tree_learner=feature contract (SURVEY.md §2 parallelism
        # table): feature parallel splits the WORK by columns but every
        # machine holds the FULL dataset — upstream keeps all rows on each
        # worker precisely so the winner exchange never moves row
        # partitions.  Process-local ingestion therefore CONVERTS here:
        # rows are allgathered once at ingestion (the documented memory
        # cost of this learner — it is why data/voting parallel are the
        # recommended modes at scale, see README "Multi-chip scaling"),
        # and training proceeds as the replicated-rows column-sharded
        # learner over the same global mesh, SPMD-identical on every
        # process.  Thresholds need no distributed sketch: after the merge
        # every process fits the mapper on identical full data.
        from mmlspark_tpu.parallel.distributed import host_allgather_ragged_rows

        def _merge_rows(ds: Dataset) -> Dataset:
            col = lambda a: (  # noqa: E731 — 1-D ride-along columns
                None if a is None
                else host_allgather_ragged_rows(
                    np.ascontiguousarray(a)[:, None]
                )[:, 0]
            )
            return Dataset(
                host_allgather_ragged_rows(np.ascontiguousarray(ds.X)),
                col(ds.label),
                weight=col(ds.weight),
                # groups concatenate in process order — the same
                # process-aligned contract the ranking metrics use
                group=col(ds.group),
                init_score=col(ds.init_score),
            )

        train_set = _merge_rows(train_set)
        valid_sets = [_merge_rows(v) for v in valid_sets]
        process_local = False

    # process_local metric evaluation never pulls score snapshots to hosts
    # (they are row-sharded across processes): metrics are computed from
    # psum-able sufficient statistics INSIDE the jitted scan — the direct
    # analog of the reference's Network-reduced `LGBM_BoosterGetEval` each
    # iteration (SURVEY.md §3.1, §5.8).  Valid sets hold ONLY this
    # process's partition rows (sharded like the train set); every process
    # must pass the same number of valid sets in the same order (SPMD).
    # Ranking groups are process-aligned (the reference's
    # repartitionByGroupingColumn contract) and only group METADATA is
    # allgathered.
    device_eval = process_local
    if process_local:
        # Fail fast on a violated SPMD contract (e.g. one barrier task with
        # an empty validation split passing None): a mismatched valid-set
        # count would otherwise pair collectives across DIFFERENT call
        # sites and deadlock or crash with garbage shapes.
        from mmlspark_tpu.parallel.distributed import host_allgather

        sig = host_allgather(np.asarray([
            len(valid_sets), int(bool(cfg.is_provide_training_metric)),
            int(isinstance(obj, LambdaRank)),
        ]))
        if not (sig == sig[0]).all():
            raise ValueError(
                "process_local SPMD contract violated: every process must "
                "pass the same number of valid_sets (use an EMPTY array "
                "for an empty partition, never None) and identical "
                f"eval/objective flags; got {sig.tolist()} across processes"
            )

    # ---- binning (cached on the Dataset — LightGBM bins at Dataset
    # construction and reuses across training calls) --------------------
    if bin_mapper is None:
        if process_local:
            # A per-process local fit would give every process DIFFERENT
            # thresholds (silently wrong model); route through the
            # distributed sample-sketch so all processes agree.
            from mmlspark_tpu.ops.binning import distributed_fit

            # distinct from fitted_mapper's key: the sketch samples
            # differently, so the two fits must never share a cache slot
            key = ("dist", cfg.max_bin, tuple(cfg.categorical_feature), cfg.seed)
            bin_mapper = train_set._mapper_cache.get(key)
            if bin_mapper is None:
                bin_mapper = distributed_fit(
                    train_set.X,
                    max_bin=cfg.max_bin,
                    categorical_features=tuple(cfg.categorical_feature),
                    seed=cfg.seed,
                    threads=cfg.num_threads,
                )
                train_set._mapper_cache = {key: bin_mapper}
        else:
            bin_mapper = train_set.fitted_mapper(cfg)
    with obs.span("booster.binning"):
        bins_np = train_set.binned(bin_mapper)
    n, F = bins_np.shape
    B = bin_mapper.num_bins
    sp_prepare.set(rows=int(n), features=int(F))

    # ---- "auto" knob resolution ----------------------------------------
    # The resolved values live on cfg from here on (GrowConfig, the scan
    # cache key, and the padding math all read them).
    cfg = resolve_auto_config(
        cfg,
        n=n,
        backend=jax.default_backend(),
        num_devices=D,
        num_features=F,
        num_bins=B,
    )

    # ---- feature-parallel: columns sharded, rows replicated ------------
    feature_par = (
        cfg.tree_learner in ("feature", "feature_parallel")
        and mesh is not None
        and D > 1
    )
    # ---- reduce-scatter histogram merge (data-parallel only) -----------
    # Rows stay sharded exactly as data-parallel; the merge collective
    # scatters merged histograms over contiguous feature blocks, so the
    # feature axis needs the same multiple-of-D padding feature-parallel
    # uses.  Voting/feature-parallel keep their own comm patterns.
    reduce_scatter = (
        cfg.hist_merge == "reduce_scatter"
        and mesh is not None
        and D > 1
        and not feature_par
        and cfg.tree_learner not in ("voting", "voting_parallel")
    )
    # ---- hierarchical 2D-mesh merge (ISSUE 14) -------------------------
    # Rows shard over BOTH axes (each device owns n/(H·d) rows); the
    # windowed merge psum_scatters host-locally over the fast axis, so
    # the feature axis pads to a multiple of d (the fast-axis size), not
    # of the full device count.
    hierarchical = hierarchical_req and mesh is not None
    # Row sharding spans BOTH mesh axes under hierarchical (each device owns
    # n/(H·d) rows); everything else shards rows over the 1-D data axis.
    row_axes = ROW_AXES if hierarchical else DATA_AXIS
    F_real = F
    if feature_par or reduce_scatter:
        # Pad columns to a multiple of the shard count; padded columns are
        # masked out of every candidate search (feat_valid below).
        # Categoricals: each shard derives its local columns' kinds at RUN
        # time from axis_index (tree.py _local_cat_mask) — right-padding
        # never renumbers real columns, so the global indices stay valid.
        f_pad = (-F) % D
        if f_pad:
            bins_np = _pad_cols(bins_np, f_pad if feature_par else 0)  # reduce_scatter pads its histogram, not the rows' matrix
            F += f_pad
    elif hierarchical:
        f_pad = (-F) % d_feat
        if f_pad:
            bins_np = _pad_cols(bins_np, f_pad)
            F += f_pad

    # ---- padding: shard count × histogram chunk ------------------------
    # Each of the D shards holds n_local rows; n_local must be one chunk or
    # a multiple of chunks so the scan in build_histogram stays shape-static.
    chunk = cfg.hist_chunk
    if process_local:
        # Global padding agreement without global data: every process pads
        # its partition to the same per-device row count, derived from the
        # allgathered per-process counts (a few ints on the wire).
        from mmlspark_tpu.parallel.distributed import host_allgather

        proc_counts = host_allgather(np.asarray([n])).reshape(-1)
        d_local = max(len(mesh.local_devices), 1)
        n_local = (int(proc_counts.max()) + d_local - 1) // d_local
        if n_local > chunk:
            n_local = ((n_local + chunk - 1) // chunk) * chunk
        n_pad = n_local * d_local - n  # THIS process's padding
    else:
        # feature-parallel replicates rows: every shard holds all n rows,
        # so only the histogram-chunk alignment applies.
        D_rows = 1 if feature_par else D
        n_local = (n + D_rows - 1) // D_rows
        if n_local > chunk:
            n_local = ((n_local + chunk - 1) // chunk) * chunk
        n_pad = n_local * D_rows - n
    # Ranking queries: the plan (ops/rank_plan: queries bucketed by length)
    # is built once for a data set from its group sizes and kept on the
    # device with it, like the binned matrix; a later fit on the same set
    # builds and sends nothing.  Process-aligned groups (distributed
    # lambdarank): every process's queries live wholly inside its own row
    # block, the plan is built GLOBALLY from allgathered group metadata
    # (engine/dist_metrics.assemble_global_groups) and replicated, and the
    # pairwise computation runs unchanged over the globally sharded scores
    # — gathering each query's scores is the one collective.
    train_groups_host = None
    rank_counts = None
    if isinstance(obj, LambdaRank):
        if train_set.group is None:
            raise ValueError("lambdarank requires group sizes")
        if int(np.sum(train_set.group)) != n:
            raise ValueError(
                "group sizes must sum to this dataset's row count "
                f"({int(np.sum(train_set.group))} != {n})"
            )
        with obs.span("booster.rank_plan") as sp_plan:
            from mmlspark_tpu.ops.rank_plan import build_rank_plan, plan_from_matrix

            group = np.asarray(train_set.group, np.int64)
            plan_key = (hash(group.tobytes()), _mesh_cache_key(mesh))
            held = None if process_local else train_set._rank_plan_cache.get(plan_key)
            sp_plan.set(cache_hit=held is not None)
            if process_local:
                # never cached: assembling the groups is a collective that
                # every process must enter in every fit
                from jax.sharding import PartitionSpec as P

                from mmlspark_tpu.engine.dist_metrics import assemble_global_groups
                from mmlspark_tpu.parallel.distributed import make_global_array

                row_off = jax.process_index() * n_local * d_local
                train_groups_host = assemble_global_groups(group, row_off)
                plan = plan_from_matrix(*train_groups_host)
                held = (plan, plan.device_arrays(lambda a: make_global_array(mesh, P(), a)))
            elif held is None:
                plan = build_rank_plan(group)
                obs.inc(
                    "train.upload_bytes",
                    float(sum(a.nbytes for a in jax.tree_util.tree_leaves(plan.host_arrays()))),
                )
                held = (plan, plan.device_arrays())
                train_set._rank_plan_cache = {plan_key: held}  # size 1, like the matrix's
            plan = held[0]
            obj.set_plan(*held)
            rank_counts = {
                "rank.queries": plan.queries,
                "rank.pair_slots": plan.pair_slots(obj.max_position),
                "rank.pair_terms": plan.pair_terms(obj.max_position),
            }
            sp_plan.set(
                queries=plan.queries, buckets=len(plan.buckets), rows=int(n + n_pad),
                shapes=" ".join(f"{g}x{m}" for g, m in plan.shape_key[0]),
            )

    # ---- init score ----------------------------------------------------
    # dart (tree rescaling would corrupt the folded bias) and rf (averaged
    # output would divide it) keep a zero init instead of bias folding.
    use_bfa = (
        cfg.boost_from_average
        and cfg.boosting not in ("dart", "rf")
        and train_set.init_score is None
        and init_model is None  # the old forest already embeds its bias
    )

    # ---- device-resident data ------------------------------------------
    # Under a mesh, rows are sharded over the data axis up front so the
    # binned matrix lives partitioned in HBM (SURVEY.md §7.2) and per-
    # iteration programs never reshuffle it.  Feature-parallel shards the
    # columns and replicates everything that follows the rows.
    from jax.sharding import PartitionSpec as P

    if feature_par:
        bins_spec, row_spec, krow_spec = P(None, DATA_AXIS), P(), P()
    else:
        bins_spec, row_spec, krow_spec = P(row_axes, None), P(row_axes), P(None, row_axes)
    put = _placer(mesh, process_local)
    placement = (_mesh_cache_key(mesh), process_local, feature_par, hierarchical)
    _sent = _Uploads()
    (y_dev, w_dev, valid_mask, init_scores_dev, init), rows_cached = _fit_row_state(
        train_set, obj, cfg, n=n, n_pad=n_pad, use_bfa=use_bfa,
        process_local=process_local, placement=placement, put=put,
        row_spec=row_spec, krow_spec=krow_spec, sent=_sent,
    )
    dev_key = (id(bin_mapper), n_pad, *placement)
    bins_dev = train_set._dev_bins_cache.get(dev_key)
    sp_upload = phases.enter(
        "booster.upload", bins_cached=bins_dev is not None, rows_cached=rows_cached
    )
    if bins_dev is None:
        # only now: a resident matrix (StreamedDataset) is padded on the
        # device, and a fit that finds the padded copy must not make another
        bins_dev = put(_sent(_pad_rows(bins_np, n_pad)), bins_spec)
    # Size-1 like the host caches: each entry pins a full-matrix device
    # copy, and sweeps over mesh/chunk configs must not accumulate HBM.
    train_set._dev_bins_cache = {dev_key: bins_dev}
    if init_model is not None:
        # Replay the base forest over the already-placed binned matrix:
        # under a mesh this runs sharded (bins_dev carries the row sharding
        # into the jitted forest scorer), with no second binning pass and no
        # unsharded full-matrix copy.  Padded rows score garbage, harmlessly
        # — their gradients are zeroed by the bag mask.
        init_scores_dev = init_scores_dev + init_model._raw_scores_binned(bins_dev)
    scores = init_scores_dev

    voting = (
        cfg.tree_learner in ("voting", "voting_parallel")
        and mesh is not None
        and D > 1
    )
    grow_policy = cfg.grow_policy
    if voting and grow_policy != "depthwise":
        # The two-round vote is level-synchronous by construction; the
        # lossguide (one-split-per-step) grower would vote on a single leaf
        # at a time, which is just data-parallel with extra rounds.
        warnings.warn(
            "voting_parallel uses the depthwise grower; overriding "
            f"grow_policy={grow_policy!r}"
        )
        grow_policy = "depthwise"
    split_batch = cfg.split_batch
    if (
        (feature_par or reduce_scatter or hierarchical)
        and grow_policy == "lossguide"
        and split_batch == 0
    ):
        # The winner exchange lives in the windowed grower; one split per
        # pass reproduces LightGBM's exact leaf-wise sequence there.
        split_batch = 1
    quantize_on = cfg.hist_quantize != "off"
    qlevels = quantize_levels(cfg.num_grad_quant_bins)
    if quantize_on:
        # Wire plan from the PADDED GLOBAL row count (the worst-case row
        # total any merged bin can see): picks the pre-wire right-shift
        # that fits partial sums in the wire dtype, and raises on int32
        # ACCUMULATOR overflow (per-shard rows × each channel's largest
        # bucket must fit 2³¹) — trips at config time, never silently
        # wraps on device.
        quantize_shift = quantize_wire_plan(
            n + n_pad, cfg.hist_quantize,
            num_shards=D if mesh is not None else 1, levels=qlevels,
        )
    else:
        quantize_shift = 0
    # ---- GOSS: an exact-count sample every iteration -------------------
    # Counts follow the real rows alone, so they are known here.  On one
    # device the trees grow from the sample alone, gathered into a buffer of
    # goss_buf rows (goss_compact), and every row is routed through the new
    # tree after; over a mesh the rows stay where they lie and the sample
    # rides the bag weights.
    goss = cfg.boosting == "goss"
    goss_top = goss_rest = 0
    goss_buf = None
    if goss:
        n_real = int(np.sum(proc_counts)) if process_local else n
        goss_top, goss_rest = goss_counts(n_real, cfg.top_rate, cfg.other_rate)
        if mesh is None:
            goss_buf = goss_rows(goss_top + goss_rest, chunk)
    goss_amp = goss_amplification(cfg.top_rate, cfg.other_rate)
    gcfg = GrowConfig(
        num_bins=B,
        num_leaves=cfg.num_leaves,
        max_depth=cfg.max_depth,
        min_data_in_leaf=cfg.min_data_in_leaf,
        min_sum_hessian_in_leaf=cfg.min_sum_hessian_in_leaf,
        lambda_l1=cfg.lambda_l1,
        lambda_l2=cfg.lambda_l2,
        min_gain_to_split=cfg.min_gain_to_split,
        learning_rate=cfg.learning_rate if cfg.boosting != "rf" else 1.0,
        hist_backend=cfg.hist_backend,
        hist_chunk=chunk,
        hist_precision=cfg.hist_precision,
        hist_merge=(
            "hierarchical" if hierarchical
            else "reduce_scatter" if reduce_scatter
            else "allreduce"
        ),
        hist_quantize=cfg.hist_quantize,
        quantize_shift=quantize_shift,
        quantize_levels=qlevels,
        quantize_stochastic=bool(cfg.stochastic_rounding),
        grow_policy=grow_policy,
        split_batch=split_batch,
        categorical_features=tuple(int(f) for f in cfg.categorical_feature),
        cat_smooth=cfg.cat_smooth,
        cat_l2=cfg.cat_l2,
        max_cat_threshold=(
            cfg.max_cat_threshold if cfg.max_cat_threshold > 0 else cfg.max_bin
        ),
        # cap the cat scan's value-bin axis at the max observed cat
        # cardinality (bins past it are unused for every cat feature)
        cat_value_bins=max(
            (
                len(getattr(bin_mapper, "cat_maps", {}).get(f, ()))
                for f in cfg.categorical_feature
            ),
            default=0,
        ),
        voting=voting,
        top_k=cfg.top_k,
        # TPU-only: the MXU contraction is shape-deterministic, while
        # XLA:CPU threads the gemm by the host's device count, so the
        # f32 sum order differs between process layouts of the same mesh
        # and the recorded leaf values lose bitwise layout-parity
        # (tools/bench_pod.py gate); the scatter path accumulates in row
        # order on every layout.
        onehot_stats=jax.default_backend() == "tpu",
    )

    def _grow_classes(gcfg_):
        # One tree per class via lax.map, NOT vmap: batching the grower's
        # pallas/scatter ops multiplies Mosaic/XLA compile time ~25x (188s
        # observed for a 63-leaf/256-bin tree on v5e), while lax.map
        # compiles the body once and runs the K trees sequentially — which
        # matches real execution anyway.
        if quantize_on:
            # Quantized twin: per-class SR keys and (2,) grad/hess scales
            # ride the lax.map xs alongside the class gradients.
            def grow_all_q(bins_a, grad_a, hess_a, bag_a, fmask_a,
                           qkeys_a, qscales_a):
                def one(args):
                    g, h, fm, qk, qs = args
                    return grow_tree_auto(gcfg_, bins_a, g, h, bag_a, fm,
                                          qk, qs)

                return jax.lax.map(
                    one, (grad_a, hess_a, fmask_a, qkeys_a, qscales_a)
                )

            return grow_all_q

        def grow_all(bins_a, grad_a, hess_a, bag_a, fmask_a):
            def one(args):
                g, h, fm = args
                return grow_tree_auto(gcfg_, bins_a, g, h, bag_a, fm)

            return jax.lax.map(one, (grad_a, hess_a, fmask_a))

        return grow_all

    if mesh is None:
        grow = _grow_classes(gcfg)
    elif feature_par:
        # Feature-parallel shard_map: COLUMNS sharded (bins + feature
        # masks), rows/gradients replicated; each shard histograms only its
        # feature block and the winner exchange (all_gather of per-leaf
        # candidates + owner psum of the row partition) replaces the
        # histogram allreduce entirely — LightGBM tree_learner=feature
        # (SURVEY.md §2 parallelism table).
        from jax.sharding import PartitionSpec as P

        tree_spec = Tree(*([P()] * len(Tree._fields)))
        grow = jax.shard_map(
            _grow_classes(
                dataclasses.replace(
                    gcfg, axis_name=DATA_AXIS, feature_parallel=True
                )
            ),
            mesh=mesh,
            in_specs=(
                P(None, DATA_AXIS), P(None, None), P(None, None), P(None),
                P(None, DATA_AXIS),
            ),
            out_specs=(tree_spec, P(None, None)),
            check_vma=False,
        )
    else:
        # Per-shard grower: local rows in, psum-med histograms inside
        # (GrowConfig.axis_name), replicated tree out.  check_vma=False: the
        # tree's replication is established by psum-determinism, which the
        # static checker cannot see through argmax.
        from jax.sharding import PartitionSpec as P

        tree_spec = Tree(*([P()] * len(Tree._fields)))
        # Quantized runs append replicated (K, 2) SR keys + (K, 2) scales
        # (global max-abs, computed once pre-shard — no pmax needed).
        q_specs = (P(None, None), P(None, None)) if quantize_on else ()
        grow = jax.shard_map(
            _grow_classes(dataclasses.replace(
                gcfg,
                axis_name=(ROW_AXES if hierarchical else DATA_AXIS),
                feature_axis_name=(FEATURE_AXIS if hierarchical else None),
            )),
            mesh=mesh,
            in_specs=(P(row_axes, None), P(None, row_axes), P(None, row_axes), P(row_axes), P(None, None)) + q_specs,
            out_specs=(tree_spec, P(None, row_axes)),
            check_vma=False,
        )

    def _fmask_one(key):
        # feature_fraction samples over the REAL features; feature-parallel
        # padding columns stay masked out (False) so no shard ever proposes
        # a split on one.
        m = _feature_mask(key, F_real, cfg.feature_fraction)
        if F != F_real:
            m = jnp.pad(m, (0, F - F_real))
        return m

    def _quantize_inputs(grad, hess, bag, key):
        # Per-iteration channel scales over the GLOBAL bagged batch —
        # grad/hess are still the full (sharded) arrays here, outside
        # shard_map, so jnp.max IS the global max-abs and no pmax is
        # needed.  One SR key per class, folded off the iteration key with
        # a fixed tag so the stochastic-rounding stream is decoupled from
        # the bagging/feature-sampling streams (same-seed reruns are
        # bitwise identical; unrelated knobs don't perturb rounding).
        with jax.named_scope("quant_round"):
            qscales = jax.vmap(
                lambda g, h: quantize_channel_scales(g, h, bag, qlevels)
            )(grad, hess)  # (K, 2)
        qkeys = jax.random.split(jax.random.fold_in(key, 0x51AB), K)
        return qkeys, qscales

    # Device data enters the jitted step as ARGUMENTS, never closure
    # captures: closed-over arrays become jaxpr constants and XLA spends
    # minutes constant-folding through the 10s-of-MB binned matrix (75s →
    # 8s compile observed at 262k×64).
    @jax.jit
    def iteration(bins_a, y_a, w_a, vmask_a, ostate_a, scores, key, bag_in):
        with _class_scope(K, "class_grad"):
            grad, hess = obj.grad_hess_from(
                ostate_a, scores if K > 1 else scores[0], y_a, w_a
            )
        if K == 1:
            grad, hess = grad[None, :], hess[None, :]
        _, fkey = jax.random.split(key)
        # Decouple the feature-sampling stream from bagging (LightGBM has
        # independent feature_fraction_seed / bagging_seed streams).  The
        # per-iteration loop is DART's alone (GOSS always takes the scan).
        fkey = jax.random.fold_in(fkey, cfg.feature_fraction_seed)
        bag = bag_in
        fmask = jax.vmap(_fmask_one)(jax.random.split(fkey, K))
        if quantize_on:
            qkeys, qscales = _quantize_inputs(grad, hess, bag, key)
            tree, leaf_ids = grow(bins_a, grad, hess, bag, fmask,
                                  qkeys, qscales)
        else:
            qscales = None
            tree, leaf_ids = grow(bins_a, grad, hess, bag, fmask)
        return tree, _leaf_delta(tree, leaf_ids), qscales

    # LightGBM bagging semantics: a bag is drawn at iterations where
    # ``it % bagging_freq == 0`` and *reused* until the next draw.
    resample_bag = jax.jit(lambda key, vmask_a: _bag_weights(key, cfg, vmask_a))
    do_bagging = cfg.bagging_fraction < 1.0 and cfg.bagging_freq > 0
    full_bag = valid_mask.astype(jnp.float32)
    current_bag = full_bag

    # ---- valid sets ----------------------------------------------------
    vsets = []
    names = list(valid_names) if valid_names else [f"valid_{i}" for i in range(len(valid_sets))]
    for vs in valid_sets:
        vbins_np = vs.binned(bin_mapper)
        if process_local:
            # Each process contributes ONLY its valid partition, padded to
            # an allgathered common per-device count (same contract as the
            # train rows above); labels/weights/mask ride as global sharded
            # arrays for the in-scan stats reductions.
            from jax.sharding import PartitionSpec as P

            from mmlspark_tpu.parallel.distributed import (
                host_allgather,
                make_global_array,
            )

            vcounts = host_allgather(np.asarray([vs.num_rows])).reshape(-1)
            nv_local = (int(vcounts.max()) + d_local - 1) // d_local
            v_pad = nv_local * d_local - vs.num_rows
            vb = make_global_array(
                mesh, P(row_axes, None), _sent(_pad_rows(vbins_np, v_pad))
            )
            vy = make_global_array(
                mesh, P(row_axes),
                _sent(_pad_rows(vs.label, v_pad).astype(np.float32)),
            )
            vw = None if vs.weight is None else make_global_array(
                mesh, P(row_axes),
                _sent(_pad_rows(vs.weight, v_pad).astype(np.float32)),
            )
            vvm = make_global_array(
                mesh, P(row_axes),
                _sent(np.concatenate(
                    [np.ones(vs.num_rows, bool), np.zeros(v_pad, bool)]
                )),
            )
            vscore_np = np.broadcast_to(
                np.asarray(init, dtype=np.float32).reshape(-1, 1),
                (K, vs.num_rows + v_pad),
            ).copy()
            if vs.init_score is not None:
                vscore_np = vscore_np + _pad_rows(
                    vs.init_score.astype(np.float32), v_pad
                ).reshape(1, -1)
            vscore = make_global_array(
                mesh, P(None, row_axes), _sent(vscore_np)
            )
            if init_model is not None:
                vscore = vscore + init_model._raw_scores_binned(vb)
            vsets.append({
                "bins": vb, "scores": vscore, "data": vs,
                "eval_arrays": (vy, vw, vvm),
                "row_offset": jax.process_index() * nv_local * d_local,
            })
            continue
        vb = jnp.asarray(_sent(vbins_np))
        vscore = np.broadcast_to(
            np.asarray(init, dtype=np.float32).reshape(-1, 1), (K, vs.num_rows)
        ).copy()
        if vs.init_score is not None:
            vscore = vscore + vs.init_score.astype(np.float32).reshape(1, -1)
        if init_model is not None:
            vscore = vscore + np.asarray(
                init_model._raw_scores_binned(vb), dtype=np.float32
            )
        vsets.append({
            "bins": vb, "scores": jnp.asarray(_sent(vscore)), "data": vs,
        })

    if cfg.is_provide_training_metric:
        # The training set joins the eval loop as a LAST pseudo-valid;
        # early stopping excludes it via the explicit is_train_pseudo
        # check in _es_update (the ANY-pair rule watches every real
        # (valid set, metric) pair).  Its scores snapshot reuses the
        # sharded padded bins already on device.
        names.append("training")
        vsets.append({
            "bins": bins_dev, "scores": scores, "data": train_set,
            "eval_arrays": (y_dev, w_dev, valid_mask),
            "row_offset": (
                jax.process_index() * n_local * d_local if process_local else 0
            ),
        })

    sp_upload.set(bytes=_sent.bytes, **_placement(bins_dev))
    sp_program = phases.enter("booster.program")
    predict_v = jax.jit(
        lambda tree, vbins: jax.vmap(lambda t: predict_tree_binned(t, vbins, B))(tree)
    )

    # ---- metrics / early stopping --------------------------------------
    # LightGBM accepts a COMMA-SEPARATED metric list ("auc,binary_logloss")
    # or a Python list; every metric is recorded per eval set.  Early
    # stopping follows LightGBM's documented rule — training stops when
    # ANY (validation set, metric) pair fails to improve for
    # early_stopping_round iterations (the training pseudo-valid never
    # participates); ``best_iteration`` reports the FIRST metric on the
    # FIRST valid set, matching the single-metric surface.
    raw_metric = cfg.metric or obj.default_metric
    if isinstance(raw_metric, str):
        metric_names = [m.strip() for m in raw_metric.split(",") if m.strip()]
    else:
        metric_names = [str(m) for m in raw_metric]
    # LightGBM's metric="None"/"na"/"null"/"custom" DISABLES evaluation:
    # valid sets are ignored (nothing recorded, no snapshot transfers);
    # early stopping then has nothing to watch and raises.
    metric_names = [
        m for m in metric_names
        if m.lower() not in ("none", "na", "null", "custom")
    ]
    if not metric_names:
        if cfg.early_stopping_round > 0:
            raise ValueError(
                "early stopping needs at least one metric; "
                f"metric={cfg.metric!r} disables evaluation"
            )
        valid_sets = ()
        vsets, names = [], []
        metric_names = [obj.default_metric]  # name only; nothing evaluates
    # dedupe, order-preserving (LightGBM dedups metric lists; a repeated
    # name would double-append into one evals_result curve)
    metric_names = list(dict.fromkeys(metric_names))
    metric_name = metric_names[0]
    metric_infos = [
        eval_metrics.get_metric(
            m, alpha=cfg.alpha, fair_c=cfg.fair_c,
            tweedie_variance_power=cfg.tweedie_variance_power,
        )
        for m in metric_names
    ]
    needs_groups = any(mi[2] for mi in metric_infos)
    higher_better = metric_infos[0][1]
    best_score, best_iter = (-np.inf if higher_better else np.inf), -1
    # (vset index, metric index) → (best value, best iteration)
    es_state: Dict[Tuple[int, int], Tuple[float, int]] = {}

    if device_eval and vsets:
        # Attach the device evaluators (one per metric) + aux arrays to
        # every eval set; shared group matrices upload once.
        from jax.sharding import PartitionSpec as P

        from mmlspark_tpu.engine.dist_metrics import (
            assemble_global_groups,
            get_device_metric,
        )
        from mmlspark_tpu.parallel.distributed import make_global_array

        _uploaded: Dict[int, object] = {}

        def _up(a):
            if id(a) not in _uploaded:
                _uploaded[id(a)] = make_global_array(mesh, P(), a)
            return _uploaded[id(a)]

        for vi, vs in enumerate(vsets):
            gi = gv = None
            if needs_groups:
                is_train_pseudo = (
                    cfg.is_provide_training_metric and vi == len(vsets) - 1
                )
                if is_train_pseudo and train_groups_host is not None:
                    gi, gv = train_groups_host
                else:
                    dset = vs["data"]
                    if dset.group is None:
                        raise ValueError(
                            f"metric {metric_names!r} needs group sizes on "
                            f"eval set {names[vi]!r}"
                        )
                    gi, gv = assemble_global_groups(
                        dset.group, vs["row_offset"]
                    )
            evs = [
                get_device_metric(
                    m, alpha=cfg.alpha, fair_c=cfg.fair_c,
                    tweedie_variance_power=cfg.tweedie_variance_power,
                    auc_eval_bins=cfg.auc_eval_bins,
                    group_idx=gi, group_valid=gv,
                )
                for m in metric_names
            ]
            vs["evaluators"] = evs
            vs["aux"] = vs["eval_arrays"] + (
                tuple(
                    tuple(_up(a) for a in ev.aux_host()) for ev in evs
                ),
            )

    def eval_metric(mi: int, scores_arr, dset: Dataset):
        fn, _, ng = metric_infos[mi]
        s = np.asarray(scores_arr)
        s_eval = s if K > 1 else s[0]
        kw = {}
        if ng:
            kw["group_sizes"] = dset.group
        return fn(dset.label, s_eval[..., : dset.num_rows] if K > 1 else s_eval[: dset.num_rows], w=dset.weight, **kw)

    def _es_update(vs_i: int, mi: int, m: float, it: int, is_train_pseudo: bool):
        """ANY-pair stall rule; returns True when this pair stalls."""
        nonlocal best_score, best_iter
        if cfg.early_stopping_round <= 0 or is_train_pseudo:
            return False
        if cfg.first_metric_only and mi > 0:
            return False
        hb = metric_infos[mi][1]
        bs, bi = es_state.get((vs_i, mi), (-np.inf if hb else np.inf, -1))
        if (m > bs) if hb else (m < bs):
            es_state[(vs_i, mi)] = (m, it)
            if vs_i == 0 and mi == 0:
                best_score, best_iter = m, it
            return False
        if it - bi >= cfg.early_stopping_round:
            # LightGBM's early_stopping callback reports the TRIGGERING
            # pair's best, not pair (0,0)'s — on multi-metric/multi-set
            # runs they can differ (r4 advisor).  Also covers the case
            # where pair (0,0) never improved (best_iter would stay -1).
            best_score, best_iter = bs, bi
            return True
        return False

    # ---- DART / RF state ----------------------------------------------
    trees_host: List[Tree] = []
    tree_weights: List[float] = []
    rng = np.random.default_rng(cfg.drop_seed)
    evals_result: Dict[str, Dict[str, List[float]]] = {
        nm: {m: [] for m in metric_names} for nm in names
    }
    # All per-iteration keys in one device call, pulled to host once: a
    # jax.random.split per iteration is a dispatch round-trip each (adds up
    # fast over remote-dispatch links).
    # Continuation (modelString warm start or checkpoint resume) CONTINUES
    # the per-iteration key stream where the base forest left off — reusing
    # keys 0..k would re-draw the identical bags/feature subsets for the
    # new trees (correlated forest).
    key_start = init_model._used_iters(None) if init_model is not None else 0
    total_keyed = key_start + cfg.num_iterations
    root_key = jax.random.PRNGKey(cfg.bagging_seed + 7919 * cfg.seed)
    # Keys are derived from the ABSOLUTE iteration index via fold_in, NOT
    # by position in a split(root_key, 2*total) table: jax.random.split
    # has no prefix property, so every entry of such a table changes with
    # the REQUESTED total — a 4-iteration run then a resume-to-8 drew
    # different bags/feature masks than one straight 8-iteration run,
    # breaking the checkpoint-resume bitwise contract (ISSUE 14).
    # fold_in(root_key, i) depends only on (seed, i); the bag stream rides
    # a fold_in-tagged sibling root so it stays decoupled from the
    # grower/feature-sampling stream exactly as before.
    _abs_idx = jnp.arange(total_keyed, dtype=jnp.uint32)
    iter_keys_all = np.asarray(
        jax.vmap(lambda i: jax.random.fold_in(root_key, i))(_abs_idx)
    )
    bag_keys_all = np.asarray(
        jax.vmap(
            lambda i: jax.random.fold_in(
                jax.random.fold_in(root_key, 0x00BA66ED), i
            )
        )(_abs_idx)
    )

    # DART in the scan: the drop decisions consume only HOST RNG (never
    # data), so the whole schedule is precomputed as a (T, T) mask with the
    # exact RNG call order of the legacy loop, and the scan carries the
    # per-tree weight vector plus per-tree prediction buffers (P: (T, K, n))
    # so dropped contributions are one einsum instead of per-tree predict
    # dispatches.  Gated to the single-controller path, no checkpointing
    # (the checkpoint writer assumes unit weights), and a P-buffer HBM
    # budget — outside those, the legacy per-iteration loop below remains.
    dart = cfg.boosting == "dart"
    # Carry memory counts the training P buffer AND the per-valid-set PV
    # buffers (the training pseudo-valid carries a zero-size dummy); the
    # T^2 drop-schedule matrix is bounded separately.
    _dart_carry_rows = int(scores.shape[-1]) + sum(
        int(np.shape(vs["scores"])[-1]) for vi, vs in enumerate(vsets)
        if not (cfg.is_provide_training_metric and vi == len(vsets) - 1)
    )
    # Mesh runs ride the scan too (VERDICT r3 #5): the P/PV buffers are
    # created row-sharded over the data axis (below), the drop einsum and
    # dynamic_update_slice are elementwise over the sharded rows, and the
    # drop schedule is host-RNG-only (identical on every process).
    # ckpt_path is always None for dart (no resume — LightGBM semantics);
    # kept in the gate as a guard against future checkpoint loosening.
    dart_scan = (
        dart and ckpt_path is None
        and cfg.num_iterations <= 4096
        and cfg.num_iterations * K * _dart_carry_rows <= _DART_SCAN_MAX_ELS
    )
    if dart:
        # ONE schedule for both paths (scan xs / legacy loop) so the RNG
        # call order can never diverge between them.
        drop_rows = _dart_drop_schedule(rng, cfg)
        it_indices = np.arange(cfg.num_iterations, dtype=np.int32)

    if cfg.boosting != "dart" or dart_scan:
        # ---- FAST PATH: the whole boosting run as ONE lax.scan ----------
        # Round 1 spent ~42s of a 44s / 50-iteration bench in per-iteration
        # dispatch + host sync (the device compute per iteration is
        # ~50ms) — exactly the reference's reason
        # for keeping its hot loop inside native code (SURVEY.md §3.1 HOT
        # LOOP).  Scanning over iterations makes the whole run one XLA
        # program: 1 dispatch total without early stopping, 1 per
        # `early_stopping_round` chunk with it (metrics are checked on host
        # between chunks from per-iteration score snapshots; trees grown
        # past the stopping point are discarded, so semantics match the
        # per-iteration check exactly).
        n_iter = cfg.num_iterations
        if do_bagging:
            # LightGBM bagging reuse: iteration `it` uses the bag drawn at
            # the last multiple of bagging_freq.  Recomputing the draw from
            # the same key inside the scan body reproduces reuse without a
            # carried bag array.  Iteration indices are GLOBAL (offset by
            # the warm-start forest) so resumed draws differ from the base
            # forest's.
            global_it = np.arange(key_start, total_keyed)
            draw_at = (global_it // cfg.bagging_freq) * cfg.bagging_freq
            bag_keys = bag_keys_all[draw_at]
        else:
            bag_keys = np.zeros((n_iter, 2), dtype=iter_keys_all.dtype)
        iter_keys = iter_keys_all[key_start:total_keyed]

        vbins_t = tuple(vs["bins"] for vs in vsets)
        vaux_t = (
            tuple(vs["aux"] for vs in vsets) if device_eval and vsets else ()
        )
        evaluators = [vs.get("evaluators") for vs in vsets]
        it_global = np.arange(key_start, total_keyed, dtype=np.int32)
        # ONE packed xs upload per chunk: iteration keys (c,2) + bag keys
        # (c,2) + global iteration index ride one (c,5) uint32 array,
        # unpacked inside the scan body.
        xs_key = (
            cfg.bagging_seed, cfg.seed, cfg.bagging_freq, do_bagging,
            key_start, total_keyed, n_iter,
        )
        xs_dev = _XS_CACHE.get(xs_key)
        sp_program.set(xs_cache_hit=xs_dev is not None)
        if xs_dev is None:
            xs_packed = np.concatenate(
                [
                    np.asarray(iter_keys, dtype=np.uint32),
                    np.asarray(bag_keys, dtype=np.uint32),
                    it_global[:, None].astype(np.uint32),
                ],
                axis=1,
            )
            xs_dev = jnp.asarray(xs_packed)
            if len(_XS_CACHE) >= _XS_CACHE_MAX:
                _XS_CACHE.pop(next(iter(_XS_CACHE)))
            _XS_CACHE[xs_key] = xs_dev

        # Like `iteration` above: device data enters as ARGUMENTS (valid
        # bins included, eval label/weight/mask/group aux included) so
        # nothing large becomes a jaxpr constant.
        def _build_scan_chunk():
            from jax.sharding import NamedSharding
            from jax.sharding import PartitionSpec as _PS

            _rep = NamedSharding(mesh, _PS()) if mesh is not None else None

            def scan_chunk(
                bins_a, y_a, w_a, vmask_a, ostate_a, init_scores_a, vbins_a,
                vaux_a, carry, xs_c, *dart_xs,
            ):
                def body(car, xs):
                    if dart_scan:
                        scores_c, vscores_c, P, PVs, wts = car
                        xs_row, drop_row, it_idx = xs
                        key, bag_key = xs_row[:2], xs_row[2:4]
                        it_g = xs_row[4].astype(jnp.int32)
                        # dropped contribution removed in ONE einsum over
                        # the carried per-tree prediction buffer (exact
                        # precision: scores must match legacy replay)
                        sub_w = drop_row * wts  # pre-rescale weights
                        sub = jnp.einsum(
                            "t,tkn->kn", sub_w, P,
                            precision=jax.lax.Precision.HIGHEST,
                        )
                        train_scores = scores_c - sub
                    else:
                        scores_c, vscores_c = car
                        (xs_row,) = xs
                        key, bag_key = xs_row[:2], xs_row[2:4]
                        it_g = xs_row[4].astype(jnp.int32)
                        train_scores = (
                            init_scores_a if cfg.boosting == "rf" else scores_c
                        )
                    with _class_scope(K, "class_grad"):
                        grad, hess = obj.grad_hess_from(
                            ostate_a,
                            train_scores if K > 1 else train_scores[0], y_a, w_a,
                        )
                    if K == 1:
                        grad, hess = grad[None, :], hess[None, :]
                    gkey, fkey = jax.random.split(key)
                    fkey = jax.random.fold_in(fkey, cfg.feature_fraction_seed)
                    if goss:
                        bag = goss_sample(grad, vmask_a, gkey, goss_top,
                                          goss_rest, goss_amp)
                    elif do_bagging:
                        bag = _bag_weights(bag_key, cfg, vmask_a)
                    else:
                        bag = vmask_a.astype(jnp.float32)
                    fmask = jax.vmap(_fmask_one)(
                        jax.random.split(fkey, K)
                    )
                    rows_g = (bins_a, grad, hess, bag)
                    if goss_buf is not None:
                        rows_g = goss_compact(*rows_g, goss_buf,
                                              backend=gcfg.hist_backend)
                    if quantize_on:
                        qkeys, qscales = _quantize_inputs(*rows_g[1:], key)
                        tree, leaf_ids = grow(*rows_g, fmask, qkeys, qscales)
                    else:
                        tree, leaf_ids = grow(*rows_g, fmask)
                    if goss_buf is not None:
                        # the grower's leaf ids are the buffer's rows: the
                        # score update routes every row through the tree
                        leaf_ids = jax.vmap(lambda t: _replay_leaf_ids(
                            t, bins_a, B, scope="goss_route"))(tree)
                    delta = _leaf_delta(tree, leaf_ids)
                    if dart_scan:
                        # DART normalization (legacy-loop semantics): new
                        # tree at 1/(k+1), dropped trees rescaled by
                        # k/(k+1) and re-added — the re-add is exactly
                        # factor * the subtract einsum, so no second
                        # (T, K, n) contraction.  (use_bfa never reaches
                        # dart: boost_from_average excludes it.)
                        kdrop = jnp.sum(drop_row)
                        has = kdrop > 0
                        w_new = jnp.where(has, 1.0 / (kdrop + 1.0), 1.0)
                        factor = jnp.where(has, kdrop / (kdrop + 1.0), 1.0)
                        wts = jnp.where(drop_row > 0, wts * factor, wts)
                        scores_c = train_scores + factor * sub + w_new * delta
                        P = jax.lax.dynamic_update_slice(
                            P, delta[None], (it_idx, 0, 0)
                        )
                        wts = wts.at[it_idx].set(w_new)
                    else:
                        with _class_scope(K, "class_update"):
                            scores_c = scores_c + delta
                    nv = len(vbins_a)
                    new_vs = []
                    new_pvs = []
                    for vi, (vsc, vb) in enumerate(zip(vscores_c, vbins_a)):
                        if cfg.is_provide_training_metric and vi == nv - 1:
                            # the training pseudo-valid (always last) IS the
                            # carry — no second full-data tree replay
                            new_vs.append(scores_c)
                            if dart_scan:
                                new_pvs.append(PVs[vi])
                            continue
                        vdelta = jax.vmap(
                            lambda t: predict_tree_binned(t, vb, B)
                        )(tree)
                        if dart_scan:
                            PV = PVs[vi]
                            # valid-score drop adjustment: Σ drop·(w_new_t
                            # − w_old_t)·PV = (factor−1)·Σ drop·w_old·PV
                            adj = (factor - 1.0) * jnp.einsum(
                                "t,tkn->kn", sub_w, PV,
                                precision=jax.lax.Precision.HIGHEST,
                            )
                            new_pvs.append(jax.lax.dynamic_update_slice(
                                PV, vdelta[None], (it_idx, 0, 0)
                            ))
                            new_vs.append(vsc + adj + w_new * vdelta)
                        else:
                            new_vs.append(vsc + vdelta)
                    vscores_c = tuple(new_vs)
                    if device_eval and vsets:
                        # In-scan sufficient-statistics evaluation: the ys
                        # output per eval set is a tiny replicated (S,)
                        # vector (the psum-ed stats), never a row-sharded
                        # score snapshot — the §5.8 Network-reduced eval.
                        stats_out = []
                        for vi2, vsc in enumerate(vscores_c):
                            ay, aw, am, aextras = vaux_a[vi2]
                            sc = (
                                vsc / (it_g.astype(jnp.float32) + 1.0)
                                if cfg.boosting == "rf" else vsc
                            )
                            per_metric = []
                            for mi2, ev in enumerate(evaluators[vi2]):
                                st = ev.stats(sc, ay, aw, am, *aextras[mi2])
                                if _rep is not None:
                                    st = jax.lax.with_sharding_constraint(
                                        st, _rep
                                    )
                                per_metric.append(st)
                            stats_out.append(tuple(per_metric))
                        ys_v = tuple(stats_out)
                    else:
                        ys_v = vscores_c
                    # quantized runs stack the per-iteration (K, 2) scales
                    # so the host can emit train.grad/hess_scale gauges
                    out = (tree, ys_v) + ((qscales,) if quantize_on else ())
                    if dart_scan:
                        car = (scores_c, vscores_c, P, tuple(new_pvs), wts)
                        return car, out
                    return (scores_c, vscores_c), out

                return jax.lax.scan(
                    body, carry, (xs_c,) + tuple(dart_xs)
                )

            return jax.jit(scan_chunk)

        # Reuse the jitted program across train() calls when nothing it
        # closes over can differ.  The cached program closes over the FIRST
        # call's objective instance, which is sound because objectives are
        # stateless-by-construction (Objective.stateful) — stateful ones
        # (LambdaRank's query plan) hand their device state in as an
        # ARGUMENT (``device_state``), and participate with the SHAPES the
        # program was traced over as their key: another data set's plan of
        # the same bucket shapes reuses the program.
        state_key = obj.state_key() if obj.stateful else None
        scan_cache_hit = False
        if device_eval and vsets:
            # Evaluator aux shapes and group-count constants are per-call
            # state; the distributed-eval program skips the cross-call
            # cache (jit still reuses compiles across this run's chunks).
            scan_chunk, program_notes = _build_scan_chunk(), {}
        elif obj.stateful and state_key is None:
            scan_chunk, program_notes = _build_scan_chunk(), {}
        else:
            # gcfg carries every data-derived static baked into the traced
            # program (cat_value_bins from the bin mapper, onehot_stats from the
            # backend, resolved split_batch/grow_policy, hist_chunk) — keying on
            # the whole frozen dataclass keeps the key honest as fields are
            # added, instead of re-enumerating cfg fields that feed it.
            cache_key = (
                _cfg_cache_key(cfg), K, F, F_real, B, _mesh_cache_key(mesh),
                type(obj).__name__, state_key, gcfg,
                (goss_top, goss_rest, goss_buf),  # follow the row count
            )
            entry = _SCAN_CACHE.get(cache_key)
            scan_cache_hit = entry is not None
            if entry is None:
                entry = (_build_scan_chunk(), {})
                if len(_SCAN_CACHE) >= _SCAN_CACHE_MAX:
                    _SCAN_CACHE.pop(next(iter(_SCAN_CACHE)))
                _SCAN_CACHE[cache_key] = entry
            scan_chunk, program_notes = entry
        merge_ledger = hist_ledger = None
        if obs.enabled():
            # what one iteration's collectives bring each device and what
            # its histogram passes issue, read off ONE abstract trace of the
            # grower, made once a program and kept with it (a program that
            # is not cached across calls reads it anew each fit): the
            # dispatches below count both once for every iteration that RAN
            if "hist_ledger" not in program_notes:
                program_notes.update(_grow_ledgers(
                    grow, gcfg, K, bins_dev, F, quantize_on,
                    merges=mesh is not None and D > 1, rows=goss_buf,
                ))
            merge_ledger = program_notes["merge_ledger"]
            hist_ledger = program_notes["hist_ledger"]
        if quantize_on and obs.enabled():
            for channel, level in zip(("grad", "hess", "count"), qlevels):
                obs.inc("train.quant_levels", float(level), channel=channel)

        if (
            n * n_iter >= _TRACE_CACHE_MIN_WORK
            and not (obj.stateful and state_key is None)
        ):
            # AOT trace cache (core/trace_cache): later processes skip the
            # ~15s Python trace of this program entirely — deserialize the
            # exported StableHLO and call (the compile cache still serves
            # XLA).  r5: covers sharded programs too — the mesh topology
            # rides the key (mesh_trace_key), and under multiple
            # controllers load-vs-export is allgather-agreed so every
            # process runs a byte-identical program.  Key covers config,
            # objective state, arg shapes, source hash, jax version,
            # platform, topology.  Stateful objectives without a state
            # fingerprint can never trace-cache (their state is baked into
            # the traced program).
            from mmlspark_tpu.core.trace_cache import enabled as _tc_on
            from mmlspark_tpu.core.trace_cache import (
                mesh_trace_key,
                mesh_spans_processes,
                wrap_aot,
            )

            if _tc_on():
                scan_chunk = wrap_aot(
                    scan_chunk,
                    key_material=repr((
                        _cfg_cache_key(cfg), K, F, F_real, B,
                        type(obj).__name__, state_key, dart_scan,
                        len(vsets), cfg.is_provide_training_metric,
                        tuple(metric_names) if device_eval else None,
                        gcfg,  # data-derived statics (cat_value_bins, ...)
                        (goss_top, goss_rest, goss_buf),
                        mesh_trace_key(mesh), process_local, feature_par,
                    )),
                    # Load-vs-export agreement only for programs every rank
                    # runs: a meshless train inside a multi-process job
                    # (rank-local comparator, per-rank AutoML worker) must
                    # load/export purely locally — the collective would
                    # deadlock against ranks that never enter it.
                    multi_controller=(
                        process_local or mesh_spans_processes(mesh)
                    ),
                )

        if cfg.early_stopping_round > 0 and vsets:
            chunk_iters = min(n_iter, max(cfg.early_stopping_round, 1))
        elif vsets:
            # Metrics need per-iteration valid-score snapshots, which scan
            # stacks into a (chunk, K, n_valid) buffer — cap the chunk so
            # that buffer (and its host transfer) stays bounded regardless
            # of num_iterations × valid size.  Device-eval stacks only
            # (chunk, S) stat vectors, so the whole run is one dispatch.
            chunk_iters = n_iter if device_eval else min(n_iter, 64)
        else:
            chunk_iters = n_iter
        if ckpt_path is not None:
            chunk_iters = min(chunk_iters, max(cfg.checkpoint_every, 1))
        if cfg.scan_dispatch_iters > 0:
            chunk_iters = min(chunk_iters, cfg.scan_dispatch_iters)
        ckpt_host_chunks: List[Tree] = []  # fetched once per chunk, reused

        def _write_snapshot(booster_snap):
            import os

            from mmlspark_tpu.parallel import elastic

            if process_local and jax.process_index() != 0:
                return  # every process holds the same replicated model

            # Atomic pickle + sha256 sidecar: resume verifies the digest
            # and self-heals (fresh start) on a torn/corrupt snapshot.
            elastic.write_checkpoint(ckpt_path, booster_snap)
            tmp = ckpt_txt + ".tmp"
            with open(tmp, "w") as f:
                f.write(
                    booster_snap.save_model_string(
                        num_iteration=booster_snap.num_iterations
                    )
                )
            os.replace(tmp, ckpt_txt)
            # Rank-0 shard manifest: which process held which data shards
            # at snapshot time (advisory — resume re-derives ownership
            # from the CURRENT process count, see parallel/elastic.py).
            shard_paths = getattr(train_set, "shard_paths", None)
            elastic.write_manifest(
                cfg.checkpoint_dir,
                elastic.ShardManifest(
                    process_count=jax.process_count(),
                    iterations_done=int(booster_snap.num_iterations),
                    shards=(
                        [list(map(str, g)) for g in shard_paths]
                        if shard_paths else
                        [[] for _ in range(jax.process_count())]
                    ),
                ),
            )

        def _write_checkpoint(new_chunk):
            # Each chunk is fetched from device ONCE and kept host-side;
            # the snapshot concatenates the host copies (atomic replace so
            # a crash never leaves a torn checkpoint).
            ckpt_host_chunks.append(
                _fetch_tree_chunks([new_chunk], bool(cfg.categorical_feature))[0]
            )
            so_far = Tree(
                *[np.concatenate(a, axis=0) for a in zip(*ckpt_host_chunks)]
            )
            if use_bfa:
                so_far = _fold_bias(so_far, init)
            _write_snapshot(
                _finalize_booster(
                    so_far, np.ones(so_far.split_leaf.shape[0]), bin_mapper,
                    cfg, init_model, {}, -1,
                )
            )

        if dart_scan:
            if mesh is not None:
                # (T, K, n) buffers sharded over the data axis from birth —
                # a mesh DART run never materializes an unsharded P buffer
                from jax.sharding import NamedSharding
                from jax.sharding import PartitionSpec as _PS

                _pbuf_sh = NamedSharding(mesh, _PS(None, None, DATA_AXIS))

                def _pbuf(shape):
                    return jax.jit(
                        lambda: jnp.zeros(shape, jnp.float32),
                        out_shardings=_pbuf_sh,
                    )()
            else:
                def _pbuf(shape):
                    return jnp.zeros(shape, jnp.float32)

            # the training pseudo-valid (always last) never reads its PV
            # (its scores ARE the carry) — a zero-size dummy keeps the
            # carry structure without the (T, K, n) allocation.  PV
            # sharding mirrors each valid set's scores: row-sharded only
            # in process_local mode (where valid sets are sharded).
            zero_pv = tuple(
                jnp.zeros((0,), jnp.float32)
                if cfg.is_provide_training_metric and vi == len(vsets) - 1
                else (
                    _pbuf((n_iter,) + np.shape(vs["scores"]))
                    if process_local
                    else jnp.zeros(
                        (n_iter,) + np.shape(vs["scores"]), jnp.float32
                    )
                )
                for vi, vs in enumerate(vsets)
            )
            carry = (
                scores, tuple(vs["scores"] for vs in vsets),
                _pbuf((n_iter,) + np.shape(scores)),
                zero_pv, jnp.zeros((n_iter,), jnp.float32),
            )
        else:
            carry = (scores, tuple(vs["scores"] for vs in vsets))
        tree_chunks: List[Tree] = []
        n_done = 0
        stop_at: Optional[int] = None
        chunk_idx = 0
        sp_program.set(
            scan_cache_hit=scan_cache_hit, devices=D,
            hist_merge=gcfg.hist_merge if mesh is not None and D > 1 else "none",
        )
        if goss:
            sp_program.set(goss=(
                f"a={cfg.top_rate} b={cfg.other_rate} m={goss_top + goss_rest}"
                f" buffer={goss_buf or 0}"
            ))
        if quantize_on:
            sp_program.set(
                quant_levels="x".join(str(v) for v in qlevels),
                quant_wire=cfg.hist_quantize,
            )
            if hist_ledger:
                sp_program.set(quant_bucket_body="+".join(sorted(
                    {body for body, _, scope in hist_ledger if scope == "quant_hist"}
                )))
        phases.close()  # the dispatches are booster.train's own children
        while n_done < n_iter and stop_at is None:
            t_chunk = time.perf_counter()
            step_t = obs.steps.begin()
            c = min(chunk_iters, n_iter - n_done)
            dart_xs = (
                (jnp.asarray(drop_rows[n_done : n_done + c]),
                 jnp.asarray(it_indices[n_done : n_done + c]))
                if dart_scan else ()
            )
            # cold=True marks the chunk whose dispatch blocks on Python
            # tracing + XLA compile (or trace/compile-cache loads); later
            # chunks measure pure async-dispatch cost.
            scan_args = (
                bins_dev, y_dev, w_dev, valid_mask, obj.device_state(),
                init_scores_dev, vbins_t, vaux_t, carry,
                jax.lax.slice(xs_dev, (n_done, 0), (n_done + c, 5))
                if c < n_iter else xs_dev,
                *dart_xs,
            )
            # what obs.device.regions() needs to ask for this program again
            # (shapes and shardings; nothing is lowered here)
            obs.device.note_program(
                "booster.fit", id(getattr(scan_chunk, "jitted", scan_chunk)),
                scan_chunk, scan_args,
            )
            with obs.span(
                "booster.scan_dispatch",
                chunk=chunk_idx, iters=c, cold=(chunk_idx == 0),
            ):
                carry, scan_ys = scan_chunk(*scan_args)
            del scan_args  # let go of the carry that went in
            for op, (calls, nbytes) in (merge_ledger or {}).items():
                obs.inc("train.merge_calls", float(calls * c), op=op)
                obs.inc("train.merge_bytes", float(nbytes * c), op=op)
            for name, per_iter in (rank_counts or {}).items():
                obs.inc(name, float(per_iter * c))
            for (body, vals_kind, scope), work in (hist_ledger or {}).items():
                for name, per_iter in work.items():
                    obs.inc("hist." + name, float(per_iter * c), body=body, vals=vals_kind, scope=scope)
            if quantize_on and hist_ledger:
                obs.inc("train.quant_refine_cols", float(program_notes["quant_refine_cols"] * c))
            if K > 1:
                obs.inc("train.class_trees", float(K * c))
            if goss:
                for name, per_iter in (("top_rows", goss_top), ("rest_rows", goss_rest), ("sample_rows", goss_top + goss_rest)):
                    obs.inc("goss." + name, float(per_iter * c))
            if quantize_on:
                trees_c, vsnap_c, qsc_c = scan_ys
            else:
                trees_c, vsnap_c = scan_ys
            tree_chunks.append(trees_c)
            if ckpt_path is not None:
                _write_checkpoint(trees_c)
            if vsets:
                # One batched transfer (issues every copy async, then waits)
                # — per-array np.asarray pulls pay a full dispatch RTT each.
                # Device-eval: each snap is (c, S) replicated stats, so the
                # transfer is O(iters × stats), independent of valid size.
                # each snap: (c, K, nv) host snapshot | per-metric (c, S)
                snaps = jax.device_get(list(vsnap_c))
                for j in range(c):
                    it = n_done + j
                    stop = False
                    for vs_i, (nm, vs, sn) in enumerate(
                        zip(names, vsets, snaps)
                    ):
                        is_tp = (
                            cfg.is_provide_training_metric
                            and vs_i == len(vsets) - 1
                        )
                        for mi, mname in enumerate(metric_names):
                            if device_eval:
                                m = vs["evaluators"][mi].finalize(sn[mi][j])
                            else:
                                div = (it + 1) if cfg.boosting == "rf" else 1
                                m = eval_metric(mi, sn[j] / div, vs["data"])
                            evals_result[nm][mname].append(m)
                            if _es_update(vs_i, mi, m, it, is_tp):
                                stop = True
                    if stop:
                        stop_at = it
                        break
            n_done += c
            if c:
                # Derived per-step telemetry: chunk wall + attribution
                # deltas split across the fused iterations (obs/steps.py).
                obs.steps.end(step_t, "scan", n_done - c, n=c,
                              chunk=chunk_idx)
            if obs.enabled() and c:
                # The whole-run scan fuses iterations on-device, so
                # per-iteration wall is DERIVED: the chunk's wall (dispatch
                # + eval sync) split evenly across its iterations.  The
                # legacy/DART loop below records REAL per-iteration spans.
                per_it = (time.perf_counter() - t_chunk) / c
                for j in range(n_done - c, n_done):
                    obs.record_span(
                        "booster.iteration", per_it, it=j, derived=True
                    )
                if quantize_on:
                    qsc_np = np.asarray(jax.device_get(qsc_c))  # (c, K, 2)
                    for jq, j in enumerate(range(n_done - c, n_done)):
                        obs.gauge(
                            "train.grad_scale",
                            float(qsc_np[jq, :, 0].max()), it=j,
                        )
                        obs.gauge(
                            "train.hess_scale",
                            float(qsc_np[jq, :, 1].max()), it=j,
                        )
            chunk_idx += 1

        kept = (stop_at + 1) if stop_at is not None else n_iter
        phases.enter("booster.collect", iters=kept)
        if ckpt_path is None and init_model is None:
            # The forest STAYS device-resident: one jitted concat/slice/
            # bias-fold program instead of a packed fetch + 10 re-uploads
            # (~3 RPC latencies per fit through remote-dispatch links).
            # Host copies materialize lazily (Booster._host_trees) only
            # for export/pickle paths.  Checkpoint and warm-start runs
            # keep the host path (their concat logic is numpy).
            stacked = _stack_chunks_device(
                tree_chunks, kept,
                np.asarray(init, np.float32).reshape(-1) if use_bfa else None,
            )
        else:
            # checkpointing already host-copied every chunk — reuse those
            chunks_np = (
                ckpt_host_chunks if ckpt_path is not None
                else _fetch_tree_chunks(tree_chunks, bool(cfg.categorical_feature))
            )  # one packed transfer otherwise
            stacked = Tree(
                *[np.concatenate(arrs, axis=0)[:kept] for arrs in zip(*chunks_np)]
            )
            if use_bfa:
                stacked = _fold_bias(stacked, init)
        if vsets:
            for nm in names:
                for mname in metric_names:
                    evals_result[nm][mname] = evals_result[nm][mname][:kept]
        if dart_scan:
            # dart forbids early stopping (ValueError above), so
            # kept == n_iter and the final carry's weight vector IS the
            # trained forest's weights
            assert kept == n_iter
            weights = np.asarray(carry[-1]).astype(np.float64)
        else:
            weights = np.ones(kept)
        final = _finalize_booster(
            stacked, weights, bin_mapper, cfg, init_model, evals_result,
            best_iter if cfg.early_stopping_round > 0 else -1,
        )
        if ckpt_path is not None:
            # Terminal snapshot: rewrite the checkpoint as the RETURNED
            # model (early stopping may have truncated past-chunk trees) and
            # record that the run COMPLETED this request, so a rerun with
            # the same dir returns this snapshot unchanged instead of
            # training past the recorded stopping point.
            final._ckpt_completed_for = requested_total
            _write_snapshot(final)
        return final

    assert key_start == 0  # dart forbids warm start, so no offset here
    if device_eval and vsets:
        # Legacy-loop (dart) counterpart of the in-scan stats: one jitted
        # stats reduction per eval set over the sharded score/label arrays.
        from jax.sharding import NamedSharding
        from jax.sharding import PartitionSpec as _PS

        _rep_leg = NamedSharding(mesh, _PS())

        def _make_stats_fn(evs):
            # ONE jitted dispatch returns every metric's stats tuple (a
            # per-metric fn would multiply the per-iteration RPC count by
            # the metric count on remote-dispatch links)
            @jax.jit
            def f(s, aux):
                ay, aw, am, aextras = aux
                return tuple(
                    jax.lax.with_sharding_constraint(
                        ev.stats(s, ay, aw, am, *aextras[mi]), _rep_leg
                    )
                    for mi, ev in enumerate(evs)
                )

            return f

        _legacy_stats = [_make_stats_fn(vs["evaluators"]) for vs in vsets]
    phases.close()  # the per-iteration spans are booster.train's own children
    for it in range(cfg.num_iterations):
        t_it = time.perf_counter()
        step_t = obs.steps.begin()
        sub = iter_keys_all[it]
        if do_bagging and it % cfg.bagging_freq == 0:
            current_bag = resample_bag(bag_keys_all[it], valid_mask)
        # drop set from the shared precomputed schedule (same RNG stream
        # as the scan path — see _dart_drop_schedule)
        dropped_idx: List[int] = (
            list(np.nonzero(drop_rows[it])[0]) if dart else []
        )
        if dropped_idx:
            drop_pred = []
            for t_i in dropped_idx:
                p = predict_v(trees_host[t_i], bins_dev)
                drop_pred.append(p)
                scores = scores - tree_weights[t_i] * p

        if cfg.boosting == "rf":
            train_scores = init_scores_dev  # RF: every tree fits the init residual
        else:
            train_scores = scores

        tree, delta, qsc = iteration(
            bins_dev, y_dev, w_dev, valid_mask, obj.device_state(),
            train_scores, sub, current_bag,
        )
        if qsc is not None and obs.enabled():
            qsc_np = np.asarray(qsc)  # (K, 2)
            obs.gauge("train.grad_scale", float(qsc_np[:, 0].max()), it=it)
            obs.gauge("train.hess_scale", float(qsc_np[:, 1].max()), it=it)

        # boost_from_average bias folding into tree 0 (LightGBM AddBias).
        # Running scores already start at the init value, so the in-loop
        # ``delta`` stays unbiased — only the *stored* tree gets the bias so
        # that predict-time Σtrees reproduces init + residuals.
        w_new = 1.0
        if it == 0 and use_bfa:
            bias = jnp.asarray(np.asarray(init, dtype=np.float32).reshape(K, 1))
            active = jnp.arange(cfg.num_leaves)[None, :] < tree.num_leaves[:, None]
            tree = tree._replace(leaf_value=jnp.where(active, tree.leaf_value + bias, 0.0))
        if dropped_idx:
            # DART normalization: new tree weighted 1/(k+1), dropped trees
            # rescaled by k/(k+1) and re-added (DART paper; LightGBM
            # ``DartBooster`` semantics with learning rate folded in leaves).
            k = len(dropped_idx)
            w_new = 1.0 / (k + 1.0)
            factor = k / (k + 1.0)
            for j, t_i in enumerate(dropped_idx):
                tree_weights[t_i] *= factor
                scores = scores + tree_weights[t_i] * drop_pred[j]
        # RF keeps a running sum averaged at eval time; boosted modes add the
        # (possibly DART-weighted) new tree.
        scores = scores + w_new * delta

        # Keep the tree as device arrays: a per-iteration np.asarray would
        # force a host sync (painful over remote-dispatch links); the single
        # conversion happens at stacking time below.
        trees_host.append(tree)
        tree_weights.append(w_new)

        # ---- validation & early stopping -------------------------------
        stop = False
        for vi_l, (nm, vs) in enumerate(zip(names, vsets)):
            # Valid scores start at init; the stored tree-0 bias must not be
            # double counted, so replay the *unbiased* growth delta.  The
            # stored tree already includes the bias, so subtract it back out.
            vdelta = predict_v(tree, vs["bins"])
            if it == 0 and use_bfa:
                vdelta = vdelta - jnp.asarray(
                    np.asarray(init, dtype=np.float32).reshape(K, 1)
                )
            if dropped_idx:
                k = len(dropped_idx)
                factor = k / (k + 1.0)
                for t_i in dropped_idx:
                    vp = predict_v(trees_host[t_i], vs["bins"])
                    # tree_weights[t_i] is already rescaled; its previous value
                    # was tree_weights[t_i]/factor.
                    vs["scores"] = vs["scores"] + (
                        tree_weights[t_i] - tree_weights[t_i] / factor
                    ) * vp
            vs["scores"] = vs["scores"] + w_new * vdelta
            div = (it + 1) if cfg.boosting == "rf" else 1
            is_tp = (
                cfg.is_provide_training_metric and vi_l == len(vsets) - 1
            )
            if device_eval:
                # one dispatch + one batched pull for ALL metrics
                sts = jax.device_get(
                    _legacy_stats[vi_l](vs["scores"] / div, vs["aux"])
                )
            for mi, mname in enumerate(metric_names):
                if device_eval:
                    m = vs["evaluators"][mi].finalize(sts[mi])
                else:
                    m = eval_metric(mi, vs["scores"] / div, vs["data"])
                evals_result[nm][mname].append(m)
                if _es_update(vi_l, mi, m, it, is_tp):
                    stop = True
        # Real per-iteration wall (grow dispatch + validation) — the
        # legacy/DART loop is iteration-at-a-time in Python, unlike the
        # fused scan path above.
        obs.record_span("booster.iteration", time.perf_counter() - t_it, it=it)
        obs.steps.end(step_t, "legacy", it)
        if stop:
            break

    # ---- stack trees (legacy/DART path) --------------------------------
    phases.enter("booster.collect", iters=len(trees_host))
    # Stack on DEVICE in ONE jitted program, then one host transfer per
    # field: pulling each tree's 8 small arrays separately costs a full
    # dispatch round-trip per pull (~0.5s each through a remote-dispatch
    # link — 400 pulls dominated wall-clock), and eager per-field stacks
    # cost 8 separate remote compiles.
    stacked_dev = jax.jit(
        lambda ts: jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *ts)
    )(trees_host)
    stacked = Tree(*[np.asarray(a) for a in stacked_dev])
    weights = np.asarray(tree_weights)
    return _finalize_booster(
        stacked, weights, bin_mapper, cfg, init_model, evals_result,
        best_iter if cfg.early_stopping_round > 0 else -1,
    )


def _placement(arr) -> dict:
    """Span attributes that say where a matrix lives: how many devices hold
    it and whether they hold shards of it or copies."""
    sh = getattr(arr, "sharding", None)
    if sh is None:
        return {"devices": 0, "sharded": False}
    return {
        "devices": len(sh.device_set),
        "sharded": not sh.is_fully_replicated,
    }


def _grow_jaxpr(grow, K: int, bins_dev, F_mask: int, quantized: bool,
                rows: Optional[int] = None):
    """The grower's jaxpr at the fit's shapes (``rows`` rows where it grows
    from a buffer of its own, GOSS's sample): an abstract trace, made once
    a program, with recording off so the trace-time ``collective.*``
    counters do not tick for it."""
    from mmlspark_tpu.obs import _state as obs_state

    n = rows or bins_dev.shape[0]
    f32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.float32)  # noqa: E731
    args = [
        jax.ShapeDtypeStruct((n,) + bins_dev.shape[1:], bins_dev.dtype), f32(K, n),
        f32(K, n), f32(n), jax.ShapeDtypeStruct((K, F_mask), jnp.bool_),
    ]
    if quantized:
        args += [jax.ShapeDtypeStruct((K, 2), jnp.uint32), f32(K, 2)]
    was, obs_state.enabled = obs_state.enabled, False
    try:
        return jax.make_jaxpr(grow)(*args)
    finally:
        obs_state.enabled = was


def _grow_ledgers(grow, gcfg: GrowConfig, K: int, bins_dev, F_mask: int,
                  quantized: bool, merges: bool, rows: Optional[int] = None) -> dict:
    """The ``program_notes`` of ONE boosting iteration, from one abstract
    trace of the grower: ``merge_ledger``, the bytes each device receives in
    its collectives (:func:`~mmlspark_tpu.parallel.distributed.collective_ledger`;
    ``None`` off a mesh), and ``hist_ledger`` (:func:`hist_ledger`).  The
    windowed grower's loop counts as the passes of a full tree
    (``full_tree_passes``; ``tests/test_dp_resident.py`` holds it to the
    loop's own trips): the program does not carry its trip count out, so a
    tree that runs out of valid splits early is counted high, and
    ``train.merge_bytes`` and ``hist.*`` are the work of full trees.
    ``quant_refine_cols``: the winner columns the refinement passes
    re-accumulate, a window's slots a pass of the windowed grower, one a
    step of the lossguide grower."""
    from mmlspark_tpu.engine.tree import full_tree_passes, windowed_grower
    from mmlspark_tpu.parallel.distributed import collective_ledger

    jaxpr = _grow_jaxpr(grow, K, bins_dev, F_mask, quantized, rows)
    trips = full_tree_passes(gcfg)
    hists = hist_ledger(jaxpr, while_trips=trips)
    refines = sum(work["passes"] for (_, _, scope), work in hists.items() if scope == "quant_refine")
    return {
        "merge_ledger": collective_ledger(jaxpr, while_trips=trips) if merges else None,
        "hist_ledger": hists,
        "quant_refine_cols": refines * (gcfg.level_window if windowed_grower(gcfg) else 1),
    }


def scope_entries(jaxpr, scopes, while_trips: int = 1, visit=None) -> dict:
    """``{scope: entries}``: how often one execution of a traced program
    enters each ``jax.named_scope`` of ``scopes``.  Equations that follow
    one another under a scope, at one level of the program, are one entry;
    a ``scan`` multiplies by its length, a ``while`` loop by
    ``while_trips`` (its count is not in the program: see
    ``collective_ledger``).  ``visit(scope, equations, times)`` is called
    for each entry."""
    out = dict.fromkeys(scopes, 0)

    def scope_of(eqn):
        names = str(eqn.source_info.name_stack).split("/")
        return next((s for s in scopes if s in names), None)

    def walk(jp, mult):
        for here, run in itertools.groupby(jp.eqns, scope_of):
            if here is not None:
                out[here] += mult
                if visit is not None:
                    visit(here, list(run), mult)
                continue
            for eqn in run:
                name = eqn.primitive.name
                inner = mult
                if name == "scan":
                    inner = mult * int(eqn.params["length"])
                elif name == "while":
                    inner = mult * int(while_trips)
                for sub in jax.core.jaxprs_in_params(eqn.params):
                    walk(sub, inner)

    walk(jaxpr.jaxpr, 1)
    return out


# the scopes a grower builds its histograms under (engine/tree.py)
HIST_SCOPES = ("hist_build", "quant_hist", "quant_refine")


def hist_ledger(jaxpr, while_trips: int = 1) -> dict:
    """What the histogram passes of one execution of a traced grower issue:
    ``{(body, vals, scope): {"passes", "rowcols", "mxu_flops",
    "vpu_elems"}}``.  A pass is one entry of a scope of :data:`HIST_SCOPES`
    (:func:`scope_entries`: full trees), labelled by the kernel body its
    calls reach (``plain``, ``by_leaf``, ``nibble``: the Pallas wrappers of
    ``ops/pallas_hist.py``, found by the name of their ``jit`` equation;
    ``scatter``: the other backend's scatter-add) and by the row values'
    dtype (``f32``, or ``i16`` buckets).  ``rowcols`` are the rows × columns
    those calls read, a chunk loop's calls times its length;
    ``mxu_flops`` and ``vpu_elems`` what the bodies issue for them, as each
    body states beside its kernel (``pallas_hist.call_work``), and 0 on the
    scatter backend."""
    from mmlspark_tpu.ops.pallas_hist import WRAPPERS, call_work

    out: dict = {}

    def calls(eqns, mult, found):
        for eqn in eqns:
            name = eqn.primitive.name
            if name in ("jit", "pjit") and eqn.params.get("name") in WRAPPERS:
                found.append((mult, call_work(eqn)))
            elif name == "scatter-add":
                updates = eqn.invars[2].aval  # (3, chunk rows x columns)
                found.append((mult, {
                    "body": "scatter", "quant": jnp.issubdtype(updates.dtype, jnp.integer),
                    "rowcols": int(updates.shape[-1]), "mxu_flops": 0, "vpu_elems": 0,
                }))
            else:
                inner = mult * int(eqn.params["length"]) if name == "scan" else mult
                for sub in jax.core.jaxprs_in_params(eqn.params):
                    calls(sub.eqns, inner, found)
        return found

    def visit(scope, run, times):
        found = calls(run, 1, [])
        if not found:
            return
        first = found[0][1]
        work = out.setdefault(
            (first["body"], "i16" if first["quant"] else "f32", scope),
            dict.fromkeys(("passes", "rowcols", "mxu_flops", "vpu_elems"), 0),
        )
        work["passes"] += times
        for mult, call in found:
            for name in ("rowcols", "mxu_flops", "vpu_elems"):
                work[name] += times * mult * call[name]

    scope_entries(jaxpr, HIST_SCOPES, while_trips=while_trips, visit=visit)
    return out


def _fold_bias(stacked: Tree, init) -> Tree:
    """boost_from_average bias folding into the STORED tree 0 (LightGBM
    AddBias): in-scan deltas stay unbiased (running scores already start at
    init), so the bias lands on the persisted leaf values exactly once."""
    bias = np.asarray(init, dtype=np.float32).reshape(-1)  # (K,) or (1,)
    lv = stacked.leaf_value.copy()  # (T, K, L)
    active = (
        np.arange(lv.shape[-1])[None, :] < stacked.num_leaves[0][:, None]
    )  # (K, L)
    lv[0] = np.where(active, lv[0] + bias[:, None], 0.0)
    return stacked._replace(leaf_value=lv)


def _finalize_booster(
    stacked: Tree,
    weights: np.ndarray,
    bin_mapper: BinMapper,
    cfg: TrainConfig,
    init_model: Optional[Booster],
    evals_result: Dict[str, Dict[str, List[float]]],
    best_iter: int,
) -> Booster:
    """Warm-start concat + Booster construction (shared by both train paths)."""
    t_offset = 0
    if init_model is not None:
        # Keep only the iterations the base scores came from: an early-
        # stopped init_model contributes best_iteration+1 trees, not its
        # full (partly discarded) forest.
        t_offset = init_model._used_iters(None)
        stacked = _concat_forests(init_model._slice_trees(t_offset), stacked)
        weights = np.concatenate([init_model.tree_weights[:t_offset], weights])
    booster = Booster(
        trees=Tree(*[jnp.asarray(a) for a in stacked]),
        tree_weights=weights,
        bin_mapper=bin_mapper,
        config=cfg,
        best_iteration=t_offset + best_iter if best_iter >= 0 else -1,
        average_output=cfg.boosting == "rf",
    )
    booster.evals_result = evals_result
    return booster
