"""mmlspark_tpu.engine.multi_train — K boosters, ONE XLA dispatch.

The retrain loop (``loop/controller.py``) emits many SMALL per-tenant
training jobs — the "millions of users" shape of ROADMAP item 3 is
thousands of per-segment models, each a few thousand rows.  Trained
one at a time, every tenant pays a fresh trace + compile for its own
row count (XLA compiles one program per shape), and the dispatch
overhead dominates the actual device work.  This module is the
training-side twin of ``engine/forest.MultiPackedForest``: stack K
boosters that share ONE binning authority into a single jitted
program, so the whole batch is one trace, one compile, one dispatch.

Layout contract (documented in ``ops/README.md``): every tensor the
standalone fused-scan trainer carries grows a leading model axis —
bins ``(K, N, F)``, labels/weights/masks ``(K, N)``, running scores
``(K, C, N)``, per-iteration key material ``(K, T, 5)``.  The model
axis is driven by ``jax.lax.map`` (compile the body once, run models
sequentially — the same trade ``_grow_classes`` makes for the class
axis: vmapping the grower multiplies Mosaic/XLA compile time ~25x),
and the per-model boosting run is the standalone ``lax.scan`` body,
verbatim.  XLA therefore sees ONE program regardless of K.

Bitwise parity contract: every stacked model is bit-identical to its
standalone ``train()`` run — same fold_in key schedule (per-model
root keys ride the xs input), same histogram accumulation (rows pad
with ``bag == 0`` entries whose grad/hess/count contributions are
exact zeros, and both paths stay inside ``build_histogram``'s
single-chunk branch), same split tie-breaks (the grower runs the
identical gcfg).  Models with fewer iterations than the stack's
maximum are MASKED (``scores += act * delta`` with ``act ∈ {0, 1}``
— multiply-by-1.0 is IEEE-exact), never retraced; their surplus
trees are dropped on the host.

Exclusions (ValueError, never silent degradation): row subsampling
(bagging / GOSS) draws shape-``(n,)`` uniforms, so a padded stack
would consume different random streams than the standalone run;
DART / RF reshape the whole loop; ranking objectives carry per-model
group state; early stopping needs valid sets the stacked path does
not take; quantized histogram wires and mesh learners are
single-model concerns.  Everything else — categoricals,
feature_fraction, warm starts, boost_from_average, is_unbalance —
rides through unchanged.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from mmlspark_tpu import obs
from mmlspark_tpu.engine.booster import (
    _PARALLEL_LEARNERS,
    Booster,
    Dataset,
    TrainConfig,
    _capture_quality_baseline,
    _cfg_cache_key,
    _feature_mask,
    _fetch_tree_chunks,
    _finalize_booster,
    _fold_bias,
    _leaf_delta,
    _pad_rows,
    resolve_auto_config,
)
from mmlspark_tpu.engine.tree import GrowConfig, Tree, grow_tree_auto
from mmlspark_tpu.ops.binning import BinMapper
from mmlspark_tpu.ops.objectives import LambdaRank, get_objective

__all__ = [
    "MultiTrainJob", "multi_train", "fit_shared_mapper",
    "mapper_fingerprint",
]


def mapper_fingerprint(bin_mapper: BinMapper) -> str:
    """Content digest of a fitted mapper — the shared-authority test.

    Identity (``is``) is too strict for the loop: every checkpoint
    round-trip clones the champion's mapper, yet fleets co-trained
    under one authority still carry bit-identical bin vocabularies.
    Mappers with equal fingerprints bin every row identically, which
    is all the stacked layout needs.
    """
    import hashlib
    import json

    blob = json.dumps(
        bin_mapper.to_dict(), sort_keys=True,
        default=lambda o: np.asarray(o).tolist(),
    )
    return hashlib.sha256(blob.encode()).hexdigest()


@dataclasses.dataclass
class MultiTrainJob:
    """One tenant's slot in a stacked train: params + data (+ warm
    start).  ``name`` is carried through for the serving/loop callers
    (``serve/coresident`` swaps are keyed by tenant name)."""

    params: dict
    train_set: Dataset
    init_model: Optional[Booster] = None
    name: Optional[str] = None


def fit_shared_mapper(
    datasets: Sequence[Dataset], params: dict
) -> BinMapper:
    """Fit ONE binning authority over the pooled rows of every tenant.

    The shared-authority contract is what makes a stacked train
    possible at all (one ``(K, N, F)`` bins tensor needs one bin
    vocabulary); it is also the fleet deployment shape — co-resident
    serving (``serve/coresident``) already bins every tenant through
    one stacked boundary table.
    """
    from mmlspark_tpu.ops.binning import BinningAuthority

    cfg = TrainConfig.from_params(dict(params))
    X = np.concatenate([np.asarray(ds.X) for ds in datasets], axis=0)
    return BinningAuthority.fit(
        X,
        max_bin=cfg.max_bin,
        categorical_features=tuple(cfg.categorical_feature),
        seed=cfg.seed,
        threads=cfg.num_threads,
    ).mapper


# Config fields allowed to differ across a stack: everything else is a
# static the ONE traced program closes over, so a mismatch would
# silently train model i under model 0's hyperparameters.
_PER_MODEL_FIELDS = frozenset(
    {"seed", "bagging_seed", "num_iterations", "verbosity"}
)

# One-program trace ledger: the jitted stacked body appends here at
# TRACE time (the Python closure runs once per trace, never per
# dispatch), so tests can pin "K=64 models, one program" directly.
_TRACE_EVENTS: List[Tuple[int, int]] = []  # (models, iters) per trace

# Jitted stacked programs cached across multi_train() calls, same
# discipline as booster._SCAN_CACHE (bounded FIFO keyed on every
# static the closure bakes in).
_MULTI_CACHE: Dict[Tuple, callable] = {}
_MULTI_CACHE_MAX = 8


def _static_fingerprint(cfg: TrainConfig) -> Tuple:
    return tuple(
        (f.name, getattr(cfg, f.name))
        for f in dataclasses.fields(cfg)
        if f.name not in _PER_MODEL_FIELDS
    )


def _validate_job(cfg: TrainConfig, job: MultiTrainJob, i: int) -> None:
    tag = job.name or f"jobs[{i}]"
    if cfg.boosting != "gbdt":
        raise ValueError(
            f"multi_train supports boosting='gbdt' only; {tag} asked for "
            f"{cfg.boosting!r} (dart/rf reshape the per-iteration loop, and "
            "goss grows each tree from a sample of its own model's rows: "
            "none can share the stacked program)"
        )
    if cfg.bagging_freq > 0 and cfg.bagging_fraction < 1.0:
        raise ValueError(
            f"multi_train does not support bagging ({tag}): the bag draw "
            "is a shape-(n,) uniform, so padded stacked rows would "
            "consume a different random stream than the standalone run "
            "and break the bitwise-parity contract"
        )
    if cfg.early_stopping_round > 0:
        raise ValueError(
            f"multi_train takes no valid sets, so early_stopping_round "
            f"has nothing to watch ({tag}); cap num_iterations per job "
            "instead (shorter jobs are masked, not retraced)"
        )
    if cfg.checkpoint_dir:
        raise ValueError(
            f"multi_train does not checkpoint ({tag}): stacked jobs are "
            "small and re-run whole; use train() for checkpointed fits"
        )
    if cfg.tree_learner in _PARALLEL_LEARNERS:
        raise ValueError(
            f"multi_train is single-device by design ({tag}); "
            f"tree_learner={cfg.tree_learner!r} needs a mesh"
        )
    if cfg.hist_quantize != "off":
        raise ValueError(
            f"multi_train requires hist_quantize='off' ({tag}): the "
            "quantized wire's SR keys are per-model state the stacked "
            "program does not carry"
        )
    if job.train_set.group is not None:
        raise ValueError(
            f"ranking groups are per-model state ({tag}); multi_train "
            "does not support lambdarank"
        )


def _grow_classes(gcfg_):
    # Mirror of booster._train_impl._grow_classes (meshless, unquantized
    # — the only legs multi_train admits): one tree per class via
    # lax.map, NOT vmap, because batching the grower's scatter/pallas
    # ops multiplies compile time ~25x while lax.map compiles the body
    # once.  The model axis above makes the same trade.
    def grow_all(bins_a, grad_a, hess_a, bag_a, fmask_a):
        def one(args):
            g, h, fm = args
            return grow_tree_auto(gcfg_, bins_a, g, h, bag_a, fm)

        return jax.lax.map(one, (grad_a, hess_a, fmask_a))

    return grow_all


def _build_multi_program(cfg, gcfg, obj, Kc, F, has_w):
    """The ONE jitted program: lax.map over the model axis of the
    standalone fused-scan body.  Every statement inside ``body`` is the
    standalone ``scan_chunk`` body's no-bagging/no-dart/no-valid leg,
    token for token — that textual identity IS the parity argument."""
    grow = _grow_classes(gcfg)

    def _fmask_one(key):
        return _feature_mask(key, F, cfg.feature_fraction)

    def one_model(args):
        if has_w:
            bins_a, y_a, w_a, vmask_a, init_sc, xs_m, act_m = args
        else:
            bins_a, y_a, vmask_a, init_sc, xs_m, act_m = args
            w_a = None

        def body(scores_c, xt):
            xs_row, act = xt
            key = xs_row[:2]
            grad, hess = obj.grad_hess(
                scores_c if Kc > 1 else scores_c[0], y_a, w_a
            )
            if Kc == 1:
                grad, hess = grad[None, :], hess[None, :]
            gkey, fkey = jax.random.split(key)
            fkey = jax.random.fold_in(fkey, cfg.feature_fraction_seed)
            bag = vmask_a.astype(jnp.float32)
            fmask = jax.vmap(_fmask_one)(jax.random.split(fkey, Kc))
            tree, leaf_ids = grow(bins_a, grad, hess, bag, fmask)
            delta = _leaf_delta(tree, leaf_ids)
            # Finished models are MASKED, not retraced: act is 1.0 for
            # live iterations (×1.0 is IEEE-exact, scores stay bitwise)
            # and 0.0 past a model's horizon (its surplus trees are
            # sliced off on the host).
            scores_c = scores_c + act * delta
            return scores_c, tree

        return jax.lax.scan(body, init_sc, (xs_m, act_m))

    def multi_chunk(bins_s, y_s, w_s, vmask_s, init_s, xs_s, act_s):
        # Trace-time ledger entry: this Python body runs once per
        # trace/compile, so the list length counts PROGRAMS, not
        # dispatches — the "one program for the whole stack" pin.
        _TRACE_EVENTS.append(
            (int(bins_s.shape[0]), int(xs_s.shape[1]))
        )
        if has_w:
            operand = (bins_s, y_s, w_s, vmask_s, init_s, xs_s, act_s)
        else:
            operand = (bins_s, y_s, vmask_s, init_s, xs_s, act_s)
        return jax.lax.map(one_model, operand)

    return jax.jit(multi_chunk)


def multi_train(
    jobs: Sequence[MultiTrainJob],
    bin_mapper: Optional[BinMapper] = None,
) -> List[Booster]:
    """Train every job in ONE stacked XLA dispatch; returns one
    :class:`Booster` per job, in order, each bitwise-identical to its
    standalone ``train(job.params, job.train_set,
    init_model=job.init_model)`` run under the same shared mapper.

    ``bin_mapper`` is the shared authority.  It may be omitted only
    when every job warm-starts (the init models' pinned mapper is the
    authority then, and all must carry the SAME one).
    """
    jobs = list(jobs)
    if not jobs:
        return []

    cfgs = [TrainConfig.from_params(dict(j.params)) for j in jobs]
    for i, (cfg, job) in enumerate(zip(cfgs, jobs)):
        _validate_job(cfg, job, i)

    # ---- shared binning authority --------------------------------------
    if bin_mapper is None:
        mappers = {
            mapper_fingerprint(j.init_model.bin_mapper):
                j.init_model.bin_mapper
            for j in jobs
            if j.init_model is not None
        }
        if len(mappers) != 1 or any(j.init_model is None for j in jobs):
            raise ValueError(
                "multi_train needs ONE shared binning authority: pass "
                "bin_mapper=..., or warm-start every job from boosters "
                "that share a mapper (fit_shared_mapper pools tenant "
                "rows into one)"
            )
        bin_mapper = next(iter(mappers.values()))
    shared_fp = mapper_fingerprint(bin_mapper)
    for i, job in enumerate(jobs):
        if job.init_model is not None and (
            job.init_model.bin_mapper is not bin_mapper
            and mapper_fingerprint(job.init_model.bin_mapper) != shared_fp
        ):
            raise ValueError(
                f"jobs[{i}]'s init_model was binned under a different "
                "authority; warm-start continuation pins the mapper"
            )
        # Pin the shared mapper into each Dataset's cache so a later
        # standalone train() on the same Dataset bins identically —
        # the parity tests (and any caller comparing the two paths)
        # rely on this.
        job.train_set.pin_mapper(bin_mapper, cfgs[i])

    # ---- per-model host prep (mirrors _train_impl, meshless) -----------
    objs = [
        get_objective(cfg.objective, **cfg.objective_params())
        for cfg in cfgs
    ]
    obj = objs[0]
    if isinstance(obj, LambdaRank):
        raise ValueError("multi_train does not support ranking objectives")
    Kc = obj.num_model_per_iteration
    B = bin_mapper.num_bins

    bins_list, n_list = [], []
    for i, job in enumerate(jobs):
        bins_np = np.asarray(job.train_set.binned(bin_mapper))
        bins_list.append(bins_np)
        n_list.append(int(bins_np.shape[0]))
        if job.init_model is not None:
            if job.init_model.num_class != (Kc if Kc > 1 else 1):
                raise ValueError(
                    f"jobs[{i}]'s init_model num_class does not match"
                )
    F = int(bins_list[0].shape[1])
    if any(b.shape[1] != F for b in bins_list):
        raise ValueError(
            "every job must share the authority's feature width"
        )

    backend = jax.default_backend()
    cfgs = [
        resolve_auto_config(
            cfg, n=n, backend=backend, num_devices=1,
            num_features=F, num_bins=B,
        )
        for cfg, n in zip(cfgs, n_list)
    ]
    fp0 = _static_fingerprint(cfgs[0])
    for i, cfg in enumerate(cfgs[1:], 1):
        if _static_fingerprint(cfg) != fp0:
            diff = [
                name for (name, a), (_, b)
                in zip(fp0, _static_fingerprint(cfg)) if a != b
            ]
            raise ValueError(
                f"stacked jobs must share every static config field; "
                f"jobs[{i}] differs from jobs[0] on {diff} (only "
                f"{sorted(_PER_MODEL_FIELDS)} may vary)"
            )
    cfg0 = cfgs[0]

    chunk = cfg0.hist_chunk
    N = max(n_list)
    if N > chunk:
        raise ValueError(
            f"multi_train stacks SMALL models: max rows {N} exceeds one "
            f"histogram chunk ({chunk}); train() handles the large case"
        )

    # ---- per-model tensors, padded to (N rows, T_max iterations) -------
    T_list = [cfg.num_iterations for cfg in cfgs]
    T_max = max(T_list)
    M = len(jobs)

    bins_rows, y_rows, w_rows, vmask_rows = [], [], [], []
    init_rows, xs_rows, act_rows = [], [], []
    use_bfa_list, init_vals = [], []
    for i, (job, cfg, n) in enumerate(zip(jobs, cfgs, n_list)):
        train_set = job.train_set
        n_pad = N - n
        bins_rows.append(_pad_rows(bins_list[i], n_pad))
        y_rows.append(_pad_rows(train_set.label, n_pad))
        vmask_rows.append(
            np.concatenate([np.ones(n, bool), np.zeros(n_pad, bool)])
        )

        # weights (is_unbalance / scale_pos_weight) — standalone block
        w = train_set.weight
        if cfg.objective == "binary":
            pos = max(float((train_set.label > 0).sum()), 1.0)
            neg = max(float((train_set.label <= 0).sum()), 1.0)
            spw = neg / pos if cfg.is_unbalance else cfg.scale_pos_weight
            if spw != 1.0:
                base = (
                    np.ones(n) if w is None
                    else np.asarray(w, dtype=np.float64)
                )
                w = np.where(train_set.label > 0, base * spw, base)
        w_rows.append(
            None if w is None
            else _pad_rows(np.asarray(w, dtype=np.float64), n_pad)
        )

        # init score (boost_from_average / init_score / warm start)
        use_bfa = (
            cfg.boost_from_average
            and train_set.init_score is None
            and job.init_model is None
        )
        if use_bfa:
            init = obj.init_score(train_set.label, train_set.weight)
        else:
            init = np.zeros(Kc) if Kc > 1 else 0.0
        use_bfa_list.append(use_bfa)
        init_vals.append(init)
        init_arr = np.broadcast_to(
            np.asarray(init, dtype=np.float32).reshape(-1, 1), (Kc, N)
        ).copy()
        if train_set.init_score is not None:
            init_arr = init_arr + _pad_rows(
                train_set.init_score.astype(np.float32), n_pad
            ).reshape(1, -1)
        if job.init_model is not None:
            # Same replay the standalone warm start runs (per-row tree
            # walk — padding rows score garbage that the bag mask
            # zeroes, exactly as standalone's own chunk padding does).
            init_arr = init_arr + np.asarray(
                job.init_model._raw_scores_binned(
                    jnp.asarray(bins_rows[i])
                ),
                dtype=np.float32,
            )
        init_rows.append(init_arr)

        # per-model key schedule: absolute-index fold_in, warm starts
        # resume at the init forest's horizon — standalone verbatim.
        key_start = (
            job.init_model._used_iters(None)
            if job.init_model is not None else 0
        )
        total_keyed = key_start + cfg.num_iterations
        root_key = jax.random.PRNGKey(cfg.bagging_seed + 7919 * cfg.seed)
        _abs_idx = jnp.arange(total_keyed, dtype=jnp.uint32)
        iter_keys_all = np.asarray(
            jax.vmap(lambda k: jax.random.fold_in(root_key, k))(_abs_idx)
        )
        iter_keys = iter_keys_all[key_start:total_keyed]
        bag_keys = np.zeros(
            (cfg.num_iterations, 2), dtype=iter_keys_all.dtype
        )
        it_global = np.arange(key_start, total_keyed, dtype=np.int32)
        xs_packed = np.concatenate(
            [
                np.asarray(iter_keys, dtype=np.uint32),
                np.asarray(bag_keys, dtype=np.uint32),
                it_global[:, None].astype(np.uint32),
            ],
            axis=1,
        )
        t_pad = T_max - cfg.num_iterations
        if t_pad:
            xs_packed = np.concatenate(
                [xs_packed, np.zeros((t_pad, 5), np.uint32)]
            )
        xs_rows.append(xs_packed)
        act_rows.append(
            np.concatenate(
                [
                    np.ones(cfg.num_iterations, np.float32),
                    np.zeros(t_pad, np.float32),
                ]
            )
        )

    has_w_set = {w is not None for w in w_rows}
    if len(has_w_set) != 1:
        raise ValueError(
            "stacked jobs must uniformly carry (or omit) row weights — "
            "mixed presence would change the traced program's arity"
        )
    has_w = next(iter(has_w_set))

    gcfg = GrowConfig(
        num_bins=B,
        num_leaves=cfg0.num_leaves,
        max_depth=cfg0.max_depth,
        min_data_in_leaf=cfg0.min_data_in_leaf,
        min_sum_hessian_in_leaf=cfg0.min_sum_hessian_in_leaf,
        lambda_l1=cfg0.lambda_l1,
        lambda_l2=cfg0.lambda_l2,
        min_gain_to_split=cfg0.min_gain_to_split,
        learning_rate=cfg0.learning_rate,
        hist_backend=cfg0.hist_backend,
        hist_chunk=chunk,
        hist_precision=cfg0.hist_precision,
        hist_merge="allreduce",
        hist_quantize=cfg0.hist_quantize,
        quantize_shift=0,
        grow_policy=cfg0.grow_policy,
        split_batch=cfg0.split_batch,
        categorical_features=tuple(
            int(f) for f in cfg0.categorical_feature
        ),
        cat_smooth=cfg0.cat_smooth,
        cat_l2=cfg0.cat_l2,
        max_cat_threshold=(
            cfg0.max_cat_threshold if cfg0.max_cat_threshold > 0
            else cfg0.max_bin
        ),
        cat_value_bins=max(
            (
                len(getattr(bin_mapper, "cat_maps", {}).get(f, ()))
                for f in cfg0.categorical_feature
            ),
            default=0,
        ),
        voting=False,
        top_k=cfg0.top_k,
        onehot_stats=jax.default_backend() == "tpu",  # see _train_impl
    )

    # Per-model fields ride as runtime data (seeds through the xs
    # fold-in schedule, iteration counts through the activity mask), so
    # they must NOT key the program — two stacks differing only in
    # seeds share the cached executable.
    cache_key = (
        tuple(kv for kv in _cfg_cache_key(cfg0)
              if kv[0] not in _PER_MODEL_FIELDS),
        Kc, F, B, type(obj).__name__, gcfg, has_w,
    )
    program = _MULTI_CACHE.get(cache_key)
    if program is None:
        program = _build_multi_program(cfg0, gcfg, obj, Kc, F, has_w)
        if len(_MULTI_CACHE) >= _MULTI_CACHE_MAX:
            _MULTI_CACHE.pop(next(iter(_MULTI_CACHE)))
        _MULTI_CACHE[cache_key] = program

    # ---- the ONE dispatch ----------------------------------------------
    bins_s = jnp.asarray(np.stack(bins_rows))
    y_s = jnp.asarray(np.stack(y_rows).astype(np.float32))
    w_s = (
        jnp.asarray(np.stack(w_rows).astype(np.float32)) if has_w else None
    )
    vmask_s = jnp.asarray(np.stack(vmask_rows))
    init_s = jnp.asarray(np.stack(init_rows))
    xs_s = jnp.asarray(np.stack(xs_rows))
    act_s = jnp.asarray(np.stack(act_rows))

    t0 = time.perf_counter()
    step_t = obs.steps.begin()
    with obs.span(
        "multi_train.dispatch", models=M, iters=T_max, rows=N,
    ):
        _, trees = program(
            bins_s, y_s, w_s, vmask_s, init_s, xs_s, act_s
        )
        trees = jax.block_until_ready(trees)
    wall = time.perf_counter() - t0
    obs.inc("train.multi.dispatches")
    obs.inc("train.multi.models", float(M), K=M)
    row_iters = sum(n * t for n, t in zip(n_list, T_list))
    if wall > 0:
        obs.gauge("train.multi.rows_per_s", row_iters / wall, K=M)
    obs.steps.end(step_t, "multi", 0, n=M, models=M, iters=T_max)

    # ---- per-model host finalize ---------------------------------------
    has_cats = bool(cfg0.categorical_feature)
    (fetched,) = _fetch_tree_chunks([trees], has_cats)
    boosters: List[Booster] = []
    for i, (job, cfg) in enumerate(zip(jobs, cfgs)):
        fields = [np.asarray(a)[i, : T_list[i]] for a in fetched]
        stacked = Tree(*fields)
        if use_bfa_list[i]:
            stacked = _fold_bias(stacked, init_vals[i])
        booster = _finalize_booster(
            stacked, np.ones(T_list[i]), bin_mapper, cfg,
            job.init_model, {}, -1,
        )
        if booster.quality_baseline is None:
            booster.quality_baseline = _capture_quality_baseline(
                booster, job.train_set
            )
        boosters.append(booster)
    return boosters
