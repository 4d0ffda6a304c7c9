"""Traffic kind ``train_loop_rank``: one client, closed loop, back-to-back
``engine.booster.train`` calls with ``objective=lambdarank`` on the resident
training split and its queries, each followed by the new model's evaluation on
the resident test split: its rows scored by ``_raw_scores_binned`` and their
NDCG taken by query on the device (the retrain's gate before a ranker is
promoted).

The shape of ``train_loop``: a fit is one ``lax.scan`` dispatch and cannot be
cut short, so the window is made of whole units and closes at the last
completion.
"""

import numpy as np

from benchmark import dataset_rank, reference_rank
from benchmark.traffic import train_loop
from benchmark.traffic.train_loop import _fit_and_evaluate, _replace, _train, fault_answer_altered

reference = reference_rank  # prove.py asks the traffic for its reference's VARIANTS


def _evaluate(booster, holdout, num_iteration=None):
    """``(raw scores (rows,), NDCG@k)`` of the test split under the new model,
    both left on the device: the program's scorer (``predict_backend=scan``)
    and the program's by-query metric over the split's query plan."""
    import jax

    scores = booster._raw_scores_binned(holdout["bins"], num_iteration=num_iteration)[0]
    ndcg = holdout["ndcg"](scores, holdout["label"], *holdout["plan"])
    jax.block_until_ready((scores, ndcg))
    return scores, ndcg


def _ndcg_fn(group, k: int):
    """The program's device NDCG@k over ``group``: ``(jitted f(scores, labels,
    *plan arrays), plan arrays on the device, the plan's bucket shapes)``."""
    import jax
    import jax.numpy as jnp

    from mmlspark_tpu.engine.dist_metrics import get_device_metric

    ev = get_device_metric(f"ndcg@{k}", group_sizes=group)
    plan = tuple(jnp.asarray(a) for a in ev.aux_host())

    def f(scores, labels, *plan):
        total, queries = ev.stats(scores[None, :], labels, None, None, *plan)
        return total / queries

    return jax.jit(f), plan, [list(b) for b in ev.plan.shape_key[0]]


def setup(cfg, workload, seed, train_fn=_train, eval_fn=_evaluate):
    """The resident training and test splits with their queries, and one warm
    fit and evaluation of the cell's own shapes."""
    ds, holdout, timings = dataset_rank.build(cfg, seed)
    holdout["ndcg"], holdout["plan"], buckets = _ndcg_fn(holdout["group"], int(cfg["eval_at"]))
    holdout["eval_plan"] = {"buckets": buckets, "rows": int(cfg["holdout_rows"]), "k": int(cfg["eval_at"])}
    params = dataset_rank.train_params(cfg, workload["iterations_per_fit"])
    state = {"ds": ds, "holdout": holdout, "params": params, "train_fn": train_fn, "eval_fn": eval_fn}
    booster, _, timings["warm_fit_s"], timings["warm_eval_s"] = _fit_and_evaluate(state)
    state |= {
        "cfg": cfg, "seed": seed,
        "iterations": int(workload["iterations_per_fit"]),
        "last_fit_s": timings["warm_fit_s"] + timings["warm_eval_s"],
        # each number compared has the cell's own limit, set in PERF.md section 2
        # from the chip readings of sound runs (lower) and of the control and
        # the planted faults (upper)
        "limits": dict(workload["limits"]),
        "resolved": {
            k: getattr(booster.config, k)
            for k in (
                "hist_backend", "split_batch", "hist_precision", "hist_chunk", "hist_quantize", "grow_policy",
                "predict_backend",
            )
        },
    }
    return state, timings


def window(state, seconds, max_fits=None):
    """``train_loop``'s window of whole units, with the evaluation's plan
    shapes beside it for the reader of the ranking ops' share."""
    result = train_loop.window(state, seconds, max_fits)
    result["eval_plan"] = state["holdout"]["eval_plan"]
    return result


def free(state):
    """Drop the program's device state before the reference runs."""
    state.pop("ds", None)
    state.pop("holdout", None)


def check(state, result, variant=None):
    """``{name: (value, limit)}`` for the last fit of the window; with a
    ``variant`` (``reference_rank.VARIANTS``) the reference stands in for the
    program with that fault planted in it."""
    trees = result["booster"]._host_trees()
    scores, ndcg = result.pop("evaluation")  # the device's copy goes with it
    holdout_scores, holdout_ndcg = np.asarray(scores), float(ndcg)
    del scores, ndcg
    gaps = reference_rank.compare(
        state["cfg"], state["seed"], trees, variant=variant, holdout_scores=holdout_scores, holdout_ndcg=holdout_ndcg,
    )
    limits = state["limits"]
    result["observed"] = {**{k: v for k, v in gaps.items() if k not in limits}, "holdout_ndcg": holdout_ndcg}
    return {k: (gaps[k], lim) for k, lim in limits.items()}


# ---- planted faults: each must make ``correct`` come out false ------------
def fault_state_unchanged(params, ds):
    """The score update left out: the second tree is grown from the first
    tree's gradients, so it is the first tree again (lambdarank folds no bias)."""
    b = _train(params, ds)

    def second_is_first(a):
        a[1] = a[0]
        return a

    return _replace(b, **{name: second_is_first for name in b._host_trees()._fields})


def _regrouped(ds, group, label=None):
    from mmlspark_tpu.data.streaming import StreamedDataset

    return StreamedDataset(
        authority=ds.authority, binned_dev=ds._binned_dev, packed=False,
        num_rows=ds.num_rows, num_features=ds.num_features, label=ds.label if label is None else label,
        group=group, occupancy=ds._occupancy,
    )


def fault_half_batch(params, ds):
    """The second half of the rows left out: the first half stands in its
    place, so every sum is taken over the first half alone and the fit keeps
    the cell's own shape (no new program to compile at size)."""
    import jax
    from jax import lax

    half = ds.num_rows // 2
    rest = ds.num_rows - half
    twice = jax.jit(lambda b: lax.dynamic_update_slice(b, b[:rest], (half, 0)), donate_argnums=0)
    ds._binned_dev = twice(ds._binned_dev)  # in place: the data set is spent
    ds._bins_cache, ds._dev_bins_cache = {}, {}
    return _train(params, _regrouped(ds, ds.group, label=np.concatenate([ds.label[:half], ds.label[:rest]])))


def fault_query_shift(params, ds):
    """Query boundaries shifted by one query: every query takes its
    neighbour's size, so rows are ranked against the wrong queries' rows.  The
    sizes are the same set, so the plan's buckets and the program keep their
    shapes."""
    shifted = _regrouped(ds, np.roll(ds.group, 1))
    shifted._bins_cache, shifted._dev_bins_cache = ds._bins_cache, ds._dev_bins_cache  # the resident padded copy
    shifted._cache_refs = ds._cache_refs
    return _train(params, shifted)


def fault_topk_short(params, ds):
    """The top-K cut taken one short: pairs are formed for the K - 1 best rows
    by score, and the discount is zero from rank K - 1 on."""
    return _train({**params, "max_position": int(params["max_position"]) - 1}, ds)


def fault_holdout_tree_dropped(booster, holdout):
    """The test split scored without the model's last tree."""
    return _evaluate(booster, holdout, num_iteration=booster.num_iterations - 1)


# each fault is the part of the timed path it stands in for: ``setup``'s keyword
FAULTS = {
    "state_unchanged": {"train_fn": fault_state_unchanged},
    "answer_altered": {"train_fn": fault_answer_altered},
    "holdout_tree_dropped": {"eval_fn": fault_holdout_tree_dropped},
    "query_shift": {"train_fn": fault_query_shift},
    "topk_short": {"train_fn": fault_topk_short},
    "half_batch": {"train_fn": fault_half_batch},  # last: it spends the data set
}
