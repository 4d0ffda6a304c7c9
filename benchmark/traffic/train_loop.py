"""Traffic kind ``train_loop``: one client, closed loop, back-to-back
``engine.booster.train`` calls on the resident data set, each followed, where
the configuration holds rows out, by the new model's evaluation on the
resident holdout (the retrain's gate before a model is promoted).

A fit is one ``lax.scan`` dispatch and cannot be cut short, so the window is
made of whole fits: a new one starts only if the last fit's wall, evaluation
included, still fits before ``--seconds``, and the window closes at the last
completion.
"""

import functools
import time

import numpy as np

from benchmark import dataset, reference


def _train(params, ds):
    import jax

    from mmlspark_tpu.engine.booster import train

    booster = train(params, ds)
    jax.block_until_ready(booster.trees)
    return booster


def _evaluate(booster, holdout, num_iteration=None):
    """``(raw scores (rows,), log loss)`` of the holdout under the new model,
    both left on the device.  The scores are the program's, by the scorer that
    ``train()`` warm-starts from (``_raw_scores_binned``; the configuration
    asks for the ``scan`` backend, whose program does not depend on the trees'
    depth); the loss is a plain reduction over them."""
    import jax

    scores = booster._raw_scores_binned(holdout["bins"], num_iteration=num_iteration)[0]
    loss = _logloss()(scores, holdout["label"])
    jax.block_until_ready((scores, loss))
    return scores, loss


@functools.cache
def _logloss():
    import jax
    import jax.numpy as jnp

    return jax.jit(lambda s, y: jnp.mean(jax.nn.softplus(s) - y * s))


def _fit_and_evaluate(state):
    """One unit of the loop: ``(booster, evaluation or None, fit s, evaluation s)``."""
    t0 = time.perf_counter()
    booster = state["train_fn"](state["params"], state["ds"])
    t1 = time.perf_counter()
    evaluation = state["eval_fn"](booster, state["holdout"]) if state["holdout"] else None
    return booster, evaluation, t1 - t0, time.perf_counter() - t1


def setup(cfg, workload, seed, train_fn=_train, eval_fn=_evaluate):
    """The resident data set and holdout, and one warm fit and evaluation of
    the cell's own shapes."""
    ds, holdout, timings = dataset.build(cfg, seed)
    params = dataset.train_params(cfg, workload["iterations_per_fit"])
    state = {"ds": ds, "holdout": holdout, "params": params, "train_fn": train_fn, "eval_fn": eval_fn}
    booster, _, timings["warm_fit_s"], timings["warm_eval_s"] = _fit_and_evaluate(state)
    state |= {
        "cfg": cfg, "seed": seed,
        "label_mean": float(np.mean(ds.label)),  # of the labels the generator made
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
    """Fits back to back until the next would overrun; all the work over all
    the time.  ``max_fits`` = 1 is the traced window."""
    fits, evals = [], []
    booster = evaluation = None
    t_start = time.perf_counter()
    last = state["last_fit_s"]
    while not fits or (time.perf_counter() - t_start + last <= seconds):
        booster, evaluation, fit_s, eval_s = _fit_and_evaluate(state)
        last = fit_s + eval_s
        fits.append(fit_s)
        evals.append(eval_s)
        if max_fits and len(fits) >= max_fits:
            break
    wall = time.perf_counter() - t_start
    iters = len(fits) * state["iterations"]
    return {
        "booster": booster, "evaluation": evaluation, "fit_s": fits, "eval_s": evals, "wall_s": wall,
        "iterations": iters, "attempted": len(fits), "failed": 0,
        "end_to_end": {"train_rowiters_per_s": state["ds"].num_rows * iters / wall},
    }


def free(state):
    """Drop the program's device state before the reference runs."""
    state.pop("ds", None)
    state.pop("holdout", None)


def check(state, result, variant=None):
    """``{name: (value, limit)}`` for the last fit of the window; with a
    ``variant`` (``reference.VARIANTS``) the reference stands in for the
    program with that fault planted in it."""
    trees = result["booster"]._host_trees()
    holdout_scores, extra = None, {}
    if result.get("evaluation") is not None:
        scores, loss = result.pop("evaluation")  # the device's copy goes with it
        holdout_scores, extra = np.asarray(scores), {"holdout_logloss": float(loss)}
        del scores, loss
    gaps = reference.compare(
        state["cfg"], state["seed"], trees, state["label_mean"], variant=variant, holdout_scores=holdout_scores,
    )
    limits = state["limits"]
    result["observed"] = {**{k: v for k, v in gaps.items() if k not in limits}, **extra}
    return {k: (gaps[k], lim) for k, lim in limits.items()}


# ---- planted faults: each must make ``correct`` come out false ------------
def _replace(booster, **fields):
    import jax.numpy as jnp

    host = booster._host_trees()
    host = host._replace(**{k: f(np.array(getattr(host, k))) for k, f in fields.items()})
    booster.trees = type(host)(*[jnp.asarray(a) for a in host])
    booster._trees_np = host
    return booster


def fault_state_unchanged(params, ds):
    """The score update left out: the second tree is grown from the first
    tree's gradients, so it is the first tree again (less the folded bias)."""
    b = _train(params, ds)
    host = b._host_trees()
    bias = float(np.log(ds.label.mean() / (1 - ds.label.mean())))

    def second_is_first(name):
        def f(a):
            a[1] = a[0] - np.float32(bias) if name == "leaf_value" else a[0]
            return a
        return f

    return _replace(b, **{name: second_is_first(name) for name in host._fields if name != "num_leaves"})


def fault_half_batch(params, ds):
    """The second half of the rows left out: the first half stands in its
    place, so every mean is taken over the first half alone and the fit keeps
    the cell's own shape (no new program to compile at size)."""
    import jax
    from jax import lax

    from mmlspark_tpu.data.streaming import StreamedDataset

    n = ds.num_rows // 2
    twice = jax.jit(lambda b: lax.dynamic_update_slice(b, b[:n], (n, 0)), donate_argnums=0)
    ds._binned_dev = twice(ds._binned_dev)  # in place: the data set is spent
    half = StreamedDataset(
        authority=ds.authority, binned_dev=ds._binned_dev, packed=False, num_rows=ds.num_rows,
        num_features=ds.num_features, label=np.concatenate([ds.label[:n], ds.label[:n]]),
        occupancy=ds._occupancy,
    )
    return _train(params, half)


def fault_answer_altered(params, ds):
    """The largest leaf value of the last tree doubled where it is produced."""
    def f(a):
        a[-1, 0, np.argmax(np.abs(a[-1, 0]))] *= 2.0
        return a
    return _replace(_train(params, ds), leaf_value=f)


def fault_holdout_tree_dropped(booster, holdout):
    """The holdout scored without the model's last tree."""
    return _evaluate(booster, holdout, num_iteration=booster.num_iterations - 1)


# each fault is the part of the timed path it stands in for: ``setup``'s keyword
FAULTS = {
    "state_unchanged": {"train_fn": fault_state_unchanged},
    "answer_altered": {"train_fn": fault_answer_altered},
    "holdout_tree_dropped": {"eval_fn": fault_holdout_tree_dropped},
    "half_batch": {"train_fn": fault_half_batch},  # last: it spends the data set
}
