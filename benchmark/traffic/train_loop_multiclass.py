"""Traffic kind ``train_loop_multiclass``: ``train_loop``'s closed loop of one
client, a fit and then the new model's evaluation on the resident holdout,
for a configuration that fits K > 1 trees an iteration
(``objective=multiclass``, ``num_class``).

Set-up and window are ``train_loop``'s.  What differs: the evaluation scores
all K classes (``(K, rows)`` raw scores from ``_raw_scores_binned``) and
gates on their ``multi_logloss``; ``check`` hands the window's last fit to
``benchmark/reference_multiclass.py``; a state left unchanged is the first
iteration's K trees again.  Three faults more, each the objective's gradient
taken otherwise than the configuration states, for one fit: each class from
its own sigmoid (``multiclassova``'s rule), class k's tree grown from class
k+1's gradient, the hessian ``p (1 - p)``.  Each replaces the program's
``Multiclass.grad_hess`` for that fit, so it is traced and compiled anew
(its scan program is not the cached one, nor the exported one).
"""

import contextlib
import functools
import os

import numpy as np

from benchmark import reference_multiclass
from benchmark.traffic import train_loop
from benchmark.traffic.train_loop import _replace, _train, free, window  # noqa: F401  (the kind's interface)

reference = reference_multiclass  # prove.py asks the traffic for its reference's VARIANTS


def _evaluate(booster, holdout, num_iteration=None):
    """``(raw scores (K, rows), multi_logloss)`` of the holdout under the new
    model, both left on the device: the program's scores by the scorer
    ``train()`` warm-starts from, the loss a plain reduction over them."""
    import jax

    scores = booster._raw_scores_binned(holdout["bins"], num_iteration=num_iteration)
    loss = _multi_logloss()(scores, holdout["label"])
    jax.block_until_ready((scores, loss))
    return scores, loss


@functools.cache
def _multi_logloss():
    import jax
    import jax.numpy as jnp

    def loss(s, y):
        own = jnp.where(jnp.arange(s.shape[0])[:, None] == y.astype(jnp.int32)[None, :], s, 0.0).sum(axis=0)
        return jnp.mean(jax.nn.logsumexp(s, axis=0) - own)

    return jax.jit(loss)


def setup(cfg, workload, seed, train_fn=_train, eval_fn=_evaluate):
    return train_loop.setup(cfg, workload, seed, train_fn=train_fn, eval_fn=eval_fn)


def check(state, result, variant=None):
    """``{name: (value, limit)}`` for the last fit of the window; with a
    ``variant`` (``reference_multiclass.VARIANTS``) the reference stands in
    for the program with that fault planted in it."""
    trees = result["booster"]._host_trees()
    scores, loss = result.pop("evaluation")  # the device's copy goes with it
    holdout_scores, holdout_logloss = np.asarray(scores), float(loss)
    del scores, loss
    gaps = reference_multiclass.compare(
        state["cfg"], state["seed"], trees, variant=variant, holdout_scores=holdout_scores,
        holdout_logloss=holdout_logloss,
    )
    limits = state["limits"]
    result["observed"] = {**{k: v for k, v in gaps.items() if k not in limits}, "holdout_logloss": holdout_logloss}
    return {k: (gaps[k], lim) for k, lim in limits.items()}


# ---- planted faults: each must make ``correct`` come out false ------------
def fault_state_unchanged(params, ds):
    """The score update left out: the second iteration's K trees are grown
    from the first iteration's gradients, so they are the first's again
    (every class starts at 0: no bias is folded into them)."""
    def second_is_first(a):
        a[1] = a[0]
        return a

    b = _train(params, ds)
    return _replace(b, **{name: second_is_first for name in b._host_trees()._fields if name != "num_leaves"})


def fault_holdout_tree_dropped(booster, holdout):
    """The holdout scored without the model's last iteration of trees."""
    return _evaluate(booster, holdout, num_iteration=booster.num_iterations - 1)


@contextlib.contextmanager
def _gradient(replace):
    """The program's ``Multiclass.grad_hess`` replaced by ``replace(sound)``
    for one fit, with its program caches out of the way on both sides."""
    from mmlspark_tpu.engine import booster
    from mmlspark_tpu.ops import objectives

    sound, was = objectives.Multiclass.grad_hess, os.environ.get("MMLSPARK_TPU_NO_TRACE_CACHE")
    booster._SCAN_CACHE.clear()
    os.environ["MMLSPARK_TPU_NO_TRACE_CACHE"] = "1"
    objectives.Multiclass.grad_hess = replace(sound)
    try:
        yield
    finally:
        objectives.Multiclass.grad_hess = sound
        booster._SCAN_CACHE.clear()
        if was is None:
            os.environ.pop("MMLSPARK_TPU_NO_TRACE_CACHE", None)
        else:
            os.environ["MMLSPARK_TPU_NO_TRACE_CACHE"] = was


def _faulty(replace):
    def train_fn(params, ds):
        with _gradient(replace):
            return _train(params, ds)
    return train_fn


def _ova_gradient(sound):
    """Each class's gradient from its own sigmoid (``multiclassova``'s rule):
    the softmax's coupling of the classes lost."""
    def grad_hess(self, score, y, w):
        import jax
        import jax.numpy as jnp

        p = jax.nn.sigmoid(score)
        onehot = (jnp.arange(score.shape[0])[:, None] == y.astype(jnp.int32)[None, :]).astype(score.dtype)
        grad, hess = p - onehot, p * (1.0 - p)
        return (grad, hess) if w is None else (grad * w[None, :], hess * w[None, :])
    return grad_hess


def _class_shift(sound):
    """Class k's tree grown from class k+1's gradient and hessian."""
    def grad_hess(self, score, y, w):
        import jax.numpy as jnp

        grad, hess = sound(self, score, y, w)
        return jnp.roll(grad, -1, axis=0), jnp.roll(hess, -1, axis=0)
    return grad_hess


def _hess_halved(sound):
    """The hessian ``p (1 - p)``, the other rule, for the configuration's
    ``2 p (1 - p)``."""
    def grad_hess(self, score, y, w):
        grad, hess = sound(self, score, y, w)
        return grad, 0.5 * hess
    return grad_hess


# each fault is the part of the timed path it stands in for: ``setup``'s keyword
FAULTS = {
    "state_unchanged": {"train_fn": fault_state_unchanged},
    "answer_altered": train_loop.FAULTS["answer_altered"],
    "holdout_tree_dropped": {"eval_fn": fault_holdout_tree_dropped},
    "ova_gradient": {"train_fn": _faulty(_ova_gradient)},
    "class_shift": {"train_fn": _faulty(_class_shift)},
    "hess_halved": {"train_fn": _faulty(_hess_halved)},
    "half_batch": train_loop.FAULTS["half_batch"],  # last: it spends the data set
}
