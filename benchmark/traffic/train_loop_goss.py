"""Traffic kind ``train_loop_goss``: ``train_loop``'s closed loop of one
client, a fit and then the new model's evaluation on the resident holdout,
for a configuration that trains with gradient one-side sampling
(``boosting=goss``, ``top_rate``, ``other_rate``).

Set-up, window and ``train_loop``'s planted faults are ``train_loop``'s.
What differs is the reference ``check`` hands the window's last fit to
(``benchmark/reference_goss.py``: the five numbers over each tree's sample),
and three faults more, each a sample drawn otherwise than the configuration
states: the rest at weight 1, the rest a random count, the top drawn at
random.  Each replaces the program's ``engine.booster.goss_sample`` for one
fit, so that fit is traced and compiled anew (its scan program is not the
cached one, nor the exported one).
"""

import contextlib
import os

import numpy as np

from benchmark import reference_goss
from benchmark.traffic import train_loop
from benchmark.traffic.train_loop import _train, free, setup, window  # noqa: F401  (the kind's interface)

reference = reference_goss  # prove.py asks the traffic for its reference's VARIANTS


def check(state, result, variant=None):
    """``{name: (value, limit)}`` for the last fit of the window; with a
    ``variant`` (``reference_goss.VARIANTS``) the reference stands in for the
    program with that fault planted in it."""
    trees = result["booster"]._host_trees()
    scores, loss = result.pop("evaluation")  # the device's copy goes with it
    holdout_scores, holdout_logloss = np.asarray(scores), float(loss)
    del scores, loss
    gaps = reference_goss.compare(
        state["cfg"], state["seed"], trees, state["label_mean"], variant=variant, holdout_scores=holdout_scores,
    )
    limits = state["limits"]
    result["observed"] = {**{k: v for k, v in gaps.items() if k not in limits}, "holdout_logloss": holdout_logloss}
    return {k: (gaps[k], lim) for k, lim in limits.items()}


# ---- planted faults: each must make ``correct`` come out false ------------
@contextlib.contextmanager
def _sampler(replace):
    """The program's ``goss_sample`` replaced by ``replace(sound)`` for one
    fit, with its program caches out of the way on both sides of it."""
    from mmlspark_tpu.engine import booster

    sound, was = booster.goss_sample, os.environ.get("MMLSPARK_TPU_NO_TRACE_CACHE")
    booster._SCAN_CACHE.clear()
    os.environ["MMLSPARK_TPU_NO_TRACE_CACHE"] = "1"
    booster.goss_sample = replace(sound)
    try:
        yield
    finally:
        booster.goss_sample = sound
        booster._SCAN_CACHE.clear()
        if was is None:
            os.environ.pop("MMLSPARK_TPU_NO_TRACE_CACHE", None)
        else:
            os.environ["MMLSPARK_TPU_NO_TRACE_CACHE"] = was


def _faulty(replace):
    def train_fn(params, ds):
        with _sampler(replace):
            return _train(params, ds)
    return train_fn


def _amp_dropped(sound):
    """The rest's rows taken at weight 1: the sample's set, no amplification."""
    def sample(grad, valid, key, k_top, k_rest, amp):
        return sound(grad, valid, key, k_top, k_rest, 1.0)
    return sample


def _rest_bernoulli(sound):
    """Each row outside the top taken with probability ``other_rate``, as the
    engine drew the rest before its counts were exact: a random count."""
    import jax
    import jax.numpy as jnp

    def sample(grad, valid, key, k_top, k_rest, amp):
        w = sound(grad, valid, key, k_top, 0, amp)
        u = jax.random.uniform(jax.random.fold_in(key, 0xBE7), valid.shape)
        return jnp.where(w > 0, w, jnp.where(valid & (u < k_rest / valid.shape[0]), amp, 0.0))
    return sample


def _top_by_random(sound):
    """The top rows drawn at random: ranked by a uniform draw, not by ``|g|``."""
    import jax

    def sample(grad, valid, key, k_top, k_rest, amp):
        u = jax.random.uniform(jax.random.fold_in(key, 0x70B), grad.shape, grad.dtype)
        return sound(u, valid, key, k_top, k_rest, amp)
    return sample


# each fault is the part of the timed path it stands in for: ``setup``'s keyword
FAULTS = {
    **{k: v for k, v in train_loop.FAULTS.items() if k != "half_batch"},
    "amp_dropped": {"train_fn": _faulty(_amp_dropped)},
    "rest_bernoulli": {"train_fn": _faulty(_rest_bernoulli)},
    "top_by_random": {"train_fn": _faulty(_top_by_random)},
    "half_batch": train_loop.FAULTS["half_batch"],  # last: it spends the data set
}
