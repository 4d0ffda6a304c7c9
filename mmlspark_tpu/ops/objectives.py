"""Boosting objectives: gradients/hessians as pure JAX functions.

Parity target: LightGBM's objective set as exposed by the reference's
``objective`` param (SURVEY.md §2.3.1: "binary", "multiclass",
"multiclassova", "regression", "quantile", "huber", "fair", "poisson",
"mape", "gamma", "tweedie", "lambdarank"; upstream C++
``src/objective/*.cpp`` shipped inside the ``lightgbmlib`` jar — [REF-EMPTY]
provenance).  Conventions follow LightGBM: ``score`` is the raw (pre-link)
model output, ``grad = d loss/d score``, ``hess = d²loss/d score²``, and
``boost_from_average`` seeds the initial score.

All functions are jit-safe (static shapes, no Python control flow on traced
values) so they can live inside the training step that gets ``shard_map``-ped
over the device mesh.
"""

from __future__ import annotations

from typing import Callable, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from mmlspark_tpu.ops.rank_plan import build_rank_plan, by_bucket, score_key, to_rows


class Objective:
    """Base: single-score-per-row objective."""

    name = "base"
    num_model_per_iteration = 1  # K>1 for multiclass
    default_metric = "l2"
    # True when instances carry PER-DATASET state (set after construction,
    # e.g. LambdaRank's group matrix).  The booster's cross-call scan-program
    # cache closes over the objective from the FIRST call with a given
    # config, which is only sound for stateless instances — stateful
    # objectives MUST set this so the cache excludes them.
    stateful = False

    def __init__(self, **params):
        self.params = params
        self.sigmoid = float(params.get("sigmoid", 1.0))

    # -- host-side -------------------------------------------------------
    def init_score(self, y: np.ndarray, w: Optional[np.ndarray]) -> float:
        """boost_from_average seed (scalar raw score)."""
        return 0.0

    # -- distributed boost_from_average ----------------------------------
    # Process-local training never materializes the global label vector, so
    # the init score is computed from SUMMED sufficient statistics instead:
    # every process contributes ``init_score_stats`` (local), the vectors
    # are element-wise summed across processes (one tiny allgather), and
    # ``init_score_from_stats`` maps the global sums to the seed score.
    # The avg-based family ([weighted-sum, weight-total] → f(avg)) covers
    # every objective except the quantile/median ones, which raise.
    def init_score_stats(self, y: np.ndarray, w: Optional[np.ndarray]) -> np.ndarray:
        wv = np.ones_like(y, dtype=np.float64) if w is None else np.asarray(w, dtype=np.float64)
        return np.asarray([float(np.sum(wv * y)), float(np.sum(wv))])

    def init_score_from_stats(self, stats: np.ndarray):
        return self._init_from_avg(float(stats[0]) / max(float(stats[1]), 1e-300))

    def _init_from_avg(self, avg: float):
        return 0.0  # objectives without bias folding keep a zero seed

    def state_key(self):
        """Fingerprint of per-dataset state for STATEFUL objectives, or
        None when state is unset/unfingerprintable.  Lets the booster's
        cross-call scan-program cache include stateful instances safely:
        same config + same state key ⇒ the closed-over instance computes
        identical gradients, so the compiled program is reusable (without
        this, every lambdarank train() call re-traced the whole scan)."""
        return None

    # -- device-side -----------------------------------------------------
    def grad_hess(
        self, score: jnp.ndarray, y: jnp.ndarray, w: Optional[jnp.ndarray]
    ) -> Tuple[jnp.ndarray, jnp.ndarray]:
        raise NotImplementedError

    def device_state(self):
        """Device arrays the gradient reads beside score, label and weight
        (a pytree; empty for a stateless objective).  A jitted caller passes
        them as an ARGUMENT to :meth:`grad_hess_from`, so nothing large
        becomes a constant of its program."""
        return ()

    def grad_hess_from(self, state, score, y, w):
        return self.grad_hess(score, y, w)

    def transform(self, score: jnp.ndarray) -> jnp.ndarray:
        """Raw score → user-facing prediction (link function)."""
        return score

    def _apply_weight(self, grad, hess, w):
        if w is None:
            return grad, hess
        return grad * w, hess * w


def _avg(y, w):
    return float(np.average(y, weights=w))


class BinaryObjective(Objective):
    """Logistic loss; label in {0,1}.  grad = σ(s)−y, hess = σ(s)(1−σ(s))."""

    name = "binary"
    default_metric = "binary_logloss"

    def init_score(self, y, w):
        p = min(max(_avg(y, w), 1e-15), 1 - 1e-15)
        return float(np.log(p / (1 - p)) / self.sigmoid)

    def _init_from_avg(self, avg):
        p = min(max(avg, 1e-15), 1 - 1e-15)
        return float(np.log(p / (1 - p)) / self.sigmoid)

    def grad_hess(self, score, y, w):
        p = jax.nn.sigmoid(self.sigmoid * score)
        grad = self.sigmoid * (p - y)
        hess = self.sigmoid * self.sigmoid * p * (1.0 - p)
        return self._apply_weight(grad, hess, w)

    def transform(self, score):
        return jax.nn.sigmoid(self.sigmoid * score)


class RegressionL2(Objective):
    name = "regression"
    default_metric = "l2"

    def init_score(self, y, w):
        return _avg(y, w)

    def _init_from_avg(self, avg):
        return float(avg)

    def grad_hess(self, score, y, w):
        return self._apply_weight(score - y, jnp.ones_like(score), w)


class RegressionL1(Objective):
    name = "regression_l1"
    default_metric = "l1"

    def init_score(self, y, w):
        return float(np.median(y))

    def init_score_stats(self, y, w):
        raise NotImplementedError(
            f"objective {self.name!r} seeds from a quantile/median, which has "
            f"no summable sufficient statistics; process-local training "
            f"requires boost_from_average=False for it"
        )

    def grad_hess(self, score, y, w):
        return self._apply_weight(jnp.sign(score - y), jnp.ones_like(score), w)


class Huber(Objective):
    name = "huber"
    default_metric = "huber"

    def init_score(self, y, w):
        return _avg(y, w)

    def _init_from_avg(self, avg):
        return float(avg)

    def grad_hess(self, score, y, w):
        alpha = float(self.params.get("alpha", 0.9))
        d = score - y
        grad = jnp.clip(d, -alpha, alpha)
        return self._apply_weight(grad, jnp.ones_like(score), w)


class Fair(Objective):
    name = "fair"
    default_metric = "fair"

    def init_score(self, y, w):
        return _avg(y, w)

    def _init_from_avg(self, avg):
        return float(avg)

    def grad_hess(self, score, y, w):
        c = float(self.params.get("fair_c", 1.0))
        d = score - y
        denom = jnp.abs(d) + c
        return self._apply_weight(c * d / denom, c * c / (denom * denom), w)


class Poisson(Objective):
    name = "poisson"
    default_metric = "poisson"

    def init_score(self, y, w):
        return float(np.log(max(_avg(y, w), 1e-15)))

    def _init_from_avg(self, avg):
        return float(np.log(max(avg, 1e-15)))

    def grad_hess(self, score, y, w):
        max_delta = float(self.params.get("poisson_max_delta_step", 0.7))
        ez = jnp.exp(score)
        return self._apply_weight(ez - y, ez * np.exp(max_delta), w)

    def transform(self, score):
        return jnp.exp(score)


class Gamma(Objective):
    name = "gamma"
    default_metric = "gamma"

    def init_score(self, y, w):
        return float(np.log(max(_avg(y, w), 1e-15)))

    def _init_from_avg(self, avg):
        return float(np.log(max(avg, 1e-15)))

    def grad_hess(self, score, y, w):
        ye = y * jnp.exp(-score)
        return self._apply_weight(1.0 - ye, ye, w)

    def transform(self, score):
        return jnp.exp(score)


class Tweedie(Objective):
    name = "tweedie"
    default_metric = "tweedie"

    def init_score(self, y, w):
        return float(np.log(max(_avg(y, w), 1e-15)))

    def _init_from_avg(self, avg):
        return float(np.log(max(avg, 1e-15)))

    def grad_hess(self, score, y, w):
        rho = float(self.params.get("tweedie_variance_power", 1.5))
        a = -y * jnp.exp((1.0 - rho) * score)
        b = jnp.exp((2.0 - rho) * score)
        grad = a + b
        hess = a * (1.0 - rho) + b * (2.0 - rho)
        return self._apply_weight(grad, hess, w)

    def transform(self, score):
        return jnp.exp(score)


class Quantile(Objective):
    name = "quantile"
    default_metric = "quantile"

    def init_score(self, y, w):
        alpha = float(self.params.get("alpha", 0.9))
        return float(np.quantile(y, alpha))

    def init_score_stats(self, y, w):
        raise NotImplementedError(
            f"objective {self.name!r} seeds from a quantile/median, which has "
            f"no summable sufficient statistics; process-local training "
            f"requires boost_from_average=False for it"
        )

    def grad_hess(self, score, y, w):
        alpha = float(self.params.get("alpha", 0.9))
        grad = jnp.where(score >= y, 1.0 - alpha, -alpha)
        return self._apply_weight(grad, jnp.ones_like(score), w)


class MAPE(Objective):
    name = "mape"
    default_metric = "mape"

    def init_score(self, y, w):
        return float(np.median(y))

    def init_score_stats(self, y, w):
        raise NotImplementedError(
            f"objective {self.name!r} seeds from a quantile/median, which has "
            f"no summable sufficient statistics; process-local training "
            f"requires boost_from_average=False for it"
        )

    def grad_hess(self, score, y, w):
        inv = 1.0 / jnp.maximum(jnp.abs(y), 1.0)
        grad = jnp.sign(score - y) * inv
        return self._apply_weight(grad, inv, w)


class Multiclass(Objective):
    """Softmax cross-entropy; one tree per class per iteration.

    ``score``/outputs have shape (K, n).  The engine's hessian is the
    diagonal ``2·p(1−p)``; LightGBM's (recalled, not checked) is
    ``K/(K−1)·p(1−p)``.
    """

    name = "multiclass"
    default_metric = "multi_logloss"

    def __init__(self, **params):
        super().__init__(**params)
        self.num_class = int(params.get("num_class", 2))
        self.num_model_per_iteration = self.num_class

    def init_score(self, y, w):
        return np.zeros(self.num_class, dtype=np.float64)

    def init_score_stats(self, y, w):
        return np.zeros(1)

    def init_score_from_stats(self, stats):
        return np.zeros(self.num_class, dtype=np.float64)

    def grad_hess(self, score, y, w):
        # score: (K, n); y: (n,) integer class labels
        p = jax.nn.softmax(score, axis=0)
        onehot = jax.nn.one_hot(y.astype(jnp.int32), self.num_class, axis=0)
        grad = p - onehot
        hess = 2.0 * p * (1.0 - p)
        if w is not None:
            grad, hess = grad * w[None, :], hess * w[None, :]
        return grad, hess

    def transform(self, score):
        return jax.nn.softmax(score, axis=0)


class MulticlassOVA(Multiclass):
    """One-vs-all: K independent binary objectives."""

    name = "multiclassova"

    def grad_hess(self, score, y, w):
        p = jax.nn.sigmoid(self.sigmoid * score)
        onehot = jax.nn.one_hot(y.astype(jnp.int32), self.num_class, axis=0)
        grad = self.sigmoid * (p - onehot)
        hess = self.sigmoid**2 * p * (1.0 - p)
        if w is not None:
            grad, hess = grad * w[None, :], hess * w[None, :]
        return grad, hess

    def transform(self, score):
        p = jax.nn.sigmoid(self.sigmoid * score)
        return p / jnp.sum(p, axis=0, keepdims=True)


class LambdaRank(Objective):
    """LambdaRank with NDCG delta weighting over query groups.

    Reference parity: LightGBM ``lambdarank`` (upstream
    ``src/objective/rank_objective.hpp`` — [REF-EMPTY]) as surfaced by
    ``LightGBMRanker`` (SURVEY.md §2.3).  Queries are carried as a
    :class:`~mmlspark_tpu.ops.rank_plan.RankPlan` (buckets by length), and
    the pairwise terms of a bucket are formed for its top ``max_position``
    rows by score against all of its rows: a pair with both members below
    the cut has equal (zero) discounts and so a zero delta-NDCG, which
    leaves ``(G_b, K, M_b)`` terms where all pairs are ``(G, M, M)``.
    """

    name = "lambdarank"
    default_metric = "ndcg"
    stateful = True  # set_plan() stores the data set's query plan

    def __init__(self, **params):
        super().__init__(**params)
        self.sigmoid = float(params.get("sigmoid", 2.0) or 2.0)
        self.label_gain = params.get("label_gain")
        self.max_position = int(params.get("max_position", 20) or 20)

    def set_groups(self, group_sizes: np.ndarray):
        """Plan and upload the queries of ``group_sizes`` rows each."""
        return self.set_plan(build_rank_plan(group_sizes))

    def set_plan(self, plan, arrays=None):
        """Install a query plan and its device arrays (``arrays``: the
        caller's placement, e.g. replicated over a multi-process mesh, or
        the copy kept with a resident data set; default: uploaded here)."""
        self._plan = plan
        self._plan_arrays = plan.device_arrays() if arrays is None else arrays
        return self

    def state_key(self):
        plan = getattr(self, "_plan", None)
        return None if plan is None else plan.shape_key

    def device_state(self):
        return self._plan_arrays

    def _gains(self, labels):
        if self.label_gain is not None:
            table = jnp.asarray(np.asarray(self.label_gain, dtype=np.float64))
            return table[labels.astype(jnp.int32)]
        return 2.0 ** labels.astype(jnp.float32) - 1.0

    def _bucket_lambdas(self, s, gain, valid, pos):
        """Gradient and hessian ``(2, G, M)`` of one bucket's rows, zero
        where ``valid`` is not."""
        K = min(self.max_position, pos.shape[0])
        top = jnp.arange(K)
        disc = 1.0 / jnp.log2(top + 2.0)
        gain = jnp.where(valid, gain, 0.0)
        idcg = jnp.sum(lax.top_k(gain, K)[0] * disc, axis=1)
        inv_idcg = jnp.where(idcg > 0, 1.0 / jnp.maximum(idcg, 1e-12), 0.0)
        # the K best rows by score (ties in row order, padding last) are the
        # pairs' one side (axis a), all M rows the other; lax.top_k puts the
        # lower index first among equals, and a full sort of four operands
        # compiles for 15-27 s a bucket where this takes one
        s_a, row_a = lax.top_k(score_key(s, valid), K)
        valid_a = top[None, :] < jnp.sum(valid, axis=1, keepdims=True)
        s_a = jnp.where(valid_a, s_a, 0.0)
        is_a = row_a[:, :, None] == pos[None, None, :]  # (G, K, M): row m holds rank a
        gain_a = jnp.sum(jnp.where(is_a, gain[:, None, :], 0.0), axis=2)
        disc_m = jnp.sum(jnp.where(is_a, disc[None, :, None], 0.0), axis=1)
        rank_m = jnp.sum(jnp.where(is_a, top[None, :, None], 0), axis=1) + jnp.where(is_a.any(axis=1), 0, K)
        gd = gain_a[:, :, None] - gain[:, None, :]
        sgn = jnp.sign(gd)
        # a pair of two top rows appears at (a, m) and at (m's rank, a's row): kept where a is the better rank
        pair = (
            valid_a[:, :, None] & valid[:, None, :] & (gd != 0)
            & (rank_m[:, None, :] > top[None, :, None])
        )
        delta = jnp.abs(gd) * jnp.abs(disc[None, :, None] - disc_m[:, None, :]) * inv_idcg[:, None, None]
        rho = jax.nn.sigmoid(-self.sigmoid * sgn * (s_a[:, :, None] - s[:, None, :]))
        lam = jnp.where(pair, -self.sigmoid * rho * delta * sgn, 0.0)  # to row a; minus it to row m
        hs = jnp.where(pair, self.sigmoid**2 * rho * (1.0 - rho) * delta, 0.0)
        # each row's sum over the pairs it is in, on either side
        grad_a, hess_a = jnp.sum(lam, axis=2), jnp.sum(hs, axis=2)  # (G, K)
        grad = jnp.sum(jnp.where(is_a, grad_a[:, :, None], 0.0) - lam, axis=1)
        hess = jnp.sum(jnp.where(is_a, hess_a[:, :, None], 0.0) + hs, axis=1)
        return jnp.stack([grad, hess])

    def grad_hess(self, score, y, w):
        return self.grad_hess_from(self._plan_arrays, score, y, w)

    def grad_hess_from(self, state, score, y, w):
        with jax.named_scope("rank_grad"):
            per_bucket = [
                self._bucket_lambdas(s, self._gains(lbl), valid, pos)
                for (s, lbl), valid, pos in by_bucket(state, (score, y))
            ]
            grad, hess = to_rows(state, per_bucket, score.shape[0])
            hess = jnp.maximum(hess, 1e-9)
        if w is not None:
            grad, hess = grad * w, hess * w
        return grad, hess


_REGISTRY = {
    "binary": BinaryObjective,
    "regression": RegressionL2,
    "regression_l2": RegressionL2,
    "l2": RegressionL2,
    "mean_squared_error": RegressionL2,
    "mse": RegressionL2,
    "regression_l1": RegressionL1,
    "l1": RegressionL1,
    "mae": RegressionL1,
    "huber": Huber,
    "fair": Fair,
    "poisson": Poisson,
    "gamma": Gamma,
    "tweedie": Tweedie,
    "quantile": Quantile,
    "mape": MAPE,
    "multiclass": Multiclass,
    "softmax": Multiclass,
    "multiclassova": MulticlassOVA,
    "multiclass_ova": MulticlassOVA,
    "ova": MulticlassOVA,
    "lambdarank": LambdaRank,
}


def get_objective(name: str, **params) -> Objective:
    try:
        cls = _REGISTRY[name]
    except KeyError:
        raise ValueError(
            f"unknown objective {name!r}; supported: {sorted(set(_REGISTRY))}"
        ) from None
    return cls(**params)
