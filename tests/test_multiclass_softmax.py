"""Multiclass softmax (K trees an iteration) against the plain reference.

The engine's fit of ``objective=multiclass`` on a resident device-binned
data set, scored by ``Booster._raw_scores_binned``, as the benchmark's
multiclass cell runs it, at 8,192 rows of the cell's 22 columns (11
categorical): every number ``benchmark/reference_multiclass.py`` recomputes
from the rows (leaf counts, leaf values, gains over every tree; the
holdout's ``(K, rows)`` scores) within the cell's own limits; each fault of
the objective's gradient that the cell plants fails them; and the device
regions that K > 1 adds are in a K > 1 fit's program and in no K = 1
fit's."""

import collections
import contextlib
import json
import os

import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import dataset, reference_multiclass
from benchmark.data import expedia
from benchmark.traffic import train_loop_multiclass
from mmlspark_tpu import obs

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SEED = 2**31 + 41
TINY = {"rows": 8192, "holdout_rows": 4096, "chunk_rows": 4096, "bin_sample_rows": 4096}


def _load(name):
    with open(os.path.join(ROOT, "benchmark", name)) as f:
        return json.load(f)


LIMITS = _load("workloads/expedia_multiclass_train_1chip.json")["limits"]


class _Classes:
    """The cell's rows with its 100 clusters folded into ``K`` classes."""

    NUM_FEATURES, CATEGORICAL = expedia.NUM_FEATURES, expedia.CATEGORICAL

    def __init__(self, K):
        self.K = K

    def chunk(self, key, index, rows):
        X, y = expedia.chunk(key, index, rows)
        return X, jnp.floor(y * self.K / expedia.NUM_CLASSES)


@pytest.fixture()
def cell(monkeypatch):
    def make(K, objective="multiclass"):
        monkeypatch.setattr(dataset, "data_module", lambda cfg: _Classes(K))
        cfg = dict(_load("configs/expedia_hotel_multiclass.json"), **TINY)
        cfg["params"] = dict(cfg["params"], objective=objective, num_class=K, num_leaves=7)
        if objective != "multiclass":
            del cfg["params"]["num_class"]
        ds, holdout, _ = dataset.build(cfg, SEED)
        return cfg, ds, holdout, dataset.train_params(cfg, 2)
    return make


def _gaps(cfg, booster, holdout):
    scores, loss = train_loop_multiclass._evaluate(booster, holdout)
    return reference_multiclass.compare(
        cfg, SEED, booster._host_trees(), holdout_scores=np.asarray(scores), holdout_logloss=float(loss),
    )


@pytest.mark.parametrize("K", [5, 100])
def test_fit_matches_the_teacher_forced_reference(cell, K):
    cfg, ds, holdout, params = cell(K)
    booster = train_loop_multiclass._train(params, ds)
    assert np.asarray(booster._host_trees().leaf_value).shape[:2] == (2, K)  # K trees an iteration
    gaps = _gaps(cfg, booster, holdout)
    assert gaps["leaf_count_gap"] == 0.0 and gaps["holdout_score_gap"] == 0.0, gaps
    assert all(gaps[k] <= lim for k, lim in LIMITS.items()), gaps
    assert gaps["holdout_logloss_gap"] < 1e-5, gaps


def test_windowed_fit_with_its_roots_built_together_matches_the_reference(cell):
    """The chip's grower (8 splits a pass), whose K trees take their roots
    from one build of every class's root (``class_roots_active``)."""
    cfg, ds, holdout, params = cell(5)
    booster = train_loop_multiclass._train(dict(params, split_batch=8), ds)
    gaps = _gaps(cfg, booster, holdout)
    assert gaps["leaf_count_gap"] == 0.0 and gaps["holdout_score_gap"] == 0.0, gaps
    assert all(gaps[k] <= lim for k, lim in LIMITS.items()), gaps
    assert gaps["holdout_logloss_gap"] < 1e-5, gaps


@pytest.mark.parametrize("fault", ["ova_gradient", "class_shift", "hess_halved"])
def test_a_gradient_fault_fails_the_limits(cell, fault):
    cfg, ds, holdout, params = cell(5)
    booster = train_loop_multiclass.FAULTS[fault]["train_fn"](params, ds)
    gaps = _gaps(cfg, booster, holdout)
    assert any(gaps[k] > lim for k, lim in LIMITS.items()), gaps


@contextlib.contextmanager
def _recording():
    obs.reset()
    obs.flight.reset()
    obs.enable()
    try:
        yield
    finally:
        obs.disable()
        obs.reset()


def test_class_regions_are_a_multiclass_fit_s_alone(cell):
    from mmlspark_tpu.engine.booster import _SCAN_CACHE

    found = {}
    for K, objective in ((5, "multiclass"), (2, "binary")):
        cfg, ds, _, params = cell(K, objective)
        _SCAN_CACHE.clear()
        with _recording():
            train_loop_multiclass._train(params, ds)
            counters = dict(obs.snapshot()["counters"])
            maps = obs.device.regions()
        fits = [m for name, m in maps.items() if name.startswith("booster.fit")]
        assert len(fits) == 1
        found[objective] = (collections.Counter(fits[0].values()), counters)
    regions, counters = found["multiclass"]
    assert regions["class_grad"] > 0 and regions["class_update"] > 0, regions
    assert counters["train.class_trees"] == 2 * 5
    regions, counters = found["binary"]
    assert regions["class_grad"] == regions["class_update"] == 0 and regions["leaf_delta"] > 0, regions
    assert "train.class_trees" not in counters
