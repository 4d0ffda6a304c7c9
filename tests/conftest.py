"""Test configuration: force an 8-device virtual CPU mesh BEFORE jax import.

Mirrors the reference's test strategy of faking a cluster in-process
(SURVEY.md §4.3: Spark ``local[*]`` with N partitions = N "machines"); here
the analog is ``jax_num_cpu_devices=8`` so distributed
``shard_map``/``psum`` paths run for real on one host (SURVEY.md §4
"Rebuild mapping").
"""

import os
import sys

# The AOT trace cache (core/trace_cache) pays an export per first-ever
# program — pure overhead across hundreds of small test configs.  The
# feature has its own dedicated test (tests/test_trace_cache.py), which
# re-enables it explicitly.
os.environ.setdefault("MMLSPARK_TPU_NO_TRACE_CACHE", "1")

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)

# The suite's own compile-cache directory (core/jit_cache.cache_dir reads
# this variable; jax reads it at import): tests share warm XLA entries
# with each other and with their subprocesses, and with nothing else.
os.environ.setdefault(
    "JAX_COMPILATION_CACHE_DIR", os.path.join(_REPO, ".jax_cache", "tests")
)
# Tests force the CPU: 8 virtual devices so the distributed
# ``shard_map``/``psum`` paths run for real on one host, Pallas kernels
# under the interpreter.  The chip is only ever reached through
# ``chip_smoke.py`` (README "Testing").
os.environ["JAX_PLATFORMS"] = "cpu"
# XLA's CPU collectives have a watchdog that ABORTS the process (not a
# Python exception) when a psum straggles past the default 30s — on a
# loaded host, 8 virtual devices sharing cores can trip it
# nondeterministically (observed as "Fatal Python error: Aborted" inside
# the shard_map/psum train path).
os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "")
    + " --xla_cpu_collective_timeout_seconds=600"
    + " --xla_cpu_collective_call_terminate_timeout_seconds=1200"
).strip()

import jax

jax.config.update("jax_platforms", "cpu")  # also when a plugin imported jax first
jax.config.update("jax_num_cpu_devices", 8)

assert jax.default_backend() == "cpu", "tests must run on the CPU backend"
assert jax.device_count() == 8, (
    "expected an 8-device virtual CPU mesh; backend initialized too early"
)

import numpy as np
import pytest


@pytest.fixture(scope="session")
def binary_df():
    """Small binary-classification DataFrame (breast-cancer, offline)."""
    from sklearn.datasets import load_breast_cancer

    from mmlspark_tpu import DataFrame

    X, y = load_breast_cancer(return_X_y=True)
    data = {f"f{i}": X[:, i] for i in range(X.shape[1])}
    data["label"] = y.astype(np.float64)
    data["features"] = list(X.astype(np.float64))
    return DataFrame(data, num_partitions=2)


@pytest.fixture(scope="session")
def regression_df():
    from sklearn.datasets import load_diabetes

    from mmlspark_tpu import DataFrame

    X, y = load_diabetes(return_X_y=True)
    data = {f"f{i}": X[:, i] for i in range(X.shape[1])}
    data["label"] = y.astype(np.float64)
    data["features"] = list(X.astype(np.float64))
    return DataFrame(data, num_partitions=2)
