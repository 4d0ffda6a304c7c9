# %% [markdown]
# # Distributed LightGBM training on a TPU mesh
#
# The reference trains across Spark executors with a socket histogram
# allreduce (`LGBM_NetworkInit`, SURVEY.md §3.1/§5.8); here the same
# semantics ride a `jax.sharding.Mesh`: rows shard over the `"data"` axis,
# per-shard histograms `psum` over ICI, and every shard computes the
# identical split.  This notebook runs the whole story on ONE host with an
# 8-device virtual CPU mesh — the exact code scales to a TPU pod by
# changing nothing (the mesh discovers the real chips).
#
# Executable as a script (`python notebooks/04_distributed_training.py`)
# or cell-by-cell in Jupyter (percent format).

# %% Force a virtual 8-device mesh BEFORE jax initializes (demo only —
# on a real TPU pod, skip this and let jax.devices() find the chips)
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_num_cpu_devices", 8)

import numpy as np

from mmlspark_tpu.engine.booster import Dataset, train
from mmlspark_tpu.ops.binning import BinMapper
from mmlspark_tpu.parallel.mesh import default_mesh

rng = np.random.default_rng(0)
n = 40_000
X = rng.normal(size=(n, 12))
y = (X[:, 0] - 0.7 * X[:, 1] + rng.logistic(size=n) * 0.8 > 0).astype(np.float64)
Xv, yv = X[32_000:], y[32_000:]
X, y = X[:32_000], y[:32_000]

# %% [markdown]
# ## 1. Data-parallel training (`tree_learner="data"`)
#
# Rows shard across all 8 devices; one `psum` per histogram pass is the
# only collective (6.3 MB/pass at the bench shape — see BASELINE.md's
# collective-bytes table).  Early stopping + metrics ride along.

# %%
params = dict(
    objective="binary", num_iterations=60, num_leaves=31,
    metric="auc,binary_logloss",      # multi-metric lists (LightGBM style)
    early_stopping_round=5, tree_learner="data",
)
booster = train(params, Dataset(X, y), valid_sets=[Dataset(Xv, yv)])
print("stopped at", booster.num_iterations, "best", booster.best_iteration)
print("final valid AUC:", booster.evals_result["valid_0"]["auc"][-1])

# %% [markdown]
# ## 2. Bandwidth-reduced modes
#
# `voting` elects top-k features per leaf and psums only the elected
# histogram slices (LightGBM's parallel voting).  `feature` shards
# COLUMNS and exchanges only per-leaf winners (categoricals included).

# %%
for mode, extra in [
    ("voting", dict(tree_learner="voting", top_k=6)),
    ("feature", dict(tree_learner="feature")),
]:
    b = train(dict(params, early_stopping_round=0, num_iterations=20, **extra),
              Dataset(X, y))
    from mmlspark_tpu.engine.eval_metrics import auc
    print(f"{mode:>10}: AUC={auc(yv, b.predict(Xv)):.4f}")

# %% [markdown]
# ## 3. Multi-host: the process-local contract
#
# On a real cluster every host calls `train(..., process_local=True)`
# with ONLY its partition (`jax.make_array_from_process_local_data`
# assembles the global sharded arrays — no host ever holds another's
# rows).  Validation metrics and early stopping are computed from
# psum-able sufficient statistics INSIDE the jitted scan
# (`engine/dist_metrics`), so nothing row-sized crosses hosts.  With one
# process it degenerates to the mesh run above — same code:

# %%
pl = train(params, Dataset(X, y), valid_sets=[Dataset(Xv, yv)],
           process_local=True)
assert pl.num_iterations == booster.num_iterations
print("process_local stop parity OK")

# %% [markdown]
# ## 4. From Spark: the barrier stage body
#
# Inside `rdd.barrier().mapPartitions`, each task derives a rendezvous
# from `BarrierTaskContext.getTaskInfos()` and calls `barrier_train_task`
# with its partition (+ optional validation split and process-aligned
# ranking groups).  See `spark_bridge.py` and
# `tests/test_pyspark_integration.py` for the live-Spark version; the
# 2/4-process parity suites in `tests/test_spark_bridge.py` run the same
# body as real OS processes.
#
# ```python
# def task(it):
#     ctx = BarrierTaskContext.get()
#     bctx = barrier_context_from_task_infos(
#         [i.address for i in ctx.getTaskInfos()], ctx.partitionId())
#     rows = np.concatenate(list(it), axis=0)
#     return [barrier_train_task(rows, bctx, params)]  # model str on task 0
# ```
