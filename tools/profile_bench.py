"""Profile the criteo-proxy bench config: host binning vs device scan vs
transfers, plus the AUC ablation VERDICT r2 asked for (bf16-hist vs grow
policy).  Writes stderr detail lines; run on the real TPU.

Usage: python tools/profile_bench.py [--quick]
"""

import sys
import time

import numpy as np

sys.path.insert(0, "/root/repo")

from bench import N_FEATURES, N_ITER, N_ROWS, NUM_LEAVES, MAX_BIN, auc, make_data


def _log(*a):
    print(*a, flush=True)


def main():
    quick = "--quick" in sys.argv
    import jax

    from bench import bench_config
    from mmlspark_tpu.engine.booster import Dataset, train
    from mmlspark_tpu.ops.binning import BinMapper

    X, y = make_data()
    _log(f"backend={jax.default_backend()}")

    # --- host binning breakdown ---
    t0 = time.perf_counter()
    bm = BinMapper(max_bin=MAX_BIN).fit(X)
    t_fit = time.perf_counter() - t0
    t0 = time.perf_counter()
    bins = bm.transform(X)
    t_transform = time.perf_counter() - t0
    _log(f"host binning: fit={t_fit:.3f}s transform={t_transform:.3f}s")

    # --- upload time ---
    t0 = time.perf_counter()
    bins_dev = jax.device_put(bins)
    bins_dev.block_until_ready()
    t_up = time.perf_counter() - t0
    _log(f"device_put({bins.nbytes/1e6:.1f}MB uint8): {t_up:.3f}s")

    configs = [
        ("depthwise/default", dict(grow_policy="depthwise", hist_precision="default")),
        ("depthwise/highest", dict(grow_policy="depthwise", hist_precision="highest")),
        ("lossguide/default", dict(grow_policy="lossguide", hist_precision="default")),
        ("lossguide/highest", dict(grow_policy="lossguide", hist_precision="highest")),
    ]
    if quick:
        configs = configs[:1]

    ds = Dataset(X, y)
    for name, extra in configs:
        # the EXACT bench config, varying only the ablation axes (the
        # bench pins split_batch, which depthwise configs override)
        params = dict(bench_config(), split_batch=-1, **extra)  # -1 = never batch (0 now auto-resolves on TPU)
        t0 = time.perf_counter()
        booster = train(params, ds, bin_mapper=bm)
        cold = time.perf_counter() - t0
        runs = []
        for _ in range(2):
            t0 = time.perf_counter()
            booster = train(params, ds, bin_mapper=bm)
            runs.append(time.perf_counter() - t0)
        a = auc(y[:100_000], booster.predict(X[:100_000]))
        _log(
            f"{name}: cold={cold:.2f}s steady={[round(r,2) for r in runs]} "
            f"auc={a:.4f}"
        )

    # CPU baseline AUC for the ablation target
    if not quick:
        from sklearn.ensemble import HistGradientBoostingClassifier

        clf = HistGradientBoostingClassifier(
            max_iter=N_ITER, max_leaf_nodes=NUM_LEAVES, max_bins=MAX_BIN,
            learning_rate=0.1, min_samples_leaf=20, early_stopping=False,
            validation_fraction=None,
        )
        t0 = time.perf_counter()
        clf.fit(X, y)
        t_cpu = time.perf_counter() - t0
        a = auc(y[:100_000], clf.predict_proba(X[:100_000])[:, 1])
        _log(f"sklearn: fit={t_cpu:.2f}s auc={a:.4f}")


if __name__ == "__main__":
    main()
