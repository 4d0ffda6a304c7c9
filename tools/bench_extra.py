"""Extra benchmarks for BASELINE.md configs 3/5/6 (VERDICT r2 #7):

- config 3: LightGBMRanker lambdarank wall-clock + NDCG@5 on MSLR-style
  synthetic groups (136 features, graded 0-4 labels — the MSLR-WEB30K
  schema).
- config 5: ONNXModel ResNet-50 inference images/sec over the DataFrame
  transformer path (real architecture built in-repo — no network, so the
  weights are random; images/sec does not depend on weight values).
- config 6: ImageFeaturizer (ResNet-50 headless) + LightGBMClassifier
  transfer-learning pipeline end-to-end wall-clock.

Prints one JSON line per config to STDOUT (this is NOT the driver's
bench.py — that contract stays one line, criteo-proxy); detail to stderr.
"""

import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

FLOAT = 1


def _log(*a):
    print(*a, file=sys.stderr, flush=True)


# --------------------------------------------------------------------------
# ResNet-50 graph, built with the in-repo protobuf helpers
# --------------------------------------------------------------------------
def resnet50_onnx_bytes(seed=0, num_classes=1000):
    """The genuine ResNet-50 v1 compute graph (conv7x7 → 4 bottleneck
    stages [3,4,6,3] → GAP → FC), random weights."""
    from mmlspark_tpu.onnx.importer import export_model_bytes, make_node

    rng = np.random.default_rng(seed)
    nodes, inits = [], {}

    def conv(name, x, cin, cout, k, stride=1, pad=None):
        w = (rng.normal(size=(cout, cin, k, k)) * np.sqrt(2.0 / (cin * k * k))).astype(np.float32)
        inits[f"{name}_w"] = w
        p = (k // 2) if pad is None else pad
        nodes.append(make_node(
            "Conv", [x, f"{name}_w"], [name], strides=[stride, stride],
            pads=[p, p, p, p], kernel_shape=[k, k],
        ))
        return name

    def bn(name, x, c):
        inits[f"{name}_s"] = np.abs(rng.normal(1, 0.1, c)).astype(np.float32)
        inits[f"{name}_b"] = np.zeros(c, np.float32)
        inits[f"{name}_m"] = np.zeros(c, np.float32)
        inits[f"{name}_v"] = np.ones(c, np.float32)
        nodes.append(make_node(
            "BatchNormalization",
            [x, f"{name}_s", f"{name}_b", f"{name}_m", f"{name}_v"], [name],
            epsilon=1e-5,
        ))
        return name

    def relu(name, x):
        nodes.append(make_node("Relu", [x], [name]))
        return name

    def bottleneck(name, x, cin, cmid, cout, stride):
        h = relu(f"{name}_r1", bn(f"{name}_bn1", conv(f"{name}_c1", x, cin, cmid, 1), cmid))
        h = relu(f"{name}_r2", bn(f"{name}_bn2", conv(f"{name}_c2", h, cmid, cmid, 3, stride), cmid))
        h = bn(f"{name}_bn3", conv(f"{name}_c3", h, cmid, cout, 1), cout)
        if cin != cout or stride != 1:
            sc = bn(f"{name}_bns", conv(f"{name}_cs", x, cin, cout, 1, stride), cout)
        else:
            sc = x
        nodes.append(make_node("Add", [h, sc], [f"{name}_sum"]))
        return relu(f"{name}_out", f"{name}_sum")

    x = relu("stem_r", bn("stem_bn", conv("stem", "data", 3, 64, 7, 2, 3), 64))
    nodes.append(make_node("MaxPool", [x], ["pool0"], kernel_shape=[3, 3],
                           strides=[2, 2], pads=[1, 1, 1, 1]))
    x, cin = "pool0", 64
    for si, (blocks, cmid, cout, stride) in enumerate([
        (3, 64, 256, 1), (4, 128, 512, 2), (6, 256, 1024, 2), (3, 512, 2048, 2),
    ]):
        for b in range(blocks):
            x = bottleneck(f"s{si}b{b}", x, cin, cmid, cout, stride if b == 0 else 1)
            cin = cout
    nodes.append(make_node("GlobalAveragePool", [x], ["gap"]))
    nodes.append(make_node("Flatten", ["gap"], ["feat"], axis=1))
    inits["fc_w"] = (rng.normal(size=(num_classes, 2048)) * 0.01).astype(np.float32)
    inits["fc_b"] = np.zeros(num_classes, np.float32)
    nodes.append(make_node("Gemm", ["feat", "fc_w", "fc_b"], ["logits"], transB=1))
    return export_model_bytes(
        nodes, [("data", (None, 3, 224, 224), FLOAT)], ["feat", "logits"], inits
    )


def bench_resnet50(n_images=512, batch=64):
    from mmlspark_tpu import DataFrame
    from mmlspark_tpu.models.onnx_model import ONNXModel

    payload = resnet50_onnx_bytes()
    _log(f"resnet50 onnx payload: {len(payload)/1e6:.1f} MB, "
         f"{n_images} images, miniBatchSize={batch}")
    rng = np.random.default_rng(1)
    imgs = rng.normal(size=(n_images, 3, 224, 224)).astype(np.float32)
    df = DataFrame({"image": list(imgs)})
    model = ONNXModel(
        miniBatchSize=batch,
        feedDict={"data": "image"},
        fetchDict={"cls": "logits"},
    ).setModelPayload(payload)
    t0 = time.perf_counter()
    out = model.transform(df)
    cold = time.perf_counter() - t0
    assert np.stack(out["cls"]).shape == (n_images, 1000)
    runs = []
    for _ in range(2):
        t0 = time.perf_counter()
        model.transform(df)
        runs.append(time.perf_counter() - t0)
    best = min(runs)
    ips = n_images / best
    _log(f"resnet50: cold={cold:.2f}s steady={[round(r, 2) for r in runs]} "
         f"-> {ips:.1f} images/s")
    # Device-resident throughput: the DataFrame path above uploads every
    # image from the host (≈300 MB for 512 images).  Feeding a
    # device-resident batch isolates model compute.  Chained async
    # dispatches + one final fetch to sync.
    import jax
    import jax.numpy as jnp

    from mmlspark_tpu.onnx.importer import OnnxFunction

    fn = OnnxFunction(payload)
    jf = jax.jit(lambda d: fn({"data": d})["logits"])
    xb = jax.device_put(jnp.asarray(
        rng.normal(size=(batch, 3, 224, 224)).astype(np.float32)))
    np.asarray(jf(xb))  # compile + warm
    reps = 16
    t0 = time.perf_counter()
    out = None
    for _ in range(reps):
        out = jf(xb)
    np.asarray(out[:1, :1])  # force completion of the chain
    dev_s = time.perf_counter() - t0
    dev_ips = reps * batch / dev_s
    _log(f"resnet50 device-resident: {reps}x{batch} images in {dev_s:.2f}s "
         f"-> {dev_ips:.1f} images/s (compute-bound figure)")
    print(json.dumps({
        "metric": "ONNXModel ResNet-50 DataFrame inference (batch 64, 224x224)",
        "value": round(ips, 1), "unit": "images/s",
        "cold_s": round(cold, 2),
        "device_resident_images_s": round(dev_ips, 1),
    }))
    return payload


def _sync_booster(b):
    """train() returns an async device-resident forest (r4); a tiny fetch
    waits for it."""
    import numpy as _np

    _np.asarray(b.trees.num_leaves)

def bench_ranker():
    from mmlspark_tpu.engine.booster import Dataset, train

    # MSLR-WEB30K schema: 136 features, graded relevance 0-4, ~120 docs per
    # query. 1024 queries x 128 docs = 131k rows.
    rng = np.random.default_rng(2)
    G, M, F = 1024, 128, 136
    n = G * M
    X = rng.normal(size=(n, F))
    w = rng.normal(size=F) * (rng.random(F) < 0.25)
    rel_score = X @ w + rng.normal(scale=2.0, size=n)
    y = np.clip(np.digitize(rel_score, np.quantile(rel_score, [0.55, 0.75, 0.9, 0.97])), 0, 4).astype(np.float64)
    group = np.full(G, M, dtype=np.int64)
    # Timed runs train WITHOUT per-iteration metric snapshots (the 50
    # host-side NDCG evals + snapshot transfers are reporting overhead, not
    # training); NDCG@5 is computed once from the final model below.
    params = dict(
        objective="lambdarank", num_iterations=50, num_leaves=63,
        max_bin=255, min_data_in_leaf=20, learning_rate=0.1,
    )  # growth/precision knobs ride the engine auto-resolution (r5);
    # measured NDCG@5 0.8323 bf16 vs 0.8303 f32 at this config — the
    # quality check below is the gate either way.
    ds = Dataset(X, y, group=group)
    t0 = time.perf_counter()
    booster = train(params, ds)
    _sync_booster(booster)
    cold = time.perf_counter() - t0
    t0 = time.perf_counter()
    booster = train(params, ds)
    _sync_booster(booster)
    steady = time.perf_counter() - t0
    from mmlspark_tpu.engine.eval_metrics import get_metric

    ndcg_fn, _, _ = get_metric("ndcg")
    ndcg5 = ndcg_fn(y, booster.predict(X, raw_score=True), w=None,
                    group_sizes=group)
    _log(f"ranker: cold={cold:.2f}s steady={steady:.2f}s train-NDCG@5={ndcg5:.4f}")
    print(json.dumps({
        "metric": "LightGBMRanker lambdarank 131kx136 (50 iters, 63 leaves, 1024 groups)",
        "value": round(steady, 3), "unit": "s",
        "train_ndcg5": round(float(ndcg5), 4), "cold_s": round(cold, 2),
    }))


def bench_transfer_pipeline(payload, n_images=256):
    """Config 6: featurize images with headless ResNet-50, train a GBDT on
    the 2048-d features — the reference's transfer-learning pipeline."""
    from mmlspark_tpu import DataFrame
    from mmlspark_tpu.models.onnx_model import ONNXModel
    from mmlspark_tpu.models.lightgbm import LightGBMClassifier

    rng = np.random.default_rng(3)
    imgs = rng.normal(size=(n_images, 3, 224, 224)).astype(np.float32)
    labels = (rng.random(n_images) > 0.5).astype(np.float64)
    df = DataFrame({"image": list(imgs), "label": labels})
    t0 = time.perf_counter()
    feats = ONNXModel(
        miniBatchSize=64, feedDict={"data": "image"},
        fetchDict={"features": "feat"},
    ).setModelPayload(payload).transform(df)
    clf = LightGBMClassifier(
        numIterations=20, numLeaves=15, featuresCol="features",
    ).fit(feats)
    out = clf.transform(feats)
    wall = time.perf_counter() - t0
    assert len(out["prediction"]) == n_images
    _log(f"transfer pipeline ({n_images} images): {wall:.2f}s e2e")
    print(json.dumps({
        "metric": "ImageFeaturizer(ResNet-50)+LightGBMClassifier e2e (256 images)",
        "value": round(wall, 3), "unit": "s",
    }))


def bench_catmix():
    """Criteo-schema proxy: 13 numeric + 26 categorical features (the real
    Criteo display-ads column mix — the north-star dataset), binary label.
    Oracle: sklearn HistGradientBoosting with NATIVE categorical support
    (`categorical_features`), same rows/iters/leaves/bins."""
    import time

    from bench import make_catmix_data  # one generator, no drift
    from mmlspark_tpu.engine.booster import Dataset, train

    X, y, cat_idx = make_catmix_data()

    params = dict(
        objective="binary", num_iterations=50, num_leaves=63, max_bin=255,
        min_data_in_leaf=20, learning_rate=0.1,
        categorical_feature=cat_idx,
        # engine defaults: max_cat_threshold=0 = auto/uncapped (the
        # vectorized candidate scan evaluates every sorted prefix anyway;
        # LightGBM's 32-cap is a CPU-cost artifact costing ~0.009 AUC here)
    )  # growth/precision knobs ride the engine auto-resolution (r5)
    ds = Dataset(X, y)
    t0 = time.perf_counter()
    booster = train(params, ds)
    _sync_booster(booster)
    cold = time.perf_counter() - t0
    steadies = []
    for _ in range(2):
        t0 = time.perf_counter()
        booster = train(params, ds)
        _sync_booster(booster)
        steadies.append(time.perf_counter() - t0)
    steady = min(steadies)
    tpu_auc = _auc(y[:100_000], booster.predict(X[:100_000]))

    from sklearn.ensemble import HistGradientBoostingClassifier

    clf = HistGradientBoostingClassifier(
        max_iter=50, max_leaf_nodes=63, max_bins=255, learning_rate=0.1,
        min_samples_leaf=20, early_stopping=False, validation_fraction=None,
        categorical_features=cat_idx,
    )
    t0 = time.perf_counter()
    clf.fit(X, y)
    cpu_s = time.perf_counter() - t0
    cpu_auc = _auc(y[:100_000], clf.predict_proba(X[:100_000])[:, 1])
    _log(
        f"catmix: tpu cold={cold:.2f}s steady={steady:.2f}s AUC={tpu_auc:.4f}"
        f" | sklearn(native cats)={cpu_s:.2f}s AUC={cpu_auc:.4f}"
    )
    gap = abs(tpu_auc - cpu_auc)
    print(json.dumps({
        "metric": "criteo-schema catmix 262kx(13num+26cat) GBDT train "
                  "(50 iters, 63 leaves)",
        "value": round(steady, 3), "unit": "s",
        "vs_baseline": round(cpu_s / steady, 3) if gap <= 0.005 else 0.0,
        "auc_gap": round(gap, 5),
    }))


def _auc(y, p):
    # the tie-correct rank AUC (sequential ranks over tied scores give
    # order-dependent garbage — see train/compute_statistics.py)
    from mmlspark_tpu.engine.eval_metrics import auc

    return float(auc(y, p))


def bench_adult():
    """Config 1: Adult-census-class binary classification THROUGH THE
    ESTIMATOR FACADE (`LightGBMClassifier.fit` on a DataFrame) — the
    single-executor user path.  AdultCensusIncome itself is unreachable
    offline, so the schema is reproduced synthetically: 48,842 rows,
    6 numeric + 8 categorical columns at the real columns' cardinalities
    (workclass 9, education 16, marital 7, occupation 15, relationship 6,
    race 5, sex 2, native-country 42).  Also measures the facade's COLD
    fit on a warm persistent compile cache (the library-level jit cache —
    VERDICT r3 weak #2's 'real user first fit' number)."""
    from mmlspark_tpu import DataFrame
    from mmlspark_tpu.models.lightgbm import LightGBMClassifier

    rng = np.random.default_rng(1)
    n = 48_842
    cards = [9, 16, 7, 15, 6, 5, 2, 42]
    Xn = np.column_stack([
        rng.normal(38, 13, n),            # age
        rng.lognormal(11.5, 1.0, n),      # fnlwgt
        rng.integers(1, 17, n).astype(float),   # education-num
        rng.exponential(1000, n) * (rng.random(n) < 0.1),  # capital-gain
        rng.exponential(100, n) * (rng.random(n) < 0.05),  # capital-loss
        rng.normal(40, 12, n),            # hours-per-week
    ])
    Xc = np.column_stack([rng.integers(0, c, n) for c in cards])
    logits = (
        0.04 * (Xn[:, 0] - 38) + 0.25 * (Xn[:, 2] - 10)
        + 0.002 * np.minimum(Xn[:, 3], 2000) + 0.02 * (Xn[:, 5] - 40)
        + 0.8 * (Xc[:, 1] % 4 == 1) - 0.5 * (Xc[:, 2] % 3 == 0)
        + 0.6 * (Xc[:, 7] % 5 == 2)
    )
    y = (logits + rng.logistic(size=n) * 1.5 > 0.8).astype(np.float64)
    X = np.column_stack([Xn, Xc.astype(np.float64)])
    cat_idx = list(range(6, 14))
    # quality gate on HELD-OUT AUC: train-AUC at 100x31 on noisy tabular
    # data measures overfitting depth (tie-level fitting order), not model
    # quality — both libraries land within ~1e-3 on the test fold
    ntr = 39_000
    Xtr, ytr, Xte, yte = X[:ntr], y[:ntr], X[ntr:], y[ntr:]

    df = DataFrame({
        "features": list(Xtr), "label": ytr,
    })
    est = LightGBMClassifier(
        numIterations=100, numLeaves=31, categoricalSlotIndexes=cat_idx,
    )  # splitBatch rides the auto default (r5)
    t0 = time.perf_counter()
    model = est.fit(df)  # COLD facade fit (warm persistent compile cache)
    _sync_booster(model.getBooster())
    cold = time.perf_counter() - t0
    t0 = time.perf_counter()
    model = est.fit(df)
    _sync_booster(model.getBooster())
    steady = time.perf_counter() - t0
    tpu_auc = _auc(yte, model.getBooster().predict(Xte))

    from sklearn.ensemble import HistGradientBoostingClassifier

    clf = HistGradientBoostingClassifier(
        max_iter=100, max_leaf_nodes=31, early_stopping=False,
        validation_fraction=None, categorical_features=cat_idx,
    )
    t0 = time.perf_counter()
    clf.fit(Xtr, ytr)
    cpu_s = time.perf_counter() - t0
    cpu_auc = _auc(yte, clf.predict_proba(Xte)[:, 1])
    _log(
        f"adult: facade cold(warm jit cache)={cold:.2f}s steady={steady:.2f}s "
        f"test-AUC={tpu_auc:.4f} | sklearn={cpu_s:.2f}s test-AUC={cpu_auc:.4f}"
    )
    print(json.dumps({
        "metric": "adult-schema 48842x(6num+8cat) facade fit (100 iters, 31 leaves)",
        "value": round(steady, 3), "unit": "s",
        "facade_cold_warm_cache_s": round(cold, 3),
        "vs_baseline": round(cpu_s / steady, 3)
        if abs(tpu_auc - cpu_auc) <= 0.01 else 0.0,
        "auc_gap": round(abs(tpu_auc - cpu_auc), 5),
    }))


def bench_boston():
    """Config 2: Boston-housing-class regression (506x13 schema,
    synthesized offline) — MSE + wall through the engine, sklearn
    HistGradientBoostingRegressor as oracle.  At 506 rows this measures
    small-data dispatch overhead, not throughput (the reference's config
    is the same single-executor toy)."""
    from mmlspark_tpu.engine.booster import Dataset, train

    rng = np.random.default_rng(2)
    n, F = 506, 13
    X = rng.normal(size=(n, F))
    yv = (
        X @ rng.normal(size=F) + 0.6 * X[:, 5] ** 2 - 0.4 * X[:, 0] * X[:, 12]
        + rng.normal(scale=0.5, size=n)
    )
    params = dict(objective="regression", num_iterations=100, num_leaves=31,
                  min_data_in_leaf=5)
    ds = Dataset(X, yv)
    _sync_booster(train(params, ds))  # warm-up must COMPLETE before timing
    t0 = time.perf_counter()
    booster = train(params, ds)
    _sync_booster(booster)
    steady = time.perf_counter() - t0
    mse = float(np.mean((booster.predict(X) - yv) ** 2))

    from sklearn.ensemble import HistGradientBoostingRegressor

    reg = HistGradientBoostingRegressor(
        max_iter=100, max_leaf_nodes=31, early_stopping=False,
        validation_fraction=None,
    )
    t0 = time.perf_counter()
    reg.fit(X, yv)
    cpu_s = time.perf_counter() - t0
    cpu_mse = float(np.mean((reg.predict(X) - yv) ** 2))
    _log(f"boston: steady={steady:.2f}s MSE={mse:.4f} | "
         f"sklearn={cpu_s:.2f}s MSE={cpu_mse:.4f}")
    print(json.dumps({
        "metric": "boston-schema 506x13 regression train (100 iters, 31 leaves)",
        "value": round(steady, 3), "unit": "s",
        "mse": round(mse, 4), "sklearn_mse": round(cpu_mse, 4),
        "vs_baseline": round(cpu_s / steady, 3),
    }))


def main():
    import jax

    from bench import enable_compile_cache

    enable_compile_cache()
    _log(f"backend={jax.default_backend()}")
    which = set(sys.argv[1:]) or {
        "ranker", "resnet", "pipeline", "catmix", "adult", "boston",
    }
    payload = None
    if "resnet" in which or "pipeline" in which:
        payload = bench_resnet50()
    if "pipeline" in which:
        bench_transfer_pipeline(payload)
    if "ranker" in which:
        bench_ranker()
    if "catmix" in which:
        bench_catmix()
    if "adult" in which:
        bench_adult()
    if "boston" in which:
        bench_boston()


if __name__ == "__main__":
    main()
