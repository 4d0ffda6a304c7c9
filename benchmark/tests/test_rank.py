"""CPU tests of the ranking cell's benchmark code: the generator, the plain
all-pairs reference's pieces, the three readers, and the traffic kind end to
end at 8,192 rows through the runner, sound and with every fault planted."""

import argparse
import json

import numpy as np
import pytest

from benchmark import reference_rank
from benchmark import run as runner
from benchmark.data import istella
from benchmark.metrics import rank_grad_share_pct, rank_pad_waste_pct, rank_plan_s
from benchmark.traffic import train_loop_rank


# ---- data ---------------------------------------------------------------------
@pytest.mark.parametrize("seed", [0, 7, 12345, 2**31 + 99, 3110000017])
def test_query_sizes_sum_to_the_configurations_rows(seed):
    with open(runner.os.path.join(runner.ROOT, "benchmark", "configs", "istella_lambdarank.json")) as f:
        cfg = json.load(f)
    for split, queries, rows in ((0, cfg["queries"], cfg["rows"]), (1, cfg["holdout_queries"], cfg["holdout_rows"])):
        sizes = istella.query_sizes(seed, queries, rows, split=split)
        assert len(sizes) == queries and int(sizes.sum()) == rows
        assert sizes.min() >= 1 and sizes.max() <= istella.SIZE_MAX
        np.testing.assert_array_equal(sizes, istella.query_sizes(seed, queries, rows, split=split))
    assert cfg["rows"] + cfg["holdout_rows"] == cfg["published"]["rows"]
    assert cfg["queries"] + cfg["holdout_queries"] == cfg["published"]["queries"]
    assert cfg["reduced"] == []


def test_generator_is_a_function_of_the_seed_and_states_its_shares():
    import jax

    from benchmark.dataset import seed_key

    gen = jax.jit(istella.chunk, static_argnums=2)
    big = 2**31 + 12345
    Xa, ya = gen(seed_key(big), 1, 65536)
    Xb, yb = gen(seed_key(big), 1, 65536)
    Xc, _ = gen(seed_key(big), istella.HOLDOUT_FIRST_CHUNK, 65536)
    assert Xa.shape == (65536, istella.NUM_FEATURES) == (65536, 220)
    np.testing.assert_array_equal(np.asarray(Xa), np.asarray(Xb))
    np.testing.assert_array_equal(np.asarray(ya), np.asarray(yb))
    assert not np.array_equal(np.asarray(Xa), np.asarray(Xc))
    X = np.asarray(Xa)
    assert np.isfinite(X).all() and (X >= 0).all()  # non-negative, nothing missing
    shares = np.bincount(np.asarray(ya).astype(int), minlength=5) / len(ya)
    np.testing.assert_allclose(shares, istella.GRADE_SHARES, atol=0.004)


# ---- the reference's own pieces ---------------------------------------------------
def test_all_pairs_by_hand():
    """One query of three rows, labels 2, 0, 1, equal scores, K = 2: ranks are
    row order, discounts 1, 1/log2(3), 0."""
    import jax.numpy as jnp

    s = jnp.zeros((1, 4))
    lbl = jnp.asarray([[2.0, 0.0, 1.0, 0.0]])
    valid = jnp.asarray([[True, True, True, False]])
    g, h = reference_rank.all_pairs(s, lbl, valid, K=2, sigma=1.0)
    d = np.array([1.0, 1.0 / np.log2(3.0), 0.0])
    gain = np.array([3.0, 0.0, 1.0])
    idcg = 3.0 * d[0] + 1.0 * d[1]
    lam = {(i, j): -0.5 * (gain[i] - gain[j]) * abs(d[i] - d[j]) / idcg for i, j in ((0, 1), (0, 2), (2, 1))}
    want = np.array([lam[0, 1] + lam[0, 2], -lam[0, 1] - lam[2, 1], lam[2, 1] - lam[0, 2], 0.0])
    np.testing.assert_allclose(np.asarray(g)[0], want, rtol=1e-6)
    np.testing.assert_allclose(np.asarray(h)[0][:3], -0.5 * np.array([lam[0, 1] + lam[0, 2], lam[0, 1] + lam[2, 1], lam[2, 1] + lam[0, 2]]), rtol=1e-6)


def test_query_gradients_place_every_query_in_its_rows():
    import jax.numpy as jnp

    rng = np.random.default_rng(3)
    sizes = np.array([1, 40, 17, 300, 5, 16])
    n = int(sizes.sum())
    score = jnp.asarray(np.round(rng.normal(size=n), 1).astype(np.float32))
    label = jnp.asarray(rng.integers(0, 5, n).astype(np.float32))
    g, h = reference_rank.QueryGradients(sizes, n, K=20, sigma=1.0)(score, label)
    start = 0
    for sz in sizes:  # each query alone gives the same rows
        g1, h1 = reference_rank.QueryGradients([sz], int(sz), K=20, sigma=1.0)(score[start : start + sz], label[start : start + sz])
        np.testing.assert_allclose(np.asarray(g)[start : start + sz], np.asarray(g1), rtol=1e-5, atol=1e-7)
        np.testing.assert_allclose(np.asarray(h)[start : start + sz], np.asarray(h1), rtol=1e-5, atol=1e-9)
        start += sz
    assert abs(float(np.asarray(g).sum())) < 1e-4  # every pair's two sides cancel


def test_ndcg_by_query_by_hand():
    # query 1: scores tied, so row order: gains 1, 3 against the ideal 3, 1; query 2 has no relevant row
    got = reference_rank.ndcg_by_query([0.5, 0.5, 0.1, 0.2], [1.0, 2.0, 0.0, 0.0], [2, 2], k=10)
    q1 = (1.0 + 3.0 / np.log2(3.0)) / (3.0 + 1.0 / np.log2(3.0))
    assert got == pytest.approx((q1 + 1.0) / 2.0, rel=1e-12)
    assert reference_rank.ndcg_by_query([0.5, 0.6, 0.1], [1.0, 2.0, 0.0], [3], k=1) == pytest.approx(1.0)


# ---- the three readers, on handed-in spans, counters and op seconds ---------------
def _ctx():
    plan = {"name": "booster.rank_plan", "id": 7, "parent": "booster.prepare", "parent_id": 3, "start_ns": 1_000, "end_ns": 3_001_000,
            "attrs": {"cache_hit": True, "queries": 1409, "buckets": 3, "rows": 7340032, "shapes": "4x32 1311x128 98x2048"}}
    older = dict(plan, id=2, start_ns=10, end_ns=500_000_010, attrs=dict(plan["attrs"], cache_hit=False))
    # names as the v5e's trace gives them: the HLO name and the shape it produces
    op_s = {
        "fusion.15 f32[1311,20,128]": 0.010, "fusion.3 f32[98,20,2048]": 0.004, "fusion.81 f32[1311,128]": 0.002,
        "slice_select_fusion.3 f32[1311,20]": 0.001, "pad.12 f32[7342080]": 0.003, "pad_maximum_fusion.4 f32[2,167808]": 0.0005,
        "custom-call.2 f32[2,368641]": 0.0015, "fusion f32[7340032,2]": 0.006, "copy.166 f32[1,1,98,2048]": 0.0005,
        "fusion.9 f32[512,10,256]": 0.002, "fusion.77 f32[512,10]": 0.0005,  # the evaluation's plan
        "dynamic-slice.248 f32[128]": 0.004, "dynamic-slice_bitcast_fusion.10 f32[1,128]": 0.003, "dynamic-slice.9 f32[32]": 0.3, "fusion.90 f32[128]": 0.1,
        "_pallas_hist_by_leaf_nibble.1 f32[263,3,220,256]": 1.5, "fusion.64 s32[7340032]": 0.05, "fusion.5 f32[4,32]": 0.5,
        "compare_select_fusion.111 f32[1,7340032]": 0.02,
    }
    return {
        "spans": [older, plan], "trace": {"busy_s": 2.0, "window_s": 2.5, "op_s": op_s},
        "cfg": {"params": {"max_position": 20}}, "window": {"eval_plan": {"buckets": [[512, 256]], "rows": 3129004, "k": 10}},
        "setup_counters": {"rank.pair_slots": 1000.0, "rank.pair_terms": 700.0, "rank.queries": 10.0},
        "window_counters": {"rank.pair_slots": 5000.0, "rank.pair_terms": 3800.0, "rank.queries": 50.0},
    }


def test_rank_plan_s_reads_the_last_plan_span():
    assert rank_plan_s.read(_ctx()) == pytest.approx(0.003)
    assert rank_plan_s.read({"spans": []}) is None


def test_rank_pad_waste_reads_the_windows_counters():
    assert rank_pad_waste_pct.read(_ctx()) == pytest.approx(100.0 * (1 - 3100.0 / 4000.0))
    assert rank_pad_waste_pct.read({"setup_counters": {}, "window_counters": {}}) is None


def test_rank_grad_share_finds_the_ranking_ops_by_shape():
    ctx = _ctx()
    ranking = 0.010 + 0.004 + 0.002 + 0.001 + 0.003 + 0.0005 + 0.0015 + 0.006 + 0.0005 + 0.002 + 0.0005 + 0.007
    assert rank_grad_share_pct.read(ctx) == pytest.approx(100.0 * ranking / 2.0)
    # a bucket of under 8 queries lends no shape: (4, 32) is anyone's
    assert not rank_grad_share_pct.is_ranking_op("fusion.5 f32[4,32]", rank_grad_share_pct.plan_shapes([(4, 32), (1311, 128)], 7340032, 20))
    # a program without the span (the parent), or a trace without such ops: nothing, never 0
    assert rank_grad_share_pct.read(dict(ctx, spans=[])) is None
    assert rank_grad_share_pct.read(dict(ctx, trace={"busy_s": 1.0, "window_s": 1.0, "op_s": {"fusion.1 f32[8]": 1.0}})) is None


# ---- the runner, end to end ---------------------------------------------------------
TINY = {"rows": 8192, "queries": 64, "holdout_rows": 4096, "holdout_queries": 32, "chunk_rows": 4096, "bin_sample_rows": 4096}


@pytest.fixture()
def tiny_cells(monkeypatch):
    orig = runner.load_cell

    def load(name):
        bench, cell, cfg, workload = orig(name)
        cfg = dict(cfg, **TINY)
        # 8,192 rows hold no 255 leaves of hessian 100 each
        cfg["params"] = dict(cfg["params"], num_leaves=7, min_sum_hessian_in_leaf=1e-3, hist_backend="pallas", hist_precision="highest")
        return bench, cell, cfg, workload

    monkeypatch.setattr(runner, "load_cell", load)


def _args(**kw):
    return argparse.Namespace(**dict(dict(workload="istella_rank_train_1chip", seed=2**31 + 7, seconds=0.0, trace=0), **kw))


def test_run_end_to_end(tiny_cells):
    out = runner.run(_args(), need_chip=False)
    assert out["correct"] is True, out["check"]
    assert set(out["metrics"]) == {"train_rowiters_per_s", "peak_hbm_gb", "setup_s"}
    assert set(out["check"]) == {
        "leaf_count_gap", "leaf_value_gap", "leaf_value_median_gap", "split_gain_gap", "holdout_score_gap", "holdout_ndcg_gap",
    }
    assert 0.0 < out["observed"]["holdout_ndcg"] < 1.0
    json.dumps(out)


def test_fp8_control_is_not_correct(tiny_cells):
    out = runner.run(_args(), need_chip=False, variant="fp8")
    assert out["correct"] is False, out["check"]


@pytest.mark.parametrize("fault", sorted(train_loop_rank.FAULTS))
def test_planted_fault_is_not_correct(tiny_cells, fault):
    out = runner.run(_args(), need_chip=False, traffic_overrides=train_loop_rank.FAULTS[fault])
    assert out["correct"] is False, out["check"]


def test_prove_finds_the_variants_and_faults():
    assert train_loop_rank.reference.VARIANTS == (None, "fp8")
    assert {"query_shift", "topk_short", "state_unchanged", "answer_altered", "holdout_tree_dropped", "half_batch"} == set(train_loop_rank.FAULTS)
    assert list(train_loop_rank.FAULTS)[-1] == "half_batch"  # it spends the data set
