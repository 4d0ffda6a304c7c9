"""Plain reference for a ranking cell, and the comparison that decides
``correct`` there.

It follows ``reference.py``: the trees the timed fit produced are taken as the
structure to be judged, and everything they claim is recomputed from rows,
labels and query sizes regenerated from the seed, with bin edges fitted here.
What is new is the gradient.  LambdaRank's gradient of a row depends on the
other rows of its query, so it is computed here per query in the ALL-PAIRS
form, every pair ``(i, j)`` of a query's rows, with no sort and no top-K cut:
a row's rank is the count of rows that come before it (higher score, or equal
score and earlier row), the discount is zero from rank K on, and a pair whose
two discounts are equal contributes zero because its delta is zero, not
because it is skipped.  Plain ``jax.numpy`` in float32, a block of queries at a
time so that ``(queries, width, width)`` fits, sums across chunks in float64.

Teacher-forced like the accepted reference: tree ``t``'s gradients come from
the scores the reference gets by routing raw rows through the program's trees
``0...t-1`` and adding their leaf values in float32, in the program's order
(lambdarank folds no bias: scores start at 0).  From those gradients and its
own routing it recomputes each leaf's count and value and each split's gain.
The test split is routed the same way; ``holdout_score_gap`` is the widest
distance to the program's scores and ``holdout_ndcg_gap`` the distance between
the program's NDCG@k and the reference's, taken per query in float64 from the
reference's scores, ties in row order.

It imports nothing of the program.
"""

import sys

import numpy as np

from benchmark.reference import (
    _EPS, BLOCK_ROWS, _round_mantissa, fit_edges, make_scorer, real_trees, route, split_gain, subtree_members,
)

VARIANTS = (None, "fp8")  # the reference put in the program's place: the control
PAIR_BLOCK_ELS = 1 << 24  # (queries, width, width) elements of one all-pairs block
MIN_WIDTH = 16


def all_pairs(s, lbl, valid, K: int, sigma: float):
    """Gradient and hessian ``(B, W)`` of a block of queries whose rows lie
    side by side, padded to ``W``: the configuration's equations, pair by pair."""
    import jax
    import jax.numpy as jnp

    pos = jnp.arange(s.shape[1])
    earlier = pos[None, :] < pos[:, None]  # [i, j]: row j lies before row i

    def rank_by(v):  # rows that come before row i: larger v, or equal v and earlier
        before = (v[:, None, :] > v[:, :, None]) | ((v[:, None, :] == v[:, :, None]) & earlier[None])
        return jnp.sum(before & valid[:, None, :], axis=2)

    def discount(rank):
        return jnp.where(valid & (rank < K), 1.0 / jnp.log2(rank + 2.0), 0.0)

    gain = jnp.where(valid, 2.0 ** lbl - 1.0, 0.0)
    idcg = jnp.sum(gain * discount(rank_by(gain)), axis=1)
    inv_idcg = jnp.where(idcg > 0, 1.0 / jnp.maximum(idcg, 1e-30), 0.0)
    d = discount(rank_by(s))
    gd = gain[:, :, None] - gain[:, None, :]
    pair = valid[:, :, None] & valid[:, None, :] & (gd > 0)
    delta = gd * jnp.abs(d[:, :, None] - d[:, None, :]) * inv_idcg[:, None, None]
    rho = jax.nn.sigmoid(-sigma * (s[:, :, None] - s[:, None, :]))
    lam = jnp.where(pair, -sigma * rho * delta, 0.0)
    hs = jnp.where(pair, sigma * sigma * rho * (1.0 - rho) * delta, 0.0)
    return jnp.sum(lam, axis=2) - jnp.sum(lam, axis=1), jnp.sum(hs, axis=2) + jnp.sum(hs, axis=1)


class QueryGradients:
    """The all-pairs gradient of every row from the score and label vectors:
    queries are grouped by padded width (powers of two), and each width's
    queries go through ``all_pairs`` a block at a time."""

    def __init__(self, sizes, num_rows: int, K: int, sigma: float):
        import jax
        import jax.numpy as jnp
        from jax import lax

        sizes = np.asarray(sizes, np.int64)
        starts = np.cumsum(sizes) - sizes
        width = np.maximum(MIN_WIDTH, 2 ** np.ceil(np.log2(np.maximum(sizes, 1))).astype(np.int64))
        self.classes = []
        for w in np.unique(width):
            sel = np.flatnonzero(width == w)
            block = max(1, PAIR_BLOCK_ELS // int(w * w))
            pad = (-len(sel)) % block  # empty queries fill the last block
            st = np.concatenate([starts[sel], np.zeros(pad, np.int64)]).reshape(-1, block)
            sz = np.concatenate([sizes[sel], np.zeros(pad, np.int64)]).reshape(-1, block)
            self.classes.append((int(w), jnp.asarray(st, jnp.int32), jnp.asarray(sz, jnp.int32)))

        def run(score, label, st, sz, w):
            pos = jnp.arange(w)

            def one_block(args):
                b_st, b_sz = args
                idx = b_st[:, None] + pos[None, :]
                valid = pos[None, :] < b_sz[:, None]
                at = jnp.where(valid, idx, 0)
                g, h = all_pairs(score[at], label[at], valid, K, sigma)
                return jnp.where(valid, idx, num_rows), g, h  # padding lands on a spare row

            idx, g, h = lax.map(one_block, (st, sz))
            return idx.reshape(-1), g.reshape(-1), h.reshape(-1)

        self._run = jax.jit(run, static_argnums=4)
        self._rows = num_rows

    def __call__(self, score, label):
        import jax.numpy as jnp

        grad = jnp.zeros(self._rows + 1, jnp.float32)
        hess = jnp.zeros(self._rows + 1, jnp.float32)
        for w, st, sz in self.classes:
            idx, g, h = self._run(score, label, st, sz, w)
            grad, hess = grad.at[idx].add(g), hess.at[idx].add(h)
        return grad[:-1], jnp.maximum(hess[:-1], 1e-9)


def make_leaf_pass(data, chunk_rows: int, S: int, C: int):
    """The jitted pass over one chunk for one tree: regenerates the chunk,
    routes its raw rows through the tree and returns ``(leaf sums (L, C), leaf
    of each row)``; ``vals`` are the rows' summands, zero past the split's end."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    L = S + 1
    block = min(BLOCK_ROWS, chunk_rows)
    if chunk_rows % block:
        raise ValueError("chunk_rows must be a multiple of the reference's block")

    @jax.jit
    def run(key, index, tree, vals):
        X, _ = data.chunk(key, index, chunk_rows)

        def one_block(carry, xv):
            Xb, vb = xv
            leaf = route(Xb, tree)
            oh = (leaf[:, None] == jnp.arange(L)[None, :]).astype(jnp.float32)
            return carry + jnp.einsum("bl,bc->lc", oh, vb, precision=lax.Precision.HIGHEST), leaf

        nb = chunk_rows // block
        sums, leaf = lax.scan(one_block, jnp.zeros((L, C), jnp.float32), (X.reshape(nb, block, -1), vals.reshape(nb, block, C)))
        return sums, leaf.reshape(-1)

    return run


def ndcg_by_query(score, label, sizes, k: int) -> float:
    """Mean over queries of NDCG@k in float64, ties in row order; a query with
    no relevant row counts 1."""
    score, label = np.asarray(score, np.float64), np.asarray(label, np.float64)
    sizes = np.asarray(sizes, np.int64)
    start = np.cumsum(sizes) - sizes
    qid = np.repeat(np.arange(len(sizes)), sizes)
    row = np.arange(len(score))
    rank = row - np.repeat(start, sizes)  # a row's position within its query, once sorted
    disc = np.where(rank < k, 1.0 / np.log2(rank + 2.0), 0.0)
    gain = 2.0 ** label - 1.0

    def dcg(key):  # rows by query, then by key descending, then in row order
        return np.add.reduceat(gain[np.lexsort((row, -key, qid))] * disc, start)

    got, ideal = dcg(score), dcg(gain)
    return float(np.mean(np.where(ideal > 0, got / np.maximum(ideal, 1e-300), 1.0)))


def compare(cfg, seed, trees, variant=None, holdout_scores=None, holdout_ndcg=None):
    """Every number compared, as ``{name: value}``.

    ``trees``: the program's forest as host arrays.  ``holdout_scores``,
    ``holdout_ndcg``: the program's raw scores of the test split under
    ``trees`` and its NDCG@``eval_at``.  With a ``variant`` the program's
    counts, leaf values and gains are replaced by the reference's own, computed
    with the named fault planted in it: ``fp8`` (gradients and hessians
    rounded to float8 e4m3: the control).
    """
    import jax
    import jax.numpy as jnp

    from benchmark.dataset import data_module, seed_key
    from benchmark.dataset_rank import split_chunks

    if variant not in VARIANTS:
        raise ValueError(f"unknown variant {variant!r}")
    data = data_module(cfg)
    key = seed_key(seed)
    chunk_rows, rows, h_rows = int(cfg["chunk_rows"]), int(cfg["rows"]), int(cfg["holdout_rows"])
    make = jax.jit(data.chunk, static_argnums=2)
    X0, _ = make(key, 0, chunk_rows)
    edges = fit_edges(np.asarray(X0[: int(cfg["bin_sample_rows"])]), data.CATEGORICAL, int(cfg["max_bin"]))
    del X0
    params = cfg["params"]
    lr = float(params["learning_rate"])
    l2 = float(params.get("lambda_l2", 0.0))
    K, sigma = int(params["max_position"]), float(params.get("sigmoid", 1.0))  # train()'s default, LightGBM's
    T = trees.split_leaf.shape[0]
    S = trees.split_leaf.shape[-1]
    L = S + 1
    rt = real_trees(trees, edges)
    prog_leaf = np.asarray(trees.leaf_value, np.float64).reshape(T, L)
    prog_count = np.asarray(trees.leaf_count, np.float64).reshape(T, L)
    prog_gain = np.asarray(trees.split_gain, np.float64).reshape(T, S)
    dev_trees = {k: jnp.asarray(v) for k, v in rt.items()}
    leaf_vals = jnp.asarray(prog_leaf, jnp.float32)

    gaps = {}
    if holdout_scores is not None:
        # the control leaves the scorer alone: its gaps are the sound run's, 0 here
        gaps |= {"holdout_score_gap": 0.0, "holdout_ndcg_gap": 0.0}
        if variant is None:
            scorer = make_scorer(data, chunk_rows, T)
            plan = split_chunks(h_rows, chunk_rows)
            ref = np.concatenate([
                np.asarray(scorer(key, jnp.int32(data.HOLDOUT_FIRST_CHUNK + c), dev_trees, leaf_vals))[:keep]
                for c, keep in plan
            ])
            h_label = np.concatenate([np.asarray(make(key, data.HOLDOUT_FIRST_CHUNK + c, chunk_rows)[1])[:keep] for c, keep in plan])
            gaps["holdout_score_gap"] = float(np.max(np.abs(np.asarray(holdout_scores, np.float32) - ref)))
            sizes = data.query_sizes(seed, int(cfg["holdout_queries"]), h_rows, split=1)
            ref_ndcg = ndcg_by_query(ref, h_label, sizes, int(cfg["eval_at"]))
            gaps["holdout_ndcg_gap"] = abs(float(holdout_ndcg) - ref_ndcg)
            w = int(np.argmax(np.abs(holdout_scores - ref)))
            print(
                f"detail holdout: worst row {w} score {ref[w]:.6g} got {holdout_scores[w]:.6g}; "
                f"ndcg@{cfg['eval_at']} {ref_ndcg:.9g} got {float(holdout_ndcg):.9g}",
                file=sys.stderr,
            )

    plan = split_chunks(rows, chunk_rows)
    padded = len(plan) * chunk_rows
    label = jnp.concatenate([make(key, c, chunk_rows)[1] for c, _ in plan])[:rows]
    gradients = QueryGradients(data.query_sizes(seed, int(cfg["queries"]), rows, split=0), rows, K, sigma)
    C = 5 if variant == "fp8" else 3
    leaf_pass = make_leaf_pass(data, chunk_rows, S, C)
    score = jnp.zeros(rows, jnp.float32)
    gaps |= {"leaf_count_gap": 0.0, "leaf_value_gap": 0.0, "leaf_value_median_gap": 0.0, "split_gain_gap": 0.0}
    for t in range(T):
        g, h = gradients(score, label)
        vals = [g, h, jnp.ones_like(g)]
        if variant == "fp8":
            vals += [_round_mantissa(g, 3), _round_mantissa(h, 3)]
        vals = jnp.pad(jnp.stack(vals, axis=1), ((0, padded - rows), (0, 0)))  # zero past the split's end
        tree_t = jax.tree_util.tree_map(lambda a: a[t], dev_trees)
        sums = np.zeros((L, C))
        leaves = []
        for c, _ in plan:
            ls, leaf = leaf_pass(key, jnp.int32(c), tree_t, vals[c * chunk_rows : (c + 1) * chunk_rows])
            sums += np.asarray(ls, np.float64)
            leaves.append(leaf)
        # teacher forcing: the program's leaf values, added in float32 as the program adds them
        score = score + leaf_vals[t][jnp.concatenate(leaves)[:rows]]
        exact = sums[:, :3]
        v_sums = sums[:, [3, 4, 2]] if variant == "fp8" else exact

        active = np.flatnonzero(rt["split_leaf"][t] >= 0)
        n_leaves = len(active) + 1
        left, right = subtree_members(rt["split_leaf"][t])
        G, H, N = (exact[:n_leaves, i] for i in range(3))
        ref_delta = -G / (H + l2 + _EPS) * lr
        if variant is None:
            got_delta, got_count = prog_leaf[t, :n_leaves], prog_count[t, :n_leaves]
        else:
            got_delta = -v_sums[:n_leaves, 0] / (v_sums[:n_leaves, 1] + l2 + _EPS) * lr
            got_count = v_sums[:n_leaves, 2]
        gaps["leaf_count_gap"] = max(gaps["leaf_count_gap"], float(np.max(np.abs(got_count - N) / np.maximum(N, 1.0))))
        scale = np.maximum(np.abs(ref_delta), np.median(np.abs(ref_delta)))
        leaf_gap = np.abs(got_delta - ref_delta) / scale
        gaps["leaf_value_gap"] = max(gaps["leaf_value_gap"], float(np.max(leaf_gap)))
        gaps["leaf_value_median_gap"] = max(gaps["leaf_value_median_gap"], float(np.median(leaf_gap)))
        w = int(np.argmax(leaf_gap))
        print(
            f"detail tree {t}: {n_leaves} leaves; worst leaf {w} rows {N[w]:.0f} hessian {H[w]:.6g} value {ref_delta[w]:.6g} "
            f"got {got_delta[w]:.6g}; median gap {np.median(leaf_gap):.3g}; fewest rows {N.min():.0f}",
            file=sys.stderr,
        )

        ref_gain = np.zeros(len(active))
        got_gain = np.zeros(len(active))
        for i, s in enumerate(active):
            ref_gain[i] = split_gain(exact[left[s]].sum(axis=0), exact[right[s]].sum(axis=0), l2)
            got_gain[i] = (
                prog_gain[t, s] if variant is None
                else split_gain(v_sums[left[s]].sum(axis=0), v_sums[right[s]].sum(axis=0), l2)
            )
        scale = np.maximum(ref_gain, np.median(ref_gain))
        gain_gap = np.abs(got_gain - ref_gain) / scale
        gaps["split_gain_gap"] = max(gaps["split_gain_gap"], float(np.max(gain_gap)))
        w = int(np.argmax(gain_gap))
        print(f"detail tree {t}: worst split {active[w]} gain {ref_gain[w]:.6g} got {got_gain[w]:.6g}; median gap {np.median(gain_gap):.3g}", file=sys.stderr)
    return gaps
