"""Plain reference for a multiclass softmax cell (K trees an iteration), and
the comparison that decides ``correct`` there.

It is ``reference.compare`` for K > 1: the program's trees are taken as the
answer to be judged and everything they claim is recomputed from rows
regenerated from the seed, in float32 at ``highest`` with float64 across
chunks.  What K adds:

- each iteration's gradients come from the softmax over ALL K scores of the
  row at the iteration's start, ``g_k = p_k - [y = k]``, ``h_k = 2 p_k (1 -
  p_k)`` (the configuration's ``equations``), taken here in plain
  ``jax.numpy``;
- teacher-forced on the program's trees AND their float32 leaf values: the
  scores at iteration ``t`` are every row routed through the program's
  trees of iterations ``0 ... t-1`` and their leaf values added, so the
  gradients judged are the ones the program should have had;
- per tree (one of each class an iteration): every leaf's row count, the
  leaf value from the reference's sums, and each recorded gain, as in
  ``reference.compare``; the worst over all ``T x K`` trees;
- the holdout: each raw row routed through every tree, the program's leaf
  values added class by class in iteration order, held against the
  program's ``(K, rows)`` scores (``holdout_score_gap``), and the program's
  ``multi_logloss`` against the reference's own in float64
  (``holdout_logloss_gap``, observed and not held).

Rows are routed by the reference's OWN bins: the raw values against the bin
edges it fits itself (``reference.fit_edges``), a split's left set a
256-entry table (a numeric split ``bin <= split_bin`` with the missing bin
going ``default_left``; a categorical split its member categories, the
missing bin right), packed into eight ``uint32`` words a split.  That is
``reference.route``'s raw routing against ``reference.real_trees``' real
thresholds and category sets, bin for bin, at a few operations a row a
split, where the categorical compare of every category costs 255.

It imports nothing of the program.
"""

import sys

import numpy as np

from benchmark import reference
from benchmark.reference import _EPS, CAT_L2, _round_mantissa, fit_edges, split_gain, subtree_members

VARIANTS = reference.VARIANTS  # the control: fp8 in the reference's own leaf sums
BLOCK_ROWS = 8192
WORDS = 8  # a split's 256-entry left table in uint32 words


def softmax_grad_hess(scores, y):
    """``(g, h)`` of the configuration's equations for ``(K, rows)`` scores."""
    import jax
    import jax.numpy as jnp

    p = jax.nn.softmax(scores, axis=0)
    onehot = (jnp.arange(scores.shape[0])[:, None] == y.astype(jnp.int32)[None, :]).astype(jnp.float32)
    return p - onehot, 2.0 * p * (1.0 - p)


def flat_trees(trees):
    """The forest's arrays with leading axes ``(T, K)`` made one, ``T * K``:
    tree ``t * K + k`` is class ``k``'s tree of iteration ``t``."""
    return {name: np.asarray(a).reshape((-1,) + np.asarray(a).shape[2:]) for name, a in trees._asdict().items()}


def left_tables(ft, edges):
    """``(TK, S, WORDS)`` uint32: bit ``b`` of split ``s`` is set where a row
    of the reference's bin ``b`` of the split's column goes left."""
    split_leaf, feat = ft["split_leaf"], ft["split_feat"]
    TK, S = split_leaf.shape
    B = WORDS * 32
    table = np.zeros((TK, S, B), bool)
    below = np.arange(B - 1)
    for i in range(TK):
        for s in np.flatnonzero(split_leaf[i] >= 0):
            f = int(feat[i, s])
            n = len(edges.rows[f])
            if ft["split_cat"][i, s]:
                table[i, s, :n] = ft["cat_threshold"][i, s, :n]  # the missing bin goes right
            else:
                table[i, s, : B - 1] = below <= min(int(ft["split_bin"][i, s]), n - 1)
                table[i, s, B - 1] = bool(ft["default_left"][i, s])
    return np.packbits(table, axis=-1, bitorder="little").view("<u4")


def bins_of(X, table, is_cat_col, B):
    """The reference's bin of every raw value: its count of edges below (a
    categorical value: its place among the kept categories), ``B - 1`` where
    missing or a category not kept."""
    import jax.numpy as jnp

    v = jnp.where(is_cat_col[None, :], jnp.trunc(X), X)
    pos = (table[None, :, :] < v[:, :, None]).sum(axis=-1).astype(jnp.int32)
    seen = (table[None, :, :] == v[:, :, None]).any(axis=-1)
    pos = jnp.where(is_cat_col[None, :] & ~seen, B - 1, pos)
    return jnp.where(jnp.isnan(X), B - 1, pos)


def route(bins_t, words, feat, split_leaf):
    """Leaf of each row of ``bins_t`` (F, rows) under one tree: rows start in
    leaf 0 and each split moves those that go right to leaf ``s + 1``."""
    import jax.numpy as jnp
    from jax import lax

    def step(s, leaf):
        b = lax.dynamic_index_in_dim(bins_t, feat[s], axis=0, keepdims=False)
        hi = b >> 5
        w = jnp.zeros_like(b, jnp.uint32)
        for j in range(WORDS):  # the word by selects: a gather from a small table is slow on a TPU
            w = jnp.where(hi == j, words[s, j], w)
        left = ((w >> (b & 31).astype(jnp.uint32)) & 1) == 1
        move = (split_leaf[s] >= 0) & (leaf == split_leaf[s]) & ~left
        return jnp.where(move, s + 1, leaf)

    return lax.fori_loop(0, feat.shape[0], step, jnp.zeros(bins_t.shape[1], jnp.int32))


def route_all(bins_t, words, feat, split_leaf):
    """``(K, rows)`` leaves of the rows under K trees."""
    import jax

    return jax.vmap(route, in_axes=(None, 0, 0, 0))(bins_t, words, feat, split_leaf)


def add_leaf_values(scores, leaf, values):
    """``scores + values[k][leaf[k]]`` by one select a leaf: each row adds
    exactly its leaf's float32."""
    import jax.numpy as jnp

    delta = jnp.zeros_like(scores)
    for l in range(values.shape[1]):
        delta = jnp.where(leaf == l, values[:, l : l + 1], delta)
    return scores + delta


def make_passes(data, chunk_rows, num_bins, K, L, variant):
    """The jitted steps over one chunk: ``binned(key, index, table, is_cat)
    -> (bins (F, rows) int32, y)``; ``sums(bins, y, scores, words, feat,
    split_leaf) -> ((K, L, C) leaf sums, (K, rows) leaves)``, the columns
    gradient, hessian, count and, for the control, its rounded gradient and
    hessian; ``advance(scores, leaves, values)``."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    block = min(BLOCK_ROWS, chunk_rows)
    if chunk_rows % block:
        raise ValueError("chunk_rows must be a multiple of the reference's block")
    nb = chunk_rows // block
    C = 5 if variant == "fp8" else 3
    hp = lax.Precision.HIGHEST

    # key and edges are arguments: as closure constants they would make a new
    # program, and a compile, of every seed
    @jax.jit
    def binned(key, index, table, is_cat_col):
        X, y = data.chunk(key, index, chunk_rows)
        bins = lax.map(lambda Xb: bins_of(Xb, table, is_cat_col, num_bins), X.reshape(nb, block, -1))
        return bins.reshape(chunk_rows, -1).T, y

    @jax.jit
    def sums(bins, y, scores, words, feat, split_leaf):
        def one_block(acc, xs):
            bins_b, y_b, s_b = xs
            g, h = softmax_grad_hess(s_b, y_b)
            leaf = route_all(bins_b, words, feat, split_leaf)
            vals = [g, h, jnp.ones_like(g)]
            if variant == "fp8":
                vals += [_round_mantissa(g, 3), _round_mantissa(h, 3)]
            vals = jnp.stack(vals, axis=-1)  # (K, b, C)
            oh = (leaf[:, :, None] == jnp.arange(L)[None, None, :]).astype(jnp.float32)
            return acc + jnp.einsum("kbl,kbc->klc", oh, vals, precision=hp), leaf

        xs = (
            bins.reshape(bins.shape[0], nb, block).transpose(1, 0, 2),
            y.reshape(nb, block),
            scores.reshape(K, nb, block).transpose(1, 0, 2),
        )
        acc, leaves = lax.scan(one_block, jnp.zeros((K, L, C), jnp.float32), xs)
        return acc, leaves.transpose(1, 0, 2).reshape(K, chunk_rows)

    advance = jax.jit(add_leaf_values)
    return binned, sums, advance, C


def compare(cfg, seed, trees, variant=None, holdout_scores=None, holdout_logloss=None):
    """Every number compared, as ``{name: value}``: ``reference.compare``'s
    five over every tree, and ``holdout_logloss_gap`` beside them.

    ``trees``: the program's forest as host arrays, leading axes ``(T, K)``.
    ``holdout_scores``: the program's ``(K, rows)`` raw scores of the
    configuration's holdout under them, ``holdout_logloss`` its
    ``multi_logloss`` of them.  With a ``variant`` the program's counts,
    leaf values and gains are replaced by the reference's own, computed with
    the named fault planted in it: ``fp8`` (gradients and hessians rounded
    to float8 e4m3, the precision below the configuration's bf16 histogram
    multiplies: the control)."""
    import jax
    import jax.numpy as jnp

    from benchmark.dataset import chunk_plan, data_module, holdout_chunks, seed_key

    if variant not in VARIANTS:
        raise ValueError(f"unknown variant {variant!r}")
    data = data_module(cfg)
    key = seed_key(seed)
    X0, _ = jax.jit(data.chunk, static_argnums=2)(key, 0, int(cfg["chunk_rows"]))
    edges = fit_edges(np.asarray(X0[: int(cfg["bin_sample_rows"])]), data.CATEGORICAL, int(cfg["max_bin"]))
    del X0
    if edges.num_bins > WORDS * 32:
        raise ValueError(f"{edges.num_bins} bins do not fit a left table of {WORDS * 32}")
    params = cfg["params"]
    lr = float(params["learning_rate"])
    l2 = float(params.get("lambda_l2", 0.0))
    n_chunks, chunk_rows = chunk_plan(cfg)
    T, K, S = np.asarray(trees.split_leaf).shape
    L = S + 1
    ft = flat_trees(trees)
    ft["cat_threshold"] = ft["cat_threshold"].reshape(T * K, S, -1)
    prog_leaf = ft["leaf_value"].reshape(T, K, L).astype(np.float32)
    prog_count = ft["leaf_count"].reshape(T, K, L).astype(np.float64)
    prog_gain = ft["split_gain"].reshape(T, K, S).astype(np.float64)
    words = jnp.asarray(left_tables(ft, edges).reshape(T, K, S, WORDS))
    feat = jnp.asarray(ft["split_feat"].reshape(T, K, S).astype(np.int32))
    split_leaf = jnp.asarray(ft["split_leaf"].reshape(T, K, S).astype(np.int32))
    table, is_cat_col = jnp.asarray(edges.table), jnp.asarray(edges.is_cat)
    binned, sums, advance, C = make_passes(data, chunk_rows, edges.num_bins, K, L, variant)

    gaps = {}
    if holdout_scores is not None:
        # the control leaves the scorer alone: its gaps are the sound run's, 0 here
        gaps["holdout_score_gap"] = gaps["holdout_logloss_gap"] = 0.0
        if variant is None:
            ref, ys = [], []
            for c in range(holdout_chunks(cfg)):
                bins, y = binned(key, jnp.int32(n_chunks + c), table, is_cat_col)
                s = jnp.zeros((K, chunk_rows), jnp.float32)
                for t in range(T):
                    s = advance(s, route_all(bins, words[t], feat[t], split_leaf[t]), jnp.asarray(prog_leaf[t]))
                ref.append(np.asarray(s))
                ys.append(np.asarray(y, np.int64))
            ref, ys = np.concatenate(ref, axis=1), np.concatenate(ys)
            got = np.asarray(holdout_scores, np.float32)
            diff = np.abs(got - ref)
            gaps["holdout_score_gap"] = float(np.max(diff))
            k, w = np.unravel_index(int(np.argmax(diff)), diff.shape)
            print(f"detail holdout: worst class {k} row {w} score {ref[k, w]:.6g} got {got[k, w]:.6g}", file=sys.stderr)
            ref64 = ref.astype(np.float64)
            top = ref64.max(axis=0)
            lse = top + np.log(np.exp(ref64 - top).sum(axis=0))
            own = float(np.mean(lse - ref64[ys, np.arange(len(ys))]))
            if holdout_logloss is not None:
                gaps["holdout_logloss_gap"] = abs(float(holdout_logloss) - own)
            print(f"detail holdout: multi_logloss {own:.9g} got {holdout_logloss}", file=sys.stderr)
            del ref, ref64, got, diff

    gaps |= {"leaf_count_gap": 0.0, "leaf_value_gap": 0.0, "leaf_value_median_gap": 0.0, "split_gain_gap": 0.0}
    chunks = [binned(key, jnp.int32(c), table, is_cat_col) for c in range(n_chunks)]
    scores = [jnp.zeros((K, chunk_rows), jnp.float32) for _ in range(n_chunks)]  # the engine's init: 0 for every class
    for t in range(T):
        acc = np.zeros((K, L, C))
        for c, (bins, y) in enumerate(chunks):
            part, leaves = sums(bins, y, scores[c], words[t], feat[t], split_leaf[t])
            acc += np.asarray(part, np.float64)
            if t + 1 < T:  # teacher-forced: the program's float32 leaf values move the scores
                scores[c] = advance(scores[c], leaves, jnp.asarray(prog_leaf[t]))
        exact = acc[..., :3]
        judged = acc[..., [3, 4, 2]] if variant == "fp8" else exact
        for k in range(K):
            sl = ft["split_leaf"][t * K + k]
            active = np.flatnonzero(sl >= 0)
            n_leaves = len(active) + 1
            G, H, N = (exact[k, :n_leaves, i] for i in range(3))
            ref_delta = -G / (H + l2 + _EPS) * lr
            if variant is None:
                got_delta, got_count = prog_leaf[t, k, :n_leaves].astype(np.float64), prog_count[t, k, :n_leaves]
            else:
                got_delta = -judged[k, :n_leaves, 0] / (judged[k, :n_leaves, 1] + l2 + _EPS) * lr
                got_count = judged[k, :n_leaves, 2]
            gaps["leaf_count_gap"] = max(gaps["leaf_count_gap"], float(np.max(np.abs(got_count - N) / np.maximum(N, 1.0))))
            leaf_gap = np.abs(got_delta - ref_delta) / np.maximum(np.abs(ref_delta), np.median(np.abs(ref_delta)))
            gaps["leaf_value_gap"] = max(gaps["leaf_value_gap"], float(np.max(leaf_gap)))
            gaps["leaf_value_median_gap"] = max(gaps["leaf_value_median_gap"], float(np.median(leaf_gap)))
            if not len(active):
                continue
            left, right = subtree_members(sl)
            ref_gain, got_gain = np.zeros(len(active)), np.zeros(len(active))
            for i, s in enumerate(active):
                reg = l2 + (CAT_L2 if ft["split_cat"][t * K + k, s] else 0.0)
                ref_gain[i] = split_gain(exact[k, left[s]].sum(axis=0), exact[k, right[s]].sum(axis=0), reg)
                got_gain[i] = (
                    prog_gain[t, k, s] if variant is None
                    else split_gain(judged[k, left[s]].sum(axis=0), judged[k, right[s]].sum(axis=0), reg)
                )
            gain_gap = np.abs(got_gain - ref_gain) / np.maximum(np.maximum(ref_gain, np.median(ref_gain)), _EPS)
            gaps["split_gain_gap"] = max(gaps["split_gain_gap"], float(np.max(gain_gap)))
        print(f"detail iteration {t}: worst so far count {gaps['leaf_count_gap']:.3g} value {gaps['leaf_value_gap']:.3g} "
              f"median {gaps['leaf_value_median_gap']:.3g} gain {gaps['split_gain_gap']:.3g}", file=sys.stderr)
    return gaps
