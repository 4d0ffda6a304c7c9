"""Plain reference for a training cell, and the comparison that decides
``correct``.

Tree growth is a chain of argmax choices, so two sound fits of one data set
need not grow the same trees: a near-tie flips under bf16-multiply
histograms and everything below it differs.  The comparison therefore
follows the trees the timed fit produced (it takes their structure as the
answer to be judged) and recomputes, from rows regenerated from the seed and
in float32 with float64 accumulation across chunks, everything those trees
claim:

- rows are routed by the RAW value against each split's real threshold or
  category set, never through the program's binned cache, so a row the device
  binner misplaced shows in the counts;
- per leaf: row count, gradient and hessian sums, hence the leaf value (the
  worst leaf, and the median leaf, which is steady from seed to seed and is
  what a lower precision moves); the second tree's gradients come from the
  reference's own first-tree scores, so a score update that did not happen
  shows there;
- per split: the gain of that split from the reference's sums, against the
  gain the program recorded;
- at the root and a seeded sample of nodes: the full histogram of the node,
  a plain scan of every numeric threshold and every sorted categorical
  prefix (LightGBM's rules), and the share by which the split the program
  chose lies below the reference's best (observed, not compared: near-ties at
  small nodes make it swing from 1e-6 to 0.06 on sound runs, and a scan with
  the categorical prefix order broken read 0.06 to 0.43: PERF.md section 2).

It imports nothing of the program and takes no table from it: the bin edges
are fitted here (``fit_edges``) from the same first rows of the seed's stream
that set-up hands to the program's own fit, by LightGBM's rule written as a
plain walk.  A program whose edges came out otherwise routes rows otherwise,
and its leaf counts show it.
"""

import math
import sys

import numpy as np

_EPS = 1e-15
CAT_SMOOTH = 10.0
CAT_L2 = 10.0
MIN_SUM_HESSIAN = 1e-3
SAMPLED_NODES = 8
BLOCK_ROWS = 16384


def floor32(a):
    """Largest float32 not above each float64: ``x <= a`` for a float32 ``x``
    is then ``x <= floor32(a)`` exactly."""
    a = np.asarray(a, np.float64)
    a32 = a.astype(np.float32)
    over = a32.astype(np.float64) > a
    return np.where(over, np.nextafter(a32, np.float32(-np.inf)), a32).astype(np.float32)


MIN_DATA_IN_BIN = 3


def numeric_uppers(col, max_bin):
    """Upper edges of one numeric column from its sample: one bin per distinct
    value where they fit (edges midway between neighbours), else a greedy walk
    that closes a bin once it holds ``1 / max_bin`` of the rows; the last bin
    is open above."""
    distinct, counts = np.unique(col[~np.isnan(col)], return_counts=True)
    if len(distinct) == 0:
        return np.array([np.inf])
    mid = (distinct[:-1] + distinct[1:]) / 2.0
    if len(distinct) <= max_bin:
        return np.append(mid, np.inf)
    target = max(counts.sum() / max_bin, MIN_DATA_IN_BIN)
    uppers, held, closed_at = [], 0, 0.0
    for i in range(len(distinct) - 1):
        held += counts[i]
        if held >= closed_at + target and len(uppers) < max_bin - 1:
            uppers.append(mid[i])
            closed_at = held
    return np.array(uppers + [np.inf])


def top_categories(col, max_bin):
    """The ``max_bin`` most frequent category values of a sample (ties to the
    smaller value), sorted; rarer ones fall to the missing bin."""
    cats, counts = np.unique(col[~np.isnan(col)].astype(np.int64), return_counts=True)
    return np.sort(cats[np.argsort(-counts, kind="stable")][:max_bin])


def fit_edges(sample, categorical, max_bin):
    """``Edges`` from the sample rows ``(n, F)``."""
    sample = np.asarray(sample, np.float64)
    cat = set(categorical)
    uppers = [None if f in cat else numeric_uppers(sample[:, f], max_bin) for f in range(sample.shape[1])]
    maps = {f: top_categories(sample[:, f], max_bin) for f in cat}
    return Edges(uppers, maps, categorical, max_bin + 1)


class Edges:
    """Bin edges as plain arrays: numeric uppers (floored to float32) or
    sorted category values, one padded row per column."""

    def __init__(self, upper_bounds, cat_maps, categorical, num_bins):
        F = len(upper_bounds)
        self.num_bins = int(num_bins)
        self.is_cat = np.zeros(F, bool)
        self.is_cat[list(categorical)] = True
        rows = [
            np.asarray(cat_maps[f], np.float32) if self.is_cat[f] else floor32(upper_bounds[f])
            for f in range(F)
        ]
        self.table = np.full((F, self.num_bins), np.inf, np.float32)
        for f, r in enumerate(rows):
            self.table[f, : len(r)] = r
        self.rows = rows


def real_trees(trees, edges: Edges):
    """The program's trees (host arrays, leading axes (T, 1)) with every split
    in raw-value form."""
    T = trees.split_leaf.shape[0]
    S = trees.split_leaf.shape[-1]
    V = max([len(r) for f, r in enumerate(edges.rows) if edges.is_cat[f]] + [1])
    out = {
        "split_leaf": np.asarray(trees.split_leaf, np.int32).reshape(T, S),
        "feat": np.asarray(trees.split_feat, np.int32).reshape(T, S),
        "dleft": np.asarray(trees.default_left, bool).reshape(T, S),
        "is_cat": np.asarray(trees.split_cat, bool).reshape(T, S),
        "thr": np.zeros((T, S), np.float32),
        "cat_vals": np.full((T, S, V), np.nan, np.float32),
    }
    split_bin = np.asarray(trees.split_bin).reshape(T, S)
    members = np.asarray(trees.cat_threshold, bool).reshape(T, S, -1)
    for t in range(T):
        for s in range(S):
            if out["split_leaf"][t, s] < 0:
                continue
            row = edges.rows[out["feat"][t, s]]
            if out["is_cat"][t, s]:
                vals = row[members[t, s, : len(row)]]
                out["cat_vals"][t, s, : len(vals)] = vals
            else:
                out["thr"][t, s] = row[min(split_bin[t, s], len(row) - 1)]
    return out


def subtree_members(split_leaf):
    """For each split step of one tree, the final leaves under its left and
    right child: ``(left (S, L) bool, right (S, L) bool)``."""
    S = len(split_leaf)
    L = S + 1
    under = np.eye(L, dtype=bool)  # under[i]: final leaves now carrying id i
    left = np.zeros((S, L), bool)
    right = np.zeros((S, L), bool)
    for s in range(S - 1, -1, -1):
        p = split_leaf[s]
        if p < 0:
            continue
        left[s], right[s] = under[p], under[s + 1]
        under[p] = under[p] | under[s + 1]
    return left, right


def _score(G, H, l2):
    return G * G / (H + l2 + _EPS)


def split_gain(GHl, GHr, l2):
    return (
        _score(GHl[0], GHl[1], l2) + _score(GHr[0], GHr[1], l2)
        - _score(GHl[0] + GHr[0], GHl[1] + GHr[1], l2)
    )


def _valid(Cl, Hl, Cr, Hr, min_data):
    return (Cl >= min_data) & (Cr >= min_data) & (Hl >= MIN_SUM_HESSIAN) & (Hr >= MIN_SUM_HESSIAN)


def best_split(hist, is_cat, params):
    """Best gain over every candidate of one node.  ``hist``: (F, B, 3) sums of
    gradient, hessian and count per bin, the last bin holding missing values.
    Returns ``(gain, description)``."""
    l2 = float(params.get("lambda_l2", 0.0))
    min_data = int(params["min_data_in_leaf"])
    tot = hist[0].sum(axis=0)
    best = (-np.inf, None)
    for f in range(hist.shape[0]):
        h = hist[f]
        vb, miss = h[:-1], h[-1]
        if is_cat[f]:
            used = np.flatnonzero(vb[:, 2] > 0)
            if len(used) < 2:
                continue
            order = used[np.argsort(vb[used, 0] / (vb[used, 1] + CAT_SMOOTH), kind="stable")]
            for direction in (order, order[::-1]):
                left = np.cumsum(vb[direction], axis=0)[:-1]  # proper prefixes
                right = tot[None, :] - left
                gain = split_gain(left.T, right.T, l2 + CAT_L2)
                gain = np.where(_valid(left[:, 2], left[:, 1], right[:, 2], right[:, 1], min_data), gain, -np.inf)
                k = int(np.argmax(gain))
                if gain[k] > best[0]:
                    mask = np.zeros(hist.shape[1], bool)
                    mask[direction[: k + 1]] = True
                    best = (float(gain[k]), {"cat": True, "feat": f, "members": mask})
        else:
            cum = np.cumsum(vb, axis=0)
            for dleft in (False, True):
                left = cum + miss[None, :] if dleft else cum
                right = tot[None, :] - left
                gain = split_gain(left.T, right.T, l2)
                gain = np.where(_valid(left[:, 2], left[:, 1], right[:, 2], right[:, 1], min_data), gain, -np.inf)
                k = int(np.argmax(gain))
                if gain[k] > best[0]:
                    best = (float(gain[k]), {"cat": False, "feat": f, "bin": k, "dleft": dleft})
    return best


def eval_split(hist, d, params):
    """Gain of the described split under ``hist``."""
    l2 = float(params.get("lambda_l2", 0.0))
    h = hist[d["feat"]]
    tot = h.sum(axis=0)
    if d["cat"]:
        left = h[: len(d["members"])][d["members"]].sum(axis=0)
        l2 += CAT_L2
    else:
        left = h[: d["bin"] + 1].sum(axis=0) + (h[-1] if d["dleft"] else 0.0)
    return float(split_gain(left, tot - left, l2))


def _round_mantissa(x, bits: int):
    """float32 rounded to nearest-even at ``bits`` of mantissa (float8 e4m3
    keeps 3).  Done on the bit pattern: a convert to a float8 type and back is
    folded away by the TPU compiler and reads as no rounding at all."""
    import jax.numpy as jnp
    from jax import lax

    drop = 23 - bits
    u = lax.bitcast_convert_type(x, jnp.uint32)
    u = u + jnp.uint32((1 << (drop - 1)) - 1) + ((u >> drop) & jnp.uint32(1))
    u = u & jnp.uint32(~((1 << drop) - 1) & 0xFFFFFFFF)
    return lax.bitcast_convert_type(u, jnp.float32)


def route(X, tr):
    """Leaf of each raw row of ``X`` under one tree in raw-value form
    (``real_trees``): rows start in leaf 0 and each split moves those that go
    right."""
    import jax.numpy as jnp
    from jax import lax

    def step(s, leaf):
        x = lax.dynamic_index_in_dim(X, tr["feat"][s], axis=1, keepdims=False)
        left = lax.cond(
            tr["is_cat"][s],
            lambda: (jnp.trunc(x)[:, None] == tr["cat_vals"][s][None, :]).any(axis=1),
            lambda: jnp.where(jnp.isnan(x), tr["dleft"][s], x <= tr["thr"][s]),
        )
        move = (tr["split_leaf"][s] >= 0) & (leaf == tr["split_leaf"][s]) & ~left
        return jnp.where(move, s + 1, leaf)

    return lax.fori_loop(0, tr["feat"].shape[0], step, jnp.zeros(X.shape[0], jnp.int32))


def make_scorer(data, chunk_rows, T):
    """The jitted reference scorer of one chunk: regenerates it and adds, tree
    by tree in float32, the leaf value each raw row is routed to."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    block = min(BLOCK_ROWS, chunk_rows)

    @jax.jit
    def run(key, index, trees, leaf_vals):
        X, _ = data.chunk(key, index, chunk_rows)

        def one_block(Xb):
            score = jnp.zeros(Xb.shape[0], jnp.float32)
            for j in range(T):
                score = score + leaf_vals[j][route(Xb, jax.tree_util.tree_map(lambda a: a[j], trees))]
            return score

        return lax.map(one_block, X.reshape(chunk_rows // block, block, -1)).reshape(-1)

    return run


VARIANTS = (None, "fp8")  # the reference put in the program's place: the control


def make_pass(data, chunk_rows, num_bins, T, S, variant):
    """The jitted reference pass over one chunk for tree ``t``: regenerates the
    chunk, scores it with the reference's earlier trees, routes it through tree
    ``t`` and returns ``(leaf sums (L, C), node histograms (F, B, K*C))``.  The
    columns are gradient, hessian, count and, for the control, its gradients
    and hessians."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    L = S + 1
    B = num_bins
    block = min(BLOCK_ROWS, chunk_rows)
    if chunk_rows % block:
        raise ValueError("chunk_rows must be a multiple of the reference's block")
    C = 5 if variant == "fp8" else 3
    hp = lax.Precision.HIGHEST

    def bins_of(X, table, is_cat_col):
        v = jnp.where(is_cat_col[None, :], jnp.trunc(X), X)
        pos = (table[None, :, :] < v[:, :, None]).sum(axis=-1).astype(jnp.int32)
        seen = (table[None, :, :] == v[:, :, None]).any(axis=-1)
        pos = jnp.where(is_cat_col[None, :] & ~seen, B - 1, pos)
        return jnp.where(jnp.isnan(X), B - 1, pos)

    def one_block(carry, xy, table, is_cat_col, trees, leaf_vals, init, t, members):
        X, y = xy
        score = jnp.full(X.shape[0], init, jnp.float32)
        for j in range(T - 1):  # earlier trees only; later ones add nothing
            tr = jax.tree_util.tree_map(lambda a: a[j], trees)
            score = score + lax.cond(
                j < t, lambda: leaf_vals[j][route(X, tr)], lambda: jnp.zeros_like(score)
            )
        p = jax.nn.sigmoid(score)
        g, h = p - y, p * (1.0 - p)
        leaf = route(X, jax.tree_util.tree_map(lambda a: a[t], trees))
        vals = [g, h, jnp.ones_like(g)]
        if variant == "fp8":
            vals += [_round_mantissa(g, 3), _round_mantissa(h, 3)]
        vals = jnp.stack(vals, axis=1)  # (b, C)
        oh_leaf = (leaf[:, None] == jnp.arange(L)[None, :]).astype(jnp.float32)
        leaf_sums = jnp.einsum("bl,bc->lc", oh_leaf, vals, precision=hp)
        mask = jnp.einsum("bl,kl->bk", oh_leaf, members, precision=hp)
        W = (mask[:, :, None] * vals[:, None, :]).reshape(X.shape[0], -1)
        oh_bin = (bins_of(X, table, is_cat_col)[:, :, None] == jnp.arange(B)[None, None, :]).astype(jnp.float32)
        hist = jnp.einsum("bfv,bk->fvk", oh_bin, W, precision=hp)
        return (carry[0] + leaf_sums, carry[1] + hist), None

    # key and edges are arguments: as closure constants they would make a new
    # program, and a compile, of every seed
    @jax.jit
    def run(key, table, is_cat_col, index, trees, leaf_vals, init, t, members):
        X, y = data.chunk(key, index, chunk_rows)
        nb = chunk_rows // block
        zero = (
            jnp.zeros((L, C), jnp.float32),
            jnp.zeros((X.shape[1], B, members.shape[0] * C), jnp.float32),
        )
        out, _ = lax.scan(
            lambda c, xy: one_block(c, xy, table, is_cat_col, trees, leaf_vals, init, t, members),
            zero,
            (X.reshape(nb, block, -1), y.reshape(nb, block)),
        )
        return out

    return run, C


def compare(cfg, seed, trees, label_mean, variant=None, holdout_scores=None):
    """Every number compared, as ``{name: value}``.

    ``holdout_scores``: the program's raw scores of the configuration's
    holdout rows under ``trees``; ``holdout_score_gap`` is the widest distance
    to the reference's scores of the same rows, routed by raw value through
    the same trees.

    ``trees``: the program's forest as host arrays.  With a ``variant`` the
    program's counts, leaf values, gains and split choices are replaced by the
    reference's own, computed with the named fault planted in it: ``fp8``
    (gradients and hessians rounded to float8 e4m3, the precision below the
    bf16 multiplies the configuration states: the control).
    """
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
    params = cfg["params"]
    lr = float(params["learning_rate"])
    l2 = float(params.get("lambda_l2", 0.0))
    n_chunks, chunk_rows = chunk_plan(cfg)
    T = trees.split_leaf.shape[0]
    S = trees.split_leaf.shape[-1]
    L = S + 1
    rt = real_trees(trees, edges)
    prog_leaf = np.asarray(trees.leaf_value, np.float64).reshape(T, L)
    prog_count = np.asarray(trees.leaf_count, np.float64).reshape(T, L)
    prog_gain = np.asarray(trees.split_gain, np.float64).reshape(T, S)
    split_bin = np.asarray(trees.split_bin).reshape(T, S)
    cat_members = np.asarray(trees.cat_threshold, bool).reshape(T, S, -1)
    p0 = min(max(float(label_mean), 1e-15), 1 - 1e-15)
    init = math.log(p0 / (1 - p0))

    run, C = make_pass(data, chunk_rows, edges.num_bins, T, S, variant)
    table, is_cat_col = jnp.asarray(edges.table), jnp.asarray(edges.is_cat)
    dev_trees = {k: jnp.asarray(v) for k, v in rt.items()}
    ref_leaf = np.zeros((T, L), np.float32)
    rng = np.random.default_rng(seed)
    holdout_gap = {}
    if holdout_scores is not None:
        # the control leaves the scorer alone: its gap is the sound run's, 0 here
        holdout_gap["holdout_score_gap"] = 0.0
        if variant is None:
            scorer = make_scorer(data, chunk_rows, T)
            leaf_vals = jnp.asarray(prog_leaf, jnp.float32)
            ref = np.concatenate([
                np.asarray(scorer(key, jnp.int32(n_chunks + c), dev_trees, leaf_vals))
                for c in range(holdout_chunks(cfg))
            ])
            holdout_gap["holdout_score_gap"] = float(np.max(np.abs(np.asarray(holdout_scores, np.float32) - ref)))
            w = int(np.argmax(np.abs(holdout_scores - ref)))
            print(f"detail holdout: worst row {w} score {ref[w]:.6g} got {holdout_scores[w]:.6g}", file=sys.stderr)
    gaps = {**holdout_gap, "leaf_count_gap": 0.0, "leaf_value_gap": 0.0, "leaf_value_median_gap": 0.0, "split_gain_gap": 0.0, "split_choice_gap": 0.0}
    for t in range(T):
        active = np.flatnonzero(rt["split_leaf"][t] >= 0)
        n_leaves = len(active) + 1
        left, right = subtree_members(rt["split_leaf"][t])
        extra = active[active > 0]
        picked = [0] + sorted(rng.choice(extra, min(SAMPLED_NODES - 1, len(extra)), replace=False).tolist())
        members = np.zeros((SAMPLED_NODES, L), np.float32)
        for i, s in enumerate(picked):
            members[i] = left[s] | right[s]
        sums = np.zeros((L, C))
        hist = np.zeros((len(edges.rows), edges.num_bins, SAMPLED_NODES * C))
        for c in range(n_chunks):
            ls, hs = run(
                key, table, is_cat_col, jnp.int32(c), dev_trees, jnp.asarray(ref_leaf),
                jnp.float32(init), jnp.int32(t), jnp.asarray(members),
            )
            sums += np.asarray(ls, np.float64)
            hist += np.asarray(hs, np.float64)
        hist = hist.reshape(hist.shape[0], hist.shape[1], SAMPLED_NODES, C)
        exact_sums, exact_hist = sums[:, :3], hist[..., :3]
        # the sums the judged side is built from, where the reference stands in for the program
        if variant == "fp8":
            v_sums, v_hist = sums[:, [3, 4, 2]], hist[..., [3, 4, 2]]
        else:
            v_sums, v_hist = exact_sums, exact_hist

        G, H, N = (exact_sums[:n_leaves, i] for i in range(3))
        ref_delta = -G / (H + l2 + _EPS) * lr
        ref_leaf[t, :n_leaves] = ref_delta
        if variant is None:
            got_delta = prog_leaf[t, :n_leaves] - (np.float32(init) if t == 0 else 0.0)
            got_count = prog_count[t, :n_leaves]
        else:
            got_delta = -v_sums[:n_leaves, 0] / (v_sums[:n_leaves, 1] + l2 + _EPS) * lr
            got_count = v_sums[:n_leaves, 2]
        gaps["leaf_count_gap"] = max(gaps["leaf_count_gap"], float(np.max(np.abs(got_count - N) / np.maximum(N, 1.0))))
        scale = np.maximum(np.abs(ref_delta), np.median(np.abs(ref_delta)))
        leaf_gap = np.abs(got_delta - ref_delta) / scale
        gaps["leaf_value_gap"] = max(gaps["leaf_value_gap"], float(np.max(leaf_gap)))
        gaps["leaf_value_median_gap"] = max(gaps["leaf_value_median_gap"], float(np.median(leaf_gap)))
        w = int(np.argmax(leaf_gap))
        print(f"detail tree {t}: worst leaf {w} rows {N[w]:.0f} value {ref_delta[w]:.6g} got {got_delta[w]:.6g}; median gap {np.median(leaf_gap):.3g}", file=sys.stderr)

        ref_gain = np.zeros(len(active))
        got_gain = np.zeros(len(active))
        for i, s in enumerate(active):
            reg = l2 + (CAT_L2 if rt["is_cat"][t, s] else 0.0)
            ref_gain[i] = split_gain(exact_sums[left[s]].sum(axis=0), exact_sums[right[s]].sum(axis=0), reg)
            got_gain[i] = (
                prog_gain[t, s] if variant is None
                else split_gain(v_sums[left[s]].sum(axis=0), v_sums[right[s]].sum(axis=0), reg)
            )
        scale = np.maximum(ref_gain, np.median(ref_gain))
        gain_gap = np.abs(got_gain - ref_gain) / scale
        gaps["split_gain_gap"] = max(gaps["split_gain_gap"], float(np.max(gain_gap)))
        w = int(np.argmax(gain_gap))
        print(f"detail tree {t}: worst split {active[w]} gain {ref_gain[w]:.6g} got {got_gain[w]:.6g}; median gap {np.median(gain_gap):.3g}", file=sys.stderr)

        for i, s in enumerate(picked):
            exact = exact_hist[:, :, i, :]
            best, _ = best_split(exact, edges.is_cat, params)
            if variant is not None:
                _, chosen = best_split(v_hist[:, :, i, :], edges.is_cat, params)
            elif rt["is_cat"][t, s]:
                chosen = {"cat": True, "feat": int(rt["feat"][t, s]), "members": cat_members[t, s, :-1]}
            else:
                chosen = {"cat": False, "feat": int(rt["feat"][t, s]), "bin": int(split_bin[t, s]), "dleft": bool(rt["dleft"][t, s])}
            if chosen is None or not np.isfinite(best):
                gaps["split_choice_gap"] = max(gaps["split_choice_gap"], 1.0)
                continue
            got = eval_split(exact, chosen, params)
            gaps["split_choice_gap"] = max(gaps["split_choice_gap"], max(0.0, best - got) / best)
    return gaps
