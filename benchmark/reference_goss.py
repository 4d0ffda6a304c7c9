"""Plain reference for a cell that trains with gradient one-side sampling
(GOSS), and the comparison that decides ``correct`` there.

Each tree is grown from a SAMPLE of the rows: the ``top_rows`` rows of
largest ``|g|`` and ``rest_rows`` rows drawn from the others, whose gradients
and hessians count ``amp`` times (the configuration file's ``equations``).
What the trees claim is then what ``reference.compare`` judges, over the
sample: the sampled rows' count in each leaf, the leaf values from the
sample's amplified sums, each recorded gain, the holdout's scores.

Teacher-forced as in ``reference.compare``: tree ``t``'s gradients come from
routing regenerated raw rows through the program's trees ``0...t-1`` and
adding the reference's OWN leaf values of them.  The sample is this file's
own: ``|g|`` of every row in a pass of its own, the top rows by a plain
stable ``argsort`` (the reference may sort), the rest's draw from the key
schedule the configuration writes down, again by a stable ``argsort``, and
the amplification in float32.  A program that samples otherwise (another
count, another rank, no amplification) puts other rows in the leaves.

It imports nothing of the program.
"""

import math
import sys

import numpy as np

from benchmark import reference
from benchmark.reference import (
    _EPS, BLOCK_ROWS, CAT_L2, SAMPLED_NODES, _round_mantissa, best_split, eval_split, fit_edges, make_scorer,
    real_trees, route, split_gain, subtree_members,
)

VARIANTS = reference.VARIANTS  # the control: fp8 in the reference's own leaf sums
TAG = 0x6055  # folded into an iteration's sampling key for the rest's draw


def counts(n: int, params) -> tuple:
    """``(top_rows, rest_rows)`` of a sample of ``n`` rows."""
    top = min(max(1, math.floor(float(params["top_rate"]) * n)), n)
    return top, min(math.floor(float(params["other_rate"]) * n), n - top)


def amplification(params) -> np.float32:
    return np.float32((1.0 - float(params["top_rate"])) / float(params["other_rate"]))


def rest_draw(params, t: int, n: int):
    """The draw ``u`` of iteration ``t`` over ``n`` rows, from the key
    schedule of the configuration's ``equations``."""
    import jax

    root = jax.random.PRNGKey(int(params.get("bagging_seed", 3)) + 7919 * int(params.get("seed", 0)))
    gkey = jax.random.split(jax.random.fold_in(root, t))[0]
    return jax.random.uniform(jax.random.fold_in(gkey, TAG), (n,), jax.numpy.float32)


def _gradients(X, y, trees, leaf_vals, init, t, T):
    """Teacher-forced binary log-loss gradients of tree ``t`` for one block."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    score = jnp.full(X.shape[0], init, jnp.float32)
    for j in range(T - 1):  # earlier trees only; later ones add nothing
        tr = jax.tree_util.tree_map(lambda a: a[j], trees)
        score = score + lax.cond(j < t, lambda: leaf_vals[j][route(X, tr)], lambda: jnp.zeros_like(score))
    p = jax.nn.sigmoid(score)
    return p - y, p * (1.0 - p)


def make_rank_pass(data, chunk_rows, T):
    """The jitted pass giving one chunk's ``|g|`` under tree ``t``."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    block = min(BLOCK_ROWS, chunk_rows)

    @jax.jit
    def run(key, index, trees, leaf_vals, init, t):
        X, y = data.chunk(key, index, chunk_rows)
        nb = chunk_rows // block
        g = lax.map(lambda xy: _gradients(xy[0], xy[1], trees, leaf_vals, init, t, T)[0],
                    (X.reshape(nb, block, -1), y.reshape(nb, block)))
        return jnp.abs(g).reshape(-1)

    return run


def sample_weights(s, params, t):
    """``w`` of every row: 1 on the top, ``amp`` on the rest, 0 elsewhere;
    the selection by stable ``argsort``s."""
    import jax.numpy as jnp
    from jax import lax

    n = s.shape[0]
    k_top, k_rest = counts(n, params)
    key = lax.bitcast_convert_type(s, jnp.uint32)  # s >= 0: the bits order as the values
    top = jnp.zeros(n, bool).at[jnp.argsort(~key, stable=True)[:k_top]].set(True)
    u = lax.bitcast_convert_type(rest_draw(params, t, n), jnp.uint32)
    u = jnp.where(top, jnp.uint32(0xFFFFFFFF), u)  # u < 1: a top row sorts after every other
    rest = jnp.zeros(n, bool).at[jnp.argsort(u, stable=True)[:k_rest]].set(True)
    return jnp.where(top, 1.0, jnp.where(rest, amplification(params), 0.0)).astype(jnp.float32)


def make_pass(data, chunk_rows, num_bins, T, S, variant):
    """``reference.make_pass`` over the sample: each row's values
    ``(g w, h w, 1[w > 0])`` for the weight ``w`` handed in with the chunk."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    L, B = S + 1, num_bins
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

    def one_block(carry, xyw, table, is_cat_col, trees, leaf_vals, init, t, members):
        X, y, w = xyw
        g, h = _gradients(X, y, trees, leaf_vals, init, t, T)
        leaf = route(X, jax.tree_util.tree_map(lambda a: a[t], trees))
        gw, hw = g * w, h * w
        vals = [gw, hw, (w > 0).astype(jnp.float32)]
        if variant == "fp8":
            vals += [_round_mantissa(gw, 3), _round_mantissa(hw, 3)]
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
    def run(key, table, is_cat_col, index, w, trees, leaf_vals, init, t, members):
        X, y = data.chunk(key, index, chunk_rows)
        nb = chunk_rows // block
        zero = (jnp.zeros((L, C), jnp.float32), jnp.zeros((X.shape[1], B, members.shape[0] * C), jnp.float32))
        out, _ = lax.scan(
            lambda c, xyw: one_block(c, xyw, table, is_cat_col, trees, leaf_vals, init, t, members),
            zero,
            (X.reshape(nb, block, -1), y.reshape(nb, block), w.reshape(nb, block)),
        )
        return out

    return run, C


def compare(cfg, seed, trees, label_mean, variant=None, holdout_scores=None):
    """Every number compared, as ``{name: value}``: ``reference.compare``'s
    five and ``split_choice_gap``, over each tree's sample; ``sample_rows``
    (the reference's own count) beside them.  With a ``variant`` the
    program's counts, leaf values, gains and split choices are replaced by
    the reference's own with that fault planted in it."""
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

    rank_pass = make_rank_pass(data, chunk_rows, T)
    run, C = make_pass(data, chunk_rows, edges.num_bins, T, S, variant)
    table, is_cat_col = jnp.asarray(edges.table), jnp.asarray(edges.is_cat)
    dev_trees = {k: jnp.asarray(v) for k, v in rt.items()}
    ref_leaf = np.zeros((T, L), np.float32)
    rng = np.random.default_rng(seed)
    gaps = {}
    if holdout_scores is not None:
        # the control leaves the scorer alone: its gap is the sound run's, 0 here
        gaps["holdout_score_gap"] = 0.0
        if variant is None:
            scorer = make_scorer(data, chunk_rows, T)
            leaf_vals = jnp.asarray(prog_leaf, jnp.float32)
            ref = np.concatenate([
                np.asarray(scorer(key, jnp.int32(n_chunks + c), dev_trees, leaf_vals))
                for c in range(holdout_chunks(cfg))
            ])
            gaps["holdout_score_gap"] = float(np.max(np.abs(np.asarray(holdout_scores, np.float32) - ref)))
            w = int(np.argmax(np.abs(holdout_scores - ref)))
            print(f"detail holdout: worst row {w} score {ref[w]:.6g} got {holdout_scores[w]:.6g}", file=sys.stderr)
    gaps |= {"leaf_count_gap": 0.0, "leaf_value_gap": 0.0, "leaf_value_median_gap": 0.0, "split_gain_gap": 0.0, "split_choice_gap": 0.0}
    for t in range(T):
        s = jnp.concatenate([
            rank_pass(key, jnp.int32(c), dev_trees, jnp.asarray(ref_leaf), jnp.float32(init), jnp.int32(t))
            for c in range(n_chunks)
        ])
        w_all = sample_weights(s, params, t)
        del s
        gaps["sample_rows"] = float(jnp.sum(w_all > 0))
        active = np.flatnonzero(rt["split_leaf"][t] >= 0)
        n_leaves = len(active) + 1
        left, right = subtree_members(rt["split_leaf"][t])
        extra = active[active > 0]
        picked = [0] + sorted(rng.choice(extra, min(SAMPLED_NODES - 1, len(extra)), replace=False).tolist())
        members = np.zeros((SAMPLED_NODES, L), np.float32)
        for i, s_ in enumerate(picked):
            members[i] = left[s_] | right[s_]
        sums = np.zeros((L, C))
        hist = np.zeros((len(edges.rows), edges.num_bins, SAMPLED_NODES * C))
        for c in range(n_chunks):
            ls, hs = run(
                key, table, is_cat_col, jnp.int32(c), w_all[c * chunk_rows : (c + 1) * chunk_rows], dev_trees,
                jnp.asarray(ref_leaf), jnp.float32(init), jnp.int32(t), jnp.asarray(members),
            )
            sums += np.asarray(ls, np.float64)
            hist += np.asarray(hs, np.float64)
        del w_all
        hist = hist.reshape(hist.shape[0], hist.shape[1], SAMPLED_NODES, C)
        exact_sums, exact_hist = sums[:, :3], hist[..., :3]
        # the sums the judged side is built from, where the reference stands in for the program
        v_sums, v_hist = (sums[:, [3, 4, 2]], hist[..., [3, 4, 2]]) if variant == "fp8" else (exact_sums, exact_hist)

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
        print(f"detail tree {t}: sample {gaps['sample_rows']:.0f} rows; worst leaf {w} rows {N[w]:.0f} value {ref_delta[w]:.6g} got {got_delta[w]:.6g}; median gap {np.median(leaf_gap):.3g}", file=sys.stderr)

        ref_gain, got_gain = np.zeros(len(active)), np.zeros(len(active))
        for i, s_ in enumerate(active):
            reg = l2 + (CAT_L2 if rt["is_cat"][t, s_] else 0.0)
            ref_gain[i] = split_gain(exact_sums[left[s_]].sum(axis=0), exact_sums[right[s_]].sum(axis=0), reg)
            got_gain[i] = (
                prog_gain[t, s_] if variant is None
                else split_gain(v_sums[left[s_]].sum(axis=0), v_sums[right[s_]].sum(axis=0), reg)
            )
        gain_gap = np.abs(got_gain - ref_gain) / np.maximum(ref_gain, np.median(ref_gain))
        gaps["split_gain_gap"] = max(gaps["split_gain_gap"], float(np.max(gain_gap)))
        w = int(np.argmax(gain_gap))
        print(f"detail tree {t}: worst split {active[w]} gain {ref_gain[w]:.6g} got {got_gain[w]:.6g}; median gap {np.median(gain_gap):.3g}", file=sys.stderr)

        for i, s_ in enumerate(picked):
            exact = exact_hist[:, :, i, :]
            best, _ = best_split(exact, edges.is_cat, params)
            if variant is not None:
                _, chosen = best_split(v_hist[:, :, i, :], edges.is_cat, params)
            elif rt["is_cat"][t, s_]:
                chosen = {"cat": True, "feat": int(rt["feat"][t, s_]), "members": cat_members[t, s_, :-1]}
            else:
                chosen = {"cat": False, "feat": int(rt["feat"][t, s_]), "bin": int(split_bin[t, s_]), "dleft": bool(rt["dleft"][t, s_])}
            if chosen is None or not np.isfinite(best):
                gaps["split_choice_gap"] = max(gaps["split_choice_gap"], 1.0)
                continue
            gaps["split_choice_gap"] = max(gaps["split_choice_gap"], max(0.0, best - eval_split(exact, chosen, params)) / best)
    return gaps
