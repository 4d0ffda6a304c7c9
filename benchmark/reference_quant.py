"""Plain reference for a quantized-training cell, and the comparison that
decides ``correct`` there.

The program rounds each row's gradient and hessian to a handful of integer
levels (LightGBM's ``use_quantized_grad``), picks every split's LEAF AND
COLUMN from integer histograms of those buckets, then re-accumulates the
winning column from the float32 rows and takes threshold, recorded gain and
category set from that, and the leaf values from the rows' exact float32 sums
(the configuration file's ``equations``).  So what the trees CLAIM, counts,
leaf values, recorded gains, holdout scores, is float arithmetic and is judged
by ``reference.compare`` as it stands, teacher-forced on the window's last
fit's trees.  What the levels can move is the choice of the column, and that
is the one number added here.

``quant_choice_gap``: at the nodes ``split_choice_gap`` already visits (the
root and seven seeded nodes a tree), the share by which the best exact gain
WITHIN THE COLUMN THE PROGRAM CHOSE lies under the node's best exact gain over
all columns, less the largest such share that this file's OWN stochastic
rounding of the same node gives over ``DRAWS`` draws of its own at the
configuration's levels (the column taken from the rounded histogram, the gain
from the exact one, as the equations say); the worst node counts.  A program
that rounds as stated is one more draw and reads at or near 0; one that rounds
to fewer levels chooses worse columns than any of the draws.  The reference's
own worst share is reported beside it (``quant_choice_own``).

Gradients are teacher-forced as in ``reference.compare``: tree ``t``'s come
from routing regenerated raw rows through the program's trees ``0...t-1`` and
adding their float32 leaf values.  The scales are this file's own: the
largest ``|g|`` and ``h`` over all rows, found in a pass of their own.

It imports nothing of the program.
"""

import math
import sys

import numpy as np

from benchmark import reference
from benchmark.reference import BLOCK_ROWS, SAMPLED_NODES, best_split, fit_edges, real_trees, route, subtree_members

VARIANTS = reference.VARIANTS  # the control is the float reference's: fp8 in its own leaf sums
DRAWS = 4


def levels_of(params) -> tuple:
    """Largest bucket of gradient and hessian under the configuration's
    ``num_grad_quant_bins``: LightGBM's rule."""
    bins = int(params["num_grad_quant_bins"])
    return bins // 2, bins


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


def make_scale_pass(data, chunk_rows, T):
    """The jitted pass that finds one chunk's largest ``|g|`` and ``h`` under
    tree ``t``'s gradients."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    block = min(BLOCK_ROWS, chunk_rows)

    @jax.jit
    def run(key, index, trees, leaf_vals, init, t):
        X, y = data.chunk(key, index, chunk_rows)
        nb = chunk_rows // block

        def one_block(xy):
            g, h = _gradients(xy[0], xy[1], trees, leaf_vals, init, t, T)
            return jnp.max(jnp.abs(g)), jnp.max(h)

        gm, hm = lax.map(one_block, (X.reshape(nb, block, -1), y.reshape(nb, block)))
        return jnp.max(gm), jnp.max(hm)

    return run


def make_node_pass(data, chunk_rows, num_bins, T, S, levels):
    """The jitted pass over one chunk for tree ``t``: histograms ``(F, B,
    nodes * C)`` of the sampled nodes, the columns being gradient, hessian,
    count and, for each of ``DRAWS`` draws, the gradient's and the hessian's
    bucket (whole numbers, exact in float32 over a chunk)."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    L, B = S + 1, num_bins
    block = min(BLOCK_ROWS, chunk_rows)
    if chunk_rows % block:
        raise ValueError("chunk_rows must be a multiple of the reference's block")
    C = 3 + 2 * DRAWS
    hp = lax.Precision.HIGHEST
    top = jnp.asarray(levels, jnp.float32)

    def bins_of(X, table, is_cat_col):
        v = jnp.where(is_cat_col[None, :], jnp.trunc(X), X)
        pos = (table[None, :, :] < v[:, :, None]).sum(axis=-1).astype(jnp.int32)
        seen = (table[None, :, :] == v[:, :, None]).any(axis=-1)
        pos = jnp.where(is_cat_col[None, :] & ~seen, B - 1, pos)
        return jnp.where(jnp.isnan(X), B - 1, pos)

    def one_block(carry, xyu, table, is_cat_col, trees, leaf_vals, init, t, members, scales):
        X, y, u = xyu
        g, h = _gradients(X, y, trees, leaf_vals, init, t, T)
        leaf = route(X, jax.tree_util.tree_map(lambda a: a[t], trees))
        gh = jnp.stack([g, h], axis=1)  # (b, 2)
        # the configuration's rounding: floor(v / scale + u), u ~ U[0, 1), clipped to the levels
        q = jnp.clip(jnp.floor(gh[:, None, :] / scales[None, None, :] + u), -top, top)  # (b, DRAWS, 2)
        vals = jnp.concatenate([gh, jnp.ones_like(g)[:, None], q.reshape(X.shape[0], -1)], axis=1)  # (b, C)
        oh_leaf = (leaf[:, None] == jnp.arange(L)[None, :]).astype(jnp.float32)
        mask = jnp.einsum("bl,kl->bk", oh_leaf, members, precision=hp)
        W = (mask[:, :, None] * vals[:, None, :]).reshape(X.shape[0], -1)
        oh_bin = (bins_of(X, table, is_cat_col)[:, :, None] == jnp.arange(B)[None, None, :]).astype(jnp.float32)
        return carry + jnp.einsum("bfv,bk->fvk", oh_bin, W, precision=hp), None

    # key, edges and scales are arguments: as closure constants they would make
    # a new program, and a compile, of every seed
    @jax.jit
    def run(key, table, is_cat_col, index, trees, leaf_vals, init, t, members, scales):
        X, y = data.chunk(key, index, chunk_rows)
        nb = chunk_rows // block
        ukey = jax.random.fold_in(jax.random.fold_in(jax.random.fold_in(key, 340034), t), index)
        u = jax.random.uniform(ukey, (nb, block, DRAWS, 2), jnp.float32)
        zero = jnp.zeros((X.shape[1], B, members.shape[0] * C), jnp.float32)
        out, _ = lax.scan(
            lambda c, xyu: one_block(c, xyu, table, is_cat_col, trees, leaf_vals, init, t, members, scales),
            zero,
            (X.reshape(nb, block, -1), y.reshape(nb, block), u),
        )
        return out

    return run, C


def _best_within(hist, feat, is_cat, params) -> float:
    """Best exact gain over the candidates of column ``feat`` alone."""
    gain, _ = best_split(hist[feat : feat + 1], is_cat[feat : feat + 1], params)
    return gain


def choice_gaps(cfg, seed, trees, label_mean) -> dict:
    """``{"quant_choice_gap", "quant_choice_own"}`` for the program's trees."""
    import jax
    import jax.numpy as jnp

    from benchmark.dataset import chunk_plan, data_module, seed_key

    data = data_module(cfg)
    key = seed_key(seed)
    X0, _ = jax.jit(data.chunk, static_argnums=2)(key, 0, int(cfg["chunk_rows"]))
    edges = fit_edges(np.asarray(X0[: int(cfg["bin_sample_rows"])]), data.CATEGORICAL, int(cfg["max_bin"]))
    del X0
    params = cfg["params"]
    levels = levels_of(params)
    n_chunks, chunk_rows = chunk_plan(cfg)
    T = trees.split_leaf.shape[0]
    S = trees.split_leaf.shape[-1]
    L = S + 1
    rt = real_trees(trees, edges)
    p0 = min(max(float(label_mean), 1e-15), 1 - 1e-15)
    init = math.log(p0 / (1 - p0))
    # the program's float32 leaf values as deltas: tree 0 carries the folded bias
    leaf_vals = np.asarray(trees.leaf_value, np.float32).reshape(T, L).copy()
    leaf_vals[0] -= np.float32(init)
    leaf_vals = jnp.asarray(leaf_vals)

    scale_pass = make_scale_pass(data, chunk_rows, T)
    node_pass, C = make_node_pass(data, chunk_rows, edges.num_bins, T, S, levels)
    table, is_cat_col = jnp.asarray(edges.table), jnp.asarray(edges.is_cat)
    dev_trees = {k: jnp.asarray(v) for k, v in rt.items()}
    rng = np.random.default_rng(seed)  # reference.compare's stream: the same nodes
    out = {"quant_choice_gap": 0.0, "quant_choice_own": 0.0}
    for t in range(T):
        active = np.flatnonzero(rt["split_leaf"][t] >= 0)
        left, right = subtree_members(rt["split_leaf"][t])
        extra = active[active > 0]
        picked = [0] + sorted(rng.choice(extra, min(SAMPLED_NODES - 1, len(extra)), replace=False).tolist())
        members = np.zeros((SAMPLED_NODES, L), np.float32)
        for i, s in enumerate(picked):
            members[i] = left[s] | right[s]
        maxima = np.array([
            [float(v) for v in scale_pass(key, jnp.int32(c), dev_trees, leaf_vals, jnp.float32(init), jnp.int32(t))]
            for c in range(n_chunks)
        ], np.float32).max(axis=0)
        scales = (maxima / np.asarray(levels, np.float32)).astype(np.float32)
        hist = np.zeros((len(edges.rows), edges.num_bins, SAMPLED_NODES * C))
        for c in range(n_chunks):
            hist += np.asarray(node_pass(
                key, table, is_cat_col, jnp.int32(c), dev_trees, leaf_vals, jnp.float32(init), jnp.int32(t),
                jnp.asarray(members), jnp.asarray(scales),
            ), np.float64)
        hist = hist.reshape(hist.shape[0], hist.shape[1], SAMPLED_NODES, C)
        for i, s in enumerate(picked):
            exact = hist[:, :, i, :3]
            best, _ = best_split(exact, edges.is_cat, params)
            if not np.isfinite(best) or best <= 0:
                continue
            got = _best_within(exact, int(rt["feat"][t, s]), edges.is_cat, params)
            own = 0.0
            for d in range(DRAWS):
                rounded = np.stack(
                    [hist[:, :, i, 3 + 2 * d] * scales[0], hist[:, :, i, 4 + 2 * d] * scales[1], exact[:, :, 2]], axis=-1,
                )
                _, chosen = best_split(rounded, edges.is_cat, params)
                if chosen is None:
                    own = 1.0
                    continue
                own = max(own, max(0.0, best - _best_within(exact, chosen["feat"], edges.is_cat, params)) / best)
            gap = max(0.0, best - got) / best
            out["quant_choice_own"] = max(out["quant_choice_own"], own)
            out["quant_choice_gap"] = max(out["quant_choice_gap"], max(0.0, gap - own))
            print(
                f"detail tree {t} node {s}: rows {exact[0, :, 2].sum():.0f} column share {gap:.4g} own draws' worst {own:.4g}",
                file=sys.stderr,
            )
    return out


def compare(cfg, seed, trees, label_mean, variant=None, holdout_scores=None):
    """Every number compared, as ``{name: value}``: ``reference.compare``'s,
    and the two of ``choice_gaps`` (the control rounds the float reference's
    own leaf sums and leaves the program's choices alone, so they read as a
    sound run's under it)."""
    gaps = reference.compare(cfg, seed, trees, label_mean, variant=variant, holdout_scores=holdout_scores)
    return {**gaps, **choice_gaps(cfg, seed, trees, label_mean)}
