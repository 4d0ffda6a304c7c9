"""The windowed grower's row route in one pass: every split of a pass
applied to each row block by one Pallas body.

A pass of ``engine/tree.py`` ``grow_tree_depthwise`` splits up to W leaves
(its window's slots).  Each row of a split leaf then moves to the leaf's
new right child or stays, by the bin its row holds in the split's column:
numeric splits by threshold, with the missing bin ``B - 1`` going the
default way; categorical splits by membership in the chosen set of bins.

Done in XLA, that is one fusion a slot over every row: a one-row slice of
the ``(F, n)`` uint8 matrix and a ``(1, n)`` chain of compares and selects
that fills one sublane of eight in every vector register.  This kernel
reads each ``(F, bm)`` block of the resident matrix once, where it lies,
with the block's leaf ids, and decides all W slots together:

- the W split columns come out of the block as one ``(W, F) x (F, rm)``
  product on the MXU with a one-hot of the slots' features: the byte
  tier's bins, integers up to 255, are exact in bfloat16 and every output
  sums one term, so the product at default precision is the bins to the
  bit.  The body takes uint8 bins alone;
- every slot's goes-left bit is one lookup in its bit set of bins, ``⌈B/32⌉``
  words packed once a pass (:func:`slot_table`): a categorical split's
  chosen bins, a numeric split's bins at or below its threshold with the
  missing bin added where it goes left.  So a numeric and a categorical
  slot are the same arithmetic, on dense ``(W, rm)`` registers;
- a row moves where its leaf is a slot's and the bit is clear.  A row's
  leaf matches at most one slot (the slots' leaves are distinct, and a new
  id is never a slot's leaf), so the max over the slots of the moved ids
  is the row's new id.

The leaf ids are updated in place (``input_output_aliases``).  The result
is the slot loop's to the bit: the moves are integer logic
(``tests/test_row_route.py``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

# The widest matrix the block route takes.  A pass reads the whole block,
# about F bytes a row (the uint8 rows in byte tiles), and the int32 id read
# and written, 8.  The slot loop reads one column eight times.  On a v5e
# (kernel probe, 8 slots, 256 bins, random bins and tables; ms a pass):
#   F =  22,  4,194,304 rows, categorical: loop 2.305, this body 0.408
#   F =  39, 25,165,824 rows, categorical: loop 13.316, this body 2.233
#   F = 220,  7,340,032 rows, numeric:     loop 1.665, this body 2.316
# At 22 and 39 columns the body costs the same 0.09 ns a row: its per-row
# vector work binds, not the block.  At 220 the block's 232 bytes a row
# bind (735 GB/s).  The loop costs 0.028 ns a row and slot on numeric
# columns, 0.064 on categorical ones.  At 128 columns the block's 136 bytes
# a row take about 0.19 ns where the cheaper loop takes 0.23; past about 160
# the loop is as fast, and at 220 faster.  A (128, BM) uint8 block, double
# buffered, fits the default scoped VMEM (tests/test_tpu_compile.py).
MAX_COLS = 128

# Rows a grid step reads (its block) and a step's inner loop works on at
# once (the one-hot product's N and the (W, RM) registers).
BM, RM = 32768, 4096

_FIELDS = 3  # leaf, feature, new id; then the words of the goes-left set


def member_words(members):
    """``(..., B)`` bool → ``(..., ⌈B/32⌉)`` uint32: bin ``b`` is bit
    ``b % 32`` of word ``b // 32``."""
    B = members.shape[-1]
    nw = (B + 31) // 32
    pad = [(0, 0)] * (members.ndim - 1) + [(0, nw * 32 - B)]
    bits = jnp.pad(members, pad).reshape(*members.shape[:-1], nw, 32)
    return (bits.astype(jnp.uint32) << jnp.arange(32, dtype=jnp.uint32)).sum(axis=-1, dtype=jnp.uint32)


def slot_table(leaf, feat, thr, dleft, is_cat, new_id, members):
    """The ``(W, 3 + ⌈B/32⌉)`` int32 table one pass hands the kernel, from
    its slots' ``(W,)`` leaf, feature, threshold bin, missing-goes-left,
    is-categorical and new right-child id, and the ``(W, B)`` categorical
    sets (bins that go left).  A slot that does not split carries its own
    leaf as its new id: its rows "move" to where they are."""
    B = members.shape[-1]
    b = jnp.arange(B, dtype=jnp.int32)
    numeric = jnp.where(b == B - 1, dleft[:, None], b <= thr[:, None])
    left = jnp.where(is_cat[:, None], members, numeric)
    words = lax.bitcast_convert_type(member_words(left), jnp.int32)
    head = jnp.stack([leaf, feat, new_id], axis=1).astype(jnp.int32)
    return jnp.concatenate([head, words], axis=1)


def _route_kernel(tab_ref, bins_ref, ids_ref, out_ref, *, rm: int):
    """One row block: ``out = ids`` with every row of a slot's leaf whose bin
    is not in the slot's set moved to the slot's new id."""
    F, bm = bins_ref.shape
    tab = tab_ref[...]  # (W, 3 + nw)
    W, nw = tab.shape[0], tab.shape[1] - _FIELDS
    leaf, new = tab[:, 0:1], tab[:, 2:3]
    onehot = (lax.broadcasted_iota(jnp.int32, (W, F), 1) == tab[:, 1:2]).astype(jnp.float32)

    def sub(s, carry):
        sl = pl.ds(pl.multiple_of(s * rm, rm), rm)
        block = bins_ref[:, sl].astype(jnp.int32).astype(jnp.float32)  # (F, rm)
        col = lax.dot_general(
            onehot, block, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32,
        ).astype(jnp.int32)  # (W, rm): each slot's split column
        hi = col >> 5
        word = jnp.zeros_like(col)
        for j in range(nw):
            word = jnp.where(hi == j, tab[:, _FIELDS + j : _FIELDS + j + 1], word)
        left = lax.shift_right_logical(word, col & 31) & 1
        ids = ids_ref[:, sl]  # (1, rm)
        moved = jnp.where((ids == leaf) & (left == 0), new, -1)
        dest = jnp.max(moved, axis=0, keepdims=True)
        out_ref[:, sl] = jnp.where(dest >= 0, dest, ids)
        return carry

    lax.fori_loop(0, bm // rm, sub, 0)


@functools.partial(jax.jit, static_argnames=("bm", "rm", "interpret"))
def _pallas_route(bins_t, leaf_ids, table, bm: int, rm: int, interpret: bool):
    """``(n,)`` leaf ids after the slots of ``table``, over the whole
    ``(F, n)`` uint8 ``bins_t``; a ragged last block's writes past ``n``
    are dropped."""
    F, n = bins_t.shape
    out = pl.pallas_call(
        functools.partial(_route_kernel, rm=rm),
        grid=(pl.cdiv(n, bm),),
        in_specs=[
            pl.BlockSpec(table.shape, lambda i: (0, 0)),
            pl.BlockSpec((F, bm), lambda i: (0, i)),
            pl.BlockSpec((1, bm), lambda i: (0, i)),
        ],
        out_specs=pl.BlockSpec((1, bm), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, n), jnp.int32),
        input_output_aliases={2: 0},  # the leaf ids, updated in place
        interpret=interpret,
    )(table, bins_t, leaf_ids.reshape(1, n))
    return out.reshape(n)


def route_rows(bins_t, leaf_ids, table, bm: int = BM, rm: int = RM):
    """``(n,)`` int32 leaf ids after one pass's splits (:func:`slot_table`),
    read off the ``(F, n)`` uint8 matrix ``bins_t`` (the byte tier: wider
    bins are not exact in the body's product), its blocks where they lie.
    Inside a program the ids are updated in place: the kernel's output is
    its input buffer."""
    bm = min(bm, bins_t.shape[1])  # a block as long as the arrays, or a ragged last block
    rm = rm if bm % rm == 0 else bm
    return _pallas_route(bins_t, leaf_ids.astype(jnp.int32), table, bm, rm, jax.default_backend() == "cpu")
