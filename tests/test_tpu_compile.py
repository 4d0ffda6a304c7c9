"""The chip's own compiler on the histogram kernels of the benchmark's cells,
at the cells' shapes, with no chip: libtpu compiles for a v5e that is described
and not attached (Mosaic's tiling and VMEM limits, which interpret mode does
not see).  Nothing runs, so nothing here says a result or a time.

All of it lives in this one file and behind fixtures: the process that
describes the topology holds libtpu until it exits, so no module may do it at
import, and under ``pytest -n`` only the worker given this file does it.
"""

import os

import jax
import jax.numpy as jnp
import pytest

CHUNK = 2_097_152  # rows of one histogram chunk in the Criteo cells (1,048,576 in the ranking cell)
CHUNKS = 3  # the matrix handed to a call is several chunks long: the call reads one of them in place
W, B = 8, 256  # leaf slots a pass (split_batch 8) and bins


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:1x1", chips_per_host_bounds=(1, 1, 1)
        )
    except Exception as e:  # no libtpu here, or another process holds it
        pytest.skip(f"no v5e topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


# A deviceless compile is written to the persistent cache and cannot be read
# back without a chip: the next run warns and compiles again, which is all
# this asks of it.  (Turning the cache off around the compiles means a
# ``reset_cache()``, after which later tests of the same worker pay the
# cache's set-up inside what they measure: tests/test_streaming.py's host
# memory test then fails.)
@pytest.mark.filterwarnings("ignore:Error reading persistent compilation cache entry")
@pytest.mark.parametrize(
    "kernel, vals_dtype, bins_dtype, cols, bf, chunk, out_dtype",
    [
        # criteo_quant_train_1chip: every pass's bucket build, int16 row values into an int32
        # (48, 4992) accumulator in the factorized body (W = 8: M = 3*W*2), a block as tall as the 39 columns
        pytest.param("_pallas_hist_by_leaf_nibble", jnp.int16, jnp.uint8, 39, 39, CHUNK, "s32", id="bucket-build-nibble-int16"),
        # the plain body's bucket build, which wider windows (W = 32) and <= 128 bins still take
        pytest.param("_pallas_hist_by_leaf", jnp.int16, jnp.uint8, 39, 39, CHUNK, "s32", id="bucket-build-int16"),
        # istella_rank_train_1chip: 220 columns under 32-tall blocks, the seventh ragged
        pytest.param("_pallas_hist_by_leaf_nibble", jnp.float32, jnp.uint8, 220, 32, CHUNK // 2, "f32", id="float-nibble-ragged"),
        # the quantized cell's float32 refinement: one composed winner column of int32, a block one row tall
        pytest.param("_pallas_hist_by_leaf_nibble", jnp.float32, jnp.int32, 1, 1, CHUNK, "f32", id="refine-column"),
        # the float Criteo cells' pass: 39 columns, one block
        pytest.param("_pallas_hist_by_leaf_nibble", jnp.float32, jnp.uint8, 39, 39, CHUNK, "f32", id="float-nibble"),
    ],
)
def test_histogram_kernel_compiles_for_the_chip(one_chip, kernel, vals_dtype, bins_dtype, cols, bf, chunk, out_dtype):
    """The call as the chunk loop makes it: the whole matrix of several chunks,
    the chunk's index a scalar, the blocks the wrappers choose at these
    widths.  Mosaic takes each, and XLA puts nothing between the resident
    arrays and the kernel: no ``pad``, ``copy`` or ``dynamic-slice`` that
    produces an array of the matrix's dtype."""
    import re

    from mmlspark_tpu.ops import pallas_hist

    struct = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)  # noqa: E731
    n = CHUNKS * chunk
    compiled = getattr(pallas_hist, kernel).lower(
        struct((cols, n), bins_dtype), struct((3, n), vals_dtype), struct((1, n), jnp.int32), struct((1,), jnp.int32),
        num_leaves=W, num_bins=B, bm=16384, bf=bf, rm=1024, chunk=chunk, interpret=False, precision="default",
    ).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert f"{out_dtype}[3,{W},{cols},{B}]" in text  # (3, W, F, B) in the accumulator the values ask for
    moved = "u8" if bins_dtype == jnp.uint8 else r"s32\[1,"
    made = re.findall(rf"= ({moved}\S*) (pad|copy|dynamic-slice)\(", text)
    assert not made, made


@pytest.mark.filterwarnings("ignore:Error reading persistent compilation cache entry")
def test_compaction_kernel_compiles_for_the_chip(one_chip):
    """``criteo_goss_train_1chip``'s compaction: 132,120,576 rows of 39 binned
    columns and three row values (gradient, hessian, weight) into a
    39,845,888-row buffer, 129,024 grid steps whose offsets fit the SMEM.
    Mosaic takes the byte-plane step (bit casts, shifts, the lane scan's rolls)
    and XLA adds no sort, gather or scatter around the kernel."""
    from mmlspark_tpu.ops.pallas_compact import _pallas_compact

    struct = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)  # noqa: E731
    n = 63 * CHUNK
    text = _pallas_compact.lower(
        struct((39, n), jnp.uint8), struct((3, n), jnp.float32), rows=19 * CHUNK, interpret=False
    ).compile().as_text()
    assert "tpu_custom_call" in text
    assert not [op for op in ("sort(", "gather(", "scatter(") if op in text]


@pytest.mark.filterwarnings("ignore:Error reading persistent compilation cache entry")
@pytest.mark.parametrize("K", [100, 5])
def test_class_roots_kernel_compiles_for_the_chip(one_chip, K):
    """``expedia_multiclass_train_1chip``'s roots: 100 classes in 13 groups of
    eight on 4,194,304 x 22 rows, one chunk (and five classes, a group that
    runs past K).  Mosaic takes the body, and XLA copies no ``(K, n)``
    gradient, hessian or bag row to feed it: the class slots are read where
    they lie."""
    import re

    from mmlspark_tpu.ops import pallas_hist

    struct = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)  # noqa: E731
    F, n = 22, 2 * CHUNK
    text = pallas_hist._pallas_hist_class_roots.lower(
        struct((F, n), jnp.uint8), struct((K, n), jnp.float32), struct((K, n), jnp.float32),
        struct((1, n), jnp.float32), struct((1,), jnp.int32),
        num_bins=B, bm=16384, bf=F, rm=1024, chunk=n, interpret=False, precision="default",
    ).compile().as_text()
    assert "tpu_custom_call" in text
    assert f"f32[{K},3,{F},{B}]" in text
    made = re.findall(rf"= ((?:f32|u8)\[\d+,{n}\]\S*) (pad|copy|dynamic-slice)\(", text)
    assert not made, made


@pytest.mark.filterwarnings("ignore:Error reading persistent compilation cache entry")
@pytest.mark.parametrize(
    "cols, rows",
    [(39, 12 * CHUNK), (22, 2 * CHUNK), (39, 10 * CHUNK), (128, 2 * CHUNK)],
    ids=["criteo-one-chip", "expedia", "criteo-four-chip-shard", "widest-block"],
)
def test_route_kernel_compiles_for_the_chip(one_chip, cols, rows):
    """The windowed grower's one-pass row route at the cells' shapes: the whole
    resident matrix and the leaf ids (a chip's shard of them in the four-chip
    cell), and at the widest block it takes (``MAX_COLS``, the VMEM its
    double-buffered blocks need at most).  Mosaic takes the body, and XLA puts no gather, scatter or copy of
    the matrix or the ids around it: the ids are updated in place."""
    import functools
    import re

    from mmlspark_tpu.ops import pallas_route

    struct = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)  # noqa: E731
    call = functools.partial(pallas_route._pallas_route, bm=pallas_route.BM, rm=pallas_route.RM, interpret=False)
    text = jax.jit(call, donate_argnums=1).lower(
        struct((cols, rows), jnp.uint8), struct((rows,), jnp.int32), struct((W, 3 + B // 32), jnp.int32),
    ).compile().as_text()
    assert "tpu_custom_call" in text
    assert not [op for op in ("gather(", "scatter(") if op in text]
    shape = re.compile(rf"(u8\[{cols},|s32\[1,|s32\[){rows}\]")
    made = [line for line in text.splitlines() if shape.search(line) and re.search(r" (pad|copy|copy-start|dynamic-slice|transpose)\(", line)]
    assert not made, made


def test_the_wide_fit_holds_no_route_kernel(one_chip, monkeypatch):
    """The grower's program for the chip calls the route kernel at 39 columns
    and not at 220 (the ranking cell's width, which keeps the slot loop)."""
    from mmlspark_tpu.engine.tree import GrowConfig, grow_tree_depthwise

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")  # the kernels as the chip runs them
    struct = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)  # noqa: E731
    n = 16384
    for cols, engaged in ((39, True), (220, False)):
        cfg = GrowConfig(num_bins=B, num_leaves=63, split_batch=W, hist_backend="pallas", hist_precision="default")
        text = jax.jit(lambda *a: grow_tree_depthwise(cfg, *a)).lower(
            struct((n, cols), jnp.uint8), *[struct((n,), jnp.float32)] * 3, struct((cols,), jnp.bool_),
        ).as_text()
        assert "_hist_leaf_nibble_kernel" in text
        assert ("_route_kernel" in text) == engaged
