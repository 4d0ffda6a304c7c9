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
