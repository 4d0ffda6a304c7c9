"""Set-up of a ranking cell: rows from the seed on the device, binned by the
program's device binner into the resident uint8 cache, with the query sizes
of each split beside them.

``dataset.py``'s plan takes whole chunks only; a public ranking set's row
counts are what they are (7,325,625 and 3,129,004), so here every chunk is
made at ``chunk_rows`` and the last of a split keeps its first rows.  Raw
float32 chunks live only inside one jitted step each.
"""

import time

import numpy as np

from benchmark.dataset import data_module, fit_authority, seed_key


def split_chunks(rows: int, chunk: int):
    """``[(chunk number, rows kept), ...]`` covering ``rows``."""
    return [(i, min(chunk, rows - i * chunk)) for i in range(-(-rows // chunk))]


def build(cfg: dict, seed: int):
    """``(StreamedDataset with its group sizes, holdout, timings)``.  The
    holdout is the source's test split, binned the same way and kept on the
    device: ``{"bins": uint8 (rows, F), "label": float32 (rows,), "group":
    int64 (queries,) on the host}``."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    from mmlspark_tpu.data.streaming import StreamedDataset
    from mmlspark_tpu.ops.device_binning import bin_rows_device

    data = data_module(cfg)
    key = seed_key(seed)
    chunk = int(cfg["chunk_rows"])
    F = data.NUM_FEATURES
    t0 = time.perf_counter()
    authority = fit_authority(cfg, data, key)
    t_fit = time.perf_counter() - t0
    binner = authority.device_binner()
    B = int(authority.num_bins)
    on_tpu = jax.default_backend() == "tpu"

    # the ingest step of data/streaming.stream_ingest, fed from the device
    # (the key is an argument: as a closure constant it would make a new
    # program, and a compile, of every seed)
    @jax.jit
    def make(arrays, key, index):
        X, y = data.chunk(key, index, chunk)
        if on_tpu:
            from mmlspark_tpu.ops.pallas_binhist import bin_occ_rows

            bins, o = bin_occ_rows(arrays, X, missing_bin=binner.missing_bin, n_bounds=binner.n_bounds, num_bins=B)
        else:
            bins = bin_rows_device(arrays, X, missing_bin=binner.missing_bin, n_bounds=binner.n_bounds).astype(jnp.uint8)
            o = tally(bins)
        return bins, o, y

    place = jax.jit(lambda buf, bins, at: lax.dynamic_update_slice(buf, bins, (at, 0)), donate_argnums=0)
    tally = jax.jit(lambda bins: jnp.zeros((F, B), jnp.int32).at[jnp.arange(F)[None, :], bins.astype(jnp.int32)].add(1))

    def fill(first, rows):
        """One split: its binned rows, their occupancy and labels; the last
        chunk's rows past ``rows`` are not the set's."""
        buf = jnp.zeros((rows, F), jnp.uint8)
        occ = jnp.zeros((F, B), jnp.int32)
        labels = []
        for i, keep in split_chunks(rows, chunk):
            bins, o, y = make(binner.arrays, key, jnp.int32(first + i))
            buf = place(buf, bins[:keep], jnp.int32(i * chunk))
            occ = occ + o - (tally(bins[keep:]) if keep < chunk else 0)
            labels.append(y[:keep])
        return buf, occ, labels

    buf, occ, labels = fill(0, int(cfg["rows"]))
    label = np.concatenate([np.asarray(y) for y in labels])
    del labels
    buf.block_until_ready()
    ds = StreamedDataset(
        authority=authority, binned_dev=buf, packed=False, num_rows=int(cfg["rows"]), num_features=F, label=label,
        group=data.query_sizes(seed, int(cfg["queries"]), int(cfg["rows"]), split=0),
        occupancy=np.asarray(occ, np.int64),
    )
    hbuf, _, labels = fill(data.HOLDOUT_FIRST_CHUNK, int(cfg["holdout_rows"]))
    holdout = {
        "bins": hbuf, "label": jnp.concatenate(labels),
        "group": data.query_sizes(seed, int(cfg["holdout_queries"]), int(cfg["holdout_rows"]), split=1),
    }
    jax.block_until_ready((holdout["bins"], holdout["label"]))
    return ds, holdout, {"bin_fit_s": t_fit, "generate_bin_s": time.perf_counter() - t0 - t_fit}


def train_params(cfg: dict, iterations: int) -> dict:
    p = dict(cfg["params"])
    p["num_iterations"] = int(iterations)
    p["max_bin"] = int(cfg["max_bin"])
    p["categorical_feature"] = ()
    return p
