"""Set-up of a training cell: rows from the seed on the device, binned by the
program's device binner into the resident uint8 cache.

Raw float32 chunks live only inside one jitted step each; nothing raw stays
on the device once the cache is built.
"""

import importlib
import time

import numpy as np


def seed_key(seed: int):
    """A PRNG key from a seed of up to 63 bits (the driver's pass 2**31)."""
    import jax

    return jax.random.fold_in(jax.random.key(seed & 0x7FFFFFFF), seed >> 31)


def data_module(cfg: dict):
    return importlib.import_module(f"benchmark.data.{cfg['data']}")


def chunk_plan(cfg: dict):
    rows, chunk = int(cfg["rows"]), int(cfg["chunk_rows"])
    if rows % chunk:
        raise ValueError(f"rows {rows} is not a whole number of {chunk}-row chunks")
    return rows // chunk, chunk


def holdout_chunks(cfg: dict) -> int:
    rows, chunk = int(cfg.get("holdout_rows", 0)), int(cfg["chunk_rows"])
    if rows % chunk:
        raise ValueError(f"holdout_rows {rows} is not a whole number of {chunk}-row chunks")
    return rows // chunk


def fit_authority(cfg: dict, data, key):
    """Bin edges from the first ``bin_sample_rows`` generated rows, on the host
    (LightGBM fits its edges from a sample of that size too)."""
    import jax

    from mmlspark_tpu.ops.binning import BinningAuthority

    sample = int(cfg["bin_sample_rows"])
    X, _ = jax.jit(data.chunk, static_argnums=2)(key, 0, int(cfg["chunk_rows"]))
    return BinningAuthority.fit(
        np.asarray(X[:sample], np.float64),
        max_bin=int(cfg["max_bin"]),
        categorical_features=tuple(data.CATEGORICAL),
        seed=0,
    )


def build(cfg: dict, seed: int):
    """``(StreamedDataset, holdout, timings)`` for the configuration at
    ``seed``.  The holdout is the ``holdout_rows`` that follow the training
    rows in the seed's stream, binned the same way and kept on the device as
    ``{"bins": uint8 (rows, F), "label": float32 (rows,)}``; ``None`` where the
    configuration holds none out."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    from mmlspark_tpu.data.streaming import StreamedDataset
    from mmlspark_tpu.ops.device_binning import bin_rows_device

    data = data_module(cfg)
    key = seed_key(seed)
    n_chunks, chunk = chunk_plan(cfg)
    h_chunks = holdout_chunks(cfg)
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

            bins, o = bin_occ_rows(
                arrays, X, missing_bin=binner.missing_bin,
                n_bounds=binner.n_bounds, num_bins=B,
            )
        else:
            bins = bin_rows_device(
                arrays, X, missing_bin=binner.missing_bin, n_bounds=binner.n_bounds
            ).astype(jnp.uint8)
            o = jnp.zeros((F, B), jnp.int32).at[
                jnp.arange(F)[None, :], bins.astype(jnp.int32)
            ].add(1)
        return bins, o, y

    # one chunk into its place: the only step that knows a buffer's length
    place = jax.jit(lambda buf, bins, at: lax.dynamic_update_slice(buf, bins, (at * chunk, 0)), donate_argnums=0)

    def fill(first, count):
        buf = jnp.zeros((count * chunk, F), jnp.uint8)
        occ = jnp.zeros((F, B), jnp.int32)
        labels = []
        for i in range(count):
            bins, o, y = make(binner.arrays, key, jnp.int32(first + i))
            buf, occ = place(buf, bins, jnp.int32(i)), occ + o
            labels.append(y)
        return buf, occ, labels

    buf, occ, labels = fill(0, n_chunks)
    label = np.concatenate([np.asarray(y) for y in labels])
    del labels
    buf.block_until_ready()
    ds = StreamedDataset(
        authority=authority, binned_dev=buf, packed=False,
        num_rows=n_chunks * chunk, num_features=F, label=label,
        occupancy=np.asarray(occ, np.int64),
    )
    holdout = None
    if h_chunks:
        hbuf, _, labels = fill(n_chunks, h_chunks)
        holdout = {"bins": hbuf, "label": jnp.concatenate(labels)}
        jax.block_until_ready(holdout)
    return ds, holdout, {"bin_fit_s": t_fit, "generate_bin_s": time.perf_counter() - t0 - t_fit}


def train_params(cfg: dict, iterations: int) -> dict:
    data = data_module(cfg)
    p = dict(cfg["params"])
    p["num_iterations"] = int(iterations)
    p["max_bin"] = int(cfg["max_bin"])
    p["categorical_feature"] = tuple(data.CATEGORICAL)
    return p
