"""Traffic kind ``train_loop_dp``: ``train_loop`` on one host's chips.  One
client, closed loop, back-to-back data-parallel ``engine.booster.train`` calls
(``tree_learner=data`` in the configuration's ``params``; the engine builds
its mesh over the host's chips) on a resident data set whose binned matrix is
row-sharded ``('data', None)``, each followed by the new model's scores and
log loss on the resident, row-sharded holdout.

The configuration gives the rows A CHIP holds (``rows``, ``holdout_rows``:
what one chip's peak is the yardstick of) and ``chips``.  The stream's chunks
are dealt as a row sharding of the concatenated matrix deals them: chip ``c``
holds training chunks ``c*T .. c*T+T-1`` and holdout chunks
``D*T + c*H .. D*T + c*H+H-1`` (``T``, ``H`` chunks a chip, ``D`` chips), each
made and binned on the chip that keeps it.  The rate counts the host's rows,
and the reference is handed a copy of the configuration with the host's
counts: it walks every chunk of the stream on its own and knows no shard.
"""

import time

import numpy as np

from benchmark import dataset, reference  # noqa: F401  (prove.py reads traffic.reference)
from benchmark.traffic import train_loop
from benchmark.traffic.train_loop import _evaluate, _fit_and_evaluate, _train, check, free, window  # noqa: F401

AXIS = "data"


def global_cfg(cfg: dict) -> dict:
    """The configuration with the host's row counts in place of a chip's."""
    chips = int(cfg["chips"])
    return {**cfg, "rows": int(cfg["rows"]) * chips, "holdout_rows": int(cfg.get("holdout_rows", 0)) * chips}


def host_mesh(chips: int):
    """The mesh ``train()`` builds for ``tree_learner=data``: every device of
    the host on one ``data`` axis.  The data set is placed on the same one."""
    import jax

    from mmlspark_tpu.parallel.mesh import default_mesh

    if len(jax.devices()) != chips:
        raise SystemExit(f"the configuration is laid out over {chips} chips; JAX found {len(jax.devices())}")
    return default_mesh()


def build(cfg: dict, seed: int):
    """``(StreamedDataset, holdout, timings)``, sharded over the host's chips
    with no host round trip of a binned byte: one sharded step makes and bins
    one chunk on every chip at a time."""
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    from mmlspark_tpu.data.streaming import StreamedDataset
    from mmlspark_tpu.ops.device_binning import bin_rows_device

    data = dataset.data_module(cfg)
    key = dataset.seed_key(seed)
    D = int(cfg["chips"])
    mesh = host_mesh(D)
    per_chip, chunk = dataset.chunk_plan(cfg)
    h_per_chip = dataset.holdout_chunks(cfg)
    F = data.NUM_FEATURES
    t0 = time.perf_counter()
    authority = dataset.fit_authority(cfg, data, key)
    t_fit = time.perf_counter() - t0
    binner = authority.device_binner()
    B = int(authority.num_bins)
    on_tpu = jax.default_backend() == "tpu"

    # dataset.build's ingest step, one chunk a chip: the chunk's index comes
    # from the shard's position (key, index and stride are arguments: one
    # program for every seed, for the training rows and for the holdout)
    def make_local(arrays, key, first, stride, i):
        index = first + lax.axis_index(AXIS) * stride + i
        X, y = data.chunk(key, index, chunk)
        if on_tpu:
            from mmlspark_tpu.ops.pallas_binhist import bin_occ_rows

            bins, o = bin_occ_rows(arrays, X, missing_bin=binner.missing_bin, n_bounds=binner.n_bounds, num_bins=B)
        else:
            bins = bin_rows_device(arrays, X, missing_bin=binner.missing_bin, n_bounds=binner.n_bounds).astype(jnp.uint8)
            o = jnp.zeros((F, B), jnp.int32).at[jnp.arange(F)[None, :], bins.astype(jnp.int32)].add(1)
        return bins, o[None], y

    make = jax.jit(jax.shard_map(
        make_local, mesh=mesh, in_specs=(P(), P(), P(), P(), P()),
        out_specs=(P(AXIS, None), P(AXIS, None, None), P(AXIS)), check_vma=False,
    ))
    # one chunk into its place in each chip's block: the only step that knows a buffer's length
    place = jax.jit(
        jax.shard_map(
            lambda buf, bins, at: lax.dynamic_update_slice(buf, bins, (at * chunk, 0)),
            mesh=mesh, in_specs=(P(AXIS, None), P(AXIS, None), P()), out_specs=P(AXIS, None),
        ),
        donate_argnums=0,
    )
    rows_sh = NamedSharding(mesh, P(AXIS, None))

    def fill(first, count):
        buf = jax.jit(lambda: jnp.zeros((D * count * chunk, F), jnp.uint8), out_shardings=rows_sh)()
        occ = jnp.zeros((D, F, B), jnp.int32)
        labels = []
        for i in range(count):
            bins, o, y = make(binner.arrays, key, jnp.int32(first), jnp.int32(count), jnp.int32(i))
            buf, occ = place(buf, bins, jnp.int32(i)), occ + o
            labels.append(y)
        # step i holds chunk i of every chip: (count, D, chunk) -> the stream's order, on the host
        # (a transpose of the small leading axes on the device is a relayout the compiler takes minutes over)
        label = np.stack([np.asarray(y) for y in labels]).reshape(count, D, chunk).transpose(1, 0, 2).reshape(-1)
        return buf, occ, label

    buf, occ, label = fill(0, per_chip)
    buf.block_until_ready()
    ds = StreamedDataset(
        authority=authority, binned_dev=buf, packed=False,
        num_rows=D * per_chip * chunk, num_features=F, label=label,
        occupancy=np.asarray(occ, np.int64).sum(axis=0),
    )
    holdout = None
    if h_per_chip:
        hbuf, _, hlabel = fill(D * per_chip, h_per_chip)
        holdout = {"bins": hbuf, "label": jax.device_put(hlabel, NamedSharding(mesh, P(AXIS)))}
        jax.block_until_ready(holdout)
    return ds, holdout, {"bin_fit_s": t_fit, "generate_bin_s": time.perf_counter() - t0 - t_fit}


def _require_resident_sharded_fit():
    """A program from before this deployment was supported pads a copy of the
    resident sharded matrix to 40 columns in every fit (a gigabyte a chip
    beside a reservation that leaves none) and counts no executed merge byte:
    it is told at once, before a quarter of an hour's compile, by the ledger
    it lacks."""
    from mmlspark_tpu.parallel import distributed

    if not hasattr(distributed, "collective_ledger"):
        raise SystemExit(
            "this program has no parallel.distributed.collective_ledger: it re-pads a resident sharded "
            "data set in every fit and cannot run the data-parallel deployment"
        )


def setup(cfg, workload, seed, train_fn=_train, eval_fn=_evaluate):
    """The sharded resident data set and holdout, and one warm fit and
    evaluation of the cell's own shapes."""
    _require_resident_sharded_fit()
    ds, holdout, timings = build(cfg, seed)
    params = dataset.train_params(cfg, workload["iterations_per_fit"])
    state = {"ds": ds, "holdout": holdout, "params": params, "train_fn": train_fn, "eval_fn": eval_fn}
    booster, _, timings["warm_fit_s"], timings["warm_eval_s"] = _fit_and_evaluate(state)
    state |= {
        "cfg": global_cfg(cfg), "seed": seed,  # the reference's: the host's counts
        "label_mean": float(np.mean(ds.label)),
        "iterations": int(workload["iterations_per_fit"]),
        "last_fit_s": timings["warm_fit_s"] + timings["warm_eval_s"],
        "limits": dict(workload["limits"]),  # PERF.md section 2
        "resolved": {
            "devices": int(cfg["chips"]),
            **{
                k: getattr(booster.config, k)
                for k in (
                    "tree_learner", "hist_merge", "hist_backend", "split_batch", "hist_precision", "hist_chunk",
                    "hist_quantize", "grow_policy", "predict_backend",
                )
            },
        },
    }
    return state, timings


# ---- planted faults: train_loop's four, and a shard lost ------------------
def fault_shard_lost(params, ds):
    """The last chip's shard lost: a copy of the first chip's rows and labels
    stands in its place before the fit (same shapes, same program), so a
    quarter of every histogram counts the wrong rows."""
    import jax

    from mmlspark_tpu.data.streaming import StreamedDataset

    arr = ds._binned_dev
    shards = sorted(arr.addressable_shards, key=lambda s: s.index[0].start or 0)
    parts = [s.data for s in shards]
    parts[-1] = jax.device_put(parts[0], shards[-1].device)
    ds._binned_dev = jax.make_array_from_single_device_arrays(arr.shape, arr.sharding, parts)  # the data set is spent
    n = ds.num_rows // len(shards)
    lost = StreamedDataset(
        authority=ds.authority, binned_dev=ds._binned_dev, packed=False, num_rows=ds.num_rows,
        num_features=ds.num_features, label=np.concatenate([ds.label[:-n], ds.label[:n]]),
        occupancy=ds._occupancy,
    )
    return _train(params, lost)


# both of the last two spend the data set.  A shard lost leaves [s0, s1, s2, s0];
# half of the batch then copies the first half over the second whatever it
# holds, and takes its labels from the set-up's own: each reads its own fault.
FAULTS = {
    **{k: v for k, v in train_loop.FAULTS.items() if k != "half_batch"},
    "shard_lost": {"train_fn": fault_shard_lost},
    "half_batch": train_loop.FAULTS["half_batch"],
}
