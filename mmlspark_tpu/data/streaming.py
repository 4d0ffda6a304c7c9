"""Streamed out-of-core training: sketch-fit edges, device-side binning.

The full pipeline behind :func:`train_streaming` (ROADMAP item 2):

1. **Sketch pass** (host, chunked): stream chunks off the mmap'd shards
   (:mod:`mmlspark_tpu.data.loader`) and fold each into a mergeable
   :class:`~mmlspark_tpu.data.sketch.DatasetSketch` — no full-dataset
   pass, no full-dataset residency.
2. **Merge** (control plane): serialize the per-process sketch, gather
   bit-exact f64 blobs via the sanctioned
   :func:`~mmlspark_tpu.parallel.distributed.host_allgather_blobs`
   collective, fold in process order, and derive global bin edges → one
   :class:`~mmlspark_tpu.ops.binning.BinningAuthority` shared by every
   rank.
3. **Ingest pass** (3-stage pipeline, fused): a real decode → upload →
   device-step pipeline.  Stage 1 (decode thread) reads chunk *t+2*
   off the mmap'd shards; stage 2 (upload thread) ``jax.device_put``\ s
   chunk *t+1*; the consumer dispatches chunk *t*'s single fused device
   step — binning through the authority's double-single boundary table
   (``ops/device_binning.py``; on TPU the fused Pallas bin+occupancy
   kernel, ``ops/pallas_binhist.py``, so binned rows never round-trip
   HBM before the tally) and the donated ``dynamic_update_slice`` into
   the preallocated cache (O(1) extra memory per chunk).  Each stage
   has its own bounded queue (depth ``MMLSPARK_TPU_INGEST_DEPTH``,
   default 2) and the consumer never syncs on the chunk it just
   dispatched — completed steps are collected a bounded number of
   chunks later, so decode, upload, and device work genuinely overlap
   (``StreamedDataset.ingest_stats`` records the achieved
   ``overlap_ratio`` and ``max_in_flight``).  On the XLA path the exact
   occupancy tally and quality-sample slice move OFF the device step
   onto the collector (a vectorized host ``bincount`` over the binned
   uint8 chunk — cheaper than an on-device scatter-add on hosts, and
   overlapped with later chunks' device work); the Pallas path keeps
   the fused in-VMEM tally.  Both produce bitwise-identical caches,
   occupancy, and samples.  The cache is nibble-packed
   two-rows-per-byte when ``num_bins ≤ 16`` and rides 1-byte indices
   through 256 bins (``ops/binpack.py``).
4. **Train**: the resulting :class:`StreamedDataset` drops into the
   stock ``engine/booster.py`` trainer — ``binned()`` hands back the
   device-resident cache, so ``_train_impl`` skips host binning and goes
   straight to padding/sharding.

Host residency: O(chunk) for features (the only O(n) host arrays are the
label/weight vectors — 8 bytes/row — and the capped quality sample).
Current scope: single-controller (any local mesh size); with multiple
processes the sketch/merge phases are already collective-correct, but
the ingest pass assembles a process-local device cache, which
``process_local`` training consumes partition-wise.

obs: the whole fit rides a ``train.binning`` span with
``train.binning.sketch`` / ``train.binning.merge`` /
``train.binning.device_bin`` children; inside the ingest pass each
stage is spanned — ``ingest.decode`` (stage-1 shard read),
``ingest.upload`` (stage-2 device transfer), ``ingest.bin``
(consumer fused-step dispatch), ``ingest.collect`` (bounded-lag
occupancy/sample collection), ``ingest.drain`` (final await) — plus
the loader counters: ``ingest.buffer_stall_ns`` = the upload stage
waiting on decode (disk/convert-bound), ``ingest.pipeline_stall_ns``
= the consumer waiting on upload (transfer-bound), and the
``ingest.overlap_ratio`` gauge — ``python -m tools.obs report`` shows
the breakdown.
"""

from __future__ import annotations

import collections
import math
import time
from typing import Optional, Sequence, Tuple

import numpy as np

from mmlspark_tpu import obs
from mmlspark_tpu.data.loader import (
    ChunkPrefetcher,
    chunk_stream,
    default_ingest_depth,
)
from mmlspark_tpu.data.sketch import (
    DEFAULT_COMPACTOR_CAP,
    DEFAULT_EXACT_BUDGET,
    DatasetSketch,
    merge_sketch_states,
)
from mmlspark_tpu.ops.binning import BinningAuthority

DEFAULT_CHUNK_ROWS = 65536


def process_shard_source(
    paths: Sequence[str],
    label_paths: Optional[Sequence[str]] = None,
    *,
    process_count: Optional[int] = None,
    process_index: Optional[int] = None,
):
    """This process's deterministic partition of a global ``data/`` shard
    list, as an :class:`~mmlspark_tpu.data.loader.NpySource` (ISSUE 14).

    Every process passes the SAME global path list; ownership is a pure
    function of the sorted list and the current process count
    (``parallel.elastic.assign_shards`` round-robin), so a run resumed
    over fewer survivors re-partitions the dead host's shards with no
    coordination — re-form the mesh (``parallel.mesh.mesh2d``) over the
    survivors, call this again, and train with the checkpoint as
    ``init_model``.  The sketch/merge phases then see every row exactly
    once regardless of the process count.

    The returned source carries ``shard_paths`` — the full per-process
    assignment (list per process) — which the trainer's checkpoint
    writer records in the rank-0 shard manifest.
    """
    import jax

    from mmlspark_tpu.data.loader import NpySource
    from mmlspark_tpu.parallel.elastic import assign_shards

    nproc = process_count if process_count is not None else jax.process_count()
    pidx = process_index if process_index is not None else jax.process_index()
    order = np.argsort(np.asarray([str(p) for p in paths]))
    paths = [paths[i] for i in order]
    if label_paths is not None:
        if len(label_paths) != len(paths):
            raise ValueError("label_paths must pair 1:1 with shard paths")
        label_paths = [label_paths[i] for i in order]
    groups = assign_shards(paths, nproc)
    mine = groups[pidx]
    if not mine:
        raise ValueError(
            f"process {pidx} of {nproc} owns no shards ({len(paths)} total); "
            "write at least one shard per process"
        )
    own_labels = (
        None if label_paths is None
        else assign_shards(label_paths, nproc)[pidx]
    )
    src = NpySource(mine, own_labels)
    src.shard_paths = groups
    return src


def stream_fit_binning(
    source,
    max_bin: int = 255,
    categorical_features: Sequence[int] = (),
    chunk_rows: int = DEFAULT_CHUNK_ROWS,
    min_data_in_bin: int = 3,
    exact_budget: int = DEFAULT_EXACT_BUDGET,
    compactor_cap: int = DEFAULT_COMPACTOR_CAP,
) -> Tuple[BinningAuthority, DatasetSketch]:
    """Chunked sketch pass + cross-process merge → global bin edges.

    Returns ``(authority, merged_sketch)`` — the sketch is returned so
    callers can read ``rank_epsilon`` / ``is_exact`` (the declared
    accuracy of the derived edges).  Every process must call this
    collectively (it ends in an allgather); all processes return
    identical edges.
    """
    import jax

    sk = DatasetSketch(
        source.num_features, max_bin=max_bin,
        categorical_features=categorical_features,
        min_data_in_bin=min_data_in_bin, exact_budget=exact_budget,
        compactor_cap=compactor_cap,
    )
    with obs.span("train.binning.sketch", features=source.num_features):
        # prefetch thread overlaps shard I/O with sketch folding
        for chunk in ChunkPrefetcher(chunk_stream(source, chunk_rows)):
            sk.update(chunk.X)
    with obs.span("train.binning.merge", processes=jax.process_count()):
        from mmlspark_tpu.parallel.distributed import host_allgather_blobs

        if jax.process_count() > 1:
            merged = merge_sketch_states(host_allgather_blobs(sk.to_state()))
        else:
            merged = sk
        authority = BinningAuthority.from_sketch(merged)
    return authority, merged


class StreamedDataset:
    """A :class:`~mmlspark_tpu.engine.booster.Dataset` stand-in whose
    binned matrix lives ON DEVICE (assembled chunk-by-chunk by
    :func:`stream_ingest`) and whose raw ``X`` never existed host-resident.

    Duck-typed against the trainer's Dataset surface: ``binned()`` /
    ``fitted_mapper()`` / ``label`` / ``num_rows`` / the cache dicts —
    plus ``quality_feature_specs`` / ``quality_binned_sample``, the
    streamed substitutes the quality-baseline capture uses instead of
    materializing the full binned matrix on host.

    **Held on the device**: the binned matrix for the data set's whole
    life, and between fits, one entry each, what ``Dataset`` holds: the
    padded copy of the matrix where the rows are not whole histogram
    chunks (``_dev_bins_cache``), a ranking fit's query plan
    (``_rank_plan_cache``) and the fit's per-row state
    (``_row_state_cache``: labels, weights, row mask, init scores), so a
    second ``train()`` on this set under the same objective settings and
    placement computes and sends nothing that follows the rows.  An entry
    is replaced when a fit's key differs and freed with the data set (which
    cannot be pickled at all).  As with ``Dataset``, the row state is keyed
    by the identity of ``label`` / ``weight`` / ``init_score``, and storing
    it makes those arrays read-only: assigning a new array is seen, a write
    into one raises ``ValueError``, and only a write through another view
    of the same memory goes unseen.
    """

    def __init__(
        self,
        *,
        authority: BinningAuthority,
        binned_dev,
        packed: bool,
        num_rows: int,
        num_features: int,
        label: Optional[np.ndarray] = None,
        weight: Optional[np.ndarray] = None,
        group: Optional[np.ndarray] = None,
        occupancy: Optional[np.ndarray] = None,
        sample: Optional[np.ndarray] = None,
        ingest_stats: Optional[dict] = None,
    ):
        self.authority = authority
        self._binned_dev = binned_dev
        self._packed = bool(packed)
        self.num_rows = int(num_rows)
        self.num_features = int(num_features)
        self.X = None  # the whole point: raw features never fully on host
        self.label = None if label is None else np.asarray(label, np.float64)
        self.weight = None if weight is None else np.asarray(weight, np.float64)
        self.group = None if group is None else np.asarray(group, np.int64)
        self.init_score = None
        self._occupancy = occupancy  # (F, B) int64 exact bin occupancy
        self._sample = sample        # (≤cap, F) uint8 host quality sample
        # pipeline telemetry from stream_ingest: depth, max_in_flight,
        # per-stage seconds, overlap_ratio (see its docstring)
        self.ingest_stats = dict(ingest_stats) if ingest_stats else {}
        # trainer-facing caches (same contract as Dataset's)
        self._mapper_cache = {}
        self._bins_cache = {}
        self._dev_bins_cache = {}
        self._rank_plan_cache = {}
        self._row_state_cache = {}
        self._cache_refs = []

    @property
    def packed(self) -> bool:
        """True when the device cache is nibble-packed (2 rows/byte)."""
        return self._packed

    @property
    def binned_cache_nbytes(self) -> int:
        return int(self._binned_dev.nbytes)

    def __getstate__(self):
        raise TypeError(
            "StreamedDataset holds a device-resident cache and cannot be "
            "pickled; persist the shard source path + BinningAuthority "
            "and re-ingest instead"
        )

    def fitted_mapper(self, cfg):
        """The edges are FIXED by the stream fit; a config asking for
        different binning cannot be honored post-ingest."""
        bm = self.authority.mapper
        if (int(cfg.max_bin) != int(bm.max_bin)
                or tuple(cfg.categorical_feature)
                != tuple(bm.categorical_features)):
            raise ValueError(
                "StreamedDataset was ingested with max_bin="
                f"{bm.max_bin}, categorical={tuple(bm.categorical_features)}; "
                f"training asked for max_bin={cfg.max_bin}, categorical="
                f"{tuple(cfg.categorical_feature)} — re-run stream_fit_"
                "binning/stream_ingest with the new binning config"
            )
        return bm

    def binned(self, bin_mapper):
        """The device-resident binned matrix (unpacked view).  Cached per
        mapper id like ``Dataset.binned`` — the unpack of a packed cache
        happens once per mapper, on device."""
        if bin_mapper is not self.authority.mapper and (
            int(bin_mapper.num_bins) != int(self.authority.num_bins)
        ):
            raise ValueError(
                "StreamedDataset is bound to its ingest-time bin edges; "
                "got a mapper with a different bin count"
            )
        key = id(bin_mapper)
        bins = self._bins_cache.get(key)
        if bins is None:
            if self._packed:
                import jax

                from mmlspark_tpu.ops.binpack import unpack_rows

                bins = jax.jit(
                    unpack_rows, static_argnums=1
                )(self._binned_dev, self.num_rows)
            else:
                bins = self._binned_dev
            self._bins_cache = {key: bins}
            self._dev_bins_cache = {}
            self._cache_refs = [bin_mapper]
        return bins

    # -- quality-baseline hooks (no full host materialization) ---------
    def quality_feature_specs(self, bin_mapper):
        """Per-feature occupancy specs from the EXACT per-chunk device
        tallies accumulated during ingest — the streamed substitute for
        ``quality.feature_specs_from_binned`` over a host matrix."""
        if self._occupancy is None:
            return None
        occ = np.asarray(self._occupancy)
        missing_bin = int(bin_mapper.missing_bin)
        specs = []
        for f in range(self.num_features):
            counts_full = occ[f]
            if bin_mapper.is_categorical(f):
                cats = np.asarray(
                    bin_mapper.cat_maps.get(f, np.empty(0, np.int64)),
                    np.int64,
                )
                nv = len(cats)
                spec = {"kind": "cat", "cats": cats.tolist()}
            else:
                edges = np.asarray(bin_mapper.upper_bounds[f], np.float64)
                nv = len(edges)
                spec = {"kind": "num", "edges": edges.tolist()}
            counts = np.concatenate(
                [counts_full[:nv], [counts_full[missing_bin]]]
            )
            spec["counts"] = counts.astype(float).tolist()
            specs.append(spec)
        return specs

    def quality_binned_sample(self, cap: int) -> Optional[np.ndarray]:
        """Capped binned row sample collected during ingest (host uint8)."""
        if self._sample is None or not len(self._sample):
            return None
        return self._sample[:cap]


def stream_ingest(
    source,
    authority: BinningAuthority,
    chunk_rows: int = DEFAULT_CHUNK_ROWS,
    pack: str = "auto",
    quality_sample_cap: int = 4096,
    seed: int = 0,
    fuse: str = "auto",
    depth: Optional[int] = None,
    overlap: bool = True,
) -> StreamedDataset:
    """3-stage pipelined raw-f32 ingest into a persistent device cache —
    ONE fused device step per chunk, three chunks in flight.

    Stage 1 (decode thread) reads chunk *t+2* off the mmap'd shards and
    draws its quality-sample indices; stage 2 (upload thread) runs chunk
    *t+1*'s ``jax.device_put`` (the ``ingest.upload`` span); the
    consumer DISPATCHES chunk *t*'s fused program — bin → optional
    nibble pack → donated ``dynamic_update_slice`` into the preallocated
    cache (``ingest.bin`` span) — and never syncs on it: each step's
    results are collected up to ``depth`` chunks later (``ingest.collect``
    span), so decode, upload, and device work genuinely overlap.  Each
    stage queue holds ``depth`` items (``MMLSPARK_TPU_INGEST_DEPTH``,
    default 2); ``ingest.buffer_stall_ns`` counts the upload stage
    starved by decode, ``ingest.pipeline_stall_ns`` the consumer starved
    by upload.  The final ``ingest.drain`` span awaits the tail.
    ``overlap=False`` is the serial comparator (collect + block every
    chunk) — bitwise-identical output, used by parity tests and the
    ingest bench to attribute the overlap win.

    On the XLA path the exact occupancy tally and sample gather ride the
    COLLECTOR, not the device step: the binned uint8 chunk comes back to
    host (bounded lag, overlapped with later device steps) and folds
    into an int64 ``bincount`` — cheaper than the device scatter-add on
    hosts and bitwise-identical.  The Pallas path (TPU) keeps the fused
    in-VMEM tally and on-device sample gather, and its collector is a
    no-op bookkeeper.

    ``pack="auto"`` nibble-packs the cache when ``num_bins ≤ 16``
    (halving its bytes); ``"never"`` forces plain uint8.  At larger bin
    counts the cache rides the byte tier (1 byte/index up to 256 bins —
    ``ops/binpack.py``).

    ``fuse="auto"`` routes the bin body through the fused Pallas kernel
    (:mod:`mmlspark_tpu.ops.pallas_binhist`) on TPU and through the XLA
    body elsewhere; ``"pallas"`` / ``"xla"`` force a path (cpu pallas
    runs interpret mode: tests only).  All paths produce
    bitwise-identical caches, occupancy, and samples.

    The returned dataset's ``ingest_stats`` dict records ``depth``,
    ``max_in_flight`` (peak chunks resident in the pipeline),
    per-stage seconds, and ``overlap_ratio`` — the fraction of the
    smaller of {device-step wall, decode+upload wall} hidden behind the
    other (0 = fully serial, 1 = fully hidden) — also published as the
    ``ingest.overlap_ratio`` gauge.
    """
    import jax
    import jax.numpy as jnp
    from jax import lax

    from mmlspark_tpu.ops.binpack import can_pack, pack_rows
    from mmlspark_tpu.ops.device_binning import bin_rows_device

    if pack not in ("auto", "never"):
        raise ValueError(f"pack must be 'auto' or 'never', got {pack!r}")
    if fuse not in ("auto", "pallas", "xla"):
        raise ValueError(
            f"fuse must be 'auto', 'pallas' or 'xla', got {fuse!r}"
        )
    depth = default_ingest_depth() if depth is None else max(1, int(depth))
    binner = authority.device_binner()
    n, F = int(source.num_rows), int(source.num_features)
    B = int(authority.num_bins)
    do_pack = pack == "auto" and can_pack(B)
    if do_pack and chunk_rows % 2:
        chunk_rows += 1  # row pairs must not straddle chunks

    missing_bin, n_bounds = binner.missing_bin, binner.n_bounds
    use_pallas = fuse == "pallas" or (
        fuse == "auto" and jax.default_backend() == "tpu"
    )

    if use_pallas:
        from mmlspark_tpu.ops.pallas_binhist import bin_occ_rows

        def _step_fused(buf, counts, arrays, rows, start):
            binned_u8, occ = bin_occ_rows(
                arrays, rows, missing_bin=missing_bin,
                n_bounds=n_bounds, num_bins=B,
            )
            cache = pack_rows(binned_u8) if do_pack else binned_u8
            return (
                lax.dynamic_update_slice(buf, cache, (start, 0)),
                counts + occ,
            )

        def _step_fused_sampled(buf, counts, arrays, rows, start, sample_idx):
            binned_u8, occ = bin_occ_rows(
                arrays, rows, missing_bin=missing_bin,
                n_bounds=n_bounds, num_bins=B,
            )
            samp = jnp.take(binned_u8, sample_idx, axis=0)
            cache = pack_rows(binned_u8) if do_pack else binned_u8
            return (
                lax.dynamic_update_slice(buf, cache, (start, 0)),
                counts + occ, samp,
            )

        # donated cache + occupancy: rewritten in place chunk by chunk
        # (O(1) extra device memory per step on backends with donation)
        step_fused = jax.jit(_step_fused, donate_argnums=(0, 1))
        step_fused_sampled = jax.jit(_step_fused_sampled, donate_argnums=(0, 1))
    else:

        def _step_xla(buf, arrays, rows, start):
            binned = bin_rows_device(
                arrays, rows, missing_bin=missing_bin, n_bounds=n_bounds
            )
            binned_u8 = binned.astype(jnp.uint8)
            cache = pack_rows(binned_u8) if do_pack else binned_u8
            return lax.dynamic_update_slice(buf, cache, (start, 0)), binned_u8

        # donated cache rewritten in place; the binned chunk is a fresh
        # output the collector folds into host occupancy/sample
        step_xla = jax.jit(_step_xla, donate_argnums=(0,))

    buf_rows = (n + 1) // 2 if do_pack else n
    buf = jnp.zeros((buf_rows, F), jnp.uint8)
    occupancy_dev = jnp.zeros((F, B), jnp.int32) if use_pallas else None
    occ_host = None if use_pallas else np.zeros((F, B), np.int64)
    label = None
    sample_parts = []  # host arrays (XLA) / device arrays (Pallas)
    sample_per_chunk = (
        0 if quality_sample_cap <= 0 or n == 0
        else max(1, math.ceil(quality_sample_cap * chunk_rows / n))
    )

    # Per-stage wall accounting: each key is written by exactly one
    # thread (decode_s: stage-1, upload_s: stage-2, step_s: consumer).
    walls = {"decode_s": 0.0, "upload_s": 0.0, "step_s": 0.0}

    def _decoded_chunks():
        # stage-1 thread: shard read/convert (the chunk_stream pull IS
        # the decode work — mmap slice + dtype convert + stitch)
        it = chunk_stream(source, chunk_rows)
        while True:
            t0 = time.perf_counter()
            with obs.span("ingest.decode"):
                c = next(it, None)
            if c is None:
                return
            walls["decode_s"] += time.perf_counter() - t0
            yield c

    def _draw_sample_idx(c):
        # still stage-1: the per-chunk sample draw is host work that
        # must not ride the consumer's dispatch loop
        if not sample_per_chunk:
            return (c, None)
        rng = np.random.default_rng([seed, 7, c.index])
        k = min(sample_per_chunk, len(c.X))
        return (c, np.sort(rng.choice(len(c.X), k, replace=False)))

    def _upload(item):
        # stage-2 thread: chunk t+1 transfers while chunk t executes its
        # fused step.  The block makes the span honest device-transfer
        # time (and never blocks the consumer).  The host X reference is
        # DROPPED here (X=None) so queued uploads hold only the device
        # copy — host residency stays O(depth) chunk buffers, not
        # O(2·depth).
        c, idx = item
        t0 = time.perf_counter()
        with obs.span("ingest.upload", rows=len(c.X), bytes=int(c.X.nbytes)):
            dev = jax.device_put(c.X)
            dev.block_until_ready()
        walls["upload_s"] += time.perf_counter() - t0
        return (c._replace(X=None), idx, dev)

    # pending: dispatched-but-uncollected steps, oldest first.  Bounded
    # by `depth` so device work stays ≤ depth chunks ahead of the host.
    pending = collections.deque()
    max_in_flight = 0
    pending_cap = depth if overlap else 0

    def _collect(entry):
        binned_dev, idx, c_index = entry
        if use_pallas:
            # occupancy/sample already folded on device; nothing to sync
            if binned_dev is not None:
                sample_parts.append(binned_dev)  # deferred device samp
            return
        with obs.span("ingest.collect", chunk=c_index):
            binned_host = np.asarray(binned_dev)  # syncs THIS chunk only
            # per-feature bincount: faster than one flattened bincount
            # AND only an O(rows) transient, keeping host peak O(chunk)
            for f in range(F):
                np.add(
                    occ_host[f],
                    np.bincount(binned_host[:, f], minlength=B),
                    out=occ_host[f],
                )
            if idx is not None:
                sample_parts.append(binned_host[idx])

    t_wall0 = time.perf_counter()
    with obs.span(
        "train.binning.device_bin", rows=n, features=F, packed=do_pack,
        fused_kernel=use_pallas, depth=depth, overlap=overlap,
    ):
        decoded = ChunkPrefetcher(
            _decoded_chunks(), transform=_draw_sample_idx, depth=depth,
            stall_counter="ingest.buffer_stall_ns", feed_steps=False,
            name="decode",
        )
        feed = ChunkPrefetcher(
            iter(decoded), transform=_upload, depth=depth,
            stall_counter="ingest.pipeline_stall_ns", feed_steps=True,
            count_chunks=False, name="upload",
        )
        try:
            # Per-chunk step telemetry: each feed-loop pass is one ingest
            # step whose wall splits into pipeline stall (fed by
            # data/loader.py) + bin dispatch (obs/steps.py).
            step_t = obs.steps.begin()
            for chunk, idx, rows_dev in feed:
                c_rows = int(rows_dev.shape[0])
                start = chunk.start // 2 if do_pack else chunk.start
                t0 = time.perf_counter()
                with obs.span("ingest.bin", rows=c_rows):
                    if use_pallas:
                        if idx is not None:
                            buf, occupancy_dev, samp = step_fused_sampled(
                                buf, occupancy_dev, binner.arrays, rows_dev,
                                np.int32(start), jnp.asarray(idx, jnp.int32),
                            )
                            pending.append((samp, None, chunk.index))
                        else:
                            buf, occupancy_dev = step_fused(
                                buf, occupancy_dev, binner.arrays, rows_dev,
                                np.int32(start),
                            )
                            pending.append((None, None, chunk.index))
                    else:
                        buf, binned_u8 = step_xla(
                            buf, binner.arrays, rows_dev, np.int32(start)
                        )
                        pending.append((binned_u8, idx, chunk.index))
                if chunk.y is not None:
                    if label is None:
                        label = np.empty(n, np.float64)
                    label[chunk.start:chunk.start + c_rows] = chunk.y[:c_rows]
                in_flight = len(pending) + feed.qsize() + decoded.qsize()
                if in_flight > max_in_flight:
                    max_in_flight = in_flight
                while len(pending) > pending_cap:
                    _collect(pending.popleft())
                if not overlap:
                    # serial comparator: fully drain the device per chunk
                    buf.block_until_ready()
                walls["step_s"] += time.perf_counter() - t0
                obs.steps.end(step_t, "ingest", chunk.index, rows=c_rows)
                step_t = obs.steps.begin()
            with obs.span("ingest.drain"):
                while pending:
                    _collect(pending.popleft())
                buf.block_until_ready()
                if use_pallas:
                    occupancy_dev.block_until_ready()
        finally:
            # release stage threads even when the loop dies mid-pipeline
            # (downstream first so upstream sees its consumer gone)
            feed.close()
            decoded.close()
    wall_s = time.perf_counter() - t_wall0

    # overlap attribution: how much of the smaller side (device-step
    # wall vs decode+upload wall) was hidden behind the other
    host_side = walls["decode_s"] + walls["upload_s"]
    hidden = max(0.0, host_side + walls["step_s"] - wall_s)
    denom = min(walls["step_s"], host_side)
    overlap_ratio = min(1.0, hidden / denom) if denom > 1e-9 else 0.0
    ingest_stats = {
        "depth": int(depth),
        "overlap": bool(overlap),
        "max_in_flight": int(max_in_flight),
        "decode_s": walls["decode_s"],
        "upload_s": walls["upload_s"],
        "step_s": walls["step_s"],
        "wall_s": wall_s,
        "hidden_s": hidden,
        "overlap_ratio": overlap_ratio,
    }
    if obs.enabled():
        obs.gauge("ingest.overlap_ratio", overlap_ratio)
        obs.gauge("ingest.max_in_flight", float(max_in_flight))

    sample = (
        np.concatenate([np.asarray(s) for s in sample_parts])
        [:quality_sample_cap]
        if sample_parts else None
    )
    return StreamedDataset(
        authority=authority,
        binned_dev=buf,
        packed=do_pack,
        num_rows=n,
        num_features=F,
        label=label,
        occupancy=(
            np.asarray(occupancy_dev, np.int64) if use_pallas else occ_host
        ),
        sample=sample,
        ingest_stats=ingest_stats,
    )


def train_streaming(
    params: dict,
    source,
    valid_sets: Sequence = (),
    valid_names: Optional[Sequence[str]] = None,
    chunk_rows: int = DEFAULT_CHUNK_ROWS,
    pack: str = "auto",
    fuse: str = "auto",
    exact_budget: int = DEFAULT_EXACT_BUDGET,
    compactor_cap: int = DEFAULT_COMPACTOR_CAP,
    mesh=None,
    init_model=None,
    return_dataset: bool = False,
    process_local: Optional[bool] = None,
    ingest_depth: Optional[int] = None,
    overlap: bool = True,
):
    """End-to-end streamed training: sketch-fit → device ingest → the
    stock :func:`mmlspark_tpu.engine.booster.train` loop.

    ``params`` is the usual LightGBM-style dict; ``max_bin`` /
    ``categorical_feature`` / ``min_data_in_bin`` flow into the sketch
    fit so the streamed edges answer the same binning config the
    in-memory path would.  With ``return_dataset=True`` returns
    ``(booster, streamed_dataset)`` so callers can reuse the ingested
    cache across training calls.

    Multi-process (the pod rehearsal path): pass a per-process source
    (:func:`process_shard_source`) on every process and call this
    collectively.  ``process_local`` defaults to ``process_count() > 1``
    — the sketch merge is already collective, every process's 3-stage
    ingest pipeline runs INDEPENDENTLY (no collective until training),
    and the trainer assembles the global row-sharded arrays from the
    per-process caches (``engine/booster.py`` ``process_local=True``).
    ``ingest_depth`` / ``overlap`` tune the pipeline
    (:func:`stream_ingest`).

    With ``init_model`` set this is the WARM-START refit entry (the
    closed loop's append-trees path, ISSUE 18): the sketch fit is
    skipped and the fresh shards are binned through the init_model's
    own authority — continuation pins the thresholds its trees were
    grown on — with ``num_iterations`` counting NEW trees and the
    per-iteration RNG continuing at the absolute fold_in schedule.
    """
    import jax

    from mmlspark_tpu.engine.booster import TrainConfig
    from mmlspark_tpu.engine.booster import train as _train

    if process_local is None:
        process_local = jax.process_count() > 1
    cfg = TrainConfig.from_params(params)
    if init_model is not None:
        # Warm-start refit (the closed loop's append-trees path):
        # continuation replays the old trees, which pins their
        # thresholds — so the fresh shards are ingested through the
        # init_model's OWN BinningAuthority instead of sketch-fitting
        # new edges the trainer would then have to reject.
        authority = init_model.bin_authority()
        bm = authority.mapper
        if (int(cfg.max_bin) != int(bm.max_bin)
                or tuple(cfg.categorical_feature)
                != tuple(bm.categorical_features)):
            raise ValueError(
                "warm-start streamed refit pins the init_model's binning "
                f"(max_bin={bm.max_bin}, categorical="
                f"{tuple(bm.categorical_features)}); params asked for "
                f"max_bin={cfg.max_bin}, categorical="
                f"{tuple(cfg.categorical_feature)}"
            )
        with obs.span("train.binning", streamed=True, warm_start=True,
                      rows=source.num_rows):
            train_set = stream_ingest(
                source, authority, chunk_rows=chunk_rows, pack=pack,
                fuse=fuse, quality_sample_cap=4096, seed=cfg.seed,
                depth=ingest_depth, overlap=overlap,
            )
    else:
        with obs.span("train.binning", streamed=True, rows=source.num_rows):
            authority, sketch = stream_fit_binning(
                source,
                max_bin=cfg.max_bin,
                categorical_features=tuple(cfg.categorical_feature),
                chunk_rows=chunk_rows,
                exact_budget=exact_budget,
                compactor_cap=compactor_cap,
            )
            if obs.enabled():
                obs.gauge(
                    "ingest.sketch_rank_epsilon", float(sketch.rank_epsilon)
                )
            train_set = stream_ingest(
                source, authority, chunk_rows=chunk_rows, pack=pack,
                fuse=fuse, quality_sample_cap=4096, seed=cfg.seed,
                depth=ingest_depth, overlap=overlap,
            )
    if train_set.label is None:
        raise ValueError(
            "streamed training needs labels: the shard source yielded none "
            "(NpySource(label_paths=...) or write_row_group_shards(y=...))"
        )
    # Propagate the global shard assignment (process_shard_source) so the
    # trainer's rank-0 checkpoint manifest records who held what.
    train_set.shard_paths = getattr(source, "shard_paths", None)
    booster = _train(
        params, train_set, valid_sets=valid_sets, valid_names=valid_names,
        bin_mapper=authority.mapper, init_model=init_model, mesh=mesh,
        process_local=process_local,
    )
    return (booster, train_set) if return_dataset else booster
