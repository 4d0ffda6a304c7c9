"""Multi-host process-group rendezvous.

Reference mechanism being replaced (SURVEY.md §3.1, §5.8): every Spark task
binds a port, reports ``ip:port`` to a driver ``ServerSocket``, receives the
comma-joined machine list back, and calls ``LGBM_NetworkInit(machines, port,
timeout, numMachines)`` so the native library can form its TCP allreduce
ring.

TPU-native replacement: ``jax.distributed.initialize(coordinator_address,
num_processes, process_id)``.  The coordinator address plays the role of the
driver rendezvous socket, and process ids come from the launcher (a Spark
barrier task context, GKE/JobSet indices, or explicit arguments).  After
initialization, ``jax.devices()`` spans all hosts and one SPMD program over a
global mesh replaces the reference's gang-scheduled barrier stage.
"""

from __future__ import annotations

import os
from dataclasses import dataclass
from typing import Optional

from mmlspark_tpu import obs


@dataclass(frozen=True)
class BarrierContext:
    """The information the reference extracts from Spark's barrier stage
    (task addresses + this task's index), normalized for jax.distributed."""

    coordinator_address: str
    num_processes: int
    process_id: int


_ENV_COORD = "MMLSPARK_TPU_COORDINATOR"
_ENV_NPROC = "MMLSPARK_TPU_NUM_PROCESSES"
_ENV_PID = "MMLSPARK_TPU_PROCESS_ID"
_ENV_LOCAL_DEVICES = "MMLSPARK_TPU_LOCAL_DEVICES"


def ensure_local_device_count(n: int) -> None:
    """Pin THIS process's device visibility to ``n`` virtual CPU devices.

    The multi-host smoke topology (2 real processes × N virtual CPU
    devices each) needs every process to expose the same local device
    count BEFORE jax initializes its backends — afterwards the flag is
    inert.  Idempotent; appends to ``XLA_FLAGS`` rather than clobbering
    whatever collective-timeout flags the harness already set.
    """
    flag = f"--xla_force_host_platform_device_count={n}"
    cur = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in cur:
        os.environ["XLA_FLAGS"] = (cur + " " + flag).strip()


def barrier_context_from_cli(argv=None) -> Optional[BarrierContext]:
    """CLI twin of :func:`barrier_context_from_env` for launcher scripts
    (``--coordinator host:port --num-processes N --process-id I
    [--local-devices D]``).  Unrecognized arguments are ignored so runners
    can mix their own flags in; returns None when no coordinator was given
    (single-process).  ``--local-devices`` additionally pins per-process
    device visibility (see :func:`ensure_local_device_count`).
    """
    import argparse
    import sys

    p = argparse.ArgumentParser(add_help=False)
    p.add_argument("--coordinator", default=None)
    p.add_argument("--num-processes", type=int, default=1)
    p.add_argument("--process-id", type=int, default=0)
    p.add_argument("--local-devices", type=int, default=0)
    ns, _ = p.parse_known_args(
        list(sys.argv[1:] if argv is None else argv)
    )
    n_local = ns.local_devices or int(
        os.environ.get(_ENV_LOCAL_DEVICES, "0")
    )
    if n_local:
        ensure_local_device_count(n_local)
    if not ns.coordinator:
        return barrier_context_from_env()
    return BarrierContext(
        coordinator_address=ns.coordinator,
        num_processes=ns.num_processes,
        process_id=ns.process_id,
    )


def barrier_context_from_env() -> Optional[BarrierContext]:
    """Derive rendezvous info from the environment.

    Checked in order:
    1. ``MMLSPARK_TPU_{COORDINATOR,NUM_PROCESSES,PROCESS_ID}`` — set by the
       Spark-side integration: the barrier stage elects task 0's host as
       coordinator (``BarrierTaskContext.getTaskInfos().head.address``) and
       exports these before spawning the per-host Python runner, exactly
       where the reference builds its machine list (SURVEY.md §3.1).
    2. Cloud TPU metadata conventions (``TPU_WORKER_ID``/
       ``TPU_WORKER_HOSTNAMES``), in which case jax's own auto-detection is
       preferred — return None and let ``jax.distributed.initialize()``
       no-arg autodetect.
    """
    coord = os.environ.get(_ENV_COORD)
    if coord:
        return BarrierContext(
            coordinator_address=coord,
            num_processes=int(os.environ.get(_ENV_NPROC, "1")),
            process_id=int(os.environ.get(_ENV_PID, "0")),
        )
    return None


_initialized = False


def initialize_distributed(
    context: Optional[BarrierContext] = None, timeout_s: int = 1200
) -> bool:
    """Form the multi-host process group (idempotent).

    ``timeout_s`` mirrors the reference's ``timeout`` param (1200s default —
    SURVEY.md §2.3.1) guarding against a hung rendezvous.  Returns True if a
    multi-process group was initialized, False for single-process runs.
    """
    global _initialized
    if _initialized:
        return True
    import jax

    ctx = context or barrier_context_from_env()
    if ctx is None:
        # Single process (or TPU-pod auto-detection handled by jax itself on
        # Cloud TPU VMs). Nothing to rendezvous.
        return False
    # (CPU pods — the 2-real-process smoke topology — run cross-process
    # computations over gloo, the installed jax's default CPU collectives.)
    jax.distributed.initialize(
        coordinator_address=ctx.coordinator_address,
        num_processes=ctx.num_processes,
        process_id=ctx.process_id,
        initialization_timeout=timeout_s,
    )
    _initialized = True
    # Re-anchor obs rank stamping (ISSUE 14 satellite): anything recorded
    # BEFORE bring-up resolved (and cached) rank 0 on every process; stamp
    # the launcher env and drop the cache so per-process export/blackbox
    # files split correctly from here on.
    import os as _os

    _os.environ.setdefault("MMLSPARK_TPU_PROCESS_ID", str(ctx.process_id))
    _os.environ.setdefault(
        "MMLSPARK_TPU_NUM_PROCESSES", str(ctx.num_processes)
    )
    from mmlspark_tpu.obs import _state as _obs_state

    _obs_state.reset_rank_cache()
    return True


def global_mesh():
    """A 1-D mesh over ALL processes' devices (call after
    :func:`initialize_distributed`) — delegates to
    :func:`mmlspark_tpu.parallel.mesh.default_mesh`."""
    from mmlspark_tpu.parallel.mesh import default_mesh

    return default_mesh()


def make_global_array(mesh, spec, local_rows):
    """Assemble a globally-sharded array from PROCESS-LOCAL row data.

    The multi-controller ingestion path (SURVEY.md §7.3.4): every process
    holds only ITS partition (as the reference's per-task native Dataset
    held only the partition rows) and contributes it to one global array —
    ``jax.device_put`` of a host array would instead require every process
    to hold the identical FULL dataset.  ``spec`` must shard the leading
    (row) axis over the mesh's process dimension.
    """
    import jax
    from jax.sharding import NamedSharding

    sharding = NamedSharding(mesh, spec)
    if jax.process_count() == 1:
        return jax.device_put(local_rows, sharding)
    # Cross-process assembly blocks until every process contributes — run
    # it under the watchdog so a missing rank is diagnosed, not silent.
    with obs.collective_watchdog(
        "make_global_array", shape=tuple(getattr(local_rows, "shape", ())),
        **obs.trace_attrs(),
    ):
        return jax.make_array_from_process_local_data(sharding, local_rows)


def _leaf_nbytes(x) -> int:
    """Total payload bytes of a pytree's leaves (trace-time shapes)."""
    import jax
    import numpy as np

    return int(
        sum(
            int(np.prod(leaf.shape)) * np.dtype(leaf.dtype).itemsize
            for leaf in jax.tree_util.tree_leaves(x)
        )
    )


# ---------------------------------------------------------------------------
# Device-collective wrappers (traced): the sanctioned call sites for the
# big in-program collectives.  Each delegates to jax.lax at CALL time (so
# tracing shims like tools/bench_scaling.CollectiveRecorder still see the
# call) and rides the collective watchdog, which — when obs is enabled —
# emits ``collective.calls`` / ``collective.bytes`` counters labeled by op
# (psum, reduce_scatter, all_gather).  The counters are TRACE-TIME
# accounting (a window of fits that run a cached program reads 0; what RAN
# is ``train.merge_bytes`` / ``train.merge_calls``, which the booster adds
# at each ``booster.scan_dispatch`` from :func:`collective_ledger`): one
# increment per traced call site, with nbytes = the bytes
# each device RECEIVES per execution of that site (psum: the full reduced
# array; reduce_scatter: the 1/D slice; all_gather: the D-fold result) —
# i.e. per-pass wire volume, the quantity the MULTICHIP comms ledger and
# ``python -m tools.obs report`` track.  Each wrapper additionally emits a
# ``collective.axis_bytes`` counter labeled by op AND axis scope
# (:func:`axis_scope`), the per-axis split of the ledger: "intra" bytes
# never leave a host on the 2D mesh, "inter" bytes cross the slow axis.
# The analyzer's COL004 rule points full-histogram ``lax.psum`` call sites
# at these helpers; COL007 flags full-histogram operands whose axis
# argument crosses the inter-host axis.
# ---------------------------------------------------------------------------


# The scope every wire op of the merge runs under, beside the grower's
# ``hist_build`` and ``split_scan``: the device trace names the ops by it.
MERGE_SCOPE = "hist_merge"

_COLLECTIVE_PRIMS = {
    "psum": "psum", "psum2": "psum", "psum_invariant": "psum",
    "pmax": "pmax", "pmin": "pmin",
    "reduce_scatter": "reduce_scatter",
    "all_gather": "all_gather", "all_gather_invariant": "all_gather",
    "ppermute": "ppermute", "all_to_all": "all_to_all",
}


def collective_ledger(jaxpr, while_trips: int = 1) -> dict:
    """``{op: (calls, bytes)}`` one execution of a traced program makes:
    for each collective in ``jaxpr`` (a ``ClosedJaxpr`` or ``Jaxpr``, read
    through ``shard_map``, ``scan``, ``cond`` and calls) the bytes each
    device RECEIVES, by the convention of the wrappers below (the result's
    bytes), times how often its site runs.  A ``scan`` multiplies by its
    length.  A ``while`` loop's count is not in the program: ``while_trips``
    stands for it (the booster passes the passes of a full tree,
    ``engine.tree.full_tree_passes``), so a tree that stops early is counted
    high.  ``cond`` counts its largest branch.  This is what the EXECUTED
    counters ``train.merge_bytes`` / ``train.merge_calls`` are made from;
    the wrappers' own ``collective.*`` counters tick once a traced site."""
    out: dict = {}

    def add(dst, op, calls, nbytes):
        c, b = dst.get(op, (0, 0))
        dst[op] = (c + calls, b + nbytes)

    def walk(jp, mult, dst):
        for eqn in getattr(jp, "jaxpr", jp).eqns:
            name = eqn.primitive.name
            op = _COLLECTIVE_PRIMS.get(name)
            if op is not None:
                nbytes = _leaf_nbytes([v.aval for v in eqn.outvars])
                add(dst, op, mult, mult * nbytes)
                continue
            inner = mult
            if name == "scan":
                inner = mult * int(eqn.params["length"])
            elif name == "while":
                inner = mult * int(while_trips)
            if name == "cond":
                branches = [{} for _ in eqn.params["branches"]]
                for br, cur in zip(eqn.params["branches"], branches):
                    walk(br, mult, cur)
                best = max(branches, key=lambda d: sum(b for _, b in d.values()))
                for op_, (c, b) in best.items():
                    add(dst, op_, c, b)
                continue
            for v in eqn.params.values():
                for sub in v if isinstance(v, (tuple, list)) else (v,):
                    if hasattr(sub, "eqns") or hasattr(sub, "jaxpr"):
                        # a while's cond_jaxpr runs once more than its body:
                        # it holds no collective here, and counts as the body
                        walk(sub, inner, dst)

    walk(jaxpr, 1, out)
    return out


def axis_scope(axis_name) -> str:
    """Classify a collective's mesh-axis argument by link tier.

    Modeled topology of :func:`mmlspark_tpu.parallel.mesh.mesh2d` (so the
    ledger's split is meaningful on virtual CPU meshes too): the
    ``"feature"`` axis connects devices WITHIN one host ("intra" — fast
    ICI), while any axis set naming ``"data"`` spans hosts ("inter" —
    slow DCN on a real pod; a flat 1-D "data" mesh's collectives are all
    inter-host under this model, which is exactly the flat-vs-hierarchical
    comparison the MULTICHIP ledger records).
    """
    from mmlspark_tpu.parallel.mesh import DATA_AXIS

    axes = (
        tuple(axis_name) if isinstance(axis_name, (tuple, list))
        else (axis_name,)
    )
    return "inter" if DATA_AXIS in axes else "intra"


def psum_axes(x, axis_name):
    """Cross-layout bitwise-deterministic ``psum`` over tuple mesh axes.

    ``lax.psum(x, ("data", "feature"))`` on a float operand leaves the
    summation order to the runtime, and the order differs between a
    single-process mesh and a real multi-process pod (measured: a
    (3, L) f32 all-reduce over a (2, 4) mesh lands on different
    last-ulp sums under in-process XLA vs the distributed runtime —
    and decomposing per-axis does NOT fix it, the intra-host grouping
    itself shifts with the process layout).  The same logical program
    would then produce different models, sinking the bitwise parity
    the multi-controller contract promises (tools/multihost_smoke.py).

    The only layout-invariant pieces are (a) data movement — a gather
    is bit-exact however the wire chunks it — and (b) local arithmetic,
    which compiles identically on every process.  So: per axis, FAST
    (intra-host) axis first, ``all_gather`` the partials (device order
    is the mesh order on every layout) and reduce them locally in the
    program's fixed order.  The fast step is intra-host wire; the slow
    step then gathers ONE already-reduced partial per host, so the
    inter-host amplification over a true all-reduce is only the host
    count.  Still costlier than a real reduce, so reserve this for
    SMALL operands on correctness-critical paths (per-leaf stat
    totals, winner refinement columns — a few KB); bulk histograms
    keep the real reduce collectives.  Integer operands and single
    axes stay on ``lax.psum`` (exact / already order-free).  No
    watchdog or byte accounting: this is the pure in-kernel primitive
    (see :func:`device_psum_exact` for the ledgered twin).
    """
    import jax.numpy as jnp
    from jax import lax

    if (
        isinstance(axis_name, (tuple, list))
        and len(axis_name) > 1
        and jnp.issubdtype(jnp.result_type(x), jnp.floating)
    ):
        for ax in reversed(tuple(axis_name)):  # ROW_AXES = (slow, fast)
            x = jnp.sum(lax.all_gather(x, ax), axis=0)
        return x
    return lax.psum(x, axis_name)


def device_psum(x, axis_name):
    """``lax.psum`` under the collective watchdog + byte accounting.

    Tuple axes ride one fused ``lax.psum`` (callers on order-sensitive
    float paths use :func:`psum_axes` instead); the bytes land on the
    slowest tier any named axis touches.
    """
    import jax
    from jax import lax

    with obs.collective_watchdog("psum", **obs.trace_attrs()) as wd:
        with jax.named_scope(MERGE_SCOPE):
            x = lax.psum(x, axis_name)
        wd.attrs["nbytes"] = _leaf_nbytes(x)
        obs.inc("collective.axis_bytes", wd.attrs["nbytes"],
                name="psum", axis=axis_scope(axis_name))
    return x


def device_psum_exact(x, axis_name):
    """Bitwise layout-invariant ``psum`` (see :func:`psum_axes`) under
    the collective watchdog, with each gather step's bytes ledgered
    against ITS link tier as ``all_gather`` — because that IS the wire
    op.  Non-float or single-axis operands fall through to the ordinary
    ledgered :func:`device_psum` (already order-exact)."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    axes = (
        tuple(axis_name) if isinstance(axis_name, (tuple, list))
        else (axis_name,)
    )
    if len(axes) < 2 or not jnp.issubdtype(
        jnp.result_type(x), jnp.floating
    ):
        return device_psum(x, axis_name)
    with obs.collective_watchdog("all_gather", **obs.trace_attrs()) as wd:
        total = 0
        for ax in reversed(axes):  # fast (intra-host) axis first
            with jax.named_scope(MERGE_SCOPE):
                g = lax.all_gather(x, ax)
            nb = _leaf_nbytes(g)
            total += nb
            obs.inc("collective.axis_bytes", nb,
                    name="all_gather", axis=axis_scope(ax))
            x = jnp.sum(g, axis=0)
        wd.attrs["nbytes"] = total
    return x


def _pad_to_axis(x, axis_name, dimension: int):
    """``x`` zero-padded along ``dimension`` to a multiple of the mesh axis
    size (static under ``shard_map``), which ``psum_scatter`` asks for.  The
    histogram is padded, a few KB a pass, never the binned matrix: 39
    columns on 4 chips scatter as 40, and the grower masks the slot that
    no column fills out of every candidate search."""
    import jax.numpy as jnp
    from jax import lax

    pad = (-x.shape[dimension]) % lax.psum(1, axis_name)
    if not pad:
        return x
    widths = [(0, 0)] * x.ndim
    widths[dimension] = (0, pad)
    return jnp.pad(x, widths)


def device_psum_scatter(x, axis_name, scatter_dimension: int = 0,
                        tiled: bool = True):
    """``lax.psum_scatter``: reduce + scatter contiguous blocks of
    ``scatter_dimension`` over the mesh axis — each device receives the
    fully-reduced values for its 1/D block (``tiled=True`` keeps the axis
    in place at size/D).  A dimension the axis size does not divide is
    zero-padded up to it here (:func:`_pad_to_axis`): the caller masks what
    the padding adds (the grower: the feature slots past the last column)."""
    import jax
    from jax import lax

    x = _pad_to_axis(x, axis_name, scatter_dimension)
    with obs.collective_watchdog("reduce_scatter", **obs.trace_attrs()) as wd:
        with jax.named_scope(MERGE_SCOPE):
            out = lax.psum_scatter(
                x, axis_name, scatter_dimension=scatter_dimension, tiled=tiled
            )
        wd.attrs["nbytes"] = _leaf_nbytes(out)
        obs.inc("collective.axis_bytes", wd.attrs["nbytes"],
                name="reduce_scatter", axis=axis_scope(axis_name))
    return out


def device_all_gather(x, axis_name, **kw):
    """``lax.all_gather`` under the collective watchdog + byte accounting."""
    import jax
    from jax import lax

    with obs.collective_watchdog("all_gather", **obs.trace_attrs()) as wd:
        with jax.named_scope(MERGE_SCOPE):
            out = lax.all_gather(x, axis_name, **kw)
        wd.attrs["nbytes"] = _leaf_nbytes(out)
        obs.inc("collective.axis_bytes", wd.attrs["nbytes"],
                name="all_gather", axis=axis_scope(axis_name))
    return out


def _require_int_wire(x, op: str) -> None:
    import jax
    import numpy as np

    for leaf in jax.tree_util.tree_leaves(x):
        if not np.issubdtype(np.dtype(leaf.dtype), np.integer):
            raise TypeError(
                f"{op} carries the quantized integer histogram wire; got "
                f"dtype {leaf.dtype} — quantize first (ops.histogram."
                "quantize_hist_vals) or use the float wrapper"
            )


def device_psum_int(x, axis_name):
    """Integer-wire ``lax.psum`` (ISSUE 9 quantized histogram merge).

    Same op label / watchdog / byte accounting as :func:`device_psum`,
    plus a ``hist.quantized_bytes`` counter so the wire savings of the
    quantized path are directly readable from one obs counter.  Rejects
    non-integer operands: the caller's wire plan (shift + dtype) is what
    makes the integer sum overflow-safe, so a float sneaking in here
    means the plan was bypassed.
    """
    import jax
    from jax import lax

    _require_int_wire(x, "device_psum_int")
    with obs.collective_watchdog("psum", **obs.trace_attrs()) as wd:
        with jax.named_scope(MERGE_SCOPE):
            x = lax.psum(x, axis_name)  # integer sum: order-exact
        wd.attrs["nbytes"] = _leaf_nbytes(x)
        obs.inc("collective.axis_bytes", wd.attrs["nbytes"],
                name="psum", axis=axis_scope(axis_name))
        obs.inc("hist.quantized_bytes", wd.attrs["nbytes"])
    return x


def device_psum_scatter_int(x, axis_name, scatter_dimension: int = 0,
                            tiled: bool = True):
    """Integer-wire ``lax.psum_scatter`` (see :func:`device_psum_int`)."""
    import jax
    from jax import lax

    _require_int_wire(x, "device_psum_scatter_int")
    x = _pad_to_axis(x, axis_name, scatter_dimension)
    with obs.collective_watchdog("reduce_scatter", **obs.trace_attrs()) as wd:
        with jax.named_scope(MERGE_SCOPE):
            out = lax.psum_scatter(
                x, axis_name, scatter_dimension=scatter_dimension, tiled=tiled
            )
        nbytes = _leaf_nbytes(out)
        wd.attrs["nbytes"] = nbytes
        obs.inc("hist.quantized_bytes", nbytes)
        obs.inc("collective.axis_bytes", nbytes,
                name="reduce_scatter", axis=axis_scope(axis_name))
    return out


def host_allgather(arr) -> "np.ndarray":
    """Allgather a SMALL host array across processes → (nproc, *shape).

    The host-side control-plane collective for per-process metadata (row
    counts, label sufficient statistics, binning samples) — never the data
    plane.  Single-process: returns the array with a leading axis of 1.
    """
    import jax
    import numpy as np

    a = np.ascontiguousarray(arr)
    if jax.process_count() == 1:
        return a[None]
    from jax.experimental import multihost_utils as mhu

    # Gather RAW BYTES: routing float64/int64 through jax would silently
    # truncate to 32-bit (jax_enable_x64 is off), which perturbs e.g.
    # binning-sample values — bin boundaries must be bit-identical to a
    # single-host fit.
    raw = a.reshape(-1).view(np.uint8)
    # The PR 1 deadlock class lived exactly here: a subset of ranks inside
    # an allgather no other rank entered hangs FOREVER with no diagnostic.
    # The watchdog logs a rank-stamped "stuck in collective" line past a
    # soft timeout (and, when obs is enabled, records count/duration).
    with obs.collective_watchdog(
        "host_allgather", nbytes=int(raw.nbytes), **obs.trace_attrs()
    ):
        gathered = np.asarray(mhu.process_allgather(raw))  # (nproc, nbytes)
    return gathered.view(a.dtype).reshape((gathered.shape[0],) + a.shape)


def host_allgather_ragged_rows(arr) -> "np.ndarray":
    """Concatenate every process's rows (differing counts allowed), in
    process order.  Intended for BOUNDED payloads (binning samples ≤
    ``bin_construct_sample_cnt`` rows) and for the ONE sanctioned
    full-dataset use: feature-parallel ingestion, whose LightGBM contract
    is that every machine holds the full data anyway — note the gather
    transiently pads to ``nproc × max_rows``, so callers moving datasets
    accept ~2× the merged size in peak host memory."""
    import numpy as np

    arr = np.ascontiguousarray(arr)
    counts = host_allgather(np.asarray([len(arr)])).reshape(-1)
    if len(counts) == 1:
        return arr
    m = int(counts.max())
    padded = np.zeros((m,) + arr.shape[1:], arr.dtype)
    padded[: len(arr)] = arr
    gathered = host_allgather(padded)  # (nproc, m, ...)
    return np.concatenate(
        [gathered[i, : counts[i]] for i in range(len(counts))], axis=0
    )


def host_allgather_blobs(vec) -> "list":
    """Allgather one flat per-process vector, returning the PER-PROCESS
    blobs as a list in process order (unlike
    :func:`host_allgather_ragged_rows`, which concatenates — callers that
    must deserialize each process's payload separately need the
    boundaries preserved).

    The streaming quantile-sketch merge rides this: every process
    serializes its :class:`~mmlspark_tpu.data.sketch.DatasetSketch` to a
    flat float64 state vector (KB-scale — sketch sizes are bounded by
    ``exact_budget``/``compactor_cap`` per feature, never O(rows)), the
    blobs gather bit-exactly (``host_allgather`` is a raw-bytes gather,
    immune to the x64 truncation trap), and every process folds them in
    the SAME process order — deterministic identical merged edges on all
    ranks.  Single-process: a one-element list, no wire traffic.
    """
    import numpy as np

    vec = np.ascontiguousarray(vec).reshape(-1)
    lens = host_allgather(np.asarray([len(vec)])).reshape(-1)
    if len(lens) == 1:
        return [vec]
    m = int(lens.max())
    padded = np.zeros(m, vec.dtype)
    padded[: len(vec)] = vec
    gathered = host_allgather(padded)  # (nproc, m)
    return [gathered[i, : lens[i]] for i in range(len(lens))]
