"""AOT trace cache: persist EXPORTED training programs across processes.

The persistent compile cache (core/jit_cache) eliminates XLA compilation
on warm starts, but a fresh process still pays Python TRACING of the
whole-run scan program — measured ~15 s of the ~21 s warm-cache cold fit
at the bench shape (BASELINE.md r4 decomposition), against a reference
with zero compile/trace cost (SURVEY.md §3.1).  ``jax.export`` captures
the traced+lowered StableHLO; serializing it per (program config, arg
signature, source hash) lets every LATER process skip tracing entirely:
deserialize → call, with XLA compilation still served by the compile
cache.

Safety model — a stale trace is a CORRECTNESS bug, so the cache key
includes:
- the full training-config fingerprint + objective state (the caller's
  ``key_material``),
- the shapes/dtypes of every argument (chunk sizes, row counts, ...),
- a SHA-256 over the source bytes of every module the program traces
  through (``mmlspark_tpu/{engine,ops,parallel}``), so ANY code edit
  invalidates,
- the jax version and backend platform.

Scope (r5: EXTENDED to sharded programs — r4 verdict next #1): meshless,
single-controller mesh, AND multi-controller (``process_local``) training
programs all export.  Sharded lowerings carry their shardings in the
StableHLO (``jax.export`` records them against the trace-time device
assignment), so the caller's ``key_material`` must include the mesh
topology (axis names/shape, device kind, process count) — the booster
passes ``_mesh_trace_key``.  Under multiple controllers every process
must execute a BYTE-IDENTICAL program (the replicated-model contract is
psum-determinism, which mixing a freshly-traced program on one process
with a deserialized one on another could break in ulps), so load-vs-
export is AGREED via a tiny host allgather: all processes load only when
every process has the blob; otherwise all export.  The agreement runs
only when the caller attests the program IS multi-controller
(``wrap_aot(..., multi_controller=True)``, from the mesh topology) —
never merely because the job has multiple processes, which would let a
meshless rank-local train deadlock in a collective no other rank enters.

Elastic resume (r11, ISSUE 14) leans on the topology key: a surviving
process re-forms a SMALLER mesh over its own devices — e.g. ``(2, 4)``
across two hosts collapsing to ``(1, 4)`` after a peer dies — while the
SAME cache directory (often a shared filesystem) still holds the pod-era
blobs.  ``mesh_trace_key``'s mesh shape + ``pc{process_count}``
components make those keys disjoint, so the survivor re-exports for its
new topology instead of replaying a program whose collectives expect
dead participants; when the pod re-forms at full strength, the original
blobs hit again unchanged.  Writes are tmp+rename atomic per process,
so concurrent ranks racing the same digest never tear a reader.

Blobs live in the one compiled-artifact directory
(:func:`mmlspark_tpu.core.jit_cache.cache_dir`:
``JAX_COMPILATION_CACHE_DIR`` when set, else ``<checkout>/.jax_cache``)
beside the XLA entries and the ``aot-*``/``pft-*`` artifacts, and ride the
same LRU prune.

Opt out with ``MMLSPARK_TPU_NO_TRACE_CACHE=1``.  A blob that does not
deserialize (truncated write, disk rot) is re-exported; a graph
``jax.export`` rejects (``NotImplementedError``/``ValueError`` — an effect
or a primitive without a serialization rule) turns the wrapper off for that
program and counts ``trace_cache.off``.  Anything else — a kernel that
fails to lower, an API mismatch — raises.
"""

from __future__ import annotations

import hashlib
import os
from typing import Callable, Optional

import numpy as np

from mmlspark_tpu import obs

_SRC_HASH: Optional[str] = None
_REGISTERED = False
# In-process memo of deserialized/exported programs and the jitted calls
# that run them: repeated train() calls build fresh wrappers, and
# re-deserializing the scan blob (or tracing its call anew) per fit would
# tax steady-state runs.
_EXP_MEMO: dict = {}
_EXP_MEMO_MAX = 8


def _source_hash() -> str:
    global _SRC_HASH
    if _SRC_HASH is None:
        import mmlspark_tpu

        root = os.path.dirname(os.path.abspath(mmlspark_tpu.__file__))
        h = hashlib.sha256()
        for sub in ("engine", "ops", "parallel"):
            d = os.path.join(root, sub)
            if not os.path.isdir(d):
                continue
            for fn in sorted(os.listdir(d)):
                if fn.endswith(".py"):
                    h.update(fn.encode())
                    with open(os.path.join(d, fn), "rb") as f:
                        h.update(f.read())
        _SRC_HASH = h.hexdigest()
    return _SRC_HASH


def enabled() -> bool:
    return not os.environ.get("MMLSPARK_TPU_NO_TRACE_CACHE")


def _register_trees():
    global _REGISTERED
    if _REGISTERED:
        return
    from jax import export as jexport

    from mmlspark_tpu.engine.tree import Tree

    jexport.register_namedtuple_serialization(
        Tree, serialized_name="mmlspark_tpu.engine.tree.Tree"
    )
    _REGISTERED = True


def _arg_signature(args) -> str:
    import jax

    leaves, treedef = jax.tree_util.tree_flatten(args)
    parts = [str(treedef)]
    for a in leaves:
        parts.append(f"{tuple(np.shape(a))}:{np.result_type(a)}")
    return "|".join(parts)


def mesh_trace_key(mesh) -> str:
    """Topology component of a sharded program's trace-cache key: the
    exported lowering is valid for any device assignment with the same
    mesh SHAPE/axes on the same hardware generation, so key on those (not
    concrete device ids, which relabel across restarts) + process count."""
    import jax

    if mesh is None:
        return "meshless"
    kind = jax.devices()[0].device_kind
    return (
        f"{tuple(mesh.axis_names)}:{mesh.devices.shape}:{kind}"
        f":pc{jax.process_count()}"
    )


def mesh_spans_processes(mesh) -> bool:
    """True iff ``mesh`` places devices on more than one process — the
    program lowered over it is genuinely multi-controller, so every
    process executes it in lockstep (the SPMD contract)."""
    if mesh is None:
        return False
    procs = {getattr(d, "process_index", 0) for d in mesh.devices.flat}
    return len(procs) > 1


def _all_processes_ok(local_ok: bool, multi_controller: bool) -> bool:
    """Collective AND over processes (multi-controller agreement — see the
    module docstring's byte-identical-program contract).

    The collective runs ONLY for genuinely multi-controller programs
    (``multi_controller`` — derived by the caller from the mesh topology /
    process_local flag, never from ``jax.process_count()`` alone): a
    meshless program inside a multi-process job is NOT executed by every
    rank, so a process-count-gated allgather here would block forever
    waiting on ranks that never enter it, and ranks wrapping different
    local programs would pair unrelated agreement collectives.
    """
    import jax

    if not multi_controller or jax.process_count() == 1:
        return local_ok
    from mmlspark_tpu.parallel.distributed import host_allgather

    flags = host_allgather(np.asarray([1 if local_ok else 0], np.int32))
    return bool(flags.reshape(-1).min())


def _all_processes_have(path: str, multi_controller: bool) -> bool:
    """True iff EVERY participating process's cache holds the blob."""
    return _all_processes_ok(os.path.exists(path), multi_controller)


def _load_or_export(jitted, args, digest: str, multi_controller: bool):
    """The exported program for ``digest``: deserialized from the cache
    directory when every participating process holds the blob, else
    exported now (one trace — the price the plain jit path pays) and
    written for later processes.  ``None`` when ``jax.export`` rejects
    the graph (counted as ``trace_cache.off``)."""
    import struct
    import warnings

    from jax import export as jexport

    from mmlspark_tpu.core.jit_cache import cache_dir, record_cache_hit

    path = os.path.join(cache_dir(), digest + ".jaxexp")
    exp = None
    # Every non-deterministic step below is COLLECTIVE-agreed under
    # multiple controllers (blob existence, deserialize success), so all
    # processes take the same branch and run byte-identical programs; an
    # export rejection is a deterministic property of the program,
    # failing identically on every process, so the per-process `off`
    # fallback stays safe.
    if _all_processes_have(path, multi_controller):
        try:
            with obs.span("trace_cache.load"), open(path, "rb") as f:
                exp = jexport.deserialize(bytearray(f.read()))
            record_cache_hit(path)
        except (OSError, struct.error, ValueError):
            exp = None  # torn/corrupt blob on SOME process
        if not _all_processes_ok(exp is not None, multi_controller):
            exp = None  # any process failed → everyone exports
    if exp is not None:
        obs.inc("trace_cache.hit")
        return exp
    # the re-trace this miss pays is counted where it happens
    # (jit_cache._on_jit_duration: device.compile_events{kind=trace})
    obs.inc("trace_cache.miss")
    try:
        with obs.span("trace_cache.export"):
            exp = jexport.export(jitted)(*args)
            blob = exp.serialize()
    except (NotImplementedError, ValueError) as e:
        # jax.export's own refusals (an effect or custom call with no
        # serialization guarantee).  A kernel that fails to lower fails
        # again, loudly, in the plain jit call the caller falls back to.
        obs.inc("trace_cache.off")
        warnings.warn(f"trace cache off for this program: {e}")
        return None
    try:
        os.makedirs(os.path.dirname(path), exist_ok=True)
        tmp = path + f".tmp{os.getpid()}"
        with open(tmp, "wb") as f:
            f.write(blob)
        os.replace(tmp, path)
    except OSError:
        pass  # best-effort write; the export still serves
    return exp


def wrap_aot(
    jitted: Callable, key_material: str, multi_controller: bool = False,
    donate_argnums: tuple = (),
) -> Callable:
    """Wrap a jitted function so its traced program persists across
    processes.  First call per argument signature: load the exported
    blob if present (NO tracing), else export once (one trace — the same
    price the plain jit path pays) and save for future processes.

    ``multi_controller`` asserts the wrapped program is executed by EVERY
    process (a mesh spanning processes / process_local ingestion — the
    booster derives it via :func:`mesh_spans_processes`).  Only then is
    load-vs-export agreed collectively; meshless programs load/export
    purely locally even inside a multi-process job, so a rank-local train
    (e.g. a rank-0-only serial comparator) can never deadlock here.

    ``donate_argnums``: ``jitted``'s own, so that the exported program
    overwrites the same arguments."""
    import jax

    state: dict = {}

    def runner(exp, args):
        """The exported program as one jitted call of ``args``' flat
        leaves, under the module name ``jit_call_exported``, donating the
        leaves of ``donate_argnums``."""
        tree = jax.tree_util.tree_structure(args)
        sizes = [len(jax.tree_util.tree_leaves(a)) for a in args]
        donate = tuple(j for i in donate_argnums for j in range(sum(sizes[:i]), sum(sizes[: i + 1])))

        def call_exported(*flat):
            return jax.tree_util.tree_leaves(exp.call(*jax.tree_util.tree_unflatten(tree, flat)))

        return jax.jit(call_exported, donate_argnums=donate)

    def run(entry, args):
        exp, fn = entry
        return jax.tree_util.tree_unflatten(exp.out_tree, fn(*jax.tree_util.tree_leaves(args)))

    def call(*args):
        if state.get("off"):
            return jitted(*args)
        sig = _arg_signature(args)
        entry = state.get(sig)
        if entry is not None:
            obs.inc("trace_cache.memo_hit")
            return run(entry, args)
        _register_trees()
        digest = hashlib.sha256(
            "\x1e".join(
                [
                    key_material,
                    sig,
                    _source_hash(),
                    jax.__version__,
                    jax.default_backend(),
                ]
            ).encode()
        ).hexdigest()
        entry = _EXP_MEMO.get(digest)
        if entry is not None:
            obs.inc("trace_cache.memo_hit")
        else:
            exp = _load_or_export(jitted, args, digest, multi_controller)
            if exp is None:
                state["off"] = True
                return jitted(*args)
            if len(_EXP_MEMO) >= _EXP_MEMO_MAX:
                _EXP_MEMO.pop(next(iter(_EXP_MEMO)))
            entry = _EXP_MEMO[digest] = (exp, runner(exp, args))
        state[sig] = entry
        return run(entry, args)

    def lower(*args):
        """``jitted.lower`` for the program ``call`` dispatches on ``args``
        (arrays or ``ShapeDtypeStruct``s): the jitted call that ran the
        exported program (``runner``), so that its compile is a load of the
        executable that ran; ``jitted`` itself where the cache is off for
        it (``obs.device.regions`` asks)."""
        entry = state.get(_arg_signature(args))
        if entry is None:
            return jitted.lower(*args)
        return entry[1].lower(*jax.tree_util.tree_leaves(args))

    call.lower = lower
    call.jitted = jitted
    return call
