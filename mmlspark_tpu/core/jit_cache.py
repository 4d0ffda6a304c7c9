"""Persistent XLA compile cache — library-level cold-start amortization.

The reference has ZERO compile cost: LightGBM's C++ trains immediately
(SURVEY.md §3.1), so every second XLA spends compiling is a real regression
for a first-time user.  JAX's persistent compilation cache eliminates this
on every process AFTER the first on a machine, which matches how the
reference's long-lived executors amortize JVM/native warmup — and it is ON
for library users, not just the benchmark.

Enabled automatically from :func:`mmlspark_tpu.engine.booster.train` (and
therefore every estimator facade).

Directory contract (:func:`cache_dir`) — ONE rule for all three artifact
kinds (jax's XLA entries, the ``aot-*``/``pft-*`` artifacts below, and
``core/trace_cache``'s ``*.jaxexp`` blobs):

- ``JAX_COMPILATION_CACHE_DIR`` set → everything lives in that directory
  and this module never writes ``jax_compilation_cache_dir``;
- else a ``jax_compilation_cache_dir`` the caller configured in code;
- else ``<checkout>/.jax_cache`` next to the package (git-ignored) — a
  fixed path derived from the package location, never the home
  directory, a temp dir, a pid or a clock, so two processes started from
  the same checkout always share entries.

The same thresholds apply in every branch: every program is cached
(``jax_persistent_cache_min_compile_time_secs=0`` — the scan-program zoo is
many small programs and the write cost is trivial next to any compile), and
the directory is pruned to a size cap at enable time.

Controls:

- ``MMLSPARK_TPU_NO_COMPILE_CACHE=1`` — opt out.
- ``MMLSPARK_TPU_COMPILE_CACHE_MAX_MB`` — size cap for the LRU prune
  (default 128: the in-checkout directory is copied wherever the tree is).

Hit/miss accounting rides jax's public ``jax.monitoring`` events
(``/jax/compilation_cache/cache_hits`` / ``cache_misses``) into the
``jit_cache.hit`` / ``jit_cache.miss`` obs counters.  Those events carry no
cache key, so jax's own entries are NOT touched on a hit: their LRU order
in :func:`prune_cache_dir` is the filesystem's atime/mtime (relatime-coarse).
The artifacts this module reads itself are touched explicitly
(:func:`record_cache_hit`).

What jit itself costs rides jax's duration events the same way
(:func:`_on_jit_duration`): ``jit.traces`` / ``jit.trace_s`` from
``/jax/core/compile/jaxpr_trace_duration`` (a total, and the same again
under ``span=<the innermost obs span open on the thread, or none>``: what
was being done when something was traced), ``jit.lower_s`` from
``/jax/core/compile/jaxpr_to_mlir_module_duration``, ``jit.backend_s``
from ``/jax/core/compile/backend_compile_duration`` (the backend's compile
OR its load from the persistent cache).  Listeners are registered whether
or not the persistent cache is in use.

AOT artifacts (ISSUE 11 / ROADMAP item 3a)
------------------------------------------
jax's persistent cache only skips the XLA *compile*; a fresh process
still pays the full trace/lower before the cache is even consulted.  The
``aot-*`` artifact kind stores the WHOLE compiled executable
(``jax.experimental.serialize_executable``), so a second process goes
straight from disk bytes to a callable.  The ``pft-*`` kind stores the
packed-forest host arrays (the Python per-tree pack loop).  Both kinds ride
the same LRU prune — :func:`prune_cache_dir` is kind-agnostic by
construction (it orders every file by last access, whatever its prefix).

Keys are content fingerprints (:func:`aot_fingerprint`): schema
version, jax/jaxlib versions, backend platform + device kind + device
count, ``XLA_FLAGS``, the ids of the devices the arguments live on (the
executable is compiled FOR those devices and is loaded back onto exactly
them — a one-device program stays one-device on a 4- or 8-device host),
the caller's static meta (forest slice, bin config), and every argument
leaf's shape/dtype.  Any drift lands on a different key; stale artifacts
simply age out of the LRU.  An artifact whose bytes do not unpickle
(truncated write, disk rot) is deleted and reported as a miss; any other
load failure — an API mismatch with the installed jax — raises.

obs: ``jit_cache.aot_serialize`` / ``jit_cache.aot_deserialize`` spans
time the (de)serialization; ``jit_cache.aot_hits`` / ``aot_misses`` /
``aot_bytes`` counters feed :func:`cache_counters` and
``tools.obs report``.
"""

from __future__ import annotations

import os
import pickle
import threading

from mmlspark_tpu import obs

_done = False
_listening = False

AOT_SCHEMA = 2  # bump to invalidate every serialized artifact at once

_DEFAULT_MAX_MB = 128.0

# <checkout>/.jax_cache: the package's parent directory is the checkout
# root for a source tree (the only way this repo is run).
_CHECKOUT_CACHE_DIR = os.path.join(
    os.path.dirname(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    ),
    ".jax_cache",
)

_HIT_EVENT = "/jax/compilation_cache/cache_hits"
_MISS_EVENT = "/jax/compilation_cache/cache_misses"
_TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
_LOWER_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
_BACKEND_EVENT = "/jax/core/compile/backend_compile_duration"


def cache_dir() -> str:
    """The one directory every compiled artifact lives in (module
    docstring: env var, else the caller's jax config, else the fixed
    in-checkout path)."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    return jax.config.jax_compilation_cache_dir or _CHECKOUT_CACHE_DIR


def enable_compile_cache() -> bool:
    """Idempotently point jax at the persistent compile cache.

    Returns True when the cache is (now) enabled, False on the opt-out or
    when the directory cannot be created (read-only checkout).
    """
    global _done
    if _done:
        return True
    _listen_for_cache_events()  # retraces are counted with the cache off too
    if os.environ.get("MMLSPARK_TPU_NO_COMPILE_CACHE"):
        return False
    import jax

    path = cache_dir()
    try:
        os.makedirs(path, exist_ok=True)
    except OSError:
        return False
    if path == _CHECKOUT_CACHE_DIR:  # neither the env var nor the caller chose
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    # Min-time-0 writes EVERY program, so the dir grows without bound
    # across shapes/configs — prune to the size cap, oldest-access first,
    # at enable time (once per process).
    prune_cache_dir(path)
    _done = True
    # jax lazily imports etils.epath inside the FIRST compile's
    # get_compile_options once a cache dir is set — ~75 ms of pure
    # Python import that would otherwise land in the first predict's
    # cold window.  Front-load it here, where enabling the cache is
    # already declared process setup.
    import etils.epath  # noqa: F401

    return True


def record_cache_hit(path: str) -> None:
    """Refresh ``path``'s timestamps after a cache hit.

    Most Linux mounts use relatime (atime refreshed at most once per 24 h),
    so a hot entry's atime looks cold and :func:`prune_cache_dir`'s LRU
    would evict it ahead of genuinely stale entries.  ``os.utime`` bumps
    mtime too, which every mount option keeps accurate.
    """
    try:
        os.utime(path)
    except OSError:
        pass


def _on_cache_event(event: str, **_kwargs) -> None:
    if event == _HIT_EVENT:
        obs.inc("jit_cache.hit")
    elif event == _MISS_EVENT:
        obs.inc("jit_cache.miss")
        # Unified compile-event ledger (obs/device.py): a cache miss here
        # is exactly one XLA compile paid.
        obs.device.compile_event("compile")


_trace_depth = threading.local()


def _on_jit_scalar(event: str, _value, **_kwargs) -> None:
    """jax announces a trace's START as a scalar event: the depth it keeps
    lets :func:`_on_jit_duration` tell a jitted function traced inside
    another's trace (its seconds are already in the outer one's) from a
    top-level one."""
    if event == _TRACE_EVENT:
        _trace_depth.n = getattr(_trace_depth, "n", 0) + 1


def _on_jit_duration(event: str, duration: float, **_kwargs) -> None:
    """``jit.traces`` counts the top-level calls that left jit's C++ fast
    path and went through its tracing path — every retrace, and also a
    call that then hits jax's own trace cache, which costs microseconds —
    and ``jit.trace_s`` their seconds; a retrace in a timed path (a new
    ``jax.jit`` object around an old function) shows in both although the
    persistent cache hits.  ``jit.lower_s`` is the lowering to MLIR,
    ``jit.backend_s`` the backend's compile or its load from the cache."""
    if event == _TRACE_EVENT:
        n = _trace_depth.n = max(getattr(_trace_depth, "n", 1) - 1, 0)
        if n == 0:
            obs.inc("jit.traces")
            obs.inc("jit.trace_s", duration)
            # the same again by what was being done: the innermost obs
            # span open on this thread (a new Booster's first evaluation
            # traces its scorer under ``booster.score_binned``)
            span = obs.tracing.current_span_name() or "none"
            obs.inc("jit.traces", span=span)
            obs.inc("jit.trace_s", duration, span=span)
            # Unified compile-event ledger (obs/device.py): a Python
            # (re-)trace, wherever it happens (a trace-cache miss's
            # export included).
            obs.device.compile_event("trace")
    elif event == _LOWER_EVENT:
        obs.inc("jit.lower_s", duration)
    elif event == _BACKEND_EVENT:
        obs.inc("jit.backend_s", duration)


def _listen_for_cache_events() -> None:
    """Feed jax's persistent-cache hit/miss events and its trace / lower /
    backend durations into the obs counters (registered once per
    process)."""
    global _listening
    if _listening:
        return
    import jax.monitoring

    jax.monitoring.register_event_listener(_on_cache_event)
    jax.monitoring.register_scalar_listener(_on_jit_scalar)
    jax.monitoring.register_event_duration_secs_listener(_on_jit_duration)
    _listening = True


def cache_counters() -> dict:
    """Current hit/miss/pruned counters for the persistent cache (from the
    obs registry; zeros while obs is disabled).  The serving readiness
    gate snapshots these at startup: pre-warming is proven by the miss
    AND hit counters staying flat across first real requests — a warmed
    shape never reaches the compilation cache at all.  ``aot_*`` keys
    count the serialized-executable artifacts: a replica that warmed
    from disk shows ``aot_hits`` with ``miss`` flat.
    """
    counters = obs.snapshot().get("counters", {})
    return {
        key: float(counters.get(f"jit_cache.{key}", 0.0))
        for key in ("hit", "miss", "pruned",
                    "aot_hits", "aot_misses", "aot_bytes")
    }


# ---------------------------------------------------------------------------
# AOT artifacts: serialized executables + packed-forest blobs
# ---------------------------------------------------------------------------
def _arg_devices(args) -> list:
    """The devices ``args``' array leaves live on, in id order — the
    devices a program lowered from these arguments is compiled for
    (the first device when no leaf is a device array)."""
    import jax

    devs = {
        d.id: d
        for leaf in jax.tree_util.tree_leaves(args)
        if isinstance(leaf, jax.Array)
        for d in leaf.devices()
    }
    return [devs[i] for i in sorted(devs)] or jax.devices()[:1]


def aot_fingerprint(kind: str, meta: dict, args=()) -> str:
    """Content fingerprint for an AOT artifact.

    Hashes everything that determines executable validity: schema
    version, jax + jaxlib versions, backend platform / device kind /
    device count, ``XLA_FLAGS``, the ids of the devices ``args`` live on
    (:func:`load_aot` loads the executable back onto exactly those), the
    caller's static ``meta`` (e.g. forest slice T/K/depth, bin config,
    raw_score), and the shape+dtype of every leaf in ``args`` (the bucket
    shape lives here).  Model WEIGHTS are deliberately excluded for
    executables — they are runtime arguments, so one artifact serves
    every model version with the same shapes (a hot-swap warms for free).
    """
    import hashlib
    import json

    import jax
    import jaxlib

    devs = jax.devices()
    spec = [
        (tuple(int(d) for d in getattr(leaf, "shape", ())),
         str(getattr(leaf, "dtype", type(leaf).__name__)))
        for leaf in jax.tree_util.tree_leaves(args)
    ]
    blob = json.dumps(
        {
            "schema": AOT_SCHEMA,
            "kind": kind,
            "jax": jax.__version__,
            "jaxlib": jaxlib.__version__,
            "backend": jax.default_backend(),
            "device_kind": devs[0].device_kind,
            "device_count": len(devs),
            "devices": [d.id for d in _arg_devices(args)],
            "xla_flags": os.environ.get("XLA_FLAGS", ""),
            "meta": meta,
            "args": spec,
        },
        sort_keys=True,
        default=str,
    )
    return hashlib.sha256(blob.encode()).hexdigest()[:32]


def _artifact_path(kind: str, key: str) -> str:
    return os.path.join(cache_dir(), f"{kind}-{key}")


def save_artifact(kind: str, key: str, data: bytes) -> bool:
    """Atomically write an artifact blob into the cache dir (tmp +
    rename), then prune the dir to its LRU budget.  Returns False when
    the directory is not writable or caching is opted out
    (``MMLSPARK_TPU_NO_COMPILE_CACHE``)."""
    if os.environ.get("MMLSPARK_TPU_NO_COMPILE_CACHE"):
        return False
    try:
        d = cache_dir()
        os.makedirs(d, exist_ok=True)
        path = _artifact_path(kind, key)
        tmp = f"{path}.tmp.{os.getpid()}"
        with open(tmp, "wb") as fh:
            fh.write(data)
        os.replace(tmp, path)
        prune_cache_dir(d)
        return True
    except OSError:
        return False


def load_artifact(kind: str, key: str):
    """Artifact bytes for ``kind-key``, bumping its LRU timestamp on the
    way out; ``None`` when absent (or caching is opted out)."""
    if os.environ.get("MMLSPARK_TPU_NO_COMPILE_CACHE"):
        return None
    try:
        path = _artifact_path(kind, key)
        with open(path, "rb") as fh:
            data = fh.read()
        record_cache_hit(path)
        return data
    except OSError:
        return None


def save_aot(key: str, compiled) -> bool:
    """Serialize a compiled executable under ``aot-<key>``; False when the
    artifact could not be written (see :func:`save_artifact`)."""
    from jax.experimental import serialize_executable as se

    with obs.span("jit_cache.aot_serialize", key=key):
        data = pickle.dumps(se.serialize(compiled))
    if save_artifact("aot", key, data):
        obs.inc("jit_cache.aot_bytes", float(len(data)))
        return True
    return False


def load_aot(key: str, devices):
    """Deserialize the ``aot-<key>`` executable onto ``devices`` (the
    devices it was compiled for — part of the key, see
    :func:`aot_fingerprint`); ``None`` on miss.

    jax's default (``execution_devices=None``) loads onto EVERY device of
    the backend, which turns a one-device program into an N-shard one on
    a multi-device host — hence the explicit list.  A present artifact
    whose bytes do not unpickle is deleted and reported as a miss, so the
    caller's trace path replaces it; anything else raises.
    """
    data = load_artifact("aot", key)
    if data is not None:
        from jax.experimental import serialize_executable as se

        try:
            payload = pickle.loads(data)
        except (pickle.UnpicklingError, EOFError):
            try:
                os.remove(_artifact_path("aot", key))
            except OSError:
                pass
        else:
            with obs.span("jit_cache.aot_deserialize", key=key):
                exe = se.deserialize_and_load(
                    *payload, execution_devices=devices
                )
            obs.inc("jit_cache.aot_hits")
            # Unified compile-event ledger (obs/device.py): an AOT load
            # replaces a compile with a deserialize.
            obs.device.compile_event("deserialize")
            return exe
    obs.inc("jit_cache.aot_misses")
    return None


def load_or_compile_aot(kind: str, meta: dict, args, lower):
    """Disk-first compiled-executable resolution shared by the
    single-model serving program (``kind="packed_raw_rows"``, booster)
    and the co-resident super-table program
    (``kind="multi_packed_raw_rows"``, serve.coresident): fingerprint the
    statics + arg shapes + arg devices, try ``load_aot``, and only on a
    genuine miss call ``lower()`` (returning a jax lowering), compile,
    and persist.

    Returns ``(executable, how)`` with ``how`` in ``{"from_disk",
    "traced"}``.
    """
    key = aot_fingerprint(kind, meta, args)
    exe = load_aot(key, _arg_devices(args))
    if exe is not None:
        return exe, "from_disk"
    exe = lower().compile()
    save_aot(key, exe)
    return exe, "traced"


def save_pft(key: str, arrays_state: bytes) -> bool:
    """Store pickled packed-forest host arrays under ``pft-<key>`` (the
    per-tree Python pack loop is the dominant from-disk cold cost)."""
    if save_artifact("pft", key, arrays_state):
        obs.inc("jit_cache.aot_bytes", float(len(arrays_state)))
        return True
    return False


def load_pft(key: str):
    """Pickled packed-forest bytes for ``pft-<key>`` (``None`` on miss);
    counts into the same aot hit/miss counters — it is part of the same
    warm-from-disk story."""
    data = load_artifact("pft", key)
    if data is not None:
        obs.inc("jit_cache.aot_hits")
        return data
    obs.inc("jit_cache.aot_misses")
    return None


def prune_cache_dir(path: str, max_mb: float | None = None) -> int:
    """Best-effort LRU prune of ``path`` to ``max_mb``; returns files removed.

    Eviction order is max(atime, mtime).  Relatime mounts refresh atime at
    most once per 24 h, so the artifacts this package reads itself record
    their hits explicitly by bumping mtime (:func:`record_cache_hit`) — a
    freshly-hit artifact outlives a stale one regardless of mount options;
    jax's own entries are ordered by what the filesystem recorded (module
    docstring).  Never raises; concurrent processes racing on the same
    file just skip it.

    jax keeps one entry as two files, ``<key>-cache`` and (where its own
    eviction is on, ``jax_compilation_cache_max_size``) ``<key>-atime``:
    the pair goes or stays as ONE unit, and a ``-cache`` whose ``-atime``
    is gone is removed whatever the budget — jax's eviction raises on
    such an orphan, and from then on refuses every new entry that needs
    room (seen on the chip: the 17-minute fit compiled again in every
    process).
    """
    if max_mb is None:
        try:
            max_mb = float(
                os.environ.get(
                    "MMLSPARK_TPU_COMPILE_CACHE_MAX_MB", _DEFAULT_MAX_MB
                )
            )
        except ValueError:  # e.g. "2g" — keep the never-raises contract
            max_mb = _DEFAULT_MAX_MB
    budget = max_mb * (1 << 20)
    try:
        units: dict = {}  # entry -> [last access, bytes, its files]
        with os.scandir(path) as it:
            for e in it:
                if e.is_file():
                    st = e.stat()
                    unit = units.setdefault(
                        e.name.removesuffix("-cache").removesuffix("-atime"),
                        [0.0, 0, []],
                    )
                    unit[0] = max(unit[0], st.st_atime, st.st_mtime)
                    unit[1] += st.st_size
                    unit[2].append(e.path)
        evicts = _jax_evicts()
        victims = [
            u for u in units.values()
            if evicts and len(u[2]) == 1 and u[2][0].endswith("-cache")
        ]  # orphans first, whatever the budget
        total = sum(u[1] for u in units.values() if u not in victims)
        for u in sorted(u for u in units.values() if u not in victims):
            if total <= budget:
                break
            victims.append(u)
            total -= u[1]
        removed = 0
        for _, _, paths in victims:
            for p in paths:
                try:
                    os.remove(p)
                    removed += 1
                except OSError:
                    continue
        if removed:
            obs.inc("jit_cache.pruned", removed)
        return removed
    except OSError:
        return 0


def _jax_evicts() -> bool:
    """jax's own LRU eviction is on (it then keeps ``-atime`` files)."""
    import jax

    return jax.config.jax_compilation_cache_max_size != -1
