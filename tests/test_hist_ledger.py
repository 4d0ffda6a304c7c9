"""The program accounts for its own device work (ISSUE 36): the histogram
work ledger against hand counts, the ``hist.*`` counters at each dispatch, each
kernel body's stated work against a walk over its own traced body, the region
map ``obs.device.regions()`` of what was compiled, and jit's trace seconds by
the span that was open."""

import collections
import contextlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mmlspark_tpu import obs
from mmlspark_tpu.engine import booster as booster_mod
from mmlspark_tpu.engine.booster import Dataset, train
from mmlspark_tpu.engine.tree import GrowConfig, full_tree_passes
from mmlspark_tpu.ops import pallas_hist

ROWS, CHUNK, COLS, ITERS = 2048, 1024, 6, 2
# 63 leaves at 8 splits a pass, the benchmark cells' grower
PARAMS = dict(objective="binary", num_iterations=ITERS, num_leaves=63, split_batch=8, min_data_in_leaf=1, hist_chunk=CHUNK,
              learning_rate=0.2, seed=3, verbosity=0, predict_backend="scan")


def _rows(seed=0):
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(ROWS, COLS))
    return X, (X[:, 0] - X[:, 1] + rng.normal(scale=0.5, size=ROWS) > 0).astype(np.float64)


@contextlib.contextmanager
def _recording():
    obs.reset()
    obs.flight.reset()
    obs.enable()
    try:
        yield
    finally:
        obs.disable()
        obs.reset()


def _counters(prefix="hist."):
    return {k: v for k, v in obs.snapshot()["counters"].items() if k.startswith(prefix)}


def _rise(before, after):
    return {k: v - before.get(k, 0.0) for k, v in after.items() if v != before.get(k, 0.0)}


# ---- the ledger against hand counts -------------------------------------------
PASSES = full_tree_passes(GrowConfig(num_bins=256, num_leaves=63, split_batch=8))  # 1, 2, 4, then 8 a pass: ten
M, N = 3 * 8 * 2, 128  # the nibble body's tile at W = 8 and 256 bins
CASES = {
    # what one fit of ITERS trees passes over, by (body, vals, scope): passes and the columns a pass reads (a
    # kernel's block is as tall as the matrix: six columns, and the refinement's one composed column)
    "float_scatter": (dict(), {("scatter", "f32", "hist_build"): (ITERS * (PASSES + 1), COLS)}),
    "float_pallas": (dict(hist_backend="pallas"), {("nibble", "f32", "hist_build"): (ITERS * (PASSES + 1), COLS)}),
    "quant_scatter": (dict(use_quantized_grad=True, num_grad_quant_bins=4), {
        ("scatter", "i16", "quant_hist"): (ITERS * (PASSES + 1), COLS), ("scatter", "f32", "quant_refine"): (ITERS * PASSES, 1)}),
    "quant_pallas": (dict(use_quantized_grad=True, num_grad_quant_bins=4, hist_backend="pallas"), {
        ("nibble", "i16", "quant_hist"): (ITERS * (PASSES + 1), COLS), ("nibble", "f32", "quant_refine"): (ITERS * PASSES, 1)}),
}


@pytest.mark.parametrize("name", sorted(CASES))
def test_ledger_counts_a_windowed_fits_passes_rowcols_and_flops(name):
    extra, want = CASES[name]
    assert PASSES == 10
    with _recording():
        train(dict(PARAMS, **extra), Dataset(*_rows()))
        got = _counters()
    for (body, vals, scope), (passes, cols) in want.items():
        label = f"{{body={body},scope={scope},vals={vals}}}"
        assert got["hist.passes" + label] == passes  # iterations x (full_tree_passes + the root's)
        assert got["hist.rowcols" + label] == passes * ROWS * cols
        assert got["hist.mxu_flops" + label] == (0 if body == "scatter" else 2 * M * N * passes * ROWS * cols)
        assert (got["hist.vpu_elems" + label] > 0) == (body != "scatter")
    assert {k for k in got if k.startswith("hist.passes{")} == {
        f"hist.passes{{body={b},scope={s},vals={v}}}" for b, v, s in want}


# ---- the row route's form, counted ------------------------------------------------
@pytest.mark.parametrize("backend, cols, body", [("pallas", 39, "block"), ("pallas", 220, "slots"), ("scatter", 39, "slots")])
def test_route_passes_count_the_form_that_moved_a_fits_rows(backend, cols, body):
    """``route.passes{body=block|slots}``: every windowed pass of the fit, under
    the one-pass Pallas body at 39 columns on the Pallas backend, under the slot
    loop at 220 (past ``ops/pallas_route.MAX_COLS``) and on the scatter backend."""
    rng = np.random.default_rng(cols)
    X = rng.normal(size=(ROWS, cols))
    y = (X[:, 0] + rng.normal(size=ROWS) > 0).astype(np.float64)
    with _recording():
        train(dict(PARAMS, hist_backend=backend), Dataset(X, y))
        got = _counters("route.")
    other = {"block": "slots", "slots": "block"}[body]
    assert got == {f"route.passes{{body={body}}}": ITERS * PASSES, f"route.passes{{body={other}}}": 0.0}


# ---- each body's stated work against its own traced body ------------------------
_ELEMENTWISE = {"convert_element_type", "eq", "gt", "mul", "add", "shift_right_arithmetic", "and"}


def _walk(jaxpr, times, acc):
    """Elements of every elementwise array and flops of every matmul a traced
    kernel body builds; a loop multiplies by its length."""
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        shape = eqn.outvars[0].aval.shape if eqn.outvars else ()
        if name in _ELEMENTWISE and shape:
            acc["vpu"] += times * int(np.prod(shape))
        elif name == "dot_general":
            ((lc, _), _), a, b = eqn.params["dimension_numbers"], eqn.invars[0].aval.shape, eqn.invars[1].aval.shape
            acc["mxu"] += times * 2 * int(np.prod(a)) * int(np.prod(b)) // int(np.prod([a[i] for i in lc]))
        subs = list(jax.core.jaxprs_in_params(eqn.params))
        for sub in subs[-1:] if name == "cond" else subs:  # the branch that accumulates
            _walk(sub, times * int(eqn.params["length"]) if name == "scan" else times, acc)
    return acc


@pytest.mark.parametrize("vals_dtype", [jnp.float32, jnp.int16], ids=["f32", "i16"])
# the factorized body at the slots of each route: the per-feature build's one, a window of 32, one of 8
@pytest.mark.parametrize("wrapper, leaves", [("_pallas_hist", 1), ("_pallas_hist_by_leaf", 32), ("_pallas_hist_by_leaf_nibble", 8)],
                         ids=["_pallas_hist-None", "_pallas_hist_by_leaf-32", "_pallas_hist_by_leaf_nibble-8"])
def test_stated_work_of_a_body_is_what_its_traced_body_builds(wrapper, leaves, vals_dtype):
    F, n = 16, 4096
    args = [jnp.zeros((F, n), jnp.uint8), jnp.zeros((3, n), vals_dtype), jnp.zeros((1, n), jnp.int32), jnp.zeros(1, jnp.int32)]
    kw = dict(num_leaves=leaves, num_bins=256, bm=2048, bf=8, rm=1024, chunk=n, interpret=True, precision="default")
    jaxpr = jax.make_jaxpr(lambda *a: pallas_hist._pallas_hist_by_leaf_nibble(*a, **kw))(*args)
    (eqn,) = (e for e in jaxpr.eqns if e.primitive.name in ("jit", "pjit"))
    work = pallas_hist.call_work(eqn)
    (call,) = (e for e in eqn.params["jaxpr"].eqns if e.primitive.name == "pallas_call")
    cells = int(np.prod(call.params["grid_mapping"].grid))
    body = _walk(call.params["jaxpr"], 1, collections.Counter())
    assert (work["body"], work["rowcols"], work["quant"]) == ("nibble", F * n, vals_dtype == jnp.int16)
    assert work["vpu_elems"] == cells * body["vpu"]
    assert work["mxu_flops"] == cells * body["mxu"] == 2 * (3 * leaves) * 256 * F * n


def test_stated_work_of_the_class_roots_body_is_what_its_traced_body_builds():
    """Twelve classes: a whole group of eight and a ragged one of four, each
    a pass over the chunk's rows at the nibble body's tile (M = 48, 12,288
    MXU flops a row-column)."""
    F, n, K = 16, 4096, 12
    args = [jnp.zeros((F, n), jnp.uint8), jnp.zeros((K, n), jnp.float32), jnp.zeros((K, n), jnp.float32),
            jnp.zeros((1, n), jnp.float32), jnp.zeros(1, jnp.int32)]
    kw = dict(num_bins=256, bm=2048, bf=8, rm=1024, chunk=n, interpret=True, precision="default")
    jaxpr = jax.make_jaxpr(lambda *a: pallas_hist._pallas_hist_class_roots(*a, **kw))(*args)
    (eqn,) = (e for e in jaxpr.eqns if e.primitive.name in ("jit", "pjit"))
    work = pallas_hist.call_work(eqn)
    (call,) = (e for e in eqn.params["jaxpr"].eqns if e.primitive.name == "pallas_call")
    cells = int(np.prod(call.params["grid_mapping"].grid))
    body = _walk(call.params["jaxpr"], 1, collections.Counter())
    assert (work["body"], work["passes"], work["rowcols"], work["quant"]) == ("class_root", 2, 2 * F * n, False)
    assert work["vpu_elems"] == cells * body["vpu"]
    assert work["mxu_flops"] == cells * body["mxu"] == 2 * M * N * 2 * F * n


def test_a_call_on_the_whole_matrix_counts_one_chunks_rowcols():
    """The chunk loop hands every call the whole matrix: the ledger reads a
    call's rows and columns off the inner grid and blocks, one chunk's, and a
    pass over ``chunks`` calls counts the matrix once."""
    from mmlspark_tpu.ops.histogram import build_histogram_by_leaf

    F, chunk, chunks, W = 39, 2048, 3, 8
    n = chunks * chunk
    args = (jnp.zeros((F, n), jnp.uint8), jnp.zeros((3, n), jnp.float32), jnp.zeros(n, jnp.int32))

    def one_pass(*a):
        with jax.named_scope("hist_build"):
            return build_histogram_by_leaf(*a, W, 256, backend="pallas", chunk=chunk)

    jaxpr = jax.make_jaxpr(one_pass)(*args)
    (loop,) = (e for e in jaxpr.eqns if e.primitive.name == "scan")
    (eqn,) = (e for e in loop.params["jaxpr"].eqns if e.params.get("name") == "_pallas_hist_by_leaf_nibble")
    assert eqn.invars[0].aval.shape == (F, n)  # the operand is the matrix, not a chunk of it
    work = pallas_hist.call_work(eqn)
    assert work["rowcols"] == F * chunk and work["mxu_flops"] == 2 * M * N * F * chunk  # 12,288 a row-column
    (ledger,) = booster_mod.hist_ledger(jaxpr).values()
    assert ledger["passes"] == 1 and ledger["rowcols"] == F * n and ledger["mxu_flops"] == 12_288 * F * n


# ---- a multiclass fit's roots, and a fit of one tree an iteration ---------------
def test_a_multiclass_fit_counts_its_roots_as_a_pass_for_eight_classes():
    """K = 100: the roots in ⌈100/8⌉ = 13 passes an iteration, each over every
    row and column, and every class tree's windowed passes without a root
    pass of its own."""
    K = 100
    X, _ = _rows(5)
    y = np.floor((X[:, 0] - X[:, 0].min()) / np.ptp(X[:, 0]) * (K - 1e-9))
    params = dict(PARAMS, objective="multiclass", num_class=K, num_leaves=4, split_batch=2, hist_backend="pallas")
    passes = full_tree_passes(GrowConfig(num_bins=256, num_leaves=4, split_batch=2))
    with _recording():
        train(params, Dataset(X, y))
        got = _counters()
    roots, trees = "{body=class_root,scope=hist_build,vals=f32}", "{body=nibble,scope=hist_build,vals=f32}"
    assert got["hist.passes" + roots] == ITERS * 13
    assert got["hist.rowcols" + roots] == ITERS * 13 * ROWS * COLS
    assert got["hist.mxu_flops" + roots] == 2 * M * N * ITERS * 13 * ROWS * COLS
    assert got["hist.passes" + trees] == ITERS * K * passes
    assert {k for k in got if k.startswith("hist.passes{")} == {"hist.passes" + roots, "hist.passes" + trees}


def test_a_fit_of_one_tree_an_iteration_builds_its_own_root():
    """K = 1 at the Criteo cells' shape (39 columns, 63 leaves at 8 splits a
    pass): no class-root pass, and 11 passes a tree, the root's among them."""
    rng = np.random.default_rng(6)
    X = rng.normal(size=(ROWS, 39))
    y = (X[:, 0] - X[:, 1] > 0).astype(np.float64)
    with _recording():
        train(dict(PARAMS, hist_backend="pallas"), Dataset(X, y))
        got = _counters()
    assert not [k for k in got if "class_root" in k]
    label = "{body=nibble,scope=hist_build,vals=f32}"
    assert got["hist.passes" + label] / ITERS == PASSES + 1 == 11
    assert got["hist.rowcols" + label] == ITERS * 11 * ROWS * 39


# ---- the counters at each dispatch, and one grower trace a program -------------
def test_counters_rise_by_the_ledger_at_each_dispatch_and_the_grower_is_traced_once(monkeypatch):
    traced = []
    grow_jaxpr = booster_mod._grow_jaxpr
    monkeypatch.setattr(booster_mod, "_grow_jaxpr", lambda *a: traced.append(1) or grow_jaxpr(*a))
    params = dict(PARAMS, num_leaves=15, split_batch=4, scan_dispatch_iters=1)  # two dispatches of one iteration
    X, y = _rows(1)
    with _recording():
        train(params, Dataset(X, y))
        first = _counters()
        notes = [n for _, n in booster_mod._SCAN_CACHE.values() if "hist_ledger" in n][-1]
        train(params, Dataset(X, y))  # a _SCAN_CACHE hit
        second = _counters()
        hit = [s["attrs"]["scan_cache_hit"] for s in obs.flight.spans("booster.program")]
    assert hit == [False, True] and len(traced) == 1
    (label, work), = notes["hist_ledger"].items()
    assert label == ("scatter", "f32", "hist_build") and notes["merge_ledger"] is None
    for name, per_iter in work.items():
        key = f"hist.{name}{{body=scatter,scope=hist_build,vals=f32}}"
        assert first[key] == per_iter * ITERS and second[key] == 2 * per_iter * ITERS
    train(params, Dataset(X, y))  # obs off: nothing counted, nothing traced
    assert not _counters() and len(traced) == 1
    assert not obs.device._PROGRAMS


# ---- the region map -----------------------------------------------------------
HLO = """\
HloModule jit_f

%fused_computation.2 (p: f32[8]) -> f32[8] {
  %p = f32[8]{0} parameter(0)
  %mul.1 = f32[8]{0} multiply(%p, %p), metadata={op_name="jit(f)/while/body/row_route/mul"}
  ROOT %copy.9 = f32[8]{0:T(256)} copy(%mul.1)
}

ENTRY %main.3 (x: f32[8]) -> (f32[8], s32[4]) {
  %x = f32[8]{0} parameter(0)
  %fusion.7 = f32[8]{0:T(256)} fusion(%x), kind=kLoop, calls=%fused_computation.2
  %add.2 = (s32[4]{0}, f32[2]{0}) add(%x, %x), metadata={op_name="jit(f)/hist_build/vmap(chunk_copy)/add" source_line=3}
  ROOT %tuple = (f32[8]{0}, s32[4]{0}) tuple(%fusion.7, %add.2), metadata={op_name="jit(f)/tuple"}
}
"""


def test_module_regions_reads_the_innermost_scope_and_a_bare_fusions_body():
    got = obs.device.module_regions(HLO)
    assert got["fusion.7", "f32[8]"] == "row_route"  # no metadata of its own: its computation's
    assert got["add.2", "(s32[4]"] == "chunk_copy"  # the innermost of two, through a transform's wrapper
    assert got["tuple", "(f32[8]"] is None and got["x", "f32[8]"] is None
    assert got["mul.1", "f32[8]"] == "row_route"


def test_regions_are_lazy_hold_every_scope_entered_and_compile_once():
    X, y = _rows(2)
    with _recording():
        ds = Dataset(X, y)
        model = train(PARAMS, ds)
        model._raw_scores_binned(jnp.asarray(model.bin_mapper.transform(X)))
        assert [k[0] for k in obs.device._PROGRAMS] == ["booster.fit", "booster.scorer"]
        assert all(entry[2] is None for entry in obs.device._PROGRAMS.values())  # noted, not lowered
        before = dict(obs.snapshot()["counters"])
        train(PARAMS, ds)  # a cached program: noting it again lowers nothing
        assert "jit.lower_s" not in _rise(before, obs.snapshot()["counters"]) and len(obs.device._PROGRAMS) == 2
        maps = obs.device.regions()  # asked: each program's executable looked up and walked
        assert all(entry[2] is not None for entry in obs.device._PROGRAMS.values())
        before = dict(obs.snapshot()["counters"])
        assert obs.device.regions() == maps  # memoised: a second call compiles nothing
        assert not {"jit.lower_s", "jit.backend_s", "jit.traces"} & set(_rise(before, obs.snapshot()["counters"]))
    fit, scorer = (collections.Counter(maps[name].values()) for name in sorted(maps))
    for scope in ("hist_build", "chunk_copy", "row_route", "split_scan", "leaf_stats", "leaf_delta"):
        assert fit[scope] > 0, scope
    assert scorer["replay_step"] > 0
    assert set(fit) | set(scorer) <= set(obs.device.SCOPES) | {None}


# ---- retraces say what was traced -----------------------------------------------
def test_trace_seconds_carry_the_open_span_and_sum_to_the_total():
    X, y = _rows(3)
    with _recording():
        model = train(PARAMS, Dataset(X, y))
        bins = jnp.asarray(model.bin_mapper.transform(X))
        before = dict(obs.snapshot()["counters"])
        model._raw_scores_binned(bins)  # a new Booster's first evaluation builds and traces its scorer
        rise = _rise(before, obs.snapshot()["counters"])
        after = dict(obs.snapshot()["counters"])
    assert rise["jit.trace_s{span=booster.score_binned}"] > 0 and rise["jit.traces{span=booster.score_binned}"] >= 1
    for name in ("jit.trace_s", "jit.traces"):
        by_span = [v for k, v in after.items() if k.startswith(name + "{span=")]
        assert sum(by_span) == pytest.approx(after[name])


# ---- over a mesh: a chip's own rows, and the merge's region --------------------
def test_sharded_fit_counts_a_chips_rowcols_and_maps_the_merge():
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    from mmlspark_tpu.data.streaming import StreamedDataset
    from mmlspark_tpu.ops.binning import BinningAuthority
    from mmlspark_tpu.parallel.mesh import DATA_AXIS, default_mesh

    D, mesh = 4, default_mesh(4)
    X, y = _rows(4)
    authority = BinningAuthority.fit(X, max_bin=255, seed=0)
    sharding = NamedSharding(mesh, P(DATA_AXIS, None))
    bins = jax.device_put(authority.mapper.transform(X).astype(np.uint8), sharding)
    ds = StreamedDataset(authority=authority, binned_dev=bins, packed=False, num_rows=ROWS, num_features=COLS, label=y)
    with _recording():
        model = train(dict(PARAMS, tree_learner="data", hist_chunk=256), ds, mesh=mesh)
        model._raw_scores_binned(bins)
        got = _counters()
        maps = obs.device.regions()
    label = "{body=scatter,scope=hist_build,vals=f32}"
    assert got["hist.passes" + label] == ITERS * (PASSES + 1)
    assert got["hist.rowcols" + label] == ITERS * (PASSES + 1) * (ROWS // D) * COLS  # the shard's rows, not the host's
    fit, scorer = (collections.Counter(maps[name].values()) for name in sorted(maps))
    assert fit["hist_merge"] > 0 and fit["chunk_copy"] > 0 and fit["row_route"] > 0 and scorer["replay_step"] > 0
