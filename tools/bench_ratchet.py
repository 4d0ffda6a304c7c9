"""CI perf ratchet: pin the committed bench ledgers to enforced floors.

The repo's builder-run perf claims live in hand-regenerated ledgers at
the repo root (``PREDICT_BENCH.json``, ``INGEST_BENCH.json``,
``MULTICHIP_COMMS.json``, ``MULTI_TRAIN_BENCH.json``,
``LOOP_BENCH.json``, ``BENCH_POD.json``) — all ``"backend": "cpu"``
today; the chip's numbers are the driver's ``PERF_LEDGER.jsonl``, which
this tool does not read.  Nothing in CI
stopped a PR from silently regressing them — a bench rerun could write
a worse number and the diff would merge green (ROADMAP item 5(b)).

This tool closes the loop in three layers:

1. **Schema validation** — every ledger is validated against
   :data:`LEDGER_SCHEMAS` (required dotted paths + types) before any
   number is read, so a truncated or hand-mangled ledger fails loudly
   (exit 2), not as a silently-skipped gate.
2. **Ratchet gates** — ``RATCHET.json`` (committed) pins each headline
   metric to a bound derived from the last blessed ledger value plus a
   per-backend tolerance band (:data:`GATES`).  Default mode re-reads
   the ledgers and evaluates every gate: a regressed ledger (e.g. a
   bench rerun that got slower, or a hand edit) exits 1.  Gates whose
   claim is accelerator-only (the INGEST steady-vs-host ratio on
   ``backend: cpu``, where the ledger itself records
   ``gate_enforced: false``) are evaluated but ADVISORY — reported,
   never fatal.  Wall-clock gates ratchet the *recorded* ledger value
   (machine-pinned by the bench protocol); byte/ratio/bitwise gates are
   machine-independent and always enforced.
3. **Smoke replay** (``--smoke``) — re-runs the cheap smoke benches
   (``bench_predict --smoke``, ``bench_ingest --smoke``) into
   ``bench_out/`` and asserts the MECHANISM invariants on the fresh
   outputs (bitwise-vs-scan everywhere, AOT warm-from-disk beats the
   cleared cold, multi-chunk ingest ran, gate fields present).  Wall
   numbers from a CI box are never compared against bench-box ledgers.

``--update`` re-derives ``RATCHET.json`` from the current ledgers
(value ± band) — the deliberate re-blessing step after a bench rerun;
the diff review is where a regression gets caught by a human instead.

Exit codes: 0 all enforced gates pass; 1 enforced gate failed;
2 schema/IO error.  ``--ledger-dir`` points at an alternate ledger set
(CI's seeded-regression leg points it at
``tests/fixtures/ratchet_regression`` and asserts exit 1).

Usage::

    python -m tools.bench_ratchet [--smoke] [--update] [--json]
        [--ledger-dir DIR] [--ratchet FILE]
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH_OUT = os.path.join(REPO, "bench_out")

# ---------------------------------------------------------------------------
# Ledger schemas: required dotted paths -> type (or tuple of types).
# ``[]`` in a path means "every element of this list".  Optional keys are
# NOT listed — the schema pins what the ratchet and the docs rely on.
# ---------------------------------------------------------------------------

LEDGER_SCHEMAS = {
    "PREDICT_BENCH.json": {
        "bench": str,
        "config.iters": int,
        "config.batches": list,
        "results": list,
        "results[].backend": str,
        "results[].batch": int,
        "results[].p50_ms": (int, float),
        "results[].p99_ms": (int, float),
        "results[].rows_per_s": (int, float),
        "results[].bitwise_vs_scan": bool,
        "cold_start.cleared_cold_ms": (int, float),
        "cold_start.cold_from_disk_ms": (int, float),
        "cold_start.speedup": (int, float),
        "cold_start.bitwise_across_processes": bool,
    },
    "INGEST_BENCH.json": {
        "metric": str,
        "value": (int, float),
        "unit": str,
        "host_total_s": (int, float),
        "vs_host_binning": (int, float),
        "gate_steady_le_half_host": bool,
        "gate_enforced": bool,
        "gate_byte_ws_le_half_int32": bool,
        "byte_hist_working_set_bytes": int,
        "int32_hist_working_set_bytes": int,
        "backend": str,
    },
    "MULTICHIP_COMMS.json": {
        "n_devices": int,
        "mesh_shape": list,
        "ledger.allreduce": dict,
        "ledger.hierarchical.inter_host_bytes": int,
        "ledger.hierarchical.intra_host_bytes": int,
        "ledger.hierarchical.inter_bytes_ratio_vs_flat_allreduce":
            (int, float),
        "ledger.hierarchical.auc_drift_vs_f32_serial": (int, float),
    },
    "MULTI_TRAIN_BENCH.json": {
        "bench": str,
        "backend": str,
        "results": list,
        "results[].k": int,
        "results[].sequential_s": (int, float),
        "results[].stacked_s": (int, float),
        "results[].speedup": (int, float),
        "results[].parity_bitwise": bool,
        "results[].dispatches": int,
        "e2e.requests": int,
        "e2e.errors": int,
        "e2e.batched_dispatches": int,
        "gates.parity_bitwise": bool,
        "gates.one_dispatch_per_stack": bool,
        "gates.e2e_zero_errors": bool,
        "gates.e2e_swap_parity": bool,
    },
    "BENCH_POD.json": {
        "bench": str,
        "backend": str,
        "iters": int,
        "dataset.rows": int,
        "dataset.shards": int,
        "runs.p1.pipeline_wall_s": (int, float),
        "runs.p1.rows_per_s_process": (int, float),
        "runs.p2.pipeline_wall_s": (int, float),
        "runs.p2.rows_per_s_process": (int, float),
        "runs.p4.pipeline_wall_s": (int, float),
        "runs.p4.rows_per_s_process": (int, float),
        "scaling.two_proc": (int, float),
        "scaling.gate_enforced": bool,
        "parity.bitwise": bool,
        "parity.digest_2proc": str,
        "resume.ok": bool,
        "resume.iterations_at_kill": int,
        "overlap.p1.ratio": (int, float),
    },
    "LOOP_BENCH.json": {
        "bench": str,
        "backend": str,
        "steady.requests": int,
        "shifted.requests": int,
        "recovery.excess_psi": (int, float),
        "recovery.psi_alert": (int, float),
        "rollback.restored_version": int,
        "gates.zero_5xx": bool,
        "gates.alarm_fired": bool,
        "gates.promoted": bool,
        "gates.psi_recovered": bool,
        "gates.poisoned_rejected": bool,
        "gates.rollback_ok": bool,
        "gates.rollback_pin": bool,
    },
}

# ---------------------------------------------------------------------------
# Gates.  ``path`` is a dotted path into the named ledger; ``op`` is the
# pass direction for the CURRENT value vs the ratchet bound; ``band`` is
# the per-backend tolerance applied at --update time when deriving the
# bound from the blessed value (``None`` -> exact).  ``advisory_when``
# (optional) is a dotted ledger path whose falsy value demotes the gate
# to advisory — the INGEST steady gate is a device-vs-host claim the
# cpu ledger records honestly but does not enforce.
# ---------------------------------------------------------------------------

GATES = [
    {
        "id": "predict.p99_ms_bulk_packed",
        "ledger": "PREDICT_BENCH.json",
        "path": "results[backend=packed,batch=65536].p99_ms",
        "op": "<=",
        "band": {"cpu": 0.25, "*": 0.15},
    },
    {
        "id": "predict.cold_start_speedup",
        "ledger": "PREDICT_BENCH.json",
        "path": "cold_start.speedup",
        "op": ">=",
        # The 10x warm-from-disk claim is the hard floor regardless of
        # how much headroom the blessed run had.
        "band": {"*": 0.5},
        "min_bound": 10.0,
    },
    {
        "id": "predict.bitwise_vs_scan",
        "ledger": "PREDICT_BENCH.json",
        "path": "results[].bitwise_vs_scan",
        "op": "all_true",
        "band": None,
    },
    {
        "id": "predict.cold_bitwise_across_processes",
        "ledger": "PREDICT_BENCH.json",
        "path": "cold_start.bitwise_across_processes",
        "op": "all_true",
        "band": None,
    },
    {
        "id": "comms.inter_bytes_ratio",
        "ledger": "MULTICHIP_COMMS.json",
        "path": "ledger.hierarchical.inter_bytes_ratio_vs_flat_allreduce",
        "op": ">=",
        # Byte counting is deterministic — tight band on any backend.
        "band": {"*": 0.05},
    },
    {
        "id": "comms.inter_host_bytes",
        "ledger": "MULTICHIP_COMMS.json",
        "path": "ledger.hierarchical.inter_host_bytes",
        "op": "<=",
        "band": {"*": 0.05},
    },
    {
        "id": "ingest.steady_s",
        "ledger": "INGEST_BENCH.json",
        "path": "value",
        "op": "<=",
        "band": {"cpu": 0.20, "*": 0.10},
        "advisory_when": "gate_enforced",
    },
    {
        # cpu TREND gate (ISSUE 20): unlike ingest.steady_s (a
        # device-vs-host claim, advisory on cpu), this one is ALWAYS
        # enforced — the steady wall ratchets against its own blessed
        # record and may never re-bless above the pre-pipeline 3.61 s
        # (the ISSUE-17 ledger the 3-stage overlap had to beat).
        "id": "ingest.steady_trend",
        "ledger": "INGEST_BENCH.json",
        "path": "value",
        "op": "<=",
        "band": {"cpu": 0.25, "*": 0.10},
        "max_bound": 3.61,
    },
    {
        "id": "ingest.byte_working_set",
        "ledger": "INGEST_BENCH.json",
        "path": "gate_byte_ws_le_half_int32",
        "op": "all_true",
        "band": None,
    },
    # Closed-loop invariants (tools/bench_loop.py) — mechanism gates, all
    # machine-independent: the loop either closed (alarm → retrain →
    # shadow → promote → drift recovered, zero 5xx throughout) or it
    # didn't, whatever the wall clock said.
    {
        "id": "loop.zero_5xx",
        "ledger": "LOOP_BENCH.json",
        "path": "gates.zero_5xx",
        "op": "all_true",
        "band": None,
    },
    {
        "id": "loop.drift_corrected",
        "ledger": "LOOP_BENCH.json",
        "path": "gates.promoted",
        "op": "all_true",
        "band": None,
    },
    {
        "id": "loop.psi_recovered",
        "ledger": "LOOP_BENCH.json",
        "path": "gates.psi_recovered",
        "op": "all_true",
        "band": None,
    },
    {
        "id": "loop.poisoned_rejected",
        "ledger": "LOOP_BENCH.json",
        "path": "gates.poisoned_rejected",
        "op": "all_true",
        "band": None,
    },
    {
        "id": "loop.rollback_pin_flip",
        "ledger": "LOOP_BENCH.json",
        "path": "gates.rollback_ok",
        "op": "all_true",
        "band": None,
    },
    # Stacked many-model training (tools/bench_multi_train.py).  Parity
    # and one-dispatch are mechanism gates; the stacked-vs-sequential
    # speedup is wall-clock but carries a HARD per-backend floor — the
    # headline claim is ≥2x on cpu and ≥5x on an accelerator, whatever
    # headroom the blessed run had.
    {
        "id": "multi.parity_bitwise",
        "ledger": "MULTI_TRAIN_BENCH.json",
        "path": "gates.parity_bitwise",
        "op": "all_true",
        "band": None,
    },
    {
        "id": "multi.one_dispatch",
        "ledger": "MULTI_TRAIN_BENCH.json",
        "path": "gates.one_dispatch_per_stack",
        "op": "all_true",
        "band": None,
    },
    {
        "id": "multi.speedup_k8",
        "ledger": "MULTI_TRAIN_BENCH.json",
        "path": "results[k=8].speedup",
        "op": ">=",
        "band": {"*": 0.5},
        "min_bound": {"cpu": 2.0, "*": 5.0},
    },
    {
        "id": "multi.speedup_k64",
        "ledger": "MULTI_TRAIN_BENCH.json",
        "path": "results[k=64].speedup",
        "op": ">=",
        "band": {"*": 0.5},
        "min_bound": {"cpu": 2.0, "*": 5.0},
    },
    {
        "id": "multi.e2e_zero_5xx",
        "ledger": "MULTI_TRAIN_BENCH.json",
        "path": "gates.e2e_zero_errors",
        "op": "all_true",
        "band": None,
    },
    {
        "id": "multi.e2e_swap_parity",
        "ledger": "MULTI_TRAIN_BENCH.json",
        "path": "gates.e2e_swap_parity",
        "op": "all_true",
        "band": None,
    },
    # Pod rehearsal (tools/bench_pod.py).  Parity and resume are
    # mechanism gates — the process boundary is either invisible to the
    # math or it isn't.  The 2-process scaling ratio carries the ≥1.7x
    # floor of the rehearsal's acceptance, but ONLY where the topology
    # can deliver it: the ledger records ``scaling.gate_enforced: false``
    # on cpu (every "process" shares the host's core) and the gate
    # demotes to advisory-with-trend there.
    {
        "id": "pod.scaling_2proc",
        "ledger": "BENCH_POD.json",
        "path": "scaling.two_proc",
        "op": ">=",
        "band": {"*": 0.15},
        "min_bound": 1.7,
        "advisory_when": "scaling.gate_enforced",
    },
    {
        "id": "pod.parity_bitwise",
        "ledger": "BENCH_POD.json",
        "path": "parity.bitwise",
        "op": "all_true",
        "band": None,
    },
    {
        "id": "pod.resume_ok",
        "ledger": "BENCH_POD.json",
        "path": "resume.ok",
        "op": "all_true",
        "band": None,
    },
]


def _log(*a):
    print("[bench_ratchet]", *a, file=sys.stderr, flush=True)


# ---------------------------------------------------------------------------
# Ledger access
# ---------------------------------------------------------------------------


def discover_ledgers(ledger_dir: str) -> dict:
    """Map schema name -> list of matching ledger paths.  Every schema
    must match at least one file (a vanished ledger is a schema error)."""
    out = {}
    for name in LEDGER_SCHEMAS:
        p = os.path.join(ledger_dir, name)
        out[name] = [p] if os.path.isfile(p) else []
    return out


def _walk(obj, path: str):
    """Yield values at a dotted path; ``x[]`` fans out over a list and
    ``x[k=v,...]`` selects matching list elements."""
    if path == "":
        yield obj
        return
    head, _, rest = path.partition(".")
    if head.endswith("]") and "[" in head:
        key, _, sel = head[:-1].partition("[")
        seq = obj.get(key) if isinstance(obj, dict) else None
        if not isinstance(seq, list):
            return
        if sel:
            want = dict(kv.split("=", 1) for kv in sel.split(","))
            for el in seq:
                if isinstance(el, dict) and all(
                    str(el.get(k)) == v for k, v in want.items()
                ):
                    yield from _walk(el, rest)
        else:
            for el in seq:
                yield from _walk(el, rest)
        return
    if not isinstance(obj, dict) or head not in obj:
        return
    yield from _walk(obj[head], rest)


def validate_ledger(schema_name: str, obj: dict) -> list:
    """Schema errors (empty list = valid)."""
    errors = []
    for path, want in LEDGER_SCHEMAS[schema_name].items():
        vals = list(_walk(obj, path))
        if not vals:
            errors.append(f"missing required key {path!r}")
            continue
        want_t = want if isinstance(want, tuple) else (want,)
        for v in vals:
            # bool is an int subclass; a numeric-typed field must
            # reject it explicitly
            if (isinstance(v, bool) and bool not in want_t
                    and (int in want_t or float in want_t)):
                errors.append(f"{path!r} expected "
                              f"{'/'.join(t.__name__ for t in want_t)}, "
                              "got bool")
            elif want is bool and not isinstance(v, bool):
                errors.append(f"{path!r} expected bool, got "
                              f"{type(v).__name__}")
            elif not isinstance(v, want):
                errors.append(
                    f"{path!r} expected {want}, got {type(v).__name__}"
                )
    return errors


def load_ledgers(ledger_dir: str):
    """(ledgers, errors): schema-validated ledger objects by schema name."""
    errors = []
    ledgers = {}
    found = discover_ledgers(ledger_dir)
    for name, paths in found.items():
        if not paths:
            errors.append(f"{name}: no ledger found in {ledger_dir}")
            continue
        for p in paths:
            try:
                with open(p) as f:
                    obj = json.load(f)
            except (OSError, ValueError) as e:
                errors.append(f"{os.path.basename(p)}: unreadable ({e})")
                continue
            errs = validate_ledger(name, obj)
            errors.extend(f"{os.path.basename(p)}: {e}" for e in errs)
            if not errs:
                ledgers[name] = obj
    return ledgers, errors


def _backend_of(name: str, ledgers: dict) -> str:
    led = ledgers.get(name, {})
    for v in _walk(led, "backend"):
        return str(v)
    return "cpu"


def _band_for(gate: dict, backend: str):
    band = gate.get("band")
    if band is None:
        return None
    return band.get(backend, band.get("*", 0.10))


def _min_bound_for(gate: dict, backend: str):
    """The gate's hard floor, resolved per backend: a plain number
    applies everywhere, a dict maps backend -> floor (``"*"`` default)
    — the speedup claims are backend-relative (2x cpu, 5x device)."""
    mb = gate.get("min_bound")
    if isinstance(mb, dict):
        return mb.get(backend, mb.get("*"))
    return mb


def _max_bound_for(gate: dict, backend: str):
    """Hard CEILING for ``<=`` gates: ``--update`` may tighten the bound
    toward the blessed value but never re-bless above this — the trend
    gates pin a historical record (ingest's pre-pipeline 3.61 s) as the
    worst value any future blessing can legitimize."""
    mb = gate.get("max_bound")
    if isinstance(mb, dict):
        return mb.get(backend, mb.get("*"))
    return mb


# ---------------------------------------------------------------------------
# Ratchet file
# ---------------------------------------------------------------------------


def derive_ratchet(ledgers: dict) -> dict:
    """A fresh RATCHET mapping gate id -> bound, from blessed ledgers."""
    out = {"gates": {}}
    for gate in GATES:
        led = ledgers.get(gate["ledger"])
        if led is None:
            continue
        vals = list(_walk(led, gate["path"]))
        if not vals:
            continue
        backend = _backend_of(gate["ledger"], ledgers)
        entry = {"source": f"{gate['ledger']}:{gate['path']}",
                 "backend": backend}
        if gate["op"] == "all_true":
            entry["bound"] = True
        else:
            v = float(vals[-1])
            band = _band_for(gate, backend)
            bound = v * (1 + band) if gate["op"] == "<=" else v * (1 - band)
            mb = _min_bound_for(gate, backend)
            if mb is not None and gate["op"] == ">=":
                bound = max(bound, mb)
            xb = _max_bound_for(gate, backend)
            if xb is not None and gate["op"] == "<=":
                bound = min(bound, xb)
            entry["blessed"] = v
            entry["band"] = band
            entry["bound"] = round(bound, 6)
        adv = gate.get("advisory_when")
        if adv is not None:
            entry["enforced"] = bool(next(_walk(led, adv), False))
        else:
            entry["enforced"] = True
        out["gates"][gate["id"]] = entry
    return out


def ratchet_path(ledger_dir: str, explicit=None) -> str:
    if explicit:
        return explicit
    local = os.path.join(ledger_dir, "RATCHET.json")
    if os.path.isfile(local):
        return local
    return os.path.join(REPO, "RATCHET.json")


# ---------------------------------------------------------------------------
# Gate evaluation
# ---------------------------------------------------------------------------


def evaluate(ledgers: dict, ratchet: dict) -> list:
    """Per-gate results: {id, value, bound, op, enforced, ok}."""
    results = []
    for gate in GATES:
        spec = ratchet.get("gates", {}).get(gate["id"])
        led = ledgers.get(gate["ledger"])
        if spec is None or led is None:
            continue
        vals = list(_walk(led, gate["path"]))
        # advisory gates re-resolve enforcement from the ledger UNDER
        # EVALUATION (not the one blessed into RATCHET.json): a fixture
        # or accelerator rerun that records gate_enforced=true must be
        # held to the gate even though the blessing ran on cpu
        adv = gate.get("advisory_when")
        if adv is not None:
            enforced = bool(next(_walk(led, adv), False))
        else:
            enforced = bool(spec.get("enforced", True))
        res = {
            "id": gate["id"],
            "op": gate["op"],
            "bound": spec.get("bound"),
            "enforced": enforced,
        }
        if not vals:
            res.update(value=None, ok=False,
                       detail="value missing from ledger")
        elif gate["op"] == "all_true":
            res.update(value=all(bool(v) for v in vals),
                       ok=all(bool(v) for v in vals))
        else:
            v = float(vals[-1])
            bound = float(spec["bound"])
            ok = v <= bound if gate["op"] == "<=" else v >= bound
            res.update(value=v, ok=ok)
        results.append(res)
    return results


# ---------------------------------------------------------------------------
# Smoke replay (mechanism gates on fresh outputs, bench_out/ scratch)
# ---------------------------------------------------------------------------


def _run_bench(argv, out_path) -> dict:
    _log("replay:", " ".join(argv))
    env = dict(os.environ, JAX_PLATFORMS=os.environ.get(
        "JAX_PLATFORMS", "cpu"))
    r = subprocess.run(argv, env=env, cwd=REPO, capture_output=True,
                       text=True, timeout=1800)
    if r.returncode != 0:
        raise RuntimeError(
            f"{argv[2]} exited {r.returncode}: {r.stderr[-2000:]}"
        )
    with open(out_path) as f:
        return json.load(f)


def smoke_replay() -> list:
    """Replay the smoke benches into ``bench_out/`` and evaluate the
    machine-independent mechanism gates on the fresh outputs."""
    os.makedirs(BENCH_OUT, exist_ok=True)
    results = []

    p_out = os.path.join(BENCH_OUT, "predict_smoke.json")
    pred = _run_bench(
        [sys.executable, "-m", "tools.bench_predict", "--smoke",
         "--json", p_out], p_out)
    bitwise = all(
        bool(r.get("bitwise_vs_scan")) for r in pred.get("results", [])
    )
    results.append({
        "id": "smoke.predict_bitwise", "op": "all_true", "bound": True,
        "enforced": True, "value": bitwise, "ok": bitwise,
    })
    cs = pred.get("cold_start", {})
    warm_faster = (
        float(cs.get("cold_from_disk_ms", 1e9))
        < float(cs.get("cleared_cold_ms", 0.0))
        and bool(cs.get("bitwise_across_processes"))
    )
    results.append({
        "id": "smoke.predict_cold_start_mechanism", "op": "all_true",
        "bound": True, "enforced": True,
        "value": warm_faster, "ok": warm_faster,
    })

    i_out = os.path.join(BENCH_OUT, "ingest_smoke.json")
    ing = _run_bench(
        [sys.executable, "-m", "tools.bench_ingest", "--smoke",
         "--out", i_out], i_out)
    multi_chunk = (
        "gate_steady_le_half_host" in ing
        and bool(ing.get("gate_byte_ws_le_half_int32"))
    )
    results.append({
        "id": "smoke.ingest_mechanism", "op": "all_true", "bound": True,
        "enforced": True, "value": multi_chunk, "ok": multi_chunk,
    })
    return results


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------


def _render(results: list) -> str:
    lines = [f"  {'gate':<38} {'value':>14} {'op':>9} {'bound':>12} "
             f"{'status':>9}"]
    for r in results:
        status = ("PASS" if r["ok"]
                  else "ADVISORY" if not r["enforced"] else "FAIL")
        val = r["value"]
        val = f"{val:.4g}" if isinstance(val, float) else str(val)
        lines.append(
            f"  {r['id']:<38} {val:>14} {r['op']:>9} "
            f"{str(r['bound']):>12} {status:>9}"
        )
    return "\n".join(lines)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="python -m tools.bench_ratchet")
    ap.add_argument("--ledger-dir", default=REPO,
                    help="directory holding the ledgers (default: repo "
                         "root; CI's regression leg points this at the "
                         "seeded fixture)")
    ap.add_argument("--ratchet", default=None,
                    help="RATCHET.json path (default: <ledger-dir>/"
                         "RATCHET.json, falling back to the repo root)")
    ap.add_argument("--update", action="store_true",
                    help="re-derive RATCHET.json from the current "
                         "ledgers (the deliberate re-blessing step)")
    ap.add_argument("--smoke", action="store_true",
                    help="additionally replay the smoke benches into "
                         "bench_out/ and check mechanism gates")
    ap.add_argument("--json", action="store_true", help="machine output")
    ns = ap.parse_args(argv)

    ledgers, errors = load_ledgers(ns.ledger_dir)
    if errors:
        for e in errors:
            _log("schema:", e)
        print(json.dumps({"schema_errors": errors}, indent=1)
              if ns.json else
              "bench_ratchet: schema errors:\n  " + "\n  ".join(errors))
        return 2

    rpath = ratchet_path(ns.ledger_dir, ns.ratchet)
    if ns.update:
        ratchet = derive_ratchet(ledgers)
        tmp = rpath + ".new"
        try:
            with open(tmp, "w") as f:
                json.dump(ratchet, f, indent=1, sort_keys=True)
                f.write("\n")
            os.replace(tmp, rpath)
        except BaseException:
            try:
                os.remove(tmp)
            except OSError:
                pass
            raise
        _log("re-blessed", rpath)

    try:
        with open(rpath) as f:
            ratchet = json.load(f)
    except (OSError, ValueError) as e:
        _log(f"ratchet file {rpath}: {e}")
        return 2

    results = evaluate(ledgers, ratchet)
    if ns.smoke:
        try:
            results.extend(smoke_replay())
        except (RuntimeError, OSError, ValueError,
                subprocess.TimeoutExpired) as e:
            _log("smoke replay failed:", e)
            return 2

    failed = [r for r in results if not r["ok"] and r["enforced"]]
    advisory = [r for r in results if not r["ok"] and not r["enforced"]]
    payload = {
        "ledger_dir": ns.ledger_dir,
        "ratchet": rpath,
        "results": results,
        "failed": [r["id"] for r in failed],
        "advisory_failures": [r["id"] for r in advisory],
    }
    if ns.json:
        print(json.dumps(payload, indent=1, sort_keys=True))
    else:
        print(f"bench_ratchet — {len(results)} gate(s), "
              f"{len(failed)} failed, {len(advisory)} advisory")
        print(_render(results))
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
