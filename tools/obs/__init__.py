"""tools.obs — offline reporting over ``mmlspark_tpu.obs`` JSONL exports
and ``blackbox.rank<R>.jsonl`` flight-recorder dumps.

- ``python -m tools.obs report [--json] [path]`` aggregates the span
  records (and the final snapshot record each rank appends at exit) from
  a ``MMLSPARK_TPU_OBS=<path>`` run.  Multi-process runs write per-rank
  files (``<path>.rank<R>``); the report reads the base path plus every
  rank sibling it finds.
- ``python -m tools.obs report --diff A B`` diffs two runs' snapshots
  (counter deltas, histogram p50/p99 shifts) — each side may be a JSONL
  export, a raw snapshot JSON, or a ``tools/bench_*.py`` output JSON
  (whose embedded ``"obs"`` key is found automatically).
- ``python -m tools.obs timeline <paths...>`` merges per-rank blackbox
  dumps (and/or exports) onto one wall clock via each dump's paired
  wall/monotonic anchor, with per-step compute vs collective-wait
  attribution.
- ``python -m tools.obs trace <request_id>`` reconstructs one serving
  request's critical path (queue wait → batch-close wait → predict →
  reply) across the request/batch trace-id fan-in.
- ``python -m tools.obs drift [--json] [path | --url URL]`` summarizes
  the model-quality monitor's ``quality.*``/``slo.*`` series (drift
  alarms, PSI gauges, burn rates) from any snapshot-bearing file, or
  pulls a live app's ``GET /driftz`` for full per-feature detail.

Stdlib plus this repo's own ``mmlspark_tpu.obs.flight`` (the one place a
span's begin meets its end) — usable on a machine without jax installed.
"""

from __future__ import annotations

import glob
import json
import os
from typing import Dict, List, Optional


def discover_files(path: str) -> List[str]:
    """The base export file plus any ``<path>.rank<R>`` siblings."""
    files = []
    if os.path.isfile(path):
        files.append(path)
    files.extend(sorted(glob.glob(glob.escape(path) + ".rank*")))
    return files


def load_records(path: str) -> List[dict]:
    """All well-formed JSONL records across the export's rank files.
    Malformed lines (torn writes from a killed process) are skipped."""
    records: List[dict] = []
    for fn in discover_files(path):
        with open(fn, "r") as f:
            for line in f:
                line = line.strip()
                if not line:
                    continue
                try:
                    rec = json.loads(line)
                except ValueError:
                    continue
                if isinstance(rec, dict):
                    records.append(rec)
    return records


def _rank_label(rec: dict, fallback: Optional[dict] = None):
    """Merge key for one record's writing process.  Plain runs keep the
    integer rank (exact pre-fleet behavior); fleet replicas — which are
    all rank 0 of their own process — append the ``replica`` tag their
    records carry, so N same-host replicas aggregate side by side
    instead of silently folding into one \"rank 0\".  Real multi-process
    records additionally carry jax's ``process_index``; when it disagrees
    with the launcher rank (coordinator renumbering, or records written
    before bring-up resolved the rank) the label keeps both so distinct
    processes never fold together."""
    fb = fallback or {}
    rank = rec.get("rank", fb.get("rank", 0))
    pi = rec.get("process_index", fb.get("process_index"))
    if pi is not None and pi != rank:
        rank = f"{rank}/p{pi}"
    rep = rec.get("replica") or fb.get("replica")
    return f"{rank}.{rep}" if rep else rank


def aggregate(records: List[dict]) -> dict:
    """Fold span records into per-name stats and step records
    (``obs/steps.py`` exports) into per-kind wall/compute/collective/
    ingest-stall attribution; keep the LAST snapshot per rank/replica
    (the exit-time one supersedes any mid-run export_snapshot)."""
    spans: Dict[str, dict] = {}
    steps: Dict[str, dict] = {}
    snapshots: Dict[str, dict] = {}
    ranks = set()
    for rec in records:
        kind = rec.get("kind")
        if kind == "span":
            name = rec.get("name", "?")
            dur = float(rec.get("dur_s", 0.0))
            rk = _rank_label(rec)
            ranks.add(rk)
            agg = spans.get(name)
            if agg is None:
                agg = spans[name] = {
                    "count": 0,
                    "total_s": 0.0,
                    "max_s": 0.0,
                    "ranks": set(),
                }
            agg["count"] += 1
            agg["total_s"] += dur
            agg["max_s"] = max(agg["max_s"], dur)
            agg["ranks"].add(rk)
        elif kind == "step":
            st = rec.get("step") or {}
            sk = str(st.get("kind", "?"))
            rk = _rank_label(rec)
            ranks.add(rk)
            agg = steps.get(sk)
            if agg is None:
                agg = steps[sk] = {
                    "count": 0,
                    "wall_s": 0.0,
                    "compute_s": 0.0,
                    "collective_s": 0.0,
                    "ingest_stall_s": 0.0,
                    "max_wall_s": 0.0,
                    "ranks": set(),
                }
            agg["count"] += 1
            for f in ("wall_s", "compute_s", "collective_s",
                      "ingest_stall_s"):
                try:
                    agg[f] += float(st.get(f, 0.0) or 0.0)
                except (TypeError, ValueError):
                    pass
            try:
                agg["max_wall_s"] = max(agg["max_wall_s"],
                                        float(st.get("wall_s", 0.0) or 0.0))
            except (TypeError, ValueError):
                pass
            agg["ranks"].add(rk)
        elif kind == "snapshot":
            rk = _rank_label(rec)
            ranks.add(rk)
            snapshots[str(rk)] = rec.get("snapshot", {})
    for agg in spans.values():
        agg["mean_s"] = agg["total_s"] / agg["count"]
        agg["ranks"] = sorted(agg.pop("ranks"), key=str)
    for agg in steps.values():
        agg["mean_wall_s"] = agg["wall_s"] / agg["count"]
        agg["ranks"] = sorted(agg.pop("ranks"), key=str)
    return {
        "span_records": sum(a["count"] for a in spans.values()),
        "step_records": sum(a["count"] for a in steps.values()),
        "ranks": sorted(ranks, key=str),
        "spans": spans,
        "steps": steps,
        "device": _device_sections(snapshots),
        "snapshots": snapshots,
    }


def _device_sections(snapshots: Dict[str, dict]) -> dict:
    """Per-rank device-memory gauges + compile-event counters
    (``obs/device.py`` series) pulled out of the exit snapshots."""
    out: Dict[str, dict] = {}
    for rank, snap in snapshots.items():
        mem = {
            k: float(v) for k, v in (snap.get("gauges") or {}).items()
            if k.startswith("device.")
        }
        comp = {
            k: float(v) for k, v in (snap.get("counters") or {}).items()
            if k.startswith("device.compile_events")
        }
        if mem or comp:
            out[rank] = {"memory": mem, "compile_events": comp}
    return out


def render_text(report: dict, files: List[str]) -> str:
    out: List[str] = []
    out.append(
        f"obs report — {len(files)} file(s), "
        f"{report['span_records']} span record(s), "
        f"{report.get('step_records', 0)} step record(s), "
        f"rank(s) {report['ranks'] or [0]}"
    )
    if report["spans"]:
        out.append("")
        out.append(
            f"  {'span':<40} {'count':>7} {'total_s':>10} "
            f"{'mean_s':>10} {'max_s':>10}"
        )
        for name in sorted(
            report["spans"], key=lambda n: -report["spans"][n]["total_s"]
        ):
            a = report["spans"][name]
            out.append(
                f"  {name:<40} {a['count']:>7} {a['total_s']:>10.4f} "
                f"{a['mean_s']:>10.4f} {a['max_s']:>10.4f}"
            )
    if report.get("steps"):
        out.append("")
        out.append(
            f"  {'step kind':<12} {'count':>7} {'wall_s':>10} "
            f"{'compute_s':>10} {'collect_s':>10} {'stall_s':>10} "
            f"{'mean_s':>9}"
        )
        for sk in sorted(
            report["steps"], key=lambda k: -report["steps"][k]["wall_s"]
        ):
            a = report["steps"][sk]
            out.append(
                f"  {sk:<12} {a['count']:>7} {a['wall_s']:>10.4f} "
                f"{a['compute_s']:>10.4f} {a['collective_s']:>10.4f} "
                f"{a['ingest_stall_s']:>10.4f} {a['mean_wall_s']:>9.4f}"
            )
    for rank in sorted(report.get("device") or {}):
        d = report["device"][rank]
        out.append("")
        out.append(f"  device (rank {rank}):")
        for k in sorted(d["memory"]):
            out.append(f"    gauge    {k} = {d['memory'][k]:g}")
        for k in sorted(d["compile_events"]):
            out.append(f"    counter  {k} = {d['compile_events'][k]:g}")
    for rank in sorted(report["snapshots"]):
        snap = report["snapshots"][rank]
        counters = snap.get("counters", {})
        gauges = snap.get("gauges", {})
        hists = snap.get("histograms", {})
        out.append("")
        out.append(f"  snapshot (rank {rank}):")
        for k in sorted(counters):
            out.append(f"    counter  {k} = {counters[k]:g}")
        for k in sorted(gauges):
            out.append(f"    gauge    {k} = {gauges[k]:g}")
        for k in sorted(hists):
            h = hists[k]
            if h.get("count"):
                out.append(
                    f"    hist     {k}: count={h['count']} "
                    f"mean={h['mean']:.6g} p50={h['p50']:.6g} "
                    f"p95={h['p95']:.6g} max={h['max']:.6g}"
                )
            else:
                out.append(f"    hist     {k}: count=0")
    if not report["spans"] and not report["snapshots"]:
        out.append("  (no records)")
    return "\n".join(out)


def build_report(path: str) -> dict:
    files = discover_files(path)
    report = aggregate(load_records(path))
    report["files"] = files
    return report


def default_path() -> Optional[str]:
    raw = os.environ.get("MMLSPARK_TPU_OBS", "").strip()
    if raw and raw.lower() not in ("0", "1", "false", "true", "off", "on"):
        return raw
    return None


# ---------------------------------------------------------------------------
# Flight-recorder (blackbox) reading.
#
# A blackbox file is a sequence of dump SEGMENTS: one ``flight_header``
# line (with a paired ``ts``/``mono_ns`` wall/monotonic anchor) followed
# by its ``flight`` event lines carrying raw ``t_ns`` monotonic stamps.
# Each event's wall time is ``header.ts - (header.mono_ns - t_ns)/1e9`` —
# per-rank monotonic clocks never cross files; only reconstructed wall
# times are merged.
# ---------------------------------------------------------------------------


def discover_blackbox(path: str) -> List[str]:
    """Blackbox files named by ``path``: a directory (its
    ``blackbox.rank*.jsonl`` children), a blackbox file itself, or an obs
    export base path (blackbox siblings in its directory)."""
    if os.path.isdir(path):
        return sorted(glob.glob(os.path.join(glob.escape(path),
                                             "blackbox.rank*.jsonl")))
    base = os.path.basename(path)
    if base.startswith("blackbox.") and os.path.isfile(path):
        return [path]
    d = os.path.dirname(os.path.abspath(path))
    return sorted(glob.glob(os.path.join(glob.escape(d),
                                         "blackbox.rank*.jsonl")))


def load_blackbox(path: str) -> List[dict]:
    """Events from one blackbox file, each with a reconstructed ``wall``
    timestamp and its segment's dump ``reason`` attached."""
    events: List[dict] = []
    header: Optional[dict] = None
    with open(path, "r") as f:
        for line in f:
            line = line.strip()
            if not line:
                continue
            try:
                rec = json.loads(line)
            except ValueError:
                continue
            kind = rec.get("kind")
            if kind == "flight_header":
                header = rec
            elif kind == "flight" and header is not None:
                try:
                    wall = float(header["ts"]) - (
                        int(header["mono_ns"]) - int(rec["t_ns"])
                    ) / 1e9
                except (KeyError, TypeError, ValueError):
                    continue
                events.append({
                    "rank": _rank_label(rec, header),
                    "wall": wall,
                    "ev": rec.get("ev", "?"),
                    "name": rec.get("name", "?"),
                    "thread": rec.get("thread", "?"),
                    "detail": rec.get("detail"),
                    "reason": header.get("reason", "?"),
                    "src": "flight",
                })
    return events


def _blackbox_anchors(path: str) -> List[dict]:
    """All ``flight_header`` records in a blackbox file."""
    out = []
    with open(path, "r") as f:
        for line in f:
            try:
                rec = json.loads(line)
            except ValueError:
                continue
            if isinstance(rec, dict) and rec.get("kind") == "flight_header":
                out.append(rec)
    return out


def _export_events(path: str) -> List[dict]:
    """Obs-export span records as timeline events (wall START time =
    record ``ts`` minus the measured duration; exports stamp wall time at
    span close)."""
    events = []
    for rec in load_records(path):
        if rec.get("kind") != "span":
            continue
        try:
            ts = float(rec["ts"])
            dur = float(rec.get("dur_s", 0.0))
        except (KeyError, TypeError, ValueError):
            continue
        events.append({
            "rank": _rank_label(rec),
            "wall": ts - dur,
            "ev": "span",
            "name": rec.get("name", "?"),
            "thread": "?",
            "detail": {"dur_s": dur, **(rec.get("attrs") or {})},
            "reason": "export",
            "src": "export",
        })
    return events


def _gather_timeline_events(paths: List[str]):
    """(files, events) across blackbox dumps and obs exports."""
    files: List[str] = []
    events: List[dict] = []
    for p in paths:
        bb = discover_blackbox(p)
        for fn in bb:
            if fn not in files:
                files.append(fn)
                events.extend(load_blackbox(fn))
        if not os.path.isdir(p) and not os.path.basename(p).startswith(
            "blackbox."
        ):
            for fn in discover_files(p):
                if fn not in files:
                    files.append(fn)
            events.extend(_export_events(p))
    events.sort(key=lambda e: e["wall"])
    return files, events


def _pair_flight_spans(events: List[dict]) -> List[dict]:
    """Completed spans of a merged event list: ``sb``/``se`` pairs and
    pre-measured ``span`` events through the program's own reader
    (``mmlspark_tpu.obs.flight.pair_spans``: per rank+thread, stack-wise,
    by name), plus one ``collective.<name>`` span per watchdog
    ``collective_end``; returns span dicts with start/dur/attrs on the
    reconstructed wall clock."""
    from mmlspark_tpu.obs.flight import pair_spans

    spans = [
        {"rank": r["thread"][0], "name": r["name"],
         "start": r["start_ns"] / 1e9,
         "dur_s": max(0.0, (r["end_ns"] - r["start_ns"]) / 1e9),
         "attrs": r["attrs"]}
        for r in pair_spans(
            (int(round(e["wall"] * 1e9)), e["ev"], e["name"], e["detail"],
             (e["rank"], e["thread"]))
            for e in events
        )
    ]
    for e in events:
        if e["ev"] == "collective_end":
            d = dict(e["detail"] or {})
            dur = float(d.pop("dur_s", 0.0) or 0.0)
            spans.append({"rank": e["rank"],
                          "name": f"collective.{e['name']}",
                          "start": e["wall"] - dur, "dur_s": dur,
                          "attrs": d})
    return spans


def build_timeline(paths: List[str], step_span: str = "booster.iteration"
                   ) -> dict:
    """Merge per-rank blackbox/export files onto one wall clock.

    Returns anchors (per-rank wall-minus-monotonic offsets — the
    alignment), the merged event list, per-step compute vs
    collective-wait attribution (collective time = watchdog-wrapped
    collective spans ENDING inside a ``step_span`` interval on the same
    rank), and per-rank collective totals."""
    files, events = _gather_timeline_events(paths)
    spans = _pair_flight_spans(events)

    anchors: Dict[str, dict] = {}
    for fn in files:
        if not os.path.basename(fn).startswith("blackbox."):
            continue
        for h in _blackbox_anchors(fn):
            rank = str(_rank_label(h))
            a = anchors.setdefault(
                rank, {"offset_s": None, "reasons": [], "segments": 0}
            )
            a["segments"] += 1
            a["reasons"].append(h.get("reason", "?"))
            try:
                # Wall-clock instant of this rank's monotonic epoch: the
                # cross-rank alignment constant.
                a["offset_s"] = float(h["ts"]) - int(h["mono_ns"]) / 1e9
            except (KeyError, TypeError, ValueError):
                pass

    collectives = [s for s in spans if s["name"].startswith("collective.")]
    col_totals: Dict[str, Dict[str, float]] = {}
    for c in collectives:
        per = col_totals.setdefault(str(c["rank"]), {})
        per[c["name"]] = per.get(c["name"], 0.0) + c["dur_s"]

    steps = []
    for s in spans:
        if s["name"] != step_span:
            continue
        end = s["start"] + s["dur_s"]
        col_s = sum(
            c["dur_s"] for c in collectives
            if c["rank"] == s["rank"]
            and s["start"] <= c["start"] + c["dur_s"] <= end
        )
        steps.append({
            "rank": s["rank"],
            "start": s["start"],
            "dur_s": s["dur_s"],
            "collective_s": col_s,
            "compute_s": max(0.0, s["dur_s"] - col_s),
            "attrs": s["attrs"],
        })
    steps.sort(key=lambda s: s["start"])

    return {
        "files": files,
        "ranks": sorted({e["rank"] for e in events}, key=str),
        "anchors": anchors,
        "events": events,
        "spans": spans,
        "steps": steps,
        "collective_totals": col_totals,
    }


def render_timeline(tl: dict, max_events: int = 200) -> str:
    out: List[str] = []
    out.append(
        f"obs timeline — {len(tl['files'])} file(s), "
        f"{len(tl['events'])} event(s), rank(s) {tl['ranks'] or [0]}"
    )
    for rank in sorted(tl["anchors"]):
        a = tl["anchors"][rank]
        off = a["offset_s"]
        out.append(
            f"  rank {rank}: {a['segments']} dump segment(s) "
            f"({', '.join(a['reasons'])}); monotonic epoch at wall "
            f"{off:.6f}" if off is not None else
            f"  rank {rank}: {a['segments']} dump segment(s)"
        )
    if tl["steps"]:
        out.append("")
        out.append(
            f"  {'step':<28} {'rank':>4} {'dur_s':>10} "
            f"{'compute_s':>10} {'collective_s':>13}"
        )
        for i, s in enumerate(tl["steps"]):
            label = str((s["attrs"] or {}).get("it", i))
            out.append(
                f"  {'iteration ' + label:<28} {s['rank']:>4} "
                f"{s['dur_s']:>10.4f} {s['compute_s']:>10.4f} "
                f"{s['collective_s']:>13.4f}"
            )
    if tl["collective_totals"]:
        out.append("")
        out.append("  collective wait totals:")
        for rank in sorted(tl["collective_totals"]):
            for name, tot in sorted(tl["collective_totals"][rank].items()):
                out.append(f"    rank {rank} {name:<32} {tot:>10.4f}s")
    events = tl["events"]
    if events:
        t0 = events[0]["wall"]
        shown = events[-max_events:]
        out.append("")
        out.append(
            f"  merged events (last {len(shown)} of {len(events)}; "
            f"t=0 at first event):"
        )
        for e in shown:
            detail = ""
            if e["detail"]:
                detail = " " + json.dumps(e["detail"], sort_keys=True,
                                          default=str)
            out.append(
                f"    +{e['wall'] - t0:10.6f}s rank{e['rank']} "
                f"[{e['thread']}] {e['ev']:<14} {e['name']}{detail}"
            )
    if not events:
        out.append("  (no events)")
    return "\n".join(out)


# ---------------------------------------------------------------------------
# Per-request trace reconstruction.
#
# serve/app.py mints one trace id per request (honoring X-Request-Id) and
# records per-stage spans carrying ``rid``; the batch fan-in span
# (``serve.batch``) lists its ``members`` and binds its OWN batch trace id
# around predict, so the request → batch → predict chain is joined here.
# ---------------------------------------------------------------------------

_TRACE_STAGES = (
    "serve.queue_wait",
    "serve.batch_close_wait",
    "serve.reply",
    "serve.request",
)


def build_trace(request_id: str, paths: List[str]) -> dict:
    """Reconstruct one request's critical path from exports/blackboxes."""
    _, events = _gather_timeline_events(paths)
    spans = _pair_flight_spans(events)

    def attr(s, k):
        return (s.get("attrs") or {}).get(k)

    mine = [s for s in spans
            if attr(s, "rid") == request_id
            or attr(s, "trace_id") == request_id]
    stages: Dict[str, dict] = {}
    for s in mine:
        if s["name"] in _TRACE_STAGES and s["name"] not in stages:
            stages[s["name"]] = {"dur_s": s["dur_s"], "start": s["start"],
                                 "attrs": s["attrs"]}

    batch_id = None
    for s in mine:
        if attr(s, "batch"):
            batch_id = attr(s, "batch")
            break
    batch = None
    for s in spans:
        members = attr(s, "members") or []
        if s["name"] == "serve.batch" and (
            (batch_id and attr(s, "batch") == batch_id)
            or request_id in members
        ):
            batch_id = attr(s, "batch") or batch_id
            batch = {
                "batch_id": batch_id,
                "dur_s": s["dur_s"],
                "model": attr(s, "model"),
                "bucket": attr(s, "bucket"),
                "rows": attr(s, "rows"),
                "members": len(members),
            }
            break
    predict = [
        {"dur_s": s["dur_s"], "backend": attr(s, "backend"),
         "bucket": attr(s, "bucket"), "rows": attr(s, "rows")}
        for s in spans
        if s["name"] == "predict"
        and attr(s, "trace_id") in ((batch_id, request_id) if batch_id
                                    else (request_id,))
    ]
    admits = [
        e for e in events
        if e["ev"] == "admit" and (e["detail"] or {}).get("rid") == request_id
    ]
    return {
        "request_id": request_id,
        "found": bool(mine or admits),
        "stages": stages,
        "batch": batch,
        "predict": predict,
        "admits": [{"verdict": e["name"], "wall": e["wall"],
                    "route": (e["detail"] or {}).get("route")}
                   for e in admits],
    }


def render_trace(tr: dict) -> str:
    out = [f"obs trace — request {tr['request_id']}"]
    if not tr["found"]:
        out.append("  (no records found for this request id)")
        return "\n".join(out)
    for a in tr["admits"]:
        out.append(f"  admission: {a['verdict']} (route {a['route']})")
    order = list(_TRACE_STAGES)
    labels = {
        "serve.queue_wait": "queue wait",
        "serve.batch_close_wait": "batch-close wait",
        "serve.reply": "reply",
        "serve.request": "TOTAL (enqueue -> replied)",
    }
    for name in order[:2]:
        if name in tr["stages"]:
            out.append(
                f"  {labels[name]:<28} {tr['stages'][name]['dur_s']:.6f}s"
            )
    if tr["batch"]:
        b = tr["batch"]
        out.append(
            f"  {'batch predict':<28} {b['dur_s']:.6f}s  "
            f"(batch {b['batch_id']}, model {b['model']}, "
            f"bucket {b['bucket']}, {b['rows']} rows, "
            f"{b['members']} member request(s))"
        )
    for p in tr["predict"]:
        out.append(
            f"  {'  booster predict':<28} {p['dur_s']:.6f}s  "
            f"(backend {p['backend']}, bucket {p['bucket']})"
        )
    for name in order[2:]:
        if name in tr["stages"]:
            out.append(
                f"  {labels[name]:<28} {tr['stages'][name]['dur_s']:.6f}s"
            )
    st = tr["stages"].get("serve.request")
    if st and st.get("attrs", {}).get("bucket") is not None:
        out.append(f"  padding bucket: {st['attrs']['bucket']}")
    return "\n".join(out)


# ---------------------------------------------------------------------------
# Snapshot diffing (report --diff A B).
# ---------------------------------------------------------------------------


def _merge_snapshots(snaps: List[dict]) -> dict:
    """Fold per-rank snapshots into one: counters/sums add, gauges take
    the last writer, histogram percentiles take the max across ranks (a
    conservative approximation — exact merge would need raw samples)."""
    out = {"counters": {}, "gauges": {}, "histograms": {}, "spans": {}}
    for snap in snaps:
        for k, v in (snap.get("counters") or {}).items():
            out["counters"][k] = out["counters"].get(k, 0.0) + float(v)
        for k, v in (snap.get("gauges") or {}).items():
            out["gauges"][k] = float(v)
        for k, h in (snap.get("histograms") or {}).items():
            if not h.get("count"):
                out["histograms"].setdefault(k, {"count": 0})
                continue
            m = out["histograms"].get(k)
            if not m or not m.get("count"):
                out["histograms"][k] = dict(h)
                continue
            m["count"] += h["count"]
            m["sum"] = m.get("sum", 0.0) + h.get("sum", 0.0)
            m["mean"] = m["sum"] / m["count"]
            m["min"] = min(m.get("min", h["min"]), h["min"])
            m["max"] = max(m.get("max", h["max"]), h["max"])
            for p in ("p50", "p95", "p99"):
                if p in h:
                    m[p] = max(m.get(p, h[p]), h[p])
        for k, s in (snap.get("spans") or {}).items():
            m = out["spans"].get(k)
            if m is None:
                out["spans"][k] = dict(s)
                continue
            m["count"] += s.get("count", 0)
            m["total_s"] += s.get("total_s", 0.0)
            m["max_s"] = max(m.get("max_s", 0.0), s.get("max_s", 0.0))
            m["mean_s"] = m["total_s"] / m["count"] if m["count"] else 0.0
    return out


def snapshot_from(path: str) -> dict:
    """A merged obs snapshot from ``path``: a JSONL export (per-rank
    snapshots merged), a raw ``obs.snapshot()`` JSON, or a bench output
    JSON carrying the snapshot under its ``"obs"`` key."""
    try:
        with open(path, "r") as f:
            d = json.load(f)
    except ValueError:
        d = None  # more than one JSON document: a JSONL export
    if isinstance(d, dict):
        if "counters" in d or "histograms" in d:
            return d
        if isinstance(d.get("obs"), dict):
            return d["obs"]
        if isinstance(d.get("snapshot"), dict):
            return d["snapshot"]
        raise ValueError(f"{path}: no obs snapshot found in JSON")
    report = aggregate(load_records(path))
    snaps = [report["snapshots"][r] for r in sorted(report["snapshots"])]
    if not snaps:
        raise ValueError(f"{path}: no snapshot records in export")
    return _merge_snapshots(snaps)


def diff_snapshots(a: dict, b: dict) -> dict:
    """B minus A: counter deltas, histogram p50/p99 shifts, span-aggregate
    shifts.  Keys present on either side are included."""
    out = {"counters": {}, "histograms": {}, "spans": {}}
    ca, cb = a.get("counters") or {}, b.get("counters") or {}
    for k in sorted(set(ca) | set(cb)):
        va, vb = float(ca.get(k, 0.0)), float(cb.get(k, 0.0))
        out["counters"][k] = {"a": va, "b": vb, "delta": vb - va}
    ha, hb = a.get("histograms") or {}, b.get("histograms") or {}
    for k in sorted(set(ha) | set(hb)):
        xa, xb = ha.get(k) or {}, hb.get(k) or {}
        ent = {"count": {"a": xa.get("count", 0), "b": xb.get("count", 0)}}
        for p in ("p50", "p99"):
            pa, pb = xa.get(p), xb.get(p)
            ent[p] = {
                "a": pa, "b": pb,
                "delta": (pb - pa) if pa is not None and pb is not None
                else None,
            }
        out["histograms"][k] = ent
    sa, sb = a.get("spans") or {}, b.get("spans") or {}
    for k in sorted(set(sa) | set(sb)):
        xa, xb = sa.get(k) or {}, sb.get(k) or {}
        out["spans"][k] = {
            "count": {"a": xa.get("count", 0), "b": xb.get("count", 0)},
            "total_s": {
                "a": xa.get("total_s", 0.0), "b": xb.get("total_s", 0.0),
                "delta": xb.get("total_s", 0.0) - xa.get("total_s", 0.0),
            },
        }
    return out


# ---------------------------------------------------------------------------
# Model-quality drift reporting (drift [--json] [path | --url URL]).
#
# Two sources, one summary: a metrics snapshot's ``quality.*``/``slo.*``
# series (offline — exports, snapshot JSONs, bench outputs), or a live
# app's ``GET /driftz`` payload (full per-feature detail).
# ---------------------------------------------------------------------------


def _split_series(key: str):
    """``name{k=v,...}`` -> (name, labels dict); plain names pass
    through with no labels."""
    if key.endswith("}") and "{" in key:
        name, _, inner = key.partition("{")
        labels = {}
        for part in inner[:-1].split(","):
            k, eq, v = part.partition("=")
            if eq:
                labels[k] = v
        return name, labels
    return key, {}


def build_drift(snap: dict) -> dict:
    """Per-model drift/SLO summary from a snapshot's quality.* and slo.*
    series (see :func:`snapshot_from` for accepted inputs)."""
    models: Dict[str, dict] = {}

    def m(name: str) -> dict:
        return models.setdefault(name, {
            "alarms": {}, "clears": {}, "psi": {}, "burn": {},
            "batches_dropped": 0.0,
        })

    for key, v in (snap.get("counters") or {}).items():
        name, labels = _split_series(key)
        model = labels.get("model", "?")
        if name == "quality.drift_alarms":
            m(model)["alarms"][labels.get("kind", "?")] = float(v)
        elif name == "quality.drift_clears":
            m(model)["clears"][labels.get("kind", "?")] = float(v)
        elif name == "quality.batches_dropped":
            m(model)["batches_dropped"] += float(v)
    for key, v in (snap.get("gauges") or {}).items():
        name, labels = _split_series(key)
        model = labels.get("model", "?")
        if name in ("quality.feature_psi_max", "quality.score_psi"):
            m(model)["psi"][name.split(".", 1)[1]] = float(v)
        elif name.startswith("slo.") and name.endswith("_burn"):
            kind = name[len("slo."):-len("_burn")]
            m(model)["burn"].setdefault(kind, {})[
                labels.get("window", "?")] = float(v)
    return {
        "models": models,
        "total_alarms": sum(
            sum(e["alarms"].values()) for e in models.values()
        ),
    }


def render_drift(d: dict) -> str:
    out = [
        f"obs drift — {len(d['models'])} model route(s), "
        f"{d['total_alarms']:g} alarm transition(s)"
    ]
    if not d["models"]:
        out.append(
            "  (no quality.* series in this snapshot — monitor disabled "
            "or no traffic served)"
        )
    for name in sorted(d["models"]):
        e = d["models"][name]
        out.append("")
        out.append(f"  model {name}:")
        for k in sorted(e["psi"]):
            out.append(f"    {k:<24} {e['psi'][k]:.4f}")
        for kind in sorted(e["burn"]):
            w = e["burn"][kind]
            out.append(
                f"    {kind + '_burn':<24} fast={w.get('fast', 0.0):.3f} "
                f"slow={w.get('slow', 0.0):.3f}"
            )
        for k in sorted(e["alarms"]):
            fired, cleared = e["alarms"][k], e["clears"].get(k, 0.0)
            state = "CLEARED" if cleared >= fired else "ACTIVE"
            out.append(f"    alarm {k:<18} x{fired:g} ({state})")
        if e["batches_dropped"]:
            out.append(
                f"    {'batches_dropped':<24} {e['batches_dropped']:g}"
            )
    return "\n".join(out)


def fetch_driftz(url: str) -> dict:
    """GET a live app's /driftz (``url`` may be the app base or the full
    /driftz path)."""
    import urllib.request

    base = url.rstrip("/")
    if not base.endswith("/driftz"):
        base += "/driftz"
    with urllib.request.urlopen(base, timeout=10) as r:
        return json.loads(r.read().decode("utf-8"))


def render_driftz(payload: dict) -> str:
    status = payload.get("status")
    if "routes" not in payload:
        return f"obs drift — /driftz status: {status or '?'}"
    routes = payload.get("routes") or {}
    out = [
        f"obs drift — /driftz ({status or 'ok'}), {len(routes)} route(s), "
        f"{payload.get('dropped_batches', 0)} dropped batch(es)"
    ]
    for name in sorted(routes):
        r = routes[name]
        ref = r.get("reference")
        out.append("")
        out.append(
            f"  route {name} (version {r.get('version')}, reference: "
            + (f"{ref['n_rows']} rows, {ref['num_features']} features)"
               if ref else "none — SLO tracking only)")
        )
        active = r.get("alarms_active") or {}
        out.append(
            "    alarms active: "
            + (", ".join(sorted(active)) if active else "none")
        )
        counts = r.get("alarm_counts") or {}
        if counts:
            out.append(
                "    alarm transitions: "
                + ", ".join(f"{k} x{v}" for k, v in sorted(counts.items()))
            )
        if r.get("stale_batches"):
            out.append(f"    stale batches (swap in flight): "
                       f"{r['stale_batches']}")
        fd = r.get("feature_drift")
        if fd:
            out.append(
                f"    feature drift: live_rows={fd.get('live_rows', 0):.0f} "
                f"excess_psi_max={fd.get('excess_psi_max', 0.0):.4f}"
            )
            for t in (fd.get("top") or [])[:5]:
                out.append(
                    f"      feature {t['feature']:<5} "
                    f"excess_psi={t['excess_psi']:.4f} "
                    f"(raw {t['psi']:.4f}, bias {t['psi_bias']:.4f}) "
                    f"missing={t['missing_rate']:.3f}"
                )
        sd = r.get("score_drift")
        if sd:
            line = (
                f"    score drift:   live_rows={sd.get('live_rows', 0):.0f} "
                f"excess_psi={sd.get('excess_psi', 0.0):.4f}"
            )
            if "class_mix_psi" in sd:
                line += f" class_mix_psi={sd['class_mix_psi']:.4f}"
            out.append(line)
            rec = sd.get("recent")
            if rec:
                out.append(
                    f"      recent scores: p50={rec['p50']:.4g} "
                    f"p95={rec['p95']:.4g} (n={rec['count']})"
                )
        slo = r.get("slo") or {}
        for kind in ("availability", "latency"):
            k = slo.get(kind)
            if k:
                alert = (slo.get("alerts") or {}).get(kind)
                out.append(
                    f"    slo {kind:<12} burn fast={k['fast']:.3f} "
                    f"slow={k['slow']:.3f}"
                    + ("  ** ALERT **" if alert else "")
                )
    return "\n".join(out)


def render_diff(diff: dict, label_a: str = "A", label_b: str = "B") -> str:
    out = [f"obs diff — {label_a} -> {label_b}"]
    changed = {
        k: v for k, v in diff["counters"].items() if v["delta"] != 0
    }
    if changed:
        out.append("")
        out.append(f"  {'counter':<44} {'a':>12} {'b':>12} {'delta':>12}")
        for k, v in changed.items():
            out.append(
                f"  {k:<44} {v['a']:>12g} {v['b']:>12g} {v['delta']:>+12g}"
            )
    shifted = {
        k: v for k, v in diff["histograms"].items()
        if any(v[p]["delta"] for p in ("p50", "p99")
               if v[p]["delta"] is not None)
    }
    if shifted:
        out.append("")
        out.append(
            f"  {'histogram':<44} {'p50 a':>10} {'p50 b':>10} "
            f"{'p99 a':>10} {'p99 b':>10}"
        )

        def g(x):
            return f"{x:.4g}" if x is not None else "-"

        for k, v in shifted.items():
            out.append(
                f"  {k:<44} {g(v['p50']['a']):>10} {g(v['p50']['b']):>10} "
                f"{g(v['p99']['a']):>10} {g(v['p99']['b']):>10}"
            )
    spans = {
        k: v for k, v in diff["spans"].items() if v["total_s"]["delta"]
    }
    if spans:
        out.append("")
        out.append(
            f"  {'span':<44} {'total_s a':>12} {'total_s b':>12} "
            f"{'delta':>12}"
        )
        for k, v in spans.items():
            t = v["total_s"]
            out.append(
                f"  {k:<44} {t['a']:>12.4f} {t['b']:>12.4f} "
                f"{t['delta']:>+12.4f}"
            )
    if len(out) == 1:
        out.append("  (no differences)")
    return "\n".join(out)
