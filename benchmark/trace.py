"""Reduction of a profiler trace to busy time, kernel sums and a breakdown.

Works on plain events ``(plane, line, name, start_ns, duration_ns)`` so the
tests can feed it a small recorded list; ``load`` turns the profiler's
``.xplane.pb`` into that list with nothing but JAX.
"""

import glob
import os

DEVICE_PLANE = "/device:TPU:"
OPS_LINE = "XLA Ops"


def load(trace_dir: str) -> list:
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True))
    if not paths:
        raise FileNotFoundError(f"the profiler wrote no .xplane.pb under {trace_dir}")
    events = []
    for plane in ProfileData.from_file(paths[-1]).planes:
        for line in plane.lines:
            for e in line.events:
                events.append((plane.name, line.name, short_name(e.name), int(e.start_ns), int(e.duration_ns)))
    return events


def short_name(name: str) -> str:
    """A device op is named by its whole HLO line (``%fusion.4 = f32[8]{0} fusion(...)``):
    keep the op's own name and the shape it produces."""
    if " = " not in name:
        return name
    op, rest = name.split(" = ", 1)
    return op.lstrip("%") + " " + rest.split("{", 1)[0].split(" ", 1)[0]


def device_ops(events: list) -> dict:
    """Per device plane, its op events ``(name, start, duration)`` in start order."""
    out = {}
    for plane, line, name, start, dur in events:
        if plane.startswith(DEVICE_PLANE) and line == OPS_LINE:
            out.setdefault(plane, []).append((name, start, dur))
    for ops in out.values():
        ops.sort(key=lambda e: e[1])
    return out


def busy_union(ops: list) -> tuple:
    """``(busy_ns, gaps)``: the union of the op intervals, and the idle gaps
    between them as ``(start, end)``."""
    busy = 0
    gaps = []
    cur_s = cur_e = None
    for _, s, d in ops:
        if cur_e is None:
            cur_s, cur_e = s, s + d
        elif s <= cur_e:
            cur_e = max(cur_e, s + d)
        else:
            busy += cur_e - cur_s
            gaps.append((cur_e, s))
            cur_s, cur_e = s, s + d
    if cur_e is not None:
        busy += cur_e - cur_s
    return busy, gaps


def _host_name_at(host_events: list, t: int) -> str:
    """The shortest host span that covers ``t`` (the most specific one)."""
    best = None
    for name, s, d in host_events:
        if s <= t <= s + d and (best is None or d < best[1]):
            best = (name, d)
    return best[0] if best else "unattributed"


def reduce(events: list, window_ns: int) -> dict:
    """Busy seconds averaged over the device planes, seconds by op name, the
    top ops and the longest idle gaps named by what the host was doing."""
    planes = device_ops(events)
    if not planes:
        return {}
    host = [(n, s, d) for p, _, n, s, d in events if not p.startswith(DEVICE_PLANE) and d > 0]
    # the trace's own span: what the device idles before its first op and after
    # its last (the host's work around the dispatch) is a gap like any other
    t_lo = min(s for _, _, _, s, _ in events)
    t_hi = max(s + d for _, _, _, s, d in events)
    busy = []
    by_name = {}
    gaps = []
    for ops in planes.values():
        # nested ops (a while loop and its body) are on one line: count leaf time
        b, g = busy_union(ops)
        busy.append(b)
        gaps += g + [(t_lo, ops[0][1]), (max(s + d for _, s, d in ops), t_hi)]
        for name, _, d in leaf_ops(ops):
            by_name[name] = by_name.get(name, 0) + d
    n = len(planes)
    top = sorted(by_name.items(), key=lambda kv: -kv[1])[:10]
    longest = sorted(gaps, key=lambda g: g[0] - g[1])[:10]
    return {
        "busy_s": sum(busy) / n / 1e9,
        "window_s": window_ns / 1e9,
        "op_s": {name: d / n / 1e9 for name, d in by_name.items()},
        "device_ops": [[name, d / n / 1e9] for name, d in top],
        "idle_gaps": [[_host_name_at(host, (s + e) // 2), (e - s) / 1e9] for s, e in longest if e > s],
    }


def leaf_ops(ops: list) -> list:
    """Ops that contain no other op: a control-flow op spans its body's ops on
    the same line, and its time would be counted twice."""
    out = []
    stack = []  # (name, start, end, has_child)
    for name, s, d in ops:
        e = s + d
        while stack and stack[-1][2] <= s:
            top = stack.pop()
            if not top[3]:
                out.append((top[0], top[1], top[2] - top[1]))
        if stack:
            stack[-1][3] = True
        stack.append([name, s, e, False])
    for top in stack:
        if not top[3]:
            out.append((top[0], top[1], top[2] - top[1]))
    return out


def seconds_of(op_s: dict, names) -> float:
    """Summed seconds of the ops whose name contains one of ``names``."""
    return sum(d for name, d in op_s.items() if any(k in name for k in names))
