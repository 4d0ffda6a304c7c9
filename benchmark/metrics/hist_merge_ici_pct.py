"""The merge's share of the interconnect's peak: the least bytes a chip must
receive to merge the traced trees' histograms (``_merge.merge_least_bytes``),
over the interconnect's peak, over the collectives' summed time.  It reads
small where latency rules: a pass moves kilobytes."""

from benchmark.metrics import _merge, _program


def read(ctx):
    s = _merge.collective_seconds(ctx)
    chips = int(ctx["device"].get("count", 1))
    if s is None or chips < 2:
        return None
    if ctx["device_kind"] not in _merge.ICI_BYTES_PER_S:
        raise ValueError(f"no interconnect peak recorded for device kind {ctx['device_kind']!r}; add it to benchmark/metrics/_merge.py with its source")
    cfg = ctx["cfg"]
    least = _merge.merge_least_bytes(ctx["cols"], int(cfg["max_bin"]) + 1, int(cfg["params"]["num_leaves"]), chips)
    least_s = least * ctx["window"]["iterations"] / _merge.ICI_BYTES_PER_S[ctx["device_kind"]]
    _program.say("hist_merge_ici_pct", least_bytes_per_tree=least, least_s=least_s, collective_s=s)
    return 100.0 * least_s / s
