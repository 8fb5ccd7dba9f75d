"""The source kinds a per-layer metric file may name.

A metric is `metrics/<name>.json`: {"kind": <kind>, ...arguments}. Each
`read_<kind>(spec, ctx)` below returns the number, or None where there is
nothing to read (the metric is then left out of the line). `ctx` holds the
warm-up and window statement records, the window's counter deltas and
histogram snapshots (`runtime/logger`), the row counts, the table of
peaks and, in a traced run, the `devtrace.Profile`. A new kind is a new
Python file in this directory with its own `read_<kind>`.
"""

from __future__ import annotations


def _mean(h0: dict, h1: dict, name: str) -> float | None:
    """Mean of one of the program's histograms over the window."""
    a, b = h0.get(name, {"sum": 0.0, "count": 0}), h1.get(name)
    if b is None or b["count"] == a["count"]:
        return None
    return (b["sum"] - a["sum"]) / (b["count"] - a["count"])


def read_stats_mean(spec, ctx):
    """Mean (or, with "reduce": "sum", the sum) of `Result.stats[stat]`
    over the in-process statements of a phase; "where" keeps only records
    whose stats hold a true value under that key."""
    recs = ctx.warmup if spec.get("phase") == "warmup" else ctx.window
    vals = [r["stats"][spec["stat"]] for r in recs
            if r["stats"] and spec["stat"] in r["stats"]
            and ("where" not in spec or r["stats"].get(spec["where"]))]
    total = float(sum(vals)) * spec.get("scale", 1.0)
    if spec.get("reduce") == "sum":
        return total
    return total / len(vals) if vals else None


def read_histogram_mean(spec, ctx):
    """Mean of histogram `name` over the window, less the means of the
    histograms under "minus". `client_latency_ms` is the benchmark's own:
    the statements' latencies at the client."""
    h0, h1 = ctx.hist
    lat = [(r["t1"] - r["t0"]) * 1e3 for r in ctx.window]
    h1 = {**h1, "client_latency_ms": {"sum": sum(lat), "count": len(lat)}}
    parts = [_mean(h0, h1, n) for n in [spec["name"]] + spec.get("minus", [])]
    if any(p is None for p in parts):
        return None
    return parts[0] - sum(parts[1:])


def read_counter_delta(spec, ctx):
    return float(ctx.counters.get(spec["name"], 0))


def read_counter_share(spec, ctx):
    """100 x sum of the counters under "num" / sum of those under "den"."""
    den = sum(ctx.counters.get(n, 0) for n in spec["den"])
    if not den:
        return None
    return 100.0 * sum(ctx.counters.get(n, 0) for n in spec["num"]) / den


def read_trace_busy(spec, ctx):
    """ms a statement in which an operation ran on the first device."""
    n = len(ctx.profile.statements())
    return ctx.profile.busy_in() * 1e3 / n if n else None


def read_trace_ops_matching(spec, ctx):
    """ms a statement of the first device's operations whose names hold
    one of "patterns"; nothing where no operation matches."""
    n, t = len(ctx.profile.statements()), ctx.profile.ops_matching(spec["patterns"])
    return t * 1e3 / n if n and t else None


def read_roofline(spec, ctx):
    """The least time one chip's memory system needs for the bytes the
    query must read (tpch_data.query_bytes over the column types of the
    cell's data module, its share of them on several chips) over the time
    the first device was busy in that query's statements, in %. Bound by
    bytes: these queries do a few operations a byte. A share above 100
    means the bytes or the time are counted wrong."""
    from tpch_data import query_bytes

    q = spec["query"]
    n, busy = len(ctx.profile.statements(q)), ctx.profile.busy_in(q)
    if not n or not busy:
        return None
    peak = ctx.peaks.get(ctx.device_kind)
    if peak is None:
        raise KeyError(f"no peaks for device kind {ctx.device_kind!r} in peaks.json")
    need_s = (n * query_bytes(ctx.cell.queries[q]["reads"], ctx.rows,
                              ctx.cell.data.column_types())
              / ctx.cell.chips / peak["hbm_bytes_per_s"])
    share = 100.0 * need_s / busy
    if share > 100.0:
        raise ValueError(f"{q}_roofline reads {share:.1f}% > 100%")
    return share
