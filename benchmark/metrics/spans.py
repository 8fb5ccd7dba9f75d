"""Source kind `span_idle`: the device's idle time inside the statements,
put down to what the program's own spans say the host was doing.

    {"kind": "span_idle", "spans": "stage"}                  idle inside them
    {"kind": "span_idle", "spans": "leaf", "inside": false}  idle outside them

`spans` names the spans of the statement thread to hold the idle time
against: a span name, or "leaf" for every span of that thread with no child
on it (`parse`, `wait`, `put`, `dispatch`, ... — everything the program can
name). The idle time is the complement of the first device's busy union
(`ctx.profile.busy`) inside the statements' `bench:<label>` marks; the value
is ms a statement of it inside (or, with "inside": false, outside) the
union of those spans.

The spans come from the program's trace ring (`TRACES.between`, new with the
PR that brought this file: a program without it gives nothing to read). A
window record's `t0`/`t1` and `Trace.t0` are both `time.monotonic()`; the
marks are on the profiler's clock. The statements are paired in order with
the marks and the traces moved by the median of `mark.start - record.t0`.
Offsets that range over more than `MAX_OFFSET_RANGE_S` mean the two clocks
cannot be laid over one another: that raises, it never gives a number.
"""

from __future__ import annotations

import statistics
import sys

MAX_OFFSET_RANGE_S = 1e-3


def statement_thread_spans(trace, select: str) -> list[tuple[float, float]]:
    """[start, end) on `time.monotonic()` of the chosen spans of the thread
    that recorded the trace's first span (the statement thread)."""
    spans = trace.export()
    if not spans:
        return []
    mine = [s for s in spans if s["tid"] == spans[0]["tid"]]
    if select == "leaf":
        parents = {s["parent"] for s in mine}
        mine = [s for s in mine if s["id"] not in parents]
    else:
        mine = [s for s in mine if s["name"] == select]
    return [(trace.t0 + s["ts"] * 1e-3, trace.t0 + (s["ts"] + s["dur"]) * 1e-3)
            for s in mine]


def read_span_idle(spec, ctx):
    from devtrace import merge, overlap, query_of
    from greengage_tpu.runtime.trace import TRACES

    profile = getattr(ctx, "profile", None)
    if profile is None or not hasattr(TRACES, "between"):
        return None
    marks, records = profile.statements(), ctx.window
    if not marks or len(marks) != len(records):
        raise ValueError(f"{len(marks)} statement marks in the trace, "
                         f"{len(records)} statements in the window")
    traces = []
    for (label, _s, _d), rec in zip(marks, records):
        if query_of(label) != rec["query"]:
            raise ValueError(f"mark {label!r} pairs with a {rec['query']!r}")
        found = TRACES.between(rec["t0"], rec["t1"])
        if len(found) != 1:
            return None   # no trace of this statement in the ring: not traced
        traces.append(found[0])
    offsets = [m[1] - rec["t0"] for m, rec in zip(marks, records)]
    if max(offsets) - min(offsets) > MAX_OFFSET_RANGE_S:
        raise ValueError(
            "the profiler's clock and time.monotonic() disagree: mark.start "
            f"- record.t0 ranges over {max(offsets) - min(offsets):.6f} s")
    offset = statistics.median(offsets)
    print(f"[spans] {spec['spans']}: clock offsets of {len(offsets)} statements "
          f"range over {(max(offsets) - min(offsets)) * 1e6:.1f} us",
          file=sys.stderr, flush=True)
    busy = profile.busy[profile.first]
    total = 0.0
    for (_label, s, d), tr in zip(marks, traces):
        cover = merge([(a, b) for a, b in (
            (max(a + offset, s), min(b + offset, s + d))   # clipped to the mark
            for a, b in statement_thread_spans(tr, spec["spans"])) if b > a])
        # busy intervals are disjoint, so busy time inside `cover` is a sum
        inside = (sum(b - a for a, b in cover)
                  - sum(overlap(busy, a, b) for a, b in cover))
        total += inside if spec.get("inside", True) else (
            d - overlap(busy, s, s + d) - inside)
    return total * 1e3 / len(marks)
