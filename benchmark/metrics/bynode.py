"""Source kind `trace_by_node`: the first device's time by the plan node
that emitted each operation, and the two ends of every `dispatch` span.

    {"kind": "trace_by_node", "scopes": ["join-expand"]}
    {"kind": "trace_by_node", "read": "head" | "tail" | "no_node_share"}

`scopes` names node kinds and part names (`NODE_KINDS`, `PART_NAMES` of the
program's `exec/compile.py`): the value is ms a statement of the self time
of the operations whose innermost scope, among those names, is one of
them, so `["join"]` leaves a join's `join-expand` out. `head` is ms a
statement from a `dispatch` span's start to its first operation on the
device, `tail` from its last operation's end to the span's end, summed
over the statement's dispatches; `no_node_share` is the % of all self time
whose instruction has no plan node in its path. With `"reduce": "max"` the
value is the largest statement's, not the mean.

The program does the reading (`greengage_tpu/runtime/devprofile.py`): every
`dispatch` span carries the `program` it ran, `node_map_of(program)` gives
{instruction: scope path} from that program's own executable, `by_node`
the self times (a `while` counts only what its body's operations do not
cover, so the sums are the device's busy time). This file pairs the
window's statements with their marks and moves the traces onto the
profiler's clock exactly as `spans.py` does, refusing like it where the two
clocks cannot be laid over one another. A program older than `devprofile`,
or one whose dispatches name no program or give no map, has nothing to
read: the metric is left out. The cell's whole table goes to stderr once.
"""

from __future__ import annotations

import bisect
import statistics
import sys

MAX_OFFSET_RANGE_S = 1e-3   # spans.py's


def _statements(ctx):
    """[(query, devprofile.ByNode)] of the window's statements, or None."""
    from devtrace import query_of
    try:
        from greengage_tpu.runtime import devprofile
        from greengage_tpu.runtime.trace import TRACES
    except ImportError:
        return None
    profile = getattr(ctx, "profile", None)
    if profile is None or not hasattr(TRACES, "between"):
        return None
    marks, records = profile.statements(), ctx.window
    if not marks or len(marks) != len(records):
        raise ValueError(f"{len(marks)} statement marks in the trace, "
                         f"{len(records)} statements in the window")
    spans = []
    for (label, _s, _d), rec in zip(marks, records):
        if query_of(label) != rec["query"]:
            raise ValueError(f"mark {label!r} pairs with a {rec['query']!r}")
        found = TRACES.between(rec["t0"], rec["t1"])
        if len(found) != 1:
            return None   # no trace of this statement in the ring
        mine = [s for s in found[0].export() if s["name"] == "dispatch"
                and s["cat"] == "device"]
        if any("program" not in s["args"] for s in mine):
            return None   # a program older than the attribute
        spans.append([(found[0].t0 + s["ts"] * 1e-3,
                       found[0].t0 + (s["ts"] + s["dur"]) * 1e-3,
                       s["args"]["program"]) for s in mine])
    offsets = [m[1] - rec["t0"] for m, rec in zip(marks, records)]
    if max(offsets) - min(offsets) > MAX_OFFSET_RANGE_S:
        raise ValueError(
            "the profiler's clock and time.monotonic() disagree: mark.start "
            f"- record.t0 ranges over {max(offsets) - min(offsets):.6f} s")
    offset = statistics.median(offsets)
    maps = {pid: devprofile.node_map_of(pid)
            for pid in {p for stmt in spans for _a, _b, p in stmt}}
    if not any(maps.values()):
        return None
    ops = sorted(profile.devices[profile.first], key=lambda op: op[1])
    starts = [op[1] for op in ops]
    out = []
    for (label, s, d), stmt in zip(marks, spans):
        inside = ops[bisect.bisect_left(starts, s):
                     bisect.bisect_left(starts, s + d)]
        out.append((query_of(label), devprofile.by_node(
            inside, [(a + offset, b + offset, maps[p]) for a, b, p in stmt])))
    _log(out, devprofile.NO_NODE)
    return out


def _log(stmts: list, no_node: str) -> None:
    """`[bynode] <query> <label> <ms a statement of that query>`: every
    node and part, largest first, then what no node owns, the dispatches'
    head, tail and the idle between a program's operations."""
    for query in dict.fromkeys(q for q, _b in stmts):
        mine = [b for q, b in stmts if q == query]
        rows: dict = {}
        for b in mine:
            for (label, part), sec in b.seconds.items():
                key = f"{label}/{part}" if part else label
                rows[key] = rows.get(key, 0.0) + sec
        ends = {name: sum(getattr(d, name) for b in mine for d in b.dispatches)
                for name in ("busy_s", "span_s", "head_s", "tail_s",
                             "between_s")}
        nodes = sorted((k for k in rows if k != no_node),
                       key=lambda k: -rows[k])
        for key in nodes + [k for k in rows if k == no_node]:
            print(f"[bynode] {query} {key} {rows[key] * 1e3 / len(mine):.3f}",
                  file=sys.stderr)
        print(f"[bynode] {query} busy {sum(rows.values()) * 1e3 / len(mine):.3f}"
              + "".join(f" {name[:-2]} {v * 1e3 / len(mine):.3f}"
                        for name, v in ends.items() if name != "busy_s")
              + f" over {len(mine)} statements", file=sys.stderr, flush=True)


def read_trace_by_node(spec, ctx):
    if not hasattr(ctx, "by_node"):   # ten metrics, one reduction
        ctx.by_node = _statements(ctx)
    if ctx.by_node is None:
        return None
    from greengage_tpu.runtime.devprofile import NO_NODE, kind_of

    stmts = [b for _q, b in ctx.by_node]
    read = spec.get("read")
    if read == "no_node_share":
        busy = sum(b.busy_s() for b in stmts)
        return 100.0 * sum(sec for b in stmts for (label, _p), sec
                           in b.seconds.items() if label == NO_NODE
                           ) / busy if busy else None
    if read in ("head", "tail"):
        per = [sum(getattr(d, read + "_s") for d in b.dispatches)
               for b in stmts]
    else:
        scopes = set(spec["scopes"])
        per = [sum(sec for (label, part), sec in b.seconds.items()
                   if (part or kind_of(label)) in scopes) for b in stmts]
        if not any(per):
            return None   # no such node in the cell's programs
    value = max(per) if spec.get("reduce") == "max" else sum(per) / len(per)
    return value * 1e3
