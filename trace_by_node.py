"""A kept device trace read by plan node.

    python trace_by_node.py <dir given to --keep-trace | file.xplane.pb> [executable.hlo.txt] [--stats]

`exec/compile.py` builds every plan node's function under a
`jax.named_scope` (scan, filter, project, join with join-expand inside a
duplicate-key one, semi, agg-sort, agg-dense,
sort, limit, motion, window, union), so each device operation's metadata
holds the path of the nodes it was emitted for, innermost last. This sums
the first device's operation time inside the benchmark's statement marks
(`bench:<query>.<round>`) by innermost node and prints ms a statement, then
the largest operations with their node. The events of a v5e trace name
the HLO instruction and carry no scope (my chip run, PR 31), so give the
executable's text too (`AOT_KEEP_HLO=f python aot_tpu_compile.py` keeps
one of the same program): the instruction's metadata has the path. `--stats` prints the stat names the
trace's events carry (for when the profiler's format moves).
"""

from __future__ import annotations

import glob
import os
import re
import sys

SCOPES = ("scan", "filter", "project", "join", "join-expand", "semi", "agg-sort", "agg-dense",
          "sort", "limit", "motion", "window", "union", "constrel",
          "partialstate")
SCOPE_AT = re.compile(r"(?:^|/)(" + "|".join(map(re.escape, SCOPES)) + r")(?=/|$)")


def scopes_of_hlo(path: str) -> dict:
    """instruction name -> innermost plan-node scope, from the executable's
    text (`compiled.as_text()`, which aot_tpu_compile.py keeps): each
    instruction's metadata holds the op_name path it was traced under."""
    out = {}
    with open(path) as f:
        for ln in f:
            m = re.match(r"\s*(?:ROOT )?(%?[\w.\-]+) = .*op_name=\"([^\"]*)\"", ln)
            if m:
                found = SCOPE_AT.findall(m.group(2))
                if found:
                    out[m.group(1).lstrip("%")] = found[-1]
    return out


def node_of(event, by_name: dict) -> str:
    """Innermost plan-node scope: from the executable's text where one was
    given (a v5e trace's events carry no scope of their own), else from
    any string stat of the event."""
    name = event.name.split(" = ")[0].strip().lstrip("%")
    if name in by_name:
        return by_name[name]
    for _key, value in event.stats:
        if isinstance(value, str) and "/" in value:
            found = SCOPE_AT.findall(value)
            if found:
                return found[-1]
    return "(no node)"


def main() -> int:
    args = [a for a in sys.argv[1:] if not a.startswith("--")]
    path = args[0]
    if os.path.isdir(path):
        path = sorted(glob.glob(os.path.join(path, "plugins", "profile", "*",
                                             "*.xplane.pb")))[-1]
    from jax.profiler import ProfileData

    planes = list(ProfileData.from_file(path).planes)
    marks = [(e.name[6:], e.start_ns, e.start_ns + e.duration_ns)
             for p in planes for ln in p.lines for e in ln.events
             if e.name.startswith("bench:") and e.name != "bench:window"
             and not e.name.startswith("bench:warm:")]
    dev = min((p for p in planes if re.match(r"^/device:TPU:\d+$", p.name)),
              key=lambda p: p.name)
    line = next(ln for ln in dev.lines if ln.name == "XLA Ops")
    if "--stats" in sys.argv:
        for e in list(line.events)[:3]:
            print(e.name[:60], [(k, str(v)[:120]) for k, v in e.stats])
        return 0
    by_name = scopes_of_hlo(args[1]) if len(args) > 1 else {}
    by_node, by_op = {}, {}
    for e in line.events:
        if not any(s <= e.start_ns < t for _l, s, t in marks):
            continue
        node = node_of(e, by_name)
        by_node[node] = by_node.get(node, 0) + e.duration_ns
        key = (node, e.name[:70])
        by_op[key] = by_op.get(key, 0) + e.duration_ns
    n = max(len(marks), 1)
    print(f"{len(marks)} statements; device time a statement by plan node, ms:")
    for node, ns in sorted(by_node.items(), key=lambda kv: -kv[1]):
        print(f"  {node:12s} {ns / n / 1e6:10.1f}")
    print("largest operations, ms a statement:")
    for (node, name), ns in sorted(by_op.items(), key=lambda kv: -kv[1])[:25]:
        print(f"  {ns / n / 1e6:10.1f}  {node:10s} {name}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
