"""Device time by plan node: which node of the plan ran when on the device.

`exec/compile.py` builds every plan node's function under
``jax.named_scope("<kind>#<n>")``, so each instruction of the executable
the program holds (`CompileResult.aot_fn`) carries, in its ``op_name``,
the path of the nodes it was emitted for, innermost last. A device trace
names the instruction and carries no scope; this module joins the two:

    program registry   program_id -> CompileResult (weak), so a reader that
                       holds no Database finds a program's `node_map()`
                       after the window (`node_map_of`); a `dispatch` span's
                       ``program`` argument is that id
    parse_node_map     executable text -> {instruction name: scope path}
    self_times         an operation that encloses others on its device line
                       (a `while`, a `conditional`, a `call`) counts only
                       the time none of them covers: the self times of a
                       line are disjoint, their sum IS the busy time
    by_node            self time by (node label, part), and each dispatch's
                       head (span start to its first operation) and tail
                       (its last operation's end to span end)
    capture            run a function under `jax.profiler` and hand back
                       the first device's operations and the `gg:dispatch`
                       annotations, or None where there is nothing to read

The reducers are pure functions over ``(name, start_s, dur_s)`` tuples of
one device line. `EXPLAIN ANALYZE` (exec/session.py) and the benchmark's
`trace_by_node` metrics (benchmark/metrics/bynode.py) are the two readers.
"""

from __future__ import annotations

import glob
import io
import itertools
import os
import re
import shutil
import tempfile
import threading
import weakref
from typing import NamedTuple

NO_NODE = "(no node)"

# what a TPU profile looks like (benchmark/devtrace.py reads the same)
DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
DISPATCH_MARK = "gg:dispatch"

# ---- the program registry ------------------------------------------------
# weak: the program cache's LRU (exec/programs.py) is what keeps a program
# alive; one that was evicted, or never cached, has no map to give
_PROGRAMS: "weakref.WeakValueDictionary[int, object]" = \
    weakref.WeakValueDictionary()
_PROGRAM_IDS = itertools.count(1)
_PROGRAMS_MU = threading.Lock()


def register(program) -> int:
    """A process-wide id for a freshly made CompileResult."""
    pid = next(_PROGRAM_IDS)
    with _PROGRAMS_MU:
        _PROGRAMS[pid] = program
    return pid


def node_map_of(program_id) -> dict | None:
    """{instruction name: scope path} of a registered program; None where
    the program is gone or has no AOT executable to read."""
    with _PROGRAMS_MU:
        program = _PROGRAMS.get(program_id)
    return None if program is None else program.node_map()


_OPERAND = re.compile(r"%([\w.\-]+)")


def parse_node_map(text: str) -> dict:
    """The executable's text (`compiled.as_text()`) -> {instruction name:
    its op_name path}. A fusion carries its root's path. An instruction the
    compiler made itself (a copy, the tree a prefix sum is expanded into)
    carries none, or a bare operation name with no scope in it: it takes
    the path of its first consumer that has one, being made for it (a rule,
    not a record); where no consumer has one it keeps what it had."""
    paths, users, order = {}, {}, []
    for line in io.StringIO(text):   # a large program's text, not copied
        eq = line.find(" = ")
        if eq < 0 or not line.startswith(" "):
            continue   # a computation's header or brace, the module's line
        # "  ROOT %fusion.3 = ..." / "  %fusion.3 = ..."
        name = line[:eq].split()[-1].lstrip("%")
        order.append(name)
        at = line.find('op_name="', eq)
        if at >= 0:
            at += len('op_name="')
            paths[name] = line[at:line.index('"', at)]
        ends = [i for i in (line.find(", metadata={", eq),
                            line.find(", backend_config=", eq)) if i >= 0]
        for operand in _OPERAND.findall(line[eq:min(ends, default=len(line))]):
            users.setdefault(operand, []).append(name)
    # consumers follow their operands in the text: backwards, every
    # consumer is settled before what it consumes
    for name in reversed(order):
        if "/" not in paths.get(name, ""):
            found = next((paths[u] for u in users.get(name, ())
                          if "/" in paths.get(u, "")), None)
            if found is not None:
                paths[name] = found
    return paths


# ---- the reducers ---------------------------------------------------------
def instruction_of(op_name: str) -> str:
    """A trace event's name is the instruction's text (`%fusion.3 = u32[..]
    fusion(...)`) -> `fusion.3`."""
    return op_name.split(" = ", 1)[0].strip().lstrip("%")


def self_times(ops: list) -> list[float]:
    """Seconds of each ``(name, start_s, dur_s)`` operation that no
    operation started later on the same line covers, in the order given.
    Every instant of the line's busy union belongs to exactly one
    operation (the one started last among those that cover it), so the
    self times sum to the union whatever the nesting."""
    out = [0.0] * len(ops)
    order = sorted(range(len(ops)), key=lambda i: (ops[i][1], -ops[i][2]))
    open_ops: list[int] = []   # started and not yet seen to end, oldest first
    now = 0.0

    def run_until(t: float) -> None:
        # hand [now, t) to whoever owns it: the newest open operation
        nonlocal now
        while open_ops and now < t:
            top = open_ops[-1]
            end = ops[top][1] + ops[top][2]
            if end > now:
                out[top] += min(end, t) - now
                now = min(end, t)
            if end <= t:
                open_ops.pop()
        now = t

    for i in order:
        run_until(ops[i][1])
        open_ops.append(i)
    run_until(float("inf"))
    return out


def kind_of(label: str) -> str:
    """`join#4` -> `join`; a label an older compile left bare is its kind."""
    return label.split("#", 1)[0]


def _innermost(path: str, kinds: frozenset, parts: frozenset) -> tuple:
    """-> (node label | NO_NODE, part | None): the last node label of the
    path, and the part below it if one is. The path's last component is
    the operation's own name (a `sort`, a `scan`), never a scope; a bare
    kind is a label only in a path that holds no `<kind>#<n>` (an
    executable compiled before the labels had their index)."""
    scopes = path.split("/")[:-1]
    for indexed in (True, False):
        part = None
        for scope in reversed(scopes):
            kind, sep, n = scope.partition("#")
            if scope in parts:
                part = part or scope
            elif kind in kinds and (n.isdigit() if indexed else not sep):
                return scope, part
    return NO_NODE, part


class Dispatch(NamedTuple):
    """One dispatch span against the operations it covers."""
    span_s: float
    busy_s: float        # self time of its operations
    head_s: float        # span start to its first operation's start
    tail_s: float        # its last operation's end to span end

    @property
    def between_s(self) -> float:
        """Idle between the program's operations."""
        return self.span_s - self.head_s - self.tail_s - self.busy_s


class ByNode(NamedTuple):
    seconds: dict            # (node label | NO_NODE, part | None) -> seconds
    dispatches: list         # [Dispatch], in the order given

    def busy_s(self) -> float:
        return sum(self.seconds.values())


# A trace lays the device's clock over the host's to within a few
# milliseconds, not better (my chip runs, PR 39: the same program's first
# operation read 0.08 ms after its dispatch span's start in one run, 2.4 ms
# before it in the next). So a span covers this much more on either side,
# up to halfway to its neighbour, and an operation that reads as started
# before its own dispatch (or ended after it) moves that dispatch's line.
CLOCK_SLACK_S = 5e-3


def _cover(dispatches: list) -> list:
    """[(lo, hi, index)] by start: each span widened by the clock's slack,
    neighbours parted at the middle of the gap between them."""
    order = sorted(range(len(dispatches)), key=lambda d: dispatches[d][0])
    out = []
    for k, d in enumerate(order):
        t0, t1 = dispatches[d][:2]
        lo, hi = t0 - CLOCK_SLACK_S, t1 + CLOCK_SLACK_S
        if k:
            lo = max(lo, (dispatches[order[k - 1]][1] + t0) / 2)
        if k + 1 < len(order):
            hi = min(hi, (t1 + dispatches[order[k + 1]][0]) / 2)
        out.append((lo, hi, d))
    return out


def by_node(ops: list, dispatches: list) -> ByNode:
    """Self time of the ``(name, start_s, dur_s)`` operations of one device
    line by plan node. ``dispatches`` is ``[(t0, t1, node_map | None)]`` on
    the operations' clock: an operation belongs to the dispatch that covers
    its start (two programs both have a `fusion.12`; `_cover`), then to the
    innermost scope of its instruction's path that is a node label or a
    part. An instruction with no such scope, a dispatch with no map and an
    operation no dispatch covers all go under ``NO_NODE``."""
    from greengage_tpu.exec.compile import NODE_KINDS, PART_NAMES

    kinds, parts = frozenset(NODE_KINDS), frozenset(PART_NAMES)
    cover = _cover(dispatches)
    seconds: dict = {}
    first: list = [None] * len(dispatches)
    last: list = [None] * len(dispatches)
    busy = [0.0] * len(dispatches)
    resolved: list[dict] = [{} for _ in dispatches]
    for (name, start, dur), self_s in zip(ops, self_times(ops)):
        key = (NO_NODE, None)
        d = next((d for lo, hi, d in cover if lo <= start < hi), None)
        if d is not None:
            first[d] = start if first[d] is None else min(first[d], start)
            last[d] = max(last[d] or 0.0, start + dur)
            busy[d] += self_s
            node_map = dispatches[d][2]
            if node_map:
                instr = instruction_of(name)
                if instr not in resolved[d]:
                    path = node_map.get(instr)
                    resolved[d][instr] = key if path is None else _innermost(
                        path, kinds, parts)
                key = resolved[d][instr]
        seconds[key] = seconds.get(key, 0.0) + self_s
    out = []
    for d, (t0, t1, _map) in enumerate(dispatches):
        if first[d] is None:     # nothing of it ran on this device
            out.append(Dispatch(t1 - t0, 0.0, t1 - t0, 0.0))
            continue
        head, tail = first[d] - t0, t1 - last[d]
        if head < 0:      # the device's line reads early by that much
            head, tail = 0.0, tail + head
        elif tail < 0:    # ... or late
            head, tail = head + tail, 0.0
        out.append(Dispatch(t1 - t0, busy[d], max(head, 0.0), max(tail, 0.0)))
    return ByNode(seconds, out)


# ---- a capture of one's own ----------------------------------------------
class Capture(NamedTuple):
    ops: list          # the first device's [(name, start_s, dur_s)]
    dispatches: list   # the `gg:dispatch` annotations' [(t0_s, t1_s)]


def _has_device_plane() -> bool:
    """Whether a profile of this process would hold a device's operations:
    only a TPU's is laid out the way `_read` expects."""
    import jax

    return jax.default_backend() == "tpu"


def _read(directory: str) -> Capture | None:
    from jax.profiler import ProfileData

    path = sorted(glob.glob(os.path.join(
        directory, "plugins", "profile", "*", "*.xplane.pb")))[-1]
    devices, dispatches = {}, []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            if DEVICE_PLANE.match(plane.name):
                if line.name == OPS_LINE:
                    devices[plane.name] = [
                        (e.name, e.start_ns * 1e-9, e.duration_ns * 1e-9)
                        for e in line.events]
            else:
                dispatches += [(e.start_ns * 1e-9,
                                (e.start_ns + e.duration_ns) * 1e-9)
                               for e in line.events if e.name == DISPATCH_MARK]
    if not devices:
        return None
    return Capture(devices[min(devices)], sorted(dispatches))


def capture(fn):
    """-> (fn(), Capture | None). Runs ``fn`` once, under `jax.profiler`
    where that can show a device's operations (the tracer levels of
    `benchmark/devtrace.start`: no Python frames). None where a profiler
    session is already open, the backend has no device plane, or the
    capture raises: the statement is never the worse for it."""
    if not _has_device_plane():
        return fn(), None
    import jax

    directory = tempfile.mkdtemp(prefix="ggprof")
    try:
        try:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 1
            jax.profiler.start_trace(directory, profiler_options=opts)
        except Exception:
            # a session of someone else's is open (theirs to stop), or this
            # runtime has no profiler
            return fn(), None
        try:
            out = fn()
        finally:
            try:
                jax.profiler.stop_trace()
                captured = _read(directory)
            except Exception:
                captured = None
        return out, captured
    finally:
        shutil.rmtree(directory, ignore_errors=True)
