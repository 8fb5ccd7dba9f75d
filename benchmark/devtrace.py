"""The profiler around the window, and the reduction from its trace.

`start`/`stop` wrap `jax.profiler`, and `load` reads its file into a `Profile`: per device
the operations that ran on it (name, start, duration, in seconds on the
profiler's clock), the benchmark's own host annotations (`bench:<label>`,
written by traffic.py around every statement) and the traced window.
Everything a trace metric needs is a method here, so every PR computes the
same number the same way; `check_trace.py` checks them on a recorded trace.

What the planes are expected to look like on a TPU v5e (not yet checked
on a real trace: PERF.md §3): devices are the planes `/device:TPU:<n>`,
their operations the line `XLA Ops`; host annotations are events of the
`/host:CPU` plane's thread lines. Both carry nanoseconds on one clock.
"""

from __future__ import annotations

import bisect
import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MARK = "bench:"
WINDOW = MARK + "window"


def start(directory: str):
    """-> the open window mark, to hand to stop()."""
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0   # the host's Python frames: large, unread
    opts.host_tracer_level = 1     # TraceAnnotation and the runtime's own
    jax.profiler.start_trace(directory, profiler_options=opts)
    window = jax.profiler.TraceAnnotation(WINDOW)
    window.__enter__()
    return window


def stop(window) -> None:
    import jax

    window.__exit__(None, None, None)
    jax.profiler.stop_trace()


def load(path: str) -> "Profile":
    """From an .xplane.pb file, or the directory start() was given."""
    from jax.profiler import ProfileData

    if os.path.isdir(path):
        path = sorted(glob.glob(os.path.join(path, "plugins", "profile", "*",
                                             "*.xplane.pb")))[-1]
    devices, marks = {}, []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            if DEVICE_PLANE.match(plane.name) and line.name == OPS_LINE:
                devices[plane.name] = [
                    (e.name, e.start_ns * 1e-9, e.duration_ns * 1e-9)
                    for e in line.events]
            else:
                marks += [(e.name[len(MARK):], e.start_ns * 1e-9,
                           e.duration_ns * 1e-9)
                          for e in line.events if e.name.startswith(MARK)]
    return Profile(devices, marks)


def merge(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    """Union of [start, end) intervals as sorted disjoint intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], e))
        else:
            out.append((s, e))
    return out


def overlap(merged: list[tuple[float, float]], lo: float, hi: float) -> float:
    return sum(max(0.0, min(e, hi) - max(s, lo)) for s, e in merged)


def query_of(label: str) -> str:
    """`q1.3` (query q1, round 3) -> `q1`; a label with no round is itself."""
    head, _, tail = label.rpartition(".")
    return head if head and tail.isdigit() else label


class Profile:
    def __init__(self, devices: dict[str, list[tuple[str, float, float]]],
                 marks: list[tuple[str, float, float]]):
        window = [m for m in marks if MARK + m[0] == WINDOW]
        if len(window) != 1:
            raise ValueError(f"the trace holds {len(window)} window marks, not 1")
        if not devices or not any(devices.values()):
            raise ValueError("the trace holds no operation on any TPU device")
        self.t0, self.t1 = window[0][1], window[0][1] + window[0][2]
        self.window_s = self.t1 - self.t0
        self.devices = {d: sorted((n, s, t) for n, s, t in ops
                                  if s + t > self.t0 and s < self.t1)
                        for d, ops in devices.items()}
        self.marks = sorted((m for m in marks if MARK + m[0] != WINDOW
                             and self.t0 <= m[1] < self.t1), key=lambda m: m[1])
        self.busy = {d: merge([(max(s, self.t0), min(s + t, self.t1))
                               for _n, s, t in ops])
                     for d, ops in self.devices.items()}
        self.first = min(self.devices)
        self._starts = [m[1] for m in self.marks]

    def statements(self, query: str | None = None) -> list[tuple[str, float, float]]:
        return [m for m in self.marks if query in (None, query_of(m[0]))]

    def busy_s(self) -> float:
        """Seconds in which an operation ran, averaged over the devices."""
        return sum(overlap(b, self.t0, self.t1)
                   for b in self.busy.values()) / len(self.busy)

    def busy_in(self, query: str | None = None) -> float:
        """Busy seconds of the first device inside the statements of one
        query (all statements if None)."""
        return sum(overlap(self.busy[self.first], s, s + t)
                   for _l, s, t in self.statements(query))

    def ops_matching(self, patterns: list[str]) -> float:
        """Summed durations of the first device's operations whose name
        holds one of the patterns."""
        return sum(t for n, _s, t in self.devices[self.first]
                   if any(p in n for p in patterns))

    def label_at(self, t: float) -> str:
        """The statement the host was in at time t: of the marks that cover
        it, the one started last (closed-loop clients overlap)."""
        starts = self._starts
        i = bisect.bisect_right(starts, t)
        for label, s, d in reversed(self.marks[max(0, i - 64):i]):
            if t < s + d:
                return label
        return "between statements"

    def breakdown(self, top: int = 10) -> dict:
        ops: dict[str, float] = {}
        for n, s, t in self.devices[self.first]:
            key = f"{query_of(self.label_at(s))}/{n}"
            ops[key] = ops.get(key, 0.0) + t
        edges = ([(self.t0, self.t0)] + self.busy[self.first]
                 + [(self.t1, self.t1)])
        gaps = sorted(((b[0] - a[1], (a[1] + b[0]) / 2)
                       for a, b in zip(edges, edges[1:])), reverse=True)[:top]
        return {"device_ops": [[k, v] for k, v in sorted(
                    ops.items(), key=lambda kv: -kv[1])[:top]],
                "idle_gaps": [[self.label_at(mid), gap] for gap, mid in gaps
                              if gap > 0]}
