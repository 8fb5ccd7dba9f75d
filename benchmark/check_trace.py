"""Checks devtrace.py's reduction; runs on the CPU sandbox in seconds.

    python benchmark/check_trace.py            # check
    python benchmark/check_trace.py --record   # on a TPU: record the trace anew

Two checks. A synthetic profile whose busy union, per-statement busy time,
matching-operation time, breakdown and idle gaps are worked out by hand
below. And, once `--record` has made it on a TPU (not yet: PR 26 got no
chip), `testdata/small.xplane.pb` (two rounds of a matrix product and a
sort, marked like statements), on
which the reduction is held against a second, plainer way of computing the
same numbers from the raw events, and against what was read off the trace
by hand when it was recorded.
"""

import glob
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
SMALL = os.path.join(HERE, "testdata", "small.xplane.pb")


def close(a: float, b: float, tol: float = 1e-9) -> bool:
    return abs(a - b) <= tol * max(1.0, abs(b))


def check_synthetic() -> None:
    from devtrace import Profile, merge

    assert merge([(3, 4), (0, 1), (0.5, 2), (2, 2.5)]) == [(0, 2.5), (3, 4)]
    ops0 = [("fusion.1", 1.0, 2.0),        # q1.0: busy 1..3
            ("all-to-all.7", 2.5, 1.0),    # overlaps fusion.1: busy ..3.5
            ("fusion.2", 6.0, 1.0),        # q6.0: busy 6..7
            ("fusion.1", 11.0, 0.5),       # q1.1: busy 11..11.5
            ("copy", 19.5, 2.0)]           # runs past the window's end at 20
    ops1 = [("fusion.1", 1.0, 1.0)]
    marks = [("window", 0.0, 20.0), ("q1.0", 0.5, 4.5), ("q6.0", 5.0, 5.0),
             ("q1.1", 10.0, 5.0), ("q6.1", 15.0, 5.0), ("warm:q1.0", -3.0, 1.0)]
    p = Profile({"/device:TPU:0": ops0, "/device:TPU:1": ops1}, marks)
    assert close(p.window_s, 20.0)
    assert [m[0] for m in p.statements()] == ["q1.0", "q6.0", "q1.1", "q6.1"]
    # device 0: 2.5 + 1 + 0.5 + 0.5 (clipped) = 4.5; device 1: 1.0
    assert close(p.busy_s(), (4.5 + 1.0) / 2)
    assert close(p.busy_in("q1"), 2.5 + 0.5)
    assert close(p.busy_in("q6"), 1.0 + 0.5)
    assert close(p.busy_in(), 4.5)
    assert close(p.ops_matching(["all-to-all", "all-gather"]), 1.0)
    b = p.breakdown()
    assert b["device_ops"][0] == ["q1/fusion.1", 2.5], b
    assert ["q6/copy", 2.0] in b["device_ops"] and ["q6/fusion.2", 1.0] in b["device_ops"]
    # gaps: 0..1 (q1.0), 3.5..6 (2.5: its middle 4.75 is in q1.0),
    # 7..11 (4.0: middle 9 in q6.0), 11.5..19.5 (8.0: middle 15.5 in q6.1)
    assert b["idle_gaps"] == [["q6.1", 8.0], ["q6.0", 4.0], ["q1.0", 2.5],
                              ["q1.0", 1.0]], b
    assert close(sum(g for _n, g in b["idle_gaps"]) + 4.5, p.window_s)


def check_recorded() -> None:
    import devtrace
    from jax.profiler import ProfileData

    p = devtrace.load(SMALL)
    labels = [m[0] for m in p.statements()]
    assert labels == ["mm.0", "sort.0", "mm.1", "sort.1"], labels
    # the plainer way: sweep the raw events' end points of device 0
    plane = next(pl for pl in ProfileData.from_file(SMALL).planes
                 if pl.name == "/device:TPU:0")
    line = next(ln for ln in plane.lines if ln.name == devtrace.OPS_LINE)
    points = []
    for e in line.events:
        s, t = e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9
        s, t = max(s, p.t0), min(t, p.t1)
        if t > s:
            points += [(s, 1), (t, -1)]
    busy, depth, last = 0.0, 0, 0.0
    for t, d in sorted(points):
        if depth:
            busy += t - last
        depth, last = depth + d, t
    assert busy > 0 and close(busy, p.busy_s(), 1e-6), (busy, p.busy_s())
    b = p.breakdown()
    assert close(sum(g for _n, g in p.breakdown(top=10**6)["idle_gaps"]) + busy,
                 p.window_s, 1e-6)
    assert all(k.split("/")[0] in ("mm", "sort", "between statements")
               for k, _v in b["device_ops"]), b["device_ops"]
    assert 0 < p.busy_in("mm") and 0 < p.busy_in("sort")
    assert close(p.busy_in(), p.busy_in("mm") + p.busy_in("sort"))
    # read off the trace by hand when it was recorded (PERF.md section 3)
    for key, want in RECORDED.items():
        got = {"ops_on_device_0": len(p.devices["/device:TPU:0"]),
               "busy_us": round(p.busy_s() * 1e6),
               "window_us": round(p.window_s * 1e6)}[key]
        assert got == want, (key, got, want)


# filled in from the recording (`--record` prints them)
RECORDED: dict = {}


def record() -> None:
    import shutil
    import tempfile

    import jax
    import jax.numpy as jnp

    import devtrace

    if jax.devices()[0].platform != "tpu":
        raise SystemExit("check_trace.py --record needs a TPU")
    x = jnp.ones((2048, 2048), jnp.float32)
    mm = jax.jit(lambda a: (a @ a).sum())
    sort = jax.jit(lambda a: jnp.sort(a.reshape(-1))[:8])
    mm(x).block_until_ready()
    sort(x).block_until_ready()
    tmp = tempfile.mkdtemp(prefix="ggtrace")
    mark = devtrace.start(tmp)
    for i in range(2):
        for name, f in (("mm", mm), ("sort", sort)):
            with jax.profiler.TraceAnnotation(f"bench:{name}.{i}"):
                f(x).block_until_ready()
            time.sleep(0.01)
    devtrace.stop(mark)
    p = devtrace.load(tmp)
    out = sys.argv[2] if len(sys.argv) > 2 else os.path.dirname(SMALL)
    os.makedirs(out, exist_ok=True)
    src = glob.glob(os.path.join(tmp, "plugins", "profile", "*",
                                          "*.xplane.pb"))[0]
    shutil.copy(src, os.path.join(out, "small.xplane.pb"))
    shutil.rmtree(tmp, ignore_errors=True)
    print({"ops_on_device_0": len(p.devices["/device:TPU:0"]),
           "busy_us": round(p.busy_s() * 1e6),
           "window_us": round(p.window_s * 1e6)}, p.breakdown())


if __name__ == "__main__":
    if sys.argv[1:2] == ["--record"]:
        record()
    else:
        check_synthetic()
        if os.path.exists(SMALL):
            check_recorded()
            print("check_trace: synthetic and recorded trace hold")
        else:
            print("check_trace: synthetic holds; no recorded trace at", SMALL)
