"""The one general traffic generator and the two ways of driving it.

A traffic file (`traffic/<name>.json`) names its `kind`:

- `replay_rounds`: a *round* is the file's statements once each in their
  fixed order, in-process through `Database.sql`. A run replays whole
  rounds: it starts no round once `seconds` have passed and finishes the
  round it is in, so every run holds the same statement mix whatever the
  host's speed.
- `closed_loop`: `clients` threads, each with its own `SqlClient` on a unix
  socket to one `SqlServer` in this process, each sending its next statement
  when the last is answered. Each client's schedule is blocks of `block`
  statements holding every shape in its exact share, shuffled by the seed,
  with parameters drawn from the seed.

Each kind is a function `<kind>(env) -> (records, info)` with statement
records {query, params, t0, t1, rows | error, stats}, and a function
`<kind>_metrics(records, info, env)` that gives the kind's end-to-end
numbers once the answers are checked. Nothing here reads the clock of a device.
"""

from __future__ import annotations

import threading
import time

import numpy as np


class Statements:
    """SQL text and parameter draws for the queries a traffic file names."""

    def __init__(self, traffic: dict, sql: dict[str, str], rows: dict[str, int],
                 seed: int):
        self.sql = sql
        self.shapes = traffic.get("shapes") or [
            {"query": q} for q in traffic["round"]]
        self._draw = {}
        for i, sh in enumerate(self.shapes):
            p = sh.get("params")
            if p:
                self._draw[sh["query"]] = getattr(self, "_" + p["kind"])(
                    p, rows, np.random.default_rng([seed, 1000 + i]))

    def queries(self) -> list[str]:
        return [sh["query"] for sh in self.shapes]

    def draw(self, query: str, rng) -> dict:
        d = self._draw.get(query)
        return d(rng) if d else {}

    def text(self, query: str, params: dict) -> str:
        return self.sql[query].format(**params) if params else self.sql[query]

    # parameter kinds: each returns draw(rng) -> {name: value}

    @staticmethod
    def _uniform_int(p, rows, _rng):
        return lambda rng: {p["name"]: int(rng.integers(p["lo"], p["hi"] + 1))}

    @staticmethod
    def _zipf_key(p, rows, perm_rng):
        """Rank r with probability ~ 1/r**s over the keys 1..n of a table,
        ranks mapped to keys through a seeded permutation."""
        n = rows[p["table"]]
        cdf = np.cumsum(1.0 / np.arange(1, n + 1) ** p["s"])
        cdf /= cdf[-1]
        perm = perm_rng.permutation(n) + 1
        return lambda rng: {p["name"]: int(
            perm[min(int(np.searchsorted(cdf, rng.random())), n - 1)])}

    @staticmethod
    def _date_range(p, rows, _rng):
        """[d0, d1): d0 the first of a month from `first` to `last` in
        steps of `step_months`, d1 `span_months` later."""
        first, last = (np.datetime64(p[k], "M") for k in ("first", "last"))
        starts = np.arange(first, last + 1, np.timedelta64(p["step_months"], "M"))

        def draw(rng):
            d0 = starts[int(rng.integers(len(starts)))]
            return {"d0": f"{d0}-01", "d1": f"{d0 + p['span_months']}-01"}
        return draw


def _record(query, params, t0, t1, rows=None, stats=None, error=None) -> dict:
    return {"query": query, "params": params, "t0": t0, "t1": t1,
            "rows": rows, "stats": stats, "error": error}


def run_in_process(db, stmts: Statements, query: str, params: dict,
                   label: str) -> dict:
    """One statement through Database.sql, clocked around the call and
    annotated for the profiler (a no-op when no trace is being taken)."""
    import jax

    text = stmts.text(query, params)
    t0 = time.monotonic()
    try:
        with jax.profiler.TraceAnnotation("bench:" + label):
            r = db.sql(text)
        return _record(query, params, t0, time.monotonic(),
                       [list(row) for row in r.rows()], r.stats)
    except Exception as e:   # counted as a failed statement by the caller
        return _record(query, params, t0, time.monotonic(), error=repr(e))


def warm_up(db, stmts: Statements, seed: int, log) -> list[dict]:
    """Every statement shape through Database.sql until a run reuses both
    the plan and the program (`compiled` false; for a parameterised shape
    also a plan-cache hit), with other parameters each time. Five runs of
    a shape without getting there is a failure."""
    rng = np.random.default_rng([seed, 1])
    out = []
    for q in stmts.queries():
        for i in range(5):
            rec = run_in_process(db, stmts, q, stmts.draw(q, rng), f"warm:{q}.{i}")
            out.append(rec)
            if rec["error"]:
                raise RuntimeError(f"warm-up of {q} failed: {rec['error']}")
            st = rec["stats"]
            log(f"warm-up {q} run {i}: {(rec['t1'] - rec['t0']):.3f} s "
                f"compiled={st.get('compiled')} "
                f"plan_cache={(st.get('plan_cache') or {}).get('hit')}")
            hit = not rec["params"] or (st.get("plan_cache") or {}).get("hit")
            if i and st.get("compiled") is False and hit:
                break
        else:
            raise RuntimeError(f"{q} still compiles or re-plans after 5 runs")
    return out


def replay_rounds(env) -> tuple[list[dict], dict]:
    records, t_start, rounds = [], time.monotonic(), 0
    while True:
        for q in env.traffic["round"]:
            records.append(run_in_process(env.db, env.stmts, q, {}, f"{q}.{rounds}"))
        rounds += 1
        t_end = time.monotonic()
        if t_end - t_start >= env.seconds:
            break
    return records, {"rounds": rounds, "wall_s": t_end - t_start,
                     "t_start": t_start, "t_end": t_end}


def replay_rounds_metrics(records, info, env) -> dict:
    """Base-table rows read by whole rounds over their wall time, a chip."""
    return {"rows_per_s_chip":
            info["rounds"] * env.round_rows / info["wall_s"] / env.chips}


def closed_loop(env) -> tuple[list[dict], dict]:
    import jax
    from greengage_tpu.runtime.server import SqlClient, SqlServer

    stmts, seed = env.stmts, env.seed
    block = [sh["query"] for sh in stmts.shapes for _ in range(sh["share"])]
    srv = SqlServer(env.db, env.sock)
    srv.start()
    clients, per_client = [], []
    try:
        # one at a time: the unix listener's backlog is about five
        for _ in range(env.traffic["clients"]):
            clients.append(SqlClient(env.sock))
        # each shape once over the socket, outside the window
        rng = np.random.default_rng([seed, 3])
        for q in stmts.queries():
            clients[0].sql(stmts.text(q, stmts.draw(q, rng)))
        go = threading.Event()
        deadline = [0.0]

        def client(i: int, c, out: list):
            rng = np.random.default_rng([seed, 2, i])
            go.wait()
            while True:
                for q in rng.permutation(block):
                    if time.monotonic() >= deadline[0]:
                        return
                    params = stmts.draw(q, rng)
                    text = stmts.text(q, params)
                    t0 = time.monotonic()
                    try:
                        with jax.profiler.TraceAnnotation("bench:" + q):
                            resp = c.sql(text)
                        out.append(_record(q, params, t0, time.monotonic(),
                                           resp["rows"]))
                    except Exception as e:   # a failed statement, counted
                        out.append(_record(q, params, t0, time.monotonic(),
                                           error=repr(e)))

        threads = []
        for i, c in enumerate(clients):
            per_client.append([])
            threads.append(threading.Thread(
                target=client, args=(i, c, per_client[-1]),
                name=f"bench-client-{i}"))
            threads[-1].start()
        t_start = time.monotonic()
        deadline[0] = t_start + env.seconds
        go.set()
        for th in threads:
            th.join(timeout=env.seconds + 240)
        if any(th.is_alive() for th in threads):
            raise RuntimeError("a client got no answer 240 s after the window")
    finally:
        for c in clients:
            c.close()
        srv.stop()
    records = sorted((r for out in per_client for r in out),
                     key=lambda r: r["t1"])
    t_end = records[-1]["t1"]
    return records, {"clients": len(clients), "wall_s": t_end - t_start,
                     "t_start": t_start, "t_end": t_end}


def closed_loop_metrics(records, info, env) -> dict:
    """Taken after the answers are checked: a wrong or failed statement is
    not answered, and waits as long as the longest."""
    lat = [(r["t1"] - r["t0"]) * 1e3 for r in records]
    worst = max(lat)
    lat = sorted(worst if r["error"] else v for r, v in zip(records, lat))
    good = sum(1 for r in records if not r["error"])
    return {"stmts_per_s": good / info["wall_s"],
            "p95_ms": lat[min(len(lat) - 1, int(np.ceil(0.95 * len(lat))) - 1)]}
