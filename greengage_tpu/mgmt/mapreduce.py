"""gpmapreduce analog: YAML-defined MAP/REDUCE jobs compiled onto the
engine (reference: /root/reference/gpcontrib/gpmapreduce/ — YAML spec
with DEFINE INPUT/MAP/REDUCE + EXECUTE RUN, mappers in pl/python
yielding [key, value] rows, builtin reducers SUM/COUNT/MIN/MAX/AVG/
IDENTITY).

TPU-first translation: the REDUCE stage is where the data is big and it
compiles to a distributed GROUP BY through the ordinary planner (dense /
sort aggregation, spill, multihost — everything applies).
MAP functions are arbitrary Python by spec, so they run on the host over
the source's columns (the reference likewise runs mappers in per-segment
interpreters, not in the scan kernel); mapped rows bulk-load into an
ephemeral table DISTRIBUTED BY (key), which is exactly the motion the
reference's redistribute-before-reduce performs.

Supported YAML (the reference's demo surface):
  DEFINE:
    - INPUT:  NAME + one of TABLE | QUERY | FILE (server-local paths)
    - MAP:    NAME, FUNCTION (python), PARAMETERS, RETURNS
  EXECUTE:
    - RUN:    SOURCE, MAP (optional), REDUCE (builtin), TARGET (optional
              output table; default prints rows)
Perl mappers and custom TRANSITION reducers are rejected explicitly.
"""

from __future__ import annotations

import numpy as np

BUILTIN_REDUCERS = {
    "SUM": "sum", "COUNT": "count", "MIN": "min", "MAX": "max",
    "AVG": "avg", "IDENTITY": None,
}


class MapReduceError(ValueError):
    pass


def _parse(yaml_text: str) -> dict:
    import yaml

    doc = yaml.safe_load(yaml_text)
    if not isinstance(doc, dict):
        raise MapReduceError("not a gpmapreduce YAML document")
    inputs: dict[str, dict] = {}
    maps: dict[str, dict] = {}
    for item in doc.get("DEFINE", []) or []:
        if "INPUT" in item:
            spec = item["INPUT"]
            inputs[spec["NAME"]] = spec
        elif "MAP" in item:
            spec = item["MAP"]
            lang = str(spec.get("LANGUAGE", "python")).lower()
            if lang not in ("python",):
                raise MapReduceError(
                    f"MAP language {lang!r} is not supported (python only)")
            maps[spec["NAME"]] = spec
        elif "REDUCE" in item:
            raise MapReduceError(
                "custom TRANSITION reducers are not supported; use the "
                "builtins SUM/COUNT/MIN/MAX/AVG/IDENTITY")
    runs = [r["RUN"] for r in doc.get("EXECUTE", []) or [] if "RUN" in r]
    if not runs:
        raise MapReduceError("EXECUTE contains no RUN")
    return {"inputs": inputs, "maps": maps, "runs": runs}


def _source_rows(db, spec: dict):
    """-> (column names, list of per-column numpy/object arrays)."""
    if "TABLE" in spec:
        r = db.sql(f"select * from {spec['TABLE']}")
        return list(r.columns), [_col(r, c) for c in r._order], r
    if "QUERY" in spec:
        r = db.sql(spec["QUERY"])
        return list(r.columns), [_col(r, c) for c in r._order], r
    if "FILE" in spec:
        lines: list[str] = []
        files = spec["FILE"]
        for path in ([files] if isinstance(files, str) else files):
            # reference format is host:/path; embedded engine reads local
            p = path.split(":", 1)[1] if ":" in path else path
            with open(p) as f:
                lines.extend(ln.rstrip("\n") for ln in f)
        return ["value"], [np.array(lines, dtype=object)], None
    raise MapReduceError("INPUT needs TABLE, QUERY, or FILE")


def _col(r, cid):
    v = r.valids.get(cid)
    a = np.asarray(r.cols[cid])
    if v is not None:
        a = a.astype(object)
        a[~np.asarray(v, bool)] = None
    return a


def _compile_mapper(spec: dict):
    """Reference mapper contract: the FUNCTION body sees its PARAMETERS as
    locals and yields [key, value] lists (a generator body, compiled here
    into a wrapper function)."""
    params = [p.split()[0] for p in
              str(spec.get("PARAMETERS", "value text")).split(",")]
    body = spec["FUNCTION"]
    indented = "\n".join("    " + ln for ln in body.splitlines())
    src = f"def __mapper__({', '.join(params)}):\n{indented}\n"
    ns: dict = {}
    exec(src, {"np": np}, ns)      # job YAML is operator-trusted, like the
    return ns["__mapper__"], params  # reference's pl/python execution


def _returns(spec: dict) -> list[tuple[str, str]]:
    out = []
    for r in spec.get("RETURNS", ["key text", "value bigint"]):
        name, typ = str(r).split(None, 1)
        out.append((name, typ))
    return out


def run_job(db, yaml_text: str, out=print) -> list:
    """Execute every RUN; returns the last run's result rows."""
    job = _parse(yaml_text)
    last = []
    for i, run in enumerate(job["runs"]):
        src = job["inputs"].get(run["SOURCE"])
        if src is None:
            raise MapReduceError(f"unknown SOURCE {run['SOURCE']!r}")
        cols, arrays, _ = _source_rows(db, src)

        if "MAP" in run:
            mspec = job["maps"].get(run["MAP"])
            if mspec is None:
                raise MapReduceError(f"unknown MAP {run['MAP']!r}")
            mapper, params = _compile_mapper(mspec)
            rets = _returns(mspec)
            by_name = dict(zip(cols, arrays))
            try:
                args = [by_name[p] for p in params]
            except KeyError as e:
                raise MapReduceError(
                    f"MAP parameter {e} not found in source columns {cols}")
            n = len(args[0]) if args else 0
            out_rows: list[list] = []
            for j in range(n):
                got = mapper(*[a[j] for a in args])
                if got is None:
                    continue
                out_rows.extend(list(row) for row in got)
        else:
            def _sql_type(a) -> str:
                k = np.asarray(a).dtype.kind
                if k in ("i", "u", "b"):
                    return "bigint"
                if k == "f":
                    return "double precision"
                return "text"

            rets = [(c, _sql_type(a)) for c, a in zip(cols, arrays)]
            out_rows = [list(t) for t in zip(*arrays)] if arrays else []

        reduce_name = str(run.get("REDUCE", "IDENTITY")).upper()
        if reduce_name not in BUILTIN_REDUCERS:
            raise MapReduceError(f"unknown REDUCE {reduce_name!r}")
        agg = BUILTIN_REDUCERS[reduce_name]

        tmp = f"__mr_{i}"
        db.sql(f"drop table if exists {tmp}")
        coldefs = ", ".join(f"{nm} {ty}" for nm, ty in rets)
        db.sql(f"create table {tmp} ({coldefs}) "
               f"distributed by ({rets[0][0]})")
        load_cols = {}
        for k, (nm, ty) in enumerate(rets):
            vals = [r_[k] for r_ in out_rows]
            ty_l = ty.lower()
            if "int" in ty_l:
                load_cols[nm] = np.array(vals, dtype=np.int64)
            elif any(x in ty_l for x in ("float", "double", "real")):
                load_cols[nm] = np.array(vals, dtype=np.float64)
            else:
                load_cols[nm] = [str(v) for v in vals]
        db.load_table(tmp, load_cols)

        key, val = rets[0][0], rets[-1][0]
        if agg is None:
            r = db.sql(f"select * from {tmp}")
        else:
            r = db.sql(f"select {key}, {agg}({val}) as {val} from {tmp} "
                       f"group by {key} order by {key}")
        target = run.get("TARGET")
        if target and agg is not None and len(rets) != 2:
            raise MapReduceError(
                "TARGET with an aggregate reducer needs exactly two "
                "RETURNS columns (key, value)")
        if target:
            tdefs = ", ".join(
                f"{nm} {'bigint' if agg in ('sum', 'count') and nm == val else ty}"
                for nm, ty in rets)
            db.sql(f"drop table if exists {target}")
            db.sql(f"create table {target} ({tdefs}) "
                   f"distributed by ({key})")
            tcols = [key, val] if agg else [nm for nm, _ in rets]
            got = {}
            for cid, nm in zip(r._order, tcols):
                a = np.asarray(r.cols[cid])
                got[nm] = a if a.dtype.kind != "O" else [str(x) for x in a]
            db.load_table(target, got)
        else:
            for row in r.rows():
                out("\t".join(str(x) for x in row))
        last = r.rows()
        db.sql(f"drop table if exists {tmp}")
    return last
