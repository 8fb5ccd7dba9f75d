"""TPU-oriented cost model — the libgpdbcost analog, radically smaller,
built on seven per-row / per-byte primitive costs (CALIBRATION_DEFAULTS):
random gather, scatter(-add), sort per operand, one HBM streaming pass,
ICI bytes, and the device->host fetch (per call + per byte).
`gg checkperf -d DIR --device` measures all of them on the live devices
and `--apply` persists them for the cluster; the defaults' origins are
given beside each value.

Costs are estimated PER-CHIP WALL NANOSECONDS: global row counts divide by
nseg for partitioned work, but a broadcast build is full-size on every
chip — that asymmetry (sort-building a replicated table costs ~250x its
ICI transfer per row) is exactly what a bytes-only model got wrong, and
why the reference ships a calibrated CCostModelGPDB rather than raw I/O
counts.

Row estimates come from storage manifests (exact for scans) and, after
ANALYZE, from column statistics (planner/stats.py — the
clauselist_selectivity / ORCA statistics-calculus analog): equality uses
MCV frequencies or 1/NDV, ranges interpolate [min, max], GROUP BY takes the
NDV product, joins divide by the larger key NDV. Without stats the round-1
constants remain as fallbacks. A mis-estimate here is expensive on TPU —
each capacity-overflow retry is a full XLA recompile — so stats pay for
themselves immediately.
"""

from __future__ import annotations
import bisect
import math

from greengage_tpu import expr as E

DEFAULT_FILTER_SELECTIVITY = 0.25
EQ_SELECTIVITY = 0.05
RANGE_SELECTIVITY = 0.33

# primitive costs (ns per row / byte). These are DEFAULTS: `gg checkperf
# --device --apply` re-measures them on the live chip and persists a
# <cluster>/calibration.json that set_calibration() loads at connect, so
# the model tracks the hardware it runs on (the gpcheckperf + libgpdbcost
# calibration intent, gpMgmt/bin/gpcheckperf:1). The first five are an
# earlier builder's estimates that plan choices were tuned against; the
# two host-fetch values are what checkperf measured on one TPU v5e chip
# (PERF.md lists all seven measured primitives beside these).
CALIBRATION_DEFAULTS = {
    "ns_gather_row": 10.7,
    "ns_scatter_row": 90.0,
    "ns_sort_row": 40.0,     # per sort operand (key or payload column)
    "ns_stream_byte": 0.0025,
    "ns_ici_byte": 0.02,
    "ns_host_byte": 3.7,     # device->host fetch, ~270 MB/s
    "ns_host_call": 2.8e5,   # fixed per device->host transfer, ~0.28 ms
}


def set_calibration(values: dict | None) -> None:
    """Install measured primitive costs (keys of CALIBRATION_DEFAULTS;
    missing/invalid entries keep their defaults). None resets."""
    g = globals()
    for k, default in CALIBRATION_DEFAULTS.items():
        v = (values or {}).get(k, default)
        try:
            v = float(v)
        except (TypeError, ValueError):
            v = default
        g[k.upper()] = v if v > 0 else default


def current_calibration() -> dict:
    return {k: globals()[k.upper()] for k in CALIBRATION_DEFAULTS}


set_calibration(None)   # establish NS_GATHER_ROW .. NS_HOST_CALL globals

# Pipelined-motion overlap credit on the redistribute branch of
# motion_cost: with motion_pipeline on, the sub-exchange schedule
# (parallel/motion.py _exchange) and the host bucket pipeline
# (exec/motionpipe.py) hide part of each exchange behind neighboring
# compute, so the planner should not price a redistribute as if the
# device sat idle for the full transfer. Installed by the session from
# the motion_pipeline* GUCs (same process-global pattern as
# set_calibration — the SET broadcast keeps a multihost gang in
# lockstep). 1.0 = no credit (pipeline off / single bucket).
MOTION_PIPELINE_OVERLAP = 1.0


def set_motion_overlap(factor) -> None:
    """Install the redistribute overlap credit (0 < factor <= 1)."""
    global MOTION_PIPELINE_OVERLAP
    try:
        f = float(factor)
    except (TypeError, ValueError):
        f = 1.0
    MOTION_PIPELINE_OVERLAP = min(max(f, 0.25), 1.0)


def _value_of(e):
    """Estimation value of a comparison operand: a literal's value, or a
    hoisted parameter's est_value (sql/paramize.py — the value the
    statement that seeded the generic plan carried), unwrapping the
    binder's numeric-coercion Cast. None when unknown."""
    if isinstance(e, E.Literal):
        return e.value
    if isinstance(e, E.Param):
        return getattr(e, "_est_value", None)
    if isinstance(e, E.Cast) and isinstance(e.arg, E.Param):
        v = getattr(e.arg, "_est_value", None)
        if v is None:
            return None
        from greengage_tpu.sql.paramize import coerce_storage_value

        try:
            return coerce_storage_value(v, e.arg.type, e.type)
        except Exception:
            return None
    return None


def _col_and_lit(pred: E.Cmp):
    """-> (col_id, literal/param value, op oriented col-op-lit) or None."""
    left, right, op = pred.left, pred.right, pred.op
    flip = {"<": ">", ">": "<", "<=": ">=", ">=": "<="}
    if _value_of(left) is not None and isinstance(right, E.ColRef):
        left, right, op = right, left, flip.get(op, op)
    if isinstance(left, E.ColRef):
        v = _value_of(right)
        if v is not None:
            try:
                return left.name, float(v), op
            except (TypeError, ValueError):
                return None
    return None


def _eq_sel(cs, v: float) -> float:
    for mval, frac in cs.mcv:
        if mval == v:
            return min(max(frac, 1e-6), 1.0)
    if cs.ndv > 0:
        return min((1.0 - cs.null_frac) / cs.ndv, 1.0)
    return EQ_SELECTIVITY


def _hist_frac_below(hist: list, v: float) -> float:
    """Fraction of non-null values below ``v`` from an equi-depth histogram
    (planner/stats.py): whole buckets below v count 1/nbuckets each, the
    straddling bucket interpolates linearly within its boundaries — the
    CHistogram bucket-calculus / ineq_histogram_selectivity analog."""

    nb = len(hist) - 1
    if v <= hist[0]:
        return 0.0
    if v >= hist[-1]:
        return 1.0
    i = min(bisect.bisect_right(hist, v) - 1, nb - 1)
    lo, hi = hist[i], hist[i + 1]
    within = 0.5 if hi <= lo else (v - lo) / (hi - lo)
    return (i + min(max(within, 0.0), 1.0)) / nb


def _range_sel(cs, v: float, op: str) -> float:
    if len(cs.hist) >= 2:
        frac = _hist_frac_below(cs.hist, v)
        s = frac if op in ("<", "<=") else 1.0 - frac
        return float(min(max(s, 0.0), 1.0)) * (1.0 - cs.null_frac)
    if cs.min is None or cs.max is None:
        return RANGE_SELECTIVITY
    lo, hi = cs.min, cs.max
    if hi <= lo:
        return 0.5
    frac = (v - lo) / (hi - lo)
    if op in ("<", "<="):
        s = frac
    else:
        s = 1.0 - frac
    return float(min(max(s, 0.0), 1.0)) * (1.0 - cs.null_frac)


def _pair_ranges(conjuncts, lookup) -> list:
    """A lower and an upper bound on one column with statistics are one
    range, not two independent filters: the rows between them are
    sel(upper) + sel(lower) - 1 of the non-null ones (clauselist_selectivity's
    range pairing). -> the conjuncts, each such pair replaced by its joint
    selectivity as a float. Multiplying the two sides instead makes the
    estimate of `k between lo and lo + n` grow with lo: every refresh of a
    warehouse's oldest keys would move its capacity buckets."""
    if lookup is None:
        return list(conjuncts)
    out, open_bounds = [], {}   # column -> (index in out, side, selectivity)
    for c in conjuncts:
        info = _col_and_lit(c) if isinstance(c, E.Cmp) else None
        cs = lookup(info[0]) if info else None
        side = {"<": "hi", "<=": "hi", ">": "lo", ">=": "lo"}.get(
            info[2]) if cs is not None else None
        if side is None:
            out.append(c)
            continue
        col, v, op = info
        sel = _range_sel(cs, v, op)
        first = open_bounds.get(col)
        if first is not None and first[1] != side:
            joint = sel + first[2] - (1.0 - cs.null_frac)
            out[first[0]] = float(max(joint, 1e-6))
            del open_bounds[col]
        else:
            open_bounds.setdefault(col, (len(out), side, sel))
            out.append(c)
    return out


def filter_selectivity(pred: E.Expr, lookup=None) -> float:
    """Estimated fraction of rows passing ``pred``. ``lookup`` maps a
    column id to its ColumnStats (or None) when the caller can resolve
    column origins; without it the constant fallbacks apply."""
    if isinstance(pred, E.Cmp):
        info = _col_and_lit(pred) if lookup is not None else None
        cs = lookup(info[0]) if info else None
        if cs is not None:
            _, v, op = info
            if op == "=":
                return _eq_sel(cs, v)
            if op == "<>":
                return max(1.0 - _eq_sel(cs, v) - cs.null_frac, 0.0)
            return _range_sel(cs, v, op)
        return EQ_SELECTIVITY if pred.op == "=" else RANGE_SELECTIVITY \
            if pred.op in ("<", "<=", ">", ">=") else DEFAULT_FILTER_SELECTIVITY
    if isinstance(pred, E.InList):
        cs = (lookup(pred.arg.name)
              if lookup is not None and isinstance(pred.arg, E.ColRef) else None)
        if cs is not None and cs.ndv > 0:
            return min(len(pred.values) * (1.0 - cs.null_frac) / cs.ndv, 1.0)
        return min(len(pred.values) * EQ_SELECTIVITY, 1.0)
    if isinstance(pred, E.IsNull):
        cs = (lookup(pred.arg.name)
              if lookup is not None and isinstance(pred.arg, E.ColRef) else None)
        if cs is not None:
            return (1.0 - cs.null_frac) if pred.negate else cs.null_frac
        return 0.9 if pred.negate else 0.1
    if isinstance(pred, E.Not):
        return max(1.0 - filter_selectivity(pred.arg, lookup), 1e-4)
    if isinstance(pred, E.BoolOp) and pred.op == "and":
        s = 1.0
        for a in _pair_ranges(pred.args, lookup):
            s *= a if isinstance(a, float) else filter_selectivity(a, lookup)
        return max(s, 1e-4)
    if isinstance(pred, E.BoolOp) and pred.op == "or":
        s = 0.0
        for a in pred.args:
            s += filter_selectivity(a, lookup)
        return min(s, 1.0)
    return DEFAULT_FILTER_SELECTIVITY


def row_width(cols) -> float:
    return 8.0 * max(len(cols), 1)


def est_groups(rows: float, ndvs: list[float] | None = None) -> float:
    """Group-count estimate. With per-key NDVs (ANALYZE ran): the NDV
    product capped at the row count — the standard independence bound.
    Without: the round-1 sqrt heuristic."""
    if ndvs:
        prod = 1.0
        for d in ndvs:
            prod *= max(d, 1.0)
            if prod >= rows:
                return max(rows, 1.0)
        return max(min(prod, rows), 1.0)

    return min(max(math.sqrt(max(rows, 1.0)) * 4, 16.0), 1 << 20)


def join_rows(left_rows: float, right_rows: float,
              key_ndvs: list[tuple[float, float]] | None) -> float | None:
    """Equi-join output estimate: |L||R| * prod 1/max(ndv_l, ndv_r).
    None when any key pair lacks stats (caller falls back)."""
    if not key_ndvs:
        return None
    sel = 1.0
    for nl, nr in key_ndvs:
        if nl <= 0 or nr <= 0:
            return None
        sel /= max(nl, nr)
    return max(left_rows * right_rows * sel, 1.0)


def motion_cost(kind: str, rows: float, width: float, nseg: int) -> float:
    """Per-chip ns to move ``rows`` (GLOBAL count) of ``width`` bytes.
    Redistribute: each chip sends/receives ~rows/nseg. Broadcast: every
    chip receives (nseg-1)/nseg of the whole relation. Gather: the
    coordinator pulls everything through one device->host fetch."""
    s = max(nseg, 1)
    if kind == "broadcast":
        return rows * width * NS_ICI_BYTE * (s - 1) / s
    if kind == "gather":
        return NS_HOST_CALL + rows * width * NS_HOST_BYTE
    return (rows / s) * width * NS_ICI_BYTE * MOTION_PIPELINE_OVERLAP


def stream_cost(rows: float, width: float, nseg: int = 1) -> float:
    """One HBM pass over a partitioned relation, per chip."""
    return (rows / max(nseg, 1)) * width * NS_STREAM_BYTE


def join_build_cost(rows: float, nkeys: int, nseg: int,
                    replicated: bool = False) -> float:
    """Sort-based hash-table build (ops/join.py): one multi-operand
    lax.sort + bucket scatter-add. A replicated (broadcast) build runs
    FULL-SIZE on every chip — no 1/nseg discount."""
    per_chip = rows if replicated else rows / max(nseg, 1)
    return per_chip * (NS_SORT_ROW * (nkeys + 2) + NS_SCATTER_ROW * 0.1)


def join_probe_cost(rows: float, nkeys: int, nseg: int) -> float:
    """Run-head walk: ~2 hops x one gather per key column per hop."""
    return (rows / max(nseg, 1)) * NS_GATHER_ROW * 2 * (nkeys + 1)


def agg_cost(rows: float, groups: float, nkeys: int, naggs: int,
             width: float, nseg: int) -> float:
    """One aggregation pass. Small group domains compile to the dense
    scatter-add path (stream-class: measured Q1 ~1.4 ns/row all-in);
    unbounded cardinality falls onto the sort-based path (a multi-operand
    sort of keys + payload dominates)."""
    s = max(nseg, 1)
    per_chip = rows / s
    if groups <= 4096:
        return per_chip * width * NS_STREAM_BYTE * max(naggs, 1)
    return per_chip * NS_SORT_ROW * (nkeys + max(naggs, 1))
