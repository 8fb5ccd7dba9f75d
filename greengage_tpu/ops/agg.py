"""Vectorized grouped aggregation — the execHHashagg.c analog, TPU-first.

Two production regimes (no scatter-heavy hash table — TPU scatters
serialize on colliding indices):

  * DENSE: every group key has a known finite domain (TEXT dictionary /
    BOOL); gid is a mixed-radix index and every aggregate is one fused
    masked reduction (the Q1-class fast path).
  * SORT: unbounded cardinality; rows lax.sort by key and each run reduces
    with segmented scans. Where the reference spills its hash table to
    workfiles (execHHashagg.c), this path cannot overflow at all — only the
    output batch capacity can, which retries via the executor's exact-count
    tier mechanism.

Scalar (ungrouped) aggregates are neither: ``scalar_aggregate`` reduces the
masked rows to one cell with a full reduction per aggregate, in every phase.
"""

from __future__ import annotations

from dataclasses import dataclass

import jax.numpy as jnp
import numpy as np
from jax import lax

BIG = jnp.iinfo(jnp.int32).max   # scatter-min identity (used by ops/join)


@dataclass
class KeySpec:
    values: jnp.ndarray
    valid: jnp.ndarray | None
    type: object            # T.SqlType
    hash_lut: jnp.ndarray | None = None  # TEXT: per-dict-entry hashes


@dataclass
class AggSpec:
    name: str
    func: str               # count_star | count | sum | min | max | avg
    values: jnp.ndarray | None
    valid: jnp.ndarray | None
    # DECIMAL inputs are scaled int64; avg must descale its float64 result
    # by 10^scale (sum/min/max stay in scaled-int domain, declared DECIMAL).
    decimal_scale: int = 0


# ---------------------------------------------------------------------------
# Dense path: small known key domains (TEXT dictionaries / BOOL)
#
# gid = mixed-radix index over (code+1) digits (0 = NULL), and every
# aggregate is a fused masked reduction over a [rows, D] broadcast — one
# HBM pass, VPU-only, no scatter/gather. This is the Q1-class fast path;
# high-cardinality keys use the sort path below.
# ---------------------------------------------------------------------------


def dense_gid(keys: list[KeySpec], domains: list[int], sel):
    """-> (gid int32[n] in [0, D), D). domains[i] = |dict_i| + 1 (NULL)."""
    gid = None
    for k, dom in zip(keys, domains):
        idx = k.values.astype(jnp.int32) + 1
        if k.valid is not None:
            idx = jnp.where(k.valid, idx, 0)
        gid = idx if gid is None else gid * jnp.int32(dom) + idx
    D = 1
    for dom in domains:
        D *= dom
    return jnp.where(sel, gid, jnp.int32(0)), D


def dense_decode_keys(keys: list[KeySpec], domains: list[int], D: int):
    """Reconstruct per-group key code arrays [D] (and NULL masks) from gid
    arithmetic — no gathers."""
    iota = jnp.arange(D, dtype=jnp.int32)
    out = []
    strides = []
    s = 1
    for dom in reversed(domains):
        strides.append(s)
        s *= dom
    strides = list(reversed(strides))
    for k, dom, st in zip(keys, domains, strides):
        idx = (iota // jnp.int32(st)) % jnp.int32(dom)
        code = (idx - 1).astype(k.values.dtype)
        valid = idx > 0
        out.append((code, valid))
    return out


def _masked_reduce(op, vals, gid, D, mask, ident):
    """One fused pass: reduce vals into D groups via broadcast-compare.
    XLA fuses the [n, D] compare+select into the reduction tiles."""
    sel2 = mask[:, None] & (gid[:, None] == jnp.arange(D, dtype=jnp.int32)[None, :])
    filled = jnp.where(sel2, vals[:, None], ident)
    return op(filled, axis=0)


def _run_aggs(aggs: list[AggSpec], sel, seg_sum, seg_minmax, seg_count=None):
    """The per-function aggregate semantics, shared by every grouping
    regime. The reduce primitives are injected:

      seg_sum(masked_vals) -> per-group sums (inputs pre-masked to 0)
      seg_minmax(filled_vals, func, ident) -> per-group min/max
        (inputs pre-filled with the identity at dead/NULL rows)
      seg_count(live_mask) -> per-group int64 row counts, where a regime
        counts cheaper than it sums (the sort regime: one int32 prefix
        sum); default: seg_sum of the mask as int64

    Semantics kept in ONE place: count(*)/count ignore NULLs per column;
    sum of no rows is NULL; avg = float64 sum/count descaled by the decimal
    scale; min/max of no rows is NULL.
    """
    out_vals: dict[str, jnp.ndarray] = {}
    out_valid: dict[str, jnp.ndarray] = {}
    counts_cache: dict = {}

    def live_count(spec):
        key = None if spec is None or spec.valid is None else id(spec.valid)
        if key not in counts_cache:
            lv = sel if spec is None or spec.valid is None else (sel & spec.valid)
            counts_cache[key] = (seg_sum(lv.astype(jnp.int64))
                                 if seg_count is None else seg_count(lv))
        return counts_cache[key]

    group_count = live_count(None)
    for spec in aggs:
        if spec.func == "count_star":
            out_vals[spec.name] = group_count
            out_valid[spec.name] = None
            continue
        lv = sel if spec.valid is None else sel & spec.valid
        if spec.func == "count":
            out_vals[spec.name] = live_count(spec)
            out_valid[spec.name] = None
            continue
        vals = spec.values
        if spec.func in ("sum", "avg"):
            acc = jnp.float64 if vals.dtype.kind == "f" else jnp.int64
            s = seg_sum(jnp.where(lv, vals.astype(acc), acc(0)))
            cnt = live_count(spec)
            if spec.func == "sum":
                out_vals[spec.name] = s
                out_valid[spec.name] = cnt > 0   # SQL: sum of no rows is NULL
            else:
                denom = jnp.where(cnt == 0, jnp.int64(1), cnt).astype(jnp.float64)
                avg = s.astype(jnp.float64) / denom
                if spec.decimal_scale:
                    avg = avg / (10.0 ** spec.decimal_scale)
                out_vals[spec.name] = avg
                out_valid[spec.name] = cnt > 0
        elif spec.func in ("min", "max"):
            # the identity stays HOST-concrete (numpy, not jnp): under a
            # jit trace jnp.array() yields a tracer, and reducers fill
            # padding with it as a constant
            if vals.dtype.kind == "f":
                ident = np.array(np.inf if spec.func == "min" else -np.inf,
                                 vals.dtype)
            else:
                info = jnp.iinfo(vals.dtype)
                ident = np.array(info.max if spec.func == "min" else info.min,
                                 vals.dtype)
            filled = jnp.where(lv, vals, ident)
            out_vals[spec.name] = seg_minmax(filled, spec.func, ident)
            out_valid[spec.name] = live_count(spec) > 0
        else:
            raise NotImplementedError(spec.func)
    return out_vals, out_valid


def dense_aggregate(gid, D: int, aggs: list[AggSpec], sel):
    """_run_aggs semantics over dense group ids."""
    def seg_sum(masked):
        sel2 = gid[:, None] == jnp.arange(D, dtype=jnp.int32)[None, :]
        return jnp.sum(jnp.where(sel2, masked[:, None], masked.dtype.type(0)), axis=0)

    def seg_minmax(filled, func, ident):
        op = jnp.min if func == "min" else jnp.max
        return _masked_reduce(op, filled, gid, D, jnp.ones_like(sel), ident)

    return _run_aggs(aggs, sel, seg_sum, seg_minmax)


def scalar_aggregate(aggs: list[AggSpec], sel):
    """_run_aggs semantics with no group keys: one cell. _run_aggs has
    masked dead and NULL rows to the identity, so each aggregate is one
    full reduction over the batch. No slot table: a scatter of every row
    into one address serializes (~80 ns a row on the v5e)."""
    def seg_sum(masked):
        return jnp.sum(masked).reshape(1)

    def seg_minmax(filled, func, ident):
        op = jnp.min if func == "min" else jnp.max
        return op(filled, initial=ident).reshape(1)

    return _run_aggs(aggs, sel, seg_sum, seg_minmax)


# ---------------------------------------------------------------------------
# Sort-based grouping: the high-cardinality path.
#
# The reference spills its hybrid hash agg to workfiles when the table
# overflows (src/backend/executor/execHHashagg.c); on TPU the scatter-heavy
# slot table serializes on colliding indices, so past the dense-domain
# regime we lax.sort rows by their group keys and reduce each run with
# segmented cumsum-diffs and scans — O(n log n), fully vectorized, no
# scatter, and cardinality bounded only by the batch itself (a GROUP BY can
# never produce more groups than input rows, so nothing ever "overflows"
# the way a hash table does; only the *output capacity* chosen for the
# batch above can, which retries via the executor's tier mechanism).
# ---------------------------------------------------------------------------


def _group_encode(k: KeySpec) -> list:
    """Equality-preserving uint64 encoding (+ null operand when nullable).
    Grouping needs equal-keys-adjacent, not collation order, so TEXT groups
    by dictionary code and float64 only canonicalizes -0.0/NaN."""
    from greengage_tpu import types as T

    v = k.values
    if k.type.kind is T.Kind.FLOAT64:
        v = jnp.where(v == 0.0, 0.0, v)
        v = jnp.where(jnp.isnan(v), jnp.float64(jnp.nan), v)
        enc = v.view(jnp.uint64)
    else:
        enc = v.astype(jnp.int64).view(jnp.uint64)
    ops = []
    if k.valid is not None:
        ops.append(jnp.where(k.valid, jnp.uint8(1), jnp.uint8(0)))
        enc = jnp.where(k.valid, enc, jnp.uint64(0))
    ops.append(enc)
    return ops


def pack_bits(bounds: list) -> int | None:
    """Total packed bits for per-key integer bounds [(lo, hi) | None].
    Each key takes ceil(log2(hi - lo + 2)) bits (the +2 reserves field
    value 0 for NULL) plus nothing else. None when any key is unbounded
    or the fields exceed 63 bits (bit 63 carries the dead-row flag)."""
    if not bounds or any(b is None for b in bounds):
        return None
    total = 0
    for lo, hi in bounds:
        span = int(hi) - int(lo) + 2
        if span <= 1:
            span = 2
        total += max(span - 1, 1).bit_length()
        if total > 63:
            return None
    return total


def pack_keys(keys: list[KeySpec], bounds: list, sel):
    """Pack stats-bounded integer keys into ONE uint64 word per row
    (dead flag in bit 63, then per-key fields, NULL = field value 0).

    -> (packed uint64[n], violation bool scalar). ``violation`` fires when
    any LIVE, non-NULL value falls outside its advertised bound — packing
    would alias distinct keys, so the caller must re-run unpacked (stale
    ANALYZE stats after DML). Equal packed words <=> equal key tuples
    (including NULL positions) whenever violation is False.

    Motivation (measured v5e, NOTES.md): lax.sort costs ~40 ns/row per
    OPERAND — Q3's 3-key group sort carries dead + 3 encodings + rowid = 5
    operands; packed it carries 2. That is the difference between a ~10s
    and a ~4s group phase at SF10.
    """
    n = sel.shape[0]
    word = jnp.zeros((n,), jnp.uint64)
    violation = jnp.zeros((), bool)
    for k, (lo, hi) in zip(keys, bounds):
        span = max(int(hi) - int(lo) + 2, 2)
        width = max(span - 1, 1).bit_length()
        v = k.values.astype(jnp.int64)
        in_b = (v >= lo) & (v <= hi)
        live = sel if k.valid is None else (sel & k.valid)
        violation = violation | jnp.any(live & ~in_b)
        field = jnp.where(in_b, v - jnp.int64(lo) + 1, 0).astype(jnp.uint64)
        if k.valid is not None:
            field = jnp.where(k.valid, field, jnp.uint64(0))
        word = (word << jnp.uint64(width)) | field
    word = jnp.where(sel, word, word | (jnp.uint64(1) << jnp.uint64(63)))
    return word, violation


def hash_keys(key_ops: list, sel):
    """One uint64 word a row from its key encodings: two independent 32-bit
    row hashes side by side in bits 0..62, the dead flag in bit 63. Equal
    key tuples give equal words; different ones almost always different."""
    from greengage_tpu.ops import hashing

    halves = [hashing.row_hash([hashing.hash_i64(op, seed) for op in key_ops])
              for seed in (0, 0x9E3779B9)]
    word = ((halves[1].astype(jnp.uint64) << jnp.uint64(32))
            | halves[0].astype(jnp.uint64)) >> jnp.uint64(1)
    return jnp.where(sel, word, word | (jnp.uint64(1) << jnp.uint64(63)))


def group_sort(keys: list[KeySpec], sel, bounds: list | None = None,
               hashed: bool = False):
    """Sort rows by group keys, dead rows last.

    -> (perm int32[n], boundary bool[n], sel_sorted bool[n], violation):
    perm is the gather permutation (sorted_col = col[perm]); boundary marks
    the first (live) row of each equal-key run — the group's representative
    row. ``bounds`` (per-key (lo, hi) from ANALYZE) enables the packed
    single-operand sort. ``hashed`` sorts keys that do not pack by a
    64-bit hash word instead of by every key: lax.sort costs per operand
    on the device and far more than that in the TPU compiler (seven
    operands of 2^20 rows: over a quarter of an hour, two: two minutes),
    and grouping needs equal keys adjacent, not ordered. violation is a
    bool scalar the caller must route to a flag that re-runs with neither
    (None where the keys were sorted themselves): a live value outside its
    bounds, or two different key tuples with one hash word.
    """
    n = sel.shape[0]
    violation = None
    word = None
    key_ops = []
    if bounds is not None and pack_bits(bounds) is not None:
        word, violation = pack_keys(keys, bounds, sel)
    else:
        for k in keys:
            key_ops.extend(_group_encode(k))
        if hashed and len(key_ops) > 1:
            word = hash_keys(key_ops, sel)
            violation = jnp.zeros((), bool)
    if word is not None:
        sorted_ops = lax.sort(
            (word, jnp.arange(n, dtype=jnp.int32)), num_keys=2)
        wkey = sorted_ops[0]
        perm = sorted_ops[-1]
        sel_sorted = (wkey >> jnp.uint64(63)) == 0
        if n > 1:
            first = jnp.concatenate(
                [jnp.ones((1,), bool), wkey[1:] != wkey[:-1]])
            if key_ops:
                differs = first[1:]
                # exact all the same: a run of one word must be a run of
                # one key tuple (live rows sort first, so a live row's
                # predecessor is live)
                other = jnp.zeros((n - 1,), bool)
                for op in key_ops:
                    s = op[perm]
                    other = other | (s[1:] != s[:-1])
                violation = jnp.any(~differs & other & sel_sorted[1:])
        else:
            first = jnp.ones((n,), bool)
        return perm, sel_sorted & first, sel_sorted, violation

    dead = (~sel).astype(jnp.uint8)
    operands = [dead] + key_ops + [jnp.arange(n, dtype=jnp.int32)]
    sorted_ops = lax.sort(tuple(operands), num_keys=len(operands))
    perm = sorted_ops[-1]
    sel_sorted = sorted_ops[0] == 0
    if key_ops and n > 1:
        neq = None
        for s in sorted_ops[1:1 + len(key_ops)]:
            d = s[1:] != s[:-1]
            neq = d if neq is None else (neq | d)
        first = jnp.concatenate([jnp.ones((1,), bool), neq])
    else:
        first = jnp.concatenate(
            [jnp.ones((min(n, 1),), bool), jnp.zeros((max(n - 1, 0),), bool)])
    return perm, sel_sorted & first, sel_sorted, violation


# What the two forms of `group_starts` cost on a TPU v5e (the builder's
# microbenchmark on the chip, PR 36: PERF.md §6). The search: ns a group a
# round, 20.9-23.5 for 2^17-2^21 groups among 2^25 rows, 19.7 for 2^14 among
# 2^20 (29.5 at 2^23 among 2^25). The one-operand int32 sort: ns a row, 2.43
# at 2^25 rows, 2.16 at 2^24, 1.5-1.7 at 2^20-2^22.
NS_SEARCH_GROUP_ROUND = 21.0
NS_SORT_ROW = 2.4


def group_starts_direct(out_cap: int, n: int) -> bool:
    """Whether `group_starts` takes its one-pass form for a table of
    `out_cap` groups over `n` sorted rows (both static a program; the
    compiler records the answer for the `agg_sort_capacity_direct`
    counter). The search is ceil(log2(n + 1)) rounds of `out_cap` dependent
    gathers, the one-pass form a sort of the `n` rows: at 2^25 rows they
    break even near 150,000 groups (measured: 2^17 groups 71 ms against the
    sort's 82, 2^19 groups 295 ms)."""
    return out_cap * n.bit_length() * NS_SEARCH_GROUP_ROUND > n * NS_SORT_ROW


def _starts_search(csb, out_cap: int):
    """One binary search a group over the running boundary count: lowers to
    a `while` of ceil(log2(n + 1)) rounds, each a gather of `out_cap`
    elements out of n."""
    return jnp.searchsorted(
        csb, jnp.arange(1, out_cap + 1, dtype=jnp.int32)).astype(jnp.int32)


def _starts_direct(boundary, out_cap: int):
    """One pass over the rows: a boundary row keeps its row number, every
    other row takes n, and a one-operand sort brings the boundary rows'
    numbers to the front in order, the n of absent groups behind them."""
    n = boundary.shape[0]
    rows = lax.sort(jnp.where(boundary, jnp.arange(n, dtype=jnp.int32),
                              jnp.int32(n)))
    return jnp.pad(rows, (0, max(out_cap - n, 0)),
                   constant_values=n)[:out_cap]


def group_starts(boundary, csb, out_cap: int):
    """-> int32[out_cap]: the sorted-row index of each group's first row,
    n for an absent group, the first `out_cap` groups only. `csb` is the
    running count of `boundary`. Two forms of one result, chosen by the
    shapes (`group_starts_direct`)."""
    if group_starts_direct(out_cap, csb.shape[0]):
        return _starts_direct(boundary, out_cap)
    return _starts_search(csb, out_cap)


def sorted_group_aggregate(boundary, sel_sorted, aggs: list[AggSpec],
                           out_cap: int):
    """Table-shaped aggregation over key-sorted rows.

    -> (vals {name: [out_cap]}, valids, srcpos int32[out_cap], total) where
    group g's values live at slot g (groups numbered in key-sort order) and
    srcpos[g] is the SORTED-row index of g's first row (gather keys there).
    Groups beyond out_cap are dropped — the caller flags total > out_cap
    and retries with the exact count.

    TPU cost model (v5e; ns a row). Measured on the chip at 2^25 rows by
    PR 36's builder (PERF.md §6): a one-operand int32 sort 2.4; a scatter
    whose index vector is promised ascending 8.8; any other scatter into a
    large table 7-11, because the TPU compiler first sorts its (index,
    update) pairs (7.1 with unique addresses, 11.0 with three quarters of
    the rows on one). Rows that share an address cost no more in an int32
    min or set (all but 30 rows of 2^25 on one slot of 2^15: 8.8); the one
    collision on record that did is Q6's ungrouped 64-bit sums as a scatter
    into two slots, 91 a row (ledger, PR 32). `gg checkperf --device`
    (PR 23): a random scatter-add 9.85, a random gather 8.79, a two-operand
    sort 1.61 an operand. A binary search over rows is ~21 a group a round
    (`group_starts_direct`).
    So: sums/counts = whole-batch cumsum + span difference at the M group
    boundaries. int64 (scaled DECIMAL) sums split into 32-bit limbs with
    separate cumsums so the span difference is EXACT regardless of batch
    magnitude; float64 sums scatter-add into the group table (a whole-batch
    prefix sum would cost a small group the batch total's rounding).
    min/max are not invertible, so they scatter into the group-id table
    (paid per min/max aggregate).
    All spec arrays must already be key-sorted."""
    n = sel_sorted.shape[0]
    csb = jnp.cumsum(boundary.astype(jnp.int32))
    total = csb[-1] if n else jnp.int32(0)
    # first sorted row of group g; RAW positions keep n for absent groups
    # so the span ends don't truncate the last real group off by one
    raw = group_starts(boundary, csb, out_cap)
    ends = jnp.clip(
        jnp.concatenate([raw[1:], jnp.full((1,), n, jnp.int32)]) - 1,
        0, max(n - 1, 0))
    srcpos = jnp.clip(raw, 0, max(n - 1, 0))
    gid = csb - 1                      # per-row group slot (dead rows get
    # the last group's id but every reducer masks them to the identity)
    tgt = jnp.where((gid >= 0) & (gid < out_cap), gid, out_cap)

    def span(cs):
        base = jnp.where(srcpos > 0, cs[jnp.clip(srcpos - 1, 0, max(n - 1, 0))],
                         jnp.zeros((), cs.dtype))
        return cs[ends] - base

    def seg_sum(masked):
        if masked.dtype == jnp.int64:
            lo = masked & jnp.int64(0xFFFFFFFF)     # [0, 2^32)
            hi = masked >> jnp.int64(32)            # arithmetic shift
            return (span(jnp.cumsum(hi)) << jnp.int64(32)) + span(jnp.cumsum(lo))
        if masked.dtype == jnp.float64:
            # floats cannot limb-split: a whole-batch prefix sum loses
            # precision proportional to the BATCH total (a small group's
            # span difference subtracts two near-equal ~1e12 prefixes), so
            # float sums pay the scatter — accumulation stays group-local,
            # matching per-group summation accuracy
            tbl = jnp.zeros((out_cap + 1,), jnp.float64).at[tgt].add(masked)
            return tbl[:out_cap]
        return span(jnp.cumsum(masked))

    def seg_minmax(filled, func, ident):
        tbl = jnp.full((out_cap + 1,), ident, dtype=filled.dtype)
        tbl = tbl.at[tgt].min(filled) if func == "min" else tbl.at[tgt].max(filled)
        return tbl[:out_cap]

    def seg_count(live):
        # a count is below n < 2^31: one int32 prefix sum, exact. As an
        # int64 sum it would split into limbs, and the TPU compiler spends
        # tens of minutes on a prefix sum of (mask as int64) >> 32, which
        # it knows to be zero (Q18's program did not compile for that)
        return span(jnp.cumsum(live.astype(jnp.int32))).astype(jnp.int64)

    vals, valids = _run_aggs(aggs, sel_sorted, seg_sum, seg_minmax, seg_count)
    return vals, valids, srcpos, total
