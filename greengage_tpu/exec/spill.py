"""Host-offload spill: pass-partitioned execution past HBM capacity.

The workfile-manager role (reference: src/backend/utils/workfile_manager/
workfile_mgr.c:544, hybrid hash agg spilling in execHHashagg.c) rethought
for the TPU memory hierarchy: host RAM plays the workfile, and the unit of
spilling is a whole EXECUTION PASS instead of a hash batch.

Applicability: plans whose below-gather tree is
    [Sort|Limit|Project|Filter]* FinalAggregate( Motion( PartialAggregate(
        probe-linear subtree )))
— every TPC-H-style join+GROUP BY/scalar aggregate. The probe-linear
subtree is row-linear in one big table (joins only fan out on their PROBE
side; builds stay whole), so partitioning that table's rows into P chunks
and running the subtree + PARTIAL aggregate per chunk yields partial
states whose union merges exactly in the FINAL aggregate:

    pass p:  chunk_p -> joins -> partial agg   (fits in HBM)
             gather partial rows to host       (small)
    merge:   final plan with the partial subtree replaced by a host-staged
             input of all passes' partial rows

This completes any such query whose PER-PASS working set fits, instead of
rejecting it at the vmem admission check.
"""

from __future__ import annotations
import copy
import itertools
import threading

import numpy as np

from greengage_tpu import expr as E
from greengage_tpu import types as T
from greengage_tpu.runtime import interrupt
from greengage_tpu.runtime import memaccount
from greengage_tpu.runtime import trace as _trace
from greengage_tpu.runtime.logger import counters
from greengage_tpu.planner.locus import Locus
from greengage_tpu.planner.logical import (Aggregate, ColInfo, Filter, Join,
                                           Limit, Motion, MotionKind,
                                           PartialState, Plan, Project, Scan,
                                           Sort, Window)


class NotSpillable(ValueError):
    """The plan's shape cannot be pass-partitioned soundly."""


class ScheduleRecorder:
    """Multihost spill-schedule parity (docs/PERF.md "Data movement").
    The tiered workfile's pass/bucket schedules are pure functions of
    compiled estimates + settings, so every gang member computes the same
    one. This makes that a VERIFIED invariant instead of a hope: the
    coordinator arms recording per statement (``begin``, on the
    statement's thread), every schedule decision is noted (and broadcast
    one-way to the workers for observability), workers ship the schedule
    they actually ran in their completion ack, and the session compares.
    Single-host runs never arm recording, so ``note`` is a no-op there."""

    def __init__(self, multihost):
        self.multihost = multihost
        self._tls = threading.local()

    def begin(self) -> None:
        self._tls.steps = []

    def note(self, kind: str, **info) -> None:
        steps = getattr(self._tls, "steps", None)
        if steps is None:
            return
        entry = {"kind": kind, **info}
        steps.append(entry)
        mh = self.multihost
        if mh is not None and getattr(mh, "is_coordinator", False):
            ch = getattr(mh, "channel", None)
            if ch is not None:
                try:
                    # one-way frame (workers' serve loop drops unknown
                    # ops): the schedule lands on every host's control
                    # log even if the statement later dies
                    ch.send({"op": "spill_schedule", **entry})
                except Exception:
                    pass   # observability must never fail the statement

    def collect(self) -> list:
        steps = getattr(self._tls, "steps", None)
        self._tls.steps = None
        return steps or []


def partial_state_cols(partial: Aggregate) -> list:
    """ColInfos for a partial Aggregate's actual output: group keys plus
    the @c/@s/@m state columns the final phase merges (the compiler's
    partial-phase naming contract, exec/compile.py _c_aggregate)."""
    # keys re-exposed with name == id: the host-input staging maps columns
    # by storage NAME, and the ephemeral table's storage names are the ids
    out = [ColInfo(ci.id, ci.type, ci.id, ci.dict_ref)
           for ci, _ in partial.group_keys]
    for ci, a in partial.aggs:
        if a.func in ("count", "count_star"):
            out.append(ColInfo(ci.id + "@c", T.INT64, ci.id + "@c"))
        elif a.func == "sum":
            out.append(ColInfo(ci.id + "@s", a.type, ci.id + "@s"))
        elif a.func == "avg":
            stype = E.agg_result_type("sum", a.arg.type)
            out.append(ColInfo(ci.id + "@s", stype, ci.id + "@s"))
            out.append(ColInfo(ci.id + "@c", T.INT64, ci.id + "@c"))
        elif a.func in ("min", "max"):
            out.append(ColInfo(ci.id + "@m", a.arg.type, ci.id + "@m",
                               dict_ref=getattr(a.arg, "_dict_ref", None)))
    return out

_WRAPPERS = (Sort, Limit, Project, Filter)


def find_spill_split(plan: Motion):
    """-> (capture_agg, replace_target) of the DEEPEST reduction point on
    the plan's spine, or None.

    A reduction point is an Aggregate whose output rows merge exactly
    across disjoint input partitions:
      - a partial aggregate (states are sums/counts/min/max: additive) —
        capture its STATE columns, swap the partial itself in the merge;
      - a keys-only "dedupe" aggregate (DISTINCT level: dedupe is
        idempotent under union — dedupe(∪ dedupe(chunk)) = dedupe(∪)) —
        capture its key rows, swap the subtree BELOW its redistribute
        Motion so the merge re-hashes the union before re-deduping.
    The walk descends through wrappers, motions, and single-phase
    aggregates so a DISTINCT dedupe buried under the outer aggregate's
    own phases is still found (execHHashagg.c spills the dedupe level the
    same way)."""
    node = plan.child
    best = None
    while True:
        while isinstance(node, _WRAPPERS):
            node = node.child
        if (isinstance(node, Aggregate) and node.phase == "final"
                and isinstance(node.child, Motion)
                and isinstance(node.child.child, Aggregate)
                and node.child.child.phase == "partial"):
            partial = node.child.child
            best = (partial, partial, False)
            node = partial.child
            continue
        if (isinstance(node, Aggregate) and node.phase == "single"
                and not node.aggs and node.group_keys
                # the merge re-runs this dedupe over host-staged rows
                # carrying the OUTPUT ids, so every key must be a plain
                # pass-through column (the binder's id invariant)
                and all(isinstance(e, E.ColRef) and e.name == ci.id
                        for ci, e in node.group_keys)):
            if (isinstance(node.child, Motion)
                    and node.child.kind is MotionKind.REDISTRIBUTE):
                # the existing motion re-hashes the merge's union rows,
                # co-locating cross-pass duplicates before the re-dedupe
                best = (node, node.child.child, False)
                node = node.child.child
            else:
                # colocated dedupe (input already hashed on the keys):
                # duplicates of a key can still span PASSES, and the
                # contiguous host staging scatters them across segments —
                # the merge must insert a redistribute of its own
                best = (node, node.child, True)
                node = node.child
            continue
        if isinstance(node, Aggregate) and node.phase == "single":
            node = node.child
            continue
        if isinstance(node, Motion) and node.kind is MotionKind.REDISTRIBUTE:
            node = node.child
            continue
        break
    return best


def spill_candidate_tables(plan: Plan) -> list[str]:
    """Tables over whose row-partitions the subtree's OUTPUT is a disjoint
    union — partitioning any of them into passes is sound below an
    (order-insensitive) reduction point.

    Probe-side descent is always sound (each probe row lives in exactly
    one chunk). Build-side descent is sound only through INNER (and
    cross) joins: a chunked build partitions each probe row's matches
    across passes, which unions exactly for inner joins but double-counts
    semi joins and null-extends left joins per pass — the grace-join
    batching analog (nodeHashjoin.c) restricted the same way. Aggregates,
    windows, unions, sorts, and limits end soundness (limit/sort are not
    union-distributive; a nested agg is its own reduction point)."""
    out = []

    def walk(node):
        if isinstance(node, Scan):
            out.append(node.table)
            return
        if isinstance(node, Join):
            walk(node.left)
            if node.kind in ("inner", "cross") and not node.null_aware:
                walk(node.right)
            return
        if isinstance(node, (Project, Filter, Motion)):
            walk(node.child)

    walk(plan)
    return out


def count_scans(plan: Plan, table: str) -> int:
    n = 0
    stack = [plan]
    while stack:
        p = stack.pop()
        if isinstance(p, Scan) and p.table == table:
            n += 1
        stack.extend(p.children)
    return n



def _charge_spill(cols: dict, valids: dict, item: str) -> None:
    """Account the host-resident captured rows (the workfile bytes) to
    the statement's 'spill' owner (runtime/memaccount.py)."""
    nb = sum(int(getattr(a, "nbytes", 0)) for a in cols.values())
    nb += sum(int(getattr(a, "nbytes", 0)) for a in valids.values()
              if a is not None)
    memaccount.charge("spill", nb, item=item)


def _collect_passes(cols_spec, results):
    """Merge per-pass Result columns on the host with shared validity
    defaulting: -> (cols, valids) where valids[c] is None when every pass
    reported the column all-valid. The merged buffers are PREALLOCATED
    from the pass row counts and filled in place — the old append-then-
    np.concatenate pair transiently held a second full copy of the
    workfile at the merge peak."""
    per_pass = []                      # (rows, {id: (arr, valids|None)})
    any_invalid = {c.id: False for c in cols_spec}
    total = 0
    for res in results:
        data = {}
        rows = 0
        for c in cols_spec:
            a = np.asarray(res.cols[c.id])
            rows = len(a)
            v = res.valids.get(c.id)
            if v is not None:
                v = np.asarray(v, bool)
                any_invalid[c.id] = True
            data[c.id] = (a, v)
        per_pass.append((rows, data))
        total += rows
    dtypes = {}
    for _rows, data in per_pass:
        for cid, (a, _v) in data.items():
            dtypes[cid] = (a.dtype if cid not in dtypes
                           else np.result_type(dtypes[cid], a.dtype))
    cols = {c.id: np.empty(total, dtype=dtypes.get(c.id, np.int64))
            for c in cols_spec}
    valids = {c.id: (np.ones(total, dtype=bool)
                     if any_invalid[c.id] else None) for c in cols_spec}
    off = 0
    for rows, data in per_pass:
        for c in cols_spec:
            a, v = data[c.id]
            cols[c.id][off:off + rows] = a
            if valids[c.id] is not None and v is not None:
                valids[c.id][off:off + rows] = v
        off += rows
    return cols, valids


def _size_chunk_passes(executor, consts, pass_plan, candidates,
                       limit_bytes):
    """Largest-first chunk-size search shared by the partial-aggregate
    and window spills: pick partition tables and per-pass chunk rows
    that bring the compiled pass program's est_bytes under the limit
    (multiple tables = the grace chunk grid). -> (chosen {table: chunk},
    per_table [(table, chunk, n)], total passes, probe CompileResult);
    raises NotSpillable when no combination fits or the grid explodes."""
    from greengage_tpu.exec.compile import Compiler

    store = executor.store
    settings = executor.settings
    candidates = sorted(
        candidates, key=lambda t: -max(store.segment_rowcounts(t),
                                       default=0))
    floor = 1 << 12
    MAX_PASSES = 256
    chosen: dict[str, int] = {}          # table -> chunk rows
    comp = None
    fits = False
    for cand in candidates:
        max_rows = max(store.segment_rowcounts(cand), default=0)
        if max_rows == 0:
            continue
        chunk = max_rows
        while True:
            chunk = max(chunk // 2, floor)
            over = dict(chosen)
            over[cand] = chunk
            comp = Compiler(executor.catalog, store, executor.mesh,
                            executor.nseg, consts, settings,
                            scan_cap_override=over,
                            no_direct=True).compile(pass_plan)
            if comp.est_bytes <= limit_bytes * 0.7 or chunk == floor:
                break
        chosen[cand] = chunk
        if comp.est_bytes <= limit_bytes:
            fits = True
            break
    if not fits:
        raise NotSpillable("per-pass working set still exceeds the limit "
                           "for every partitionable table combination")
    per_table = []                        # (table, chunk, npasses)
    npasses = 1
    for t, chunk in chosen.items():
        max_rows = max(store.segment_rowcounts(t), default=0)
        n = -(-max_rows // chunk)
        per_table.append((t, chunk, n))
        npasses *= n
    if npasses > MAX_PASSES:
        raise NotSpillable(
            f"spill would need {npasses} passes (> {MAX_PASSES})")
    return chosen, per_table, npasses, comp


def spill_run(executor, plan: Motion, consts, out_cols, raw: bool,
              instrument: bool = False):
    """Execute ``plan`` in partitioned passes. Raises ValueError when the
    plan shape is not spillable (caller surfaces the vmem rejection).
    ``instrument`` (EXPLAIN ANALYZE) collects per-node row counts from
    every pass and the merge program, summed back onto the ORIGINAL plan's
    node identities (the pass subtree shares node objects with the plan;
    the merge path's clones are remapped via _replace_child's node map)."""
    split = find_spill_split(plan)
    if split is None:
        raise NotSpillable("plan shape not spillable")
    capture_agg, replace_target, add_motion = split
    subtree = (capture_agg.child if capture_agg is replace_target
               else replace_target)
    candidates = [t for t in spill_candidate_tables(subtree)
                  if not t.startswith("@") and count_scans(plan, t) == 1]
    if not candidates:
        raise NotSpillable("no partitionable table below the reduction point")
    store = executor.store

    from greengage_tpu.exec.executor import effective_limit_bytes

    settings = executor.settings
    limit_bytes = effective_limit_bytes(settings)

    # pass program: gather the reduction point's output rows (partial
    # STATE columns / dedupe keys; raw storage representation — finalize
    # must not decode)
    state_cols = partial_state_cols(capture_agg)
    capture = PartialState(capture_agg, state_cols)
    capture.locus = capture_agg.locus
    capture.est_rows = capture_agg.est_rows
    pass_plan = Motion(MotionKind.GATHER, capture)
    pass_plan.locus = Locus.entry()

    # choose the partition tables (largest first — probe side AND/OR
    # inner-join build sides, the grace-join regime: when both sides of a
    # join exceed HBM, BOTH are range-partitioned and the passes walk the
    # cartesian chunk grid, exactly nodeHashjoin.c's batch x batch
    # schedule but with whole execution passes) and the chunk sizes that
    # bring the pass program under the limit
    chosen, per_table, npasses, comp = _size_chunk_passes(
        executor, consts, pass_plan, candidates, limit_bytes)
    # lockstep parity: the pass schedule every gang member must agree on
    executor.spill_schedule.note(
        "agg", passes=npasses, chunks=[[t, c, n] for t, c, n in per_table])

    # run the passes, landing partial rows in the tiered workfile (host
    # RAM, overflowing to compressed disk segments — exec/workfile.py).
    # While pass k's jitted program runs, a background thread warms pass
    # k+1's cold block reads into the block cache (exec/staging.py; all
    # passes share the same committed files, so after the budget-resident
    # first pass this is a cheap cache probe)

    from greengage_tpu.exec import staging as _staging
    from greengage_tpu.exec import workfile as _workfile

    grids = [[(t, (i * c, (i + 1) * c)) for i in range(n)]
             for t, c, n in per_table]
    caps = {t: c for t, c, _ in per_table}
    partial_cols = state_cols
    combos = list(itertools.product(*grids))
    prefetcher = _staging.PassPrefetcher(
        executor.stager, comp.input_spec, store.manifest.snapshot())
    wf = _workfile.SpillWorkfile(executor, partial_cols, "partials")
    try:
        try:
            for i, combo in enumerate(combos):
                # spill pass boundary = CHECK_FOR_INTERRUPTS (the
                # cleaner's documented cancellation point; user cancels
                # land here too)
                interrupt.check_interrupts()
                if i + 1 < len(combos):
                    prefetcher.kick()
                with _trace.span("spill-pass", cat="spill", index=i,
                                 total=len(combos)):
                    wf.add(executor.run_single(
                        pass_plan, consts, partial_cols, raw=True,
                        scan_cap_override=caps,
                        row_ranges=dict(combo), instrument=instrument))
        finally:
            prefetcher.close()
        aux_cols, aux_valids = wf.assemble()

        # merge program: the original plan with the replace target
        # swapped for a host input of the merged captured rows. Partial
        # case: the partial itself is replaced (its states redistribute +
        # final-merge above). Dedupe case: the subtree BELOW the dedupe's
        # redistribute is replaced, so the union re-hashes (co-locating
        # cross-pass duplicates) and the dedupe re-runs on device.
        aux_name = "@spill:partials"
        host_scan = Scan(aux_name, list(partial_cols))
        host_scan.locus = (capture_agg.locus
                           if capture_agg is replace_target
                           else Locus.strewn(executor.nseg))
        host_scan.est_rows = float(len(next(iter(aux_cols.values()), [])))
        repl: Plan = host_scan
        if add_motion:
            key_cols = [ci for ci, _ in capture_agg.group_keys]
            m = Motion(MotionKind.REDISTRIBUTE, host_scan,
                       hash_exprs=[E.ColRef(ci.id, ci.type)
                                   for ci in key_cols])
            m.locus = Locus.hashed(tuple(ci.id for ci in key_cols),
                                   executor.nseg)
            m.est_rows = host_scan.est_rows
            repl = m
        node_map: dict = {}
        merged = _replace_child(plan, replace_target, repl, node_map)
        from greengage_tpu.exec.executor import AdmissionError

        try:
            with _trace.span("spill-merge", cat="spill", passes=npasses):
                res = executor.run_single(
                    merged, consts, out_cols, raw=raw,
                    aux_tables={aux_name: (aux_cols, aux_valids)},
                    instrument=instrument)
        except AdmissionError:
            if capture_agg.aggs:      # partial-state merges never regress
                raise
            # recursive-merge level (execHHashagg.c batch recursion): the
            # dedupe working set (~the full key domain for near-unique
            # keys) exceeds HBM even after pass capture. Partition the
            # captured keys BY KEY HASH into disjoint buckets — dedupe is
            # exact per bucket, and the additive partial states above the
            # dedupe sum exactly across buckets.
            res, extra = _bucketed_dedupe_merge(
                executor, merged, capture_agg, host_scan, aux_name,
                aux_cols, aux_valids, consts, out_cols, raw, limit_bytes)
            if instrument:
                _merge_node_rows(res, wf.stats, node_map)
            return res, npasses + extra
        if instrument:
            _merge_node_rows(res, wf.stats, node_map)
        return res, npasses
    finally:
        wf.close()


def _merge_node_rows(res, pass_stats, node_map) -> None:
    """EXPLAIN ANALYZE accounting across spill passes: per-node row
    counts from the pass programs' stats dicts (their subtree nodes ARE
    the original plan's objects) sum with the merge program's (clone ids
    remapped to their originals), landing in the final Result's stats
    under the ORIGINAL plan-node identities the session's describe()
    walk uses. ``pass_stats`` is a list of per-pass Result.stats dicts
    (the tiered workfile retains stats, not whole Results)."""
    agg: dict = {}
    for st in pass_stats:
        for nid, n in (((st or {}).get("node_rows")) or {}).items():
            agg[nid] = agg.get(nid, 0) + n
    if isinstance(res.stats, dict):
        for nid, n in ((res.stats.get("node_rows")) or {}).items():
            nid = node_map.get(nid, nid)
            agg[nid] = agg.get(nid, 0) + n
    else:
        res.stats = {}
    res.stats["node_rows"] = agg


def _find_partial_above(plan: Plan, target: Plan):
    """DEEPEST final->Motion->partial aggregate pattern whose partial
    subtree contains ``target``."""
    found = None

    def walk(node):
        nonlocal found
        if (isinstance(node, Aggregate) and node.phase == "final"
                and isinstance(node.child, Motion)
                and isinstance(node.child.child, Aggregate)
                and node.child.child.phase == "partial"
                and _contains(node.child.child, target)):
            found = node.child.child
        for c in node.children:
            walk(c)

    walk(plan)
    return found


def _bucket_hash(aux_cols, aux_valids, key_ids) -> np.ndarray:
    from greengage_tpu.storage import native

    n = len(next(iter(aux_cols.values())))
    h = np.full(n, 0x9E3779B9, np.uint32)
    for kid in key_ids:
        a = np.asarray(aux_cols[kid])
        if a.dtype.kind == "f":
            # hashfloat8 parity (ops/hashing._canon_f64): -0.0 -> 0.0 and
            # all NaN payloads -> one pattern, or equal keys split buckets
            a = a.astype(np.float64)
            a = np.where(np.isnan(a), np.float64("nan"), a + 0.0)
            a = a.view(np.int64)
        hk = native.hash_i64(a.astype(np.int64))
        v = aux_valids.get(kid)
        if v is not None:
            hk = np.where(np.asarray(v, bool), hk, np.uint32(0x27D4EB2F))
        h = native.hash_combine(h, hk)
    return h


def _bucketed_dedupe_merge(executor, merged, dedupe, host_scan, aux_name,
                           aux_cols, aux_valids, consts, out_cols, raw,
                           limit_bytes):
    """Run the merge in key-hash buckets, capturing the outer partial
    aggregate's states per bucket; one small final pass merges them."""
    # anchor on the host scan: _replace_child shallow-copied every node on
    # the path, so the dedupe OBJECT from the original tree is not in
    # ``merged`` — but the inserted host scan is (by reference)
    outer_partial = _find_partial_above(merged, host_scan)
    if outer_partial is None:
        raise NotSpillable(
            "dedupe working set exceeds the limit and no additive "
            "aggregate sits above the distinct level to merge buckets")
    key_ids = [ci.id for ci, _ in dedupe.group_keys]
    h = _bucket_hash(aux_cols, aux_valids, key_ids)

    state_cols = partial_state_cols(outer_partial)
    capture = PartialState(outer_partial, state_cols)
    capture.locus = outer_partial.locus
    capture.est_rows = outer_partial.est_rows
    bucket_plan = Motion(MotionKind.GATHER, capture)
    bucket_plan.locus = Locus.entry()

    # size K against the COMPILED per-bucket estimate (bucket 0 as the
    # representative subset; the hash is uniform)
    from greengage_tpu.exec.compile import Compiler

    K = 2
    while True:
        m0 = (h % np.uint32(K)) == 0
        sub = {k: np.asarray(v)[m0] for k, v in aux_cols.items()}
        subv = {k: (np.asarray(v, bool)[m0] if v is not None else None)
                for k, v in aux_valids.items()}
        comp = Compiler(executor.catalog, executor.store, executor.mesh,
                        executor.nseg, consts, executor.settings,
                        aux_tables={aux_name: (sub, subv)},
                        no_direct=True).compile(bucket_plan)
        if comp.est_bytes <= max(limit_bytes, 1) * 0.9 or K >= 64:
            break
        K *= 2
    if comp.est_bytes > limit_bytes:
        raise NotSpillable(
            "per-bucket dedupe working set still exceeds the limit at 64 "
            "merge buckets")
    bucket = h % np.uint32(K)
    executor.spill_schedule.note("dedupe", buckets=K)

    # bucketed merge on the motion pipeline (exec/motionpipe.py): bucket
    # k+1's host subset build overlaps bucket k's device program
    from greengage_tpu.exec import motionpipe as _motionpipe

    run_bkts = [b for b in range(K) if (bucket == b).any()]

    def _bstage(bkt, _i):
        m = bucket == bkt
        sub_cols = {k: np.asarray(v)[m] for k, v in aux_cols.items()}
        sub_valids = {k: (np.asarray(v, bool)[m] if v is not None else None)
                      for k, v in aux_valids.items()}
        return sub_cols, sub_valids

    def _bcompute(staged, _bkt, _i):
        sub_cols, sub_valids = staged
        return executor.run_single(
            bucket_plan, consts, state_cols, raw=True,
            aux_tables={aux_name: (sub_cols, sub_valids)})

    bucket_results = _motionpipe.run_pipeline(
        run_bkts, _bstage, _bcompute, settings=executor.settings,
        label="dedupe")
    s_cols, s_valids = _collect_passes(state_cols, bucket_results)
    _charge_spill(s_cols, s_valids, "merge-buckets")
    aux2 = "@spill:partials2"
    host_scan = Scan(aux2, list(state_cols))
    host_scan.locus = outer_partial.locus
    host_scan.est_rows = float(len(next(iter(s_cols.values()), [])))
    final_plan = _replace_child(merged, outer_partial, host_scan)
    res = executor.run_single(
        final_plan, consts, out_cols, raw=raw,
        aux_tables={aux2: (s_cols, s_valids)})
    res.stats = dict(res.stats or {})
    res.stats["spill_merge_buckets"] = K
    return res, K


def _sortable_host_key(arr: np.ndarray, valid, desc: bool,
                       nulls_first: bool):
    """-> list of numpy arrays (minor->major within this key) whose
    ascending np.lexsort order equals the engine's order for this key.
    None when the host representation does not order (raw surrogates)."""
    a = np.asarray(arr)
    if a.dtype.kind in ("i", "u", "b"):
        enc = a.astype(np.int64)
        enc = (enc ^ np.int64(-0x8000000000000000)).astype(np.uint64)
        if desc:
            enc = ~enc
    elif a.dtype.kind == "f":
        bits = a.astype(np.float64).view(np.uint64)
        enc = np.where(bits >> np.uint64(63),
                       ~bits, bits | np.uint64(1 << 63))
        if desc:
            enc = ~enc
    elif a.dtype.kind in ("U", "S"):
        # C-locale string order == the dictionary rank order the device
        # sorts by; numpy cannot complement strings, so DESC strings use
        # a negated RANK over the merged domain instead
        uniq, inv = np.unique(a, return_inverse=True)
        enc = inv.astype(np.int64)
        if desc:
            enc = -enc
        enc = (enc ^ np.int64(-0x8000000000000000)).astype(np.uint64)
    else:
        return None
    nul = (np.zeros(len(a), np.uint8) if valid is None
           else (~np.asarray(valid, bool)).astype(np.uint8))
    if nulls_first:
        nul = 1 - nul
    # major key: null class; minor: encoded value (lexsort order)
    return [enc, nul]


def _host_sort_spec(sort: Sort, out_cols) -> list[tuple]:
    """Validate a Sort's keys as host-mergeable gathered output columns
    -> [(col id, desc, nulls_first)]; raises NotSpillable otherwise.
    Shared by the external-merge sort spill and the window spill's final
    host ordering — any key type must be known host-orderable BEFORE
    paying the pass loop."""
    by_id = {c.id: c for c in out_cols}
    keyspec = []
    for e, desc, nf in sort.keys:
        if not isinstance(e, E.ColRef) or e.name not in by_id:
            raise NotSpillable("sort key is not a gathered output column")
        kc = by_id[e.name]
        # raw TEXT arrives as int64 row surrogates whose numeric order is
        # row id, not string order
        if getattr(kc, "raw_ref", None) is not None \
                or getattr(kc, "raw_chain", None) is not None:
            raise NotSpillable("sort key is raw-encoded text")
        keyspec.append((e.name, bool(desc),
                        bool(desc) if nf is None else bool(nf)))
    return keyspec


def _host_lexsort(cols: dict, valids: dict, keyspec: list[tuple]):
    """One stable ascending lexsort over order-preserving key encodings
    (the k-way merge step); keys minor->major, so reverse the SQL key
    order and emit each key's (enc, null-class) pair in that order."""
    lex: list[np.ndarray] = []
    for name, desc, nf in reversed(keyspec):
        enc = _sortable_host_key(cols[name], valids[name], desc, nf)
        if enc is None:
            raise NotSpillable("sort key host representation does not order")
        lex.extend(enc)
    perm = np.lexsort(lex)
    cols = {k: v[perm] for k, v in cols.items()}
    valids = {k: (v[perm] if v is not None else None)
              for k, v in valids.items()}
    return cols, valids


def spill_sort_run(executor, plan: Motion, consts, out_cols, raw: bool,
                   instrument: bool = False):
    """External-merge sort spill (tuplesort.c role,
    /root/reference/src/backend/utils/sort/tuplesort.c:1): an ORDER BY
    whose input exceeds HBM runs as partitioned passes of the ORIGINAL
    plan — each pass sorts its chunk on device and arrives on the host
    already globally ordered (merge-sorted gather) — then the host merges
    the sorted runs with one stable lexsort over order-preserving key
    encodings (the k-way merge step, with host RAM as the workfile)."""
    if not isinstance(plan, Motion) or plan.kind is not MotionKind.GATHER:
        raise NotSpillable("sort spill needs a gathered result")
    node = plan.child
    limit_node = None
    if isinstance(node, Limit):
        limit_node = node
        node = node.child
    if not isinstance(node, Sort):
        raise NotSpillable("no sort at the gather point")
    sort = node
    keyspec = _host_sort_spec(sort, out_cols)
    candidates = [t for t in spill_candidate_tables(sort.child)
                  if not t.startswith("@") and count_scans(plan, t) == 1]
    if not candidates:
        raise NotSpillable("no partitionable table below the sort")
    # passes must NOT carry the Limit: its host re-limit would drop each
    # CHUNK's first `offset` rows; offset/limit apply once after the merge
    if limit_node is not None:
        pass_plan = copy.copy(plan)
        pass_plan.child = sort
    else:
        pass_plan = plan
    store = executor.store

    from greengage_tpu.exec.compile import Compiler
    from greengage_tpu.exec.executor import effective_limit_bytes

    settings = executor.settings
    limit_bytes = effective_limit_bytes(settings)
    candidates.sort(key=lambda t: -max(store.segment_rowcounts(t), default=0))
    cand = candidates[0]
    max_rows = max(store.segment_rowcounts(cand), default=0)
    if max_rows == 0:
        raise NotSpillable("empty partition candidate")
    floor = 1 << 12
    chunk = max_rows
    comp = None
    while True:
        chunk = max(chunk // 2, floor)
        comp = Compiler(executor.catalog, store, executor.mesh,
                        executor.nseg, consts, settings,
                        scan_cap_override={cand: chunk},
                        no_direct=True).compile(pass_plan)
        if comp.est_bytes <= limit_bytes * 0.7 or chunk == floor:
            break
    if comp.est_bytes > limit_bytes:
        raise NotSpillable("per-pass working set still exceeds the limit")
    npasses = -(-max_rows // chunk)
    if npasses > 256:
        raise NotSpillable(f"sort spill would need {npasses} passes (> 256)")
    executor.spill_schedule.note("sort", passes=npasses,
                                 chunks=[[cand, chunk, npasses]])

    from greengage_tpu.exec import staging as _staging
    from greengage_tpu.exec import workfile as _workfile

    prefetcher = _staging.PassPrefetcher(
        executor.stager, comp.input_spec, store.manifest.snapshot())
    wf = _workfile.SpillWorkfile(executor, out_cols, "sorted-runs")
    try:
        try:
            for p in range(npasses):
                interrupt.check_interrupts()   # sorted-run pass boundary
                if p + 1 < npasses:
                    # warm the next sorted run's cold reads while this
                    # pass's device sort executes (same files, later row
                    # range)
                    prefetcher.kick()
                with _trace.span("spill-pass", cat="spill", index=p,
                                 total=npasses):
                    wf.add(executor.run_single(
                        pass_plan, consts, out_cols, raw=raw,
                        scan_cap_override={cand: chunk},
                        row_ranges={cand: (p * chunk, (p + 1) * chunk)},
                        instrument=instrument))
        finally:
            prefetcher.close()

        cols, valids = wf.assemble()

        cols, valids = _host_lexsort(cols, valids, keyspec)
        if limit_node is not None:
            lo = limit_node.offset
            hi = None if limit_node.limit is None else lo + limit_node.limit
            cols = {k: v[lo:hi] for k, v in cols.items()}
            valids = {k: (v[lo:hi] if v is not None else None)
                      for k, v in valids.items()}

        from greengage_tpu.exec.executor import Result

        res = Result(columns=wf.columns, cols=cols, valids=valids,
                     _order=list(wf.order),
                     stats=dict(wf.base_stats or {}))
        res.stats["spill_kind"] = "sort"
        if instrument:
            # per-node rows sum across the sorted-run passes; the pass
            # plan's instrumented subtree IS the original plan's node
            # objects (the Limit, dropped from passes, stays
            # unannotated). Drop pass 0's counts inherited via base_stats
            # first — _merge_node_rows would otherwise double-count that
            # pass.
            res.stats.pop("node_rows", None)
            _merge_node_rows(res, wf.stats, {})
        return res, npasses
    finally:
        wf.close()


def _window_spill_point(plan: Motion):
    """-> (window, sort_node, limit_node) when the below-gather spine is
    [Limit?] [Sort?] [Project|Filter]* Window(partitioned) — the
    window-spill shape. None otherwise. Sort/Limit lift to the host
    merge (row order is the only thing they change); Project/Filter are
    row-wise and union-distributive, so they run inside every bucket."""
    node = plan.child
    sort_node = limit_node = None
    while isinstance(node, _WRAPPERS):
        if isinstance(node, Limit):
            if limit_node is not None or sort_node is not None:
                return None    # a Limit BELOW a Sort truncates pre-order
            limit_node = node
        elif isinstance(node, Sort):
            if sort_node is not None:
                return None
            sort_node = node
        node = node.child
    if not isinstance(node, Window) or getattr(node, "global_mode", False) \
            or not node.partition_keys:
        return None
    return node, sort_node, limit_node


def spill_window_run(executor, plan: Motion, consts, out_cols, raw: bool,
                     instrument: bool = False):
    """Window-partition spill: a window whose working set exceeds the
    admission limit completes by partitioning the PARTITION BY hash
    space into passes — exactly the DISTINCT spill's recursive-merge
    regime, but the bucketed unit is a whole window computation.

    Soundness: window functions depend ONLY on rows of their own
    partition, and a hash of the PARTITION BY keys puts every row of a
    partition in the same bucket — so running the window per disjoint
    bucket and unioning the outputs is exact (execHHashagg.c's batch
    partitioning, applied to nodeWindowAgg.c's input).

    Three phases:
      1. capture — chunked passes over the biggest base table(s) gather
         the window's INPUT rows (the subtree below its Redistribute) to
         the host: per-pass working set is chunk-sized (host RAM is the
         workfile);
      2. window passes — captured rows bucket by hash(PARTITION BY) % K;
         each bucket restages as an ephemeral host table, redistributes
         by the partition keys, and runs the window + its row-wise
         wrappers on device;
      3. finalize — any Sort above the window merges on the host over
         the unioned bucket outputs (the spill_sort_run lexsort), then
         LIMIT/OFFSET trims once."""
    settings = executor.settings
    if not bool(getattr(settings, "window_spill_enabled", True)):
        raise NotSpillable("window spill disabled (window_spill_enabled)")
    if not isinstance(plan, Motion) or plan.kind is not MotionKind.GATHER:
        raise NotSpillable("window spill needs a gathered result")
    point = _window_spill_point(plan)
    if point is None:
        raise NotSpillable("no partitioned window at the spill point")
    window, sort_node, limit_node = point
    if not all(isinstance(e, E.ColRef) for e in window.partition_keys):
        raise NotSpillable("window partition keys are not plain columns")
    keyspec = (_host_sort_spec(sort_node, out_cols)
               if sort_node is not None else None)
    child = window.child
    subtree = (child.child if isinstance(child, Motion)
               and child.kind is MotionKind.REDISTRIBUTE else child)
    sub_cols = []
    for c in subtree.out_cols():
        if getattr(c, "raw_ref", None) is not None \
                or getattr(c, "raw_chain", None) is not None:
            raise NotSpillable("window input carries raw-encoded text")
        # name == id: host staging maps aux columns by storage NAME
        sub_cols.append(ColInfo(c.id, c.type, c.id, c.dict_ref))
    sub_ids = {c.id for c in sub_cols}
    key_ids = [e.name for e in window.partition_keys]
    if not set(key_ids) <= sub_ids:
        raise NotSpillable("window partition keys are not captured "
                           "input columns")

    from greengage_tpu.exec import staging as _staging
    from greengage_tpu.exec import workfile as _workfile
    from greengage_tpu.exec.compile import Compiler
    from greengage_tpu.exec.executor import effective_limit_bytes

    limit_bytes = effective_limit_bytes(settings)
    store = executor.store

    # ---- phase 1: chunked capture of the window's input rows ---------
    capture = PartialState(subtree, sub_cols)
    capture.locus = subtree.locus
    capture.est_rows = subtree.est_rows
    pass_plan = Motion(MotionKind.GATHER, capture)
    pass_plan.locus = Locus.entry()
    candidates = [t for t in spill_candidate_tables(subtree)
                  if not t.startswith("@") and count_scans(plan, t) == 1]
    if not candidates:
        raise NotSpillable("no partitionable table below the window")
    chosen, per_table, nchunks, comp = _size_chunk_passes(
        executor, consts, pass_plan, candidates, limit_bytes)
    executor.spill_schedule.note(
        "window-capture", passes=nchunks,
        chunks=[[t, c, n] for t, c, n in per_table])
    grids = [[(t, (i * c, (i + 1) * c)) for i in range(n)]
             for t, c, n in per_table]
    caps = {t: c for t, c, _ in per_table}
    combos = list(itertools.product(*grids))
    prefetcher = _staging.PassPrefetcher(
        executor.stager, comp.input_spec, store.manifest.snapshot())
    wf = _workfile.SpillWorkfile(executor, sub_cols, "window-input")
    try:
        try:
            for i, combo in enumerate(combos):
                interrupt.check_interrupts()   # spill pass boundary
                if i + 1 < len(combos):
                    prefetcher.kick()
                with _trace.span("spill-pass", cat="spill", index=i,
                                 total=len(combos), phase="capture"):
                    wf.add(executor.run_single(
                        pass_plan, consts, sub_cols, raw=True,
                        scan_cap_override=caps,
                        row_ranges=dict(combo), instrument=instrument))
        finally:
            prefetcher.close()
        aux_cols, aux_valids = wf.assemble()
    finally:
        wf.close()

    # ---- phase 2: window over PARTITION BY hash buckets --------------
    aux_name = "@spill:window"
    host_scan = Scan(aux_name, list(sub_cols))
    host_scan.locus = Locus.strewn(executor.nseg)
    host_scan.est_rows = float(len(next(iter(aux_cols.values()), [])))
    key_cols = {c.id: c for c in sub_cols}
    m = Motion(MotionKind.REDISTRIBUTE, host_scan,
               hash_exprs=[E.ColRef(k, key_cols[k].type) for k in key_ids])
    m.locus = Locus.hashed(tuple(key_ids), executor.nseg)
    m.est_rows = host_scan.est_rows
    node_map: dict = {}

    def rebuild(nd):
        if nd is window:
            w = copy.copy(window)
            node_map[id(w)] = id(window)
            w.child = m
            w.locus = m.locus
            return w
        if nd is sort_node or nd is limit_node:
            return rebuild(nd.child)
        clone = copy.copy(nd)
        node_map[id(clone)] = id(nd)
        clone.child = rebuild(nd.child)
        return clone

    bucket_plan = Motion(MotionKind.GATHER, rebuild(plan.child))
    bucket_plan.locus = Locus.entry()
    if bool(getattr(settings, "plan_validate", True)):
        # the bucket plan is a real plan: machine-check the spill shape
        # (hashed-on-partition-keys window, motion boundary) like any
        # other statement before paying K dispatches
        from greengage_tpu.analysis.plancheck import validate_plan

        validate_plan(bucket_plan, executor.catalog)

    h = _bucket_hash(aux_cols, aux_valids, key_ids)
    K = 1
    while True:
        mk = (h % np.uint32(max(K, 1))) == 0
        sub = {k: np.asarray(v)[mk] for k, v in aux_cols.items()}
        subv = {k: (np.asarray(v, bool)[mk] if v is not None else None)
                for k, v in aux_valids.items()}
        bcomp = Compiler(executor.catalog, store, executor.mesh,
                         executor.nseg, consts, settings,
                         aux_tables={aux_name: (sub, subv)},
                         no_direct=True).compile(bucket_plan)
        if bcomp.est_bytes <= max(limit_bytes, 1) * 0.9 or K >= 64:
            break
        K *= 2
    if bcomp.est_bytes > limit_bytes:
        raise NotSpillable(
            "per-bucket window working set still exceeds the limit at 64 "
            "partition buckets")
    bucket = h % np.uint32(K)
    executor.spill_schedule.note("window", buckets=K)

    # bucketed window passes on the motion pipeline (exec/motionpipe.py):
    # bucket k+1's host subset build + restage overlaps bucket k's device
    # program. Bucket 0 always runs (result schema base).
    from greengage_tpu.exec import motionpipe as _motionpipe

    run_bkts = [b for b in range(K) if b == 0 or (bucket == b).any()]

    def _bstage(bkt, _i):
        mk = bucket == bkt
        sub = {k: np.asarray(v)[mk] for k, v in aux_cols.items()}
        subv = {k: (np.asarray(v, bool)[mk] if v is not None else None)
                for k, v in aux_valids.items()}
        return sub, subv

    def _bcompute(staged, bkt, _i):
        sub, subv = staged
        with _trace.span("spill-pass", cat="spill", index=bkt, total=K,
                         phase="window"):
            return executor.run_single(
                bucket_plan, consts, out_cols, raw=raw,
                aux_tables={aux_name: (sub, subv)}, instrument=instrument)

    bucket_results = _motionpipe.run_pipeline(
        run_bkts, _bstage, _bcompute, settings=settings, label="window")
    cols, valids = _collect_passes(out_cols, bucket_results)
    _charge_spill(cols, valids, "window-output")

    # ---- phase 3: host ordering + limit ------------------------------
    if keyspec is not None:
        cols, valids = _host_lexsort(cols, valids, keyspec)
    if limit_node is not None:
        lo = limit_node.offset
        hi = None if limit_node.limit is None else lo + limit_node.limit
        cols = {k: v[lo:hi] for k, v in cols.items()}
        valids = {k: (v[lo:hi] if v is not None else None)
                  for k, v in valids.items()}

    from greengage_tpu.exec.executor import Result

    base = bucket_results[0]
    res = Result(columns=base.columns, cols=cols, valids=valids,
                 _order=list(base._order), stats=dict(base.stats or {}))
    res.stats["spill_kind"] = "window"
    res.stats["spill_window_buckets"] = K
    if instrument:
        # per-node rows: capture passes share the ORIGINAL subtree's node
        # objects; bucket programs run clones remapped via node_map. Drop
        # bucket 0's counts inherited through base.stats first.
        res.stats.pop("node_rows", None)
        agg: dict = {}
        for st in wf.stats:
            for nid, nr in (((st or {}).get("node_rows")) or {}).items():
                agg[nid] = agg.get(nid, 0) + nr
        for r in bucket_results:
            for nid, nr in (((r.stats or {}).get("node_rows")) or {}).items():
                nid = node_map.get(nid, nid)
                agg[nid] = agg.get(nid, 0) + nr
        res.stats["node_rows"] = agg
    counters.inc("window_spill_runs")
    counters.inc("window_spill_passes", nchunks + K)
    return res, nchunks + K


def _replace_child(plan: Plan, target: Plan, repl: Plan,
                   node_map: dict | None = None) -> Plan:
    """Shallow-rebuild the path from ``plan`` to ``target`` with the target
    swapped (the original tree stays untouched for re-raising).
    ``node_map`` (optional) collects id(clone) -> id(original) for the
    cloned path nodes so instrumented row counts from the merged plan can
    be attributed back to the original tree's nodes."""

    if plan is target:
        return repl
    clone = copy.copy(plan)
    if node_map is not None:
        node_map[id(clone)] = id(plan)
    for attr in ("child", "left", "right"):
        c = getattr(plan, attr, None)
        if c is None:
            continue
        if c is target or _contains(c, target):
            setattr(clone, attr, _replace_child(c, target, repl, node_map))
    return clone


def _contains(plan: Plan, target: Plan) -> bool:
    if plan is target:
        return True
    return any(_contains(c, target) for c in plan.children)
