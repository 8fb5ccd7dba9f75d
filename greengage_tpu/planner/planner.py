"""The parallelizer: locus propagation + Motion insertion.

Reference parity: cdbparallelize/apply_motion walking the plan and cutting
it at Motions (src/backend/cdb/cdbllize.c:132, cdbmutate.c:396), with the
join motion decision following cdbpath_motion_for_join
(src/backend/cdb/cdbpath.c:922): colocated -> no motion; one side already
hashed on its join keys -> redistribute the other; replicated side -> no
motion; otherwise min-cost of (redistribute both, broadcast one).

Aggregates follow the two/three-stage logic of cdbgroup.c:678: grouped by
the distribution key -> one phase; otherwise partial agg -> Redistribute by
group keys -> final merge; no group keys -> partial -> Gather -> final on
the coordinator (Entry locus).
"""

from __future__ import annotations

import numpy as np

from greengage_tpu import expr as E
from greengage_tpu import types as T
from greengage_tpu.catalog import PolicyKind
from greengage_tpu.planner import cost as C
from greengage_tpu.planner import stats as S
from greengage_tpu.planner.locus import Locus, LocusKind
from greengage_tpu.planner.logical import (
    Aggregate, ColInfo, Filter, Join, Limit, Motion, MotionKind, Plan, Project,
    Scan, Sort, Union, Window,
)
from greengage_tpu.runtime.logger import counters


def _param_value(e) -> E.Expr | None:
    """A comparison operand whose VALUE is a hoisted parameter — a bare
    Param or the binder's numeric coercion Cast around one. The returned
    expression is stored in the pushed prune predicate and resolved to a
    concrete storage value at staging time (exec/staging.resolve_prune)."""
    if isinstance(e, E.Param):
        return e
    if isinstance(e, E.Cast) and isinstance(e.arg, E.Param):
        return e
    return None


def _year_days(y: int) -> int:
    """days-since-epoch of Jan 1 of ``y`` (host calendar math)."""
    return int((np.datetime64(f"{y:04d}-01-01") - np.datetime64("1970-01-01"))
               .astype(int))


def _year_prune(lhs, rhs, op, by_id) -> list[tuple] | None:
    """``extract_year(date_col) <op> int literal`` -> equivalent day-range
    prune predicates on the base column, or None. Exact because year is
    monotone non-decreasing in days-since-epoch."""
    if not (isinstance(lhs, E.Func) and lhs.name == "extract_year"
            and len(lhs.args) == 1 and isinstance(lhs.args[0], E.ColRef)
            and lhs.args[0].name in by_id
            and lhs.args[0].type.kind is T.Kind.DATE
            and isinstance(rhs, E.Literal) and rhs.value is not None
            and isinstance(rhs.value, (int, np.integer))
            and op in ("=", "<", "<=", ">", ">=")):
        return None
    y = int(rhs.value)
    if not 1 <= y < 9999:
        return None
    col = by_id[lhs.args[0].name]
    if op == "=":
        return [(col, ">=", _year_days(y)), (col, "<=", _year_days(y + 1) - 1)]
    if op == "<=":
        return [(col, "<=", _year_days(y + 1) - 1)]
    if op == "<":
        return [(col, "<=", _year_days(y) - 1)]
    if op == ">=":
        return [(col, ">=", _year_days(y))]
    return [(col, ">=", _year_days(y + 1))]      # op == ">"


class Planner:
    def __init__(self, catalog, store, numsegments: int,
                 force_multi_join: bool = False, feedback=None):
        self.catalog = catalog
        self.store = store
        self.nseg = numsegments
        self.force_multi_join = force_multi_join
        # feedback-driven row-scale corrections (planner/feedback.py):
        # None when cost_feedback is off or no store is wired in — the
        # session passes its FeedbackStore so observed actuals correct
        # est_rows per structural node digest
        self.feedback = feedback

    # ------------------------------------------------------------------
    def plan(self, node: Plan) -> Plan:
        # LIMIT directly under the top Gather is handled per-segment + host
        # re-limit; any deeper LIMIT needs single-segment execution (marked
        # here, enforced in _plan_limit)
        self._root_limits = set()
        top = node
        while isinstance(top, Limit):
            self._root_limits.add(id(top))
            top = top.child
        node = self._rec(node)
        # top: deliver to the coordinator
        if node.locus.kind is not LocusKind.ENTRY:
            node = self._gather(node)
        return node

    def _rec(self, node: Plan) -> Plan:
        m = getattr(self, "_plan_" + type(node).__name__.lower())
        out = m(node)
        if self.feedback is not None and isinstance(
                out, (Filter, Join, Aggregate)):
            # measured-traffic correction: scale the freshly computed
            # estimate by the digest's applied feedback scale BEFORE the
            # parent reads it, so motion choice, capacity sizing, and
            # admission all see corrected cardinalities. This is also
            # what supersedes a ParamRef.est_value seed: the populating
            # statement's literals seed the selectivity once, observed
            # actuals correct it forever after.
            out.est_rows = self.feedback.corrected_rows(out)
        return out

    # ------------------------------------------------------------------
    def _plan_scan(self, node: Scan) -> Plan:
        schema = self.catalog.get(node.table)
        pol = schema.policy
        nseg = pol.numsegments
        rows = sum(self.store.segment_rowcounts(node.table))
        node.est_rows = float(rows)
        if pol.kind is PolicyKind.HASH:
            by_name = {c.name: c.id for c in node.cols}
            try:
                ids = tuple(by_name[k] for k in pol.keys)
                node.locus = Locus.hashed(ids, nseg)
            except KeyError:
                # distribution key not scanned: still partitioned, key unknown
                node.locus = Locus.strewn(nseg)
        elif pol.kind is PolicyKind.REPLICATED:
            node.locus = Locus.segment_general(nseg)
        else:
            node.locus = Locus.strewn(nseg)
        return node

    def _plan_constrel(self, node) -> Plan:
        node.locus = Locus.strewn(self.nseg)
        node.est_rows = 1.0
        return node

    def _plan_filter(self, node: Filter) -> Plan:
        node.child = self._rec(node.child)
        node.locus = node.child.locus
        node.est_rows = node.child.est_rows * C.filter_selectivity(
            node.predicate, self._stats_lookup(node.child))
        self._maybe_direct_dispatch(node)
        return node

    def _maybe_direct_dispatch(self, node: Filter) -> None:
        """Scan-level predicate pushdown: (a) direct dispatch
        (cdbtargeteddispatch.c) when equality literals cover the full
        hash-distribution key; (b) zone-map prune predicates
        (PartitionSelector analog) for range/equality conjuncts over
        numeric/date columns — staging skips blocks they rule out."""
        child = node.child
        if not isinstance(child, Scan):
            return
        schema = self.catalog.get(child.table)
        by_id = {c.id: c.name for c in child.cols}
        found: dict[str, object] = {}
        prune: list[tuple] = []
        conjuncts = (list(node.predicate.args)
                     if isinstance(node.predicate, E.BoolOp)
                     and node.predicate.op == "and" else [node.predicate])
        flip = {"<": ">", ">": "<", "<=": ">=", ">=": "<="}
        for c in conjuncts:
            if not isinstance(c, E.Cmp):
                continue
            lhs, rhs, op = c.left, c.right, c.op
            if isinstance(rhs, (E.ColRef, E.Func)) \
                    and (isinstance(lhs, E.Literal) or _param_value(lhs)):
                lhs, rhs, op = rhs, lhs, flip.get(op, op)
            # extract_year(d) <op> literal (the TPC-DS date-filter shape):
            # year is monotone in days-since-epoch, so the conjunct
            # implies exact day bounds on the BASE date column — zone
            # maps / block indexes prune on those while the Func itself
            # stays fused in the device filter (ops/scalar.py)
            yp = _year_prune(lhs, rhs, op, by_id)
            if yp:
                prune.extend(yp)
                continue
            # hoisted literal (sql/paramize.py): the pushed predicate
            # carries the Param expression; the executor substitutes the
            # statement's current value at STAGING time, so zone-map /
            # block-index pruning stays value-exact while the compiled
            # program stays value-generic
            pp = _param_value(rhs)
            if pp is not None and isinstance(lhs, E.ColRef) \
                    and lhs.name in by_id \
                    and op in ("=", "<", "<=", ">", ">=") \
                    and lhs.type.kind in (T.Kind.INT32, T.Kind.INT64,
                                          T.Kind.DATE, T.Kind.DECIMAL,
                                          T.Kind.FLOAT64):
                prune.append((by_id[lhs.name], op, pp))
                continue
            if not (isinstance(lhs, E.ColRef) and isinstance(rhs, E.Literal)
                    and lhs.name in by_id):
                continue
            if op == "=":
                found[by_id[lhs.name]] = rhs.value
            if op in ("=", "<", "<=", ">", ">=") and rhs.value is not None \
                    and lhs.type.kind in (T.Kind.INT32, T.Kind.INT64,
                                          T.Kind.DATE, T.Kind.DECIMAL,
                                          T.Kind.FLOAT64):
                # keep ints EXACT (python int<->float comparisons are exact,
                # but float() conversion above 2^53 is not)
                v = rhs.value
                if isinstance(v, (bool, np.bool_)):
                    continue
                if isinstance(v, (int, np.integer)):
                    prune.append((by_id[lhs.name], op, int(v)))
                elif isinstance(v, (float, np.floating)):
                    prune.append((by_id[lhs.name], op, float(v)))
            elif op == "=" and lhs.type.kind is T.Kind.TEXT \
                    and rhs.value is not None \
                    and isinstance(rhs.value, (int, np.integer)):
                # dict-TEXT equality: the literal is already a storage
                # code. Codes are unordered, so ONLY equality is sound —
                # and it is, for both zone maps (code outside a block's
                # [min, max] cannot be present) and the block index
                prune.append((by_id[lhs.name], op, int(rhs.value)))
        if prune:
            child.prune_preds = tuple(prune)
            # access-path visibility: the staged read probes these
            # indexes' block sidecars (equality AND range ops)
            pruned_cols = {c for c, _, _ in prune}
            child.index_hits = tuple(sorted(
                name for name, d in getattr(schema, "indexes", {}).items()
                if d.get("column") in pruned_cols))
        if child.parts is not None and schema.is_partitioned:
            # static partition pruning from the same pushed conjuncts
            # (plan-time half of nodePartitionSelector.c); Param-valued
            # predicates have no value yet and cannot prune partitions
            # (paramize pins partition-key literals so this stays rare)
            child.parts_total = len(schema.partitions)
            keep = schema.prune_partitions(
                [(c, op, v) for c, op, v in prune
                 if not isinstance(v, E.Expr)])
            name_keep = {schema.partitions[i].storage_name(child.table)
                         for i in keep}
            child.parts = tuple(p for p in child.parts if p in name_keep)
        if schema.policy.kind is PolicyKind.HASH \
                and all(k in found for k in schema.policy.keys):
            child.direct_seg = self.store.segment_for_values(
                schema, {k: found[k] for k in schema.policy.keys})

    def _build_unique(self, plan: Plan, key_exprs) -> bool:
        """Join-key uniqueness for build-side selection: the structural
        dist-key heuristic, OR statistics — a key column whose NDV ≈ its
        table's row count is a key (covers REPLICATED dimensions like
        nation/region, which have no distribution key and were forced onto
        the duplicate-capable join path, compounding capacity estimates).
        A wrong stats guess is caught by the runtime dup flag and re-planned
        with force_multi_join."""
        if _keys_look_unique(plan, key_exprs):
            return True
        lookup = self._stats_lookup(plan)
        for e in key_exprs:
            if not isinstance(e, E.ColRef):
                continue
            org = _origin(plan, e.name)
            cs = lookup(e.name)
            if org is None or cs is None:
                continue
            try:
                ts = self.catalog.get(org[0]).stats
            except Exception:
                continue
            if ts is not None and ts.rows > 0 and cs.ndv >= 0.97 * ts.rows:
                return True
        return False

    # ---- statistics access (pg_statistic / ORCA stats-calculus analog) --
    def _stats_lookup(self, plan: Plan):
        """-> lookup(col_id) resolving a column through pass-through nodes
        to its base-table ColumnStats (None when unresolvable/unanalyzed)."""
        def lookup(col_id: str):
            org = _origin(plan, col_id)
            if org is None:
                return None
            try:
                schema = self.catalog.get(org[0])
            except Exception:
                return None
            ts = getattr(schema, "stats", None)
            if ts is None:
                return None
            return ts.columns.get(org[1])
        return lookup

    def _plan_project(self, node: Project) -> Plan:
        node.child = self._rec(node.child)
        child_locus = node.child.locus
        node.est_rows = node.child.est_rows
        if child_locus.kind is LocusKind.HASHED:
            # keep Hashed only if every distribution key passes through intact
            passthrough = {
                e.name for _, e in node.exprs if isinstance(e, E.ColRef)
            }
            if set(child_locus.keys) <= passthrough:
                # rename locus keys to projected ids
                rename = {
                    e.name: c.id for c, e in node.exprs if isinstance(e, E.ColRef)
                }
                node.locus = Locus.hashed(
                    tuple(rename[k] for k in child_locus.keys), child_locus.numsegments
                )
            else:
                node.locus = Locus.strewn(child_locus.numsegments)
        else:
            node.locus = child_locus
        return node

    # ------------------------------------------------------------------
    def _plan_join(self, node: Join) -> Plan:
        node.left = self._rec(node.left)
        node.right = self._rec(node.right)
        left, right = node.left, node.right
        nseg = self.nseg

        # Build side choice: the hash-join kernel requires unique build keys
        # (ops/join.py), so prefer the side whose join keys cover its scan's
        # distribution keys (PK-shaped: TPC-H dimension tables are
        # distributed by their primary key); among candidates pick the
        # smaller. Inner joins may swap freely (outputs are selected by id).
        if node.kind == "inner":
            lu = self._build_unique(left, node.left_keys)
            ru = self._build_unique(right, node.right_keys)
            swap = False
            if lu and not ru:
                swap = True
            elif lu == ru and left.est_rows < right.est_rows:
                swap = True
            if swap:
                node.left, node.right = right, left
                node.left_keys, node.right_keys = node.right_keys, node.left_keys
                left, right = node.left, node.right

        pairs = [
            (lk.name if isinstance(lk, E.ColRef) else None,
             rk.name if isinstance(rk, E.ColRef) else None)
            for lk, rk in zip(node.left_keys, node.right_keys)
        ]
        l2r = {l: r for l, r in pairs if l and r}
        r2l = {r: l for l, r in pairs if l and r}

        def colocated() -> bool:
            ll, rl = left.locus, right.locus
            if not (ll.kind is LocusKind.HASHED and rl.kind is LocusKind.HASHED):
                return False
            if ll.numsegments != rl.numsegments or len(ll.keys) != len(rl.keys):
                return False
            return all(l2r.get(a) == b for a, b in zip(ll.keys, rl.keys))

        def hashed_on_join_keys(locus: Locus, side_map: dict) -> bool:
            return (locus.kind is LocusKind.HASHED
                    and all(k in side_map for k in locus.keys))

        if node.kind == "cross":
            # broadcast the SMALLER side (cross-join outputs are selected
            # by id, so the sides may swap freely); without the swap a
            # 1-row constant relation on the left would broadcast the
            # whole table on the right
            if right.locus.is_partitioned and (
                    left.est_rows < right.est_rows
                    or left.locus.kind is LocusKind.SEGMENT_GENERAL):
                # also swap a REPLICATED left: as the build side it needs
                # no motion at all, where keeping it on the left forces a
                # broadcast of the partitioned right
                node.left, node.right = node.right, node.left
                left, right = right, left
            if right.locus.kind is not LocusKind.SEGMENT_GENERAL:
                node.right = self._broadcast(right)
            node.locus = left.locus
        elif right.locus.kind in (LocusKind.SEGMENT_GENERAL, LocusKind.GENERAL):
            node.locus = left.locus
        elif left.locus.kind in (LocusKind.SEGMENT_GENERAL, LocusKind.GENERAL):
            node.locus = right.locus if node.kind == "inner" else left.locus
            if node.kind != "inner":
                # outer/semi probe side replicated: broadcast build instead
                node.right = self._broadcast(right)
                node.locus = left.locus
        elif colocated():
            node.locus = left.locus
        elif hashed_on_join_keys(left.locus, l2r):
            # move build side to match probe's existing distribution
            exprs = [node.right_keys[[l for l, _ in pairs].index(k)]
                     for k in left.locus.keys]
            node.right = self._redistribute(right, exprs,
                                            tuple(l2r[k] for k in left.locus.keys))
            node.locus = left.locus
        elif hashed_on_join_keys(right.locus, r2l) and node.kind == "inner":
            exprs = [node.left_keys[[r for _, r in pairs].index(k)]
                     for k in right.locus.keys]
            node.left = self._redistribute(left, exprs,
                                           tuple(r2l[k] for k in right.locus.keys))
            node.locus = right.locus
        else:
            # neither side usable: redistribute both vs broadcast build side.
            # Calibrated comparison (cost.py): a broadcast build is sorted
            # FULL-SIZE on every chip (~40 ns/row/operand), so the ICI bytes
            # it saves must beat that extra build work — a bytes-only model
            # systematically over-broadcasts mid-size relations.
            lw = C.row_width(left.out_cols())
            rw = C.row_width(right.out_cols())
            nk = max(len(pairs), 1)
            redist = (C.motion_cost("redistribute", left.est_rows, lw, nseg)
                      + C.motion_cost("redistribute", right.est_rows, rw, nseg)
                      + C.join_build_cost(right.est_rows, nk, nseg))
            bcast = (C.motion_cost("broadcast", right.est_rows, rw, nseg)
                     + C.join_build_cost(right.est_rows, nk, nseg,
                                         replicated=True))
            if bcast < redist:
                node.right = self._broadcast(right)
                node.locus = left.locus
            else:
                lids = tuple(l for l, _ in pairs)
                rids = tuple(r for _, r in pairs)
                node.left = self._redistribute(left, list(node.left_keys), lids)
                node.right = self._redistribute(right, list(node.right_keys), rids)
                node.locus = node.left.locus
        # output cardinality: with ANALYZE stats, |L||R|/max(key NDVs);
        # fallback to the round-1 max() guess
        llook = self._stats_lookup(left)
        rlook = self._stats_lookup(right)
        est = None
        sel = 1.0
        for lk, rk in zip(node.left_keys, node.right_keys):
            ls = llook(lk.name) if isinstance(lk, E.ColRef) else None
            rs = rlook(rk.name) if isinstance(rk, E.ColRef) else None
            if ls is None or rs is None or ls.ndv <= 0 or rs.ndv <= 0:
                sel = None
                break
            # histogram join calculus (MCV x MCV + aligned-histogram
            # remainder, stats.join_selectivity); NDV division fallback
            ksel = S.join_selectivity(ls, rs,
                                      (lk.type.kind, rk.type.kind))
            if ksel is None:
                ksel = 1.0 / max(ls.ndv, rs.ndv)
            sel *= ksel * (1.0 - ls.null_frac) * (1.0 - rs.null_frac)
        if sel is not None:
            est = max(left.est_rows * right.est_rows * sel, 1.0)
        node.est_rows = est if est is not None else max(left.est_rows, right.est_rows)
        if node.kind in ("semi", "anti"):
            node.est_rows = left.est_rows * self._semi_fraction(node, llook)
        # build-side duplicate keys force the CSR multi-match kernel for
        # inner/left (semi/anti only need existence, the plain table is
        # fine); the multi kernel handles per-match residual
        # disqualification with one-null-row-per-probe collapse.
        if node.kind in ("inner", "left"):
            if self.force_multi_join or not self._build_unique(
                    node.right, node.right_keys):
                node.multi = True
                if est is None:
                    # no stats: duplicate fanout multiplies output rows;
                    # nudge the max() guess so operators above size their
                    # tables for it. The stats estimate (|L||R| x key
                    # selectivity) already counts duplicate matches, and
                    # doubling it at every join of a chain compounds into
                    # capacities 10^4 x the rows that arrive.
                    node.est_rows = max(node.est_rows, left.est_rows * 2.0)
        elif node.kind in ("semi", "anti") and node.residual is not None:
            # residual EXISTS correlation must test EVERY duplicate build
            # row (any-match): route through the CSR expansion. Stash the
            # PAIR estimate (|L||R|/max key NDV) so the compiler sizes the
            # expansion from stats instead of overflowing the first run
            node.multi = True
            if sel is not None:
                node.expand_est = max(
                    left.est_rows * right.est_rows * sel, 1.0)
        # build-side key bounds for the packed/narrowed hash table
        # (ops/join.py pack_join_keys): probe values outside the build's
        # bounds simply never match, so only the BUILD side's stats matter
        node.key_bounds = self._key_bounds(node.right, node.right_keys)
        self._maybe_direct_join(node)
        self._maybe_dynamic_partition_prune(node)
        return node

    def _semi_fraction(self, node: Join, llook) -> float:
        """Share of the probe rows a semi/anti join keeps. An IN / EXISTS
        semi-join with analyzed probe keys keeps the rows whose key is one
        of the build's: at most build rows / distinct probe keys of them
        (containment, as for an inner join). Anything else — anti joins,
        residual correlations, keys without statistics — keeps the old
        guess of a half."""
        if node.kind != "semi" or node.residual is not None:
            return 0.5
        ndv = 1.0
        for lk in node.left_keys:
            cs = llook(lk.name) if isinstance(lk, E.ColRef) else None
            if cs is None or cs.ndv <= 0:
                return 0.5
            ndv *= cs.ndv
        return min(node.right.est_rows / ndv, 1.0)

    def _maybe_dynamic_partition_prune(self, node: Join) -> None:
        """Join-driven runtime partition elimination (the
        PartitionSelector role, src/backend/executor/
        nodePartitionSelector.c:1): when a partitioned probe joins a
        small build table ON ITS PARTITION KEY, annotate the probe scan
        so STAGING first evaluates the build's (pushable) filter on the
        host, collects the surviving key values, and skips whole child
        partitions no value can land in — partitions the static pruner
        could never eliminate because the selecting predicate lives on
        the other table. Inner/semi only: a left join keeps unmatched
        probe rows, which pruned partitions would drop."""
        if node.kind not in ("inner", "semi") or getattr(node, "null_aware",
                                                         False):
            return
        for lk, rk in zip(node.left_keys, node.right_keys):
            if not (isinstance(lk, E.ColRef) and isinstance(rk, E.ColRef)):
                continue
            lorg = _origin(node.left, lk.name)
            rorg = _origin(node.right, rk.name)
            if lorg is None or rorg is None or lorg[0] == rorg[0]:
                continue
            try:
                schema = self.catalog.get(lorg[0])
            except Exception:
                continue
            if not schema.is_partitioned or schema.partition_by[1] != lorg[1]:
                continue
            scan = _find_single_scan(node.left, lorg[0])
            dim_scan = _find_single_scan(node.right, rorg[0])
            if scan is None or dim_scan is None or scan.parts is None \
                    or dim_scan.parts is not None:
                continue
            if getattr(scan, "dyn_prune", None) is not None:
                continue
            try:
                dim_rows = sum(self.store.segment_rowcounts(rorg[0]))
            except Exception:
                continue
            if dim_rows > 200_000:   # host pre-pass must stay cheap
                continue
            scan.dyn_prune = (rorg[0], tuple(dim_scan.prune_preds or ()),
                              rorg[1])
            return

    def _maybe_direct_join(self, node: Join) -> None:
        """Dense integer build keys (sequence/surrogate PKs): address the
        build table directly by (key - min) — one scatter to build, one
        gather to probe (ops/join.py build_direct). Decided from ANALYZE
        min/max; stale stats surface as a build overflow and the retry
        tier falls back to the hash table."""
        if node.multi or node.kind == "cross" or len(node.right_keys) != 1:
            return
        rk = node.right_keys[0]
        if not isinstance(rk, E.ColRef) or rk.type.kind not in (
                T.Kind.INT32, T.Kind.INT64, T.Kind.DATE):
            return
        org = _origin(node.right, rk.name)
        cs = self._stats_lookup(node.right)(rk.name)
        if org is None or cs is None or cs.min is None or cs.max is None:
            return
        try:
            ts = self.catalog.get(org[0]).stats
        except Exception:
            return
        rows = ts.rows if ts is not None else 0
        domain = int(cs.max) - int(cs.min) + 1
        # bound by the base table's density (sequence-like keys) and by a
        # hard table-memory cap. A filtered build over a big domain still
        # wins — table init is one bandwidth pass and the scatter costs
        # only the build rows, vs the iterative hash build's many rounds —
        # and the domain memory is charged to the vmem admission estimate.
        if domain <= 0 or domain > max(4 * max(rows, 1), 1 << 21) \
                or domain > (1 << 27):
            return
        node.direct_lo = int(cs.min)
        node.direct_domain = domain

    # ------------------------------------------------------------------
    def _plan_aggregate(self, node: Aggregate) -> Plan:
        node.child = self._rec(node.child)
        child = node.child
        key_ids = tuple(
            e.name for _, e in node.group_keys if isinstance(e, E.ColRef)
        )
        groups = min(self._est_groups(node, child),
                     self._group_domain_bound(node.group_keys))

        if not node.group_keys:
            # scalar aggregate: partial everywhere -> broadcast the (tiny)
            # partial states -> identical final merge on every segment
            # (SEGMENT_GENERAL result; Gather later reads one segment).
            # Keeps HAVING/projections above it on-device with no host path.
            # SINGLE_QE children go through the partial path too: a
            # single-phase scalar agg marks its output row used on EVERY
            # segment while the data lives on one, so the gather would
            # return one row per segment (advisor finding r1).
            if child.locus.kind in (LocusKind.ENTRY,
                                    LocusKind.SEGMENT_GENERAL):
                node.phase = "single"
                node.locus = child.locus
                node.est_rows = 1
                return node
            partial = self._make_partial(node)
            moved = self._broadcast(partial)
            final = self._make_final(node, partial, moved)
            final.est_rows = 1
            final.locus = Locus.segment_general(self.nseg)
            return final

        if (child.locus.kind is LocusKind.HASHED and child.locus.hashed_on(key_ids)) \
                or child.locus.kind in (LocusKind.ENTRY, LocusKind.SINGLE_QE,
                                        LocusKind.SEGMENT_GENERAL):
            node.phase = "single"
            node.locus = child.locus
            node.est_rows = groups
            node.key_bounds = self._key_bounds(child, [e for _, e in node.group_keys])
            return node

        # Agg placement is a COSTED alternative (the cdbgroup.c one-stage vs
        # two-stage choice ORCA explores as memo alternatives):
        #   two-phase: partial local -> redistribute states -> final merge
        #   one-phase: redistribute raw rows by group keys -> single agg
        # When groups ~ rows (high-NDV keys like Q3's l_orderkey), the
        # partial pass reduces nothing — it pays a full sort-agg AND moves
        # nearly the same bytes, so shipping raw rows wins.
        nk = len(node.group_keys)
        na = max(len(node.aggs), 1)
        child_w = C.row_width(child.out_cols())
        state_w = 8.0 * (nk + 2 * na)    # @s/@c/@m partial state columns
        partial_rows = min(child.est_rows, groups * max(self.nseg, 1))
        two_cost = (C.agg_cost(child.est_rows, groups, nk, na, child_w, self.nseg)
                    + C.motion_cost("redistribute", partial_rows, state_w, self.nseg)
                    + C.agg_cost(partial_rows, groups, nk, na, state_w, self.nseg))
        one_cost = (C.motion_cost("redistribute", child.est_rows, child_w, self.nseg)
                    + C.agg_cost(child.est_rows, groups, nk, na, child_w, self.nseg))
        all_colrefs = all(isinstance(e, E.ColRef) for _, e in node.group_keys)
        if all_colrefs and child.locus.is_partitioned and one_cost < two_cost:
            moved = self._redistribute(
                node.child, [e for _, e in node.group_keys], key_ids)
            node.child = moved
            node.phase = "single"
            node.locus = moved.locus
            node.est_rows = groups
            node.key_bounds = self._key_bounds(moved, [e for _, e in node.group_keys])
            return node

        # two-phase: partial local -> redistribute by group keys -> final
        partial = self._make_partial(node)
        partial.key_bounds = self._key_bounds(node.child, [e for _, e in partial.group_keys])
        key_exprs = [E.ColRef(c.id, c.type) for c, _ in partial.group_keys]
        moved = self._redistribute(
            partial, key_exprs, tuple(c.id for c, _ in partial.group_keys))
        final = self._make_final(node, partial, moved)
        final.locus = moved.locus
        final.est_rows = groups
        final.key_bounds = self._key_bounds(moved, [e for _, e in node.group_keys])
        return final

    def _key_bounds(self, child: Plan, key_exprs) -> list:
        """Per-key (lo, hi) integer bounds from ANALYZE stats — feeds the
        packed single-operand group/order sorts and narrowed join tables
        (ops/agg.py pack_keys, ops/sort.py pack_order_keys,
        ops/join.py pack_join_keys). None for unanalyzed/computed/
        non-integer keys; a stale bound is caught at runtime by the
        pack-violation flag and re-run unpacked."""
        lookup = self._stats_lookup(child)
        out = []
        for e in key_exprs:
            b = None
            if isinstance(e, E.ColRef) and e.type.kind in (
                    T.Kind.INT32, T.Kind.INT64, T.Kind.DATE, T.Kind.DECIMAL):
                # (a DECIMAL is its scaled integer, in storage and in stats)
                cs = lookup(e.name)
                if cs is not None and cs.min is not None and cs.max is not None:
                    try:
                        b = (int(cs.min), int(cs.max))
                    except (TypeError, ValueError, OverflowError):
                        b = None
                    if b is not None and e.type.kind is T.Kind.DECIMAL:
                        b = _rounded_out(*b)
            out.append(b)
        return out

    def _est_groups(self, node: Aggregate, child: Plan) -> float:
        """NDV-product estimate when every group key resolves to analyzed
        base columns; sqrt heuristic otherwise."""
        lookup = self._stats_lookup(child)
        ndvs = []
        for _, e in node.group_keys:
            cs = lookup(e.name) if isinstance(e, E.ColRef) else None
            if cs is None or cs.ndv <= 0:
                return C.est_groups(child.est_rows)
            ndvs.append(cs.ndv)
        return C.est_groups(child.est_rows, ndvs)

    def _group_domain_bound(self, group_keys) -> float:
        """Hard upper bound on distinct groups when every key has a known
        finite domain: TEXT keys can't exceed their dictionary size, BOOL
        keys can't exceed 2 (+NULL). Exact for TPC-H flag/status columns —
        keeps slot tables and result transfers at true size."""
        from greengage_tpu import types as T

        prod = 1.0
        for ci, e in group_keys:
            if ci.type.kind is T.Kind.TEXT and ci.dict_ref is not None:
                prod *= max(len(self.store.dictionary(*ci.dict_ref)), 1) + 1
            elif ci.type.kind is T.Kind.BOOL:
                prod *= 3
            else:
                return float("inf")
            if prod > 1e12:
                return float("inf")
        return prod

    def _make_partial(self, node: Aggregate) -> Aggregate:
        partial = Aggregate(
            child=node.child, group_keys=node.group_keys, aggs=node.aggs,
            phase="partial")
        partial.locus = node.child.locus
        groups = min(self._est_groups(node, node.child),
                     self._group_domain_bound(node.group_keys))
        partial.est_rows = min(node.child.est_rows, groups * max(self.nseg, 1))
        return partial

    def _make_final(self, node: Aggregate, partial: Aggregate, moved: Plan) -> Aggregate:
        final = Aggregate(
            child=moved, group_keys=node.group_keys, aggs=node.aggs, phase="final")
        return final

    # ------------------------------------------------------------------
    def _plan_union(self, node: Union) -> Plan:
        node.inputs = [self._rec(c) for c in node.inputs]
        # branches concatenate per segment (replicated branches are masked
        # to one segment by the compiler to avoid row duplication)
        node.locus = Locus.strewn(self.nseg)
        node.est_rows = sum(c.est_rows for c in node.inputs)
        return node

    # unordered global windows: every function is a whole-mesh collective
    GLOBAL_DIST = {"row_number", "count", "sum", "avg", "min", "max",
                   "first_value", "last_value"}
    # ordered global windows computable IN PLACE from all-gathered sorted
    # key runs: ranks are counted positions, ntile is arithmetic on
    # (rank, count), lag/lead/first/last resolve rank±offset via a lookup
    # into the gathered runs — rows never move
    ORDERED_GLOBAL = {"row_number", "rank", "dense_rank", "ntile",
                      "lag", "lead", "first_value", "last_value"}
    # range-repartitioned global windows (one balanced Redistribute by
    # sampled splitters of the leading key; segments own contiguous key
    # ranges, so peer groups are whole per segment and running aggregates
    # stitch with per-segment prefix totals)
    RANGE_GLOBAL = ORDERED_GLOBAL | {"sum", "count", "avg", "min", "max"}

    def _plan_window(self, node: Window) -> Plan:
        node.child = self._rec(node.child)
        child = node.child
        key_ids = tuple(e.name for e in node.partition_keys
                        if isinstance(e, E.ColRef))
        if not node.partition_keys:
            if (not node.order_keys and node.frame is None
                    and child.locus.is_partitioned
                    and all(f[1] in self.GLOBAL_DIST for f in node.wfuncs)):
                # unordered global window: the whole table is one
                # partition, so every function is a mesh collective —
                # rows stay in place instead of funneling to one chip
                # (VERDICT r3 weak #9)
                node.global_mode = True
                node.locus = child.locus
                node.est_rows = child.est_rows
                counters.inc("window_gather_free_total")
                return node
            if (node.order_keys and node.frame is None
                    and child.locus.is_partitioned):
                if all(f[1] in self.ORDERED_GLOBAL for f in node.wfuncs):
                    # ordered global ranking family over integer/date/
                    # decimal keys: each row's global rank AND the global
                    # row count are computable IN PLACE from all-gathered
                    # per-segment sorted key runs — no funnel, no row
                    # motion. Multi-key and nullable shapes pack keys into
                    # one uint64 using EXACT storage bounds from block
                    # zone maps (+1 null bit per key); a single key
                    # without usable bounds falls back to the full-64-bit
                    # encoding with runtime NULL classes (see compile)
                    spec = self._ordered_global_spec(child, node.order_keys)
                    if spec is not None:
                        node.global_mode = "ordered"
                        node.gkey_spec = spec
                        node.locus = child.locus
                        node.est_rows = child.est_rows
                        counters.inc("window_gather_free_total")
                        return node
                if all(f[1] in self.RANGE_GLOBAL for f in node.wfuncs):
                    # keys that cannot pack into the uint64 rank space
                    # (multi-key over wide domains, float keys, running
                    # aggregates): range-repartition by sampled splitters
                    # of the LEADING key — one balanced Redistribute
                    # instead of the one-chip funnel. Equal leading keys
                    # co-locate, so peer groups stay whole per segment and
                    # the segment-local kernels stitch with per-segment
                    # offsets (exec/compile.py _c_window_global_range)
                    rspec = self._range_window_spec(node.order_keys)
                    if rspec is not None:
                        m = Motion(MotionKind.REDISTRIBUTE, child,
                                   hash_exprs=[rspec["expr"]])
                        m.range_spec = rspec
                        m.locus = Locus.strewn(self.nseg)
                        m.est_rows = child.est_rows
                        node.child = m
                        node.global_mode = "range"
                        node.gkey_spec = {"mode": "range", **rspec}
                        node.locus = m.locus
                        node.est_rows = child.est_rows
                        counters.inc("window_gather_free_total")
                        return node
            # exotic global window (explicit frames, unsupported key or
            # function shapes): all rows to a single segment
            if child.locus.is_partitioned:
                counters.inc("window_funnel_total")
                const = E.Literal(0, T.INT64)
                m = Motion(MotionKind.REDISTRIBUTE, child, hash_exprs=[const])
                m.locus = Locus(LocusKind.SINGLE_QE, (), self.nseg)
                m.est_rows = child.est_rows
                node.child = m
        elif child.locus.kind is LocusKind.HASHED and child.locus.hashed_on(key_ids):
            pass   # partitions already whole per segment
        elif child.locus.is_partitioned:
            m = self._redistribute(child, list(node.partition_keys), key_ids)
            node.child = m
        node.locus = node.child.locus
        node.est_rows = child.est_rows
        return node

    _RANGE_KINDS = (T.Kind.INT32, T.Kind.INT64, T.Kind.DATE, T.Kind.DECIMAL,
                    T.Kind.FLOAT64)

    def _range_window_spec(self, order_keys):
        """Sampled-splitter range-repartition spec from the LEADING order
        key, or None. The key only needs an order-preserving uint64
        encoding (sign-flip ints / IEEE floats) — no bounds, no packing:
        routing by range just needs comparisons, and the local sort above
        handles the full key list with the general multi-operand path."""
        e, desc, nf = order_keys[0]
        if e.type.kind not in self._RANGE_KINDS \
                and not getattr(e, "_rank_space", False):
            return None
        if nf is None:
            nf = bool(desc)
        kind = "float" if e.type.kind is T.Kind.FLOAT64 else "int"
        return {"expr": e, "desc": bool(desc), "nulls_first": bool(nf),
                "kind": kind}

    def _ordered_global_spec(self, child: Plan, order_keys):
        """Distribution spec for in-place global ranking, or None (-> the
        one-chip funnel). Reference never funnels — it sorts distributed
        (nodeWindowAgg.c + tuplesort); this is the TPU-first equivalent:
        pack the ORDER BY keys order-preservingly into one uint64 so rank
        = a counted position over all-gathered sorted key runs.

        PG null placement applies: NULLS LAST asc / FIRST desc unless
        explicit. `packed` needs every key to be an INT32/INT64/DATE/
        DECIMAL ColRef with exact zone-map bounds and total width <= 64
        bits; `full64` handles ONE key of any such expression — or a
        FLOAT64 one (IEEE monotone encoding) — with no bounds at all
        (runtime NULL classes)."""
        INTISH = (T.Kind.INT32, T.Kind.INT64, T.Kind.DATE, T.Kind.DECIMAL)
        resolved = []
        for e, desc, nf in order_keys:
            if e.type.kind not in INTISH + (T.Kind.FLOAT64,) \
                    and not getattr(e, "_rank_space", False):
                return None   # rank-space TEXT keys are bounded ints
            if nf is None:
                nf = bool(desc)
            resolved.append((e, bool(desc), bool(nf)))
        fields: list | None = []
        total = 0
        for e, desc, nf in resolved:
            if getattr(e, "_rank_space", False):
                bounds = (0, (1 << e._rank_bits) - 1)
            elif e.type.kind is T.Kind.FLOAT64:
                bounds = None   # floats never pack; full64 handles one
            else:
                org = _origin(child, e.name) if isinstance(e, E.ColRef) \
                    else None
                bounds = self.store.column_bounds(*org) if org else None
            if bounds is None:
                fields = None
                break
            lo, hi = int(bounds[0]), int(bounds[1])
            bits = max((hi - lo).bit_length(), 1)
            total += bits + 1       # +1 null flag per field
            fields.append({"expr": e, "desc": desc, "nulls_first": nf,
                           "lo": lo, "hi": hi, "bits": bits})
        if fields is not None and total <= 64:
            return {"mode": "packed", "fields": fields}
        if len(resolved) == 1:
            e, desc, nf = resolved[0]
            return {"mode": "full64", "expr": e, "desc": desc,
                    "nulls_first": nf,
                    "kind": ("float" if e.type.kind is T.Kind.FLOAT64
                             else "int")}
        return None

    def _plan_sort(self, node: Sort) -> Plan:
        node.child = self._rec(node.child)
        node.locus = node.child.locus
        node.est_rows = node.child.est_rows
        node.key_bounds = self._key_bounds(
            node.child, [e for e, _, _ in node.keys])
        return node

    def _plan_limit(self, node: Limit) -> Plan:
        node.child = self._rec(node.child)
        child = node.child
        # a LIMIT buried inside the plan (subquery) must be GLOBAL: move all
        # rows to one segment first (SingleQE locus via constant-key
        # redistribute). The top-of-plan LIMIT keeps the cheaper per-segment
        # truncation + host re-limit. SEGMENT_GENERAL children are already
        # identical everywhere, so per-segment truncation is globally right.
        if id(node) not in self._root_limits and child.locus.is_partitioned:
            const = E.Literal(0, T.INT64)
            if isinstance(child, Sort):
                m = Motion(MotionKind.REDISTRIBUTE, child.child, hash_exprs=[const])
                m.locus = Locus(LocusKind.SINGLE_QE, (), self.nseg)
                m.est_rows = child.child.est_rows
                child.child = m
                child.locus = m.locus
            else:
                m = Motion(MotionKind.REDISTRIBUTE, child, hash_exprs=[const])
                m.locus = Locus(LocusKind.SINGLE_QE, (), self.nseg)
                m.est_rows = child.est_rows
                node.child = m
                child = m
        node.locus = child.locus
        if node.limit is not None:
            node.est_rows = min(child.est_rows, node.limit + node.offset)
        else:
            node.est_rows = child.est_rows
        return node

    # ------------------------------------------------------------------
    def _redistribute(self, child: Plan, exprs: list, key_ids: tuple) -> Motion:
        m = Motion(MotionKind.REDISTRIBUTE, child, hash_exprs=list(exprs))
        m.locus = Locus.hashed(key_ids, self.nseg) if all(key_ids) else Locus.strewn(self.nseg)
        m.est_rows = child.est_rows
        return m

    def _broadcast(self, child: Plan) -> Motion:
        m = Motion(MotionKind.BROADCAST, child)
        m.locus = Locus.segment_general(self.nseg)
        m.est_rows = child.est_rows * self.nseg
        return m

    def _gather(self, child: Plan) -> Motion:
        merge_keys = None
        if isinstance(child, Sort):
            merge_keys = child.keys
        elif isinstance(child, Limit) and isinstance(child.child, Sort):
            merge_keys = child.child.keys
        m = Motion(MotionKind.GATHER, child, merge_keys=merge_keys)
        m.locus = Locus.entry()
        m.est_rows = child.est_rows
        return m


def _rounded_out(lo: int, hi: int) -> tuple[int, int]:
    """(lo, hi) widened to a grid of a sixteenth of the span's power of two.
    A measure's sampled minimum and maximum are the data's own (keys and
    dates sit on their domain's ends), and the packed sorts compile their
    bounds in as constants: rounded out, like data gives the same program
    and the packed word at most one more bit."""
    s = max((hi - lo).bit_length() - 4, 0)
    return (lo >> s) << s, (((hi >> s) + 1) << s) - 1


def _find_single_scan(plan: Plan, table: str):
    """The unique Scan of ``table`` in the subtree, or None if absent or
    scanned more than once (two scans must not share one prune)."""
    found = None
    stack = [plan]
    while stack:
        p = stack.pop()
        if isinstance(p, Scan) and p.table == table:
            if found is not None:
                return None
            found = p
        stack.extend(p.children)
    return found


def _origin(plan: Plan, col_id: str):
    """Resolve a column id through pass-through nodes to its base-table
    (table, column) origin — None for computed/derived columns. The stats
    machinery uses this instead of threading provenance through every
    binder expression."""
    if isinstance(plan, Scan):
        for c in plan.cols:
            if c.id == col_id:
                return (plan.table, c.name)
        return None
    if isinstance(plan, (Filter, Motion, Limit, Sort, Window)):
        return _origin(plan.children[0], col_id)
    if isinstance(plan, Project):
        for c, e in plan.exprs:
            if c.id == col_id:
                return _origin(plan.child, e.name) if isinstance(e, E.ColRef) else None
        return None
    if isinstance(plan, Join):
        return _origin(plan.left, col_id) or _origin(plan.right, col_id)
    if isinstance(plan, Aggregate):
        for c, e in plan.group_keys:
            if c.id == col_id:
                return _origin(plan.child, e.name) if isinstance(e, E.ColRef) else None
        return None
    return None


def _keys_look_unique(plan: Plan, key_exprs) -> bool:
    """Heuristic uniqueness: the join keys include a column set that is some
    underlying Scan's full hash-distribution key (tables are conventionally
    distributed by primary key). Pass-through nodes are traversed; joins
    against a unique side preserve the probe side's keys."""
    ids = {e.name for e in key_exprs if isinstance(e, E.ColRef)}
    if not ids:
        return False
    return _scan_covers(plan, ids)


def _scan_covers(plan: Plan, ids: set) -> bool:
    if isinstance(plan, Scan):
        by_id = {c.id: c.name for c in plan.cols}
        names = {by_id[i] for i in ids if i in by_id}
        pol = plan.locus
        from greengage_tpu.planner.locus import LocusKind as LK

        if pol is not None and pol.kind is LK.HASHED:
            key_names = set()
            for c in plan.cols:
                if c.id in pol.keys:
                    key_names.add(c.name)
            return bool(key_names) and key_names <= names
        return False
    if isinstance(plan, (Filter, Motion, Limit, Sort)):
        return _scan_covers(plan.children[0], ids)
    if isinstance(plan, Project):
        # translate projected ids back to child ids for pass-through refs
        back = {c.id: e.name for c, e in plan.exprs if isinstance(e, E.ColRef)}
        child_ids = {back.get(i) for i in ids}
        if None in child_ids:
            return False
        return _scan_covers(plan.child, child_ids)
    if isinstance(plan, Aggregate):
        # grouped output is unique on its full group key set
        key_ids = {c.id for c, _ in plan.group_keys}
        return bool(key_ids) and key_ids <= ids
    if isinstance(plan, Join):
        # unique(left) x unique-matched build keeps left keys unique
        return _scan_covers(plan.left, ids)
    return False


def plan_query(root: Plan, catalog, store, numsegments: int,
               force_multi_join: bool = False, feedback=None) -> Plan:
    return Planner(catalog, store, numsegments, force_multi_join,
                   feedback=feedback).plan(root)
