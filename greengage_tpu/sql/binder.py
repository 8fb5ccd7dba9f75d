"""Binder / semantic analyzer: AST -> typed logical plan.

The parse_analyze + subquery_planner front half of the reference
(src/backend/parser/analyze.c, optimizer/plan/planner.c) collapsed into one
pass: name resolution, type checking/coercion, aggregate extraction,
predicate pushdown, greedy equi-join ordering for comma-FROM, and the
string-dictionary lowering described in greengage_tpu/expr.py (literals ->
codes, LIKE -> LUTs, cross-dictionary equality -> translation LUTs).
"""

from __future__ import annotations

import dataclasses
import itertools
import re
import copy as _copy
import datetime
import operator

import numpy as np

from greengage_tpu import expr as E
from greengage_tpu import types as T
from greengage_tpu.catalog import PolicyKind
from greengage_tpu.planner import stats as _stats
from greengage_tpu.planner.logical import (
    Aggregate, ColInfo, Filter, Join, Limit, Plan, Project, Scan, Sort,
)
from greengage_tpu.sql import ast as A
from greengage_tpu.sql.parser import SqlError

_TYPE_MAP = {
    "int": T.INT32, "integer": T.INT32, "int4": T.INT32, "smallint": T.INT32,
    "bigint": T.INT64, "int8": T.INT64,
    "double precision": T.FLOAT64, "float8": T.FLOAT64, "float": T.FLOAT64,
    "real": T.FLOAT64,
    "date": T.DATE,
    "bool": T.BOOL, "boolean": T.BOOL,
    "text": T.TEXT, "varchar": T.TEXT, "char": T.TEXT, "character": T.TEXT,
    "bpchar": T.TEXT,
}


def type_from_name(name: str, typmod: tuple[int, ...]) -> T.SqlType:
    name = name.lower()
    if name in ("decimal", "numeric"):
        scale = typmod[1] if len(typmod) > 1 else 0
        return T.decimal(scale)
    if name in _TYPE_MAP:
        return _TYPE_MAP[name]
    raise SqlError(f"unknown type {name}")


class Scope:
    """Visible columns: list of (alias, {colname: ColInfo})."""

    def __init__(self):
        self.tables: list[tuple[str, dict[str, ColInfo]]] = []

    def add(self, alias: str, cols: dict[str, ColInfo]):
        if any(a == alias for a, _ in self.tables):
            raise SqlError(f'duplicate table alias "{alias}"')
        self.tables.append((alias, cols))

    def merged(self, other: "Scope") -> "Scope":
        s = Scope()
        s.tables = self.tables + other.tables
        return s

    def resolve(self, parts: tuple[str, ...]) -> ColInfo:
        if len(parts) == 2:
            for a, cols in self.tables:
                if a == parts[0]:
                    if parts[1] not in cols:
                        raise SqlError(f'column "{parts[0]}.{parts[1]}" does not exist')
                    return cols[parts[1]]
            raise SqlError(f'missing FROM-clause entry for table "{parts[0]}"')
        hits = [cols[parts[0]] for _, cols in self.tables if parts[0] in cols]
        if not hits:
            raise SqlError(f'column "{parts[0]}" does not exist')
        if len(hits) > 1:
            raise SqlError(f'column reference "{parts[0]}" is ambiguous')
        return hits[0]

    def all_cols(self) -> list[ColInfo]:
        if getattr(self, "empty_from", False):
            raise SqlError("SELECT * with no tables specified")
        return [c for _, cols in self.tables for c in cols.values()]

    def table_cols(self, alias: str) -> list[ColInfo]:
        for a, cols in self.tables:
            if a == alias:
                return list(cols.values())
        raise SqlError(f'unknown table "{alias}"')


class Binder:
    def __init__(self, catalog, store, subquery_executor=None,
                 optimizer: bool = True, scalar_device: bool = True):
        self.catalog = catalog
        self.store = store
        self._uid = itertools.count()
        self.consts: dict[str, np.ndarray] = {}   # LUT pool shipped to device
        self._scan_for: dict[str, "Scan"] = {}    # base col id -> its Scan
        # GUC 'scalar_device_enabled': lower raw-TEXT string-function
        # chains to device byte ops (E.RawStrOp); False = the legacy
        # per-row host chains (the microbench baseline)
        self.scalar_device = scalar_device
        # callable(SelectStmt) -> (python scalar | None, SqlType): runs an
        # uncorrelated scalar subquery at bind time (InitPlan analog)
        self.subquery_executor = subquery_executor
        # GUC 'optimizer' (the planner-selection analog): True routes
        # multi-relation FROMs through the Cascades-lite memo search
        # (planner/memo.py); False keeps the left-deep DP/greedy order
        self.optimizer = optimizer
        self.memo_used = False    # set when the memo produced a join tree

    def new_id(self, hint: str) -> str:
        return f"{hint}#{next(self._uid)}"

    # ------------------------------------------------------------------
    # entry
    # ------------------------------------------------------------------
    def bind_select(self, stmt) -> tuple[Plan, list[ColInfo]]:
        # bind NEVER mutates the caller's AST: the pre-bind expanders
        # (stat aggs, ordered sets, winagg, grouping sets) rewrite in
        # place, and callers bind the same statement twice (multihost
        # plan-hash + execute; plan caches keyed on the AST) — one
        # defensive copy here establishes the invariant for all of them

        stmt = _copy.deepcopy(stmt)
        if isinstance(stmt, A.UnionStmt):
            plan, outs = self._bind_union(stmt)
        else:
            plan, outs = self._bind_select(stmt)
        needed = set()
        _collect_needed(plan, needed)
        _prune_scans(plan, needed)
        return plan, outs

    # ------------------------------------------------------------------
    def _bind_select(self, stmt: A.SelectStmt) -> tuple[Plan, list[ColInfo]]:
        # statistics aggregates (stddev/variance/covar/corr/regr_*) expand
        # into sum/count moment algebra before anything else sees them
        # (sql/stataggs.py; pg_aggregate.h:246 family)
        from greengage_tpu.sql.stataggs import expand_stat_aggs

        expand_stat_aggs(stmt)
        # ordered-set aggregates rewrite the WHOLE statement (windowed
        # inner + order-statistic outer, sql/orderedset.py)
        from greengage_tpu.sql.orderedset import expand_ordered_set

        repl = expand_ordered_set(stmt)
        if repl is not None:
            return self._bind_select(repl)
        # windows over grouped aggregates: two-level rewrite (inner agg,
        # outer windows — sql/winagg.py, the WindowAgg-over-Agg stack)
        from greengage_tpu.sql.winagg import expand_windows_over_aggs

        repl = expand_windows_over_aggs(stmt)
        if repl is not None:
            return self._bind_select(repl)
        if stmt.grouping_sets is not None:
            return self._bind_grouping_sets(stmt)
        # peel subquery predicates (IN/EXISTS) off the WHERE — they become
        # semi/anti joins around the FROM plan (cdbsubselect.c pull-up)
        conjs = _split_and(stmt.where)
        normal, subq, corr_scalar = [], [], []
        for c in conjs:
            negate = False
            inner = c
            while isinstance(inner, A.Unary) and inner.op == "not":
                negate = not negate
                inner = inner.arg
            if isinstance(inner, (A.InSubquery, A.ExistsExpr)):
                subq.append((inner, negate != getattr(inner, "negate", False)))
            elif (isinstance(inner, A.Bin)
                  and inner.op in ("=", "<>", "<", "<=", ">", ">=")
                  and not negate
                  and (isinstance(inner.left, A.ScalarSubquery)
                       ^ isinstance(inner.right, A.ScalarSubquery))):
                # comparison against a scalar subquery: correlated ones are
                # decorrelated into a join; uncorrelated ones bind normally
                # (executed as InitPlans) via the `normal` path
                sub = inner.left if isinstance(inner.left, A.ScalarSubquery) else inner.right
                if self._is_correlated(sub.query):
                    corr_scalar.append(inner)
                    continue
                normal.append(c)
            else:
                normal.append(c)
        where = _join_and(normal)

        n_agg_items = sum(1 for it in stmt.items if _contains_agg(it.expr))
        plan, scope, leftover = self._bind_from(
            stmt.from_, where, group_by=stmt.group_by or None,
            naggs=n_agg_items)
        if leftover is not None:
            # sink each WHERE conjunct below the join sides it alone
            # references (inner/cross either side, outer probe side only) —
            # the qual-pushdown explicit JOIN ... ON syntax needs, which
            # also feeds selectivity into join estimates and exposes
            # pushable conjuncts to zone maps / dynamic partition pruning
            rest = []
            for c in _split_and(leftover):
                pred = self._predicate(c, scope)
                refs = _expr_col_ids(pred)
                sunk = False
                if refs:
                    plan, sunk = _sink_pred(plan, pred, refs)
                if not sunk:
                    rest.append(pred)
            if rest:
                plan = Filter(plan, rest[0] if len(rest) == 1
                              else E.BoolOp("and", tuple(rest)))
        for node, negate in subq:
            plan = self._bind_subquery_pred(node, negate, plan, scope)
        for cmp_ast in corr_scalar:
            plan = self._bind_corr_scalar(cmp_ast, plan, scope)

        # grouping-set branches: typed NULLs resolve against this FROM
        # scope; grouping() in a PLAIN grouped select folds to 0 (PG)
        self._resolve_typed_nulls(stmt, scope)
        if stmt.group_by and _contains_grouping(stmt):
            keys = {_ast_key(g) for g in stmt.group_by}
            for it in stmt.items:
                it.expr = _gs_rewrite(it.expr, keys, keys)
            if stmt.having is not None:
                stmt.having = _gs_rewrite(stmt.having, keys, keys)
            for oi in stmt.order_by:
                oi.expr = _gs_rewrite(oi.expr, keys, keys)

        # aggregate / window detection
        has_aggs = any(
            _contains_agg(it.expr) for it in stmt.items
        ) or (stmt.having is not None and _contains_agg(stmt.having)) \
            or stmt.group_by or stmt.forced_group
        has_windows = any(_contains_window(it.expr) for it in stmt.items)
        if has_aggs and has_windows:
            raise SqlError(
                "window functions over grouped aggregates are not supported yet")
        if stmt.having is not None and _contains_window(stmt.having):
            raise SqlError("window functions are not allowed in HAVING")
        if any(_contains_window(oi.expr) for oi in stmt.order_by):
            raise SqlError(
                "window functions in ORDER BY are not supported; use a "
                "select-list alias")

        if has_aggs:
            plan, agg_scope, rewrites = self._bind_aggregate(stmt, plan, scope)
            out_scope, sel_exprs = self._bind_select_items(stmt, agg_scope, rewrites)
        elif has_windows:
            if stmt.having is not None:
                raise SqlError("HAVING requires GROUP BY or aggregates")
            plan, win_rewrites = self._bind_windows(stmt, plan, scope)
            out_scope, sel_exprs = self._bind_select_items(
                stmt, scope, win_rewrites, allow_plain=True)
        else:
            if stmt.having is not None:
                raise SqlError("HAVING requires GROUP BY or aggregates")
            out_scope, sel_exprs = self._bind_select_items(stmt, scope, {})

        proj_cols = [c for c, _ in sel_exprs]

        # ORDER BY may reference non-projected expressions: aggregates/group
        # keys resolve through the rewrite map; plain input columns (PG
        # allows them for ungrouped queries) ride as hidden pass-throughs
        agg_rewrites = rewrites if has_aggs else {}
        src_to_out = {e.name: ci for ci, e in sel_exprs if isinstance(e, E.ColRef)}
        if stmt.distinct:
            # raw DISTINCT keys become transient-dictionary codes (equal
            # strings = equal codes; rendering decodes via the dictionary).
            # Before ORDER BY binding, so sort keys see coded columns.
            for i, (ci, e) in enumerate(sel_exprs):
                if ci.raw_ref is None:
                    continue
                coded = self._raw_to_codes(e)
                if coded is None:
                    raise SqlError(
                        "raw-encoded text cannot be used as a DISTINCT key")
                ci.dict_ref = _dict_ref_of(coded)
                ci.raw_ref = None
                ci.raw_chain = None
                sel_exprs[i] = (ci, coded)
        order_keys = []
        if stmt.order_by:
            for oi in stmt.order_by:
                e = None
                if agg_rewrites:
                    hit = (agg_rewrites.get(id(oi.expr))
                           or agg_rewrites.get(_ast_key(oi.expr)))
                    if hit is not None:
                        out_ci = src_to_out.get(hit.id)
                        if out_ci is not None:
                            e = _colref(out_ci)
                        else:
                            ci = ColInfo(self.new_id("ord"), hit.type, "?order?",
                                         hit.dict_ref, hidden=True,
                                         raw_ref=hit.raw_ref,
                                         raw_chain=getattr(hit, "raw_chain",
                                                           None))
                            sel_exprs.append((ci, _colref(hit)))
                            e = _colref(ci)
                if e is None:
                    try:
                        e = self._bind_order_expr(oi.expr, proj_cols, out_scope)
                    except SqlError:
                        if stmt.distinct:
                            raise
                        if has_aggs:
                            # expression OVER aggregates/keys not in the
                            # output (order by sum(x)/count(*), expanded
                            # stddev): bind against the agg rewrites and
                            # carry it as a hidden sort column
                            e = self._rewritten_expr(
                                oi.expr, agg_rewrites, scope)
                        else:
                            e = self._expr(oi.expr, scope)
                        ci = ColInfo(self.new_id("ord"), e.type, "?order?",
                                     _dict_ref_of(e), hidden=True,
                                     raw_ref=_raw_ref_of(e),
                                     raw_chain=_raw_chain_of(e))
                        sel_exprs.append((ci, e))
                        e = _colref(ci)
                if _raw_ref_of(e) is not None and not stmt.distinct \
                        and not has_aggs:
                    # raw sort key: convert the projected column's SOURCE
                    # expression (handles ordinals/aliases uniformly) and
                    # ride the transient-dictionary codes as a hidden
                    # column (codes + rank LUT sort correctly; surrogates
                    # don't)
                    src = None
                    if isinstance(e, E.ColRef):
                        src = next((ex for ci2, ex in sel_exprs
                                    if ci2.id == e.name), None)
                    coded = self._raw_to_codes(
                        src if src is not None else e)
                    ci = ColInfo(self.new_id("ord"), coded.type, "?order?",
                                 _dict_ref_of(coded), hidden=True)
                    sel_exprs.append((ci, coded))
                    e = _colref(ci)
                if not isinstance(e, E.ColRef):
                    # expression sort key over OUTPUT columns (order by
                    # sum_sales - avg_monthly_sales): the gather's host
                    # merge needs plain column keys, so re-express the
                    # key over the outputs' SOURCE exprs and ride it as
                    # a hidden projected column
                    sub = _subst_refs(e, {ci2.id: ex
                                          for ci2, ex in sel_exprs})
                    if sub is not None:
                        ci = ColInfo(self.new_id("ord"), e.type, "?order?",
                                     _dict_ref_of(e), hidden=True)
                        sel_exprs.append((ci, sub))
                        e = _colref(ci)
                order_keys.append((self._no_raw(e, "sort key"),
                                   oi.desc, oi.nulls_first))

        plan = Project(plan, sel_exprs)

        if stmt.distinct:
            keys = [(c, E.ColRef(c.id, c.type)) for c in proj_cols]
            plan = Aggregate(plan, keys, [])

        if order_keys:
            plan = Sort(plan, order_keys)
        if stmt.limit is not None or stmt.offset:
            plan = Limit(plan, stmt.limit, stmt.offset)
        return plan, proj_cols

    # ------------------------------------------------------------------
    # subquery predicates -> semi/anti joins (cdbsubselect.c pull-up analog)
    # ------------------------------------------------------------------
    def _bind_subquery_pred(self, node, negate: bool, plan: Plan, scope) -> Plan:
        from greengage_tpu.planner.logical import Join

        if isinstance(node, A.InSubquery):
            arg = self._expr(node.arg, scope)
            subplan, subouts = self._bind_select(node.query)
            if len(subouts) != 1:
                raise SqlError("subquery for IN must return one column")
            skey = _colref(subouts[0])
            lks, rks = self._align_join_keys([arg], [skey])
            if negate:
                return Join("anti", plan, subplan, lks, rks, null_aware=True)
            return _sink_semi(plan, Join("semi", plan, subplan, lks, rks))

        # EXISTS: correlation via equality predicates against the outer scope
        q = node.query
        if q.group_by or q.having:
            raise SqlError("GROUP BY/HAVING inside EXISTS is not supported")
        if q.offset:
            raise SqlError("OFFSET inside EXISTS is not supported")
        if q.limit == 0 or (q.items and any(_contains_agg(it.expr) for it in q.items)):
            # LIMIT 0: subquery is empty, EXISTS constant-false. Ungrouped
            # aggregate select list: exactly one row always, constant-true.
            const_true = q.limit != 0
            exists_val = const_true != negate
            if exists_val:
                return plan
            return Filter(plan, E.Literal(False, T.BOOL))
        # (any other LIMIT >= 1 can't change existence — ignored)

        subplan, sub_scope, _ = self._bind_from(q.from_, None)
        inner_only, corr_pairs, outer_only, residuals, bad = \
            _split_correlation(_split_and(q.where), scope, sub_scope)
        if bad:
            raise SqlError(
                "EXISTS correlation references columns visible in neither "
                "the subquery nor the outer query")
        if residuals and not corr_pairs:
            raise SqlError(
                "non-equality EXISTS correlation needs at least one "
                "equality conjunct to join on")
        if outer_only and negate:
            # not exists(P_outer AND Q) = NOT P_outer OR NOT exists(Q):
            # not expressible as a filter + anti join; bail honestly
            raise SqlError(
                "outer-only predicates inside NOT EXISTS are not supported")
        if inner_only:
            subplan = Filter(subplan, self._predicate(_join_and(inner_only), sub_scope))
        kind = "anti" if negate else "semi"
        if corr_pairs:
            lks = [self._expr(o, scope) for o, _ in corr_pairs]
            rks = [self._expr(i, sub_scope) for _, i in corr_pairs]
            lks, rks = self._align_join_keys(lks, rks)
            res_pred = None
            if residuals:
                # mixed-reference non-equality conjuncts (l2.x <> l1.x):
                # evaluated per candidate pair over the CSR expansion —
                # a probe row qualifies iff ANY pair passes (Q21 shape).
                # SUB scope first: an alias shadowed by the subquery must
                # resolve to the INNER table (SQL innermost-wins scoping)
                both = sub_scope.merged(scope)
                res_pred = self._predicate(_join_and(residuals), both)
            joined = Join(kind, plan, subplan, lks, rks, residual=res_pred)
            if kind == "semi" and res_pred is None:
                joined = _sink_semi(plan, joined)
        else:
            # uncorrelated EXISTS: constant-key semi join (matched iff sub
            # produced any row; duplicate constant keys are fine)
            one = E.Literal(1, T.INT32)
            joined = Join(kind, plan, subplan, [one], [one])
        if outer_only:
            joined = Filter(joined, self._predicate(_join_and(outer_only), scope))
        return joined

    # ------------------------------------------------------------------
    # correlated scalar subqueries -> join on grouped aggregate
    # ------------------------------------------------------------------
    def _is_correlated(self, q: A.SelectStmt) -> bool:
        """True if the subquery's WHERE references columns outside its own
        FROM (cheap probe bind of the sub scope, cached for the rewrite)."""
        try:
            _, sub_scope, _ = self._bind_from(q.from_, None)
        except SqlError:
            return False
        self._corr_probe = (id(q), sub_scope)
        for c in _split_and(q.where):
            for parts in _name_refs(c):
                if not _in_scope(parts, sub_scope):
                    return True
        return False

    def _bind_corr_scalar(self, cmp_ast: A.Bin, plan: Plan, scope) -> Plan:
        """Decorrelate ``outer_expr <op> (SELECT agg(...) FROM s WHERE
        s.k = outer.k ...)`` into: Aggregate(s GROUP BY k) joined to the
        outer plan on k, then a Filter applying <op> (nodeSubplan ->
        join+agg rewrite). A missing group means the scalar is NULL and the
        comparison drops the row — exactly the inner join's behavior — for
        sum/avg/min/max; a bare count() is 0 over an empty set, so it uses
        a LEFT join with the NULL count mapped to 0."""
        from greengage_tpu.planner.logical import Join

        if isinstance(cmp_ast.left, A.ScalarSubquery):
            sub, outer_ast, flip = cmp_ast.left, cmp_ast.right, True
        else:
            sub, outer_ast, flip = cmp_ast.right, cmp_ast.left, False
        q = sub.query
        if len(q.items) != 1 or not _contains_agg(q.items[0].expr):
            raise SqlError(
                "correlated scalar subqueries must compute one aggregate")
        if q.group_by or q.having or q.limit is not None or q.offset:
            raise SqlError(
                "GROUP BY/HAVING/LIMIT/OFFSET in a correlated scalar "
                "subquery is not supported")
        item = q.items[0].expr
        is_bare_count = (isinstance(item, A.FuncCall) and item.name == "count"
                         and item.over is None)
        if not is_bare_count and _contains_count(item):
            raise SqlError(
                "expressions over count() in correlated scalar subqueries "
                "are not supported (count of an empty set is 0, not NULL)")
        # classify the subquery's conjuncts against the outer scope,
        # reusing the probe bind's scope from _is_correlated when possible
        probe = getattr(self, "_corr_probe", None)
        if probe is not None and probe[0] == id(q):
            sub_scope = probe[1]
        else:
            _, sub_scope, _ = self._bind_from(q.from_, None)
        inner_only, corr_pairs, outer_only, residuals, bad = \
            _split_correlation(_split_and(q.where), scope, sub_scope)
        if bad or residuals:
            raise SqlError(
                "only equality correlation is supported in scalar subqueries")
        if not corr_pairs:
            raise SqlError("scalar subquery correlation not recognized")
        if outer_only and is_bare_count:
            raise SqlError(
                "outer-only predicates in a correlated count() subquery are "
                "not supported")
        # grouped aggregate over the correlation keys
        sub_stmt = A.SelectStmt(
            items=[A.SelectItem(q.items[0].expr, alias="__sv")]
            + [A.SelectItem(ie, alias=f"__ck{i}")
               for i, (_, ie) in enumerate(corr_pairs)],
            from_=q.from_,
            where=_join_and(inner_only),
            group_by=[ie for _, ie in corr_pairs],
        )
        subplan, subouts = self._bind_select(sub_stmt)
        val_ci, key_cis = subouts[0], subouts[1:]
        lks = [self._expr(o, scope) for o, _ in corr_pairs]
        rks = [_colref(ci) for ci in key_cis]
        lks, rks = self._align_join_keys(lks, rks)
        joined = Join("left" if is_bare_count else "inner",
                      plan, subplan, lks, rks)
        outer_e = self._expr(outer_ast, scope)
        sub_e = _colref(val_ci)
        if is_bare_count:
            # count over an empty correlated set is 0, not NULL
            sub_e = E.Case(
                whens=((E.IsNull(sub_e), E.Literal(0, T.INT64)),),
                else_=sub_e, type=T.INT64)
        le, re_ = (sub_e, outer_e) if flip else (outer_e, sub_e)
        le, re_ = self._coerce_pair(le, re_)
        out = Filter(joined, E.Cmp(cmp_ast.op, le, re_))
        if outer_only:
            out = Filter(out, self._predicate(_join_and(outer_only), scope))
        return out

    # ------------------------------------------------------------------
    # window functions
    # ------------------------------------------------------------------
    _WINFUNCS = {"row_number", "rank", "dense_rank", "sum", "count", "avg",
                 "min", "max", "lag", "lead", "first_value", "last_value",
                 "ntile"}
    # first_value/last_value are legal WITHOUT order by in PostgreSQL
    # (whole-frame semantics: the frame is the entire partition) — only
    # position-offset functions truly need an ordering
    _WIN_NEED_ORDER = {"lag", "lead", "ntile"}

    def _bind_windows(self, stmt, plan, scope):
        from greengage_tpu.planner.logical import Window

        calls: list[A.FuncCall] = []

        def collect(n):
            if isinstance(n, A.FuncCall) and n.over is not None:
                calls.append(n)
                return
            for ch in _ast_children(n):
                collect(ch)

        for it in stmt.items:
            collect(it.expr)

        def spec_key(over: A.WindowSpec) -> str:
            parts = [_ast_key(p) for p in over.partition_by]
            parts.append("|")
            for oi in over.order_by:
                parts.append(f"{_ast_key(oi.expr)}:{oi.desc}:{oi.nulls_first}")
            parts.append(f"|{over.frame}")
            return " ".join(parts)

        groups: dict[str, list[A.FuncCall]] = {}
        for fc in calls:
            groups.setdefault(spec_key(fc.over), []).append(fc)

        rewrites: dict = {}
        for fcs in groups.values():
            spec = fcs[0].over
            pkeys = [self._no_raw(self._win_raw_key(self._expr(p, scope)),
                                  "window partition key")
                     for p in spec.partition_by]
            okeys = [(self._win_order_key(
                          self._no_raw(self._win_raw_key(
                              self._expr(oi.expr, scope)),
                                       "window order key")),
                      oi.desc, oi.nulls_first)
                     for oi in spec.order_by]
            frame = self._bind_frame(spec.frame)
            wfuncs = []
            for fc in fcs:
                fname = fc.name
                if fname not in self._WINFUNCS:
                    raise SqlError(f"unknown window function {fname}")
                if fc.distinct:
                    raise SqlError("DISTINCT in window functions is not supported")
                if fname in self._WIN_NEED_ORDER and not spec.order_by:
                    raise SqlError(f"{fname}() requires OVER (... ORDER BY)")
                arg = None
                param = None
                if fname in ("row_number", "rank", "dense_rank"):
                    if fc.args or fc.star:
                        raise SqlError(f"{fname}() takes no arguments")
                    rtype = T.INT64
                elif fname == "ntile":
                    param = self._win_int_param(fc, 0, fname)
                    if param < 1:
                        raise SqlError("ntile() buckets must be positive")
                    rtype = T.INT64
                elif fname in ("lag", "lead"):
                    if not fc.args:
                        raise SqlError(f"{fname}() requires an argument")
                    # raw-TEXT args ride the transient dictionary: the
                    # function only moves the value, codes decode at
                    # finalize like any dict column
                    arg = self._win_raw_key(self._expr(fc.args[0], scope))
                    k = (self._win_int_param(fc, 1, fname)
                         if len(fc.args) > 1 else 1)
                    if k < 0:
                        raise SqlError(f"{fname}() offset must be >= 0")
                    default = None
                    if len(fc.args) > 2:
                        d = self._expr(fc.args[2], scope)
                        if not isinstance(d, E.Literal):
                            raise SqlError(
                                f"{fname}() default must be a literal")
                        default = self._coerce_literal(d, arg.type).value
                    param = (k, default)
                    rtype = arg.type
                elif fname in ("first_value", "last_value"):
                    if not fc.args:
                        raise SqlError(f"{fname}() requires an argument")
                    arg = self._win_raw_key(self._expr(fc.args[0], scope))
                    rtype = arg.type
                elif fc.star or not fc.args:
                    if fname != "count":
                        raise SqlError(f"{fname}(*) is not valid")
                    rtype = T.INT64
                else:
                    arg = self._expr(fc.args[0], scope)
                    if arg.type.kind is T.Kind.TEXT and fname in ("min", "max",
                                                                  "sum", "avg"):
                        raise SqlError(
                            f"window {fname}() over text is not supported yet")
                    rtype = E.agg_result_type(
                        "count" if fname == "count" else fname, arg.type)
                if fname in ("min", "max") and frame is not None                         and frame != (None, 0) and frame != (None, None):
                    raise SqlError(
                        f"window {fname}() supports only ROWS UNBOUNDED "
                        "PRECEDING frames (running or whole-partition)")
                if arg is not None:
                    self._no_raw(arg, "window function argument")
                ci = ColInfo(self.new_id(fname), rtype, fname,
                             _dict_ref_of(arg) if arg is not None and
                             fname in ("lag", "lead", "first_value",
                                       "last_value", "min", "max") else None)
                wfuncs.append((ci, fname, arg, bool(spec.order_by), param))
                rewrites[id(fc)] = ci
            plan = Window(plan, pkeys, okeys, wfuncs, frame)
        return plan, rewrites

    def _win_int_param(self, fc, idx, fname) -> int:
        a = fc.args[idx] if len(fc.args) > idx else None
        if not isinstance(a, A.Num) or "." in a.text:
            raise SqlError(f"{fname}() parameter must be an integer literal")
        return int(a.text)

    @staticmethod
    def _bind_frame(frame):
        """AST frame -> (preceding, following) row offsets with None =
        unbounded. Only ROWS frames change evaluation; the default RANGE
        UNBOUNDED PRECEDING..CURRENT ROW is the built-in peer semantics."""
        if frame is None:
            return None
        mode, lo, hi = frame
        if mode == "range":
            if lo == ("unbounded_preceding", None) and hi == ("current", None):
                return None   # the default frame
            raise SqlError(
                "only the default RANGE frame is supported; use ROWS")

        def bound(b, is_start):
            kind, n = b
            if kind == "unbounded_preceding":
                if not is_start:
                    raise SqlError("frame end cannot be UNBOUNDED PRECEDING")
                return None
            if kind == "unbounded_following":
                if is_start:
                    raise SqlError("frame start cannot be UNBOUNDED FOLLOWING")
                return None
            if kind == "current":
                return 0
            if kind == "preceding":
                return n if is_start else -n
            return -n if is_start else n   # following

        return (bound(lo, True), bound(hi, False))

    # ------------------------------------------------------------------
    # UNION
    # ------------------------------------------------------------------
    # GROUPING SETS / ROLLUP / CUBE
    # ------------------------------------------------------------------
    def _bind_grouping_sets(self, stmt: A.SelectStmt):
        """Desugar to UNION ALL of per-set grouped selects — the MPP-honest
        translation (each branch is an independent distributed aggregate;
        the reference executes the same shape via its own Append-of-Agg
        plans for grouping extensions, gram.y:12457 -> planner groupingsets
        paths). Keys absent from a set project as typed NULLs; grouping()
        folds to a per-branch constant bitmask."""

        universe: dict[str, A.ANode] = {}
        for s in stmt.grouping_sets:
            for e in s:
                universe.setdefault(_ast_key(e), e)
        # ORDER BY exprs containing aggregates or grouping() cannot bind at
        # the union level (they reference branch-internal state): lift each
        # into a hidden helper select item ordered by name
        order_by = list(stmt.order_by)
        helpers = []
        for i, oi in enumerate(order_by):
            if _contains_agg(oi.expr) or _has_grouping_call(oi.expr):
                name = f"?gsord{i}?"
                stmt.items.append(A.SelectItem(oi.expr, alias=name))
                helpers.append(name)
                order_by[i] = A.OrderItem(A.Name((name,)), oi.desc,
                                          oi.nulls_first)
        selects = []
        for s in stmt.grouping_sets:
            sub = _copy.deepcopy(stmt)
            sub.grouping_sets = None
            sub.group_by = _copy.deepcopy(s)
            sub.order_by = []
            sub.limit = None
            sub.offset = 0
            sub.distinct = False
            sub.forced_group = True
            present = {_ast_key(e) for e in s}
            for it in sub.items:
                it.expr = _gs_rewrite(it.expr, present, set(universe))
            if sub.having is not None:
                sub.having = _gs_rewrite(sub.having, present, set(universe))
            selects.append(sub)
        u = A.UnionStmt(selects=selects, all=not stmt.distinct,
                        order_by=order_by, limit=stmt.limit,
                        offset=stmt.offset)
        plan, outs = self._bind_union(u)
        if helpers:
            for c in outs:
                if c.name in helpers:
                    c.hidden = True
        return plan, outs

    def _resolve_typed_nulls(self, stmt, scope) -> None:
        """Pre-resolve TypedNullOf nodes against the FROM scope (the agg
        output scope their bind position sees no longer has the source
        columns). Raw TEXT keys resolve through their transient dictionary
        so NULL branches stay dictionary-compatible across the union."""
        def walk(n):
            if isinstance(n, A.TypedNullOf):
                if getattr(n, "rtype", None) is None:
                    inner = self._expr(n.arg, scope)
                    conv = self._raw_to_codes(inner)
                    if conv is not None:
                        inner = conv
                    n.rtype = inner.type
                    n.rdict = _dict_ref_of(inner)
                return
            if isinstance(n, A.SelectStmt):
                return
            for c in _ast_children(n):
                walk(c)

        for it in stmt.items:
            walk(it.expr)
        if stmt.having is not None:
            walk(stmt.having)

    # ------------------------------------------------------------------
    def _bind_union(self, stmt: A.UnionStmt):
        from greengage_tpu.planner.logical import Aggregate, Limit, Sort, Union

        branches = [self._bind_select(s) for s in stmt.selects]
        arity = len(branches[0][1])
        for _, outs in branches[1:]:
            if len(outs) != arity:
                raise SqlError("UNION branches must have the same column count")
        # per-position result types (+ TEXT dictionary compatibility)
        union_cols = []
        for i in range(arity):
            t = branches[0][1][i].type
            if any(outs_[i].raw_ref is not None for _, outs_ in branches):
                raise SqlError("raw-encoded text is not supported in UNION")
            dref = branches[0][1][i].dict_ref
            for _, outs in branches[1:]:
                ot = outs[i].type
                if ot.kind is T.Kind.TEXT and t.kind is T.Kind.TEXT:
                    if outs[i].dict_ref != dref:
                        raise SqlError(
                            "UNION over text columns from different "
                            "dictionaries is not supported yet")
                elif ot != t:
                    t = T.promote(t, ot)
            union_cols.append(ColInfo(self.new_id(branches[0][1][i].name), t,
                                      branches[0][1][i].name, dref))
        # cast branches to the union types where needed
        inputs = []
        for plan, outs in branches:
            exprs = []
            for uc, oc in zip(union_cols, outs):
                e = _colref(oc)
                if oc.type != uc.type:
                    e = E.Cast(e, uc.type)
                exprs.append((ColInfo(self.new_id(uc.name), uc.type, uc.name,
                                      oc.dict_ref), e))
            inputs.append(Project(plan, exprs))
        plan = Union(inputs, union_cols)
        # positional wiring: Union's cols adopt each branch's projected ids
        plan.branch_ids = [[c.id for c, _ in p.exprs] for p in inputs]
        outs = union_cols
        if not stmt.all:
            keys = [(c, E.ColRef(c.id, c.type)) for c in union_cols]
            plan = Aggregate(plan, keys, [])
            outs = [c for c, _ in keys]
        if stmt.order_by:
            keys = []
            for oi in stmt.order_by:
                e = self._bind_order_expr(oi.expr, outs, None)
                keys.append((self._no_raw(e, "sort key"), oi.desc, oi.nulls_first))
            plan = Sort(plan, keys)
        if stmt.limit is not None or stmt.offset:
            plan = Limit(plan, stmt.limit, stmt.offset)
        return plan, outs

    # ------------------------------------------------------------------
    # FROM binding with pushdown + greedy join ordering
    # ------------------------------------------------------------------
    def _bind_from(self, from_, where, group_by=None, naggs=0):
        """``group_by``/``naggs`` describe the aggregation that will sit
        above this FROM (when the caller is a grouped SELECT): the memo
        search folds its completion cost into join-order selection."""
        if not from_:
            # FROM-less SELECT (PG's Result node): one-row constant
            # relation, live on segment 0 — lets `select 1` work as a
            # subquery / union branch / recursive base term
            from greengage_tpu.planner.logical import ConstRel

            plan = ConstRel()
            scope = Scope()
            scope.add("", {})
            scope.empty_from = True   # Star over this scope must error
            leftover = where
            return plan, scope, leftover
        items = [self._bind_table_ref(t) for t in from_]

        conjuncts = _split_and(where) if where is not None else []

        if len(items) == 1:
            plan, scope = items[0]
            plan = self._push_filters(plan, scope, conjuncts)
            return plan, scope, None

        # comma-FROM join ordering: Selinger-style DP over left-deep trees
        # when statistics exist (CJoinOrderDP.cpp analog, <= 10 relations),
        # falling back to the r1 greedy order (CJoinOrderGreedy analog)
        remaining = list(items)
        conds = list(conjuncts)
        # push single-table predicates first
        for i, (p, s) in enumerate(remaining):
            p2, conds = self._push_single_table(p, s, conds)
            remaining[i] = (p2, s)

        # keep SELECT * / scope resolution in FROM-clause order regardless
        # of the join order the optimizer picks
        orig_scopes = [sc for _, sc in remaining]

        if self.optimizer:
            # Cascades-lite memo: bushy trees + distribution-property DP.
            # ORCA's fallback-on-failure semantics (optimizer_trace_fallback
            # / planner takes over when ORCA errors): ANY memo failure
            # degrades to the left-deep DP/greedy order below instead of
            # failing the statement
            try:
                tree = self._memo_join_tree(remaining, conds, group_by,
                                            naggs)
            except Exception:
                tree = None
            if tree is not None:
                self.memo_used = True
                plan, scope, conds = self._build_join_tree(
                    tree, remaining, conds)
                leftover = _join_and(conds)
                out_scope = Scope()
                for sc in orig_scopes:
                    out_scope = out_scope.merged(sc)
                return plan, out_scope, leftover

        order = self._dp_join_order(remaining, conds)
        if order is not None:
            remaining = [remaining[i] for i in order]

        plan, scope = remaining.pop(0)
        while remaining:
            picked = None
            for i, (rp, rs) in enumerate(remaining):
                eq, rest = _extract_equi(conds, scope, rs)
                if eq:
                    picked = (i, rp, rs, eq, rest)
                    break
            if picked is None:  # no equi edge: cross join the next one
                rp, rs = remaining.pop(0)
                join = Join("cross", plan, rp, [], [])
                scope = scope.merged(rs)
                plan = join
                continue
            i, rp, rs, eq, conds = picked
            remaining.pop(i)
            lkeys = [self._expr(lhs, scope) for lhs, _ in eq]
            rkeys = [self._expr(rhs, rs) for _, rhs in eq]
            lkeys, rkeys = self._align_join_keys(lkeys, rkeys)
            plan = Join("inner", plan, rp, lkeys, rkeys)
            scope = scope.merged(rs)
        leftover = _join_and(conds)
        out_scope = Scope()
        for sc in orig_scopes:
            out_scope = out_scope.merged(sc)
        return plan, out_scope, leftover

    # ------------------------------------------------------------------
    # memo search (the ORCA engine entry; planner/memo.py)
    # ------------------------------------------------------------------
    def _memo_join_tree(self, items, conds, group_by=None, naggs=0):
        """-> nested index tree from the Cascades-lite memo, or None when
        it doesn't apply (missing stats, edge cols without NDV, too many
        or disconnected relations — the fallback DP/greedy takes over)."""
        from greengage_tpu.planner import cost as C
        from greengage_tpu.planner import memo as M

        rels = []
        col_stats = []
        for plan, scope in items:
            info = self._rel_card(plan)
            if info is None:
                return None
            rows, stats = info
            node = plan
            while isinstance(node, Filter):
                node = node.child
            schema = self.catalog.get(node.table)
            pol = schema.policy
            dist: tuple = ()
            replicated = False
            if pol.kind is PolicyKind.HASH:
                by_name = {c.name: c.id for c in node.cols}
                if all(k in by_name for k in pol.keys):
                    dist = tuple(by_name[k] for k in pol.keys)
            elif pol.kind is PolicyKind.REPLICATED:
                replicated = True
            rels.append(M.RelInfo(rows, C.row_width(plan.out_cols()),
                                  dist, replicated))
            col_stats.append(stats)

        edges: dict[tuple, M.EdgeInfo] = {}
        for c in conds:
            hit = self._edge_of(c, items)
            if hit is None:
                continue
            i, j, li, ri, kinds = hit
            si, sj = col_stats[i].get(li), col_stats[j].get(ri)
            if si is None or sj is None or si.ndv <= 0 or sj.ndv <= 0:
                return None
            key = (min(i, j), max(i, j))
            e = edges.get(key)
            if e is None:
                e = edges[key] = M.EdgeInfo(key[0], key[1])
            pair = (li, ri) if i == key[0] else (ri, li)
            e.pairs.append(pair)
            # histogram join calculus with NDV-division fallback — memo
            # edge costs see the same estimate the parallelizer uses
            ksel = _stats.join_selectivity(si, sj, kinds)
            if ksel is None:
                ksel = 1.0 / max(si.ndv, sj.ndv)
            e.sel *= ksel * (1.0 - si.null_frac) * (1.0 - sj.null_frac)
        if not edges:
            return None
        nseg = self.catalog.segments.numsegments

        # the GROUP BY above this FROM, resolved to bound col ids: joint
        # join-order + agg-placement optimization (AggInfo docstring).
        # Only simple column group keys qualify — computed keys can't match
        # a distribution property anyway.
        agg = None
        if group_by:
            gcols, ndv_prod = [], 1.0
            for g in group_by:
                hit = None
                if isinstance(g, A.Name):
                    for idx, (_, scope) in enumerate(items):
                        try:
                            ci = scope.resolve(g.parts)
                            hit = (idx, ci.id)
                            break
                        except SqlError:
                            continue
                if hit is None:
                    gcols = None
                    break
                idx, cid = hit
                cs = col_stats[idx].get(cid)
                if cs is None or cs.ndv <= 0:
                    gcols = None
                    break
                gcols.append(cid)
                ndv_prod *= max(cs.ndv, 1.0)
            if gcols:
                agg = M.AggInfo(tuple(gcols), ndv_prod, max(naggs, 1))
        return M.optimize(rels, list(edges.values()), nseg, agg)

    def _build_join_tree(self, tree, items, conds):
        """Materialize the memo's nested index tree into Join nodes,
        consuming the equi conjuncts that each join edge uses."""
        conds = list(conds)

        def rec(t):
            nonlocal conds
            if not isinstance(t, tuple):
                return items[t]
            lp, ls = rec(t[0])
            rp, rs = rec(t[1])
            eq, conds = _extract_equi(conds, ls, rs)
            merged = ls.merged(rs)
            if not eq:
                return Join("cross", lp, rp, [], []), merged
            lkeys = [self._expr(l, ls) for l, _ in eq]
            rkeys = [self._expr(r, rs) for _, r in eq]
            lkeys, rkeys = self._align_join_keys(lkeys, rkeys)
            return Join("inner", lp, rp, lkeys, rkeys), merged

        plan, scope = rec(tree)
        return plan, scope, conds

    # ------------------------------------------------------------------
    # DP join ordering (System R over left-deep trees)
    # ------------------------------------------------------------------
    def _dp_join_order(self, items, conds):
        """-> permutation of item indices minimizing the classic sum of
        intermediate cardinalities, or None (no stats / too many / cross
        products involved). Cardinalities: filtered base rows x product of
        1/max(NDV) per equi edge — the same estimates the planner uses, so
        the chosen order matches its costing."""
        n = len(items)
        if n < 3 or n > 10:
            return None
        cards = []
        col_stats = []
        for plan, scope in items:
            info = self._rel_card(plan)
            if info is None:
                return None
            cards.append(info[0])
            col_stats.append(info[1])
        # equi edges: (i, j, sel)
        edges: dict[tuple, float] = {}
        for c in conds:
            pair = self._edge_of(c, items)
            if pair is None:
                continue
            i, j, li, ri, _kind = pair
            si = col_stats[i].get(li)
            sj = col_stats[j].get(ri)
            if si is None or sj is None or si.ndv <= 0 or sj.ndv <= 0:
                return None
            sel = 1.0 / max(si.ndv, sj.ndv)
            key = (min(i, j), max(i, j))
            edges[key] = edges.get(key, 1.0) * sel
        if not edges:
            return None

        def joined_card(card, S, j):
            sel = 1.0
            connected = False
            for i in range(n):
                if S & (1 << i):
                    e = edges.get((min(i, j), max(i, j)))
                    if e is not None:
                        sel *= e
                        connected = True
            if not connected:
                return None
            return card * cards[j] * sel

        # dp[mask] = (total cost, out card, order tuple), left-deep only;
        # each round's frontier holds all masks of one popcount, so a plain
        # per-round min per mask is the full Selinger DP
        frontier = {1 << i: (0.0, cards[i], (i,)) for i in range(n)}
        for _ in range(n - 1):
            nxt: dict[int, tuple] = {}
            for mask, (cost, card, order) in frontier.items():
                for j in range(n):
                    if mask & (1 << j):
                        continue
                    jc = joined_card(card, mask, j)
                    if jc is None:
                        continue   # avoid cross products
                    m2 = mask | (1 << j)
                    c2 = cost + jc
                    cur = nxt.get(m2)
                    if cur is None or c2 < cur[0]:
                        nxt[m2] = (c2, jc, order + (j,))
            frontier = nxt
        full = (1 << n) - 1
        if full not in frontier:
            return None   # not fully connectable without cross joins
        return list(frontier[full][2])

    def _rel_card(self, plan):
        """(filtered row estimate, {col id -> ColumnStats}) for a base
        relation (possibly already wrapped in pushed Filters)."""
        from greengage_tpu.planner import cost as C

        filters = []
        node = plan
        while isinstance(node, Filter):
            filters.append(node.predicate)
            node = node.child
        if not isinstance(node, Scan):
            return None
        schema = self.catalog.get(node.table)
        ts = getattr(schema, "stats", None)
        if ts is None or ts.rows <= 0:
            return None
        by_id = {c.id: c.name for c in node.cols}
        stats_by_id = {cid: ts.columns.get(nm) for cid, nm in by_id.items()}

        def lookup(cid):
            return stats_by_id.get(cid)

        rows = float(ts.rows)
        for pred in filters:
            rows *= C.filter_selectivity(pred, lookup)
        return max(rows, 1.0), stats_by_id

    def _edge_of(self, cond, items):
        """cond is an equi edge between two distinct items ->
        (i, j, left col id, right col id) or None."""
        if not (isinstance(cond, A.Bin) and cond.op == "="):
            return None

        def side(ast):
            if not isinstance(ast, A.Name):
                return None
            for idx, (_, scope) in enumerate(items):
                try:
                    ci = scope.resolve(ast.parts)
                    return idx, ci.id, ci.type.kind
                except SqlError:
                    continue
            return None

        a, b = side(cond.left), side(cond.right)
        if a is None or b is None or a[0] == b[0]:
            return None
        return a[0], b[0], a[1], b[1], (a[2], b[2])

    def _bind_table_ref(self, t: A.TableRef):
        if isinstance(t, A.BaseTable):
            schema = self.catalog.get(t.name)
            cols = {}
            out = []
            for c in schema.columns:
                is_text = c.type.kind is T.Kind.TEXT
                is_raw = is_text and c.encoding == "raw"
                ci = ColInfo(
                    self.new_id(c.name), c.type, c.name,
                    dict_ref=(t.name, c.name) if is_text and not is_raw else None,
                    raw_ref=(t.name, c.name) if is_raw else None,
                )
                cols[c.name] = ci
                out.append(ci)
            scan = Scan(t.name, out)
            if schema.is_partitioned:
                # all child storage tables; the planner statically prunes
                # this set from pushed conjuncts (PartitionSelector role)
                scan.parts = tuple(schema.storage_tables())
                scan.parts_total = len(schema.partitions)
            for ci in out:
                self._scan_for[ci.id] = scan
            scope = Scope()
            scope.add(t.alias or t.name, cols)
            return scan, scope
        if isinstance(t, A.SubqueryRef):
            if isinstance(t.query, A.UnionStmt):
                plan, outs = self._bind_union(t.query)
            else:
                plan, outs = self._bind_select(t.query)
            scope = Scope()
            scope.add(t.alias, {c.name: c for c in outs})
            return plan, scope
        if isinstance(t, A.JoinRef):
            if t.kind == "full":
                return self._bind_full_join(t)
            lp, ls = self._bind_table_ref(t.left)
            rp, rs = self._bind_table_ref(t.right)
            merged = ls.merged(rs)
            if t.kind == "cross":
                return Join("cross", lp, rp, [], []), merged
            conjuncts = _split_and(t.on)
            eq, rest = _extract_equi(conjuncts, ls, rs)
            if not eq:
                raise SqlError("join requires at least one equality condition")
            lkeys = [self._expr(l, ls) for l, _ in eq]
            rkeys = [self._expr(r, rs) for _, r in eq]
            lkeys, rkeys = self._align_join_keys(lkeys, rkeys)
            residual = _join_and(rest)
            join = Join(t.kind, lp, rp, lkeys, rkeys,
                        residual=self._predicate(residual, merged) if residual else None)
            return join, merged
        raise SqlError(f"unsupported FROM item {type(t).__name__}")

    def _bind_full_join(self, t: A.JoinRef):
        """FULL OUTER JOIN as a union rewrite:
            A FULL JOIN B ON k  ==  (A LEFT JOIN B ON k)
                                    UNION ALL
                                    (NULL-extended B ANTI JOIN A ON k)
        Each side is bound twice (fresh column ids per instance); the two
        branches are positionally wired through a Union whose output columns
        carry the original table aliases so name resolution sees one joined
        scope. Matches nodeHashjoin.c's HJ_FILL_OUTER handling by plan shape
        rather than kernel state.
        """
        from greengage_tpu.planner.logical import Union

        conjuncts = _split_and(t.on)
        lp, ls = self._bind_table_ref(t.left)
        rp, rs = self._bind_table_ref(t.right)
        eq, rest = _extract_equi(conjuncts, ls, rs)
        if not eq:
            raise SqlError("join requires at least one equality condition")
        if rest:
            raise SqlError(
                "FULL JOIN supports only equality conditions in ON")
        lkeys = [self._expr(l, ls) for l, _ in eq]
        rkeys = [self._expr(r, rs) for _, r in eq]
        lkeys, rkeys = self._align_join_keys(lkeys, rkeys)
        branch1 = Join("left", lp, rp, lkeys, rkeys)

        # second instances for the anti branch (B rows with no A match)
        lp2, ls2 = self._bind_table_ref(t.left)
        rp2, rs2 = self._bind_table_ref(t.right)
        lkeys2 = [self._expr(l, ls2) for l, _ in eq]
        rkeys2 = [self._expr(r, rs2) for _, r in eq]
        rkeys2, lkeys2 = self._align_join_keys(rkeys2, lkeys2)
        branch2 = Join("anti", rp2, lp2, rkeys2, lkeys2)

        # flattened output: left cols then right cols, preserving alias
        # structure. (alias, name, branch-1 col, branch-2 col-or-None)
        slots = []
        for (a1, cols1), (a2, cols2) in zip(ls.tables, ls2.tables):
            for n, c in cols1.items():
                slots.append((a1, n, c, None))  # left side: NULL in branch 2
        for (a1, cols1), (a2, cols2) in zip(rs.tables, rs2.tables):
            for n, c in cols1.items():
                slots.append((a1, n, c, cols2[n]))

        union_cols = []
        b1_exprs, b2_exprs = [], []
        out_scope = Scope()
        per_alias: dict[str, dict[str, ColInfo]] = {}
        for alias, name, c1, c2 in slots:
            if c1.raw_ref is not None:
                raise SqlError(
                    "raw-encoded text is not supported in FULL JOIN")
            uc = ColInfo(self.new_id(name), c1.type, name, c1.dict_ref)
            union_cols.append(uc)
            per_alias.setdefault(alias, {})[name] = uc
            b1_exprs.append((ColInfo(self.new_id(name), c1.type, name,
                                     c1.dict_ref), _colref(c1)))
            e2 = (E.Literal(None, c1.type) if c2 is None else _colref(c2))
            b2_exprs.append((ColInfo(self.new_id(name), c1.type, name,
                                     c1.dict_ref), e2))
        for alias, cols in per_alias.items():
            out_scope.add(alias, cols)
        inputs = [Project(branch1, b1_exprs), Project(branch2, b2_exprs)]
        plan = Union(inputs, union_cols)
        plan.branch_ids = [[c.id for c, _ in p.exprs] for p in inputs]
        return plan, out_scope

    def _align_join_keys(self, lkeys, rkeys):
        """Type-align join key pairs; TEXT pairs from different dictionaries
        get a translation LUT on the right side."""
        out_l, out_r = [], []
        for lk, rk in zip(lkeys, rkeys):
            # raw TEXT join keys ride their transient dictionaries; the
            # cross-dictionary translation below then applies as usual
            if _raw_ref_of(lk) is not None:
                lk = self._raw_to_codes(lk)
            if _raw_ref_of(rk) is not None:
                rk = self._raw_to_codes(rk)
            lt, rt = lk.type, rk.type
            if lt.kind is T.Kind.TEXT and rt.kind is T.Kind.TEXT:
                ld = _dict_ref_of(lk)
                rd = _dict_ref_of(rk)
                if ld != rd and ld is not None and rd is not None:
                    left_dict = self.store.dictionary(*ld)
                    right_dict = self.store.dictionary(*rd)
                    lut = np.array(
                        [left_dict.lookup(v) for v in right_dict.values] + [-1],
                        dtype=np.int32,
                    )
                    tid = self._const(lut)
                    rk = E.Lut(rk, tid, type=T.TEXT)
                    # translated codes live in the LEFT dictionary's code
                    # space: motion/join hashing must use the left dict's
                    # hash LUT (code -1 = absent -> sentinel row)
                    object.__setattr__(rk, "_dict_ref", ld)
            elif lt != rt:
                common = T.promote(lt, rt)
                if lt != common:
                    lk = E.Cast(lk, common)
                if rt != common:
                    rk = E.Cast(rk, common)
            out_l.append(lk)
            out_r.append(rk)
        return out_l, out_r

    def _push_filters(self, plan, scope, conjuncts):
        """Bind WHERE conjuncts over a single FROM item, sinking each
        below any explicit-JOIN sides it alone references (see
        _sink_pred) — unsinkable conjuncts gather in one Filter on top."""
        rest = []
        for c in conjuncts:
            pred = self._predicate(c, scope)
            refs = _expr_col_ids(pred)
            sunk = False
            if refs:
                plan, sunk = _sink_pred(plan, pred, refs)
            if not sunk:
                rest.append(pred)
        if rest:
            plan = Filter(plan, rest[0] if len(rest) == 1
                          else E.BoolOp("and", tuple(rest)))
        return plan

    def _push_single_table(self, plan, scope, conds):
        mine, rest = [], []
        names = {c.name for c in scope.all_cols()} | {
            f"{a}.{n}" for a, cols in scope.tables for n in cols
        }
        for c in conds:
            refs = _name_refs(c)
            if refs and all(self._resolvable(r, scope) for r in refs):
                mine.append(c)
            else:
                rest.append(c)
        if mine:
            plan = Filter(plan, self._predicate(_join_and(mine), scope))
        return plan, rest

    def _resolvable(self, parts, scope) -> bool:
        try:
            scope.resolve(parts)
            return True
        except SqlError:
            return False

    # ------------------------------------------------------------------
    # aggregation
    # ------------------------------------------------------------------
    def _bind_aggregate(self, stmt, plan, scope):
        # 1. bind group key exprs
        group_exprs = []
        for g in stmt.group_by:
            if isinstance(g, A.Num):   # ordinal
                idx = int(g.text) - 1
                g = stmt.items[idx].expr
            group_exprs.append((g, self._expr(g, scope)))

        # 2. collect aggregate calls across select/having/order
        agg_nodes: list[A.FuncCall] = []

        seen_keys: dict[str, A.FuncCall] = {}

        def collect(n):
            if isinstance(n, A.FuncCall) and n.over is None and \
                    n.name in ("count", "sum", "avg", "min", "max"):
                # dedupe textually-identical aggregates (ORDER BY repeats)
                k = _ast_key(n)
                if k in seen_keys:
                    dup_map[id(n)] = seen_keys[k]
                else:
                    seen_keys[k] = n
                    agg_nodes.append(n)
                return
            for ch in _ast_children(n):
                collect(ch)

        dup_map: dict[int, A.FuncCall] = {}

        for it in stmt.items:
            collect(it.expr)
        if stmt.having is not None:
            collect(stmt.having)
        for oi in stmt.order_by:
            collect(oi.expr)

        # 3. build input projection: group keys + agg args
        proj: list[tuple[ColInfo, E.Expr]] = []
        key_cols: list[tuple[ColInfo, E.Expr]] = []
        for gast, ge in group_exprs:
            conv = self._raw_to_codes(ge)
            if conv is not None:
                ge = conv
            ci = ColInfo(self.new_id("g"), ge.type, _ast_name(gast), _dict_ref_of(ge))
            proj.append((ci, ge))
            key_cols.append((ci, E.ColRef(ci.id, ci.type)))

        aggs: list[tuple[ColInfo, E.Agg]] = []
        agg_map: dict[int, ColInfo] = {}
        distinct_args: list[ColInfo] = []
        for fc in agg_nodes:
            if fc.star:
                arg = None
                arg_ref = None
                atype = None
            else:
                ae = self._expr(fc.args[0], scope)
                if fc.name in ("min", "max"):
                    # raw text -> transient dictionary codes, then TEXT
                    # codes -> lexicographic rank space (first-seen codes
                    # don't order; ranks do and decode via the sorted
                    # dictionary)
                    conv = self._raw_to_codes(ae)
                    if conv is not None:
                        ae = conv
                    if ae.type.kind is T.Kind.TEXT:
                        ae = self._text_rank_expr(ae)
                    self._no_raw(ae, f"{fc.name}() argument")
                if fc.name != "count":
                    # count(chain) is fine (validity passes through); any
                    # value-dependent aggregate would sum surrogates
                    self._no_rawchain(ae, f"{fc.name}() argument")
                atype = ae.type
                ci_in = ColInfo(self.new_id("a_in"), ae.type, "arg", _dict_ref_of(ae))
                proj.append((ci_in, ae))
                arg_ref = E.ColRef(ci_in.id, ci_in.type)
                if _dict_ref_of(ae) is not None:
                    object.__setattr__(arg_ref, "_dict_ref", _dict_ref_of(ae))
            func = "count_star" if fc.star else fc.name
            rtype = E.agg_result_type(func, atype)
            agg = E.Agg(func, arg_ref, fc.distinct, rtype)
            # TEXT min/max results decode through the argument's (rank)
            # dictionary
            ci = ColInfo(self.new_id(func), rtype, func,
                         dict_ref=(_dict_ref_of(ae)
                                   if rtype.kind is T.Kind.TEXT and not fc.star
                                   else None))
            aggs.append((ci, agg))
            agg_map[id(fc)] = ci
            if fc.distinct:
                if fc.star:
                    raise SqlError("count(distinct *) is not valid")
                distinct_args.append(
                    ColInfo(ci_in.id, ci_in.type, ci_in.name, ci_in.dict_ref))

        if not agg_nodes and not group_exprs:
            # GROUP BY () with no aggregate calls (grouping-sets desugar
            # branch, forced_group): anchor the global one-row group with
            # an internal count(*) no output references — the executor's
            # scalar-aggregate path then applies unchanged
            synth = ColInfo(self.new_id("count"), T.INT64, "count")
            aggs.append((synth, E.Agg("count_star", None, False, T.INT64)))
        if not proj:
            dummy = ColInfo(self.new_id("one"), T.INT32, "one")
            proj.append((dummy, E.Literal(1, T.INT32)))
        plan = Project(plan, proj)
        plain_aggs = [(ci, a) for ci, a in aggs if not a.distinct]
        dist_aggs = [(ci, a) for ci, a in aggs if a.distinct]
        if dist_aggs and len(dist_aggs) > 1:
            raise SqlError(
                "multiple DISTINCT aggregates in one query are not "
                "supported yet")
        if dist_aggs and plain_aggs:
            # MIXED distinct + plain: split-and-rejoin (the reference plans
            # this with multiple agg levels): plan A aggregates the plain
            # functions, plan B dedupes the distinct argument then
            # aggregates it; A join B on the group keys reassembles one row
            # per group. Both branches share the projected input subtree.
            ci_d, agg_d = dist_aggs[0]
            dci = distinct_args[0]
            plan_a = Aggregate(plan, key_cols, plain_aggs)
            # NOTE the id invariant: an Aggregate's group-key exprs must
            # reference the SAME ids its key ColInfos carry, so the final
            # phase of a two-phase plan resolves them against the partial's
            # output. Both branches therefore reuse key_cols; the join's
            # duplicate output ids carry equal values by the join equality.
            dedupe = Aggregate(plan, list(key_cols) + [
                (dci, E.ColRef(dci.id, dci.type))], [])
            plan_b = Aggregate(
                dedupe,
                [(kc, E.ColRef(kc.id, kc.type)) for kc, _ in key_cols],
                [(ci_d, E.Agg(agg_d.func, E.ColRef(dci.id, dci.type),
                              False, agg_d.type))])
            if key_cols:
                # NULL-safe rejoin: GROUP BY treats NULL keys as one group,
                # but join equality drops NULLs — so each key joins as
                # (COALESCE(k, 0), k IS NULL) pairs, which match NULL
                # groups to each other and never collide with real zeros
                def null_safe(kc):
                    ref = _colref(kc)
                    coalesced = E.Case(
                        ((E.IsNull(ref), _zero_lit(kc.type)),), ref, kc.type)
                    if kc.dict_ref is not None:
                        # TEXT: codes hash through the dictionary LUT;
                        # code -1 hits the sentinel row
                        object.__setattr__(coalesced, "_dict_ref", kc.dict_ref)
                    return [coalesced, E.IsNull(ref)]

                lks = [e for kc, _ in key_cols for e in null_safe(kc)]
                rks = [e for kc, _ in key_cols for e in null_safe(kc)]
                lks, rks = self._align_join_keys(lks, rks)
                plan = Join("inner", plan_a, plan_b, lks, rks)
            else:
                one = E.Literal(1, T.INT32)
                plan = Join("inner", plan_a, plan_b, [one], [one])
        elif dist_aggs:
            # DISTINCT only: dedupe (group keys, arg) first, then aggregate
            # plain over the distinct combinations (the classic two-level
            # rewrite)
            dci = distinct_args[0]
            dedupe_keys = list(key_cols) + [
                (dci, E.ColRef(dci.id, dci.type))]
            plan = Aggregate(plan, dedupe_keys, [])
            ci, agg = dist_aggs[0]
            aggs = [(ci, E.Agg(agg.func, agg.arg, False, agg.type))]
            plan = Aggregate(plan, key_cols, aggs)
        else:
            plan = Aggregate(plan, key_cols, aggs)

        # 4. scope over agg outputs; rewrites: ast node -> ColInfo
        out_scope = Scope()
        cols = {}
        rewrites: dict = {}
        for (gast, _), (ci, _) in zip(group_exprs, key_cols):
            rewrites[_ast_key(gast)] = ci
            cols[ci.name] = ci
        for fc in agg_nodes:
            rewrites[id(fc)] = agg_map[id(fc)]
        for dup_id, canon in dup_map.items():
            rewrites[dup_id] = agg_map[id(canon)]
        out_scope.add("", cols)

        if stmt.having is not None:
            pred = self._rewritten_predicate(stmt.having, rewrites, scope)
            plan = Filter(plan, pred)
        return plan, out_scope, rewrites

    def _bind_select_items(self, stmt, scope, rewrites, allow_plain=False):
        sel_exprs: list[tuple[ColInfo, E.Expr]] = []
        for it in stmt.items:
            if isinstance(it.expr, A.Star):
                if rewrites and not allow_plain:
                    raise SqlError("* not allowed with GROUP BY")
                cols = (scope.table_cols(it.expr.table) if it.expr.table
                        else scope.all_cols())
                for c in cols:
                    ci = ColInfo(self.new_id(c.name), c.type, c.name, c.dict_ref,
                                 raw_ref=c.raw_ref,
                                 raw_chain=getattr(c, "raw_chain", None))
                    sel_exprs.append((ci, E.ColRef(c.id, c.type)))
                continue
            e = self._rewritten_expr(it.expr, rewrites, scope, allow_plain)
            e = self._text_literal_to_dict(e)
            name = it.alias or _ast_name(it.expr)
            if isinstance(e, E.RawChain) and e.type.kind is not T.Kind.TEXT:
                raise SqlError(
                    "numeric functions of raw-encoded text are only "
                    "supported in WHERE")
            ci = ColInfo(self.new_id(name), e.type, name, _dict_ref_of(e),
                         raw_ref=_raw_ref_of(e), raw_chain=_raw_chain_of(e))
            if _raw_chain_of(e):
                # projected raw-text chain: the surrogate decodes + applies
                # the chain per row at result finalize — a host fallback
                self._count_scalar(device=False)
            sel_exprs.append((ci, e))
        return scope, sel_exprs

    def _text_literal_to_dict(self, e: E.Expr) -> E.Expr:
        """A projected TEXT constant has no device representation of its
        own: lower it to code 0 of a one-entry derived dictionary (the
        same mechanism string-function results ride)."""
        if isinstance(e, E.Literal) and e.type.kind is T.Kind.TEXT \
                and isinstance(e.value, str):
            ref = self.store.derived_dictionary([e.value])
            lit = E.Literal(0, T.TEXT)
            object.__setattr__(lit, "_dict_ref", ref)
            return lit
        return e

    def _raw_to_codes(self, e: E.Expr):
        """Raw-TEXT expression -> dictionary-coded expression under the
        column's transient per-version dictionary (TableStore
        .raw_dictionary). This is how raw columns become usable as
        GROUP BY / ORDER BY / DISTINCT / join keys: the device sees int32
        codes with full dictionary services (hash LUTs, rank LUTs,
        translation, decode). Returns None when ``e`` is not raw."""
        rr = _raw_ref_of(e)
        if rr is None:
            return None
        base = e.arg if isinstance(e, E.RawChain) else e
        if not isinstance(base, E.ColRef) or base.name not in self._scan_for:
            raise SqlError(
                "raw-encoded text keys are only supported directly on "
                "base-table columns")
        scan = self._scan_for[base.name]
        vname = "@rc:" + rr[1]
        ref = self.store.raw_dictionary(rr[0], rr[1])
        coded: E.Expr = self._raw_aux_col(scan, vname, T.TEXT, dict_ref=ref)
        for step in (_raw_chain_of(e) or ()):
            from greengage_tpu.utils import strfuncs

            kind = strfuncs.SPECS[step[0]][2]
            coded = self._lower_str_step(coded, tuple(step), kind)
        return coded

    def _win_raw_key(self, e: E.Expr) -> E.Expr:
        """Raw-TEXT window partition/order keys re-code into the column's
        transient per-version dictionary (the same service ORDER BY uses,
        _raw_to_codes) — the device then sees bounded int32 codes with
        full dictionary services, so `ntile(4) over (order by
        raw_text_col)` rides the gather-free rank machinery instead of
        being rejected (or funneled) as raw."""
        conv = self._raw_to_codes(e)
        return conv if conv is not None else e

    def _win_order_key(self, e: E.Expr) -> E.Expr:
        """Dict-TEXT window order keys re-code into RANK space at bind
        time: ranks order lexicographically AND are small bounded ints,
        which lets the planner's in-place global ranking pack them
        (planner._ordered_global_spec) instead of funneling TEXT keys."""
        if e.type.kind is T.Kind.TEXT and _dict_ref_of(e) is not None \
                and not isinstance(e, E.RawChain) \
                and _raw_ref_of(e) is None:
            n = len(self.store.dictionary(*_dict_ref_of(e)))
            r = self._text_rank_expr(e)
            object.__setattr__(r, "_rank_space", True)
            # ranks span [0, n-1]; a power-of-two dictionary must not
            # burn an extra bit of the 64-bit packing budget
            object.__setattr__(r, "_rank_bits",
                               max((n - 1).bit_length(), 1))
            return r
        return e

    def _text_rank_expr(self, ae: E.Expr) -> E.Expr:
        """min/max over TEXT: first-seen dictionary codes do not order
        lexicographically, so re-code into rank space — a LUT onto the
        sorted dictionary, whose output dict_ref is the sorted values
        (ranks decode directly). Fixes min/max returning arbitrary
        first-seen strings."""
        d = _dict_ref_of(ae)
        if d is None:
            raise SqlError(
                "min/max over text requires a dictionary-backed column")
        dic = self.store.dictionary(*d)
        order = np.argsort(np.asarray(dic.values, dtype=object))
        rank = np.empty(len(order), dtype=np.int32)
        rank[order] = np.arange(len(order), dtype=np.int32)
        ref = self.store.derived_dictionary([dic.values[i] for i in order])
        lut = np.concatenate([rank, [np.int32(-1)]]).astype(np.int32)
        e = E.Lut(ae, self._const(lut), type=T.TEXT)
        object.__setattr__(e, "_dict_ref", ref)
        return e

    def _no_rawchain(self, e: E.Expr, what: str) -> E.Expr:
        # chain carriers are RawChain nodes OR ColRefs whose subquery
        # projection attached a chain (the surrogate decodes only at
        # finalize, so any value-consuming context would see garbage)
        if isinstance(e, E.RawChain) or _raw_chain_of(e) is not None:
            raise SqlError(
                f"string functions of raw-encoded text cannot be used in "
                f"{what} (supported: WHERE comparisons, output columns)")
        return e

    def _no_raw(self, e: E.Expr, what: str) -> E.Expr:
        if _raw_ref_of(e) is not None:
            raise SqlError(
                f"raw-encoded text cannot be used as a {what} (re-create "
                "the column as dictionary-encoded)")
        return e

    def _bind_order_expr(self, ast, proj_cols, scope):
        if isinstance(ast, A.Num) and re.fullmatch(r"\d+", ast.text):
            idx = int(ast.text) - 1
            if not 0 <= idx < len(proj_cols):
                raise SqlError(f"ORDER BY position {idx+1} out of range")
            c = proj_cols[idx]
            return _colref(c)
        if isinstance(ast, A.Name):
            # match output alias; qualified names fall back to the bare
            # column name (the projection renamed it on the way out)
            for c in proj_cols:
                if c.name == ast.parts[-1]:
                    return _colref(c)
        # expression over output columns
        s = Scope()
        s.add("", {c.name: c for c in proj_cols})
        try:
            return self._expr(ast, s)
        except SqlError:
            raise SqlError("ORDER BY must reference output columns")

    # ------------------------------------------------------------------
    # expression binding
    # ------------------------------------------------------------------
    def _predicate(self, ast, scope) -> E.Expr:
        e = self._expr(ast, scope)
        if e.type.kind is not T.Kind.BOOL:
            raise SqlError("predicate must be boolean")
        return e

    def _rewritten_expr(self, ast, rewrites, scope, allow_plain=False) -> E.Expr:
        if rewrites:
            hit = rewrites.get(id(ast)) or rewrites.get(_ast_key(ast))
            if hit is not None:
                return _colref(hit)
            if isinstance(ast, A.FuncCall) and ast.over is None:
                if ast.name in ("count", "sum", "avg", "min", "max"):
                    raise SqlError("unmatched aggregate")  # should be in rewrites
                # scalar function OVER aggregates: round(sum(x), 2)
                args = [self._rewritten_expr(a, rewrites, scope, allow_plain)
                        for a in ast.args]
                special = self._bind_device_scalar(ast.name, args)
                if special is not None:
                    return special
                from greengage_tpu.utils import strfuncs

                if ast.name in strfuncs.SPECS and ast.name != "concat":
                    return self._bind_string_func(ast.name, args)
                return self._typed_scalar_func(ast.name, len(ast.args), args)
            if isinstance(ast, A.Name):
                if allow_plain:
                    return self._expr(ast, scope)
                raise SqlError(
                    f'column "{".".join(ast.parts)}" must appear in GROUP BY')
            if isinstance(ast, (A.Num, A.Str, A.Null, A.Bool, A.DateLit,
                                A.ParamRef)):
                return self._expr(ast, scope)
            if isinstance(ast, A.ExtractExpr):
                # the standard EXTRACT(field FROM expr) spelling over
                # aggregate/group-key references
                return self._bind_extract(
                    ast.field,
                    self._rewritten_expr(ast.arg, rewrites, scope,
                                         allow_plain))
            clone = _ast_rebind(ast, lambda ch: self._rewritten_expr(
                ch, rewrites, scope, allow_plain))
            if clone is not None:
                return clone
            return self._expr(ast, scope)
        return self._expr(ast, scope)

    def _rewritten_predicate(self, ast, rewrites, scope) -> E.Expr:
        e = self._rewritten_expr(ast, rewrites, scope)
        if e.type.kind is not T.Kind.BOOL:
            raise SqlError("HAVING must be boolean")
        return e

    def _expr(self, ast, scope) -> E.Expr:
        if isinstance(ast, A.Name):
            c = scope.resolve(ast.parts)
            return _colref(c)
        if isinstance(ast, A.Num):
            if "." in ast.text:
                frac = len(ast.text.split(".")[1])
                return E.Literal(T.decimal_to_int(ast.text, frac), T.decimal(frac))
            v = int(ast.text)
            return E.Literal(v, T.literal_type(v))
        if isinstance(ast, A.ParamRef):
            # hoisted literal (sql/paramize.py): typed slot read from the
            # statement's parameter vector at execution; the hoisted value
            # rides along for ESTIMATION only (planner/cost.py) — the
            # generic plan is seeded by the statement that populated it
            p = E.Param(ast.idx, ast.ptype)
            if ast.est_value is not None:
                object.__setattr__(p, "_est_value", ast.est_value)
            return p
        if isinstance(ast, A.Str):
            return E.Literal(ast.value, T.TEXT)  # coerced by context
        if isinstance(ast, A.Null):
            return E.Literal(None, T.INT32)
        if isinstance(ast, A.TypedNullOf):
            if getattr(ast, "rtype", None) is None:
                raise SqlError("internal: TypedNullOf reached binding "
                               "without pre-resolution")
            lit = E.Literal(None, ast.rtype)
            if ast.rdict is not None:
                object.__setattr__(lit, "_dict_ref", ast.rdict)
            return lit
        if isinstance(ast, A.Bool):
            return E.Literal(ast.value, T.BOOL)
        if isinstance(ast, A.DateLit):
            return E.Literal(T.date_to_days(ast.value), T.DATE)
        if isinstance(ast, A.IntervalLit):
            raise SqlError("interval is only supported in date +/- interval")
        if isinstance(ast, A.ScalarSubquery):
            if self.subquery_executor is None:
                raise SqlError("scalar subqueries are not available here")
            value, t = self.subquery_executor(ast.query)
            return E.Literal(value, t)
        if isinstance(ast, A.ExistsExpr) or isinstance(ast, A.InSubquery):
            raise SqlError(
                "IN/EXISTS subqueries are only supported as top-level WHERE "
                "conjuncts")
        if isinstance(ast, A.Unary):
            if ast.op == "not":
                return E.Not(self._predicate(ast.arg, scope))
            a = self._expr(ast.arg, scope)
            if isinstance(a, E.Literal) and a.value is not None:
                return E.Literal(-a.value, a.type)
            return E.BinOp("-", E.Literal(0, a.type), a, a.type)
        if isinstance(ast, A.Bin):
            if ast.op == "||":
                return self._bind_concat(ast, scope)
            if ast.op in ("and", "or"):
                return E.BoolOp(ast.op, (self._predicate(ast.left, scope),
                                         self._predicate(ast.right, scope)))
            if ast.op in ("=", "<>", "<", "<=", ">", ">="):
                return self._bind_cmp(ast, scope)
            return self._bind_arith(ast, scope)
        if isinstance(ast, A.IsNullTest):
            return E.IsNull(self._expr(ast.arg, scope), ast.negate)
        if isinstance(ast, A.Between):
            arg = ast.arg
            lo = A.Bin(">=", arg, ast.lo)
            hi = A.Bin("<=", arg, ast.hi)
            e = E.BoolOp("and", (self._bind_cmp(lo, scope), self._bind_cmp(hi, scope)))
            return E.Not(e) if ast.negate else e
        if isinstance(ast, A.InExpr):
            arg = self._expr(ast.arg, scope)
            if _raw_ref_of(arg) is not None:
                vals = []
                for v in ast.values:
                    lit = self._expr(v, scope)
                    if not isinstance(lit, E.Literal):
                        raise SqlError("IN list must be literals")
                    vals.append(lit.value)
                if isinstance(arg, E.RawChain):
                    e = None
                    if vals and all(isinstance(v, str) for v in vals):
                        devs = []
                        for v in vals:
                            d0 = self._raw_strop(
                                arg, arg.chain, "cmp",
                                literal=v.encode("utf-8"))
                            if d0 is None:
                                devs = None
                                break
                            devs.append(d0)
                        if devs:
                            e = (devs[0] if len(devs) == 1
                                 else E.BoolOp("or", tuple(devs)))
                    if e is None:
                        e = self._host_pred(arg, {
                            "op": "chain",
                            "chain": [list(s) for s in arg.chain],
                            "cmp": "in", "value": vals})
                else:
                    e = None
                    if vals and all(self._device_raw_eq_ok(arg, v)
                                    for v in vals):
                        devs = [self._device_raw_pred(arg, "eq", v)
                                for v in vals]
                        # eq_ok pre-screens every value so no aux column
                        # stages for a list the host path ends up serving;
                        # the None check guards against the two predicates
                        # ever drifting apart
                        if all(d is not None for d in devs):
                            e = (devs[0] if len(devs) == 1
                                 else E.BoolOp("or", tuple(devs)))
                    if e is None:
                        e = self._host_pred(arg, {"op": "in", "values": vals})
                return E.Not(e) if ast.negate else e
            d = _dict_ref_of(arg) if arg.type.kind is T.Kind.TEXT else None
            dictionary = self.store.dictionary(*d) if d else None
            vals = []
            for v in ast.values:
                lit = self._expr(v, scope)
                if not isinstance(lit, E.Literal):
                    raise SqlError("IN list must be literals")
                if dictionary is not None:
                    vals.append(dictionary.lookup(lit.value))  # -1 = matches nothing
                else:
                    vals.append(self._coerce_literal(lit, arg.type).value)
            e = E.InList(arg, tuple(vals))
            return E.Not(e) if ast.negate else e
        if isinstance(ast, A.LikeExpr):
            arg = self._expr(ast.arg, scope)
            if arg.type.kind is not T.Kind.TEXT:
                raise SqlError("LIKE requires a text column")
            if isinstance(arg, E.RawChain):
                p = ast.pattern
                e = None
                if "_" not in p and "\\" not in p:
                    # chain + %-pattern: byte-op the chain's view, then
                    # RawLike's greedy matching inside it — all on device
                    e = self._raw_strop(
                        arg, arg.chain, "like",
                        parts=tuple(s.encode("utf-8")
                                    for s in p.split("%") if s),
                        anchored_start=not p.startswith("%"),
                        anchored_end=not p.endswith("%"))
                if e is None:
                    e = self._host_pred(arg, {
                        "op": "chain", "chain": [list(s) for s in arg.chain],
                        "cmp": "like", "value": ast.pattern})
                return E.Not(e) if ast.negate else e
            if _raw_ref_of(arg) is not None:
                p = ast.pattern
                e = None
                if (p.endswith("%") and "%" not in p[:-1] and "_" not in p
                        and "\\" not in p):
                    # pure prefix pattern: device integer compares
                    e = self._device_raw_pred(arg, "prefix", p[:-1])
                elif "%" not in p and "_" not in p and "\\" not in p:
                    # no wildcards at all: LIKE == equality
                    e = self._device_raw_pred(arg, "eq", p)
                if e is None and "_" not in p and "\\" not in p:
                    # general %-pattern (contains/suffix/multi-part):
                    # byte-matrix matching over the staged wide window
                    e = self._device_raw_like(arg, p)
                if e is None:
                    e = self._host_pred(arg,
                                        {"op": "like", "pattern": ast.pattern})
                return E.Not(e) if ast.negate else e
            d = _dict_ref_of(arg)
            if d is None:
                raise SqlError("LIKE requires a dictionary-backed column")
            dictionary = self.store.dictionary(*d)
            rx = _like_to_regex(ast.pattern)
            lut = np.array([bool(rx.fullmatch(v)) for v in dictionary.values] + [False])
            e = E.Lut(arg, self._const(lut), type=T.BOOL)
            return E.Not(e) if ast.negate else e
        if isinstance(ast, A.CaseExpr):
            whens = []
            vals = []
            for c, v in ast.whens:
                whens.append(self._predicate(c, scope))
                vals.append(self._no_rawchain(self._expr(v, scope),
                                              "CASE branches"))
            else_e = self._no_rawchain(self._expr(ast.else_, scope),
                                       "CASE branches") \
                if ast.else_ is not None else None
            out_t = vals[0].type
            for v in vals[1:]:
                out_t = T.promote(out_t, v.type)
            if else_e is not None and else_e.type != out_t:
                out_t = T.promote(out_t, else_e.type)
            return E.Case(tuple(zip(whens, vals)), else_e, out_t)
        if isinstance(ast, A.CastExpr):
            a = self._no_rawchain(self._expr(ast.arg, scope), "CAST")
            target = type_from_name(ast.type_name, ast.typmod)
            if isinstance(a, E.Literal):
                return self._coerce_literal(a, target)
            return E.Cast(a, target)
        if isinstance(ast, A.ExtractExpr):
            return self._bind_extract(ast.field, self._expr(ast.arg, scope))
        if isinstance(ast, A.FuncCall):
            if ast.name in ("count", "sum", "avg", "min", "max"):
                raise SqlError(f"aggregate {ast.name}() not allowed here")
            from greengage_tpu.utils import strfuncs

            special = self._bind_device_scalar(
                ast.name, [self._expr(a, scope) for a in ast.args])
            if special is not None:
                return special
            if ast.name in strfuncs.SPECS and ast.name != "concat":
                return self._bind_string_func(
                    ast.name, [self._expr(a, scope) for a in ast.args])
            return self._bind_scalar_func(ast, scope)
        raise SqlError(f"cannot bind {type(ast).__name__}")

    # ---- device scalar library (ops/scalar.py) -------------------------
    def _bind_extract(self, field: str, a: E.Expr) -> E.Expr:
        from greengage_tpu.ops import scalar as scalar_ops

        f = field.lower()
        if f not in scalar_ops.extract_fields():
            raise SqlError(f"extract({f}) unsupported")
        if a.type.kind is not T.Kind.DATE:
            raise SqlError("extract() requires a date")
        rt = scalar_ops.FIELD_RESULT[f]
        if isinstance(a, E.Literal):
            # constant-fold via the same civil algebra (1-row host eval)
            if a.value is None:
                return E.Literal(None, rt)
            return E.Literal(self._fold_func(E.Func(f"extract_{f}", (a,), rt)),
                             rt)
        self._count_scalar(device=True)
        return E.Func(f"extract_{f}", (a,), rt)

    def _fold_func(self, e: E.Func):
        """Evaluate a device scalar Func over literal args on the host (a
        1-row trace through the same registry implementation — bind-time
        constant folding that can never drift from device semantics)."""
        import jax.numpy as jnp
        import numpy as np_

        from greengage_tpu.ops import scalar as scalar_ops

        args = [(jnp.asarray([a.value], dtype=a.type.np_dtype), None)
                for a in e.args]
        v, _valid = scalar_ops.lookup(e.name).apply(e, args, 1)
        return np_.asarray(v)[0].item()

    def _bind_device_scalar(self, name: str, args: list) -> E.Expr | None:
        """Lower the non-strfuncs device scalar forms (ops/scalar.py):
        date_trunc/date_part, coalesce/nullif/greatest/least, and the
        DECIMAL-exact round/trunc/mod. -> None when ``name`` isn't one of
        them (caller falls through to strfuncs / the extension registry)."""
        name = name.lower()
        if name == "date_trunc":
            from greengage_tpu.ops import scalar as scalar_ops

            if len(args) != 2:
                raise SqlError("date_trunc() takes (field, date)")
            f = self._req_text_lit(args[0], "date_trunc() field").lower()
            if f not in scalar_ops.trunc_fields():
                raise SqlError(f"date_trunc({f!r}) unsupported")
            d = args[1]
            if d.type.kind is not T.Kind.DATE:
                raise SqlError("date_trunc() requires a date")
            e = E.Func("date_trunc", (d,), T.DATE, params=(f,))
            if isinstance(d, E.Literal):
                return (E.Literal(None, T.DATE) if d.value is None
                        else E.Literal(self._fold_func(e), T.DATE))
            self._count_scalar(device=True)
            return e
        if name == "date_part":
            if len(args) != 2:
                raise SqlError("date_part() takes (field, date)")
            return self._bind_extract(
                self._req_text_lit(args[0], "date_part() field"), args[1])
        if name == "coalesce":
            if not args:
                raise SqlError("coalesce() requires arguments")
            args = self._common_type(args, "coalesce")
            if len(args) == 1:
                return args[0]
            e = E.Func("coalesce", tuple(args), args[0].type)
            d = _dict_ref_of(args[0])
            if d is not None:
                object.__setattr__(e, "_dict_ref", d)
            self._count_scalar(device=True)
            return e
        if name == "nullif":
            if len(args) != 2:
                raise SqlError("nullif() takes two arguments")
            le, re_ = args
            # TEXT vs a literal ABSENT from the dictionary: equality can
            # never hold, so nullif folds to its first argument (coercing
            # through _coerce_pair would leave the -1 sentinel code,
            # which decodes to NULL — a silently wrong value)
            if isinstance(le, E.Literal) and isinstance(re_, E.Literal) \
                    and le.type.kind is T.Kind.TEXT \
                    and re_.type.kind is T.Kind.TEXT:
                if le.value is None or le.value == re_.value:
                    return E.Literal(None, T.TEXT)
                return self._text_literal_to_dict(le)
            for a, b in ((le, re_), (re_, le)):
                if isinstance(b, E.Literal) and isinstance(b.value, str) \
                        and b.type.kind is T.Kind.TEXT \
                        and _dict_ref_of(a) is not None \
                        and self.store.dictionary(
                            *_dict_ref_of(a)).lookup(b.value) < 0:
                    return self._text_literal_to_dict(le) \
                        if isinstance(le, E.Literal) else le
            le, re_ = self._coerce_pair(le, re_)
            e = E.Func("nullif", (le, re_), le.type)
            # a coerced first-argument literal carries codes in the OTHER
            # side's dictionary space — decode through that
            d = _dict_ref_of(le) or _dict_ref_of(re_)
            if d is not None and le.type.kind is T.Kind.TEXT:
                object.__setattr__(e, "_dict_ref", d)
            self._count_scalar(device=True)
            return e
        if name in ("greatest", "least"):
            if len(args) < 2:
                raise SqlError(f"{name}() requires at least two arguments")
            args = self._common_type(args, name)
            if args[0].type.kind is T.Kind.TEXT:
                raise SqlError(f"{name}() over text is not supported")
            self._count_scalar(device=True)
            return E.Func(name, tuple(args), args[0].type)
        if name in ("round", "trunc") and args \
                and args[0].type.kind is T.Kind.DECIMAL:
            if len(args) > 2:
                raise SqlError(f"{name}() takes at most two arguments")
            digits = 0
            if len(args) == 2:
                lit = args[1]
                if not isinstance(lit, E.Literal) or lit.type.kind not in (
                        T.Kind.INT32, T.Kind.INT64):
                    raise SqlError(
                        f"{name}() digits must be an integer literal")
                digits = int(lit.value)
            s = args[0].type.scale
            rt = T.decimal(max(digits, 0))
            self._count_scalar(device=True)
            return E.Func(f"{name}_dec", (args[0],), rt, params=(s, digits))
        if name == "mod" and len(args) == 2 and any(
                a.type.kind is T.Kind.DECIMAL for a in args):
            for a in args:
                if a.type.kind is T.Kind.DECIMAL:
                    continue
                if not a.type.is_integer:
                    raise SqlError("mod() over decimals takes numeric args")
            ls = args[0].type.scale if args[0].type.kind is T.Kind.DECIMAL else 0
            rs = args[1].type.scale if args[1].type.kind is T.Kind.DECIMAL else 0
            out = max(ls, rs)
            self._count_scalar(device=True)
            return E.Func("mod_dec", tuple(args), T.decimal(out),
                          params=(ls, rs, out))
        return None

    @staticmethod
    def _req_text_lit(e: E.Expr, what: str) -> str:
        if not (isinstance(e, E.Literal) and isinstance(e.value, str)):
            raise SqlError(f"{what} must be a string literal")
        return e.value

    def _common_type(self, args: list, fname: str) -> list:
        """Coerce a variadic argument list to one common type (coalesce /
        greatest / least): promote across numerics/dates, pin TEXT
        literals to the first dictionary-bearing argument's code space."""
        t = args[0].type
        for a in args[1:]:
            if a.type.kind is T.Kind.TEXT and t.kind is T.Kind.TEXT:
                continue
            t = T.promote(t, a.type)
        if t.kind is T.Kind.TEXT:
            args = [self._raw_to_codes(a) or a
                    if _raw_ref_of(a) is not None else a for a in args]
            d = next((x for x in (_dict_ref_of(a) for a in args)
                      if x is not None), None)
            if d is None:
                raise SqlError(
                    f"{fname}() over text requires a "
                    "dictionary-backed column argument")
            for a in args:
                if not isinstance(a, E.Literal) and _dict_ref_of(a) != d:
                    raise SqlError(
                        f"{fname}() over text columns from different "
                        "dictionaries is not supported")
            dic = self.store.dictionary(*d)
            lits = [a.value for a in args
                    if isinstance(a, E.Literal) and isinstance(a.value, str)]
            missing = [v for v in dict.fromkeys(lits) if dic.lookup(v) < 0]
            if missing:
                # a fallback literal ABSENT from the column's dictionary:
                # its -1 sentinel code would decode back to NULL — the
                # exact value coalesce exists to supply. Re-code every
                # argument into a derived dictionary that contains it.
                ref = self.store.derived_dictionary(
                    list(dic.values) + missing)
                dd = self.store.dictionary(*ref)
                trans = np.array([dd.lookup(v) for v in dic.values] + [-1],
                                 dtype=np.int32)
                tid = self._const(trans)
                d, dic = ref, dd
                out = []
                for a in args:
                    if isinstance(a, E.Literal):
                        out.append(a)
                    else:
                        lut = E.Lut(a, tid, type=T.TEXT)
                        object.__setattr__(lut, "_dict_ref", ref)
                        out.append(lut)
                args = out
            out = []
            for a in args:
                if isinstance(a, E.Literal) and a.value is not None \
                        and isinstance(a.value, str):
                    a = E.Literal(dic.lookup(a.value), T.TEXT)
                out.append(a)
            for a in out:
                object.__setattr__(a, "_dict_ref", d)
            return out
        out = []
        for a in args:
            if isinstance(a, E.Literal):
                out.append(self._coerce_literal(a, t))
            elif a.type != t:
                out.append(E.Cast(a, t))
            else:
                out.append(a)
        return out

    def _count_scalar(self, device: bool) -> None:
        from greengage_tpu.runtime.logger import counters

        if device:
            counters.inc("scalar_device_total")
        else:
            counters.inc("scalar_host_fallback_total")

    # ---- string functions ---------------------------------------------
    def _bind_string_func(self, name: str, args: list) -> E.Expr:
        """Lower a SQL string function; strategy depends on the subject's
        encoding — see utils/strfuncs.py. Extra arguments must be literals
        (the per-distinct-value/host-chain strategies evaluate them once)."""
        from greengage_tpu.utils import strfuncs

        lo, hi, kind = strfuncs.SPECS[name]
        if len(args) < lo or (hi is not None and len(args) > hi):
            raise SqlError(f"wrong number of arguments for {name}()")
        subject, extras = args[0], args[1:]
        lits = []
        for a in extras:
            if not isinstance(a, E.Literal):
                raise SqlError(
                    f"{name}(): arguments after the string must be literals")
            lits.append(a.value)
        if subject.type.kind is not T.Kind.TEXT:
            raise SqlError(f"{name}() requires a text argument")
        if name in ("substring", "substr") and len(lits) == 2 \
                and isinstance(lits[1], (int, float)) and lits[1] < 0:
            raise SqlError("negative substring length not allowed")
        return self._lower_str_step(subject, (name, *lits), kind)

    def _bind_concat(self, ast: A.Bin, scope) -> E.Expr:
        """x || y (textcat): flatten the chain; at most one non-literal
        part, folded into a ("concat", prefix, suffix) step around it."""
        parts: list[E.Expr] = []

        def flat(n):
            if isinstance(n, A.Bin) and n.op == "||":
                flat(n.left)
                flat(n.right)
            else:
                parts.append(self._expr(n, scope))

        flat(ast)
        rendered: list[str | None] = []
        subject_i = None
        for i, p in enumerate(parts):
            if isinstance(p, E.Literal):
                rendered.append(None if p.value is None
                                else _render_text(p))
            else:
                if subject_i is not None:
                    raise SqlError(
                        "|| supports at most one column operand (combine "
                        "literals around a single column)")
                subject_i = i
                rendered.append(None)
        if any(r is None and (subject_i != i)
               for i, r in enumerate(rendered)):
            # a NULL literal operand: || propagates NULL (textcat semantics)
            return E.Literal(None, T.TEXT)
        if subject_i is None:
            return E.Literal("".join(rendered), T.TEXT)
        subject = parts[subject_i]
        if subject.type.kind is not T.Kind.TEXT:
            raise SqlError("|| column operand must be text (use cast)")
        prefix = "".join(rendered[:subject_i])
        suffix = "".join(rendered[subject_i + 1:])
        if not prefix and not suffix:
            return subject
        return self._lower_str_step(subject, ("concat", prefix, suffix), "str")

    def _lower_str_step(self, subject: E.Expr, step: tuple, kind: str) -> E.Expr:
        """Apply one string-function step to a bound TEXT expression."""
        from greengage_tpu.utils import strfuncs

        if isinstance(subject, E.Literal):
            if subject.value is None:
                return E.Literal(None, T.TEXT if kind == "str" else T.INT32)
            try:
                v = strfuncs.apply(step[0], subject.value, *step[1:])
            except (ValueError, TypeError) as ex:
                raise SqlError(f"{step[0]}(): {ex}")
            return (E.Literal(v, T.TEXT) if kind == "str"
                    else E.Literal(int(v), T.INT32))
        if isinstance(subject, E.RawChain) or _raw_ref_of(subject) is not None:
            base = subject.arg if isinstance(subject, E.RawChain) else subject
            prev = _raw_chain_of(subject) or ()
            if kind == "int":
                # length(chain) over raw TEXT: the byte-window view's
                # length is a plain device int32 — usable in projections,
                # predicates, and aggregates with no host decode
                dev = self._raw_strop(subject, prev + (tuple(step),),
                                      "length")
                if dev is not None:
                    return dev
            t = T.TEXT if kind == "str" else T.INT32
            rc = E.RawChain(base, prev + (tuple(step),), t)
            object.__setattr__(rc, "_raw_ref", _raw_ref_of(subject))
            return rc
        d = _dict_ref_of(subject)
        if d is None:
            raise SqlError(
                f"{step[0]}() requires a text column or string literal")
        dic = self.store.dictionary(*d)
        try:
            outs = [strfuncs.apply(step[0], v, *step[1:])
                    for v in dic.values]
        except (ValueError, TypeError) as ex:
            raise SqlError(f"{step[0]}(): {ex}")
        self._count_scalar(device=True)   # dict LUT rides the fused program
        if kind == "int":
            lut = np.array(list(outs) + [0], dtype=np.int32)
            return E.Lut(subject, self._const(lut), type=T.INT32)
        dedup = list(dict.fromkeys(outs))
        ref = self.store.derived_dictionary(dedup)
        dd = self.store.dictionary(*ref)
        lut = np.array([dd.lookup(o) for o in outs] + [-1], dtype=np.int32)
        e = E.Lut(subject, self._const(lut), type=T.TEXT)
        object.__setattr__(e, "_dict_ref", ref)
        return e

    def _bind_scalar_func(self, ast: A.FuncCall, scope) -> E.Expr:
        """Resolve against the extension registry (pg_proc analog,
        reference: src/backend/parser/parse_func.c func_get_detail);
        overload resolution is by arity, coercion by declared signature."""
        return self._typed_scalar_func(
            ast.name, len(ast.args),
            [self._expr(a, scope) for a in ast.args])

    def _typed_scalar_func(self, name: str, nargs: int,
                           bound: list) -> E.Expr:
        from greengage_tpu import extensions as X

        spec = X.lookup(name, nargs)
        if spec is not None and spec.extension and \
                spec.extension not in getattr(self.catalog, "extensions", ()):
            # visibility follows THIS database's catalog, not process
            # import history (pg_proc is per-database)
            raise SqlError(f"unknown function {name}")
        if spec is None:
            ar = X.arities(name)
            if ar:
                raise SqlError(
                    f"function {name} takes "
                    f"{' or '.join(map(str, ar))} argument(s), got {nargs}")
            raise SqlError(f"unknown function {name}")
        args = [self._coerce_func_arg(a, want, name)
                for a, want in zip(bound, spec.arg_types)]
        rt = args[0].type if spec.result_type == "first" else spec.result_type
        return E.Func(spec.name, tuple(args), rt)

    @staticmethod
    def _coerce_func_arg(a: E.Expr, want: str, fname: str) -> E.Expr:
        k = a.type.kind
        num = (T.Kind.INT32, T.Kind.INT64, T.Kind.FLOAT64, T.Kind.DECIMAL)
        if want == "any":
            return a
        if want == "float64":
            if k is T.Kind.FLOAT64:
                return a
            if k in num:
                return E.Cast(a, T.FLOAT64)
        elif want == "int64":
            if k is T.Kind.INT64:
                return a
            if k is T.Kind.INT32:
                return E.Cast(a, T.INT64)
        elif want == "numeric":
            if k in num:
                return a
        elif want == "bool" and k is T.Kind.BOOL:
            return a
        elif want == "date" and k is T.Kind.DATE:
            return a
        raise SqlError(f"function {fname} expects {want}, got {a.type}")

    # ---- raw-text host predicates --------------------------------------
    def _raw_aux_col(self, scan, name: str, sqltype, dict_ref=None) -> E.Expr:
        """Reuse-or-append a virtual staged column on a scan (the shared
        mechanics of host predicates, device raw-prefix columns, and
        transient raw-dictionary codes)."""
        for c in scan.cols:
            if c.name == name:
                return _colref(c)
        ci = ColInfo(self.new_id("rp"), sqltype, name, dict_ref=dict_ref)
        scan.cols.append(ci)
        self._scan_for[ci.id] = scan
        return _colref(ci)

    def _device_raw_eq_ok(self, arg: E.Expr, value) -> bool:
        """Pure feasibility check for _device_raw_pred's eq lowering —
        callers with SEVERAL values (IN lists) must check them ALL before
        staging any aux column, or a partially-lowerable list leaves
        orphan prefix columns that disable zone-map pruning for nothing."""
        if isinstance(arg, E.RawChain) or not isinstance(arg, E.ColRef):
            return False
        if value is None or not isinstance(value, str):
            return False
        if _raw_ref_of(arg) is None or arg.name not in self._scan_for:
            return False
        from greengage_tpu.storage.table_store import RAW_PREFIX_BYTES

        return len(value.encode("utf-8")) <= RAW_PREFIX_BYTES

    def _device_raw_pred(self, arg: E.Expr, kind: str, value) -> E.Expr | None:
        """DEVICE lowering for raw-TEXT predicates (VERDICT r3 #7): the
        scan stages the column's packed 32-byte prefix (int64 lanes) and
        exact length, and equality / LIKE-'prefix%' compile to integer
        compares — one vectorized pass on the mesh instead of O(heap)
        host python per statement. None -> caller falls back to the host
        path (chains, long literals, general patterns).

        Soundness: utf-8 packing is big-endian per word with zero padding,
        so equal strings <=> equal (length, words); a literal longer than
        the prefix cap can never fully compare on device. LIKE prefixes
        mask the straddling word. Reference role: the varlena texteq /
        text_like fast paths (varlena.c), vectorized."""
        if isinstance(arg, E.RawChain) or not isinstance(arg, E.ColRef):
            return None
        if value is None or not isinstance(value, str):
            return None
        rr = _raw_ref_of(arg)
        if rr is None or arg.name not in self._scan_for:
            return None
        from greengage_tpu.storage.table_store import (RAW_PREFIX_BYTES,
                                                       RAW_PREFIX_WORDS)

        bts = value.encode("utf-8")
        if len(bts) > RAW_PREFIX_BYTES:
            return None
        scan = self._scan_for[arg.name]
        col = rr[1]
        rl = self._raw_aux_col(scan, f"@rl:{col}", T.INT32)

        def word_lit(chunk: bytes) -> int:
            return int.from_bytes(chunk.ljust(8, b"\0"), "big", signed=True)

        conj: list = []
        if kind == "eq":
            conj.append(E.Cmp("=", rl, E.Literal(len(bts), T.INT32), T.BOOL))
            # rows passing the exact-length check have zero padding beyond
            # their bytes, identical to the literal's padding — compare
            # every word the literal touches (others are zero on both
            # sides only up to the row's length... which equals the
            # literal's, so untouched words are zero for both)
            for w in range(RAW_PREFIX_WORDS):
                lit = word_lit(bts[w * 8:(w + 1) * 8])
                if w * 8 >= len(bts) and lit == 0:
                    break   # all remaining words are zero on both sides
                wcol = self._raw_aux_col(scan, f"@rp:{col}:{w}", T.INT64)
                conj.append(E.Cmp("=", wcol, E.Literal(lit, T.INT64), T.BOOL))
        elif kind == "prefix":
            conj.append(E.Cmp(">=", rl, E.Literal(len(bts), T.INT32), T.BOOL))
            full, rem = divmod(len(bts), 8)
            for w in range(full):
                wcol = self._raw_aux_col(scan, f"@rp:{col}:{w}", T.INT64)
                conj.append(E.Cmp(
                    "=", wcol, E.Literal(word_lit(bts[w * 8:(w + 1) * 8]),
                                         T.INT64), T.BOOL))
            if rem:
                mask = int.from_bytes(
                    (b"\xff" * rem).ljust(8, b"\0"), "big", signed=True)
                wcol = self._raw_aux_col(scan, f"@rp:{col}:{full}", T.INT64)
                masked = E.BinOp("&", wcol, E.Literal(mask, T.INT64), T.INT64)
                conj.append(E.Cmp(
                    "=", masked, E.Literal(word_lit(bts[full * 8:]),
                                           T.INT64), T.BOOL))
            if not conj:
                return None
        else:
            return None
        return conj[0] if len(conj) == 1 else E.BoolOp("and", tuple(conj))

    def _device_raw_like(self, arg: E.Expr, pattern: str) -> E.Expr | None:
        """GENERAL device LIKE for raw TEXT (VERDICT r4 #7): any pattern
        of literal parts separated by % lowers to byte-matrix matching
        over the staged RAW_WIDE_BYTES window (E.RawLike). Sound only
        when EVERY committed row fits the window — a longer row could
        match past it — so the column's exact max length gates the
        lowering; None falls back to the host path."""
        if isinstance(arg, E.RawChain) or not isinstance(arg, E.ColRef):
            return None
        rr = _raw_ref_of(arg)
        if rr is None or arg.name not in self._scan_for:
            return None
        from greengage_tpu.storage.table_store import (RAW_WIDE_BYTES,
                                                       RAW_WIDE_WORDS)

        parts = [s.encode("utf-8") for s in pattern.split("%") if s]
        if any(len(b) > RAW_WIDE_BYTES for b in parts):
            return None
        table, col = rr
        max_len = self.store.raw_max_len(table, col)
        if max_len > RAW_WIDE_BYTES:
            return None
        scan = self._scan_for[arg.name]
        rl = self._raw_aux_col(scan, f"@rl:{col}", T.INT32)
        # stage only the lanes the column's rows can occupy — matches can
        # never extend past max_len (the evaluator sizes W from the lanes)
        nlanes = min(max(-(-max_len // 8), 1), RAW_WIDE_WORDS)
        words = tuple(
            self._raw_aux_col(scan, f"@rw:{col}:{w}", T.INT64)
            for w in range(nlanes))
        return E.RawLike(
            words=words, length=rl, parts=tuple(parts),
            anchored_start=not pattern.startswith("%"),
            anchored_end=not pattern.endswith("%"))

    def _raw_strop(self, arg: E.Expr, steps: tuple, out: str,
                   **kw) -> E.Expr | None:
        """DEVICE lowering for scalar string-function chains over raw TEXT
        (the byte-op half of ops/scalar.py; docs/PERF.md "Scalar data-path
        fusion"): stage the column's wide byte window (@rw lanes + @rl
        length) and evaluate the chain + terminal op as elementwise work
        inside the fused program. None -> caller falls back to the host
        chain (counted in scalar_host_fallback_total). Gates:

        * the GUC scalar_device_enabled is on;
        * every chain step is byte-window-expressible (scalar.RAW_STEPS);
        * every committed row fits the staged window (raw_max_len — a
          longer row could match/measure past it);
        * the column is pure ASCII where the chain counts characters
          (upper/lower/substr/length — bytes == characters only then)."""
        from greengage_tpu.ops import scalar as scalar_ops
        from greengage_tpu.storage.table_store import (RAW_WIDE_BYTES,
                                                       RAW_WIDE_WORDS)

        if not self.scalar_device:
            return None
        base = arg.arg if isinstance(arg, E.RawChain) else arg
        rr = _raw_ref_of(arg)
        if rr is None or not isinstance(base, E.ColRef) \
                or base.name not in self._scan_for:
            return None
        ok, needs_ascii = scalar_ops.raw_steps_ok(steps)
        if not ok:
            return None
        table, col = rr
        if self.store.raw_max_len(table, col) > RAW_WIDE_BYTES:
            return None
        if needs_ascii and not self.store.raw_is_ascii(table, col):
            return None
        scan = self._scan_for[base.name]
        rl = self._raw_aux_col(scan, f"@rl:{col}", T.INT32)
        nlanes = min(max(-(-self.store.raw_max_len(table, col) // 8), 1),
                     RAW_WIDE_WORDS)
        words = tuple(
            self._raw_aux_col(scan, f"@rw:{col}:{w}", T.INT64)
            for w in range(nlanes))
        self._count_scalar(device=True)
        return E.RawStrOp(
            words=words, length=rl, steps=tuple(tuple(s) for s in steps),
            out=out, type=T.INT32 if out == "length" else T.BOOL, **kw)

    def _host_pred(self, arg: E.Expr, payload: dict) -> E.Expr:
        """Lower a predicate over a raw TEXT column into a host-evaluated
        boolean staged with the scan (the dictionary-LUT strategy at
        O(rows) host cost, cached per manifest version)."""
        rr = _raw_ref_of(arg)
        base = arg.arg if isinstance(arg, E.RawChain) else arg
        if not isinstance(base, E.ColRef) or base.name not in self._scan_for:
            raise SqlError(
                "predicates on raw-encoded text are only supported directly "
                "on base-table columns")
        if payload.get("op") == "chain":
            # a scalar function chain the device paths couldn't express:
            # the retained per-row host fallback, counted so the fused
            # coverage claim stays measurable
            self._count_scalar(device=False)
        scan = self._scan_for[base.name]
        name = self.store.host_pred_name(rr[1], payload)
        return self._raw_aux_col(scan, name, T.BOOL)

    # ---- comparisons with literal coercion ----------------------------
    def _bind_cmp(self, ast: A.Bin, scope) -> E.Expr:
        le = self._expr(ast.left, scope)
        re_ = self._expr(ast.right, scope)
        if (isinstance(le, E.Literal) and isinstance(re_, E.Literal)
                and le.type.kind is T.Kind.TEXT
                and re_.type.kind is T.Kind.TEXT):
            if le.value is None or re_.value is None:
                return E.Literal(None, T.BOOL)

            fn = {"=": operator.eq, "<>": operator.ne, "<": operator.lt,
                  "<=": operator.le, ">": operator.gt, ">=": operator.ge}
            return E.Literal(fn[ast.op](le.value, re_.value), T.BOOL)
        # raw TEXT comparisons evaluate on host (storage carries surrogates)
        for a, b, flipped in ((le, re_, False), (re_, le, True)):
            if _raw_ref_of(a) is None:
                continue
            if isinstance(a, E.RawChain):
                if not isinstance(b, E.Literal):
                    raise SqlError(
                        "raw-text function results compare only against "
                        "literals")
                op = ast.op
                if flipped:
                    op = {"<": ">", ">": "<", "<=": ">=", ">=": "<="}.get(op, op)
                if a.type.kind is T.Kind.TEXT:
                    if b.type.kind is not T.Kind.TEXT:
                        raise SqlError(
                            "raw-text function result compared to non-string")
                    val = b.value
                    if op in ("=", "<>") and isinstance(val, str):
                        dev = self._raw_strop(a, a.chain, "cmp",
                                              literal=val.encode("utf-8"))
                        if dev is not None:
                            return E.Not(dev) if op == "<>" else dev
                else:
                    if not isinstance(b.value, (int, float)):
                        raise SqlError(
                            "numeric string function compared to non-number")
                    val = b.value
                    if b.type.kind is T.Kind.DECIMAL:
                        # literals carry the scaled-int representation
                        val = b.value / 10 ** b.type.scale
                return self._host_pred(a, {
                    "op": "chain", "chain": [list(s) for s in a.chain],
                    "cmp": op, "value": val})
            if not (isinstance(b, E.Literal) and b.type.kind is T.Kind.TEXT
                    and ast.op in ("=", "<>")):
                raise SqlError(
                    "raw-encoded text supports only =/<> against string "
                    "literals, LIKE, and IN")
            e = self._device_raw_pred(a, "eq", b.value)
            if e is None:
                e = self._host_pred(a, {"op": "eq", "value": b.value})
            return E.Not(e) if ast.op == "<>" else e
        le, re_ = self._coerce_pair(le, re_)
        return E.Cmp(ast.op, le, re_)

    def _coerce_pair(self, le: E.Expr, re_: E.Expr):
        lt, rt = le.type, re_.type
        # unknown string literal adopts the other side's type
        if isinstance(re_, E.Literal) and rt.kind is T.Kind.TEXT and lt.kind is not T.Kind.TEXT:
            re_ = self._coerce_literal(re_, lt)
            rt = re_.type
        if isinstance(le, E.Literal) and lt.kind is T.Kind.TEXT and rt.kind is not T.Kind.TEXT:
            le = self._coerce_literal(le, rt)
            lt = le.type
        if lt.kind is T.Kind.TEXT and rt.kind is T.Kind.TEXT:
            # literal vs column: dictionary code; col vs col: translate dicts
            if isinstance(re_, E.Literal):
                d = _dict_ref_of(le)
                code = self.store.dictionary(*d).lookup(re_.value) if d else -1
                return le, E.Literal(code, T.TEXT)
            if isinstance(le, E.Literal):
                d = _dict_ref_of(re_)
                code = self.store.dictionary(*d).lookup(le.value) if d else -1
                return E.Literal(code, T.TEXT), re_
            ld, rd = _dict_ref_of(le), _dict_ref_of(re_)
            if ld != rd and ld is not None and rd is not None:
                left_dict = self.store.dictionary(*ld)
                right_dict = self.store.dictionary(*rd)
                lut = np.array(
                    [left_dict.lookup(v) for v in right_dict.values] + [-1],
                    dtype=np.int32)
                re_ = E.Lut(re_, self._const(lut), type=T.TEXT)
            return le, re_
        if lt == rt:
            return le, re_
        common = T.promote(lt, rt)
        if isinstance(le, E.Literal):
            le = self._coerce_literal(le, common)
        elif lt != common:
            le = E.Cast(le, common)
        if isinstance(re_, E.Literal):
            re_ = self._coerce_literal(re_, common)
        elif rt != common:
            re_ = E.Cast(re_, common)
        return le, re_

    def _coerce_literal(self, lit: E.Literal, target: T.SqlType) -> E.Literal:
        if lit.value is None:
            return E.Literal(None, target)
        if lit.type == target:
            return lit
        v = lit.value
        k = target.kind
        if lit.type.kind is T.Kind.TEXT:
            if k is T.Kind.TEXT:
                return lit
            try:
                return E.Literal(T.from_string(v, target), target)
            except ValueError as ex:
                raise SqlError(f"cannot coerce string literal to {target}: {ex}")
        if k is T.Kind.DECIMAL:
            if lit.type.kind is T.Kind.DECIMAL:
                from greengage_tpu.ops.expr_eval import _rescale_host
                return E.Literal(_rescale_host(v, lit.type.scale, target.scale), target)
            return E.Literal(int(v) * 10 ** target.scale, target)
        if k is T.Kind.FLOAT64:
            if lit.type.kind is T.Kind.DECIMAL:
                return E.Literal(v / 10 ** lit.type.scale, target)
            return E.Literal(float(v), target)
        if k in (T.Kind.INT32, T.Kind.INT64):
            return E.Literal(int(v), target)
        raise SqlError(f"cannot coerce {lit.type} literal to {target}")

    # ---- date +/- interval constant folding ---------------------------
    def _bind_arith(self, ast: A.Bin, scope) -> E.Expr:
        # date +/- interval: literal bases fold at bind time (calendar math
        # on host); column bases lower to device civil math (ops/scalar.py
        # add_months; day units are plain day arithmetic)
        if isinstance(ast.right, A.IntervalLit) and ast.op in ("+", "-"):
            base = self._expr(ast.left, scope)
            if base.type.kind is not T.Kind.DATE:
                raise SqlError("interval arithmetic requires a date")
            if isinstance(base, E.Literal):
                days = _apply_interval(base.value, ast.right, ast.op)
                return E.Literal(days, T.DATE)
            iv = ast.right
            n = int(iv.value)
            if ast.op == "-":
                n = -n
            if iv.unit.startswith("day"):
                return E.BinOp("+", base, E.Literal(n, T.INT32), T.DATE)
            if iv.unit.startswith("week"):
                return E.BinOp("+", base, E.Literal(7 * n, T.INT32), T.DATE)
            if iv.unit.startswith("month") or iv.unit.startswith("year"):
                months = n * (12 if iv.unit.startswith("year") else 1)
                self._count_scalar(device=True)
                return E.Func("add_months", (base,), T.DATE,
                              params=(months,))
            raise SqlError(f"interval unit {iv.unit} unsupported")
        le = self._expr(ast.left, scope)
        re_ = self._expr(ast.right, scope)
        self._no_rawchain(le, "arithmetic")
        self._no_rawchain(re_, "arithmetic")
        # unknown literal coercion mirrors comparison
        if isinstance(re_, E.Literal) and re_.type.kind is T.Kind.TEXT:
            re_ = self._coerce_literal(re_, le.type)
        if isinstance(le, E.Literal) and le.type.kind is T.Kind.TEXT:
            le = self._coerce_literal(le, re_.type)
        rtype = T.arith_result(ast.op, le.type, re_.type)
        return E.BinOp(ast.op, le, re_, rtype)

    def _const(self, arr: np.ndarray) -> str:
        tid = f"lut{len(self.consts)}"
        self.consts[tid] = arr
        return tid


# --------------------------------------------------------------------------
# helpers
# --------------------------------------------------------------------------

def _expr_col_ids(e) -> set:
    """Bound column ids a predicate references (generic expr walk)."""

    out: set = set()

    def walk(x):
        if isinstance(x, E.ColRef):
            out.add(x.name)
            return
        if isinstance(x, E.Expr):
            for f in dataclasses.fields(x):
                walk(getattr(x, f.name))
        elif isinstance(x, (tuple, list)):
            for y in x:
                walk(y)

    walk(e)
    return out


def _sink_pred(plan, pred, refs: set):
    """Push a bound conjunct below join nodes whose one side covers every
    referenced column: inner/cross sink either side, outer/semi/anti only
    the probe side (a WHERE pred on a left join's nullable side must stay
    above the join to reject null-extended rows). -> (plan, sunk?)."""
    if isinstance(plan, Filter):
        child, ok = _sink_pred(plan.child, pred, refs)
        if ok:
            plan.child = child
            return plan, True
        return plan, False
    if isinstance(plan, Join):
        lids = {c.id for c in plan.left.out_cols()}
        if refs <= lids:
            child, ok = _sink_pred(plan.left, pred, refs)
            plan.left = child if ok else _merge_filter(plan.left, pred)
            return plan, True
        if plan.kind in ("inner", "cross"):
            rids = {c.id for c in plan.right.out_cols()}
            if refs <= rids:
                child, ok = _sink_pred(plan.right, pred, refs)
                plan.right = child if ok else _merge_filter(plan.right, pred)
                return plan, True
    return plan, False


def _sink_semi(plan, semi):
    """Place a semi-join (IN / EXISTS, no residual) at the deepest input
    of the inner and cross joins under it whose columns cover its probe
    keys. A semi-join only removes rows of the side that owns its keys,
    so it commutes with every inner join and filter above that side, and
    the joins then see the rows it keeps instead of the whole table
    (TPC-H Q18: orders semi-joined on o_orderkey before it meets customer
    and lineitem). It stops above a base relation's own Filter: the
    planner's scan pushdown reads the Filter directly over a Scan."""
    refs = set()
    for k in semi.left_keys:
        refs |= _expr_col_ids(k)

    def place(node):
        inner = node
        while isinstance(inner, Filter):
            inner = inner.child
        if refs and isinstance(inner, Join) and inner.kind in ("inner", "cross"):
            for side in ("left", "right"):
                sub = getattr(inner, side)
                if refs <= {c.id for c in sub.out_cols()}:
                    setattr(inner, side, place(sub))
                    return node
        semi.left = node
        return semi

    return place(plan)


def _merge_filter(node, pred):
    """AND into an existing Filter rather than stacking a second one —
    the planner's scan-level pushdown (zone maps, direct dispatch) only
    inspects the Filter DIRECTLY above a Scan."""
    if isinstance(node, Filter):
        node.predicate = E.BoolOp("and", (node.predicate, pred))
        return node
    return Filter(node, pred)


_SUBST_FAIL = object()


def _subst_refs(e: E.Expr, mapping: dict):
    """Replace ColRefs (by id) with their mapped source expressions,
    rebuilding the tree; -> None when any part can't be rebuilt (caller
    keeps the original expression and its original constraints)."""
    def walk(v):
        if isinstance(v, E.ColRef):
            hit = mapping.get(v.name)
            return hit if hit is not None else v
        if isinstance(v, E.Expr):
            if not dataclasses.is_dataclass(v):
                return _SUBST_FAIL
            changes = {}
            for fld in dataclasses.fields(v):
                old = getattr(v, fld.name)
                new = walk(old)
                if new is _SUBST_FAIL:
                    return _SUBST_FAIL
                if new is not old:
                    changes[fld.name] = new
            if not changes:
                return v
            out = dataclasses.replace(v, **changes)
            for attr in ("_dict_ref", "_raw_ref", "_raw_chain",
                         "_rank_space", "_rank_bits"):
                if hasattr(v, attr):
                    object.__setattr__(out, attr, getattr(v, attr))
            return out
        if isinstance(v, tuple):
            outs = []
            for x in v:
                nx = walk(x)
                if nx is _SUBST_FAIL:
                    return _SUBST_FAIL
                outs.append(nx)
            return (tuple(outs) if any(a is not b for a, b in zip(outs, v))
                    else v)
        return v

    res = walk(e)
    return None if res is _SUBST_FAIL else res


def _colref(c: ColInfo) -> E.ColRef:
    e = E.ColRef(c.id, c.type)
    if c.dict_ref is not None:
        object.__setattr__(e, "_dict_ref", c.dict_ref)
    if c.raw_ref is not None:
        object.__setattr__(e, "_raw_ref", c.raw_ref)
    if getattr(c, "raw_chain", None):
        object.__setattr__(e, "_raw_chain", c.raw_chain)
    return e


def _raw_chain_of(e: E.Expr):
    if isinstance(e, E.RawChain):
        return e.chain
    return getattr(e, "_raw_chain", None)


def _render_text(lit: E.Literal) -> str:
    """Literal -> its SQL text form (|| operand rendering)."""
    t, v = lit.type, lit.value
    if t.kind is T.Kind.TEXT:
        return v
    if t.kind is T.Kind.DECIMAL:
        s = t.scale
        if not s:
            return str(v)
        sign = "-" if v < 0 else ""
        a = abs(v)
        return f"{sign}{a // 10**s}.{a % 10**s:0{s}d}"
    if t.kind is T.Kind.DATE:
        return (datetime.date(1970, 1, 1)
                + datetime.timedelta(days=v)).isoformat()
    if t.kind is T.Kind.BOOL:
        return "true" if v else "false"
    return str(v)


def _zero_lit(t: T.SqlType) -> E.Literal:
    if t.kind is T.Kind.TEXT:
        return E.Literal(-1, t)      # dictionary code space: -1 = absent
    if t.kind is T.Kind.FLOAT64:
        return E.Literal(0.0, t)
    if t.kind is T.Kind.BOOL:
        return E.Literal(False, t)
    return E.Literal(0, t)


def _dict_ref_of(e: E.Expr):
    return getattr(e, "_dict_ref", None)


def _raw_ref_of(e: E.Expr):
    return getattr(e, "_raw_ref", None)


_ORDERED_SET_AGGS = ("percentile_cont", "percentile_disc", "median")


def _contains_agg(ast) -> bool:
    if isinstance(ast, A.FuncCall) and ast.over is None and \
            ast.name in ("count", "sum", "avg", "min", "max",
                         *_ORDERED_SET_AGGS):
        return True
    return any(_contains_agg(c) for c in _ast_children(ast))


def _contains_count(ast) -> bool:
    if isinstance(ast, A.FuncCall) and ast.over is None and ast.name == "count":
        return True
    return any(_contains_count(c) for c in _ast_children(ast))


def _split_correlation(conjuncts, outer_scope, sub_scope):
    """Classify a subquery's WHERE conjuncts relative to the outer scope:
    -> (inner_only, corr_pairs [(outer_ast, inner_ast)], outer_only,
        residual, bad). ``residual`` = mixed-reference conjuncts that are
    NOT plain equality correlation (e.g. l2.suppkey <> l1.suppkey): they
    evaluate per candidate pair on the semi/anti join."""
    inner_only, corr_pairs, outer_only, residual, bad = [], [], [], [], []
    for c in conjuncts:
        refs = _name_refs(c)
        # innermost scope wins (SQL scoping): anything resolvable fully
        # inside the subquery is an inner predicate
        if not refs or all(_in_scope(p, sub_scope) for p in refs):
            inner_only.append(c)
            continue
        # equality with one side inner-resolvable and the other only
        # outer-resolvable = correlation (checked before outer_only so
        # tables appearing in both scopes classify as correlation)
        if isinstance(c, A.Bin) and c.op == "=":
            lrefs, rrefs = _name_refs(c.left), _name_refs(c.right)
            l_inner = lrefs and all(_in_scope(p, sub_scope) for p in lrefs)
            r_inner = rrefs and all(_in_scope(p, sub_scope) for p in rrefs)
            l_outer = lrefs and all(_in_scope(p, outer_scope) for p in lrefs)
            r_outer = rrefs and all(_in_scope(p, outer_scope) for p in rrefs)
            if l_inner and not r_inner and r_outer:
                corr_pairs.append((c.right, c.left))
                continue
            if r_inner and not l_inner and l_outer:
                corr_pairs.append((c.left, c.right))
                continue
        if refs and all(_in_scope(p, outer_scope) for p in refs):
            outer_only.append(c)
            continue
        if all(_in_scope(p, sub_scope) or _in_scope(p, outer_scope)
               for p in refs):
            residual.append(c)
            continue
        bad.append(c)
    return inner_only, corr_pairs, outer_only, residual, bad


def _contains_window(ast) -> bool:
    if isinstance(ast, A.FuncCall) and ast.over is not None:
        return True
    return any(_contains_window(c) for c in _ast_children(ast))


def _ast_children(ast):
    for f in ("left", "right", "arg", "lo", "hi", "else_", "query"):
        v = getattr(ast, f, None)
        if isinstance(v, A.ANode):
            yield v
    for v in getattr(ast, "args", []) or []:
        yield v
    for v in getattr(ast, "values", []) or []:
        if isinstance(v, A.ANode):
            yield v
    for c, v in getattr(ast, "whens", []) or []:
        yield c
        yield v


def _ast_key(ast) -> str:
    """Structural key for GROUP BY expression matching."""
    if isinstance(ast, A.Name):
        return "n:" + ".".join(ast.parts)
    if isinstance(ast, A.Num):
        return "#" + ast.text
    if isinstance(ast, A.Str):
        return "s:" + ast.value
    # every value-bearing attribute that changes semantics must enter the
    # key — a missed one silently MERGES distinct aggregates via dup_map
    # (e.g. sum(cast(x as bigint)) vs sum(cast(x as double precision)))
    parts = [type(ast).__name__, getattr(ast, "op", ""), getattr(ast, "name", ""),
             getattr(ast, "field", ""), getattr(ast, "type_name", ""),
             str(getattr(ast, "typmod", "")),
             str(getattr(ast, "negate", "")), str(getattr(ast, "distinct", "")),
             str(getattr(ast, "star", "")), str(getattr(ast, "desc", "")),
             str(getattr(ast, "value", "")), getattr(ast, "pattern", ""),
             getattr(ast, "unit", "")]
    for c in _ast_children(ast):
        parts.append(_ast_key(c))
    return "(" + " ".join(parts) + ")"


_PLAIN_AGGS = ("count", "sum", "avg", "min", "max")


def _has_grouping_call(n) -> bool:
    if isinstance(n, A.FuncCall) and n.name == "grouping" and n.over is None:
        return True
    return any(_has_grouping_call(c) for c in _ast_children(n))


def _contains_grouping(stmt) -> bool:
    return any(_has_grouping_call(it.expr) for it in stmt.items) or (
        stmt.having is not None and _has_grouping_call(stmt.having)) or any(
        _has_grouping_call(oi.expr) for oi in stmt.order_by)


def _gs_rewrite(node, present: set, universe: set):
    """Grouping-sets branch rewrite: keys absent from this set become
    TypedNullOf, grouping(...) folds to its per-branch bitmask constant
    (PG bit order: first argument = most significant). Aggregate arguments
    are left untouched — they see real rows, not key NULLs."""
    if not isinstance(node, A.ANode):
        if isinstance(node, list):
            return [_gs_rewrite(v, present, universe) for v in node]
        if isinstance(node, tuple):
            return tuple(_gs_rewrite(v, present, universe) for v in node)
        return node
    if isinstance(node, A.SelectStmt):
        return node
    if isinstance(node, A.FuncCall) and node.over is None:
        if node.name == "grouping":
            if not node.args:
                raise SqlError("grouping() requires arguments")
            mask = 0
            n = len(node.args)
            for i, a in enumerate(node.args):
                k = _ast_key(a)
                if k not in universe:
                    raise SqlError(
                        "grouping() arguments must be grouping keys")
                if k not in present:
                    mask |= 1 << (n - 1 - i)
            return A.Num(str(mask))
        if node.name in _PLAIN_AGGS or node.name in _ORDERED_SET_AGGS:
            # aggregate args (incl. WITHIN GROUP order exprs) see real
            # rows, never key NULLs
            return node
    k = _ast_key(node)
    if k in universe:
        return node if k in present else A.TypedNullOf(node)
    for f in dataclasses.fields(node):
        setattr(node, f.name,
                _gs_rewrite(getattr(node, f.name), present, universe))
    return node


def _ast_rebind(ast, rec):
    """Rebuild scalar AST nodes whose children may contain agg/key refs."""
    def cmp(op, l, r):
        lt, rt = l.type, r.type
        if lt != rt:
            common = T.promote(lt, rt)
            if lt != common:
                l = E.Cast(l, common)
            if rt != common:
                r = E.Cast(r, common)
        return E.Cmp(op, l, r)

    if isinstance(ast, A.Between):
        # HAVING-over-aggregate ratios (TPC-DS Q21): BETWEEN desugars to
        # the two comparisons here, the same as plain-expression binding
        arg, lo, hi = rec(ast.arg), rec(ast.lo), rec(ast.hi)
        e = E.BoolOp("and", (cmp(">=", arg, lo), cmp("<=", arg, hi)))
        return E.Not(e) if ast.negate else e
    if isinstance(ast, A.Bin):
        l = rec(ast.left)
        r = rec(ast.right)
        if ast.op in ("and", "or"):
            return E.BoolOp(ast.op, (l, r))
        if ast.op in ("=", "<>", "<", "<=", ">", ">="):
            return cmp(ast.op, l, r)
        return E.BinOp(ast.op, l, r, T.arith_result(ast.op, l.type, r.type))
    if isinstance(ast, A.Unary) and ast.op == "-":
        a = rec(ast.arg)
        return E.BinOp("-", E.Literal(0, a.type), a, a.type)
    if isinstance(ast, A.IsNullTest):
        return E.IsNull(rec(ast.arg), ast.negate)
    if isinstance(ast, A.CaseExpr):
        # CASE over aggregate results (the stat-agg expansion emits these:
        # negative-residue clamps, pairwise NULL restriction)
        whens = [(rec(c), rec(v)) for c, v in ast.whens]
        else_e = rec(ast.else_) if ast.else_ is not None else None
        out_t = whens[0][1].type
        for _, v in whens[1:]:
            out_t = T.promote(out_t, v.type)
        if else_e is not None and else_e.type != out_t:
            out_t = T.promote(out_t, else_e.type)
        return E.Case(tuple(whens), else_e, out_t)
    if isinstance(ast, A.CastExpr):
        return E.Cast(rec(ast.arg), type_from_name(ast.type_name, ast.typmod))
    return None


def _name_refs(ast) -> list[tuple[str, ...]]:
    out = []
    if isinstance(ast, A.Name):
        out.append(ast.parts)
    for c in _ast_children(ast):
        out.extend(_name_refs(c))
    return out


def _split_and(ast) -> list:
    if ast is None:
        return []
    if isinstance(ast, A.Bin) and ast.op == "and":
        return _split_and(ast.left) + _split_and(ast.right)
    return [ast]


def _join_and(conjuncts: list):
    if not conjuncts:
        return None
    e = conjuncts[0]
    for c in conjuncts[1:]:
        e = A.Bin("and", e, c)
    return e


def _extract_equi(conjuncts, lscope, rscope):
    """Partition conjuncts into equi-join pairs (lhs from lscope, rhs from
    rscope) and the rest."""
    eq, rest = [], []

    def side(parts):
        inl = _in_scope(parts, lscope)
        inr = _in_scope(parts, rscope)
        if inl and not inr:
            return "l"
        if inr and not inl:
            return "r"
        return None

    for c in conjuncts:
        if isinstance(c, A.Bin) and c.op == "=":
            lrefs = _name_refs(c.left)
            rrefs = _name_refs(c.right)
            if lrefs and rrefs:
                lsides = {side(p) for p in lrefs}
                rsides = {side(p) for p in rrefs}
                if lsides == {"l"} and rsides == {"r"}:
                    eq.append((c.left, c.right))
                    continue
                if lsides == {"r"} and rsides == {"l"}:
                    eq.append((c.right, c.left))
                    continue
        rest.append(c)
    return eq, rest


def _in_scope(parts, scope) -> bool:
    try:
        scope.resolve(parts)
        return True
    except SqlError:
        return False


def _ast_name(ast) -> str:
    if isinstance(ast, A.Name):
        return ast.parts[-1]
    if isinstance(ast, A.FuncCall):
        return ast.name
    if isinstance(ast, A.ExtractExpr):
        return ast.field
    return "?column?"


def _like_to_regex(pattern: str) -> "re.Pattern":
    return T.like_to_regex(pattern)


def _apply_interval(days: int, iv: A.IntervalLit, op: str) -> int:
    n = int(iv.value)
    if op == "-":
        n = -n
    d = np.datetime64("1970-01-01", "D") + np.timedelta64(days, "D")
    if iv.unit.startswith("day"):
        d = d + np.timedelta64(n, "D")
    elif iv.unit.startswith("week"):
        d = d + np.timedelta64(7 * n, "D")
    elif iv.unit.startswith("month"):
        m = d.astype("datetime64[M]") + np.timedelta64(n, "M")
        dom = (d - d.astype("datetime64[M]")).astype(int)
        d = m + np.timedelta64(dom, "D")
    elif iv.unit.startswith("year"):
        m = d.astype("datetime64[M]") + np.timedelta64(12 * n, "M")
        dom = (d - d.astype("datetime64[M]")).astype(int)
        d = m + np.timedelta64(dom, "D")
    else:
        raise SqlError(f"interval unit {iv.unit} unsupported")
    return int((d - np.datetime64("1970-01-01", "D")).astype(int))


# --------------------------------------------------------------------------
# scan pruning (projection pushdown to storage)
# --------------------------------------------------------------------------

def _collect_needed(plan: Plan, needed: set):
    from greengage_tpu.planner.logical import Motion, Window

    if isinstance(plan, Window):
        for e in plan.partition_keys:
            needed.update(E.columns_used(e))
        for e, _, _ in plan.order_keys:
            needed.update(E.columns_used(e))
        for _, _, arg, *_ in plan.wfuncs:
            if arg is not None:
                needed.update(E.columns_used(arg))
    if isinstance(plan, Project):
        for _, e in plan.exprs:
            needed.update(E.columns_used(e))
    elif isinstance(plan, Filter):
        needed.update(E.columns_used(plan.predicate))
    elif isinstance(plan, Join):
        for e in plan.left_keys + plan.right_keys:
            needed.update(E.columns_used(e))
        if plan.residual is not None:
            needed.update(E.columns_used(plan.residual))
        if plan.kind in ("inner", "left", "cross"):
            pass
    elif isinstance(plan, Aggregate):
        for _, e in plan.group_keys:
            needed.update(E.columns_used(e))
        for _, a in plan.aggs:
            if a.arg is not None:
                needed.update(E.columns_used(a.arg))
    elif isinstance(plan, Sort):
        for e, _, _ in plan.keys:
            needed.update(E.columns_used(e))
    elif isinstance(plan, Motion):
        for e in plan.hash_exprs:
            needed.update(E.columns_used(e))
    for c in plan.children:
        _collect_needed(c, needed)


def _prune_scans(plan: Plan, needed: set):
    for c in plan.children:
        _prune_scans(c, needed)
    if isinstance(plan, Scan):
        kept = [c for c in plan.cols if c.id in needed]
        if not kept:
            kept = plan.cols[:1]   # keep one column for row counting
        plan.cols = kept
