"""Literal parameterization — the prepared-statement / generic-plan pass.

A serving workload is dominated by repeated query *shapes* with varying
literals ("dashboard queries"). Today's plan cache keys on ``repr(stmt)``
— which embeds literal values — and the evaluator bakes each literal into
the traced program, so ``WHERE x > 5`` vs ``WHERE x > 6`` each pay a full
re-plan plus a multi-second XLA compile. This pass is the
plancache.c/prepared-statement analog: it walks a SELECT-shaped AST,
hoists plan-safe literals into an ordered parameter vector, and replaces
them with typed ``A.ParamRef`` nodes. The literal-stripped statement repr
(plus the hoisted literals' exact types) becomes the plan-cache key; the
values travel separately and feed the compiled program as traced scalar
inputs (ops/expr_eval.Evaluator._eval_param).

Safe/unsafe classification (docs/PERF.md "Plan cache"):

- **Hoistable**: numeric and date literals in comparisons, arithmetic,
  BETWEEN bounds, CASE branches, and extract() arguments. Zone-map prune
  predicates built over hoisted literals keep working: the planner records
  the Param in the pushed predicate and the executor substitutes the
  current value at staging time (the value affects which blocks are READ,
  never the compiled program).
- **Pinned** (stay literal, values in the cache key): everything whose
  value feeds a *plan-time* decision or a bind-time rewrite —
  - string literals (dictionary-code lookup, LIKE lowering, raw-text
    word-compare rewrites are all bind-time value rewrites);
  - any comparison against a partition key (static partition pruning
    changes the staged input spec and capacities);
  - any comparison against ``extract(year from col)`` (the planner
    derives zone-map day bounds from the year value at plan time);
  - equality against a hash-distribution key (direct dispatch pins the
    scan to one segment in the input spec);
  - IN lists, string-function arguments, CAST operands, interval
    arithmetic (the binder folds/validates these as literals);
  - LIMIT/OFFSET counts (plain AST ints — naturally part of the repr);
  - anything inside GROUP BY / ORDER BY / window specs (positional
    references, group-key matching by AST shape) or nested subqueries
    (bound by a separate pass).

A shape the binder still cannot parameterize (e.g. raw-text predicates)
raises at bind time; the session falls back to the classic value-pinned
plan under the full-repr key — correctness never depends on this pass.
"""

from __future__ import annotations

import copy
from dataclasses import dataclass

from greengage_tpu import types as T
from greengage_tpu.sql import ast as A


@dataclass(frozen=True)
class ParamVector:
    """One statement execution's hoisted literal values, slot-ordered.
    ``types`` are the exact SqlTypes the literals would have bound to —
    they are part of the plan-cache key, the values are not. Travels in
    the plan's consts dict under the reserved "@params@" key."""

    values: tuple
    types: tuple


def coerce_storage_value(v, ft, tt):
    """Numeric storage-representation coercion of a hoisted value from
    type ``ft`` to ``tt`` — the host mirror of Binder._coerce_literal's
    numeric branches, so runtime-resolved values match exactly what a
    pinned literal would have bound to."""
    if ft == tt:
        return v
    if tt.kind is T.Kind.DECIMAL:
        if ft.kind is T.Kind.DECIMAL:
            from greengage_tpu.ops.expr_eval import _rescale_host

            return _rescale_host(v, ft.scale, tt.scale)
        return int(v) * 10 ** tt.scale
    if tt.kind is T.Kind.FLOAT64:
        if ft.kind is T.Kind.DECIMAL:
            return v / 10 ** ft.scale
        return float(v)
    if tt.kind in (T.Kind.INT32, T.Kind.INT64):
        return int(v)
    return v


def resolve_param_value(expr, vec: ParamVector):
    """Concrete storage value of a prune-predicate operand built over a
    hoisted parameter — a bare expr.Param or the binder's numeric
    coercion Cast around one (planner._param_value) — so staging-time
    zone-map / block-index probes see exactly the value a pinned literal
    would have bound to."""
    from greengage_tpu import expr as E

    if isinstance(expr, E.Param):
        return vec.values[expr.slot]
    assert isinstance(expr, E.Cast) and isinstance(expr.arg, E.Param)
    return coerce_storage_value(vec.values[expr.arg.slot],
                                expr.arg.type, expr.type)


def _literal_of(node):
    """Mirror of Binder._expr literal construction: the (value, type) the
    binder would produce for this AST literal, in storage representation.
    None when the node is not a hoistable literal."""
    if isinstance(node, A.Num):
        if "." in node.text:
            frac = len(node.text.split(".")[1])
            return T.decimal_to_int(node.text, frac), T.decimal(frac)
        v = int(node.text)
        return v, T.literal_type(v)
    if isinstance(node, A.DateLit):
        return T.date_to_days(node.value), T.DATE
    if isinstance(node, A.Unary) and node.op == "-":
        inner = _literal_of(node.arg)
        if inner is None or isinstance(node.arg, A.Unary):
            return None
        v, t = inner
        # the binder folds unary minus keeping the POSITIVE literal's type
        return -v, t
    return None


class _Paramizer:
    def __init__(self, catalog, subquery_executor=None):
        self.params: list[tuple] = []   # (value, SqlType)
        # runs an uncorrelated scalar subquery -> (storage value, SqlType);
        # None leaves subqueries to the binder, which inlines them
        self.subquery_executor = subquery_executor
        # column names whose comparisons stay pinned: partition keys for
        # every op (static partition pruning is a plan-time decision),
        # hash-distribution keys for equality (direct dispatch). Matching
        # is by unqualified column name across the statement's base
        # tables — over-pinning is a perf loss, never a correctness one.
        self.pin_all: set[str] = set()
        self.pin_eq: set[str] = set()
        self.catalog = catalog

    def collect_tables(self, stmt) -> None:
        for ref in getattr(stmt, "from_", ()) or ():
            self._collect_ref(ref)

    def _collect_ref(self, ref) -> None:
        if isinstance(ref, A.JoinRef):
            self._collect_ref(ref.left)
            self._collect_ref(ref.right)
            return
        if not isinstance(ref, A.BaseTable):
            return
        try:
            schema = self.catalog.get(ref.name)
        except Exception:
            return
        if getattr(schema, "partition_by", None) is not None:
            self.pin_all.add(schema.partition_by[1])
        for k in getattr(schema.policy, "keys", ()) or ():
            self.pin_eq.add(k)

    # ------------------------------------------------------------------
    def _hoist(self, node):
        lit = _literal_of(node)
        if lit is None and self.subquery_executor is not None \
                and isinstance(node, A.ScalarSubquery):
            # a correlated one raises at its bind: paramize() then hands
            # the statement back whole and the binder decorrelates it
            lit = self.subquery_executor(node.query)
            if lit[0] is None:
                return node   # NULL stays the binder's typed literal
        if lit is None:
            return node
        v, t = lit
        if t.kind is T.Kind.TEXT or isinstance(v, bool):
            return node
        idx = len(self.params)
        self.params.append((v, t))
        return A.ParamRef(idx, t, est_value=v)

    def _pinned_name(self, node, op: str) -> bool:
        """Is ``node`` an operand whose comparisons must stay literal?"""
        if isinstance(node, A.ExtractExpr) and node.field.lower() == "year" \
                and isinstance(node.arg, A.Name):
            # extract(year from col) <op> literal: the planner derives
            # zone-map day bounds on the base column from the literal at
            # plan time (planner._year_prune) — hoisting the year would
            # make the TPC-DS date-filter pruning inert, so it stays in
            # the cache key like partition-key comparisons do
            return True
        if not isinstance(node, A.Name):
            return False
        name = node.parts[-1]
        if name in self.pin_all:
            return True
        return op == "=" and name in self.pin_eq

    def expr(self, node):
        """Rewrite one scalar expression tree in place; returns the
        (possibly replaced) node."""
        if node is None or not isinstance(node, A.ANode):
            return node
        if isinstance(node, A.Bin):
            if node.op in ("=", "<>", "<", "<=", ">", ">="):
                # a literal facing a pinned column stays pinned; the
                # opposite operand still rewrites normally
                if not self._pinned_name(node.left, node.op):
                    node.right = self._rw_operand(node.right)
                if not self._pinned_name(node.right, node.op):
                    node.left = self._rw_operand(node.left)
                return node
            if node.op in ("and", "or"):
                node.left = self.expr(node.left)
                node.right = self.expr(node.right)
                return node
            if node.op == "||" or isinstance(node.right, A.IntervalLit):
                # concat needs literals; date +/- interval folds at bind
                return node
            if node.op in ("+", "-", "*", "/", "%"):
                node.left = self._rw_operand(node.left)
                node.right = self._rw_operand(node.right)
                return node
            return node
        if isinstance(node, A.Unary):
            if node.op == "not":
                node.arg = self.expr(node.arg)
            # unary minus over a literal is handled by _rw_operand at the
            # parent; a bare `-x` recurses
            elif _literal_of(node) is None:
                node.arg = self.expr(node.arg)
            return node
        if isinstance(node, A.Between):
            node.arg = self.expr(node.arg)
            if not self._pinned_name(node.arg, "<"):
                node.lo = self._rw_operand(node.lo)
                node.hi = self._rw_operand(node.hi)
            return node
        if isinstance(node, A.IsNullTest):
            node.arg = self.expr(node.arg)
            return node
        if isinstance(node, A.InExpr):
            node.arg = self.expr(node.arg)   # values must stay literal
            return node
        if isinstance(node, A.LikeExpr):
            node.arg = self.expr(node.arg)   # pattern is a str field
            return node
        if isinstance(node, A.CaseExpr):
            node.whens = [(self.expr(c), self._rw_operand(v))
                          for c, v in node.whens]
            if node.else_ is not None:
                node.else_ = self._rw_operand(node.else_)
            return node
        if isinstance(node, A.ExtractExpr):
            node.arg = self.expr(node.arg)
            return node
        # pinned wholesale: FuncCall args (string funcs demand literals,
        # aggregates key group matching on AST shape), CastExpr (the
        # binder folds literal casts), subqueries (bound separately),
        # window specs, IntervalLit, Str/Null/Bool and bare literals in
        # non-expression positions
        return node

    def _rw_operand(self, node):
        """An operand position where a literal is hoistable."""
        rep = self._hoist(node)
        if rep is not node:
            return rep
        return self.expr(node)

    # ------------------------------------------------------------------
    def select(self, stmt: A.SelectStmt) -> None:
        self.collect_tables(stmt)
        if stmt.where is not None:
            stmt.where = self.expr(stmt.where)
        # grouped statements: the binder matches GROUP BY keys to select
        # items by AST shape — hoisting on one side only would break the
        # match, so grouped targetlists/HAVING stay pinned
        if not stmt.group_by and not stmt.grouping_sets \
                and not stmt.forced_group:
            for it in stmt.items:
                if not isinstance(it.expr, A.Star):
                    it.expr = self._rw_operand(it.expr)
            if stmt.having is not None:
                stmt.having = self.expr(stmt.having)
        for ref in stmt.from_:
            self._join_on(ref)

    def _join_on(self, ref) -> None:
        if isinstance(ref, A.JoinRef):
            if ref.on is not None:
                ref.on = self.expr(ref.on)
            self._join_on(ref.left)
            self._join_on(ref.right)


def paramize(stmt, catalog, subquery_executor=None):
    """-> (normalized stmt, ParamVector, signature) for SELECT-shaped
    statements, or (stmt, None, None) when nothing was hoisted. The
    normalized statement is a deep copy with hoistable literals replaced
    by A.ParamRef nodes; the signature is its value-free repr (ParamRef
    reprs carry the literal TYPES, so only same-typed shapes share it).
    With ``subquery_executor`` an uncorrelated scalar subquery in a
    hoistable position is run and hoisted like a literal of its value."""
    if not isinstance(stmt, (A.SelectStmt, A.UnionStmt)):
        return stmt, None, None
    if getattr(stmt, "_recursive_ctes", None):
        return stmt, None, None   # fixpoint terms re-execute via session
    norm = copy.deepcopy(stmt)
    p = _Paramizer(catalog, subquery_executor)
    try:
        if isinstance(norm, A.UnionStmt):
            for s in norm.selects:
                if isinstance(s, A.SelectStmt):
                    p.select(s)
        else:
            p.select(norm)
    except Exception:
        return stmt, None, None   # malformed AST: bind the original
    if not p.params:
        return stmt, None, None
    vec = ParamVector(tuple(v for v, _ in p.params),
                      tuple(t for _, t in p.params))
    return norm, vec, "P:" + repr(norm)
