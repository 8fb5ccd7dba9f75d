"""Statistics aggregate family as a pre-bind AST expansion.

Reference parity: the stddev/variance/covar/corr/regr_* aggregates the
reference ships as transition-function triples over float8 state arrays
(/root/reference/src/include/catalog/pg_aggregate.h:246,
/root/reference/src/backend/utils/adt/float.c float8_accum /
float8_regr_accum). The TPU-first translation is different in kind: each
statistic is EXPANDED before binding into arithmetic over the engine's
existing sum()/count() aggregates, so the two-phase partial/final
machinery, the dense/sort paths, spill, and multihost
lockstep all apply with zero new executor state. The moment algebra (the
same one float8_accum uses internally):

    Sxx = sum(x^2) - sum(x)^2/n        var_pop  = Sxx/n
                                       var_samp = Sxx/(n-1)
    Sxy = sum(x*y) - sum(x)*sum(y)/n   covar_*  = Sxy/{n, n-1}
    corr = Sxy/sqrt(Sxx*Syy)           regr_slope = Sxy/Sxx  ...

Deviations from the reference, by design:
 - results are float64 (PG computes numeric for int inputs); inputs are
   cast to double precision up front, which also keeps scaled-decimal
   sums of squares from overflowing int64.
 - division by zero yields NULL engine-wide (ops/expr_eval.zero_invalid),
   which happens to give PG semantics for var_samp(n=1) -> NULL and
   corr with a constant column -> NULL; regr_r2 with Syy=0, Sxx!=0
   returns NULL where PG returns 1.

Two-argument aggregates follow PG's (Y, X) argument order and pair
semantics: only rows where BOTH arguments are non-null contribute —
each side is wrapped in CASE WHEN other IS NOT NULL so plain sum/count
see pair-restricted inputs.
"""

from __future__ import annotations

import copy
import dataclasses

from greengage_tpu.sql import ast as A
from greengage_tpu.sql.parser import SqlError

ONE_ARG = {"stddev", "stddev_samp", "stddev_pop", "variance", "var_samp",
           "var_pop"}
TWO_ARG = {"covar_pop", "covar_samp", "corr", "regr_count", "regr_avgx",
           "regr_avgy", "regr_sxx", "regr_syy", "regr_sxy", "regr_slope",
           "regr_intercept", "regr_r2"}
STAT_AGGS = ONE_ARG | TWO_ARG


def _num(v) -> A.ANode:
    return A.Num(str(v))


def _f64(x: A.ANode) -> A.ANode:
    return A.CastExpr(copy.deepcopy(x), "double precision")


def _mul(a, b):
    return A.Bin("*", a, b)


def _div(a, b):
    return A.Bin("/", a, b)


def _sub(a, b):
    return A.Bin("-", a, b)


def _sum(x):
    return A.FuncCall("sum", [x])


def _count(x):
    return A.FuncCall("count", [copy.deepcopy(x)])


def _sqrt(x):
    return A.FuncCall("sqrt", [x])


def _nonneg(x):
    """Clamp tiny negative fp residue in a centered sum of squares (the
    reference clamps the same way, float.c float8_stddev_samp)."""
    return A.CaseExpr(
        whens=[(A.Bin("<", x, _num(0)), _num(0))],
        else_=copy.deepcopy(x))


def _pairwise(x: A.ANode, other: A.ANode) -> A.ANode:
    """x cast to double, NULLed wherever `other` is NULL (PG pair
    semantics for two-argument aggregates)."""
    return A.CaseExpr(
        whens=[(A.IsNullTest(copy.deepcopy(other), negate=True), _f64(x))],
        else_=None)


def _sxx(xf: A.ANode, n: A.ANode) -> A.ANode:
    """sum(x^2) - sum(x)^2/n over an already-float argument AST."""
    sq = _sum(_mul(copy.deepcopy(xf), copy.deepcopy(xf)))
    sx = _sum(copy.deepcopy(xf))
    return _sub(sq, _div(_mul(sx, copy.deepcopy(sx)), n))


def _expand(name: str, args: list[A.ANode]) -> A.ANode:
    if name in ONE_ARG:
        if len(args) != 1:
            raise SqlError(f"{name}() takes exactly one argument")
        x = args[0]
        xf = _f64(x)
        n = _count(x)
        ss = _nonneg(_sxx(xf, copy.deepcopy(n)))
        denom = (copy.deepcopy(n) if name.endswith("_pop")
                 else _sub(copy.deepcopy(n), _num(1)))
        var = _div(ss, denom)
        if name.startswith("stddev"):
            return _sqrt(var)
        return var

    if len(args) != 2:
        raise SqlError(f"{name}() takes exactly two arguments")
    y, x = args                      # PG order: agg(Y, X)
    yp, xp = _pairwise(y, x), _pairwise(x, y)
    prod = _mul(copy.deepcopy(xp), copy.deepcopy(yp))
    n = _count(prod)
    sx, sy = _sum(copy.deepcopy(xp)), _sum(copy.deepcopy(yp))
    sxy = _sub(_sum(copy.deepcopy(prod)),
               _div(_mul(copy.deepcopy(sx), copy.deepcopy(sy)),
                    copy.deepcopy(n)))
    sxx = _nonneg(_sxx(xp, copy.deepcopy(n)))
    syy = _nonneg(_sxx(yp, copy.deepcopy(n)))
    if name == "regr_count":
        return n
    if name == "regr_avgx":
        return _div(sx, n)
    if name == "regr_avgy":
        return _div(sy, n)
    if name == "regr_sxx":
        return sxx
    if name == "regr_syy":
        return syy
    if name == "regr_sxy":
        return sxy
    if name == "covar_pop":
        return _div(sxy, n)
    if name == "covar_samp":
        return _div(sxy, _sub(n, _num(1)))
    if name == "corr":
        return _div(sxy, _sqrt(_mul(sxx, syy)))
    if name == "regr_slope":
        return _div(sxy, sxx)
    if name == "regr_intercept":
        slope = _div(copy.deepcopy(sxy), copy.deepcopy(sxx))
        return _sub(_div(sy, copy.deepcopy(n)),
                    _mul(slope, _div(sx, n)))
    if name == "regr_r2":
        return _div(_mul(copy.deepcopy(sxy), sxy), _mul(sxx, syy))
    raise SqlError(f"unknown statistics aggregate {name}")


def _rewrite(node):
    """Depth-first AST rewrite; nested SelectStmts are left alone (each
    gets its own expand_stat_aggs when it is bound)."""
    if isinstance(node, A.SelectStmt):
        return node
    if isinstance(node, A.FuncCall) and node.name in STAT_AGGS \
            and node.over is None:
        if node.star or node.distinct:
            raise SqlError(f"{node.name}() supports neither * nor DISTINCT")
        args = [_rewrite(a) for a in node.args]
        return _expand(node.name, args)
    if isinstance(node, A.ANode):
        for f in dataclasses.fields(node):
            v = getattr(node, f.name)
            setattr(node, f.name, _rewrite(v))
        return node
    if isinstance(node, list):
        return [_rewrite(v) for v in node]
    if isinstance(node, tuple):
        return tuple(_rewrite(v) for v in node)
    return node


def expand_stat_aggs(stmt: A.SelectStmt) -> None:
    """In-place expansion over the statement's expression positions that
    may hold aggregates (select items, HAVING, ORDER BY)."""
    for it in stmt.items:
        it.expr = _rewrite(it.expr)
    if stmt.having is not None:
        stmt.having = _rewrite(stmt.having)
    for ob in stmt.order_by:
        ob.expr = _rewrite(ob.expr)
