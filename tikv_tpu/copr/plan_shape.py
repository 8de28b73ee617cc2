"""A pushed-down plan taken apart into its *shape* and its *parameters*.

TiDB inlines a statement's literals into the expression tree of every DAG it
pushes down, so two executions of one statement reach the store as different
plan bytes.  One rule, read off the plan, tells what of those bytes is a
literal the programs can take as an argument:

    the non-NULL constants on numeric lanes (INT, DECIMAL at its frac,
    DATETIME, DURATION, REAL, ENUM, SET) in the conditions of the plan's
    ``Selection`` executors, in plan order

Each becomes a numbered slot (``rpn.Param``) and its value an entry of the
request's parameters.  Everything else stays in the shape: NULL literals,
BYTES and JSON constants, a decimal's frac (and with it every ``scale_by``),
operators, columns, aggregates and their constants, a join's build chain,
output offsets, encode type.  A constant the rule leaves alone therefore
costs a program and never an answer.

Two keys follow (docs/copr_scheduler.md):

* the **shape signature** keys what is compiled or memoised per program:
  the endpoint's evaluators, the scheduler's evaluator and eligibility memos,
  the observatory's profiles;
* the **plan signature** (:func:`plan_signature`, constants included) stays
  the identity of a scheduler slot, whose riders share one response's bytes,
  and of an xregion group, whose riders share one launch.  It says the same
  as the pair ``(shape signature, parameters)`` that :func:`split` returns,
  which is what the scheduler keys slots and groups by: one walk for both.
"""

from __future__ import annotations

from .dag import (
    Aggregation,
    DagRequest,
    IndexScan,
    Join,
    Limit,
    Projection,
    Selection,
    TableScan,
    TopN,
)
from .datatypes import EvalType
from .region_cache import schema_sig
from .rpn import ColumnRef, Constant, FuncCall, Param
from .sig_map import resolve_sig

_INT_LANES = frozenset({EvalType.INT, EvalType.DECIMAL, EvalType.DATETIME,
                        EvalType.DURATION, EvalType.ENUM})


def _hoisted(c: Constant) -> bool:
    """The rule.  A value its lane cannot hold (an int past 64 bits, a float
    on an integer lane) is left to the shape."""
    v = c.value
    if c.eval_type == EvalType.REAL:
        return type(v) in (int, float)
    if type(v) not in (int, bool):
        return False
    if c.eval_type == EvalType.SET:
        return 0 <= v < 1 << 64
    return c.eval_type in _INT_LANES and -(1 << 63) <= v < 1 << 63


def _expr_sig(e, params: list | None = None):
    """Canonical, hashable form of a scalar expression tree.  With ``params``
    (inside a Selection's condition) a hoisted constant is written as its
    slot and its value appended there."""
    if e is None:
        return None
    if isinstance(e, ColumnRef):
        return ("col", e.index)
    if isinstance(e, Constant):
        if params is not None and _hoisted(e):
            params.append(e.value)
            return ("param", len(params) - 1, e.eval_type, e.frac)
        v = e.value
        if not isinstance(v, (int, float, bytes, str, bool, type(None))):
            v = repr(v)
        return ("const", e.eval_type, e.frac, v)
    if isinstance(e, Param):
        if params is not None:
            params.append(None)  # a shape brings no value; the slot counts
        return ("param", e.slot, e.eval_type, e.frac)
    if isinstance(e, FuncCall):
        op = e.op
        # wire-format ScalarFuncSig spellings fold onto kernel names, so a
        # tipb-bridged DAG and a natively-built DAG with the same plan key
        # into the same micro-batch (sig_map is the single source of truth)
        mapped = resolve_sig(op)
        if mapped is not None and not mapped.startswith("~"):
            op = mapped
        return ("fn", op, tuple(_expr_sig(c, params) for c in e.children))
    return ("?", repr(e))


def _exec_sig(ex, params: list | None = None) -> tuple:
    """One executor descriptor's shape key.  A Join recurses into its
    build chain but deliberately EXCLUDES the build ranges and region
    context — those vary per request without changing the compiled
    program shape, exactly like the probe ranges."""
    if isinstance(ex, TableScan):
        return ("tablescan", ex.table_id, schema_sig(ex.columns_info))
    if isinstance(ex, IndexScan):
        return ("indexscan", ex.table_id, ex.index_id,
                schema_sig(ex.columns_info))
    if isinstance(ex, Selection):
        return ("sel", tuple(_expr_sig(c, params) for c in ex.conditions))
    if isinstance(ex, Aggregation):
        return ("agg", bool(ex.streamed),
                tuple(_expr_sig(g) for g in ex.group_by),
                tuple((a.op, _expr_sig(a.expr)) for a in ex.agg_funcs))
    if isinstance(ex, TopN):
        return ("topn", ex.limit,
                tuple((_expr_sig(e), bool(d)) for e, d in ex.order_by))
    if isinstance(ex, Limit):
        return ("limit", ex.limit)
    if isinstance(ex, Projection):
        return ("proj", tuple(_expr_sig(e) for e in ex.exprs))
    if isinstance(ex, Join):
        return ("join", ex.join_type, ex.left_key, ex.right_key,
                tuple(_exec_sig(b) for b in ex.build))
    return (type(ex).__name__,)


def _signature(dag: DagRequest, params: list | None) -> tuple:
    parts = [_exec_sig(ex, params) for ex in dag.executors]
    # encode_type is part of the slot identity: identical requests share one
    # slot's RESPONSE BYTES, and a datum and a chunk request with the same
    # plan must never share those (mirrors the service parse-memo rule)
    parts.append(("out", tuple(dag.output_offsets or ()), dag.chunk_rows,
                  dag.encode_type))
    return tuple(parts)


def plan_signature(dag: DagRequest) -> tuple:
    """The plan's whole identity, constants included: two requests with equal
    signatures (over one region view) are one slot and share its response
    bytes."""
    return _signature(dag, None)


def split(dag: DagRequest) -> tuple[tuple, tuple]:
    """``(shape signature, parameters)`` of a plan.  Two DAGs with equal
    shape signatures compile to the same device programs and differ in the
    parameter values those programs are given; the pair is the plan's whole
    identity, as :func:`plan_signature` is."""
    params: list = []
    return _signature(dag, params), tuple(params)


def _shape_expr(e, n: list):
    # the traversal of _expr_sig, so that slots number alike
    if isinstance(e, Constant) and _hoisted(e):
        n[0] += 1
        return Param(n[0] - 1, e.eval_type, e.frac)
    if isinstance(e, FuncCall):
        return FuncCall(e.op, [_shape_expr(c, n) for c in e.children])
    return e


def shape_dag(dag: DagRequest) -> DagRequest:
    """The plan an evaluator is built from: ``dag`` with every hoisted
    constant replaced by its ``Param`` slot, so that it holds no literal a
    request could be answered for by mistake."""
    n = [0]
    execs = [
        Selection([_shape_expr(c, n) for c in ex.conditions])
        if isinstance(ex, Selection) else ex
        for ex in dag.executors
    ]
    return DagRequest(executors=execs, output_offsets=dag.output_offsets,
                      chunk_rows=dag.chunk_rows, encode_type=dag.encode_type)
