"""RPN expression engine.

Re-expression of ``tidb_query_expr/src/types/{expr.rs:12, expr_builder.rs:19,
expr_eval.rs:149}``: expression trees compile to a postfix (RPN) node list;
evaluation is a stack machine over whole columns.  The same RPN program is
interpreted twice:

* ``eval_rpn(..., xp=numpy)`` — the CPU oracle path
* ``eval_rpn(..., xp=jax.numpy)`` inside ``jit`` — the TPU path (the RPN list
  is static Python structure, so tracing unrolls it into one fused XLA graph)

Decimal frac propagation happens here (statically, from the expression types),
so kernels never branch on scale at runtime.
"""

from __future__ import annotations

from dataclasses import dataclass, field

import numpy as np

from .datatypes import Column, EvalType
from .kernels import KERNELS


# ---------------------------------------------------------------------------
# Expression tree (tipb::Expr equivalent)
# ---------------------------------------------------------------------------

@dataclass
class ColumnRef:
    index: int


@dataclass
class Constant:
    value: object  # None | int | float | bytes (decimal: pre-scaled int)
    eval_type: EvalType
    frac: int = 0


@dataclass
class Param:
    """A constant hoisted out of the plan (``copr/plan_shape.py``): slot
    ``slot`` of the parameters a request brings.  The type and the frac stay
    in the plan, the value does not."""

    slot: int
    eval_type: EvalType
    frac: int = 0


@dataclass
class FuncCall:
    op: str  # kernel name
    children: list
    # filled by type inference:
    eval_type: EvalType | None = None
    frac: int = 0


Expr = ColumnRef | Constant | Param | FuncCall


# ---------------------------------------------------------------------------
# RPN compilation with static type/frac inference
# ---------------------------------------------------------------------------

@dataclass
class RpnNode:
    kind: str  # "col" | "const" | "param" | "fn"
    eval_type: EvalType
    frac: int = 0
    index: int = 0  # col index; a param's slot
    value: object = None  # const value
    op: str = ""  # fn kernel name
    arity: int = 0
    scale_by: tuple[int, ...] = ()  # per-operand decimal rescale multipliers


@dataclass
class RpnExpression:
    nodes: list[RpnNode]

    @property
    def eval_type(self) -> EvalType:
        return self.nodes[-1].eval_type

    @property
    def frac(self) -> int:
        return self.nodes[-1].frac

    def referenced_columns(self) -> set[int]:
        return {n.index for n in self.nodes if n.kind == "col"}


DIVIDE_FRAC_INCR = 4  # MySQL: decimal division adds 4 frac digits
_VARIADIC_MIN = {
    "in": 2, "case_when": 2, "concat": 1, "coalesce": 1,
    "json_extract": 2, "json_length": 1, "json_keys": 1, "json_array": 1,
    "json_object": 2, "json_merge": 2, "json_set": 3, "json_insert": 3,
    "json_replace": 3, "json_remove": 2,
}


def compile_expr(expr: Expr, schema: list[tuple[EvalType, int]]) -> RpnExpression:
    """Compile a tree to RPN. ``schema`` maps column index → (eval_type, frac)."""
    nodes: list[RpnNode] = []
    _compile(expr, schema, nodes)
    return RpnExpression(nodes)


def _compile(expr: Expr, schema, nodes: list[RpnNode]) -> tuple[EvalType, int]:
    if isinstance(expr, ColumnRef):
        et, frac = schema[expr.index]
        nodes.append(RpnNode("col", et, frac, index=expr.index))
        return et, frac
    if isinstance(expr, Constant):
        nodes.append(RpnNode("const", expr.eval_type, expr.frac, value=expr.value))
        return expr.eval_type, expr.frac
    if isinstance(expr, Param):
        nodes.append(RpnNode("param", expr.eval_type, expr.frac, index=expr.slot))
        return expr.eval_type, expr.frac
    if isinstance(expr, FuncCall):
        if expr.op not in KERNELS:
            raise ValueError(f"unsupported scalar function {expr.op!r}")
        arity, rkind, _ = KERNELS[expr.op]
        if arity == -1:
            min_arity = _VARIADIC_MIN.get(expr.op, 1)
            if len(expr.children) < min_arity:
                raise ValueError(f"{expr.op} needs at least {min_arity} arguments")
            arity = len(expr.children)
        elif arity != len(expr.children):
            raise ValueError(f"{expr.op} expects {arity} args, got {len(expr.children)}")
        child_types = [_compile(c, schema, nodes) for c in expr.children]
        et, frac, scale_by = _infer(expr.op, rkind, child_types)
        nodes.append(
            RpnNode("fn", et, frac, op=expr.op, arity=arity, scale_by=scale_by)
        )
        expr.eval_type, expr.frac = et, frac
        return et, frac
    raise TypeError(f"not an expression: {expr!r}")


def _infer(op: str, rkind: str, child_types) -> tuple[EvalType, int, tuple[int, ...]]:
    """Result type + frac + the decimal rescaling each operand needs.

    Mixed-frac decimal operands are aligned to the max frac by multiplying the
    lower-frac side by 10^diff — done once, statically planned here.
    """
    scale_by = tuple(1 for _ in child_types)
    types = [t[0] for t in child_types]
    fracs = [t[1] for t in child_types]
    has_decimal = EvalType.DECIMAL in types

    if op == "multiply" and has_decimal:
        # scaled(a*b) = scaled(a)*scaled(b), frac adds — no rescale needed
        return EvalType.DECIMAL, sum(f for t, f in child_types if t == EvalType.DECIMAL), scale_by

    if has_decimal and rkind in ("same", "int") and len(child_types) >= 2:
        # align fracs for +,-,comparisons,mod — and n-ary value comparisons
        # (greatest/least/in), where unaligned scaled ints would compare wrong
        f = max(fracs)
        scale_by = tuple(10 ** (f - fi) for fi in fracs)
        if rkind == "int":
            return EvalType.INT, 0, scale_by
        return EvalType.DECIMAL, f, scale_by

    if rkind == "int":
        return EvalType.INT, 0, scale_by
    if rkind == "real":
        # decimal operands feeding a real function must be unscaled to their
        # numeric value: scaled-int64 * 10^-frac (float multiplier)
        if has_decimal:
            scale_by = tuple(
                10.0 ** -f if t == EvalType.DECIMAL and f else 1
                for t, f in child_types
            )
        return EvalType.REAL, 0, scale_by
    if rkind == "bytes":
        return EvalType.BYTES, 0, scale_by
    if rkind == "json":
        return EvalType.JSON, 0, scale_by
    if rkind == "same":
        return types[0], fracs[0], scale_by
    if rkind == "same_2":
        # if(c, t, f): result typed like t/f — align their fracs
        if types[1] == EvalType.DECIMAL or types[2] == EvalType.DECIMAL:
            f = max(fracs[1], fracs[2])
            scale_by = (1, 10 ** (f - fracs[1]), 10 ** (f - fracs[2]))
            return EvalType.DECIMAL, f, scale_by
        return types[1], fracs[1], scale_by
    if rkind == "same_case":
        # case_when(c1, r1, ..., [else]): typed like the result operands
        result_positions = [i for i in range(1, len(child_types), 2)]
        if len(child_types) % 2 == 1:
            result_positions.append(len(child_types) - 1)
        rtypes = [types[i] for i in result_positions]
        rfracs = [fracs[i] for i in result_positions]
        if EvalType.DECIMAL in rtypes:
            f = max(rfracs)
            sb = [1] * len(child_types)
            for i in result_positions:
                sb[i] = 10 ** (f - fracs[i])
            return EvalType.DECIMAL, f, tuple(sb)
        return rtypes[0], rfracs[0], scale_by
    raise AssertionError(rkind)


# ---------------------------------------------------------------------------
# Stack-machine evaluation
# ---------------------------------------------------------------------------

_DTYPE = {
    EvalType.INT: np.int64,
    EvalType.DECIMAL: np.int64,
    EvalType.DATETIME: np.int64,
    EvalType.DURATION: np.int64,
    EvalType.REAL: np.float64,
    # enum index / set bitmask ride integer lanes directly
    EvalType.ENUM: np.int64,
    EvalType.SET: np.uint64,
}


def param_vectors(values, float_slots) -> tuple:
    """A request's parameters as ``eval_rpn`` reads them: an int64 and a
    float64 vector, both indexed by slot (a slot uses the one its lane says,
    ``float_slots[slot]``; the other holds 0).  A lane no slot uses is None,
    so that a program is handed no argument it does not read.  A SET mask
    above 2^63 rides the int64 lane wrapped and is cast back where it is
    read."""
    ints = floats = None
    if not all(float_slots):
        ints = np.zeros(len(values), dtype=np.int64)
    if any(float_slots):
        floats = np.zeros(len(values), dtype=np.float64)
    for k, v in enumerate(values):
        if float_slots[k]:
            floats[k] = v
        else:
            ints[k] = v - (1 << 64) if v >= 1 << 63 else v
    return ints, floats


def bind_rpn(rpn: RpnExpression, values) -> RpnExpression:
    """``rpn`` with every param node turned back into the constant a request
    brought for its slot: what host-side readers of a plan's literals (zone
    maps, tile classification) and the programs that still bake them read."""
    return RpnExpression([
        RpnNode("const", n.eval_type, n.frac, value=values[n.index])
        if n.kind == "param" else n
        for n in rpn.nodes
    ])


def eval_rpn(rpn: RpnExpression, columns: list, n_rows: int, xp=np, params=None):
    """Evaluate over column (data, nulls) pairs. Returns (data, nulls).

    ``columns`` holds per-column (data, nulls) arrays (only referenced indices
    need to be present).  With ``xp=jax.numpy`` the arrays may be tracers.
    ``params`` is the request's ``param_vectors`` pair, read by param nodes:
    under ``jax.numpy`` a traced input, so one program serves every literal.
    """
    stack: list[tuple[object, object]] = []
    for node in rpn.nodes:
        if node.kind == "col":
            stack.append(columns[node.index])
        elif node.kind == "const":
            dtype = _DTYPE.get(node.eval_type, object)
            if node.value is None:
                data = xp.zeros(n_rows, dtype=dtype if dtype is not object else np.int64)
                nulls = xp.ones(n_rows, dtype=bool)
            elif node.eval_type in (EvalType.BYTES, EvalType.JSON):
                data = np.empty(n_rows, dtype=object)
                data[:] = node.value
                nulls = xp.zeros(n_rows, dtype=bool)
            else:
                data = xp.full(n_rows, node.value, dtype=dtype)
                nulls = xp.zeros(n_rows, dtype=bool)
            stack.append((data, nulls))
        elif node.kind == "param":
            dtype = _DTYPE[node.eval_type]
            v = params[1 if dtype is np.float64 else 0][node.index]
            stack.append((xp.full(n_rows, v.astype(dtype), dtype=dtype),
                          xp.zeros(n_rows, dtype=bool)))
        else:
            _, _, fn = KERNELS[node.op]
            args = stack[-node.arity :]
            del stack[-node.arity :]
            if any(m != 1 for m in node.scale_by):
                args = [
                    (d * m, nl) if m != 1 else (d, nl)
                    for (d, nl), m in zip(args, node.scale_by)
                ]
            stack.append(fn(xp, *args))
    assert len(stack) == 1, "malformed RPN"
    return stack[0]


def eval_expr_on_chunk(rpn: RpnExpression, chunk, xp=np):
    """Convenience: evaluate over a Chunk's physical columns."""
    cols = {}
    for i in rpn.referenced_columns():
        c = chunk.columns[i]
        cols[i] = (c.data, c.nulls)
    n = len(chunk.columns[0]) if chunk.columns else 0
    return eval_rpn(rpn, cols, n, xp=xp)


# -- convenience builders ---------------------------------------------------

def col(i: int) -> ColumnRef:
    return ColumnRef(i)


def const_int(v: int | None) -> Constant:
    return Constant(v, EvalType.INT)


def const_real(v: float | None) -> Constant:
    return Constant(v, EvalType.REAL)


def const_decimal(scaled: int | None, frac: int) -> Constant:
    return Constant(scaled, EvalType.DECIMAL, frac)


def const_bytes(v: bytes | None) -> Constant:
    return Constant(v, EvalType.BYTES)


def const_set(mask: int | None) -> Constant:
    """SET bitmask constant — uint64 lanes, so bit 63 survives (a plain
    const_int would wrap negative against a 64-element SET column)."""
    return Constant(mask, EvalType.SET)


def const_json(v) -> Constant:
    """Constant from a Python JSON value (encoded to binary JSON)."""
    from .json_value import json_encode

    return Constant(None if v is None else json_encode(v), EvalType.JSON)


def call(op: str, *children) -> FuncCall:
    return FuncCall(op, list(children))
