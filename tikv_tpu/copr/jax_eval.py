"""JAX/TPU DAG evaluator — the coprocessor's device execution backend.

This is the subsystem the whole build aims at: DAGs whose shape fits
(TableScan → Selection? → Aggregation? → TopN?/Limit?) run as ONE jitted XLA
program per fixed-size row block, with aggregation carry state living on
device across blocks:

    host: MVCC scan → RowBatchDecoder → numpy columns → pad to block shape
    device (jit): RPN predicates → mask; RPN agg args; segment reductions
    host: finalize via the same AggState/encoder as the CPU path

Design rules (see SURVEY.md §7):
* fixed block shapes + validity masks — never dynamic shapes, so XLA compiles
  exactly once per (plan, block, group-capacity bucket)
* selection = mask, never gather
* group ids are dictionary codes assigned on host in first-occurrence stream
  order — which makes group output order *identical* to the CPU hash-agg's
  insertion order, so responses match byte-for-byte
* all-int/decimal pipelines are exact on device (int64 lanes); REAL sums are
  float and may differ from CPU in last-ulp rounding (documented caveat)
* the per-block step is dispatched asynchronously: block N+1 is decoded on
  host while block N runs on device (runner.rs's 1ms-yield loop becomes
  pipelining)

The reference CPU path stays the default and the correctness oracle, exactly
like the plugin gating described in src/coprocessor/endpoint.rs.
"""

from __future__ import annotations

import threading
from dataclasses import dataclass

import numpy as np

import jax

# Exact int64 lanes are a correctness requirement (decimal sums, counts over
# 100M rows): without x64, jnp silently downcasts to int32 and aggregates
# overflow.  Must be set before any jnp array is created.
jax.config.update("jax_enable_x64", True)

import jax.numpy as jnp  # noqa: E402

from ..analysis.sanitizer import note_blocking
from ..util import trace
from . import observatory as _obs
from .aggr import AggDescriptor, AggState
from .cache import nv_off
from .dag import (
    Aggregation,
    DagRequest,
    IndexScan,
    Limit,
    SelectResponse,
    make_response_encoder,
    Selection,
    TableScan,
    TopN,
)
from .datatypes import Chunk, Column, EvalType
from .executors import BatchTopNExecutor, ScanSource
from .groupby import GroupDict
from .rpn import ColumnRef, RpnExpression, bind_rpn, compile_expr, eval_rpn, param_vectors
from .table import RowBatchDecoder, decode_record_handles

# this module owns the device, so it is the one that mirrors the program's
# stages into the device profiler's trace (docs/tracing.md): util/trace.py
# stays importable without jax.  With no profiler session an annotation is
# one atomic load.
trace.set_mirror(jax.profiler.TraceAnnotation)

DEFAULT_BLOCK_ROWS = 1 << 16
_GROUP_CAPACITY_START = 1024
_NO_ROW = 1 << 62  # first-active-row sentinel: "no row of this group survived"
_ZERO_GIDS: dict[int, np.ndarray] = {}
_MISSING_PLAN = object()  # sentinel: _stacked_device resolves the plan itself

_DEVICE_AGG_OPS = {
    "count", "sum", "avg", "min", "max", "var_pop",
    "first", "bit_and", "bit_or", "bit_xor",
}
_DEVICE_EVAL_TYPES = {EvalType.INT, EvalType.REAL, EvalType.DECIMAL, EvalType.DATETIME, EvalType.DURATION}
_TOPN_DEVICE_MAX = 2048  # raw TopN carries K rows of state per column
_PARAM_VECS_MAX = 1024  # sets of literals an evaluator keeps device vectors of


# ---------------------------------------------------------------------------
# Eligibility (the endpoint's routing predicate)
# ---------------------------------------------------------------------------

def supports(dag: DagRequest) -> bool:
    """True if this DAG can run on the device path."""
    try:
        _analyze(dag)
        return True
    except (_Unsupported, ValueError):
        return False


def decline_cause(dag: DagRequest) -> str | None:
    """None when the DAG is device-eligible, else a bounded-cardinality
    cause slug — the named half of :func:`supports`, so limit-bearing
    plans that stay on the CPU are never a silent fallback (the endpoint
    counts these under ``tikv_coprocessor_encoded_decline_total``
    path="device_plan")."""
    try:
        _analyze(dag)
        return None
    except _Unsupported as exc:
        return exc.cause
    except ValueError:
        return "expr_compile"


class _Unsupported(Exception):
    def __init__(self, msg: str, cause: str = "plan_shape"):
        super().__init__(msg)
        self.cause = cause


@dataclass
class _Plan:
    scan: TableScan
    selection: Selection | None
    agg: Aggregation | None
    topn: TopN | None
    limit: Limit | None


def _analyze(dag: DagRequest) -> _Plan:
    execs = list(dag.executors)
    if not execs or not isinstance(execs[0], (TableScan, IndexScan)):
        raise _Unsupported("leaf must be a scan", "leaf_not_scan")
    scan = execs[0]
    rest = execs[1:]
    plan = _Plan(scan, None, None, None, None)
    stage = 0  # 0=selection allowed, 1=agg allowed, 2=topn/limit allowed
    for e in rest:
        if isinstance(e, Selection) and stage == 0 and plan.selection is None:
            plan.selection = e
        elif isinstance(e, Aggregation) and stage <= 1 and plan.agg is None:
            plan.agg = e
            stage = 2
        elif isinstance(e, TopN) and plan.topn is None and plan.limit is None:
            plan.topn = e
            stage = 3
        elif isinstance(e, Limit) and plan.limit is None:
            plan.limit = e
            stage = 3
        else:
            from .dag import Join, Projection

            if isinstance(e, Join):
                # joins route through the dedicated device-join rung
                # (jax_join.py / docs/device_join.md), never this plan shape
                raise _Unsupported("join executors serve via the join rung",
                                   "join_executor")
            if isinstance(e, Projection):
                raise _Unsupported(
                    "projection executors serve via the join rung or CPU",
                    "projection_executor")
            raise _Unsupported(f"executor {type(e).__name__} not device-routable here",
                               "executor_shape")
    schema = [(c.ftype.eval_type, c.ftype.decimal) for c in scan.columns_info]
    for et, _ in schema:
        if et not in _DEVICE_EVAL_TYPES and et not in (EvalType.BYTES, EvalType.JSON):
            # BYTES/JSON columns may exist in the schema (group keys are
            # dictionary-encoded host-side); _check_rpn_device rejects them
            # inside device expressions
            raise _Unsupported(f"column type {et}", "column_type")
        if isinstance(scan, IndexScan) and et not in _DEVICE_EVAL_TYPES:
            # index entries decode through datum lists (object arrays), so
            # BYTES never arrives dictionary-coded on this leaf
            raise _Unsupported(f"index column type {et}", "index_column_type")
    if plan.selection is not None:
        for cond in plan.selection.conditions:
            rpn = compile_expr(cond, schema)
            _check_rpn_device(rpn, schema)
    if plan.agg is not None:
        if plan.agg.streamed:
            # stream agg emits one row per CONSECUTIVE run of the group key;
            # that equals hash-agg output (what the device computes) only
            # when the scan order sorts by the group key — guaranteed here
            # just for grouping on the HANDLE column (scan order is handle
            # order, wherever it sits in the schema).  Anything else takes
            # the CPU stream executor (stream_aggr_executor.rs semantics).
            cols_info = scan.columns_info
            if isinstance(scan, IndexScan):
                # index scan order sorts by the index column prefix
                # (index_scan_executor.rs:29 + stream_aggr_executor.rs:23's
                # common sorted-by-index shape): grouping on a PREFIX of the
                # index columns keeps stream output == hash output
                ok = all(
                    isinstance(g, ColumnRef) and g.index == gi
                    and g.index < len(cols_info)
                    and not cols_info[g.index].is_pk_handle
                    for gi, g in enumerate(plan.agg.group_by)
                )
            else:
                ok = len(plan.agg.group_by) <= 1 and all(
                    isinstance(g, ColumnRef)
                    and g.index < len(cols_info)
                    and cols_info[g.index].is_pk_handle
                    for g in plan.agg.group_by
                )
            if not ok:
                raise _Unsupported("streamed agg not sorted by group key",
                                   "streamed_agg_order")
        for a in plan.agg.agg_funcs:
            if a.op not in _DEVICE_AGG_OPS:
                raise _Unsupported(f"aggregate {a.op}", "agg_op")
            if a.expr is not None:
                rpn = compile_expr(a.expr, schema)
                _check_rpn_device(rpn, schema)
        # group-by exprs are evaluated on host (numpy) then dictionary-encoded,
        # so BYTES group keys are fine; exprs just need compilable kernels
        for g in plan.agg.group_by:
            compile_expr(g, schema)
    if plan.topn is not None and plan.agg is None:
        # raw TopN runs a device top-K merge: every schema column ships as
        # payload — numeric columns as values, BYTES as dictionary codes
        # (decoded back to bytes host-side at finalize; non-dict layouts
        # raise at run time and take the CPU fallback)
        if plan.topn.limit > _TOPN_DEVICE_MAX:
            raise _Unsupported(f"TopN limit {plan.topn.limit} too large for device",
                               "topn_limit_too_large")
        for et, _ in schema:
            if et not in _DEVICE_EVAL_TYPES and not (
                et == EvalType.BYTES and isinstance(scan, TableScan)
            ):
                raise _Unsupported(f"TopN payload column type {et}",
                                   "topn_payload_type")
        for expr, _desc in plan.topn.order_by:
            rpn = compile_expr(expr, schema)
            _check_rpn_device(rpn, schema)
            if rpn.eval_type not in _DEVICE_EVAL_TYPES:
                raise _Unsupported(f"TopN key type {rpn.eval_type}", "topn_key_type")
    return plan


def _check_rpn_device(rpn: RpnExpression, schema) -> None:
    for node in rpn.nodes:
        if node.eval_type == EvalType.BYTES or node.eval_type == EvalType.JSON:
            raise _Unsupported("bytes in device expression", "bytes_predicate")


# ---------------------------------------------------------------------------
# Device block step
# ---------------------------------------------------------------------------

def _np_dtype(et: EvalType):
    return np.float64 if et == EvalType.REAL else np.int64


_ONEHOT_CAPACITY_MAX = 64
_MATMUL_CAPACITY_MAX = 4096
_EXTREME_MASK_CAPACITY_MAX = 1024


_PREFETCH_END = object()


def _prefetch(it, depth: int = 1):
    """Run ``it`` on a worker thread, buffering ``depth`` items ahead: the
    producer (host decode — numpy-heavy, releases the GIL) overlaps the
    consumer (device dispatch).  Exceptions re-raise at the consumption
    point; an abandoned consumer unblocks the producer via queue timeout."""
    import queue as _queue

    q: _queue.Queue = _queue.Queue(maxsize=depth)
    done = threading.Event()

    def put_or_abandon(entry) -> bool:
        # EVERY put must observe `done`: an early-abandoned consumer (e.g.
        # a Limit satisfied mid-scan) never drains the queue, and a plain
        # blocking put would pin this thread + its decoded block forever
        while not done.is_set():
            try:
                q.put(entry, timeout=0.5)
                return True
            except _queue.Full:
                continue
        return False

    def produce():
        try:
            for item in it:
                if not put_or_abandon(("item", item)):
                    return
            put_or_abandon((None, _PREFETCH_END))
        except BaseException as exc:  # noqa: BLE001 — re-raised on consume
            put_or_abandon(("exc", exc))

    t = threading.Thread(target=produce, daemon=True, name="decode-prefetch")
    t.start()
    try:
        while True:
            kind, payload = q.get()
            if payload is _PREFETCH_END:
                return
            if kind == "exc":
                raise payload
            yield payload
    finally:
        done.set()


def _limb_matmul_seg_sum(x, gids, capacity: int):
    """Exact int64 per-group sums on the MXU: TPU scatter is ~1000× slower
    than reductions, so instead split each value into b-bit limbs, one-hot
    matmul every limb in a single (C×n)@(n×L) dot — systolic-array work —
    and reassemble with two's-complement wraparound.  Logical shifts make
    the limbs sign-free, so negative values round-trip exactly.

    b ≤ 8 is load-bearing: the TPU MXU's default precision truncates f32
    operands to bf16 (8 mantissa bits), so limbs must stay ≤ 2^8 to survive
    that pass bit-exact; products then accumulate in f32, exact while
    (2^b−1)·n < 2^24.  Callers guarantee n < 2^16 (block sizes)."""
    n = x.shape[0]
    bits = 8
    while bits > 1 and (2**bits - 1) * n >= 2**24:
        bits -= 1
    if (2**bits - 1) * n >= 2**24:  # n ≥ 2^23: exactness unattainable
        return jax.ops.segment_sum(x, gids, num_segments=capacity)
    n_limbs = -(-64 // bits)
    mask = jnp.int64((1 << bits) - 1)
    onehot = (gids[:, None] == jnp.arange(capacity, dtype=gids.dtype)[None, :]).astype(
        jnp.float32
    )
    limbs = jnp.stack(
        [
            (jax.lax.shift_right_logical(x, jnp.int64(k * bits)) & mask).astype(jnp.float32)
            for k in range(n_limbs)
        ],
        axis=1,
    )
    sums = jax.lax.dot_general(
        onehot, limbs, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )  # C×L, every entry an exact integer < 2^24
    acc = jnp.zeros(capacity, dtype=jnp.int64)
    for k in range(n_limbs):
        acc = acc + (sums[:, k].astype(jnp.int64) << (k * bits))
    return acc


def _scatter_ok() -> bool:
    """The one-hot/mask/limb-matmul shapes below exist because TPU scatter
    is ~1000× slower than MXU/VPU work — but on a CPU (or GPU) backend the
    trade INVERTS: XLA-CPU lowers the n×C broadcast compares to dreadful
    code while native scatter-adds are fast.  Decided at trace time, so
    each backend compiles its own best shape and results stay exact
    (segment ops are exact integer/f64 adds)."""
    return jax.default_backend() != "tpu"


def _seg_sum(x, gids, capacity: int):
    """Exact per-group sum avoiding TPU scatter: capacity 1 is a plain
    reduction; small capacities use a broadcast-compare mask reduction (VPU
    work, ~n·C lanes); int64 up to 4096 groups rides the MXU via limb
    matmuls; only float sums at large capacities fall back to scatter-based
    segment_sum (f32 matmul would diverge from the CPU oracle's f64 sums
    beyond the last-ulp exemption).  Non-TPU backends take the scatter path
    directly (_scatter_ok)."""
    if capacity == 1:
        return jnp.sum(x).reshape(1)
    if _scatter_ok():
        return jax.ops.segment_sum(x, gids, num_segments=capacity)
    if capacity <= _ONEHOT_CAPACITY_MAX:
        onehot = gids[:, None] == jnp.arange(capacity, dtype=gids.dtype)[None, :]
        return jnp.sum(jnp.where(onehot, x[:, None], jnp.zeros((), dtype=x.dtype)), axis=0)
    if x.dtype == jnp.int64 and capacity <= _MATMUL_CAPACITY_MAX:
        return _limb_matmul_seg_sum(x, gids, capacity)
    if capacity <= _MATMUL_CAPACITY_MAX:
        # float sums beyond the one-hot window: scan over blocks of 64
        # groups, each a full-precision f64 mask-reduce (VPU).  Same tree
        # reduction as the ≤64 path, so the same last-ulp behavior — and
        # still orders of magnitude cheaper than TPU scatter, which was the
        # round-1 fallback that knocked Q1-with-REAL shapes off the device.
        blocks = (capacity + _ONEHOT_CAPACITY_MAX - 1) // _ONEHOT_CAPACITY_MAX
        starts = jnp.arange(blocks, dtype=gids.dtype) * _ONEHOT_CAPACITY_MAX
        lane = jnp.arange(_ONEHOT_CAPACITY_MAX, dtype=gids.dtype)

        def one_block(start):
            onehot = gids[:, None] == (start + lane)[None, :]
            return jnp.sum(
                jnp.where(onehot, x[:, None], jnp.zeros((), dtype=x.dtype)), axis=0
            )

        out = jax.lax.map(one_block, starts)  # (blocks, 64)
        return out.reshape(blocks * _ONEHOT_CAPACITY_MAX)[:capacity]
    return jax.ops.segment_sum(x, gids, num_segments=capacity)


def _seg_extreme(x, gids, capacity: int, is_min: bool, identity):
    if capacity == 1:
        f = jnp.min if is_min else jnp.max
        return f(x).reshape(1)
    if _scatter_ok():
        f = jax.ops.segment_min if is_min else jax.ops.segment_max
        return f(x, gids, num_segments=capacity)
    if capacity <= _EXTREME_MASK_CAPACITY_MAX:
        # n×C masked reduce: pure VPU work, still far cheaper than scatter
        onehot = gids[:, None] == jnp.arange(capacity, dtype=gids.dtype)[None, :]
        masked = jnp.where(onehot, x[:, None], jnp.full((), identity, dtype=x.dtype))
        return (jnp.min if is_min else jnp.max)(masked, axis=0)
    f = jax.ops.segment_min if is_min else jax.ops.segment_max
    return f(x, gids, num_segments=capacity)


_BIT_IDENT = {"bit_and": -1, "bit_or": 0, "bit_xor": 0}
_BIT_FN = {
    "bit_and": jax.lax.bitwise_and,
    "bit_or": jax.lax.bitwise_or,
    "bit_xor": jax.lax.bitwise_xor,
}


def _seg_bitop(x, gids, capacity: int, op: str):
    """Per-group bitwise AND/OR/XOR via lax.reduce (XLA has native and/or/
    xor reduction monoids on every backend — no scatter exists for them).
    Masked n×C reduction in group-blocks of 64, same shape as _seg_sum's
    mid path; these aggregates are rare enough that the extra lanes are
    acceptable on either backend."""
    ident = jnp.int64(_BIT_IDENT[op])
    fn = _BIT_FN[op]
    if capacity == 1:
        return jax.lax.reduce(x, ident, fn, (0,)).reshape(1)
    blocks = (capacity + _ONEHOT_CAPACITY_MAX - 1) // _ONEHOT_CAPACITY_MAX
    starts = jnp.arange(blocks, dtype=gids.dtype) * _ONEHOT_CAPACITY_MAX
    lane = jnp.arange(_ONEHOT_CAPACITY_MAX, dtype=gids.dtype)

    def one_block(start):
        onehot = gids[:, None] == (start + lane)[None, :]
        masked = jnp.where(onehot, x[:, None], ident)
        return jax.lax.reduce(masked, ident, fn, (0,))

    out = jax.lax.map(one_block, starts)
    return out.reshape(blocks * _ONEHOT_CAPACITY_MAX)[:capacity]


def _build_cols(ship_cols, nullable, col_data, col_nulls, n_rows, enc=None,
                refs=None):
    """Column map for eval_rpn: NOT NULL columns get a folded constant mask.

    ``enc`` (static per-ship-col encoding descriptors from
    ``copr/encoding.py``) turns this into THE in-kernel decode point shared
    by every device program: bitpacked lanes widen ``+ refs[j]`` (refs are
    dynamic, so images with different value ranges share one executable),
    narrowed dict codes widen, RLE runs expand through one searchsorted
    gather — HBM holds the encoded payloads, everything downstream sees
    exact int64/f64 lanes."""
    no_nulls = jnp.zeros(n_rows, dtype=bool)
    nullmap = dict(zip(nullable, col_nulls))
    if enc is None:
        return {i: (col_data[j], nullmap.get(i, no_nulls)) for j, i in enumerate(ship_cols)}
    from .kernels import decode_device_column

    cols = {}
    for j, i in enumerate(ship_cols):
        cols[i] = decode_device_column(
            jnp, enc[j], col_data[j], nullmap.get(i, no_nulls),
            None if refs is None else refs[j], n_rows,
        )
    return cols


def _mixed_radix_gids(cols, group_cols, dict_lens, n_rows):
    """Group ids from resident dictionary-code columns (stable radices)."""
    local = jnp.zeros(n_rows, dtype=jnp.int64)
    for gi, dlen in zip(group_cols, dict_lens):
        codes, gnulls = cols[gi]
        local = local * (dlen + 1) + jnp.where(gnulls, dlen, codes)
    return local


def _fused_step(sel_rpns, device_aggs, capacity, n_rows, cols, n_valid, gids, offset, state,
                track_first: bool = True, params=None):
    """THE block step, shared by every device program: selection predicates →
    active mask; aggregate updates; first-active-row tracker.

    ``track_first=False`` skips the per-block first-active-row segment-min:
    with no group-by, finalize outputs the single slot unconditionally, so
    the tracker is dead work (a whole extra reduction pass per block).
    ``params``: the request's literals (``rpn.param_vectors``), a traced
    input of the program wherever the selection has param nodes."""
    first_row, carries = state
    active = jnp.arange(n_rows, dtype=jnp.int64) < n_valid
    for rpn in sel_rpns:
        d, nl = eval_rpn(rpn, cols, n_rows, xp=jnp, params=params)
        active = active & (d != 0) & ~nl
    new_carries = tuple(
        da.update(c, cols, n_rows, gids, active, capacity, offset)
        for da, c in zip(device_aggs, carries)
    )
    if not track_first:
        return (first_row, new_carries)
    ridx = jnp.where(active, offset + jnp.arange(n_rows, dtype=jnp.int64), _NO_ROW)
    block_first = _seg_extreme(ridx, gids, capacity, True, _NO_ROW)
    return (jnp.minimum(first_row, block_first), new_carries)


def _masked_nv(blocks, keep):
    """Survivor-count n_valid vector (docs/zone_maps.md): pruned blocks
    carry 0 valid rows, so the fixed-shape programs mask them out entirely
    while every compile key stays unchanged."""
    nv = np.fromiter(
        (b.n_valid if keep[bi] else 0 for bi, b in enumerate(blocks)),
        dtype=np.int64, count=len(blocks))
    return jnp.asarray(nv)


def _batch_prune_keep(evaluators, cache, params_list):
    """Fused-batch keep mask: the batch shares one block stream, so a block
    is masked out only when EVERY rider's zone maps prune it.  Returns
    (keep | None, (examined, pruned)) like the unary ``_prune_keep``."""
    from . import zone_maps as _zm

    if not _zm.enabled() or not cache.blocks:
        return None, (0, 0)
    keep = None
    for ev, params in zip(evaluators, params_list):
        if not ev.sel_rpns:
            return None, (0, 0)
        m = _zm.prune_blocks(cache, ev.bound_sel_rpns(params), path="fused")
        if m is None:
            return None, (0, 0)
        keep = m if keep is None else (keep | m)
    if keep.all():
        return None, (0, 0)
    return keep, (len(cache.blocks), int((~keep).sum()))


class _DeviceAgg:
    """Builds the jitted block update + carry init for one aggregate."""

    def __init__(self, op: str, rpn: RpnExpression | None):
        self.op = op
        self.rpn = rpn
        self.input_type = rpn.eval_type if rpn is not None else EvalType.INT
        self.frac = rpn.frac if rpn is not None else 0
        self.dtype = _np_dtype(self.input_type)

    def init_carry(self, capacity: int):
        z_i = jnp.zeros(capacity, dtype=jnp.int64)
        if self.op == "count":
            return (z_i,)
        if self.op in ("bit_and", "bit_or", "bit_xor"):
            return (z_i, jnp.full(capacity, _BIT_IDENT[self.op], dtype=jnp.int64))
        z_v = jnp.zeros(capacity, dtype=self.dtype)
        if self.op in ("sum", "avg"):
            return (z_i, z_v)
        if self.op == "first":
            # (count, first value, global row index that supplied it)
            return (z_i, z_v, jnp.full(capacity, _NO_ROW, dtype=jnp.int64))
        if self.op == "var_pop":
            return (z_i, z_v, jnp.zeros(capacity, dtype=jnp.float64))
        if self.op in ("min", "max"):
            if self.dtype == np.float64:
                ident = jnp.inf if self.op == "min" else -jnp.inf
            else:
                info = np.iinfo(np.int64)
                ident = info.max if self.op == "min" else info.min
            return (z_i, jnp.full(capacity, ident, dtype=self.dtype))
        raise AssertionError(self.op)

    def host_template(self):
        """Numpy dtype skeleton mirroring init_carry — for unpacking pulls."""
        zi = np.zeros(0, dtype=np.int64)
        zv = np.zeros(0, dtype=self.dtype)
        if self.op == "count":
            return (zi,)
        if self.op in ("bit_and", "bit_or", "bit_xor"):
            return (zi, zi)
        if self.op in ("sum", "avg"):
            return (zi, zv)
        if self.op == "first":
            return (zi, zv, zi)
        if self.op == "var_pop":
            return (zi, zv, np.zeros(0, dtype=np.float64))
        if self.op in ("min", "max"):
            return (zi, zv)
        raise AssertionError(self.op)

    def update(self, carry, cols, n_rows, gids, active, capacity, offset=0):
        """One block update. ``active``: row mask after selection+validity;
        ``offset``: the block's global first-valid-row index (used only by
        order-sensitive aggregates like ``first``)."""
        if self.rpn is None:
            data, nulls = None, None
            live = active
        else:
            data, nulls = eval_rpn(self.rpn, cols, n_rows, xp=jnp)
            live = active & ~nulls
        seg = lambda x: _seg_sum(x, gids, capacity)
        cnt = carry[0] + seg(live.astype(jnp.int64))
        if self.op == "count":
            return (cnt,)
        if self.op in ("bit_and", "bit_or", "bit_xor"):
            ident = jnp.int64(_BIT_IDENT[self.op])
            masked = jnp.where(live, data, ident)
            blockv = _seg_bitop(masked, gids, capacity, self.op)
            return (cnt, _BIT_FN[self.op](carry[1], blockv))
        vals = jnp.where(live, data, jnp.zeros_like(data))
        if self.op in ("sum", "avg"):
            return (cnt, carry[1] + seg(vals))
        if self.op == "first":
            # first live row's value per group, in stream order: per block a
            # segment-min of the live local row index picks the candidate, a
            # capacity-sized gather reads its value, and the carry keeps
            # whichever global index is smaller
            lidx = jnp.where(live, jnp.arange(n_rows, dtype=jnp.int64), jnp.int64(n_rows))
            blk_local = _seg_extreme(lidx, gids, capacity, True, n_rows)
            safe = jnp.clip(blk_local, 0, n_rows - 1)
            blk_val = data[safe]
            blk_global = jnp.where(blk_local < n_rows, offset + blk_local, _NO_ROW)
            better = blk_global < carry[2]
            return (
                cnt,
                jnp.where(better, blk_val, carry[1]),
                jnp.where(better, blk_global, carry[2]),
            )
        if self.op == "var_pop":
            f = jnp.where(live, data.astype(jnp.float64), 0.0)
            return (cnt, carry[1] + seg(vals), carry[2] + seg(f * f))
        if self.op in ("min", "max"):
            if self.dtype == np.float64:
                ident = jnp.inf if self.op == "min" else -jnp.inf
            else:
                info = np.iinfo(np.int64)
                ident = info.max if self.op == "min" else info.min
            masked = jnp.where(live, data, jnp.full_like(data, ident))
            blockv = _seg_extreme(masked, gids, capacity, self.op == "min", ident)
            merge = jnp.minimum if self.op == "min" else jnp.maximum
            return (cnt, merge(carry[1], blockv))
        raise AssertionError(self.op)

    def to_state(self, carry, n_groups: int) -> AggState:
        """Fill a CPU AggState from the device carry — finalization then goes
        through the exact same result_columns code as the CPU path."""
        st = AggState(self.op, self.input_type, self.frac)
        st.grow(n_groups)
        count = np.asarray(carry[0])[:n_groups]
        st.count = count.astype(np.int64)
        if self.op in ("sum", "avg"):
            st.sum = np.asarray(carry[1])[:n_groups].astype(st.sum.dtype if len(st.sum) else self.dtype)
        elif self.op == "var_pop":
            st.sum = np.asarray(carry[1])[:n_groups]
            st.sum_sq = np.asarray(carry[2])[:n_groups]
        elif self.op == "first":
            st.value = np.asarray(carry[1])[:n_groups]
            st.has_value = np.asarray(carry[2])[:n_groups] != _NO_ROW
        elif self.op in ("bit_and", "bit_or", "bit_xor"):
            st.value = np.asarray(carry[1])[:n_groups]
        elif self.op in ("min", "max"):
            st.value = np.asarray(carry[1])[:n_groups]
            st.has_value = count > 0
        return st


def _topn_key_operands(data, nulls, desc: bool):
    """[null_rank, key] sort operands reproducing the CPU comparator
    (_row_cmp): NULLs first ascending / last descending.  lax.sort takes
    mixed-dtype operands, so REAL keys stay f64 (negated for desc — exact)
    while int-family keys are int64 (bit-NOT for desc: negating INT64_MIN
    would overflow).  No bitcasts — the TPU compiler's x64 rewriter
    supports neither f64→s64 nor f64→u32.  Null rows pin the key
    to 0 so ties among NULLs fall through to later keys / stream order,
    exactly like the comparator's `continue`; −0 is normalized to +0 so it
    ties +0 the way python float comparison does."""
    if data.dtype == jnp.float64:
        x = data + 0.0  # −0 → +0
        kv = jnp.where(nulls, 0.0, -x if desc else x)
    else:
        v = data.astype(jnp.int64)
        kv = jnp.where(nulls, jnp.int64(0), ~v if desc else v)
    rank = jnp.where(nulls, jnp.int64(1), jnp.int64(0)) if desc else jnp.where(
        nulls, jnp.int64(0), jnp.int64(1)
    )
    return [rank, kv]


_TOPN_SORT_MIN = 2048


def _topn_chunk_rows(k: int, n_rows: int) -> int:
    """Rows merged into the carried best-K per sort.  The TPU compiler's
    time for ``lax.sort`` grows steeply with the sorted length (a few
    seconds at 2,048 rows of six int64 operands, minutes at 65,536), so a
    block is merged in chunks that keep the sort at the smallest power of
    two holding 2K rows, and at least ``_TOPN_SORT_MIN``."""
    size = _TOPN_SORT_MIN
    while size < 2 * k:
        size *= 2
    return min(size - k, n_rows)


def _topn_step(sel_rpns, order_rpns, payload_cols, k, n_rows, cols, n_valid, state,
               params=None):
    """One block of the running top-K merge: compute sort operands for the
    block's rows, then chunk by chunk (``_topn_chunk_rows``) concatenate the
    chunk with the carried best-K, stable-sort lexicographically (rank,
    key1-null, key1, key2-null, key2, …) and keep the first K.  lax.sort is
    stable, state precedes chunk rows and chunks go in stream order, so ties
    resolve in global stream order — exactly the CPU executor's seq
    tie-break.  No scatter, no gather beyond the K-slice."""
    ridx = jnp.arange(n_rows, dtype=jnp.int64)
    active = ridx < n_valid
    for rpn in sel_rpns:
        d, nl = eval_rpn(rpn, cols, n_rows, xp=jnp, params=params)
        active = active & (d != 0) & ~nl
    rank_blk = jnp.where(active, jnp.int64(0), jnp.int64(1))
    operands_blk = [rank_blk]
    for rpn, desc in order_rpns:
        d, nl = eval_rpn(rpn, cols, n_rows, xp=jnp)
        operands_blk += _topn_key_operands(d, nl, desc)
    n_key_ops = len(operands_blk)
    payload_blk = [a for ci in payload_cols for a in cols[ci]]
    m = _topn_chunk_rows(k, n_rows)
    # sort ONLY the key operands plus a row index — every extra sort operand
    # multiplies the bitonic comparator's compile cost; the K payload rows
    # are gathered by index afterwards (tiny gather, not scatter)
    idx = jnp.arange(k + m, dtype=jnp.int64)

    def merge(st, start):
        # rows [start, start+m) of the block.  The last chunk may reach past
        # the block: dynamic_slice then starts earlier, and the rows it
        # shares with the chunk before are ranked out
        take = lambda a: jax.lax.dynamic_slice_in_dim(a, start, m)
        seen = jnp.minimum(start, n_rows - m) + jnp.arange(m, dtype=jnp.int64) < start
        ops = [take(o) for o in operands_blk]
        ops[0] = jnp.where(seen, jnp.int64(1), ops[0])
        merged = [jnp.concatenate([s, b]) for s, b in zip(st, ops)]
        sorted_ops = jax.lax.sort(merged + [idx], num_keys=n_key_ops, is_stable=True)
        top = [op[:k] for op in sorted_ops[:n_key_ops]]
        top_idx = sorted_ops[n_key_ops][:k]
        payload = [
            jnp.concatenate([st[n_key_ops + j], take(p)])[top_idx]
            for j, p in enumerate(payload_blk)
        ]
        return tuple(top + payload), None

    starts = jnp.arange(-(-n_rows // m), dtype=jnp.int64) * m
    state, _ = jax.lax.scan(merge, tuple(state), starts)
    return state


def _pack_leaves(leaves):
    """Stack arbitrary leaves into (int64 matrix, float64 matrix) for a
    single-pull finalize; non-float leaves are widened to int64."""
    ints = [a.astype(jnp.int64) for a in leaves if a.dtype != jnp.float64]
    flts = [a for a in leaves if a.dtype == jnp.float64]
    k = leaves[0].shape[0]
    int_m = jnp.stack(ints) if ints else jnp.zeros((0, k), dtype=jnp.int64)
    flt_m = jnp.stack(flts) if flts else jnp.zeros((0, k), dtype=jnp.float64)
    return int_m, flt_m


def _unpack_leaves(packed, dtypes):
    int_m, flt_m = packed
    int_np = np.asarray(int_m)
    # all-integer states stay ONE pull: each device→host pull is a sync
    flt_np = np.asarray(flt_m) if flt_m.shape[0] else None
    out, ii, fi = [], 0, 0
    for dt in dtypes:
        if dt == np.float64:
            out.append(flt_np[fi])
            fi += 1
        else:
            out.append(int_np[ii].astype(dt))
            ii += 1
    return out


def _pack_state(state):
    """Flatten (first_row, carries) into at most two matrices on device (one
    int64, one float64) — every device→host pull is a blocking round trip,
    so finalize pulls once for all-integer queries, twice with REAL
    aggregates (TPU's x64 emulation cannot bitcast f64 to int lanes).
    Thin wrapper over _pack_leaves so the int/float partition contract has
    exactly one implementation."""
    first_row, carries = state
    return _pack_leaves([first_row] + jax.tree.leaves(carries))


def _unpack_state(packed, state_template):
    """Host-side inverse of _pack_state, restoring the leaf order."""
    first_t, carries_t = state_template
    leaves_t = [first_t] + jax.tree.leaves(carries_t)
    out = _unpack_leaves(packed, [t.dtype for t in leaves_t])
    treedef = jax.tree.structure(carries_t)
    return out[0], jax.tree.unflatten(treedef, out[1:])


class JaxDagEvaluator:
    """Run an eligible DAG over a scan source on the device.

    ``dag`` may be a plan's *shape* (``plan_shape.shape_dag``): the literals
    of its selection are then param nodes, the evaluator holds none of them,
    and every entry point takes the request's ``params``, which the jitted
    programs get as an argument: one executable per shape and geometry,
    whatever the literals.  A ``dag`` with its constants in place has no
    slots and runs with ``params=()``."""

    def __init__(self, dag: DagRequest, block_rows: int = DEFAULT_BLOCK_ROWS,
                 breaker=None):
        self.dag = dag
        self.plan = _analyze(dag)
        self.block_rows = block_rows
        # observatory profile key (docs/observatory.md): the scheduler's
        # plan-signature normalization, so profiles and micro-batches key
        # identically; compile events at this evaluator's jit boundaries
        # carry the sig into the device-cost ledger
        self.obs_sig, self.obs_desc = _obs.dag_sig(dag)
        # optional DeviceCircuitBreaker (copr/breaker.py): the zone path
        # consults it before running and reports its outcome, so repeated
        # zone faults trip to the generic warm path instead of re-crashing
        self.breaker = breaker
        # cost-router steering (docs/cost_router.md): "unary" skips the
        # zone probe for this run; set/cleared around run() by the
        # endpoint — a concurrent mis-read only picks a different
        # byte-identical warm rung
        self.route_hint: str | None = None
        scan = self.plan.scan
        self.schema = [(c.ftype.eval_type, c.ftype.decimal) for c in scan.columns_info]
        self.decoder = (
            RowBatchDecoder(scan.columns_info) if isinstance(scan, TableScan) else None
        )
        self.sel_rpns = (
            [compile_expr(c, self.schema) for c in self.plan.selection.conditions]
            if self.plan.selection
            else []
        )
        agg = self.plan.agg
        if agg is not None:
            self.group_rpns = [compile_expr(g, self.schema) for g in agg.group_by]
            self.device_aggs = [
                _DeviceAgg(a.op, compile_expr(a.expr, self.schema) if a.expr else None)
                for a in agg.agg_funcs
            ]
        else:
            self.group_rpns = []
            self.device_aggs = []
        if self.plan.topn is not None and agg is None:
            self.topn_rpns = [
                (compile_expr(e, self.schema), desc) for e, desc in self.plan.topn.order_by
            ]
        else:
            self.topn_rpns = []
        # which leaf columns must ship to the device
        need: set[int] = set()
        for r in self.sel_rpns:
            need |= r.referenced_columns()
        for da in self.device_aggs:
            if da.rpn is not None:
                need |= da.rpn.referenced_columns()
        if self.topn_rpns:
            # raw TopN outputs whole rows: every schema column is payload
            need |= set(range(len(self.schema)))
            for r, _d in self.topn_rpns:
                need |= r.referenced_columns()
        self.device_cols = sorted(need)
        # columns declared NOT NULL never ship a null mask — the device step
        # folds a constant all-false mask (XLA constant-propagates it away)
        from .datatypes import NOT_NULL_FLAG

        self.nullable_cols = [
            i for i in self.device_cols
            if not (scan.columns_info[i].ftype.flag & NOT_NULL_FLAG)
        ]
        # slot -> whether its lane is the float vector; the selection alone
        # may hold param nodes (plan_shape's rule)
        lanes = {n.index: n.eval_type == EvalType.REAL
                 for r in self.sel_rpns for n in r.nodes if n.kind == "param"}
        if sorted(lanes) != list(range(len(lanes))):
            raise ValueError(f"param slots {sorted(lanes)} are not 0..n-1")
        self.param_float_slots = tuple(lanes[k] for k in range(len(lanes)))
        self._param_vecs: dict[tuple, tuple] = {}
        self._capacity = _GROUP_CAPACITY_START if self.group_rpns else 1
        self._agg_fn_cache: dict[int, object] = {}
        self._run_local = threading.local()

    @property
    def n_params(self) -> int:
        return len(self.param_float_slots)

    def _check_params(self, params) -> None:
        if len(params) != self.n_params:
            raise ValueError(
                f"plan shape has {self.n_params} parameter slots, "
                f"the request brought {len(params)}")

    def param_vectors(self, params):
        """The request's literals as the programs take them: device arrays,
        kept by value (a launch with literals seen before hands the program
        arrays that are already there, where a numpy argument is a transfer
        a launch: 0.08 ms a task on a v5e's host, PERF.md §6, PR 34)."""
        self._check_params(params)
        vecs = self._param_vecs.get(params)
        if vecs is None:
            if len(self._param_vecs) >= _PARAM_VECS_MAX:
                self._param_vecs.clear()
            vecs = self._param_vecs[params] = jax.tree.map(
                jnp.asarray, param_vectors(params, self.param_float_slots))
        return vecs

    def bound_sel_rpns(self, params) -> list[RpnExpression]:
        """The selection with the request's literals back in place, for the
        host-side readers of a plan's constants (zone maps, tile
        classification) and for the rungs that still bake them into their
        programs.  Made per request and never kept: a block pruned by the
        previous query's date would be a wrong answer, not a slow one."""
        self._check_params(params)
        if not params:
            return self.sel_rpns
        return [bind_rpn(r, params) for r in self.sel_rpns]

    @property
    def _cache(self):
        """The block cache of the run in flight ON THIS THREAD.  An endpoint
        keeps one evaluator per plan, and the same plan's requests for
        different regions run at once on the server's connection threads:
        held on the instance, one run's cache answered the other's request."""
        return getattr(self._run_local, "cache", None)

    @_cache.setter
    def _cache(self, cache) -> None:
        self._run_local.cache = cache

    @property
    def _params(self) -> tuple:
        """The literals of the run in flight ON THIS THREAD (as ``_cache``)."""
        return getattr(self._run_local, "params", ())

    @_params.setter
    def _params(self, params) -> None:
        self._run_local.params = params

    # -- jit construction --------------------------------------------------

    def _build_mask_fn(self, enc=None):
        key = ("mask", enc)
        cached = self._agg_fn_cache.get(key)
        if cached is not None:
            return cached
        sel_rpns = self.sel_rpns
        device_cols = self.device_cols
        nullable = self.nullable_cols
        n_rows = self.block_rows

        def mask_fn(col_data, col_nulls, valid, refs, params):
            cols = _build_cols(device_cols, nullable, col_data, col_nulls,
                               n_rows, enc, refs)
            active = valid
            for rpn in sel_rpns:
                d, nl = eval_rpn(rpn, cols, n_rows, xp=jnp, params=params)
                active = active & (d != 0) & ~nl
            return active

        fn = _obs.timed_jit(jax.jit(mask_fn), "jax_eval.mask", "unary",
                            self.obs_sig)
        self._agg_fn_cache[key] = fn
        return fn

    def _build_agg_fn(self, capacity: int):
        """One fused device step per block: selection predicates, aggregate
        updates, AND the per-group first-active-row tracker all inside a
        single jit call, with the carry donated — so the whole block loop is
        async dispatches with ZERO device→host syncs."""
        cached = self._agg_fn_cache.get(capacity)
        if cached is not None:
            return cached
        device_aggs = self.device_aggs
        device_cols = self.device_cols
        nullable = self.nullable_cols
        sel_rpns = self.sel_rpns
        n_rows = self.block_rows
        track_first = bool(self.group_rpns)

        def agg_fn(col_data, col_nulls, n_valid, gids, block_offset, state, params):
            cols = _build_cols(device_cols, nullable, col_data, col_nulls, n_rows)
            return _fused_step(
                sel_rpns, device_aggs, capacity, n_rows, cols, n_valid, gids, block_offset, state,
                track_first=track_first, params=params,
            )

        fn = _obs.timed_jit(jax.jit(agg_fn, donate_argnums=(5,)),
                            "jax_eval.agg_step", "unary", self.obs_sig)
        self._agg_fn_cache[capacity] = fn
        return fn

    def _build_scan_fn(self, capacity: int, n_blocks: int, enc=None):
        """Whole-query device program for the warm-cache path: one jit call
        lax.scans the fused block step over ALL resident blocks — a single
        host→device round trip per query."""
        key = ("scan", capacity, n_blocks, enc)
        cached = self._agg_fn_cache.get(key)
        if cached is not None:
            return cached
        device_aggs = self.device_aggs
        device_cols = self.device_cols
        nullable = self.nullable_cols
        sel_rpns = self.sel_rpns
        n_rows = self.block_rows
        track_first = bool(self.group_rpns)

        def scan_fn(col_data, col_nulls, n_valids, gids, offsets, refs, params):
            state = (
                jnp.full(capacity, _NO_ROW, dtype=jnp.int64),
                tuple(da.init_carry(capacity) for da in device_aggs),
            )

            def body(st, xs):
                cd, cn, nv, g, off = xs
                cols = _build_cols(device_cols, nullable, cd, cn, n_rows, enc, refs)
                return _fused_step(sel_rpns, device_aggs, capacity, n_rows, cols, nv, g, off, st,
                                   track_first=track_first, params=params), None

            state, _ = jax.lax.scan(body, state, (col_data, col_nulls, n_valids, gids, offsets))
            # pack everything into ONE int64 matrix: each device→host pull
            # is a blocking round trip, so finalize must pull once
            return _pack_state(state)

        fn = _obs.timed_jit(jax.jit(scan_fn), "jax_eval.scan", "unary",
                            self.obs_sig)
        self._agg_fn_cache[key] = fn
        return fn

    def _build_scan_fn_coded(self, dict_lens: tuple, capacity: int, n_blocks: int, group_cols: list, enc=None):
        """Warm-path whole-query program where group ids are computed ON the
        device from resident dictionary codes (stable dictionaries): zero
        per-row host→device traffic per query."""
        key = ("scancoded", dict_lens, capacity, n_blocks, enc)
        cached = self._agg_fn_cache.get(key)
        if cached is not None:
            return cached
        device_aggs = self.device_aggs
        ship_cols = self._ship_cols(group_cols)
        nullable = self.nullable_cols
        sel_rpns = self.sel_rpns
        n_rows = self.block_rows
        track_first = bool(self.group_rpns)

        def scan_fn(col_data, col_nulls, n_valids, offsets, refs, params):
            state = (
                jnp.full(capacity, _NO_ROW, dtype=jnp.int64),
                tuple(da.init_carry(capacity) for da in device_aggs),
            )

            def body(st, xs):
                cd, cn, nv, off = xs
                cols = _build_cols(ship_cols, nullable, cd, cn, n_rows, enc, refs)
                gids = _mixed_radix_gids(cols, group_cols, dict_lens, n_rows)
                return _fused_step(sel_rpns, device_aggs, capacity, n_rows, cols, nv, gids, off, st,
                                   track_first=track_first, params=params), None

            state, _ = jax.lax.scan(body, state, (col_data, col_nulls, n_valids, offsets))
            return _pack_state(state)

        fn = _obs.timed_jit(jax.jit(scan_fn), "jax_eval.scan_coded", "unary",
                            self.obs_sig)
        self._agg_fn_cache[key] = fn
        return fn

    def _ship_cols(self, extra: list) -> list:
        return self.device_cols + [i for i in extra if i not in self.device_cols]

    def ship_extra_columns(self, extra) -> None:
        """Permanently extend the shipped column set (mesh evaluators build
        group dictionaries ON device, so group-by columns must ship even
        though the single-device path codes them on the host).  Keeps
        nullable_cols consistent — the NOT_NULL rule lives only here."""
        from .datatypes import NOT_NULL_FLAG

        need = set(self.device_cols) | set(extra)
        self.device_cols = sorted(need)
        scan = self.plan.scan
        self.nullable_cols = [
            i for i in self.device_cols
            if not (scan.columns_info[i].ftype.flag & NOT_NULL_FLAG)
        ]
        # derived jit caches keyed on the column set are now stale
        self._agg_fn_cache = {}

    def _stable_dict_group_cols(self, blocks):
        """If every group expr is a bare ref to a dict-encoded column whose
        dictionary object is shared by ALL cached blocks, return (col_idx
        list, dict list) — else None.  No group-by at all qualifies trivially
        (single slot, no codes needed) — crucially this keeps the zero-
        per-row-transfer path for simple aggregations."""
        if not self.group_rpns:
            return [], []
        idxs = []
        for g in self.group_rpns:
            if len(g.nodes) != 1 or g.nodes[0].kind != "col":
                return None
            idxs.append(g.nodes[0].index)
        dicts = []
        for i in idxs:
            c0 = blocks[0].cols[i]
            if not c0.is_dict_encoded:
                return None
            for b in blocks[1:]:
                if b.cols[i].dictionary is not c0.dictionary:
                    return None
            dicts.append(c0.dictionary)
        cap = 1
        for d in dicts:
            cap *= len(d) + 1
        if cap > (1 << 20):
            return None
        return idxs, dicts

    def _run_aggregated_cached(self, cache) -> SelectResponse:
        """Warm path: every block resident on device, one dispatch total.

        Tries the zone-tiled clustered layout first (jax_zone.py): group-
        clustered, range-sorted, narrowed tiles whose full/empty/partial
        classification turns most of the work into pure unmasked reductions.
        Falls back to the generic stacked-block scan when the plan or the
        data shape isn't zone-eligible."""
        blocks = cache.blocks
        n_blocks = len(blocks)

        # an image that takes writes answers from its stacked blocks: the
        # zone layout is rebuilt from the whole image, per version
        zone_resp = (None if self.route_hint == "unary" or getattr(cache, "folded", False)
                     else self._try_zone(cache))
        if zone_resp is not None:
            # observatory path marker (docs/observatory.md): the endpoint
            # reads which warm rung actually served, per response
            zone_resp._obs_path = "zone"
            return zone_resp

        # zone-map pruning (docs/zone_maps.md): the stacked programs keep
        # their compile keys — survivor counts ship through the dynamic
        # ``n_valids`` geometry they already consume, so a pruned block's
        # rows are all invalid and contribute to no aggregate or group
        keep, prune_stats = self._prune_keep(cache, "unary")

        stable = self._stable_dict_group_cols(blocks)
        if stable is not None:
            group_cols, dicts = stable
            dict_lens = tuple(len(d) for d in dicts)
            n_slots = 1
            for dl in dict_lens:
                n_slots *= dl + 1
            capacity = 1
            while capacity < n_slots:
                capacity *= 2
            ship = self._ship_cols(group_cols)
            col_data, col_nulls, refs, enc = self._stacked_device(cache, blocks, ship)
            nv_dev, off_dev = self._nvoff_device(cache, blocks)
            if keep is not None:
                nv_dev = _masked_nv(blocks, keep)
            scan_fn = self._build_scan_fn_coded(dict_lens, capacity, n_blocks, group_cols, enc)
            packed = scan_fn(col_data, col_nulls, nv_dev, off_dev, refs,
                             self.param_vectors(self._params))
            with trace.stage("device.pull"):
                state_np = _unpack_state(packed, self._host_state_template())

            def key_of(slot: int) -> tuple:
                parts = []
                rem = int(slot)
                for d, dl in zip(reversed(dicts), reversed(dict_lens)):
                    c = rem % (dl + 1)
                    rem //= dl + 1
                    parts.append(None if c == dl else bytes(d[c]))
                return tuple(reversed(parts))

            resp = self._finalize_agg(state_np, n_slots, key_of)
            resp._obs_encoding = "encoded" if enc else "plain"
            if prune_stats[0]:
                resp._obs_prune = prune_stats
            return resp

        groups = GroupDict()
        all_gids = np.zeros((n_blocks, self.block_rows), dtype=np.int32)
        for bi, blk in enumerate(blocks):
            if self.group_rpns and (keep is None or keep[bi]):
                # pruned blocks skip host gid assignment too: none of their
                # rows can be active, and groups they alone would introduce
                # stay empty and drop at finalize either way
                gids_np, _ = self._assign_gids(blk.cols, blk.n_valid, groups)
                all_gids[bi] = gids_np
        n_slots = len(groups) if self.group_rpns else 1
        capacity = _GROUP_CAPACITY_START if self.group_rpns else 1
        while capacity < n_slots:
            capacity *= 2

        col_data, col_nulls, refs, enc = self._stacked_device(cache, blocks, self.device_cols)
        nv_dev, off_dev = self._nvoff_device(cache, blocks)
        if keep is not None:
            nv_dev = _masked_nv(blocks, keep)
        scan_fn = self._build_scan_fn(capacity, n_blocks, enc)
        packed = scan_fn(col_data, col_nulls, nv_dev, all_gids, off_dev, refs,
                         self.param_vectors(self._params))
        with trace.stage("device.pull"):
            state_np = _unpack_state(packed, self._host_state_template())
        resp = self._finalize_agg(state_np, n_slots, lambda r: groups.rows[r])
        resp._obs_encoding = "encoded" if enc else "plain"
        if prune_stats[0]:
            resp._obs_prune = prune_stats
        return resp

    def _try_zone(self, cache) -> SelectResponse | None:
        """ONE definition of the zone-path protocol: probe, run, finalize.

        try_run owns the crash-fallback protocol (failures recorded and
        remembered inside ZoneEvaluator), so a None here simply means
        "serve through the generic warm path"."""
        zone = self._zone_evaluator()
        if zone is None:
            return None
        out = zone.try_run(cache, self._params)
        if out is None:
            return None
        state_np, n_slots, key_of = out
        return self._finalize_agg(state_np, n_slots, key_of)

    def _zone_evaluator(self):
        """Lazily constructed zone-path runner (None when plainly ineligible)."""
        zone = getattr(self, "_zone", None)
        if zone is False:
            return None
        if zone is None:
            from .jax_zone import ZoneEvaluator, _ZONE_AGG_OPS

            if self.plan.agg is None or any(
                da.op not in _ZONE_AGG_OPS for da in self.device_aggs
            ):
                self._zone = False
                return None
            zone = self._zone = ZoneEvaluator(self)
        return zone

    def _host_state_template(self):
        return (
            np.zeros(0, dtype=np.int64),
            tuple(da.host_template() for da in self.device_aggs),
        )

    def _prune_keep(self, cache, path: str):
        """(keep_mask | None, (examined, pruned)) for a warm cache under
        this plan's selection conjuncts (copr/zone_maps.py) — the prune
        planner sitting between ``encoding.device_plan`` and the
        launchers.  None keep means "prune proved nothing": callers run
        their exact pre-zone-map path."""
        from . import zone_maps as _zm

        if cache is None or not getattr(cache, "filled", False) or not cache.blocks:
            return None, (0, 0)
        stats = _zm.PruneStats()
        keep = _zm.prune_blocks(cache, self.bound_sel_rpns(self._params),
                                path=path, stats=stats)
        return keep, (stats.examined, stats.pruned)

    def _nvoff_device(self, cache, blocks):
        """Per-cache pinned n_valids / offsets device arrays."""
        sig = ("nvoff", self.block_rows)

        def build(_blk):
            note_blocking("device.pin:nvoff")
            nv, off = nv_off(blocks)
            return jax.block_until_ready((jnp.asarray(nv), jnp.asarray(off)))

        return cache.device_arrays(blocks[0], sig, build)

    def _stacked_device(self, cache, blocks, ship_cols, nullable_cols=None,
                        plan=_MISSING_PLAN):
        """(B, n_rows)-stacked device arrays for the given columns, pinned
        in the cache so later queries reuse them without any transfer.

        Returns ``(data, nulls, refs, enc)``: with an encoding plan
        (``copr/encoding.py``) the pinned arrays are the ENCODED payloads
        (narrow lanes, run pairs) plus the dynamic frame-of-reference
        vector, and ``enc`` is the static descriptor tuple callers bake
        into their jit keys; plain images pin exactly as before
        (``refs``/``enc`` = None)."""
        from . import encoding as _encoding

        nullable = self.nullable_cols if nullable_cols is None else nullable_cols
        if plan is _MISSING_PLAN:
            plan = _encoding.device_plan(cache, ship_cols, nullable)
        if plan is None:
            sig = ("stacked", tuple(ship_cols), tuple(nullable), self.block_rows)

            def build(_blk):
                note_blocking("device.pin:stacked")
                # decoded_data/nulls: a decode-SHIP of an encoded image
                # (cross-region signature mismatch) must not leave a full
                # decode cached on the column — the budget counts encoded
                data = tuple(
                    jnp.stack([jnp.asarray(self._pad(_encoding.decoded_data(b.cols[i]))) for b in blocks])
                    for i in ship_cols
                )
                nulls = tuple(
                    jnp.stack([jnp.asarray(self._pad(_encoding.decoded_nulls(b.cols[i]), True)) for b in blocks])
                    for i in nullable
                )
                return jax.block_until_ready((data, nulls))

            data, nulls = cache.device_arrays(blocks[0], sig, build)
            return data, nulls, None, None
        sig = ("stackedenc", tuple(ship_cols), tuple(nullable),
               self.block_rows, plan.sig, plan.null_sig)

        def build_enc(_blk):
            note_blocking("device.pin:stacked_encoded")
            data, nulls, refs = _encoding.stack_block_payloads(
                blocks, ship_cols, nullable, plan, self.block_rows)
            entry = jax.tree.map(jnp.asarray, (tuple(data), tuple(nulls), refs))
            return jax.block_until_ready(entry)

        data, nulls, refs = cache.device_arrays(blocks[0], sig, build_enc)
        return data, nulls, refs, plan.sig

    # -- host loop ---------------------------------------------------------

    def run(self, source: ScanSource, cache: "ColumnBlockCache | None" = None,
            params: tuple = ()) -> SelectResponse:
        self._check_params(params)
        self._cache = cache
        self._params = params
        if self.plan.agg is not None:
            path = "agg_cached" if (cache is not None and cache.filled
                                    and cache.blocks) else "agg"
        elif self.topn_rpns:
            path = "topn"
        else:
            path = "scan"
        try:
            # a container: its stages are device.prepare/launch/pull/finalize
            # (a compile shows as device.launch's tag ``compiled``)
            with trace.span("device.run", path=path):
                if self.plan.agg is not None:
                    if cache is not None and cache.filled and cache.blocks:
                        return self._run_aggregated_cached(cache)
                    return self._run_aggregated(source)
                if self.topn_rpns:
                    return self._run_topn(source)
                return self._run_scan_filter(source)
        finally:
            self._cache = None
            self._params = ()

    def _blocks(self, source: ScanSource | None):
        """Decoded blocks, through the block cache when one is provided.
        Cold scans (no cache) run the host MVCC decode ONE BLOCK AHEAD on a
        worker thread (SURVEY §7's double-buffering): block N executes on
        the device while block N+1 decodes — the decode cost hides behind
        device time instead of adding to it."""
        cache = self._cache
        if cache is None:
            if source is None:
                raise ValueError("no scan source and no filled block cache")
            yield from _prefetch(self._decode_blocks(source))
            return
        if not cache.filled:
            if source is None:
                raise ValueError("block cache is not filled and no source given")
            for cols, n_valid in _prefetch(self._decode_blocks(source)):
                cache.add(cols, n_valid)
            cache.filled = True
        yield from cache

    def _device_block(self, cols, n_valid):
        """(col_data, col_nulls, refs, enc) device-ready arrays; served
        from the block cache's HBM-pinned entries when a cache is active —
        as ENCODED payloads (narrow lanes / runs) when the image is encoded
        (copr/encoding.py), so per-block warm serving pins encoded HBM
        too."""
        from . import encoding as _encoding

        cache = self._cache
        build = lambda blk: (
            [jnp.asarray(self._pad(blk.cols[i].data)) for i in self.device_cols],
            [jnp.asarray(self._pad(blk.cols[i].nulls, True)) for i in self.nullable_cols],
        )
        if cache is not None and cache.filled:
            plan = _encoding.device_plan(cache, self.device_cols, self.nullable_cols)
            for blk in cache.blocks:
                if blk.cols is cols:
                    if plan is None:
                        sig = (tuple(self.device_cols), tuple(self.nullable_cols), self.block_rows)
                        d, nl = cache.device_arrays(blk, sig, build)
                        return d, nl, None, None
                    sig = ("blockenc", tuple(self.device_cols),
                           tuple(self.nullable_cols), self.block_rows,
                           plan.sig, plan.null_sig)

                    def build_enc(blk):
                        note_blocking("device.pin:block_encoded")
                        br = self.block_rows
                        data = []
                        for j, i in enumerate(self.device_cols):
                            p = _encoding.block_payload(blk.cols[i], br)
                            data.append(
                                (jnp.asarray(p[0]), jnp.asarray(p[1]))
                                if plan.sig[j][0] == "rle" else jnp.asarray(p)
                            )
                        nulls = [
                            jnp.asarray(_encoding.block_null_payload(blk.cols[i], br))
                            for i in self.nullable_cols
                        ]
                        return jax.block_until_ready(
                            (data, nulls, jnp.asarray(plan.refs)))

                    d, nl, refs = cache.device_arrays(blk, sig, build_enc)
                    return d, nl, refs, plan.sig
        col_data = [self._pad(cols[i].data) for i in self.device_cols]
        col_nulls = [self._pad(cols[i].nulls, True) for i in self.nullable_cols]
        return col_data, col_nulls, None, None

    def _decode_blocks(self, source: ScanSource):
        """Yield (columns, n_valid) blocks of exactly block_rows rows (padded)."""
        if isinstance(self.plan.scan, IndexScan):
            yield from self._decode_blocks_index(source)
            return
        br = self.block_rows
        pend_handles: list[np.ndarray] = []
        pend_values: list[bytes] = []
        drained = False
        while not drained:
            keys, values, drained = source.next_batch(br)
            if keys:
                pend_handles.append(decode_record_handles(keys))
                pend_values.extend(values)
            total = sum(len(x) for x in pend_handles)
            while total >= br or (drained and total > 0):
                handles = np.concatenate(pend_handles) if len(pend_handles) > 1 else pend_handles[0]
                take = min(br, total)
                block_h, rest_h = handles[:take], handles[take:]
                block_v, rest_v = pend_values[:take], pend_values[take:]
                pend_handles = [rest_h] if len(rest_h) else []
                pend_values = rest_v
                total = len(rest_h)
                cols = self.decoder.decode(block_h, block_v)
                yield cols, take

    def _decode_blocks_index(self, source: ScanSource):
        """Index-scan leaf (index_scan_executor.rs:29): decode index entries
        through the same BatchIndexScanExecutor the CPU pipeline uses, then
        re-block its chunks to exactly block_rows rows so the device step
        sees the fixed shapes it compiled for."""
        from .executors import BatchIndexScanExecutor
        from .table import index_range

        scan = self.plan.scan
        prefix_len = len(index_range(scan.table_id, scan.index_id)[0])
        ex = BatchIndexScanExecutor(source, scan.columns_info, prefix_len)
        br = self.block_rows
        pend: list = []  # list of column lists
        total = 0
        drained = False
        while not drained:
            r = ex.next_batch(br)
            drained = r.is_drained
            chunk = r.chunk
            n = len(chunk.columns[0]) if chunk.columns else 0
            if n:
                pend.append(chunk.columns)
                total += n
            while total >= br or (drained and total > 0):
                take = min(br, total)
                cols: list[Column] = []
                rest: list[Column] = []
                for ci in range(len(scan.columns_info)):
                    parts = [p[ci] for p in pend]
                    data = np.concatenate([np.asarray(c.data) for c in parts])
                    nulls = np.concatenate([np.asarray(c.nulls) for c in parts])
                    cols.append(
                        Column(parts[0].eval_type, data[:take], nulls[:take], parts[0].frac)
                    )
                    if total > take:
                        rest.append(
                            Column(parts[0].eval_type, data[take:], nulls[take:], parts[0].frac)
                        )
                pend = [rest] if total > take else []
                total -= take
                yield cols, take

    def _pad(self, arr: np.ndarray, fill=0) -> np.ndarray:
        n = len(arr)
        if n == self.block_rows:
            return arr
        pad = self.block_rows - n
        if arr.dtype == object:
            ext = np.empty(pad, dtype=object)
            ext[:] = b""
            return np.concatenate([arr, ext])
        return np.concatenate([arr, np.full(pad, fill, dtype=arr.dtype)])

    def _run_aggregated(self, source: ScanSource) -> SelectResponse:
        """Block loop with no device→host traffic until finalize.

        Group ids are assigned on host over ALL valid rows (pre-selection):
        groups whose every row the device filters out end up with
        ``first_row == _NO_ROW`` and are dropped at finalize, and surviving
        groups are ordered by their first *active* row — so the output is
        byte-identical to the CPU path without ever pulling the mask back.
        """
        groups = GroupDict()
        capacity = self._capacity
        agg_fn = self._build_agg_fn(capacity)
        pvec = self.param_vectors(self._params)
        carries = tuple(da.init_carry(capacity) for da in self.device_aggs)
        first_row = jnp.full(capacity, _NO_ROW, dtype=jnp.int64)
        state = (first_row, carries)
        offset = 0

        for cols, n_valid in self._blocks(source):
            # cold/COP-cache blocks are always decoded (only region images
            # encode, and those route through _run_aggregated_cached); if an
            # encoded image ever lands here, ship it decoded — this block
            # step compiles without the in-kernel decode
            col_data, col_nulls, _refs, _enc = self._device_block(cols, n_valid)
            if _enc is not None:
                # unreachable today: run() routes every filled cache to
                # _run_aggregated_cached and only region images encode —
                # but this block step compiles WITHOUT the in-kernel
                # decode, so silently feeding it narrow lanes would be
                # wrong math; fail loudly and let the endpoint's CPU
                # fallback serve
                raise RuntimeError("encoded image reached the cold block path")
            if self.group_rpns:
                gids_np, n_groups = self._assign_gids(cols, n_valid, groups)
                if n_groups > capacity:
                    # grow to the next bucket and re-jit once; state migrates
                    new_capacity = capacity
                    while n_groups > new_capacity:
                        new_capacity *= 2
                    old_first, old_carries = state
                    new_first = jnp.full(new_capacity, _NO_ROW, dtype=jnp.int64)
                    new_first = new_first.at[:capacity].set(old_first)
                    new_carries = tuple(
                        _grow_carry(da, c, new_capacity)
                        for da, c in zip(self.device_aggs, old_carries)
                    )
                    state = (new_first, new_carries)
                    capacity = new_capacity
                    self._capacity = capacity
                    agg_fn = self._build_agg_fn(capacity)
            else:
                gids_np = _ZERO_GIDS.setdefault(self.block_rows, np.zeros(self.block_rows, dtype=np.int32))
            state = agg_fn(col_data, col_nulls, n_valid, gids_np, offset, state, pvec)
            offset += n_valid

        n_slots = len(groups) if self.group_rpns else 1
        pack_key = ("pack", capacity)
        pack_fn = self._agg_fn_cache.get(pack_key)
        if pack_fn is None:
            pack_fn = _obs.timed_jit(jax.jit(_pack_state), "jax_eval.pack",
                                     "unary", self.obs_sig)
            self._agg_fn_cache[pack_key] = pack_fn
        packed = pack_fn(state)
        with trace.stage("device.pull"):
            state_np = _unpack_state(packed, state)
        return self._finalize_agg(state_np, n_slots, lambda r: groups.rows[r])

    def _finalize_agg(self, state, n_slots: int, key_of) -> SelectResponse:
        with trace.stage("device.finalize"):
            return self._finalize_agg_pulled(state, n_slots, key_of)

    def _finalize_agg_pulled(self, state, n_slots: int, key_of) -> SelectResponse:
        first_row, carries = state
        first_np = np.asarray(first_row)
        alive = np.flatnonzero(first_np[:n_slots] != _NO_ROW) if self.group_rpns else np.array([0])
        if self.group_rpns:
            order = alive[np.argsort(first_np[alive], kind="stable")]
        else:
            order = alive
        states = [
            da.to_state(jax.tree.map(np.asarray, c), n_slots)
            for da, c in zip(self.device_aggs, carries)
        ]
        out_cols: list[Column] = []
        for st in states:
            for c in st.result_columns(n_slots):
                out_cols.append(c.take(order))
        for gi, g in enumerate(self.group_rpns):
            vals = [key_of(r)[gi] for r in order]
            out_cols.append(Column.from_values(g.eval_type, vals, g.frac))
        chunk = Chunk.full(out_cols)
        # post-agg TopN / Limit are tiny — run them via the CPU executors
        chunk = self._post_agg(chunk)
        enc = make_response_encoder(self.dag)
        enc.add_chunk(chunk, self.dag.output_offsets)
        return enc.to_response()

    def _assign_gids(self, cols, n_valid: int, groups: GroupDict):
        from .executors import _coded_group_parts, cols_for_eval

        rows = np.arange(n_valid)
        # bare dict-encoded group columns: dense-code path, no unique pass
        coded = _coded_group_parts(self.group_rpns, cols, rows)
        if coded is not None:
            gids = np.zeros(self.block_rows, dtype=np.int32)
            if len(coded) == 1:
                gids[:n_valid] = groups.assign_coded(*coded[0])
            else:
                gids[:n_valid] = groups.assign_coded_multi(coded)
            return gids, len(groups)
        needed = set()
        for g in self.group_rpns:
            needed |= g.referenced_columns()
        n = len(cols[0]) if cols else 0
        np_cols = cols_for_eval(cols, needed)
        parts = []
        for g in self.group_rpns:
            d, nl = eval_rpn(g, np_cols, n, xp=np)
            parts.append((np.asarray(d)[:n_valid], np.asarray(nl)[:n_valid]))
        gids = np.zeros(self.block_rows, dtype=np.int32)
        gids[:n_valid] = groups.assign(parts)
        return gids, len(groups)

    def _post_agg(self, chunk: Chunk) -> Chunk:
        """Apply TopN/Limit over the (small) aggregated output on host."""
        schema = None
        if self.plan.topn is not None:
            agg_schema = self._agg_output_schema()
            ex = BatchTopNExecutor(_ChunkExecutor(chunk, agg_schema), self.plan.topn.order_by, self.plan.topn.limit)
            chunk = ex.next_batch(len(chunk.logical_rows) or 1).chunk
        if self.plan.limit is not None:
            chunk = Chunk(chunk.columns, chunk.logical_rows[: self.plan.limit.limit])
        return chunk

    def _agg_output_schema(self):
        out = []
        for da, a in zip(self.device_aggs, self.plan.agg.agg_funcs):
            it, frac = da.input_type, da.frac
            if a.op == "count":
                out.append((EvalType.INT, 0))
            elif a.op == "avg":
                out.append((EvalType.INT, 0))
                out.append((it, frac))
            elif a.op == "var_pop":
                out.extend([(EvalType.INT, 0), (EvalType.REAL, 0), (EvalType.REAL, 0)])
            elif a.op in ("bit_and", "bit_or", "bit_xor"):
                out.append((EvalType.INT, 0))
            else:
                out.append((it, frac))
        for g in self.group_rpns:
            out.append((g.eval_type, g.frac))
        return out

    # -- raw TopN pipeline -------------------------------------------------

    def _topn_key_operand_count(self) -> int:
        return 1 + 2 * len(self.topn_rpns)  # global rank + (null-rank, key) each

    def _topn_state_dtypes(self):
        dts = [np.int64]
        for rpn, _desc in self.topn_rpns:
            dts += [np.int64, _np_dtype(rpn.eval_type)]
        for ci in range(len(self.schema)):
            dts += [_np_dtype(self.schema[ci][0]), np.bool_]
        return dts

    def _build_topn_fn(self, k: int, enc=None):
        key = ("topn", k, enc)
        cached = self._agg_fn_cache.get(key)
        if cached is not None:
            return cached
        sel_rpns = self.sel_rpns
        order_rpns = self.topn_rpns
        device_cols = self.device_cols
        nullable = self.nullable_cols
        n_rows = self.block_rows
        payload_cols = list(range(len(self.schema)))

        def step(col_data, col_nulls, n_valid, state, refs, params):
            cols = _build_cols(device_cols, nullable, col_data, col_nulls,
                               n_rows, enc, refs)
            return _topn_step(
                sel_rpns, order_rpns, payload_cols, k, n_rows, cols, n_valid, state,
                params=params,
            )

        fn = _obs.timed_jit(jax.jit(step, donate_argnums=(3,)),
                            "jax_eval.topn", "unary", self.obs_sig)
        self._agg_fn_cache[key] = fn
        return fn

    def _run_topn(self, source: ScanSource) -> SelectResponse:
        """TableScan → Selection? → TopN (no aggregation): a running top-K
        lives ON the device — per block one fused dispatch computes selection
        + sort operands and stable-sort-merges the carried best K, so the
        whole query is async dispatches plus ONE packed pull of K rows.
        The sort-operand encoding reproduces the CPU executor's comparator
        bit-for-bit, so responses stay byte-identical."""
        k = self.plan.topn.limit
        if self.plan.limit is not None:
            k = min(k, self.plan.limit.limit)
        if k == 0:
            return make_response_encoder(self.dag).to_response()
        dtypes = self._topn_state_dtypes()
        jdt = {np.float64: jnp.float64, np.bool_: jnp.bool_}
        state = tuple(
            # empty slots carry rank 1 (sorted last, excluded at finalize)
            (jnp.ones if i == 0 else jnp.zeros)(k, dtype=jdt.get(dt, jnp.int64))
            for i, dt in enumerate(dtypes)
        )
        bytes_cols = [
            ci for ci, (et, _f) in enumerate(self.schema) if et == EvalType.BYTES
        ]
        payload_dicts: dict[int, np.ndarray] = {}
        step = None
        pvec = self.param_vectors(self._params)
        cache = self._cache
        keep, prune_stats = self._prune_keep(cache, "unary")
        # zone-order early exit (docs/zone_maps.md): with no selection and a
        # bare-column first sort key, zone bounds alone can prove which
        # blocks may still contribute to the top-k — the rest never launch.
        # Blocks stay in STREAM order (tie-breaks are stream-ordered), only
        # provably-dominated ones drop out, so the bytes cannot change.
        if (cache is not None and cache.filled and cache.blocks
                and not self.sel_rpns):
            from . import zone_maps as _zm

            rpn0, desc0 = self.topn_rpns[0]
            if (_zm.enabled() and len(rpn0.nodes) == 1
                    and rpn0.nodes[0].kind == "col"
                    and _zm.ensure_zones(cache)):
                base = (keep if keep is not None
                        else np.ones(len(cache.blocks), dtype=bool))
                cut = _zm.topn_cutoff_order(
                    cache.blocks, base, rpn0.nodes[0].index, bool(desc0), k)
                exited = int((base & ~cut).sum()) if cut is not None else 0
                if exited:
                    keep = cut
                    _zm.count_prune("unary", "early_exit", exited)
                    prune_stats = (prune_stats[0] or len(cache.blocks),
                                   prune_stats[1] + exited)
        for bi, (cols, n_valid) in enumerate(self._blocks(source)):
            for ci in bytes_cols:
                # BYTES payloads ride as dictionary codes; every block must
                # agree on the dictionary or the codes are meaningless (the
                # endpoint's CPU fallback catches this raise)
                d = cols[ci].dictionary
                if d is None:
                    raise ValueError(f"TopN BYTES payload column {ci} not dict-coded")
                seen = payload_dicts.setdefault(ci, d)
                if seen is not d and (
                    len(seen) != len(d) or any(a != b for a, b in zip(seen, d))
                ):
                    raise ValueError(f"TopN BYTES payload column {ci}: unstable dictionary")
            if keep is not None and not keep[bi]:
                continue  # zone-pruned / dominated: contributes no top-k row
            col_data, col_nulls, refs, enc_sig = self._device_block(cols, n_valid)
            if step is None:
                # the encoding signature is uniform across one source's
                # blocks (images encode image-wide), so the first block
                # fixes the compiled program
                step = self._build_topn_fn(k, enc_sig)
            state = step(col_data, col_nulls, n_valid, state, refs, pvec)
        pack_key = ("packtopn", k)
        pack_fn = self._agg_fn_cache.get(pack_key)
        if pack_fn is None:
            pack_fn = self._agg_fn_cache[pack_key] = _obs.timed_jit(
                jax.jit(lambda st: _pack_leaves(list(st))),
                "jax_eval.pack_topn", "unary", self.obs_sig)
        leaves = _unpack_leaves(pack_fn(state), dtypes)
        rank = leaves[0]
        n_out = int((rank == 0).sum())
        base = self._topn_key_operand_count()
        out_cols: list[Column] = []
        for ci, (et, frac) in enumerate(self.schema):
            data = leaves[base + 2 * ci][:n_out]
            nulls = leaves[base + 2 * ci + 1][:n_out]
            out_cols.append(
                Column(et, data, nulls.astype(bool), frac, payload_dicts.get(ci))
            )
        enc = make_response_encoder(self.dag)
        enc.add_chunk(Chunk.full(out_cols), self.dag.output_offsets)
        resp = enc.to_response()
        if prune_stats[0]:
            resp._obs_prune = prune_stats
        return resp

    # -- selection-only pipeline ------------------------------------------

    def _run_scan_filter(self, source: ScanSource) -> SelectResponse:
        """TableScan → Selection? → Limit?: device computes the row mask,
        host compacts + encodes (row encoding is host work either way)."""
        from . import encoding as _encoding

        remaining = self.plan.limit.limit if self.plan.limit else None
        sel_rpns = self.sel_rpns
        mask_jit = None
        pvec = self.param_vectors(self._params)
        # zone-map pruning (docs/zone_maps.md): blocks whose zones prove no
        # row can pass the conjuncts are skipped before any device dispatch
        # — they contribute zero rows to the stream, so the response bytes
        # are identical; with a Limit the loop also reaches its early break
        # having touched only qualifying blocks
        keep, prune_stats = self._prune_keep(self._cache, "unary")
        enc = make_response_encoder(self.dag)
        for bi, (cols, n_valid) in enumerate(self._blocks(source)):
            if keep is not None and not keep[bi]:
                continue
            valid = np.zeros(self.block_rows, dtype=bool)
            valid[:n_valid] = True
            if sel_rpns:
                # served from the block cache's HBM-pinned arrays when one is
                # active — warm selections ship only the valid mask per block
                # (encoded images ship their narrow/run payloads and decode
                # in-kernel; the output below gathers ONLY surviving rows
                # through the encodings — late materialization)
                col_data, col_nulls, refs, enc_sig = self._device_block(cols, n_valid)
                if mask_jit is None:
                    mask_jit = self._build_mask_fn(enc_sig)
                mask = np.asarray(mask_jit(col_data, col_nulls, valid, refs, pvec))
            else:
                mask = valid
            logical = np.flatnonzero(mask[: n_valid])
            if remaining is not None:
                logical = logical[:remaining]
                remaining -= len(logical)
            out_cols, logical = _encoding.late_materialize_chunk(cols, logical)
            chunk = Chunk(out_cols, logical)
            enc.add_chunk(chunk, self.dag.output_offsets)
            if remaining is not None and remaining <= 0:
                break
        resp = enc.to_response()
        if prune_stats[0]:
            resp._obs_prune = prune_stats
        return resp


_BATCH_FN_CACHE: dict = {}
_BATCH_FN_CACHE_MAX = 32


def run_batch_cached(evaluators: list["JaxDagEvaluator"], cache,
                     params_list=None) -> list[SelectResponse]:
    """Fuse K eligible queries over the same cached region into ONE device
    program — the coprocessor's answer to the reference's ``batch_commands``
    multiplexing (service/kv.rs:891) and ``batch_coprocessor`` surface: the
    per-execution and per-pull costs are paid once for the whole batch
    instead of once per query.

    Requirements: every query is an aggregation DAG whose group-by is empty or
    all bare dict-encoded columns with stable dictionaries (the same queries
    the single warm path runs with zero per-row transfers).

    ``params_list``: each query's literals.  This rung still bakes them into
    its program and keys it by them (one executable per set of literals, as
    before plans were split into shape and parameters).
    """
    if params_list is None:
        params_list = [()] * len(evaluators)
    sels = [ev.bound_sel_rpns(p) for ev, p in zip(evaluators, params_list)]
    blocks = cache.blocks
    if not blocks:
        raise ValueError("batched evaluation over an empty block cache")
    n_blocks = len(blocks)

    # Zone-tiled fast path: when EVERY query rides the clustered layout the
    # per-query cost is a handful of pure tile reductions — far below the
    # fused program's shared full-data pass — and the layouts themselves are
    # shared across queries with the same (group, sort) signature.  Cheap
    # eligibility pre-probe first (no device work), then all-or-nothing
    # execution with finalize deferred until every query served — a decline
    # falls back to the fused program with no wasted zone passes.
    def _zone_probe(ev, params):
        zone = ev._zone_evaluator()
        if zone is None or zone.declined(cache, params):
            return None
        return zone if zone.eligible(blocks) is not None else None

    zones = [_zone_probe(ev, p) for ev, p in zip(evaluators, params_list)]
    if all(z is not None for z in zones):
        outs = []
        for ev, zone, params in zip(evaluators, zones, params_list):
            out = zone.try_run(cache, params)  # crash-fallback lives inside try_run
            if out is None:  # late decline (partial-fraction or failure)
                outs = None
                break
            outs.append((ev, out))
        if outs is not None:
            return [
                ev._finalize_agg(state_np, n_slots, key_of)
                for ev, (state_np, n_slots, key_of) in outs
            ]

    specs = []  # (ev, group_cols, dicts, dict_lens, capacity)
    ship: list[int] = []
    for ev in evaluators:
        if ev.plan.agg is None:
            raise ValueError("batched evaluation requires aggregation DAGs")
        stable = ev._stable_dict_group_cols(blocks)
        if ev.group_rpns and stable is None:
            raise ValueError("batched evaluation requires stable dict group keys")
        group_cols, dicts = stable if stable else ([], [])
        dict_lens = tuple(len(d) for d in dicts)
        n_slots = 1
        for dl in dict_lens:
            n_slots *= dl + 1
        capacity = 1
        while capacity < n_slots:
            capacity *= 2
        specs.append((ev, group_cols, dicts, dict_lens, capacity, n_slots))
        for i in ev._ship_cols(group_cols):
            if i not in ship:
                ship.append(i)
    ship = sorted(ship)
    base = evaluators[0]
    nullable = sorted(set().union(*[set(ev.nullable_cols) for ev in evaluators]))
    col_data, col_nulls, refs, enc = base._stacked_device(cache, blocks, ship, nullable)
    if enc is not None:
        from . import encoding as _encoding

        _encoding.count_path("fused", "encoded")
    n_rows = base.block_rows

    key = (
        tuple((id(ev), p) for ev, p in zip(evaluators, params_list)),
        n_blocks,
        tuple(ship),
        n_rows,
        enc,
        # dict radices and capacities are baked into the compiled program —
        # a cache whose dictionaries grew must compile a fresh program
        tuple((spec[3], spec[4]) for spec in specs),
    )
    fn = _BATCH_FN_CACHE.get(key)
    if fn is None:
        def batch_fn(col_data, col_nulls, n_valids, offsets, refs):
            states = tuple(
                (
                    jnp.full(capacity, _NO_ROW, dtype=jnp.int64),
                    tuple(da.init_carry(capacity) for da in ev.device_aggs),
                )
                for (ev, _gc, _d, _dl, capacity, _ns) in specs
            )

            def body(sts, xs):
                cd, cn, nv, off = xs
                cols = _build_cols(ship, nullable, cd, cn, n_rows, enc, refs)
                new_sts = []
                for (ev, group_cols, _dicts, dict_lens, capacity, _ns), sel, st in zip(specs, sels, sts):
                    gids = _mixed_radix_gids(cols, group_cols, dict_lens, n_rows)
                    new_sts.append(
                        _fused_step(
                            sel, ev.device_aggs, capacity, n_rows, cols, nv, gids, off, st,
                            track_first=bool(ev.group_rpns),
                        )
                    )
                return tuple(new_sts), None

            states, _ = jax.lax.scan(body, states, (col_data, col_nulls, n_valids, offsets))
            # ALL queries' states pack into two matrices (int64 + float64)
            # padded to the max capacity — one pull for the whole batch
            max_cap = max(cap for (_e, _g, _d, _dl, cap, _n) in specs)
            ints, flts = [], []
            for st in states:
                first_row, carries = st
                for a in [first_row] + jax.tree.leaves(carries):
                    a = jnp.pad(a, (0, max_cap - a.shape[0]))
                    (flts if a.dtype == jnp.float64 else ints).append(a)
            int_m = jnp.stack(ints)
            flt_m = jnp.stack(flts) if flts else jnp.zeros((0, max_cap), dtype=jnp.float64)
            return int_m, flt_m

        fn = _obs.timed_jit(jax.jit(batch_fn), "jax_eval.fused_batch",
                            "fused", base.obs_sig)
        _BATCH_FN_CACHE[key] = fn
        while len(_BATCH_FN_CACHE) > _BATCH_FN_CACHE_MAX:
            _BATCH_FN_CACHE.pop(next(iter(_BATCH_FN_CACHE)))

    nv_dev, off_dev = base._nvoff_device(cache, blocks)
    keep, prune_stats = _batch_prune_keep(evaluators, cache, params_list)
    if keep is not None:
        # survivor-count geometry: masked blocks ship n_valid == 0, so the
        # fused step's validity masks exclude every one of their rows while
        # the compiled program and its pins stay byte-for-byte identical
        nv_dev = _masked_nv(blocks, keep)
    int_m, flt_m = fn(col_data, col_nulls, nv_dev, off_dev, refs)
    with trace.stage("device.pull"):
        int_np = np.asarray(int_m)
        flt_np = np.asarray(flt_m) if flt_m.shape[0] else None
    out = []
    ii = fi = 0
    for ev, _gc, dicts, dict_lens, cap, n_slots in specs:
        first_t, carries_t = ev._host_state_template()
        leaves_t = [first_t] + jax.tree.leaves(carries_t)
        leaves_np = []
        for t in leaves_t:
            if t.dtype == np.float64:
                leaves_np.append(flt_np[fi][:cap])
                fi += 1
            else:
                leaves_np.append(int_np[ii][:cap])
                ii += 1
        treedef = jax.tree.structure(carries_t)
        state_np = (leaves_np[0], jax.tree.unflatten(treedef, leaves_np[1:]))

        def key_of(slot: int, dicts=dicts, dict_lens=dict_lens) -> tuple:
            parts = []
            rem = int(slot)
            for d, dl in zip(reversed(dicts), reversed(dict_lens)):
                c = rem % (dl + 1)
                rem //= dl + 1
                parts.append(None if c == dl else bytes(d[c]))
            return tuple(reversed(parts))

        out.append(ev._finalize_agg(state_np, n_slots, key_of))
    if prune_stats[0]:
        for resp in out:
            resp._obs_prune = prune_stats
    return out


# ---------------------------------------------------------------------------
# Cross-region batched execution (copr/scheduler.py's device backend)
# ---------------------------------------------------------------------------


class XRegionPending:
    """An in-flight cross-region batch: the device program is dispatched
    (async), the pull has not happened yet.  The scheduler launches batch
    N, prepares batch N+1's caches on the host while N executes, and only
    then calls :meth:`finalize` — double-buffering without threads."""

    def __init__(self, ev: "JaxDagEvaluator", specs, capacity: int, packed,
                 order=None, prunes=None):
        self._ev = ev
        self._specs = specs  # [(dicts, dict_lens, n_slots)] per EXECUTED region
        self._capacity = capacity
        self._packed = packed  # (int_m (R,Li,cap), flt_m (R,Lf,cap)) device
        # executed-position -> caller-position (launch sorts regions by
        # block count to canonicalize the compile key)
        self._order = order
        # per-executed-region (blocks_examined, blocks_pruned) zone-map
        # stats; finalize stamps them on the responses for the observatory
        self._prunes = prunes

    def finalize(self) -> list[SelectResponse]:
        """Pull the packed states (one transfer per dtype matrix for the
        WHOLE batch) and finalize each region through the exact same
        host code as the per-region warm path — so responses stay
        byte-identical to per-request serving."""
        ev = self._ev
        int_m, flt_m = self._packed
        with trace.stage("device.pull", regions=len(self._specs)):
            int_np = np.asarray(int_m)
            flt_np = np.asarray(flt_m) if flt_m.shape[1] else None
        with trace.stage("device.release"):
            # the packed states' device buffers are dropped here and not
            # when the batch is forgotten: freeing them waits on the runtime
            self._packed = None
            del int_m, flt_m
        with trace.stage("device.finalize", regions=len(self._specs)):
            return self._finalize_pulled(int_np, flt_np)

    def _finalize_pulled(self, int_np, flt_np) -> list[SelectResponse]:
        ev = self._ev
        template = ev._host_state_template()
        out = []
        for r, (dicts, dict_lens, n_slots) in enumerate(self._specs):
            packed_r = (int_np[r], flt_np[r] if flt_np is not None
                        else np.zeros((0, self._capacity), dtype=np.float64))
            state_np = _unpack_state(packed_r, template)

            def key_of(slot: int, dicts=dicts, dict_lens=dict_lens) -> tuple:
                parts = []
                rem = int(slot)
                for d, dl in zip(reversed(dicts), reversed(dict_lens)):
                    c = rem % (dl + 1)
                    rem //= dl + 1
                    parts.append(None if c == dl else bytes(d[c]))
                return tuple(reversed(parts))

            resp = ev._finalize_agg_pulled(state_np, n_slots, key_of)
            if self._prunes is not None and self._prunes[r][0]:
                resp._obs_prune = self._prunes[r]
            out.append(resp)
        if self._order is not None:
            restored = [None] * len(out)
            for pos, i in enumerate(self._order):
                restored[i] = out[pos]
            out = restored
        return out


def xregion_specs(ev: "JaxDagEvaluator", caches):
    """Shared eligibility/geometry prologue of BOTH cross-region launchers
    (the single-device vmapped one below and ``parallel.mesh``'s shard_map
    twin): validates the plan and every cache, computes the per-region
    (dicts, dict_lens, n_slots) specs, the group columns, and the shared
    power-of-two capacity.  Raises ValueError on the documented declines
    (non-aggregation plan, unstable group dictionaries, empty cache) — ONE
    implementation so the two launchers can never disagree about what is
    batchable."""
    if ev.plan.agg is None:
        raise ValueError("cross-region batching requires aggregation DAGs")
    if not caches:
        raise ValueError("cross-region batching requires at least one region")
    specs = []
    n_slots_max = 1
    for cache in caches:
        if not cache.blocks:
            raise ValueError("cross-region batching over an empty block cache")
        stable = ev._stable_dict_group_cols(cache.blocks)
        if ev.group_rpns and stable is None:
            raise ValueError("cross-region batching requires stable dict group keys")
        _gc, dicts = stable if stable else ([], [])
        dict_lens = tuple(len(d) for d in dicts)
        n_slots = 1
        for dl in dict_lens:
            n_slots *= dl + 1
        n_slots_max = max(n_slots_max, n_slots)
        specs.append((dicts, dict_lens, n_slots))
    group_cols = [g.nodes[0].index for g in ev.group_rpns]
    capacity = 1
    while capacity < n_slots_max:
        capacity *= 2
    return specs, group_cols, capacity


def _pack_region_leaves(leaves, n_regions: int, capacity: int):
    """Region-slot-segmented variant of :func:`_pack_leaves`: flat
    ``(R*C,)`` state leaves → ``((R, Li, C) int64, (R, Lf, C) float64)``
    matrices under the SAME int/float partition rule, so
    ``XRegionPending.finalize`` unpacks either launcher's output against
    the one packing contract."""
    ints = [l.reshape(n_regions, capacity).astype(jnp.int64)
            for l in leaves if l.dtype != jnp.float64]
    flts = [l.reshape(n_regions, capacity)
            for l in leaves if l.dtype == jnp.float64]
    int_m = jnp.stack(ints, axis=1)
    flt_m = (jnp.stack(flts, axis=1) if flts
             else jnp.zeros((n_regions, 0, capacity), dtype=jnp.float64))
    return int_m, flt_m


def launch_xregion_cached(ev: "JaxDagEvaluator", caches,
                          params: tuple = ()) -> XRegionPending:
    """ONE aggregation plan over R different region images as ONE device
    program: each region's resident blocks are padded to a shared block
    geometry, stacked along a new leading region axis, and the per-region
    block scan is vmapped over that axis — one dispatch and one packed pull
    amortize the dispatch round-trip over every region in the batch.

    Correctness relies on the per-block validity masks the single-region
    step already applies: padded blocks carry ``n_valid == 0`` so padding
    never reaches an aggregate.  Group capacities are shared (the max
    region's, rounded to a power of two) while dictionary radices stay
    per-region DYNAMIC inputs — so regions whose group dictionaries differ
    still ride one compiled program.  So do the plan's literals: ``params``
    (the group shares them) is one more input, the same for every region.

    Raises ValueError when the plan or any region's data shape is not
    batchable (non-aggregation plan, unstable group dictionaries, empty
    cache); the scheduler sheds those to the per-request path.
    """
    with trace.stage("device.prepare", path="xregion"):
        return _launch_xregion_cached(ev, caches, params)


def _launch_xregion_cached(ev: "JaxDagEvaluator", caches, params) -> XRegionPending:
    # everything here but the dispatch itself (a stage of its own, which
    # suspends this one) is host work: eligibility, geometry, the regions'
    # pinned inputs, pruning
    from . import encoding as _encoding

    specs, group_cols, capacity = xregion_specs(ev, caches)
    pvec = ev.param_vectors(params)
    bound_sel = ev.bound_sel_rpns(params)  # for the zone maps: THIS request's
    ship = ev._ship_cols(group_cols)
    nullable = ev.nullable_cols
    n_rows = ev.block_rows
    # encoded residency (copr/encoding.py): the vmapped program stacks
    # per-region pinned arrays, so every region must carry the SAME
    # encoding signature — batch_plan decides (and counts) encoded vs
    # decode-ship; the descriptors ride the jit key, the per-region
    # frame-of-reference vectors ride as a dynamic (R, n_ship) input
    plans = _encoding.batch_plan(caches, ship, nullable, "xregion")
    enc = plans[0].sig if plans else None
    # canonicalize region order by block count: the compiled program's cache
    # key is the block-count tuple, so (2,3) and (3,2) must not compile two
    # programs — batches differing only in arrival order share one
    # executable.  finalize restores the caller's order.
    order = sorted(range(len(caches)), key=lambda i: len(caches[i].blocks),
                   reverse=True)
    caches = [caches[i] for i in order]
    specs = [specs[i] for i in order]
    if plans:
        plans = [plans[i] for i in order]
    n_blocks = tuple(len(c.blocks) for c in caches)
    B = max(n_blocks)
    # per-region inputs are the caches' ALREADY-PINNED device arrays (the
    # same pins the per-request warm path uses, carried into each new
    # version by ColumnBlockCache.successor) — zero per-row host→device traffic, and
    # no cross-cache pin that could go stale behind a region's back
    from . import zone_maps as _zm

    region_inputs = []
    prunes = []  # (examined, pruned) per executed region, for the riders' obs
    for r, cache in enumerate(caches):
        data, nulls, _refs, _e = ev._stacked_device(
            cache, cache.blocks, ship,
            plan=plans[r] if plans else None,
        )
        nv, off = ev._nvoff_device(cache, cache.blocks)
        # zone-map pruning (docs/zone_maps.md): masked blocks ship
        # n_valid == 0 through the dynamic nv input, so the vmapped program
        # skips their rows without perturbing the shared compile key
        pstats = _zm.PruneStats()
        keep = _zm.prune_blocks(cache, bound_sel, path="xregion",
                                stats=pstats)
        if keep is not None:
            nv = _masked_nv(cache.blocks, keep)
        prunes.append((pstats.examined, pstats.pruned))
        region_inputs.append((data, nulls, nv, off))
    dl_arr = np.array([s[1] for s in specs], dtype=np.int64).reshape(
        len(caches), len(group_cols)
    )
    refs_arr = (np.stack([np.asarray(p.refs) for p in plans])
                if plans else np.zeros((len(caches), len(ship)), dtype=np.int64))

    key = ("xregion", n_blocks, capacity, tuple(ship), tuple(nullable), enc)
    fn = ev._agg_fn_cache.get(key)
    if fn is None:
        device_aggs = ev.device_aggs
        sel_rpns = ev.sel_rpns
        track_first = bool(ev.group_rpns)

        def pad_b(a):
            pad = B - a.shape[0]
            if pad == 0:
                return a
            return jnp.pad(a, [(0, pad)] + [(0, 0)] * (a.ndim - 1))

        def xregion_fn(region_inputs, dl_arr, refs_arr, pvec):
            padded = [jax.tree.map(pad_b, ri) for ri in region_inputs]
            stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *padded)

            def one_region(ri, dlens, refs_r):  # pvec: closed over, not mapped
                cd_r, cn_r, nv_r, off_r = ri
                state = (
                    jnp.full(capacity, _NO_ROW, dtype=jnp.int64),
                    tuple(da.init_carry(capacity) for da in device_aggs),
                )

                def body(st, xs):
                    cd, cn, nv, off = xs
                    cols = _build_cols(ship, nullable, cd, cn, n_rows, enc, refs_r)
                    if group_cols:
                        gids = jnp.zeros(n_rows, dtype=jnp.int64)
                        for k, gi in enumerate(group_cols):
                            codes, gnulls = cols[gi]
                            dlen = dlens[k]
                            gids = gids * (dlen + 1) + jnp.where(gnulls, dlen, codes)
                    else:
                        gids = jnp.zeros(n_rows, dtype=jnp.int64)
                    return _fused_step(
                        sel_rpns, device_aggs, capacity, n_rows, cols, nv, gids, off, st,
                        track_first=track_first, params=pvec,
                    ), None

                state, _ = jax.lax.scan(body, state, (cd_r, cn_r, nv_r, off_r))
                return _pack_state(state)

            return jax.vmap(one_region)(stacked, dl_arr, refs_arr)

        fn = _obs.timed_jit(jax.jit(xregion_fn), "jax_eval.xregion",
                            "xregion", ev.obs_sig)
        ev._agg_fn_cache[key] = fn
        # block-count compositions drift (deltas, splits): bound the
        # executables retained for this plan so compile churn cannot grow
        # memory without limit
        xkeys = [k for k in ev._agg_fn_cache if isinstance(k, tuple)
                 and k and k[0] == "xregion"]
        while len(xkeys) > 16:
            ev._agg_fn_cache.pop(xkeys.pop(0))

    # the async dispatch itself (stage device.launch, in timed_jit's
    # wrapper); the encoded-path decision batch_plan made (and counted)
    # rides the dispatch span as a tag (docs/tracing.md)
    cur = trace.current()
    if cur is not None:
        cur.tag(encoding="encoded" if plans else "decoded")
    packed = fn(tuple(region_inputs), dl_arr, refs_arr, pvec)
    pending = XRegionPending(ev, specs, capacity, packed, order, prunes)
    # observatory encoding label for the riders' profiles
    pending.obs_encoding = "encoded" if plans else "plain"
    return pending


def run_xregion_cached(ev: "JaxDagEvaluator", caches,
                       params: tuple = ()) -> list[SelectResponse]:
    """launch + finalize in one step (tests / single-batch callers)."""
    return launch_xregion_cached(ev, caches, params).finalize()


def launch_xregion_sharded(ev: "JaxDagEvaluator", caches, mesh,
                           params: tuple = ()) -> XRegionPending:
    """The ``shard_map`` twin of :func:`launch_xregion_cached`: the same
    cross-region batch executed over EVERY device of ``mesh``, each region
    image (or block, for a block-spread huge region) scanned on its owner
    device and the partial aggregate states merged with the mesh collective
    rules.  Implemented in ``parallel.mesh`` (where the collectives and the
    merge table live); this wrapper keeps the scheduler's device backend a
    single import site.  Raises ValueError on the same documented declines
    as the single-device launcher, plus "no mesh merge rule"."""
    from ..parallel.mesh import launch_xregion_sharded as _impl

    return _impl(ev, caches, mesh, params)


class _ChunkExecutor:
    """Adapter: present an in-memory Chunk as a drained BatchExecutor."""

    def __init__(self, chunk: Chunk, schema):
        self._chunk = chunk
        self._schema = schema
        self._done = False

    def schema(self):
        return self._schema

    def next_batch(self, scan_rows: int):
        from .executors import BatchExecuteResult

        if self._done:
            return BatchExecuteResult(Chunk.full([]), True)
        self._done = True
        return BatchExecuteResult(self._chunk, True)


def _grow_carry(da: _DeviceAgg, carry, new_capacity: int):
    grown = list(da.init_carry(new_capacity))
    out = []
    for old, new in zip(carry, grown):
        old = jnp.asarray(old)
        out.append(new.at[: old.shape[0]].set(old))
    return tuple(out)
