"""DAG request model + executor-chain runner + response encoding.

Re-expression of tipb's ``DagRequest``/executor descriptors and the
``BatchExecutorsRunner`` (``tidb_query_executors/src/runner.rs:41``):

* descriptors (dataclasses standing in for the tipb protos) describe the
  executor chain: scan leaf → selection/join/projection → aggregation/topN
  → limit (joins carry their build-side chain inline — docs/device_join.md)
* ``build_executors`` (runner.rs:150) assembles the chain
* ``handle_request`` (runner.rs:399) drives ``next_batch`` with the 32→×2→1024
  growing batch size and encodes output rows into datum-encoded chunks
  (``SelectResponse``-equivalent), chunked every 1024 rows

Response bytes are produced by a deterministic encoder so the CPU oracle and
the TPU path can be compared byte-for-byte.
"""

from __future__ import annotations

from dataclasses import dataclass, field

import numpy as np

from ..analysis import bufsan as _bufsan
from ..server.wire import PASSTHROUGH_MIN as PART_MIN
from ..util import codec
from . import datum as datum_mod
from .aggr import AggDescriptor
from .datatypes import Chunk, Column, ColumnInfo, EvalType
from .executors import (
    BATCH_GROW_FACTOR,
    BATCH_INITIAL_SIZE,
    BATCH_MAX_SIZE,
    BatchExecutor,
    BatchHashAggregationExecutor,
    BatchIndexScanExecutor,
    BatchJoinExecutor,
    BatchLimitExecutor,
    BatchProjectionExecutor,
    BatchSelectionExecutor,
    BatchSimpleAggregationExecutor,
    BatchStreamAggregationExecutor,
    BatchTableScanExecutor,
    BatchTopNExecutor,
    FixtureScanSource,
    MvccScanSource,
    ScanSource,
)
from .rpn import Expr

# ---------------------------------------------------------------------------
# Executor descriptors (tipb::Executor equivalents)
# ---------------------------------------------------------------------------

@dataclass
class TableScan:
    table_id: int
    columns_info: list[ColumnInfo]


@dataclass
class IndexScan:
    table_id: int
    index_id: int
    columns_info: list[ColumnInfo]


@dataclass
class Selection:
    conditions: list[Expr]


@dataclass
class Aggregation:
    group_by: list[Expr]
    agg_funcs: list[AggDescriptor]
    streamed: bool = False


@dataclass
class TopN:
    order_by: list[tuple[Expr, bool]]  # (expr, desc)
    limit: int


@dataclass
class Limit:
    limit: int


@dataclass
class Projection:
    """Expression list over the child schema (tipb::Projection equivalent).

    Output columns are the evaluated expressions in order — the schema the
    downstream chain (and the response encoder) sees is
    ``[(expr.eval_type, expr.frac), ...]``."""

    exprs: list[Expr]


@dataclass
class Join:
    """Equi-join against a second executor chain (tipb::Join equivalent).

    The enclosing chain below this descriptor is the PROBE side; ``build``
    is the build side's own chain (a TableScan leaf plus optional
    Selections) scanned over ``build_ranges``.  Output schema is the probe
    schema followed by the build schema.  ``left_key``/``right_key`` are
    column offsets into the probe/build child schemas; ``join_type`` is
    ``"inner"`` or ``"left"`` (left-outer: unmatched probe rows emit build
    NULLs).  ``build_context`` optionally carries the build region's
    identity (region_id/region_epoch/apply_index) so the device rung can
    resolve the build side's warm image (docs/device_join.md)."""

    build: list
    build_ranges: list[tuple[bytes, bytes]]
    left_key: int
    right_key: int
    join_type: str = "inner"
    build_context: dict | None = None


ExecutorDescriptor = (TableScan | IndexScan | Selection | Aggregation | TopN
                      | Limit | Projection | Join)


#: response encodings (tipb EncodeType): datum rows are the default and the
#: compatibility oracle; TypeChunk ships whole column slabs with no row
#: materialization (docs/wire_path.md "Columnar chunk responses")
ENC_TYPE_DATUM = 0
ENC_TYPE_CHUNK = 1


@dataclass
class DagRequest:
    """The pushed-down plan (tipb::DagRequest equivalent)."""

    executors: list[ExecutorDescriptor]
    output_offsets: list[int] | None = None  # None = all columns
    chunk_rows: int = 1024
    # negotiated response encoding (tipb DagRequest.encode_type): clients
    # opt into ENC_TYPE_CHUNK per request; unsupported plans/field types
    # decline back to the datum codec (negotiate_encode_type)
    encode_type: int = ENC_TYPE_DATUM


@dataclass
class ExecSummary:
    """Per-executor execution summary (tidb_query_common/src/execute_stats.rs)."""

    num_produced_rows: int = 0
    num_iterations: int = 0


class SelectResponse:
    """The coprocessor DAG answer in either response encoding.

    Datum responses (the default) carry joined per-chunk row bytes in
    ``chunks`` exactly as before.  TypeChunk responses keep each chunk as a
    LIST of per-column slabs in ``chunk_parts`` — ``chunks`` joins lazily so
    the canonical ``encode()`` framing (and every byte-identity compare)
    stays one definition, while :meth:`encode_parts` hands the unjoined
    column slabs to the wire layer for the ``dumps_parts``/``sendmsg``
    gather write (docs/wire_path.md)."""

    def __init__(self, chunks: list[bytes] | None = None, exec_summaries=None,
                 warnings=None, encode_type: int = ENC_TYPE_DATUM,
                 chunk_parts: "list[list[bytes]] | None" = None,
                 field_types=None):
        assert chunks is not None or chunk_parts is not None
        self._chunks = chunks
        self.chunk_parts = chunk_parts
        self.exec_summaries: list[ExecSummary] = exec_summaries or []
        self.warnings: list[str] = warnings or []
        self.encode_type = encode_type
        # output schema for decoding TypeChunk columns — clients attach it
        # from their own plan (chunk_output_field_types); never on the wire
        self.field_types = field_types

    @property
    def chunks(self) -> list[bytes]:
        if self._chunks is None:
            self._chunks = [b"".join(map(bytes, p)) for p in self.chunk_parts]
        return self._chunks

    @chunks.setter
    def chunks(self, v: list[bytes]) -> None:
        self._chunks = v
        self.chunk_parts = None

    def encode(self) -> bytes:
        """Deterministic wire encoding — the byte-identity contract surface.
        Framing is shared across encode types; only chunk contents differ."""
        return b"".join(map(bytes, self.encode_parts()))

    def encode_parts(self) -> list:
        """The same bytes as :meth:`encode`, as a buffer list: each chunk's
        column slabs stay the encoder's own bytes objects (no join), so the
        wire layer's ``dumps_parts`` passthrough gather-writes them without
        a re-encoding copy.  Datum responses frame their joined chunks the
        same way."""
        per_chunk = (self.chunk_parts if self.chunk_parts is not None
                     else [[c] for c in self.chunks])
        parts: list = []
        head = bytearray()
        head += codec.encode_var_u64(len(per_chunk))
        for cols in per_chunk:
            head += codec.encode_var_u64(sum(len(c) for c in cols))
            for c in cols:
                # column slabs worth a gather iovec ride as their own part
                # (wire.PASSTHROUGH_MIN); small ones fold into the header.
                # From here the slab is an exposure: it must stay bit-stable
                # until the frame writer's send completes (bufsan tracks the
                # window under TIKV_TPU_SANITIZE=1)
                if len(c) >= PART_MIN:
                    if head:
                        parts.append(bytes(head))
                        head = bytearray()
                    _bufsan.export("encode_parts", c,
                                   site="dag.SelectResponse.encode_parts")
                    parts.append(c)
                else:
                    head += c
        head += codec.encode_var_u64(len(self.warnings))
        for w in self.warnings:
            wb = w.encode()
            head += codec.encode_var_u64(len(wb))
            head += wb
        if head:
            parts.append(bytes(head))
        return parts

    @classmethod
    def decode(cls, blob: bytes,
               encode_type: int = ENC_TYPE_DATUM) -> "SelectResponse":
        """Parse the wire encoding back (client-side partial merges and
        tests; the inverse of :meth:`encode`).  ``encode_type`` is the
        NEGOTIATED encoding the response rode (the response dict's
        ``encode_type`` key) — the framing itself is encoding-agnostic."""
        n, off = codec.decode_var_u64(blob, 0)
        chunks = []
        for _ in range(n):
            ln, off = codec.decode_var_u64(blob, off)
            chunks.append(bytes(blob[off:off + ln]))
            off += ln
        warnings = []
        if off < len(blob):
            nw, off = codec.decode_var_u64(blob, off)
            for _ in range(nw):
                ln, off = codec.decode_var_u64(blob, off)
                warnings.append(blob[off:off + ln].decode())
                off += ln
        return cls(chunks, warnings=warnings, encode_type=encode_type)

    def iter_rows(self, field_types=None) -> list[list]:
        """Decode all chunks back into python rows.  TypeChunk responses
        need the output schema (``field_types`` here, or attached by
        ``decode_wire_response``); values are identical to the datum path's
        row by row (the differential-test contract)."""
        if self.encode_type == ENC_TYPE_CHUNK:
            from . import chunk_codec

            fts = field_types or self.field_types
            if fts is None:
                raise ValueError("TypeChunk rows need the output field types")
            rows: list[list] = []
            for chunk in self.chunks:
                cols = chunk_codec.decode_chunk(chunk, fts)
                col_vals = [chunk_codec.column_values(c) for c in cols]
                rows.extend([list(r) for r in zip(*col_vals)] if col_vals
                            else [])
            return rows
        rows = []
        for chunk in self.chunks:
            off = 0
            while off < len(chunk):
                ncols, off = codec.decode_var_u64(chunk, off)
                row = []
                for _ in range(ncols):
                    d, off = datum_mod.decode_datum(chunk, off)
                    row.append(d.value)
                rows.append(row)
        return rows


# ---------------------------------------------------------------------------
# Runner
# ---------------------------------------------------------------------------

def check_supported(dag: DagRequest) -> None:
    """Raise ValueError for plans the batch pipeline cannot run
    (runner.rs:75 check_supported).  Since the device-join work
    (docs/device_join.md) Join and Projection ARE coprocessor-side here —
    inner/left-outer equi-joins carry their build chain inline, Projections
    evaluate the scalar expression surface — so only Exchange (and other
    TiDB/TiFlash-only operators) remains out of the matrix."""
    if not dag.executors:
        raise ValueError("empty executor list")
    if not isinstance(dag.executors[0], (TableScan, IndexScan)):
        raise ValueError("first executor must be a scan")
    for e in dag.executors[1:]:
        if isinstance(e, (TableScan, IndexScan)):
            raise ValueError("scan executor must be the leaf")
        if isinstance(e, Join):
            _check_join(e)
        elif not isinstance(e, (Selection, Aggregation, TopN, Limit,
                                Projection)):
            raise ValueError(f"unsupported executor {type(e).__name__}")


def _check_join(j: Join) -> None:
    """Validate one Join descriptor's build chain: its own scan leaf plus
    optional Selections — no nested joins, no aggregates (the reference
    pushes only simple build sides to storage)."""
    if j.join_type not in ("inner", "left"):
        raise ValueError(f"unsupported join type {j.join_type!r}")
    if not j.build or not isinstance(j.build[0], TableScan):
        raise ValueError("join build chain must start with a TableScan")
    for e in j.build[1:]:
        if not isinstance(e, Selection):
            raise ValueError(
                f"unsupported build-side executor {type(e).__name__}")


def _attach(ex: BatchExecutor, desc, source: ScanSource | None,
            build_leaf: BatchExecutor | None = None) -> BatchExecutor:
    """Chain one non-leaf descriptor onto ``ex`` — the single
    descriptor→executor mapping the probe chain, join build chains and the
    device join rung's downstream finisher all share."""
    if isinstance(desc, Selection):
        return BatchSelectionExecutor(ex, desc.conditions)
    if isinstance(desc, Aggregation):
        if not desc.group_by:
            return BatchSimpleAggregationExecutor(ex, desc.agg_funcs)
        if desc.streamed:
            return BatchStreamAggregationExecutor(ex, desc.group_by, desc.agg_funcs)
        return BatchHashAggregationExecutor(ex, desc.group_by, desc.agg_funcs)
    if isinstance(desc, TopN):
        return BatchTopNExecutor(ex, desc.order_by, desc.limit)
    if isinstance(desc, Limit):
        return BatchLimitExecutor(ex, desc.limit)
    if isinstance(desc, Projection):
        return BatchProjectionExecutor(ex, desc.exprs)
    if isinstance(desc, Join):
        if build_leaf is not None:
            build_ex = build_leaf
        else:
            b_src = (source.fork(desc.build_ranges)
                     if source is not None else None)
            build_ex = BatchTableScanExecutor(b_src, desc.build[0].columns_info)
        for b in desc.build[1:]:
            build_ex = _attach(build_ex, b, None)
        return BatchJoinExecutor(ex, build_ex, desc.left_key, desc.right_key,
                                 desc.join_type)
    raise AssertionError(desc)


def build_executors(dag: DagRequest, source: ScanSource,
                    leaf: BatchExecutor | None = None,
                    build_leaf: BatchExecutor | None = None) -> BatchExecutor:
    """runner.rs:150 build_executors equivalent.  ``leaf`` overrides the scan
    executor (e.g. CachedBlocksExecutor for the warm block-cache path);
    ``build_leaf`` likewise overrides a Join descriptor's build-side scan.
    Without an override, a Join's build side scans a ``source.fork`` over
    its own ranges — the same snapshot, so both sides of the join read one
    consistent view.  Construction never touches the sources (drains are
    deferred to the first next_batch), so schema-only walks with
    ``source=None`` stay valid for plans with joins."""
    check_supported(dag)
    head = dag.executors[0]
    if leaf is not None:
        ex = leaf
    elif isinstance(head, TableScan):
        ex: BatchExecutor = BatchTableScanExecutor(source, head.columns_info)
    else:
        from .table import index_range

        prefix_len = len(index_range(head.table_id, head.index_id)[0])
        ex = BatchIndexScanExecutor(source, head.columns_info, prefix_len)
    for desc in dag.executors[1:]:
        ex = _attach(ex, desc, source, build_leaf=build_leaf)
    return ex


# ---------------------------------------------------------------------------
# TypeChunk negotiation (docs/wire_path.md "Columnar chunk responses")
# ---------------------------------------------------------------------------

# response schema for chunk columns, derived from the executor chain's
# (EvalType, frac) output schema: signed 8-byte ints mirror the datum value
# domain exactly (datum_at encodes INT signed, DATETIME as the packed u64,
# decimals as the fixed-point int64 + frac), so decoded chunk rows equal
# decoded datum rows by construction.  ENUM/SET have no datum-identical
# chunk mapping here and decline.
_CHUNK_TP = {
    EvalType.INT: "LONGLONG",
    EvalType.REAL: "DOUBLE",
    EvalType.DECIMAL: "NEW_DECIMAL",
    EvalType.BYTES: "VAR_STRING",
    EvalType.JSON: "JSON",
    EvalType.DATETIME: "DATETIME",
    EvalType.DURATION: "DURATION",
}

_UNSET = object()


def chunk_output_field_types(dag: DagRequest):
    """The response column FieldTypes a TypeChunk encoding of ``dag`` uses,
    or None when the plan declines to the datum codec (the decline cause is
    stashed as ``dag._chunk_decline``).  Derived from the SAME executor
    schema both pipelines serve (build_executors(dag, None).schema() — scan
    leaves never touch the source at construction), memoized per DagRequest
    object: plans are parse-memoized per (bytes, encode_type) by the
    service, so the walk runs once per distinct plan."""
    from .chunk_codec import MAX_VEC_DECIMAL_FRAC
    from .datatypes import FieldType, FieldTypeTp

    cached = getattr(dag, "_chunk_fts", _UNSET)
    if cached is not _UNSET:
        return cached
    try:
        schema = build_executors(dag, None).schema()
    except Exception:  # noqa: BLE001 — unbuildable plan: datum decides
        dag._chunk_decline = "plan"
        dag._chunk_fts = None
        return None
    offsets = dag.output_offsets
    try:
        out_schema = (schema if offsets is None
                      else [schema[i] for i in offsets])
    except IndexError:
        dag._chunk_decline = "plan"
        dag._chunk_fts = None
        return None
    fts = []
    for et, frac in out_schema:
        tp = _CHUNK_TP.get(et)
        if tp is None or (et == EvalType.DECIMAL
                          and frac > MAX_VEC_DECIMAL_FRAC):
            dag._chunk_decline = "field_type"
            dag._chunk_fts = None
            return None
        fts.append(FieldType(getattr(FieldTypeTp, tp), decimal=frac))
    if not fts:
        # zero output columns: datum rows still carry a per-row ncols
        # marker, but a chunk of no columns cannot carry a row count —
        # decline to the datum codec
        dag._chunk_decline = "field_type"
        dag._chunk_fts = None
        return None
    dag._chunk_fts = fts
    return fts


def datum_twin(dag: DagRequest) -> DagRequest:
    """The same plan with the datum encoding — what a declined TypeChunk
    request serves as.  Shares the executor descriptors (and therefore the
    endpoint's evaluator/memo entries keyed on the datum plan bytes)."""
    twin = getattr(dag, "_datum_twin", None)
    if twin is None:
        from dataclasses import replace

        twin = replace(dag, encode_type=ENC_TYPE_DATUM)
        dag._datum_twin = twin
    return twin


def negotiate_encode_type(dag: DagRequest) -> tuple[DagRequest, str | None]:
    """Resolve the request's effective encoding: ``(dag, None)`` when the
    requested encoding serves as-is, ``(datum twin, cause)`` when a
    TypeChunk request declines (unsupported field type, unbuildable plan) —
    a decline is a datum response, never an error."""
    if dag.encode_type != ENC_TYPE_CHUNK:
        return dag, None
    if chunk_output_field_types(dag) is not None:
        return dag, None
    return datum_twin(dag), getattr(dag, "_chunk_decline", "plan")


def response_data(resp: dict) -> bytes:
    """A wire response dict's payload bytes: joins ``data_parts`` (TypeChunk
    responses ship each large column slab as its own frame part) or returns
    ``data`` — the client-side inverse of ``encode_parts``."""
    parts = resp.get("data_parts")
    if parts is not None:
        return b"".join(bytes(p) for p in parts)
    return resp["data"]


def decode_wire_response(resp: dict, dag: DagRequest) -> SelectResponse:
    """Decode a coprocessor wire response dict against the plan the client
    sent: joins the frame parts, parses the shared framing, and attaches
    the TypeChunk output schema so ``iter_rows`` decodes either encoding."""
    sr = SelectResponse.decode(response_data(resp),
                               encode_type=resp.get("encode_type",
                                                    ENC_TYPE_DATUM))
    if sr.encode_type == ENC_TYPE_CHUNK:
        sr.field_types = chunk_output_field_types(dag)
    return sr


class ResponseEncoder:
    """Row-exact chunk framer: a new chunk starts every ``chunk_rows`` rows,
    independent of producer batch boundaries — so the CPU and device paths
    emit byte-identical framing for identical row streams.

    Large batches encode through the vectorized column codec
    (``datum_vec.encode_chunk_rows`` — numpy batch varints/fixed cells, one
    ragged scatter per column); tiny batches and exotic column types keep
    the scalar per-row loop.  Both paths emit identical bytes
    (tests/test_wire_path.py)."""

    encode_type = ENC_TYPE_DATUM

    def __init__(self, chunk_rows: int):
        self.chunk_rows = chunk_rows
        self.chunks: list[bytes] = []
        self._cur = bytearray()
        self._rows = 0

    def add_chunk(self, chunk: Chunk, output_offsets: list[int] | None) -> int:
        cols = (
            chunk.columns
            if output_offsets is None
            else [chunk.columns[i] for i in output_offsets]
        )
        from . import datum_vec

        n_rows = chunk.num_rows
        if n_rows >= datum_vec.VEC_MIN_ROWS and datum_vec.supported(cols):
            buf, row_ends = datum_vec.encode_chunk_rows(cols, chunk.logical_rows)
            start_row, start_byte = 0, 0
            take = self.chunk_rows - self._rows
            while start_row + take <= n_rows:
                end_byte = int(row_ends[start_row + take - 1])
                self._cur += buf[start_byte:end_byte]
                self.chunks.append(bytes(self._cur))
                self._cur = bytearray()
                self._rows = 0
                start_row += take
                start_byte = end_byte
                take = self.chunk_rows
            self._cur += buf[start_byte:]
            self._rows += n_rows - start_row
            return n_rows
        n = 0
        for row in chunk.logical_rows:
            self._cur += codec.encode_var_u64(len(cols))
            for c in cols:
                flag, value = c.datum_at(int(row))
                datum_mod.encode_datum(self._cur, flag, value)
            n += 1
            self._rows += 1
            if self._rows == self.chunk_rows:
                self.chunks.append(bytes(self._cur))
                self._cur = bytearray()
                self._rows = 0
        return n

    def finish(self) -> list[bytes]:
        if self._rows:
            self.chunks.append(bytes(self._cur))
            self._cur = bytearray()
            self._rows = 0
        return self.chunks

    # -- shared encoder surface (the runner/evaluators stay encoding-blind) --

    def to_response(self, **kw) -> SelectResponse:
        return SelectResponse(chunks=self.finish(), **kw)

    def pending_frames(self) -> int:
        return len(self.chunks)

    def flush_response(self, n: int) -> SelectResponse:
        """Pop the first ``n`` finished chunks as one streamed response
        frame (the streaming runner's flush unit)."""
        flushed, self.chunks = self.chunks[:n], self.chunks[n:]
        return SelectResponse(chunks=flushed)


class ChunkResponseEncoder:
    """The :class:`ResponseEncoder` twin for TypeChunk responses: the same
    row-exact framing (a new chunk every ``chunk_rows`` rows, independent of
    producer batch boundaries — so streamed flushes align with the datum
    path's), but each chunk is built as per-column slabs straight from the
    producer's numpy columns:

    * ``Column.take``/``EncodedColumn.take`` late-materializes only the
      selected rows (encoded-resident columns decode only survivors),
    * null bitmap / end-offset / cell assembly is one vectorized pass per
      column (``chunk_codec.encode_np_column``) — no per-row Python,
    * ``finish()`` returns ``list[list[bytes]]`` (per chunk, per column),
      which ``SelectResponse.encode_parts`` hands to the wire gather write
      without ever joining the slabs.

    Callers guarantee supportability up front (``chunk_output_field_types``
    — the same probe the negotiation decline uses), so an unsupported
    column type here is a programming error, not a client-visible one."""

    encode_type = ENC_TYPE_CHUNK

    def __init__(self, chunk_rows: int, field_types):
        assert field_types is not None, "chunk encoding needs the output schema"
        self.chunk_rows = chunk_rows
        self.field_types = field_types
        self.chunks: list[list[bytes]] = []
        self._segs: list[list] = []  # pending row-compacted Column segments
        self._rows = 0

    def add_chunk(self, chunk: Chunk, output_offsets: list[int] | None) -> int:
        cols = (chunk.columns if output_offsets is None
                else [chunk.columns[i] for i in output_offsets])
        logical = np.asarray(chunk.logical_rows)
        n = len(logical)
        if n == 0:
            return 0
        full = (cols and n == len(cols[0])
                and logical[0] == 0 and logical[-1] == n - 1
                and np.array_equal(logical, np.arange(n)))
        taken = list(cols) if full else [c.take(logical) for c in cols]
        self._segs.append(taken)
        self._rows += n
        while self._rows >= self.chunk_rows:
            self._emit(self.chunk_rows)
        return n

    def _emit(self, k: int) -> None:
        """Assemble one chunk of exactly ``k`` rows from the pending
        segments (splitting the boundary segment), one vectorized encode
        per column."""
        from . import chunk_codec, encoding as _encoding

        pieces: list[list] = []
        got = 0
        while got < k:
            seg = self._segs[0]
            seg_n = len(seg[0]) if seg else 0
            take = min(k - got, seg_n)
            if take == seg_n:
                pieces.append(self._segs.pop(0))
            else:
                pieces.append([c.slice(0, take) for c in seg])
                self._segs[0] = [c.slice(take, seg_n) for c in seg]
            got += take
        self._rows -= k
        out_cols: list[bytes] = []
        for j, ft in enumerate(self.field_types):
            parts = [p[j] for p in pieces]
            if len(parts) > 1 and any(p.dictionary is not None for p in parts):
                # mixed dict/plain segments: codes are only meaningful
                # per-segment — materialize before concatenating
                parts = [p.decoded() for p in parts]
            d = parts[0].dictionary if len(parts) == 1 else None
            # the no-cache accessors: a resident EncodedColumn must not be
            # left holding a full decode by response encoding (the budget
            # counts encoded bytes — docs/compressed_columns.md)
            if len(parts) == 1:
                data = np.asarray(_encoding.decoded_data(parts[0]))
                nulls = np.asarray(_encoding.decoded_nulls(parts[0]))
            else:
                data = np.concatenate(
                    [np.asarray(_encoding.decoded_data(p)) for p in parts])
                nulls = np.concatenate(
                    [np.asarray(_encoding.decoded_nulls(p)) for p in parts])
            out_cols.append(chunk_codec.encode_np_column(ft, data, nulls, d))
        self.chunks.append(out_cols)

    def finish(self) -> list[list[bytes]]:
        if self._rows:
            self._emit(self._rows)
        return self.chunks

    def to_response(self, **kw) -> SelectResponse:
        return SelectResponse(chunk_parts=self.finish(),
                              encode_type=ENC_TYPE_CHUNK,
                              field_types=self.field_types, **kw)

    def pending_frames(self) -> int:
        return len(self.chunks)

    def flush_response(self, n: int) -> SelectResponse:
        flushed, self.chunks = self.chunks[:n], self.chunks[n:]
        return SelectResponse(chunk_parts=flushed, encode_type=ENC_TYPE_CHUNK,
                              field_types=self.field_types)


def make_response_encoder(dag: DagRequest):
    """The one encoder-selection rule every serving path shares (CPU runner,
    unary/zone/fused/xregion/mesh device finalizers, streaming): TypeChunk
    when the plan negotiated it, else the datum framer.  Defensive: an
    unsupported chunk plan that slipped past the entry-gate negotiation
    still serves datum bytes rather than erroring."""
    if dag.encode_type == ENC_TYPE_CHUNK:
        fts = chunk_output_field_types(dag)
        if fts is not None:
            return ChunkResponseEncoder(dag.chunk_rows, fts)
    return ResponseEncoder(dag.chunk_rows)


class BatchExecutorsRunner:
    """Drive loop (runner.rs:399)."""

    def __init__(self, dag: DagRequest, source: ScanSource | None, leaf: BatchExecutor | None = None):
        self.dag = dag
        self.executor = build_executors(dag, source, leaf)
        self.summary = ExecSummary()

    def handle_request(self) -> SelectResponse:
        enc = make_response_encoder(self.dag)
        batch_size = BATCH_INITIAL_SIZE
        while True:
            r = self.executor.next_batch(batch_size)
            self.summary.num_iterations += 1
            if r.chunk.num_rows:
                enc.add_chunk(r.chunk, self.dag.output_offsets)
                self.summary.num_produced_rows += r.chunk.num_rows
            if r.is_drained:
                break
            if batch_size < BATCH_MAX_SIZE:
                batch_size = min(batch_size * BATCH_GROW_FACTOR, BATCH_MAX_SIZE)
        return enc.to_response(exec_summaries=[self.summary])

    def handle_streaming_request(self, rows_per_stream: int = 1024):
        """Streaming path (runner.rs:471 + endpoint.rs:508-584): yield one
        SelectResponse per ~rows_per_stream output rows so unbounded scans
        never buffer whole results.  Frames flush at whole response chunks
        in EITHER encoding — TypeChunk streams column-slab frames aligned
        with the same chunk_rows framing the datum stream uses."""
        enc = make_response_encoder(self.dag)
        batch_size = BATCH_INITIAL_SIZE
        while True:
            r = self.executor.next_batch(batch_size)
            self.summary.num_iterations += 1
            if r.chunk.num_rows:
                enc.add_chunk(r.chunk, self.dag.output_offsets)
                self.summary.num_produced_rows += r.chunk.num_rows
            # flush whole chunks as soon as a frame's worth accumulated
            per_frame = max(1, rows_per_stream // self.dag.chunk_rows)
            while enc.pending_frames() >= per_frame:
                yield enc.flush_response(per_frame)
            if r.is_drained:
                break
            if batch_size < BATCH_MAX_SIZE:
                batch_size = min(batch_size * BATCH_GROW_FACTOR, BATCH_MAX_SIZE)
        # final response always carries the exec summaries, like the unary path
        yield enc.to_response(exec_summaries=[self.summary])
