"""The cold fill's currency and its instrumentation.

A region image's build (``region_cache._build``) and the scrubber's check of
it (``integrity.verify_image``) are the same three steps: resolve the visible
rows (``mvcc_batch``), fingerprint them (``integrity.row_checksums``), decode
them (``rowv2``).  Rows travel between the steps as ``ByteRows``: one flat
buffer and where each row lies in it.  Held here: the container, the two
readers of the engine's buffer (``frame_spans``, ``_short_values``) against
the per-row code they stand in for, the resolved rows against the scanner,
and the counter and stages that say which path a build took.
"""

import random

import numpy as np
import pytest

from copr_fixtures import PRODUCT_COLUMNS, TABLE_ID, rowv2_rows_decoded
from fixtures import delete_committed, put_committed, put_committed_large, rollback

from tikv_tpu.copr import integrity, mvcc_batch
from tikv_tpu.copr.byterows import ByteRows
from tikv_tpu.copr.dag import DagRequest, Limit, TableScan
from tikv_tpu.copr.endpoint import CoprRequest, Endpoint
from tikv_tpu.copr.mvcc_batch import MvccBatchScanSource
from tikv_tpu.copr.rowv2 import encode_row_v2
from tikv_tpu.copr.table import decode_record_handles, record_key, record_range
from tikv_tpu.native.engine import NativeEngine, frame_spans, native_available, parse_frames
from tikv_tpu.storage.btree_engine import BTreeEngine
from tikv_tpu.storage.kv import LocalEngine
from tikv_tpu.storage.mvcc import ForwardScanner
from tikv_tpu.storage.txn_types import Key, Write, WriteType
from tikv_tpu.util import trace
from tikv_tpu.util.metrics import REGISTRY

NON_HANDLE = [c for c in PRODUCT_COLUMNS if not c.is_pk_handle]
ENGINES = ["btree"] + (["native"] if native_available() else [])
STAGES = ("fill.resolve", "fill.fingerprint", "fill.decode")


def make_engine(kind):
    return NativeEngine() if kind == "native" else BTreeEngine()


def mixed_rows(eng, n=120):
    """v2 rows of several layouts (names of three lengths, NULL prices, ints
    of one and two bytes), committed at 100."""
    for i in range(n):
        name = [b"fig", b"banana", b"clementine"][i % 3]
        price = None if i % 11 == 0 else 100 + i * 37
        put_committed(eng, record_key(TABLE_ID, i),
                      encode_row_v2(NON_HANDLE, [name, i * 7 % 23, price]), 90, 100)


def scan_req(ts=200, apply_index=3):
    dag = DagRequest(executors=[TableScan(TABLE_ID, PRODUCT_COLUMNS), Limit(1 << 20)])
    return CoprRequest(103, dag, [record_range(TABLE_ID)], ts,
                       context={"region_id": 7, "region_epoch": (1, 1),
                                "apply_index": apply_index})


def stage_counts():
    wall = REGISTRY.histogram("tikv_trace_stage_seconds")
    return {s: wall.count(stage=s) for s in STAGES}


def moved(before, after):
    return {k: after[k] - before[k] for k in before}


# -- the container ------------------------------------------------------------

def test_byterows_reads_like_a_list_of_bytes():
    rows = [b"abc", b"", b"defgh", b"ij"]
    r = ByteRows.of(rows)
    assert ByteRows.of(r) is r
    assert len(r) == 4 and list(r) == rows
    assert [r[i] for i in range(4)] == rows and r[-1] == b"ij"
    assert list(r[1:3]) == rows[1:3]
    assert list(r[np.array([3, 0])]) == [b"ij", b"abc"]
    assert r.matrix() is None
    assert list(ByteRows.of([])) == [] and ByteRows.of([]).matrix() is None


def test_byterows_matrix_is_a_view_at_one_stride_and_a_copy_otherwise():
    buf = bytes(range(40))
    at = np.arange(4, dtype=np.int64) * 10 + 2
    r = ByteRows(buf, at, np.full(4, 3, dtype=np.int64))
    m = r.matrix()
    assert m.tolist() == [[2, 3, 4], [12, 13, 14], [22, 23, 24], [32, 33, 34]]
    assert np.shares_memory(m, r.flat) and not m.flags.writeable
    # rows out of order: gathered
    back = r[np.array([2, 0, 3])]
    g = back.matrix()
    assert g.tolist() == [[22, 23, 24], [2, 3, 4], [32, 33, 34]]
    assert not np.shares_memory(g, r.flat)
    # one row, and two
    assert r[:1].matrix().tolist() == [[2, 3, 4]]
    assert r[np.array([3, 1])].matrix().tolist() == [[32, 33, 34], [12, 13, 14]]
    packed = ByteRows.from_matrix(g)
    assert list(packed) == [bytes(x) for x in g.tolist()]


# -- the two readers of the engine's buffer -----------------------------------

def test_frame_spans_finds_what_parse_frames_cuts():
    import struct

    rng = random.Random(3)
    pairs = [(bytes(rng.randrange(256) for _ in range(rng.randrange(0, 30))),
              bytes(rng.randrange(256) for _ in range(rng.randrange(0, 300))))
             for _ in range(200)]
    buf = b"".join(struct.pack("<I", len(k)) + k + struct.pack("<I", len(v)) + v
                   for k, v in pairs)
    k_at, k_len, v_at, v_len = frame_spans(buf, len(pairs))
    assert list(ByteRows(buf, k_at, k_len)) == [k for k, _ in pairs]
    assert list(ByteRows(buf, v_at, v_len)) == [v for _, v in pairs]
    assert list(parse_frames(buf, len(pairs))) == pairs
    assert [len(a) for a in frame_spans(b"", 0)] == [0, 0, 0, 0]


def write_records():
    big = (1 << 63) + 12345  # a ten-byte varint
    recs = [
        Write(WriteType.PUT, 7, short_value=b"v" * 40),
        Write(WriteType.PUT, 300, short_value=b""),
        Write(WriteType.PUT, big, short_value=b"x" * 255),
        Write(WriteType.PUT, 1 << 40, short_value=bytes(range(200))),
        Write(WriteType.PUT, 9),                                  # value in CF_DEFAULT
        Write(WriteType.PUT, 9, short_value=b"abc", gc_fence=0),  # fenced
        Write(WriteType.PUT, 9, short_value=b"abc", has_overlapped_rollback=True),
        Write(WriteType.DELETE, 9),
        Write(WriteType.LOCK, 9),
        Write.new_rollback(9, True),
        Write(WriteType.DELETE, 9, short_value=b"v" * 10),        # not a PUT
    ]
    return recs


def test_short_values_agrees_with_write_from_bytes():
    recs = write_records()
    raw = [w.to_bytes() for w in recs]
    # records that stop short, and one that is only a type byte
    raw += [raw[0][:-1], raw[2][:5], b"P", b"", b"P\x80\x80"]
    at, ln, plain = mvcc_batch._short_values(ByteRows.of(raw))
    rows = ByteRows.of(raw)
    for j, b in enumerate(raw):
        try:
            w = Write.from_bytes(b)
        except ValueError:
            w = None
        want = (w is not None and w.write_type == WriteType.PUT
                and w.short_value is not None and not w.has_overlapped_rollback
                and w.gc_fence is None)
        assert bool(plain[j]) == want, j
        if want:
            assert rows.raw[at[j] : at[j] + ln[j]] == w.short_value, j
    assert plain.tolist()[:4] == [True] * 4 and not plain[4:].any()


# -- the resolved rows ----------------------------------------------------------

def history(eng):
    """Rows of mixed length with everything a key's history can hold."""
    mixed_rows(eng, 60)
    put_committed(eng, record_key(TABLE_ID, 5),
                  encode_row_v2(NON_HANDLE, [b"newer", 1, 2]), 110, 120)     # overwritten
    put_committed(eng, record_key(TABLE_ID, 6),
                  encode_row_v2(NON_HANDLE, [b"future", 1, 2]), 210, 220)    # above ts
    delete_committed(eng, record_key(TABLE_ID, 7), 110, 120)                 # deleted
    rollback(eng, record_key(TABLE_ID, 8), 130)                              # rolled back
    put_committed_large(eng, record_key(TABLE_ID, 9),
                        encode_row_v2(NON_HANDLE, [b"L" * 300, 1, 2]), 110, 120)  # CF_DEFAULT
    delete_committed(eng, record_key(TABLE_ID, 70), 110, 120)                # never lived


@pytest.mark.parametrize("kind", ENGINES)
@pytest.mark.parametrize("plain", [True, False])
def test_resolved_rows_equal_the_scanners(kind, plain):
    eng = make_engine(kind)
    if plain:
        mixed_rows(eng, 60)
    else:
        history(eng)
    start, end = record_range(TABLE_ID)
    want = list(ForwardScanner(eng.snapshot(), 200, Key.from_raw(start), Key.from_raw(end)))
    src = MvccBatchScanSource(eng.snapshot(), 200, [(start, end)], record_versions=True)
    keys, values = src._resolve_all()
    assert src.versions_exact
    # every record a plain PUT: the rows come as they lie in the scan's buffer
    assert isinstance(values, ByteRows) == plain
    assert list(zip(keys, values)) == want
    assert len(src.row_commit_ts) == len(want)
    handles = decode_record_handles(keys)
    assert handles.tolist() == [int.from_bytes(k[11:19], "big") - (1 << 63) for k, _ in want]
    by_handle = dict(zip(handles.tolist(), src.row_commit_ts.tolist()))
    assert by_handle[4] == 100
    if not plain:
        assert by_handle[5] == 120 and by_handle[6] == 100 and 7 not in by_handle
    # the fingerprint of the rows where they lie is the one of their bytes
    assert np.array_equal(
        integrity.row_checksums(keys, values),
        integrity.row_checksums([k for k, _ in want], [v for _, v in want]))


def test_two_ranges_come_back_as_one_list():
    eng = BTreeEngine()
    mixed_rows(eng, 60)
    start, end = record_range(TABLE_ID)
    mid = record_key(TABLE_ID, 30)
    src = MvccBatchScanSource(eng.snapshot(), 200, [(start, mid), (mid, end)],
                              record_versions=True)
    keys, values = src._resolve_all()
    want = list(ForwardScanner(eng.snapshot(), 200, Key.from_raw(start), Key.from_raw(end)))
    assert list(zip(keys, values)) == want
    assert len(src.row_commit_ts) == 60


# -- the counter and the stages -------------------------------------------------

def counted(fn):
    """``fn()``, the rows it decoded per path and the runs of each stage."""
    stages0 = stage_counts()
    out, rows = rowv2_rows_decoded(fn)
    return out, rows, moved(stages0, stage_counts())


@pytest.mark.parametrize("kind", ENGINES)
def test_cold_build_counts_its_rows_and_records_its_stages(kind):
    eng = make_engine(kind)
    mixed_rows(eng)
    warm = Endpoint(LocalEngine(eng), enable_device=True)

    def traced():
        old = trace.sample_rate()
        trace.set_sample_rate(1.0)
        try:
            with trace.start_trace("root") as root:
                return root.rec.trace_id, warm.handle_request(scan_req())
        finally:
            trace.set_sample_rate(old)

    (tid, r), rows, stages = counted(traced)
    assert r.metrics["region_cache"] == "miss"
    assert rows == {"uniform": 0, "vector": 120, "walk": 0}
    assert stages == {s: 1 for s in STAGES}
    spans = {s["name"]: s for s in trace.TRACER.get(tid)["spans"] if s.get("stage")}
    assert set(STAGES) <= set(spans)
    assert spans["fill.decode"]["tags"] == {"rows": 120, "path": "vector"}
    # the outer stage is suspended while its three parts run
    fill = [s for s in trace.TRACER.get(tid)["spans"] if s["name"] == "cache.fill"]
    assert fill and fill[-1]["tags"]["kind"] == "build" and fill[-1]["tags"]["rows"] == 120
    # a hit decodes nothing
    r, rows, stages = counted(lambda: warm.handle_request(scan_req()))
    assert r.metrics["region_cache"] == "hit"
    assert rows == {"uniform": 0, "vector": 0, "walk": 0}
    assert stages == {s: 0 for s in STAGES}


@pytest.mark.parametrize("kind", ENGINES)
def test_scrub_takes_the_same_three_steps(kind):
    eng = make_engine(kind)
    mixed_rows(eng)
    warm = Endpoint(LocalEngine(eng), enable_device=True)
    warm.handle_request(scan_req())
    results, rows, stages = counted(warm.scrubber.scrub_once)
    assert [x["outcome"] for x in results] == ["ok"]
    assert rows == {"uniform": 0, "vector": 120, "walk": 0}
    assert stages == {s: 1 for s in STAGES}


def test_a_delta_of_a_few_rows_walks():
    eng = BTreeEngine()
    mixed_rows(eng)
    warm = Endpoint(LocalEngine(eng), enable_device=True)
    warm.handle_request(scan_req())
    for i in (3, 50):
        put_committed(eng, record_key(TABLE_ID, i),
                      encode_row_v2(NON_HANDLE, [b"changed" * (i % 4 + 1), i, i * 1000]),
                      210, 220)
    r, rows, _stages = counted(lambda: warm.handle_request(scan_req(ts=300, apply_index=4)))
    assert r.metrics["region_cache"] in ("delta", "scan_delta", "wt_delta")
    assert rows == {"uniform": 0, "vector": 0, "walk": 2}
    cold = Endpoint(LocalEngine(eng), enable_device=False, enable_region_cache=False)
    assert r.data == cold.handle_request(scan_req(ts=300, apply_index=4)).data
