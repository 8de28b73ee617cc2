"""Buffer-exposure sanitizer (analysis/bufsan) — seeded strikes + regression.

The strike tests are deliberate bugs: mutate a buffer INSIDE its exposure
window (between ``wire.dumps_parts`` and the frame writer's send completion,
or between a device pin and its drop) and assert bufsan reports the
mutation with BOTH stacks — exactly the nemesis-style seeding the lock-order
sanitizer gets in test_sanitizer.py.  The race test is the regression half:
write-through folds hammering a region image while a client streams chunk
responses off it over a real socket must stay byte-identical to the CPU
oracle with ZERO violations, because the fixed tree copies-on-export
(chunk slabs are immutable bytes) and a fold makes a new version of the
image instead of writing into the one a reader holds.
"""

from __future__ import annotations

import os
import socket
import threading

import numpy as np
import pytest

from copr_fixtures import PRODUCT_COLUMNS, TABLE_ID
from test_write_through import (
    NON_HANDLE,
    REGION,
    _engine,
    _req,
    _scan_dag,
    commit_ops,
)

from tikv_tpu.analysis import bufsan, sanitizer
from tikv_tpu.copr.cache import ColumnBlockCache
from tikv_tpu.copr.dag import ENC_TYPE_CHUNK, DagRequest, Limit, TableScan
from tikv_tpu.copr.dag_wire import dag_to_wire
from tikv_tpu.copr.endpoint import Endpoint
from tikv_tpu.copr.region_cache import notify_region_write
from tikv_tpu.copr.rowv2 import encode_row_v2
from tikv_tpu.copr.table import record_key, record_range
from tikv_tpu.server import wire
from tikv_tpu.server.server import Client, Server, write_frame_parts
from tikv_tpu.server.service import KvService
from tikv_tpu.storage.kv import LocalEngine
from tikv_tpu.storage.storage import Storage
from tikv_tpu.util.metrics import REGISTRY


@pytest.fixture(autouse=True)
def _isolate():
    """Seeded violations must not leak into the session-wide sanitize gate
    (conftest) or other tests — same snapshot/restore contract as
    test_sanitizer.py, extended with the bufsan ledger."""
    s_saved = sanitizer.snapshot_state()
    b_saved = bufsan.snapshot_state()
    sanitizer.clear_reports()
    bufsan.clear()
    yield
    bufsan.restore_state(b_saved)
    sanitizer.restore_state(s_saved)


# ---------------------------------------------------------------------------
# seeded strikes — both exposure kinds, both report stacks
# ---------------------------------------------------------------------------


def test_strike_wire_part_mutated_before_send():
    """Mutate the backing array between dumps_parts and write_frame_parts:
    the release verify at send completion must report, naming both the
    export site and the release site."""
    arr = np.arange(512, dtype=np.int64)
    with sanitizer.force():
        parts = wire.dumps_parts({"data": memoryview(arr).cast("B")})
        assert bufsan.ledger_size() == 1
        assert bufsan.exposed_kinds() == {"wire_part": 1}
        arr[:5] = 999  # the strike: in-place write inside the window
        a, b = socket.socketpair()
        try:
            write_frame_parts(a, parts)
        finally:
            a.close()
            b.close()
        reps = bufsan.reports()
    assert len(reps) == 1
    text = reps[0].format()
    assert "wire.dumps_parts" in text
    assert "server.write_frame_parts" in text
    # both stacks present: the exposure stack and the release stack
    assert len(reps[0].stacks) == 2
    assert all(frames for _label, frames in reps[0].stacks)
    assert bufsan.ledger_size() == 0, "release still drops the entry"


def test_strike_device_pin_bypass_write():
    """A host write into an array while it is pinned: caught by the
    release verify at drop_device."""
    with sanitizer.force():
        cache = ColumnBlockCache()
        cache.add([], 0)
        blk = cache.blocks[0]
        host = np.arange(256, dtype=np.int64)
        cache.device_arrays(blk, ("striketest", 0), lambda b: (host,))
        assert bufsan.exposed_kinds() == {"device_pin": 1}
        host[:3] = 7  # the strike: a write into a pinned array
        cache.drop_device()
        reps = bufsan.reports()
    assert len(reps) == 1
    text = reps[0].format()
    assert "cache.device_arrays" in text
    assert "cache.drop_device" in text
    assert len(reps[0].stacks) == 2


def test_strike_mutation_choke_point_reports_immediately():
    """note_mutation (the _apply_updates choke point) must report an
    overlapping live exposure BEFORE the write, with the mutation stack."""
    arr = np.zeros(4096, dtype=np.uint8)
    with sanitizer.force():
        bufsan.export("wire_part", memoryview(arr), site="test.export")
        bufsan.note_mutation([arr[100:200]], site="test.fold")
        reps = bufsan.reports()
    assert len(reps) == 1
    text = reps[0].format()
    assert "mutation" in text and "test.fold" in text and "test.export" in text


def test_note_mutation_excludes_device_pins():
    """The coordinated host-mutate-then-scatter path would otherwise be a
    permanent false positive (docs/static_analysis.md FP policy)."""
    arr = np.zeros(4096, dtype=np.uint8)
    with sanitizer.force():
        bufsan.export("device_pin", arr, site="t.pin")
        bufsan.note_mutation([arr], site="t.fold")
        assert not bufsan.reports()
        bufsan.clear()


# ---------------------------------------------------------------------------
# ledger mechanics
# ---------------------------------------------------------------------------


def test_release_unregistered_is_quiet():
    with sanitizer.force():
        assert bufsan.release(b"never exported") == 0
        assert not bufsan.reports()


def test_ledger_bound_evicts_with_verify():
    """Past _MAX_LEDGER the oldest entry is evicted — but still verified,
    so a leaked-and-mutated exposure cannot age out silently."""
    with sanitizer.force():
        first = np.arange(64, dtype=np.uint8)
        bufsan.export("wire_part", first, site="t.first")
        first[:4] = 9  # mutate while exposed; never explicitly released
        for _ in range(bufsan._MAX_LEDGER):
            bufsan.export("wire_part", np.zeros(8, dtype=np.uint8), site="t.fill")
        assert bufsan.ledger_size() == bufsan._MAX_LEDGER
        reps = bufsan.reports()
    assert len(reps) == 1
    assert "t.first" in reps[0].format()


def test_metric_counts_export_release_violation():
    c = REGISTRY.counter("tikv_bufsan_total")
    base = {e: c.get(event=e) for e in ("export", "release", "violation")}
    arr = np.arange(128, dtype=np.uint8)
    with sanitizer.force():
        bufsan.export("wire_part", arr, site="t.m")
        arr[:2] = 1
        bufsan.release(arr, site="t.m")
    assert c.get(event="export") == base["export"] + 1
    assert c.get(event="release") == base["release"] + 1
    assert c.get(event="violation") == base["violation"] + 1


@pytest.mark.skipif(os.environ.get("TIKV_TPU_SANITIZE") == "1",
                    reason="sanitize smoke run: bufsan is globally armed")
def test_disabled_is_noop():
    arr = np.arange(64, dtype=np.uint8)
    bufsan.export("wire_part", arr, site="t.off")
    assert bufsan.ledger_size() == 0
    assert bufsan.release(arr) == 0


# ---------------------------------------------------------------------------
# the regression race: wt folds vs sendmsg gather writes (ISSUE 20 sat. 2)
# ---------------------------------------------------------------------------


def test_wt_fold_races_chunk_serving_byte_identical():
    """4 client threads pull chunk responses off the warm image over a real
    socket while writer threads fold write-through deltas into the same
    region — the folds' new versions racing the ``sendmsg`` gather writes
    on the serve side.  The racing commits are IDEMPOTENT
    (same row, same value, climbing commit ts), so every read at a high ts
    sees the same visible bytes: each warm-served chunk must byte-match the
    cold CPU oracle, real folds must have happened, and bufsan (armed for
    the whole test) must stay silent because chunk slabs are copies and a
    fold writes into no version a reader holds."""
    BIG_TS = 1 << 40
    eng = LocalEngine(_engine(v2=True))
    warm = Endpoint(eng, enable_device=True)
    cold = Endpoint(eng, enable_device=True, enable_region_cache=False)
    dag = DagRequest(executors=[TableScan(TABLE_ID, PRODUCT_COLUMNS),
                                Limit(1 << 20)],
                     encode_type=ENC_TYPE_CHUNK)
    racer_val = encode_row_v2(NON_HANDLE, [b"racer", 9, 9])
    with sanitizer.force():
        # warm the image, land the first racer write, fold it once so the
        # oracle below already includes the (stable) racer value
        warm.handle_request(_req(dag, BIG_TS, 3))
        notify_region_write(
            REGION, commit_ops(eng.kv, record_key(TABLE_ID, 5),
                               racer_val, 210, 215), 4)
        r = warm.handle_request(_req(dag, BIG_TS, 4))
        assert r.metrics["region_cache"] == "wt_delta"
        oracle_bytes = bytes(cold.handle_request(_req(dag, BIG_TS, 4)).data)
        assert oracle_bytes, "oracle must have chunk payload"

        srv = Server(KvService(Storage(engine=eng), warm))
        srv.start()
        stop = threading.Event()
        errors: list = []
        fold_mu = threading.Lock()
        latest = [4]

        def folder():
            ts = 230
            while not stop.is_set():
                with fold_mu:
                    idx = latest[0] + 1
                    ops = commit_ops(eng.kv, record_key(TABLE_ID, 5),
                                     racer_val, ts, ts + 5)
                    notify_region_write(REGION, ops, idx)
                    latest[0] = idx
                ts += 10

        def client(n_iters=12):
            try:
                c = Client(*srv.addr)
                for _ in range(n_iters):
                    resp = c.call("coprocessor", {
                        "dag": dag_to_wire(dag),
                        "ranges": [list(record_range(TABLE_ID))],
                        "start_ts": BIG_TS,
                        "context": {"region_id": REGION,
                                    "region_epoch": (1, 1),
                                    "apply_index": latest[0]},
                    })
                    assert "error" not in resp, resp.get("error")
                    got = b"".join(bytes(p) for p in resp["data_parts"])
                    if got != oracle_bytes:
                        errors.append("chunk bytes diverged from oracle")
                        return
                c.close()
            except Exception as e:  # noqa: BLE001
                errors.append(repr(e))

        folders = [threading.Thread(target=folder, daemon=True)
                   for _ in range(2)]
        clients = [threading.Thread(target=client) for _ in range(4)]
        try:
            for t in folders + clients:
                t.start()
            for t in clients:
                t.join(timeout=60)
        finally:
            stop.set()
            for t in folders:
                t.join(timeout=10)
            srv.stop()
        assert not errors, errors
        # the race must be real: deltas actually folded into the warm image
        assert warm.region_cache.stats.wt_deltas >= 1
        assert not bufsan.reports(), [r.format() for r in bufsan.reports()]
