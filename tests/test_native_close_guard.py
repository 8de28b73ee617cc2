"""A native engine cannot be freed under a reader (native/guard.h).

Three sentences hold the store: a native call in flight when ``close()`` is
called finishes normally before the engine is freed; a call made after
``close()``, through the engine or through a snapshot or cursor taken before
it, raises ``EngineClosed`` and passes no freed or NULL handle to native code;
``close()`` twice is harmless.  Before the guard the first scenario below
ended the process with a segmentation fault (``eng_close`` was ``delete e``
under a cursor's ``eng_seek``), which is what ``StoreServer.stop()`` did to a
scrub round that outlived the scrubber's join.

Every scenario runs in a child process with a time limit of its own: a crash
must cost one test, not a pytest worker.  The child prints one JSON line.
"""

import json
import os
import subprocess
import sys

import pytest

try:
    from tikv_tpu.native.engine import native_available

    _NATIVE = native_available()
except ImportError:
    _NATIVE = False

HERE = os.path.dirname(os.path.abspath(__file__))
CHILD_LIMIT_S = 90
N_KEYS = 60_000


def _loaded_engine(path=None):
    from tikv_tpu.native.engine import NativeEngine

    eng = NativeEngine(path)
    eng.bulk_load("default", [(b"k%08d" % i, b"v" * 64) for i in range(N_KEYS)])
    return eng


def _reader(fn, seen: list, count: list):
    """Run ``fn`` (which reads until it is refused, counting in ``count``) on
    a thread; ``seen`` gets the exception's class name and the count."""
    import threading

    def body():
        try:
            fn(count)
        except Exception as e:  # noqa: BLE001 — the class is what is asserted
            seen.append((type(e).__name__, count[0]))
        else:
            seen.append(("returned", count[0]))

    t = threading.Thread(target=body)
    t.start()
    return t


def _once_reading(seen_something, limit_s: float = 30.0) -> None:
    """Wait until the reader has demonstrably read (a loaded host may take a
    while to get it going), then a moment more so that it is mid-flight."""
    import time

    deadline = time.monotonic() + limit_s
    while not seen_something() and time.monotonic() < deadline:
        time.sleep(0.01)
    time.sleep(0.3)


def _refused(engine: str) -> float:
    from tikv_tpu.util.metrics import REGISTRY

    return REGISTRY.counter("tikv_engine_closed_call_total", "").get(engine=engine)


def _child_cursor() -> dict:
    """Close under a cursor that steps with ``next()``: one seek a key."""
    eng = _loaded_engine()
    snap = eng.snapshot()

    def step(count):
        cur = snap.cursor_cf("default")
        while True:  # until the engine is closed under it
            ok = cur.seek_to_first()
            while ok:
                count[0] += 1
                ok = cur.next()

    seen, count = [], [0]
    t = _reader(step, seen, count)
    _once_reading(lambda: count[0] > 1000)
    eng.close()
    eng.close()
    t.join(30)
    return {"seen": seen, "alive": t.is_alive(), "refused": _refused("kv")}


def _child_scan() -> dict:
    """Close under ``eng_scan``: the scan in flight comes back whole."""
    eng = _loaded_engine()
    snap = eng.snapshot()
    sizes: list = []

    def scan(count):
        while True:
            n, _buf = snap.scan_raw("default", b"", None)
            sizes.append(n)
            count[0] += 1

    seen, count = [], [0]
    t = _reader(scan, seen, count)
    _once_reading(lambda: count[0] > 0)
    eng.close()
    t.join(30)
    return {"seen": seen, "alive": t.is_alive(), "sizes": sorted(set(sizes))}


def _child_after() -> dict:
    """Every call after close, through whatever was taken before it."""
    import tempfile

    from tikv_tpu.native import EngineClosed
    from tikv_tpu.native.raftlog import NativeRaftLog
    from tikv_tpu.storage.engine import WriteBatch

    out: dict = {}
    with tempfile.TemporaryDirectory() as tmp:
        eng = _loaded_engine(os.path.join(tmp, "kv"))
        snap = eng.snapshot()
        cur = snap.cursor_cf("default")
        assert cur.seek(b"k") and snap.get_cf("default", b"k00000001")
        eng.start_auto_compaction(0.05)
        eng.close()
        eng.close()
        wb = WriteBatch()
        wb.put_cf("default", b"x", b"y")
        calls = {
            "cursor.next": cur.next,
            "cursor.seek": lambda: cur.seek(b"k"),
            "snap.get_cf": lambda: snap.get_cf("default", b"k00000001"),
            "snap.scan_raw": lambda: snap.scan_raw("default", b"", None),
            "snap.cursor_cf.seek_to_last":
                lambda: snap.cursor_cf("default").seek_to_last(),
            "eng.snapshot": eng.snapshot,
            "eng.write": lambda: eng.write(wb),
            "eng.get_cf": lambda: eng.get_cf("default", b"k00000001"),
            "eng.seq": eng.seq,
            "eng.cf_touched_seq": lambda: eng.cf_touched_seq("lock"),
            "eng.mem_bytes": eng.mem_bytes,
            "eng.wal_bytes": eng.wal_bytes,
            "eng.run_count": lambda: eng.run_count("default"),
            "eng.perf_context": eng.perf_context,
            "eng.checkpoint": eng.checkpoint,
            "eng.compact": eng.compact,
            "eng.mvcc_properties": eng.mvcc_properties,
            "eng.set_mem_limit": lambda: eng.set_mem_limit(1 << 20),
        }
        log = NativeRaftLog(os.path.join(tmp, "raft"))
        log.append(1, 1, [b"a", b"b"], state=b"hs")
        assert log.last_index(1) == 2
        log.close()
        log.close()
        calls.update({
            "log.append": lambda: log.append(1, 3, [b"c"]),
            "log.put_state": lambda: log.put_state(1, b"hs2"),
            "log.entries": lambda: log.entries(1),
            "log.first_index": lambda: log.first_index(1),
            "log.last_index": lambda: log.last_index(1),
            "log.state": lambda: log.state(1),
            "log.regions": log.regions,
            "log.stats": log.stats,
            "log.sync": log.sync,
            "log.purge": lambda: log.purge(1, 1),
            "log.clean": lambda: log.clean(1),
        })
        for name, fn in calls.items():
            try:
                fn()
            except EngineClosed:
                out[name] = "EngineClosed"
            except Exception as e:  # noqa: BLE001
                out[name] = repr(e)
            else:
                out[name] = "returned"
        snap.release()  # a no-op on a closed engine, and no exception
        n_kv = sum(1 for k in calls if not k.startswith("log."))
        out["refused"] = {"kv": _refused("kv"), "raftlog": _refused("raftlog"),
                          "want": {"kv": n_kv, "raftlog": len(calls) - n_kv}}
    return out


def _warm_endpoint(engine):
    """An endpoint over ``engine`` with one warm image of 4,000 rows."""
    sys.path.insert(0, HERE)
    from copr_fixtures import PRODUCT_COLUMNS, TABLE_ID
    from fixtures import put_committed

    from tikv_tpu.copr.dag import DagRequest, Limit, TableScan
    from tikv_tpu.copr.endpoint import CoprRequest, Endpoint
    from tikv_tpu.copr.table import encode_row, record_key, record_range
    from tikv_tpu.storage.kv import LocalEngine

    non_handle = [c for c in PRODUCT_COLUMNS if not c.is_pk_handle]
    for i in range(4000):
        put_committed(engine, record_key(TABLE_ID, i),
                      encode_row(non_handle, [b"apple", i % 23, i]), 90, 100)
    ep = Endpoint(LocalEngine(engine), enable_device=True)
    dag = DagRequest(executors=[TableScan(TABLE_ID, PRODUCT_COLUMNS), Limit(1 << 20)])
    r = ep.handle_request(CoprRequest(
        103, dag, [record_range(TABLE_ID)], 200,
        context={"region_id": 7, "region_epoch": (1, 1), "apply_index": 3}))
    assert r.metrics["region_cache"] == "miss"
    return ep


def _child_scrub() -> dict:
    """Close under ``scrub_once``: a round verifies an image against the
    engine, one seek a key, with no look at anything but the engine."""
    from tikv_tpu.native.engine import NativeEngine

    eng = NativeEngine()
    ep = _warm_endpoint(eng)
    first = ep.scrubber.scrub_once()
    assert [r["outcome"] for r in first] == ["ok"], first
    errors: list = []

    def rounds(count):
        while True:
            for res in ep.scrubber.scrub_once():
                if res["outcome"] != "ok":
                    errors.append(res.get("error") or res["outcome"])
                    return
            count[0] += 1

    seen, count = [], [0]
    t = _reader(rounds, seen, count)
    _once_reading(lambda: count[0] > 0)
    eng.close()
    t.join(30)
    return {"seen": seen, "alive": t.is_alive(), "errors": errors}


def _child_store_stop() -> dict:
    """``StoreServer.stop()`` with a scrub round in flight on a thread the
    store does not know, and its own cadenced scrubber running."""
    import tempfile
    import time

    from tikv_tpu.copr.dag import DagRequest, Limit, TableScan
    from tikv_tpu.copr.dag_wire import dag_to_wire
    from tikv_tpu.copr.table import encode_row, record_key, record_range
    from tikv_tpu.pd.client import MockPd
    from tikv_tpu.pd.service import PdService, RemotePd
    from tikv_tpu.server.node import FIRST_REGION_ID
    from tikv_tpu.server.server import Client, Server
    from tikv_tpu.server.standalone import StoreServer
    from tikv_tpu.util.metrics import REGISTRY

    sys.path.insert(0, HERE)
    from copr_fixtures import PRODUCT_COLUMNS, TABLE_ID

    non_handle = [c for c in PRODUCT_COLUMNS if not c.is_pk_handle]
    ctx = {"region_id": FIRST_REGION_ID}
    with tempfile.TemporaryDirectory() as tmp:
        pd = MockPd()
        pds = Server(PdService(pd))
        pds.start()
        srv = StoreServer(1, RemotePd(*pds.addr), data_dir=os.path.join(tmp, "s1"),
                          enable_device=True, integrity_scrub_interval=0.2)
        srv.start()
        srv.bootstrap_or_join(1)
        client = Client(*srv.server.addr)
        deadline = time.monotonic() + 20
        for lo in range(0, 3000, 500):
            muts = [{"op": "put", "key": record_key(TABLE_ID, i),
                     "value": encode_row(non_handle, [b"fig", i % 23, i])}
                    for i in range(lo, lo + 500)]
            start = pd.get_tso()
            while True:
                r = client.call("kv_prewrite", {
                    "mutations": muts, "primary_lock": muts[0]["key"],
                    "start_version": start, "context": ctx})
                if "error" not in r and not r.get("errors"):
                    break
                assert time.monotonic() < deadline, r  # the region elects
                time.sleep(0.1)
            r = client.call("kv_commit", {
                "keys": [m["key"] for m in muts], "start_version": start,
                "commit_version": pd.get_tso(), "context": ctx})
            assert "error" not in r, r
        dag = DagRequest(executors=[TableScan(TABLE_ID, PRODUCT_COLUMNS),
                                    Limit(1 << 20)])
        r = client.call("coprocessor", {
            "dag": dag_to_wire(dag), "ranges": [list(record_range(TABLE_ID))],
            "start_ts": pd.get_tso(), "context": ctx})
        assert "error" not in r and not r.get("errors"), r
        assert srv.copr.region_cache.stats.misses == 1
        outcomes: list = []

        def rounds(count):
            while True:
                for res in srv.copr.scrubber.scrub_once():
                    outcomes.append(res["outcome"])
                    if res["outcome"] == "error":
                        outcomes.append(res["error"])
                        return
                count[0] += 1

        seen, count = [], [0]
        t = _reader(rounds, seen, count)
        _once_reading(lambda: count[0] > 0)
        client.close()
        srv.stop()
        pds.stop()
        t.join(30)
        abandoned = REGISTRY.counter("tikv_server_stop_abandoned_thread_total", "")
        return {"seen": seen, "alive": t.is_alive(), "last": outcomes[-2:],
                "ok_rounds": outcomes.count("ok"), "refused": _refused("kv"),
                "abandoned": {n: abandoned.get(thread=n) for n in (
                    "integrity-scrub", "copr-sched", "ttl-checker",
                    "resolved-ts-advance", "geometry-tuner")}}


_CHILDREN = {
    "cursor": _child_cursor, "scan": _child_scan, "after": _child_after,
    "scrub": _child_scrub, "store_stop": _child_store_stop,
}


def _run_child(name: str) -> dict:
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONFAULTHANDLER="1",
               PYTHONPATH=os.pathsep.join(
                   [os.path.dirname(HERE), os.environ.get("PYTHONPATH", "")]))
    p = subprocess.run([sys.executable, os.path.abspath(__file__), name],
                       capture_output=True, text=True, timeout=CHILD_LIMIT_S,
                       env=env)
    assert p.returncode == 0, (p.returncode, p.stderr[-3000:])
    return json.loads(p.stdout.strip().splitlines()[-1])


needs_native = pytest.mark.skipif(not _NATIVE, reason="native engine unavailable")


@needs_native
def test_close_under_a_stepping_cursor():
    """The twenty-line reproduction of PERF.md §7 row 0a: exit 139 before the
    guard, frame ``native/engine.py:_do_seek``."""
    got = _run_child("cursor")
    assert not got["alive"]
    ((what, steps),) = got["seen"]
    assert what == "EngineClosed" and steps > 0
    assert got["refused"] == 1


@needs_native
def test_close_under_a_scan_lets_the_scan_finish():
    got = _run_child("scan")
    assert not got["alive"]
    ((what, scans),) = got["seen"]
    assert what == "EngineClosed" and scans > 0
    # every scan that came back came back whole, the one in flight too
    assert got["sizes"] == [N_KEYS]


@needs_native
def test_close_under_a_scrub_round():
    got = _run_child("scrub")
    assert not got["alive"]
    # the round met the closed engine either where it takes its snapshot or
    # inside verify_image's scan; both say so
    seen = got["seen"][0][0]
    assert seen == "EngineClosed" or (
        seen == "returned" and "EngineClosed" in got["errors"][0]), got


@needs_native
def test_calls_after_close_raise_engine_closed():
    got = _run_child("after")
    refused = got.pop("refused")
    assert set(got.values()) == {"EngineClosed"}, got
    assert refused["kv"] >= refused["want"]["kv"]
    assert refused["raftlog"] == refused["want"]["raftlog"]


@needs_native
def test_store_stop_with_a_scrub_round_in_flight():
    got = _run_child("store_stop")
    assert not got["alive"], got
    assert got["ok_rounds"] > 0
    seen = got["seen"][0][0]
    assert seen == "EngineClosed" or "EngineClosed" in str(got["last"]), got
    assert got["refused"] >= 1
    # the store's own threads all ended inside their joins
    assert set(got["abandoned"].values()) == {0}, got


if __name__ == "__main__":
    print(json.dumps(_CHILDREN[sys.argv[1]]()))
