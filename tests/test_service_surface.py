"""Socket tests for the Appendix-A RPCs added in round 2: MVCC debug reads,
raw_batch_scan, GC support (unsafe_destroy_range, physical_scan_lock + lock
observer trio), get_store_safe_ts, get_lock_wait_info, Backup and
Diagnostics services — each driven over the framed-TCP wire against the full
single-node assembly (kv.rs:229-1061, server.rs:887-993)."""

import threading
import time

import pytest

from tikv_tpu.pd.client import MockPd
from tikv_tpu.server.node import FIRST_REGION_ID
from tikv_tpu.server.server import Client
from tikv_tpu.server.standalone import StoreServer
from tikv_tpu.pd.service import PdService
from tikv_tpu.server.server import Server


@pytest.fixture(scope="module")
def node_client():
    pd = MockPd()
    pds = Server(PdService(pd))
    pds.start()
    from tikv_tpu.pd.service import RemotePd

    srv = StoreServer(1, RemotePd(*pds.addr))
    srv.start()
    srv.bootstrap_or_join(1)
    # wait for leadership
    deadline = time.monotonic() + 10
    while time.monotonic() < deadline:
        p = srv.store.peers.get(FIRST_REGION_ID)
        if p is not None and p.node.is_leader():
            break
        time.sleep(0.05)
    client = Client(*srv.server.addr)
    yield srv, client, pd
    client.close()
    srv.stop()
    pds.stop()


CTX = {"region_id": FIRST_REGION_ID}


def _put(client, pd, key, value):
    ts1 = pd.get_tso()
    r = client.call(
        "kv_prewrite",
        {
            "mutations": [{"op": "put", "key": key, "value": value}],
            "primary_lock": key,
            "start_version": ts1,
            "context": CTX,
        },
    )
    assert "error" not in r and not r.get("errors"), r
    r = client.call(
        "kv_commit",
        {"keys": [key], "start_version": ts1, "commit_version": pd.get_tso(), "context": CTX},
    )
    assert "error" not in r, r
    return ts1


def test_mvcc_get_by_key_and_start_ts(node_client):
    srv, client, pd = node_client
    ts1 = _put(client, pd, b"mk", b"mv1")
    _put(client, pd, b"mk", b"mv2")
    r = client.call("mvcc_get_by_key", {"key": b"mk", "context": CTX})
    assert "error" not in r, r
    assert r["info"]["lock"] is None
    assert len(r["info"]["writes"]) == 2
    assert r["info"]["writes"][0]["short_value"] == b"mv2"  # newest first
    r2 = client.call("mvcc_get_by_start_ts", {"start_ts": ts1, "context": CTX})
    assert r2["key"] == b"mk"
    assert any(w["start_ts"] == ts1 for w in r2["info"]["writes"])


def test_mvcc_get_by_start_ts_finds_live_lock(node_client):
    srv, client, pd = node_client
    ts = pd.get_tso()
    r = client.call(
        "kv_prewrite",
        {
            "mutations": [{"op": "put", "key": b"locked-k", "value": b"x"}],
            "primary_lock": b"locked-k",
            "start_version": ts,
            "context": CTX,
        },
    )
    assert "error" not in r and not r.get("errors"), r
    r = client.call("mvcc_get_by_start_ts", {"start_ts": ts, "context": CTX})
    assert r["key"] == b"locked-k"
    assert r["info"]["lock"] is not None and r["info"]["lock"]["start_ts"] == ts
    # cleanup: rollback so later tests see no lock
    client.call("kv_batch_rollback", {"keys": [b"locked-k"], "start_version": ts, "context": CTX})


def test_raw_batch_scan(node_client):
    srv, client, pd = node_client
    for i in range(6):
        client.call("raw_put", {"key": b"rb%d" % i, "value": b"v%d" % i, "context": CTX})
    r = client.call(
        "raw_batch_scan",
        {"ranges": [[b"rb0", b"rb2"], [b"rb4", b"rb9"]], "each_limit": 10, "context": CTX},
    )
    got = [k for k, _v in r["kvs"]]
    assert got == [b"rb0", b"rb1", b"rb4", b"rb5"]


def test_kv_gc_is_deliberate_stub(node_client):
    srv, client, pd = node_client
    r = client.call("kv_gc", {"context": CTX})
    assert "deprecated" in r["error"]["other"]


def test_lock_observer_trio_and_physical_scan(node_client):
    srv, client, pd = node_client
    max_ts = pd.get_tso() + (1000 << 18)
    assert client.call("register_lock_observer", {"max_ts": max_ts}) == {}
    ts = pd.get_tso()
    client.call(
        "kv_prewrite",
        {
            "mutations": [{"op": "put", "key": b"obs-k", "value": b"x"}],
            "primary_lock": b"obs-k",
            "start_version": ts,
            "context": CTX,
        },
    )
    r = client.call("check_lock_observer", {})
    assert r["is_clean"] is True
    assert any(l["key"] == b"obs-k" and l["lock_ts"] == ts for l in r["locks"]), r
    # physical scan sees it too (green GC fallback path)
    r = client.call("physical_scan_lock", {"max_ts": max_ts})
    assert any(l["key"] == b"obs-k" for l in r["locks"])
    assert client.call("remove_lock_observer", {}) == {}
    r = client.call("check_lock_observer", {})
    assert "error" in r  # no observer registered anymore
    client.call("kv_batch_rollback", {"keys": [b"obs-k"], "start_version": ts, "context": CTX})


def test_unsafe_destroy_range(node_client):
    srv, client, pd = node_client
    _put(client, pd, b"udr-a", b"1")
    _put(client, pd, b"udr-b", b"2")
    _put(client, pd, b"uds-keep", b"3")
    r = client.call("unsafe_destroy_range", {"start_key": b"udr-", "end_key": b"udr-\xff"})
    assert "error" not in r, r
    r = client.call("kv_get", {"key": b"udr-a", "version": pd.get_tso(), "context": CTX})
    assert r.get("value") is None
    r = client.call("kv_get", {"key": b"uds-keep", "version": pd.get_tso(), "context": CTX})
    assert r["value"] == b"3"


def test_get_store_safe_ts(node_client):
    srv, client, pd = node_client
    _put(client, pd, b"sts", b"v")
    srv.resolved_ts.advance_all()
    r = client.call("get_store_safe_ts", {})
    assert r["safe_ts"] > 0


def test_get_lock_wait_info(node_client):
    srv, client, pd = node_client
    r = client.call("get_lock_wait_info", {})
    assert r == {"entries": []}
    done = threading.Event()

    def waiter():
        try:
            srv.lock_manager.wait_for(900, 800, b"wk", timeout=2.0)
        finally:
            done.set()

    t = threading.Thread(target=waiter, daemon=True)
    t.start()
    deadline = time.monotonic() + 2
    entries = []
    while time.monotonic() < deadline and not entries:
        entries = client.call("get_lock_wait_info", {})["entries"]
        time.sleep(0.02)
    assert entries and entries[0]["txn"] == 900 and entries[0]["wait_for_txn"] == 800
    srv.lock_manager.wake_up(b"wk", 800)
    done.wait(3)


def test_pessimistic_lock_waits_for_release(node_client):
    """kv_pessimistic_lock with wait_timeout_ms parks on the waiter manager
    and retries after the blocker commits (waiter_manager.rs flow)."""
    srv, client, pd = node_client
    ts1 = pd.get_tso()
    r = client.call(
        "kv_prewrite",
        {
            "mutations": [{"op": "put", "key": b"pw-k", "value": b"v1"}],
            "primary_lock": b"pw-k",
            "start_version": ts1,
            "context": CTX,
        },
    )
    assert "error" not in r and not r.get("errors"), r
    results = {}

    def contender():
        c2 = Client(*srv.server.addr)
        ts2 = pd.get_tso()
        resp = c2.call(
            "kv_pessimistic_lock",
            {
                "keys": [b"pw-k"],
                "primary_lock": b"pw-k",
                "start_version": ts2,
                "for_update_ts": ts2,
                "wait_timeout_ms": 5000,
                "context": CTX,
            },
            timeout=15,
        )
        if "conflict" in (resp.get("error") or {}):
            # the blocker committed above our for_update_ts while we waited:
            # like TiDB, retry at a fresh for_update_ts (the wait part —
            # which this test measures — already succeeded)
            resp = c2.call(
                "kv_pessimistic_lock",
                {
                    "keys": [b"pw-k"],
                    "primary_lock": b"pw-k",
                    "start_version": ts2,
                    "for_update_ts": pd.get_tso(),
                    "wait_timeout_ms": 0,
                    "context": CTX,
                },
                timeout=15,
            )
        results["resp"] = resp
        results["ts2"] = ts2
        c2.close()

    t = threading.Thread(target=contender, daemon=True)
    t.start()
    # the contender is parked on the wait queue
    deadline = time.monotonic() + 3
    entries = []
    while time.monotonic() < deadline and not entries:
        entries = client.call("get_lock_wait_info", {})["entries"]
        time.sleep(0.02)
    assert entries and entries[0]["wait_for_txn"] == ts1, entries
    # blocker commits -> waiter wakes, retries, acquires
    client.call(
        "kv_commit",
        {"keys": [b"pw-k"], "start_version": ts1, "commit_version": pd.get_tso(), "context": CTX},
    )
    t.join(10)
    assert not t.is_alive()
    assert "error" not in results["resp"], results["resp"]
    # cleanup the pessimistic lock
    client.call(
        "kv_pessimistic_rollback",
        {"keys": [b"pw-k"], "start_version": results["ts2"], "for_update_ts": results["ts2"], "context": CTX},
    )


def test_backup_service_over_wire(node_client, tmp_path):
    srv, client, pd = node_client
    _put(client, pd, b"bk-1", b"bv1")
    _put(client, pd, b"bk-2", b"bv2")
    backup_ts = pd.get_tso()
    r = client.call(
        "backup",
        {
            "storage": f"local://{tmp_path}",
            "ranges": [[b"bk-", b"bk-\xff"]],
            "backup_ts": backup_ts,
            "name_prefix": "t1",
            "context": CTX,
        },
    )
    assert "error" not in r, r
    assert r["files"][0]["kvs"] == 2
    # the file is really in the external storage
    from tikv_tpu.sidecar.backup import LocalStorage

    st = LocalStorage(str(tmp_path))
    assert "t1-0000" in st.list()


def test_diagnostics_service(node_client, tmp_path):
    srv, client, pd = node_client
    log = tmp_path / "store.log"
    log.write_text(
        "2026-07-29 10:00:00 INFO start ok\n"
        "2026-07-29 10:00:01 WARN slow request region=1\n"
        "2026-07-29 10:00:02 ERROR disk failure on /dev/x\n"
    )
    srv.service.diagnostics.log_path = str(log)
    r = client.call("diagnostics_search_log", {"patterns": ["region=1"]})
    assert len(r["lines"]) == 1 and r["lines"][0]["level"] == "WARN"
    r = client.call("diagnostics_search_log", {"levels": ["ERROR"]})
    assert len(r["lines"]) == 1 and "disk failure" in r["lines"][0]["message"]
    info = client.call("diagnostics_server_info", {})
    assert info["cpu_count"] >= 1 and info["pid"] > 0 and "memory" in info


def test_standalone_builds_mesh_endpoint_on_multidevice(tmp_path):
    """Under the 8-virtual-device test mesh, the ASSEMBLED store serves the
    coprocessor through a (regions × groups) mesh (the copr scale-out path
    is reachable from the real serving assembly)."""
    import jax

    from tikv_tpu.pd.client import MockPd
    from tikv_tpu.pd.service import PdService
    from tikv_tpu.server.server import Server
    from tikv_tpu.server.standalone import StoreServer

    assert jax.device_count() == 8
    pds = Server(PdService(MockPd()))
    pds.start()
    from tikv_tpu.pd.service import RemotePd

    srv = StoreServer(1, RemotePd(*pds.addr), enable_device=True)
    try:
        mesh = srv.copr.mesh
        assert mesh is not None and mesh.size == 8
        assert dict(mesh.shape) == {"regions": 4, "groups": 2}
    finally:
        srv.stop()
        pds.stop()
