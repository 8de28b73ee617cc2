#!/usr/bin/env python
"""BASELINE config #5: a real 3-process cluster serving YCSB-E range scans
and TPC-H Q1-shaped coprocessor pushdown over TCP.

Reuses the multiprocess deployment shape proven by
tests/test_multiprocess_cluster.py (reference: test_raftstore ServerCluster,
src/server.rs:601): one PD service + three `tikv_tpu.server.standalone`
store PROCESSES over durable engine dirs (native LSM + raft log engine).
The lineitem-shaped table loads through MVCC transactions, splits into three
regions whose leaders spread across the stores, then:

  * YCSB-E — fixed-length range scans (kv_scan, 50 rows) at uniform-random
    starts against every region leader; metric = scanned rows/sec.
  * Q1 pushdown — the Q1 selection + group-by (sums/counts — the mergeable
    shape TiDB pushes down) runs per region leader through the REAL
    coprocessor service path; partials merge client-side and are verified
    against a numpy oracle over the generated arrays; metric = rows/sec
    through the executors.

Importable: ``run(...)`` returns the metrics dict (bench.py embeds it in the
driver detail JSON); ``python bench_cluster.py`` prints one JSON line.
"""

from __future__ import annotations

import json
import os
import shutil
import signal
import subprocess
import sys
import tempfile
import threading
import time

_HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, _HERE)

import numpy as np

TABLE_ID = 101
FIRST_REGION_ID = 1


def _spawn_store(store_id: int, pd_addr, data_dir: str,
                 accelerator: bool = False, device_platform: str = "cpu"):
    env = dict(os.environ)
    if not (accelerator and device_platform == "tpu"):
        # BASELINE config 5's "TPU copr plugin" role: ONE store owns the
        # chip (a chip belongs to one process) and keeps the caller's
        # platform; the others serve on the CPU backend by name
        env["JAX_PLATFORMS"] = "cpu"
    env.pop("XLA_FLAGS", None)
    env["PYTHONPATH"] = _HERE
    # EVERY store enables the device serving path since the wire-path PR:
    # generic leader serving rides the region column cache + scheduler
    # coalescing on whatever backend the store has (JAX-on-CPU for the
    # non-accelerator stores) — the 28k rows/s wall was per-request Python
    # MVCC serving, not the wire itself (docs/wire_path.md)
    argv = [sys.executable, "-m", "tikv_tpu.server.standalone",
            "--store-id", str(store_id), "--pd", f"{pd_addr[0]}:{pd_addr[1]}",
            "--dir", data_dir, "--expect-stores", "3", "--enable-device"]
    # stderr is inherited: a store that dies at device init says why
    return subprocess.Popen(argv, env=env, cwd=_HERE, stdout=subprocess.PIPE)


def _wait_ready(proc, timeout=120.0):
    # readline() blocks with no deadline of its own: a silent hung startup
    # must still fail the bench (not freeze the driver) — the watchdog kills
    # the process, which EOFs the pipe and breaks the loop.  The error names
    # the wedge (vs a fast crash) and how long the store stalled, so a
    # BENCH_rN tail alone distinguishes "device init hung at startup" from
    # "store crashed": rc=-9 with elapsed≈timeout is the watchdog's kill.
    timeout = float(os.environ.get("BENCH_CLUSTER_READY_TIMEOUT", str(timeout)))
    t0 = time.monotonic()
    watchdog = threading.Timer(timeout, lambda: os.kill(proc.pid, signal.SIGKILL))
    watchdog.daemon = True
    watchdog.start()
    try:
        while True:
            line = proc.stdout.readline()
            if not line:
                elapsed = time.monotonic() - t0
                rc = proc.poll()
                kind = ("wedged at startup (watchdog kill)"
                        if rc == -signal.SIGKILL and elapsed >= timeout - 1.0
                        else "exited before READY")
                raise RuntimeError(
                    f"store process {kind}: rc={rc} after {elapsed:.1f}s "
                    f"(timeout {timeout:.0f}s) argv={proc.args}")
            if line.startswith(b"READY"):
                return
    finally:
        watchdog.cancel()


DEVICE_STORE = 1  # the store that owns the accelerator (config 5's TPU plugin)


class _Cluster:
    def __init__(self, tmp: str, device_platform: str = "cpu"):
        from tikv_tpu.pd.client import MockPd
        from tikv_tpu.pd.service import PdService
        from tikv_tpu.server.server import Client, Server

        self.Client = Client
        self.pd = MockPd()
        self.pd_server = Server(PdService(self.pd))
        self.pd_server.start()
        self.procs = [
            _spawn_store(
                sid, self.pd_server.addr, os.path.join(tmp, f"s{sid}"),
                accelerator=sid == DEVICE_STORE, device_platform=device_platform,
            )
            for sid in (1, 2, 3)
        ]
        for p in self.procs:
            _wait_ready(p)
        self._clients: dict[int, object] = {}
        # region -> leader store, refreshed from NotLeader response hints
        # (the client-go region-cache role): a hint re-routes the NEXT call
        # immediately instead of re-polling pd.leaders on a sleep loop
        self._route: dict[int, int] = {}

    def client_for_store(self, sid: int):
        c = self._clients.get(sid)
        if c is None:
            addr = self.pd.get_store_addr(sid)
            c = self._clients[sid] = self.Client(addr[0], addr[1])
        return c

    def leader_client(self, region_id: int, timeout=30.0):
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            # the route cache (NotLeader hints) answers before PD's
            # heartbeat-lagged leader view
            sid = self._route.get(region_id) or self.pd.leaders.get(region_id)
            if sid is not None:
                return self.client_for_store(sid), sid
            time.sleep(0.1)
        raise RuntimeError(f"no leader reported for region {region_id}")

    def call_leader(self, region_id: int, method: str, req: dict, timeout=60.0):
        """Leader-following call with NotLeader/epoch retry.  A NotLeader
        response carrying a leader hint updates the route cache and re-routes
        IMMEDIATELY — no sleep, no pd.leaders re-poll."""
        deadline = time.monotonic() + timeout
        last = None
        hot_hops = 0
        while time.monotonic() < deadline:
            try:
                c, sid = self.leader_client(region_id)
                r = c.call(method, dict(req, context={"region_id": region_id}),
                           timeout=20.0)
            except (ConnectionError, TimeoutError, OSError, RuntimeError) as e:
                last = e
                self._route.pop(region_id, None)
                hot_hops = 0
                time.sleep(0.2)
                continue
            if isinstance(r, dict) and (r.get("error") or r.get("errors")):
                last = r
                hint = ((r.get("error") or {}).get("not_leader") or {}).get("leader_store")
                if hint and hint != sid:
                    self._route[region_id] = hint
                    # ONE sleepless re-route per backoff window: mid-election
                    # two stores can hint at each other, and an unbounded hot
                    # loop would hammer both until the deadline
                    if hot_hops < 1:
                        hot_hops += 1
                        continue
                else:
                    self._route.pop(region_id, None)
                hot_hops = 0
                time.sleep(0.2)
                continue
            self._route[region_id] = sid
            return r
        raise RuntimeError(f"{method} on region {region_id} never succeeded: {last!r}")

    def shutdown(self):
        for c in self._clients.values():
            try:
                c.close()
            except OSError:
                pass
        for p in self.procs:
            try:
                os.kill(p.pid, signal.SIGKILL)
            except OSError:
                pass
            p.wait()
        self.pd_server.stop()


def _lineitem_cols():
    from tikv_tpu.copr.datatypes import ColumnInfo, FieldType

    return [
        ColumnInfo(1, FieldType.int64(), is_pk_handle=True),
        ColumnInfo(2, FieldType.int64()),          # quantity
        ColumnInfo(3, FieldType.decimal_type(2)),  # extendedprice
        ColumnInfo(4, FieldType.decimal_type(2)),  # discount
        ColumnInfo(5, FieldType.int64()),          # shipdate
        ColumnInfo(6, FieldType.varchar()),        # returnflag
        ColumnInfo(7, FieldType.varchar()),        # linestatus
    ]


def run(rows: int = 60_000, scan_seconds: float = 8.0, scan_len: int = 50,
        device_platform: str = "cpu") -> dict:
    from tikv_tpu.copr.dag import Aggregation, DagRequest, SelectResponse, Selection, TableScan
    from tikv_tpu.copr.aggr import AggDescriptor
    from tikv_tpu.copr.dag_wire import dag_to_wire
    from tikv_tpu.copr.rpn import call as rpn_call, col, const_int
    from tikv_tpu.copr.table import encode_row, record_key, record_range
    from tikv_tpu.storage.txn_types import Key

    tmp = tempfile.mkdtemp(prefix="bench-cluster-")
    out: dict = {"rows": rows}
    cluster = _Cluster(tmp, device_platform=device_platform)
    try:
        # ---- load the table through MVCC transactions --------------------
        rng = np.random.default_rng(11)
        qty = rng.integers(1, 51, rows)
        price = rng.integers(90000, 10500000, rows)
        disc = rng.integers(0, 11, rows)
        ship = rng.integers(8400, 10600, rows)
        rf = rng.integers(0, 3, rows)
        ls = rng.integers(0, 2, rows)
        flags, stats = (b"A", b"N", b"R"), (b"F", b"O")
        cols = _lineitem_cols()
        non_handle = cols[1:]
        t0 = time.perf_counter()
        batch = int(os.environ.get("BENCH_CLUSTER_TXN_BATCH", "500"))
        loaded = 0
        for s in range(0, rows, batch):
            e = min(s + batch, rows)
            muts = []
            for i in range(s, e):
                rk = record_key(TABLE_ID, i)
                val = encode_row(non_handle, [
                    int(qty[i]), int(price[i]), int(disc[i]), int(ship[i]),
                    flags[rf[i]], stats[ls[i]],
                ])
                muts.append({"op": "put", "key": rk, "value": val})
            # a batch can straddle a region boundary after the split: group
            # by region, one txn per group (the leader rejects foreign keys)
            by_region: dict[int, list] = {}
            for m in muts:
                by_region.setdefault(_region_for(cluster, m["key"]), []).append(m)
            for region_id, group in by_region.items():
                ts = cluster.pd.get_tso()
                cluster.call_leader(region_id, "kv_prewrite", {
                    "mutations": group, "primary_lock": group[0]["key"],
                    "start_version": ts,
                })
                cluster.call_leader(region_id, "kv_commit", {
                    "keys": [m["key"] for m in group], "start_version": ts,
                    "commit_version": cluster.pd.get_tso(),
                })
            loaded = e
            # split into three regions once enough data exists, so the rest
            # of the load and both workloads spread across all stores
            if loaded == batch * 2:
                _split_and_spread(cluster, rows)
        out["load_s"] = round(time.perf_counter() - t0, 1)
        out["load_rows_per_s"] = round(rows / (time.perf_counter() - t0), 1)

        regions = sorted(
            rid for rid, r in cluster.pd.regions.items()
            if _overlaps_table(r)
        )
        leaders = {rid: cluster.pd.leaders.get(rid) for rid in regions}
        out["regions"] = len(regions)
        out["leader_stores"] = sorted(set(leaders.values()))

        # ---- YCSB-E: fixed-length range scans ----------------------------
        # YCSB drives with concurrent clients when the host has cores for
        # them (BENCH_CLUSTER_YCSB_CLIENTS); on this 1-core builder the
        # servers already saturate the core, so the default stays 1 —
        # extra clients would only measure context-switch overhead
        read_ts = cluster.pd.get_tso()
        n_clients = max(1, int(os.environ.get(
            "BENCH_CLUSTER_YCSB_CLIENTS",
            "1" if (os.cpu_count() or 1) < 4 else "4")))
        starts = rng.integers(0, max(rows - scan_len, 1), 100_000)
        stop_at = time.monotonic() + scan_seconds
        totals = []

        def ycsb_worker(wid: int):
            conns: dict[int, object] = {}
            scans = 0
            got_rows = 0
            lats: list[float] = []
            i = wid
            try:
                while time.monotonic() < stop_at:
                    h = int(starts[i % len(starts)])
                    i += n_clients
                    rk = record_key(TABLE_ID, h)
                    region_id = _region_for(cluster, rk)
                    sid = cluster.pd.leaders.get(region_id)
                    if sid is None:
                        time.sleep(0.05)
                        continue
                    try:
                        c = conns.get(sid)
                        if c is None:
                            addr = cluster.pd.get_store_addr(sid)
                            c = conns[sid] = cluster.Client(addr[0], addr[1])
                        t_req = time.monotonic()
                        r = c.call("kv_scan", {
                            "start_key": rk, "limit": scan_len, "version": read_ts,
                            "context": {"region_id": region_id},
                        }, timeout=20.0)
                    except (ConnectionError, TimeoutError, OSError, RuntimeError):
                        # transient (leader transfer, slow scan): drop the
                        # connection and keep driving — work already counted
                        # must survive, like the old call_leader retry loop
                        bad = conns.pop(sid, None)
                        if bad is not None:
                            try:
                                bad.close()
                            except OSError:
                                pass
                        time.sleep(0.1)
                        continue
                    if isinstance(r, dict) and not r.get("error"):
                        scans += 1
                        got_rows += len(r.get("pairs", ()))
                        lats.append(time.monotonic() - t_req)
            finally:
                # counts gathered before any failure still aggregate
                totals.append((scans, got_rows, lats))
                for c in conns.values():
                    try:
                        c.close()
                    except OSError:
                        pass

        workers = [threading.Thread(target=ycsb_worker, args=(w,))
                   for w in range(n_clients)]
        for t in workers:
            t.start()
        for t in workers:
            t.join()
        scans = sum(s for s, _r, _l in totals)
        scanned_rows = sum(r for _s, r, _l in totals)
        all_lats = [l for _s, _r, ls in totals for l in ls]
        out["ycsb_e_clients"] = n_clients
        out["ycsb_e_scans_per_s"] = round(scans / scan_seconds, 1)
        out["ycsb_e_rows_per_s"] = round(scanned_rows / scan_seconds, 1)
        if all_lats:
            # the BASELINE metric pairs rows/sec with request latency tails
            p50, p99 = np.percentile(all_lats, [50, 99])
            out["ycsb_e_p50_ms"] = round(float(p50) * 1e3, 2)
            out["ycsb_e_p99_ms"] = round(float(p99) * 1e3, 2)

        # ---- Q1 pushdown: mergeable sums/counts per region ---------------
        def q1_dag():
            aggs = [
                AggDescriptor("sum", col(1)),                        # sum(qty)
                AggDescriptor("sum", col(2)),                        # sum(price)
                AggDescriptor("sum", col(3)),                        # sum(disc)
                AggDescriptor("count", None),
            ]
            return DagRequest(executors=[
                TableScan(TABLE_ID, cols),
                Selection([rpn_call("le", col(4), const_int(10500))]),
                Aggregation([col(5), col(6)], aggs),
            ])

        wire_dag = dag_to_wire(q1_dag())
        results: dict[int, bytes] = {}
        errs: list = []

        def push(rid):
            try:
                r = cluster.call_leader(rid, "coprocessor", {
                    "dag": wire_dag, "ranges": [list(record_range(TABLE_ID))],
                    "start_ts": read_ts,
                })
                results[rid] = r["data"]
            except Exception as exc:  # noqa: BLE001
                errs.append(exc)

        t0 = time.perf_counter()
        threads = [threading.Thread(target=push, args=(rid,)) for rid in regions]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        q1_t = time.perf_counter() - t0
        if errs:
            raise errs[0]
        # client-side partial merge + oracle check (row layout: aggregates
        # first, then the group-by keys — dag.py Aggregation encoding)
        merged: dict[tuple, list] = {}
        for rid, blob in results.items():
            for row in SelectResponse.decode(blob).iter_rows():
                key = (row[4], row[5])
                acc = merged.setdefault(key, [0, 0])
                acc[0] += int(row[0])   # sum(qty)
                acc[1] += int(row[3])   # count
        mask = ship <= 10500
        want_count = int(mask.sum())
        got_count = sum(v[1] for v in merged.values())
        if got_count != want_count:
            raise AssertionError(f"Q1 merge mismatch: {got_count} != {want_count}")
        want_qty = int(qty[mask].sum())
        got_qty = sum(v[0] for v in merged.values())
        if got_qty != want_qty:
            raise AssertionError(f"Q1 sum(qty) mismatch: {got_qty} != {want_qty}")
        out["q1_pushdown_rows_per_s"] = round(rows / q1_t, 1)
        out["q1_groups"] = len(merged)

        # ---- generic wire serving, sustained (docs/wire_path.md) ----------
        # THE previously-frozen number: plain unary coprocessor RPCs to the
        # region LEADERS over TCP — no client-side device routing, no
        # cache_version hints.  Server-side the stores now serve these off
        # the region column cache through the read scheduler's continuous
        # lanes (identical requests from concurrent connections share one
        # execution slot), so sustained throughput measures the whole
        # decode -> coalesce -> execute -> encode wire path warm.
        wire_secs = float(os.environ.get("BENCH_CLUSTER_WIRE_SECONDS", "6"))
        clients_per_region = int(os.environ.get(
            "BENCH_CLUSTER_WIRE_CLIENTS_PER_REGION", "2"))
        wire_req = {"dag": wire_dag,
                    "ranges": [list(record_range(TABLE_ID))],
                    "start_ts": read_ts}

        def q1_unary(conn_cache: dict, sid: int, rid: int, timeout=30.0):
            c = conn_cache.get(sid)
            if c is None:
                addr = cluster.pd.get_store_addr(sid)
                c = conn_cache[sid] = cluster.Client(addr[0], addr[1])
            return c.call("coprocessor",
                          dict(wire_req, context={"region_id": rid}),
                          timeout=timeout)

        # A loaded store can transiently refuse through the read ladder —
        # forward breaker half-open after one slow hop, follower watermark
        # briefly behind the region's apply index.  A real client retries
        # those classes (docs/stale_reads.md, util/retry.py); the bench
        # workers do the same, BOUNDED, so a genuine routing regression
        # still fails loud instead of being masked.
        _TRANSIENT_REFUSALS = ("not_leader", "data_not_ready",
                               "server_is_busy")

        def q1_unary_retry(conn_cache: dict, sid: int, rid: int,
                           timeout=30.0, attempts=8):
            last = None
            for i in range(attempts):
                r = q1_unary(conn_cache, sid, rid, timeout=timeout)
                err = r.get("error")
                if not err:
                    return r
                if not any(k in err for k in _TRANSIENT_REFUSALS):
                    raise RuntimeError(str(err))
                last = err
                time.sleep(0.05 * (i + 1))
            raise RuntimeError(
                f"transient refusal persisted after {attempts} attempts "
                f"(store {sid}, region {rid}): {last}")

        # warmup: one request per region builds the leader's region image
        # and compiles the plan, so the timed window measures serving (the
        # leader-following helper also refreshes the route cache)
        for rid in regions:
            cluster.call_leader(rid, "coprocessor", wire_req, timeout=120.0)
            leaders[rid] = cluster._route.get(rid, leaders[rid])
        wire_counts: dict[int, int] = {rid: 0 for rid in regions}
        wire_count_mu = threading.Lock()
        wire_samples: dict[int, bytes] = {}
        wire_errs: list = []
        wire_stop = time.monotonic() + wire_secs

        def wire_worker(rid: int):
            conns: dict[int, object] = {}
            served = 0  # thread-local: 2 workers share each rid slot, and
            # a racy `wire_counts[rid] += 1` would undercount the very
            # number the wire acceptance floor is judged on
            try:
                while time.monotonic() < wire_stop:
                    r = q1_unary_retry(conns, leaders[rid], rid)
                    prev = wire_samples.setdefault(rid, r["data"])
                    if prev != r["data"]:
                        raise AssertionError(
                            f"region {rid}: coalesced response bytes drifted")
                    served += 1
            except Exception as exc:  # noqa: BLE001
                wire_errs.append(exc)
            finally:
                with wire_count_mu:
                    wire_counts[rid] += served
                for c in conns.values():
                    try:
                        c.close()
                    except OSError:
                        pass

        t0 = time.perf_counter()
        wts = [threading.Thread(target=wire_worker, args=(rid,))
               for rid in regions for _ in range(clients_per_region)]
        for t in wts:
            t.start()
        for t in wts:
            t.join()
        wire_dt = time.perf_counter() - t0
        if wire_errs:
            raise wire_errs[0]
        # byte-identity: the warm wire responses merge to the same groups
        # the per-request leader round produced
        merged_wire: dict[tuple, list] = {}
        for rid, blob in wire_samples.items():
            for row in SelectResponse.decode(blob).iter_rows():
                key = (row[4], row[5])
                acc = merged_wire.setdefault(key, [0, 0])
                acc[0] += int(row[0])
                acc[1] += int(row[3])
        if merged_wire != merged:
            raise AssertionError("sustained wire serving merge differs from oracle")
        total_reqs = sum(wire_counts.values())
        # each request processes one region's share of the table, so the
        # sustained row rate is (whole-table rows) x (mean rounds per region)
        out["q1_wire_requests"] = total_reqs
        out["q1_wire_clients"] = clients_per_region * len(regions)
        out["q1_wire_rows_per_s"] = round(
            rows * (total_reqs / max(len(regions), 1)) / wire_dt, 1)

        # ---- TypeChunk wire serving (docs/wire_path.md) -------------------
        # The same sustained Q1 workload with the per-request chunk opt-in:
        # responses come back as column slabs (encode_type + data_parts),
        # decoded against the sent plan and merged to the same oracle groups
        from tikv_tpu.copr.dag import (
            ENC_TYPE_CHUNK,
            decode_wire_response,
            response_data,
        )

        chunk_dag = q1_dag()
        chunk_dag.encode_type = ENC_TYPE_CHUNK
        wire_dag_chunk = dag_to_wire(chunk_dag)
        chunk_req = dict(wire_req, dag=wire_dag_chunk)

        def q1_chunk_retry(conn_cache, sid, rid, timeout=30.0, attempts=8):
            last = None
            for i in range(attempts):
                c = conn_cache.get(sid)
                if c is None:
                    addr = cluster.pd.get_store_addr(sid)
                    c = conn_cache[sid] = cluster.Client(addr[0], addr[1])
                r = c.call("coprocessor",
                           dict(chunk_req, context={"region_id": rid}),
                           timeout=timeout)
                err = r.get("error")
                if not err:
                    return r
                if not any(k in err for k in _TRANSIENT_REFUSALS):
                    raise RuntimeError(str(err))
                last = err
                time.sleep(0.05 * (i + 1))
            raise RuntimeError(
                f"transient refusal persisted after {attempts} attempts "
                f"(store {sid}, region {rid}): {last}")

        chunk_counts: dict[int, int] = {rid: 0 for rid in regions}
        chunk_count_mu = threading.Lock()
        chunk_samples: dict[int, dict] = {}
        chunk_errs: list = []
        chunk_secs = float(os.environ.get("BENCH_CLUSTER_WIRE_SECONDS", "6"))
        # warmup one chunk request per region (negotiation + encoder path)
        warm_chunk: dict[int, object] = {}
        for rid in regions:
            q1_chunk_retry(warm_chunk, leaders[rid], rid, timeout=120.0)
        for c in warm_chunk.values():
            try:
                c.close()
            except OSError:
                pass
        chunk_stop = time.monotonic() + chunk_secs

        def chunk_worker(rid: int):
            conns: dict[int, object] = {}
            served = 0
            try:
                while time.monotonic() < chunk_stop:
                    r = q1_chunk_retry(conns, leaders[rid], rid)
                    if not r.get("encode_type"):
                        raise AssertionError(
                            f"region {rid}: chunk opt-in answered datum")
                    prev = chunk_samples.setdefault(rid, r)
                    if response_data(prev) != response_data(r):
                        raise AssertionError(
                            f"region {rid}: chunk response bytes drifted")
                    served += 1
            except Exception as exc:  # noqa: BLE001
                chunk_errs.append(exc)
            finally:
                with chunk_count_mu:
                    chunk_counts[rid] += served
                for c in conns.values():
                    try:
                        c.close()
                    except OSError:
                        pass

        t0 = time.perf_counter()
        cts = [threading.Thread(target=chunk_worker, args=(rid,))
               for rid in regions for _ in range(clients_per_region)]
        for t in cts:
            t.start()
        for t in cts:
            t.join()
        chunk_dt = time.perf_counter() - t0
        if chunk_errs:
            raise chunk_errs[0]
        merged_chunk: dict[tuple, list] = {}
        for rid, resp in chunk_samples.items():
            for row in decode_wire_response(resp, chunk_dag).iter_rows():
                key = (row[4], row[5])
                acc = merged_chunk.setdefault(key, [0, 0])
                acc[0] += int(row[0])
                acc[1] += int(row[3])
        if merged_chunk != merged:
            raise AssertionError("TypeChunk wire serving merge differs from oracle")
        chunk_total = sum(chunk_counts.values())
        out["q1_wire_chunk_requests"] = chunk_total
        out["q1_wire_chunk_rows_per_s"] = round(
            rows * (chunk_total / max(len(regions), 1)) / chunk_dt, 1)

        # ---- Q1 via the device store -------------------------------------
        # One accelerator per deployment: every region's device-eligible DAG
        # routes to the store that owns it, using follower replica reads
        # (raftkv.py ReadIndex barrier) for regions whose leader is
        # elsewhere — so a single chip serves the whole keyspace while
        # leaders stay spread for writes.  One coprocessor_batch RPC carries
        # all region sub-requests.
        dev_client = cluster.client_for_store(DEVICE_STORE)

        def device_round():
            # cache_version: the table is static after load, so the read_ts
            # doubles as the data version — repeated rounds then ride the
            # endpoint's block cache + zone layout instead of re-scanning
            # MVCC per request (the reference's cop-cache keys on region
            # apply version the same way, cache.rs:10)
            reqs = [
                {"dag": wire_dag, "ranges": [list(record_range(TABLE_ID))],
                 "start_ts": read_ts,
                 "context": {"region_id": rid, "replica_read": True,
                             "cache_version": read_ts}}
                for rid in regions
            ]
            t0 = time.perf_counter()
            r = dev_client.call("coprocessor_batch", {"requests": reqs},
                                timeout=180.0)
            return r, time.perf_counter() - t0

        def check(r):
            for sub in r["responses"]:
                if sub.get("error"):
                    raise RuntimeError(f"device-store coprocessor error: {sub['error']}")
            return r

        r0, cold_dt = device_round()  # compile + block-cache fill
        check(r0)
        out["q1_device_cold_rows_per_s"] = round(rows / cold_dt, 1)
        # one untimed warm round: the zone layout builds lazily on the first
        # cache-hit query, and that one-time cost belongs to warmup
        check(device_round()[0])
        ts = []
        for _ in range(3):
            r, dt = device_round()
            check(r)  # a failed round must fail the metric, not speed it up
            ts.append(dt)
        merged_dev: dict[tuple, list] = {}
        for sub in r["responses"]:
            for row in SelectResponse.decode(sub["data"]).iter_rows():
                key = (row[4], row[5])
                acc = merged_dev.setdefault(key, [0, 0])
                acc[0] += int(row[0])
                acc[1] += int(row[3])
        if merged_dev != merged:
            raise AssertionError("device-store Q1 merge differs from leader-path merge")
        out["q1_device_rows_per_s"] = round(rows / float(np.median(ts)), 1)
        out["q1_device_round_ms"] = [round(x * 1e3, 1) for x in ts]
        out["q1_device_from_device"] = all(
            bool(sub.get("from_device")) for sub in r["responses"]
        )
        out["q1_device_platform"] = device_platform

        # ---- device-owner routing (docs/wire_path.md) ---------------------
        # Each region's Q1 goes to the WRONG store — one that neither leads
        # nor warms the region.  The receiving store's dispatch tier
        # forwards one hop to the advertised device owner (whose warm image
        # serves it) instead of bouncing NotLeader or serving a cold CPU
        # fallback.  Placement rides the PD heartbeat, so first wait until
        # every store's owner map covers the bench regions.
        own_deadline = time.monotonic() + 15.0
        probe = cluster.client_for_store(2)
        while time.monotonic() < own_deadline:
            owners = probe.call("debug_device_owners", {}).get("owners", {})
            if all(rid in owners for rid in regions):
                break
            time.sleep(0.3)
        else:
            raise RuntimeError(
                f"device-owner placement never advertised: {owners}")
        out["device_owners"] = {int(k): v for k, v in owners.items()}
        store_ids = (1, 2, 3)

        def _wrong(rid):
            # prefer a store that neither leads the region, nor owns its
            # image, nor is the accelerator store (whose cache holds every
            # region after the device phase): that store MUST forward
            avoid = {leaders[rid], owners.get(rid), DEVICE_STORE}
            for s in store_ids:
                if s not in avoid:
                    return s
            return next(s for s in store_ids
                        if s != leaders[rid] and s != owners.get(rid))

        wrong_store = {rid: _wrong(rid) for rid in regions}
        own_secs = float(os.environ.get("BENCH_CLUSTER_OWNER_SECONDS", "4"))
        own_counts: dict[int, int] = {rid: 0 for rid in regions}
        own_samples: dict[int, bytes] = {}
        own_errs: list = []
        # warmup one forwarded request per region (route + breaker state)
        warm_conns2: dict[int, object] = {}
        for rid in regions:
            q1_unary_retry(warm_conns2, wrong_store[rid], rid, timeout=120.0)
        for c in warm_conns2.values():
            try:
                c.close()
            except OSError:
                pass
        own_stop = time.monotonic() + own_secs

        def owner_worker(rid: int):
            conns: dict[int, object] = {}
            try:
                while time.monotonic() < own_stop:
                    r = q1_unary_retry(conns, wrong_store[rid], rid)
                    prev = own_samples.setdefault(rid, r["data"])
                    if prev != r["data"]:
                        raise AssertionError(
                            f"region {rid}: owner-routed bytes drifted")
                    own_counts[rid] += 1
            except Exception as exc:  # noqa: BLE001
                own_errs.append(exc)
            finally:
                for c in conns.values():
                    try:
                        c.close()
                    except OSError:
                        pass

        t0 = time.perf_counter()
        ots = [threading.Thread(target=owner_worker, args=(rid,))
               for rid in regions]
        for t in ots:
            t.start()
        for t in ots:
            t.join()
        own_dt = time.perf_counter() - t0
        if own_errs:
            raise own_errs[0]
        merged_own: dict[tuple, list] = {}
        for rid, blob in own_samples.items():
            for row in SelectResponse.decode(blob).iter_rows():
                key = (row[4], row[5])
                acc = merged_own.setdefault(key, [0, 0])
                acc[0] += int(row[0])
                acc[1] += int(row[3])
        if merged_own != merged:
            raise AssertionError("owner-routed serving merge differs from oracle")
        own_total = sum(own_counts.values())
        out["q1_owner_routed_requests"] = own_total
        out["q1_owner_routed_rows_per_s"] = round(
            rows * (own_total / max(len(regions), 1)) / own_dt, 1)

        # ---- per-stage wire histogram summary (tikv_wire_stage_seconds) ---
        stages_total: dict[str, dict] = {}
        for sid in store_ids:
            c = cluster.client_for_store(sid)
            st = c.call("debug_wire_stages", {}).get("stages", {})
            for stage, v in st.items():
                agg = stages_total.setdefault(stage, {"count": 0, "seconds": 0.0})
                agg["count"] += v.get("count", 0)
                agg["seconds"] += v.get("seconds", 0.0)
        out["wire_stages"] = {
            s: {"count": v["count"], "seconds": round(v["seconds"], 4),
                "mean_us": round(1e6 * v["seconds"] / max(v["count"], 1), 1)}
            for s, v in sorted(stages_total.items())
        }
        out["ok"] = True
        return out
    finally:
        cluster.shutdown()
        shutil.rmtree(tmp, ignore_errors=True)


def _region_for(cluster, raw_key: bytes):
    from tikv_tpu.storage.txn_types import Key
    from tikv_tpu.util import keys as keymod

    enc = keymod.data_key(Key.from_raw(raw_key).encoded)
    best = None
    for rid, region in cluster.pd.regions.items():
        start = keymod.data_key(region.start_key) if region.start_key else b""
        end = keymod.data_key(region.end_key) if region.end_key else None
        if enc >= start and (end is None or enc < end):
            best = rid
    return best if best is not None else FIRST_REGION_ID


def _overlaps_table(region) -> bool:
    from tikv_tpu.copr.table import record_range
    from tikv_tpu.storage.txn_types import Key

    lo_raw, hi_raw = record_range(TABLE_ID)
    # region boundaries live in ENCODED (memcomparable) key space
    lo = Key.from_raw(lo_raw).encoded
    hi = Key.from_raw(hi_raw).encoded
    start = region.start_key or b""
    end = region.end_key or None
    return (end is None or end > lo) and start < hi


def _split_and_spread(cluster, rows: int) -> None:
    """Split the table range into 3 regions and move leaders apart."""
    from tikv_tpu.copr.table import record_key
    from tikv_tpu.storage.txn_types import Key

    for frac in (1 / 3, 2 / 3):
        split_raw = record_key(TABLE_ID, int(rows * frac))
        region_id = _region_for(cluster, split_raw)
        # the service memcomparable-encodes user keys itself (kv.rs
        # split_region Key::from_raw) — pass the RAW record key
        cluster.call_leader(region_id, "kv_split_region", {"split_key": split_raw})
    # leader spread: one region leader per store via PD operators
    deadline = time.monotonic() + 20
    while time.monotonic() < deadline:
        regions = sorted(
            rid for rid, r in cluster.pd.regions.items() if _overlaps_table(r))
        leaders = {rid: cluster.pd.leaders.get(rid) for rid in regions}
        if len(regions) >= 3 and None not in leaders.values():
            break
        time.sleep(0.2)
    want = dict(zip(regions, (1, 2, 3)))
    for rid, sid in want.items():
        if cluster.pd.leaders.get(rid) != sid:
            region = cluster.pd.regions.get(rid)
            peer = region.peer_on_store(sid) if region is not None else None
            if peer is not None:
                cluster.pd.add_operator(
                    rid, {"type": "transfer_leader", "peer_id": peer.peer_id,
                          "store_id": sid})
    time.sleep(1.5)  # let heartbeats deliver the operators


def main() -> None:
    rows = int(os.environ.get("BENCH_CLUSTER_ROWS", "60000"))
    secs = float(os.environ.get("BENCH_CLUSTER_SCAN_SECONDS", "8"))
    out = run(rows, secs,
              device_platform=os.environ.get("BENCH_CLUSTER_DEVICE", "cpu"))
    print(json.dumps({
        "metric": "cluster3_q1_pushdown_rows_per_sec",
        "value": out["q1_pushdown_rows_per_s"],
        "unit": "rows/sec",
        "vs_baseline": 0.0,
        **out,
    }))


if __name__ == "__main__":
    main()
