#!/usr/bin/env python
"""The quickest proof that the served coprocessor path still runs on the chip.

ONE process: an in-process PD service, one durable
``StoreServer(enable_device=True)`` with every other argument at its default,
and socket ``Client``s.  A lineitem-shaped table goes in through the socket
(``kv_prewrite`` / ``kv_commit``) into regions split with ``kv_split_region``;
a fixed plan set is then served through the socket, one coprocessor task per
region as TiDB issues them, cold once and warm twice; rows are written into a
warm region and read back.  Every response is byte-compared with
``BatchExecutorsRunner`` (the repo's plain reference) over the rows that were
written.

Each phase prints one JSON object on a line of its own.  The LAST line of
standard output is the contract's result and nothing else:

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

Anything but a TPU ends the script non-zero before any result is printed,
and so does any phase that raises: nothing here records an error and carries
on.  ``--chips 4`` runs ONLY the four-chip path (mesh-sharded warm serving)
and what it is compared with; ``--rows-per-region`` / ``--regions`` are for
rehearsals at a smaller size and are printed when used.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import tempfile
import threading
import time
import traceback

_HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, _HERE)

TABLE_ID = 101
REGIONS = 4
# ~48 MiB of key+value: what a region holds right after a split at upstream's
# 96 MiB region-split-size (SURVEY.md:11), at 19-byte keys and 56-byte rows
ROWS_PER_REGION = 600_000
ROWS_PER_REGION_4CHIP = 100_000
LOAD_BATCH = 2_000
LOAD_BUDGET_S = 360.0
WRITE_ROWS = 300
READ_BACK_SAMPLE = 2_000
PASSES = ("cold", "warm1", "warm2")
MAX_EXTRA_WARM = 2

_CACHE_OUTCOMES = ("hit", "miss", "delta", "wt_delta", "too_big", "stale",
                   "uncacheable")
_SERVE_PATHS = ("cpu", "unary", "zone", "fused", "xregion", "mesh", "rank",
                "hash", "dict_rewrite")
_ERROR_CAUSES = ("device_error", "zone_error")


def emit(phase: str, **kw) -> None:
    print(json.dumps({"phase": phase, **kw}), flush=True)


# ---------------------------------------------------------------------------
# plans
# ---------------------------------------------------------------------------


def plan_set() -> dict:
    """name -> DagRequest.  Q6 reduces at capacity 1; Q1 groups over resident
    dictionary codes; g550 groups by quantity x discount (550 groups, so the
    1024-slot bucket and with it the limb-matmul segment sum); minmax runs
    the masked min/max form; topn the running top-K merge; scan_chunk a
    Selection+Limit scan answered in TypeChunk encoding."""
    from dataclasses import replace

    import lineitem_fixture as fx
    from tikv_tpu.copr.aggr import AggDescriptor
    from tikv_tpu.copr.dag import (
        ENC_TYPE_CHUNK, Aggregation, DagRequest, Selection, TableScan, TopN,
    )
    from tikv_tpu.copr.rpn import call, col, const_int

    schema = fx._lineitem()
    le_ship = Selection([call("le", col(4), const_int(10500))])
    return {
        "q6": fx.q6_dag(),
        "q1": fx.q1_dag(),
        "g550": DagRequest(executors=[
            TableScan(TABLE_ID, schema), le_ship,
            Aggregation([col(1), col(3)],
                        [AggDescriptor("sum", col(2)),
                         AggDescriptor("count", None)]),
        ]),
        "minmax": DagRequest(executors=[
            TableScan(TABLE_ID, schema), le_ship,
            Aggregation([col(5)],
                        [AggDescriptor("min", col(2)),
                         AggDescriptor("max", col(2)),
                         AggDescriptor("min", col(4)),
                         AggDescriptor("max", col(1))]),
        ]),
        # lineitem_fixture._topn_endpoint's plan over the whole row: the flag
        # columns ride as dictionary codes, and the plans share one image per
        # region
        "topn": DagRequest(executors=[
            TableScan(TABLE_ID, schema), le_ship,
            TopN([(col(2), True), (col(1), False)], 100),
        ]),
        "scan_chunk": replace(fx._filter_dag("selection", limit=4096),
                              encode_type=ENC_TYPE_CHUNK),
    }


AGG_PLANS = ("q6", "q1", "g550", "minmax")
# the sharded launcher groups on resident dictionary codes; plain integer
# group columns have none, and the one-device warm path serves them
MESH_DECLINES = ("g550",)
JOIN_SKIPPED = (
    "the PR-18 join does not fit this fixture: its build side is a second "
    "table in another region whose apply index the client must name in the "
    "Join descriptor, which a socket client has no RPC to learn, and its "
    "answer is the joined rows of a whole region"
)


# ---------------------------------------------------------------------------
# load order
# ---------------------------------------------------------------------------


def load_order(regions: int, rows_per_region: int, batch: int):
    """``(region, first row, end row)`` batch by batch.  Regions 0 and 1
    alternate, so they grow together; the others follow one after another.
    Whenever a time budget stops this, what is left is whole regions first
    (never fewer than two) and only then fewer rows per region."""
    steps = range(0, rows_per_region, batch)
    for s in steps:
        for k in range(min(regions, 2)):
            yield k, s, min(s + batch, rows_per_region)
    for k in range(2, regions):
        for s in steps:
            yield k, s, min(s + batch, rows_per_region)


def settle(loaded: list[int], rows_per_region: int) -> tuple[int, int]:
    """(regions, rows per region) that the rows loaded so far fill evenly."""
    full = 0
    while full < len(loaded) and loaded[full] == rows_per_region:
        full += 1
    if full >= 2:
        return full, rows_per_region
    rows = min(loaded[:2])
    if len(loaded) < 2 or rows == 0:
        raise RuntimeError(f"two regions did not get a row each: {loaded}")
    return 2, rows


# ---------------------------------------------------------------------------
# the assembly
# ---------------------------------------------------------------------------


class Smoke:
    """PD service + one durable device store + socket clients, and the
    phases that drive them."""

    def __init__(self, seed: int, regions: int, rows_per_region: int):
        self.seed = seed
        self.regions = regions
        self.rows_per_region = rows_per_region
        self.tmp = tempfile.mkdtemp(prefix="chip-smoke-")
        self.srv = None
        self.pd_server = None
        self.client = None
        self.region_ids: list[int] = []
        self.timings: dict = {}
        self.rows: list = []       # per region, the (key, value) rows written
        self._decoded: dict = {}   # region -> its rows decoded, for the reference

    # -- lifecycle ---------------------------------------------------------

    def start(self) -> None:
        from tikv_tpu.pd.client import MockPd
        from tikv_tpu.pd.service import PdService, RemotePd
        from tikv_tpu.server.server import Client, Server
        from tikv_tpu.server.standalone import StoreServer

        self.pd = MockPd()
        self.pd_server = Server(PdService(self.pd))
        self.pd_server.start()
        self.srv = StoreServer(1, RemotePd(*self.pd_server.addr),
                               data_dir=os.path.join(self.tmp, "s1"),
                               enable_device=True)
        self.srv.start()
        self.srv.bootstrap_or_join(1)
        self.client = Client(*self.srv.server.addr)
        engines = {"kv": type(self.srv.engine).__name__,
                   "raft_log": type(self.srv.raft_log).__name__}
        if engines != {"kv": "NativeEngine", "raft_log": "NativeRaftLog"}:
            raise RuntimeError(f"a deployment's engines do not serve: {engines}")
        # The router's random probes (5% explore, 2% cold, "cpu" among the
        # candidates) and the tuner's 30 s block_rows steps (each drops every
        # warm image) would make what is asserted below a matter of chance.
        # Both are held; measured routing stays on.
        router = self.srv.copr.cost_router
        router.cfg.epsilon = 0.0
        router.cfg.cold_probe_rate = 0.0
        self.srv.copr.geometry_tuner.enabled = False
        rc = self.srv.copr.region_cache
        emit("start", engines=engines, block_rows=rc.block_rows,
             region_cache_budget_bytes=rc.byte_budget,
             scheduler_running=self.srv.copr.scheduler.running,
             mesh=(dict(self.srv.copr.mesh.shape)
                   if self.srv.copr.mesh is not None else None),
             held=["cost_router.epsilon", "cost_router.cold_probe_rate",
                   "geometry_tuner"])

    def stop(self) -> None:
        if self.client is not None:
            self.client.close()
        if self.srv is not None:
            self.srv.stop()
        if self.pd_server is not None:
            self.pd_server.stop()
        shutil.rmtree(self.tmp, ignore_errors=True)

    def call(self, region_id: int, method: str, req: dict,
             timeout: float = 120.0) -> dict:
        """One RPC to the region's leader over the ONE connection a client
        keeps to a store (tasks multiplex on it), retried while a freshly
        split region elects; any other error is the caller's."""
        deadline = time.monotonic() + 30.0
        while True:
            r = self.client.call(method, dict(req, context={"region_id": region_id}),
                            timeout=timeout)
            err = r.get("error") or r.get("errors") if isinstance(r, dict) else None
            if not err:
                return r
            retriable = isinstance(err, dict) and (
                "not_leader" in err or "epoch_not_match" in err)
            if not retriable or time.monotonic() > deadline:
                raise RuntimeError(f"{method} on region {region_id}: {err!r}")
            time.sleep(0.1)

    def each_region(self, fn, concurrent: bool = True) -> list:
        """``[fn(k) for k in range(regions)]``, one thread per region unless
        told otherwise; the first failure is raised."""
        n = self.regions
        out: list = [None] * n
        errs: list = []

        gate = threading.Barrier(n if concurrent else 1)

        def one(k: int) -> None:
            try:
                gate.wait()  # a query's tasks leave together
                out[k] = fn(k)
            except Exception as e:  # noqa: BLE001 — re-raised below
                errs.append(e)

        if concurrent:
            threads = [threading.Thread(target=one, args=(k,)) for k in range(n)]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
        else:
            for k in range(n):
                one(k)
        if errs:
            raise errs[0]
        return out

    # -- load --------------------------------------------------------------

    def _put_rows(self, region_id: int, kvs) -> None:
        for s in range(0, len(kvs), LOAD_BATCH):
            chunk = kvs[s:s + LOAD_BATCH]
            muts = [{"op": "put", "key": k, "value": v} for k, v in chunk]
            ts = self.pd.get_tso()
            self.call(region_id, "kv_prewrite", {
                "mutations": muts, "primary_lock": muts[0]["key"],
                "start_version": ts})
            self.call(region_id, "kv_commit", {
                "keys": [m["key"] for m in muts], "start_version": ts,
                "commit_version": self.pd.get_tso()})

    def _region_of(self, raw_key: bytes) -> int:
        from tikv_tpu.storage.txn_types import Key

        enc = Key.from_raw(raw_key).encoded
        for rid, r in self.pd.regions.items():
            if enc >= (r.start_key or b"") and (not r.end_key or enc < r.end_key):
                return rid
        raise RuntimeError(f"no region holds {raw_key!r}")

    def load(self, budget_s: float = LOAD_BUDGET_S) -> None:
        import lineitem_fixture as fx
        from tikv_tpu.copr.table import record_key

        n, rpr = self.regions, self.rows_per_region
        t0 = time.perf_counter()
        kvs = fx.build_kvs(n * rpr, seed=self.seed)
        gen_s = time.perf_counter() - t0
        for k in range(1, n):
            split = record_key(TABLE_ID, k * rpr)
            self.call(self._region_of(split), "kv_split_region",
                      {"split_key": split})
            # PD learns the new boundaries from the next region heartbeat
            deadline = time.monotonic() + 30.0
            while len(self.pd.regions) < k + 1:
                if time.monotonic() > deadline:
                    raise RuntimeError("PD never saw the split")
                time.sleep(0.05)
        region_ids = [self._region_of(record_key(TABLE_ID, k * rpr))
                      for k in range(n)]
        if len(set(region_ids)) != n:
            raise RuntimeError(f"split left {region_ids} for {n} ranges")
        t0 = time.perf_counter()
        loaded = [0] * n
        for k, s, e in load_order(n, rpr, LOAD_BATCH):
            if time.perf_counter() - t0 > budget_s:
                break
            self._put_rows(region_ids[k], kvs[k * rpr + s:k * rpr + e])
            loaded[k] = e
            if sum(loaded) % 200_000 < LOAD_BATCH:
                print(f"chip_smoke: {sum(loaded)} rows in "
                      f"{time.perf_counter() - t0:.0f}s", file=sys.stderr)
        load_s = time.perf_counter() - t0
        # what the budget left is cut regions first, then rows per region
        self.regions, self.rows_per_region = settle(loaded, rpr)
        cut = None
        if (self.regions, self.rows_per_region) != (n, rpr):
            cut = {"asked": {"regions": n, "rows_per_region": rpr},
                   "budget_seconds": budget_s, "rows_loaded": sum(loaded),
                   "rows_cut_by": round(
                       1 - self.regions * self.rows_per_region / (n * rpr), 3)}
        n2, rows = self.regions, self.rows_per_region
        self.region_ids = region_ids[:n2]
        # one task per region covers exactly the rows the region was given
        self.ranges = [(record_key(TABLE_ID, k * rpr),
                        record_key(TABLE_ID, k * rpr + rows)) for k in range(n2)]
        self.rows = [kvs[k * rpr:k * rpr + rows] for k in range(n2)]
        nbytes = sum(len(key) + len(v) for r in self.rows for key, v in r)
        emit("load", rows=n2 * rows, regions=n2, rows_per_region=rows,
             bytes=nbytes, bytes_per_region=nbytes // n2,
             seconds=round(load_s, 1), generate_seconds=round(gen_s, 1),
             rows_per_s=round(sum(loaded) / load_s), cut=cut,
             region_ids=self.region_ids)
        self.read_back_sample()

    def read_back_sample(self) -> None:
        """An acknowledged write is read back: ``READ_BACK_SAMPLE`` keys
        spread over each region, through the socket at a fresh timestamp."""
        t0 = time.perf_counter()

        def one(k: int) -> int:
            rows = self.rows[k]
            want = rows[::max(1, len(rows) // READ_BACK_SAMPLE)]
            r = self.call(self.region_ids[k], "kv_batch_get", {
                "keys": [key for key, _v in want],
                "version": self.pd.get_tso()})
            if [tuple(p) for p in r["pairs"]] != want:
                raise AssertionError(f"region {self.region_ids[k]} does not "
                                     "read back what was written")
            return len(want)

        emit("read_back", keys=sum(self.each_region(one)), equal_to_written=True,
             seconds=round(time.perf_counter() - t0, 2))

    # -- serve -------------------------------------------------------------

    def _counters(self) -> dict:
        from tikv_tpu.copr.breaker import PATHS
        from tikv_tpu.copr.observatory import OBSERVATORY
        from tikv_tpu.util.metrics import REGISTRY

        rc = REGISTRY.counter("tikv_coprocessor_region_cache_total", "")
        sv = REGISTRY.counter("tikv_observatory_serve_total", "")
        fb = REGISTRY.counter("tikv_coprocessor_path_fallback_total", "")
        return {
            "cache": {o: rc.get(outcome=o) for o in _CACHE_OUTCOMES},
            "rung": {p: sv.get(path=p) for p in _SERVE_PATHS},
            "errors": sum(fb.get(path=p, cause=c)
                          for p in PATHS for c in _ERROR_CAUSES),
            "cost_routed_cpu": fb.get(path="unary", cause="cost_route"),
            "ledger_compiles": sum(
                a["count"] for a in OBSERVATORY.snapshot()["compiles"]
                ["by_sig_path"].values()),
            "xla_compiles": _XLA_COMPILES["count"],
            "device_fallback_total": REGISTRY.counter(
                "tikv_coprocessor_device_fallback_total", "").get(),
            "mesh_cache_hit": REGISTRY.counter(
                "tikv_coprocessor_mesh_cache_hit_total", "").get(),
            "mesh_declined": fb.get(path="mesh", cause="ineligible"),
        }

    @staticmethod
    def _delta(before: dict, after: dict) -> dict:
        out = {}
        for k, v in after.items():
            if isinstance(v, dict):
                d = {kk: int(vv - before[k][kk]) for kk, vv in v.items()
                     if vv != before[k][kk]}
                out[k] = d
            else:
                out[k] = int(v - before[k])
        return out

    def request(self, k: int, wire_dag: dict, ts: int) -> tuple[bytes, bool]:
        r = self.call(self.region_ids[k], "coprocessor", {
            "dag": wire_dag, "ranges": [list(self.ranges[k])], "start_ts": ts})
        parts = r.get("data_parts")
        data = (b"".join(bytes(p) for p in parts) if parts is not None
                else r["data"])
        return data, bool(r["from_device"])

    def serve_pass(self, wire_dag: dict, ts: int, concurrent: bool = True):
        """One coprocessor task per region.  Returns (answers, from_device
        flags, wall seconds, counter deltas)."""
        before = self._counters()
        t0 = time.perf_counter()
        out = self.each_region(lambda k: self.request(k, wire_dag, ts),
                               concurrent)
        wall = time.perf_counter() - t0
        delta = self._delta(before, self._counters())
        return [d for d, _ in out], [f for _, f in out], wall, delta

    def reference(self, dag, k: int) -> bytes:
        """The CPU pipeline's bytes for region ``k``: the endpoint's own
        oracle steps (negotiate the encoding, BatchExecutorsRunner, encode)
        over the rows that were WRITTEN, decoded once per region as
        ``lineitem_fixture.run_cpu`` does; none of the store's read path, none
        of the device path."""
        from tikv_tpu.copr.cache import ColumnBlockCache
        from tikv_tpu.copr.dag import BatchExecutorsRunner, negotiate_encode_type
        from tikv_tpu.copr.executors import CachedBlocksExecutor
        from tikv_tpu.copr.table import RowBatchDecoder, decode_record_handles

        dag, _cause = negotiate_encode_type(dag)
        schema = dag.executors[0].columns_info  # every plan scans whole rows
        blocks = self._decoded.get(k)
        if blocks is None:
            blocks = self._decoded[k] = ColumnBlockCache()
            decoder = RowBatchDecoder(schema)
            rows = self.rows[k]
            for s in range(0, len(rows), 1 << 16):
                chunk = rows[s:s + (1 << 16)]
                blocks.add(decoder.decode(
                    decode_record_handles([key_ for key_, _v in chunk]),
                    [v for _key, v in chunk]), len(chunk))
            blocks.filled = True
        resp = BatchExecutorsRunner(
            dag, None, leaf=CachedBlocksExecutor(blocks, schema)).handle_request()
        return b"".join(bytes(p) for p in resp.encode_parts())

    def _check_pass(self, name: str, pss: str, got, want, from_dev, delta) -> None:
        n = self.regions
        for k in range(n):
            if got[k] != want[k]:
                raise AssertionError(
                    f"{name}/{pss}: region {self.region_ids[k]} answered "
                    f"{len(got[k])} bytes that differ from the CPU pipeline's "
                    f"{len(want[k])}")
        off_device = from_dev.count(False)
        if off_device:
            raise AssertionError(
                f"{name}/{pss}: {off_device} of {n} answers not from the "
                f"device (cost-routed to the CPU: {delta['cost_routed_cpu']})")
        if delta["errors"] or delta["device_fallback_total"]:
            raise AssertionError(f"{name}/{pss}: a device path failed: {delta}")
        cache = delta["cache"]
        if pss == "cold":
            ok = set(cache) <= {"miss", "hit"}
        else:
            # a task the scheduler resolves and then hands to the
            # per-request path looks its image up twice
            ok = set(cache) == {"hit"} and cache["hit"] >= n
        if not ok:
            raise AssertionError(
                f"{name}/{pss}: region-cache outcomes {cache}, expected "
                f"{'a fill' if pss == 'cold' else 'hits only'}")

    def serve(self) -> None:
        from tikv_tpu.copr.dag_wire import dag_to_wire

        emit("serve", plan="join", skipped=JOIN_SKIPPED)
        for name, dag in plan_set().items():
            wire_dag = dag_to_wire(dag)
            ts0 = self.pd.get_tso()
            t0 = time.perf_counter()
            want = self.each_region(lambda k: self.reference(dag, k))
            ref_s = time.perf_counter() - t0
            line: dict = {"plan": name, "answer_bytes": [len(w) for w in want],
                          "reference_seconds": round(ref_s, 2)}
            passes = list(PASSES)
            for pss in passes:
                # no write lands between the passes, so a later timestamp
                # reads the rows the reference read
                got, from_dev, wall, delta = self.serve_pass(
                    wire_dag, ts0 if pss == "cold" else self.pd.get_tso())
                self._check_pass(name, pss, got, want, from_dev, delta)
                line[pss] = {
                    "wall_ms": round(wall * 1e3, 1),
                    "from_device": from_dev.count(True),
                    "cache": delta["cache"], "rung": delta["rung"],
                    "ledger_compiles": delta["ledger_compiles"],
                    "xla_compiles": delta["xla_compiles"],
                }
                # Which rung serves is the scheduler's call: tasks that reach
                # it within its 4 ms linger ride one cross-region program,
                # compiled per batch composition, the others the per-request
                # rungs.  A warm pass that met a composition for the first
                # time compiles it once; one that repeats must not.
                if (pss == passes[-1] and delta["ledger_compiles"]
                        and len(passes) < len(PASSES) + MAX_EXTRA_WARM):
                    passes.append(f"warm{len(passes)}")
            if line[passes[-1]]["ledger_compiles"]:
                raise AssertionError(
                    f"{name}: still compiling in {passes[-1]}: "
                    f"{[line[p]['ledger_compiles'] for p in passes]}")
            line["warm_passes"] = len(passes) - 1
            emit("serve", byte_identical=True, **line)
            self.timings[name] = {p: line[p]["wall_ms"] for p in passes}

    # -- write then read ---------------------------------------------------

    def write_then_read(self) -> None:
        """A few hundred rows change in a warm region; Q6 at a newer
        timestamp must see them, through a delta and not a refill."""
        import lineitem_fixture as fx
        from tikv_tpu.copr.dag_wire import dag_to_wire

        k = self.regions - 1
        rpr = self.rows_per_region
        # new values, drawn from another seed, for keys spread over the region
        step = max(1, rpr // WRITE_ROWS)
        rows = [(self.rows[k][i * step][0], v) for i, (_key, v) in
                enumerate(fx.build_kvs(min(WRITE_ROWS, rpr), self.seed + 1))]
        dag = fx.q6_dag()
        wire_dag = dag_to_wire(dag)
        before_bytes = self.reference(dag, k)
        self._put_rows(self.region_ids[k], rows)
        written = dict(rows)
        self.rows[k] = [(key, written.get(key, v)) for key, v in self.rows[k]]
        self._decoded.pop(k, None)
        ts = self.pd.get_tso()
        want = self.reference(dag, k)
        before = self._counters()
        got, from_dev = self.request(k, wire_dag, ts)
        delta = self._delta(before, self._counters())
        if got != want:
            raise AssertionError("write-then-read: Q6 after the write differs "
                                 "from the CPU pipeline at that timestamp")
        if want == before_bytes:
            raise AssertionError("write-then-read: the write did not change "
                                 "Q6's answer, so reading it back shows nothing")
        if not from_dev:
            raise AssertionError("write-then-read: not served from the device")
        if set(delta["cache"]) - {"delta", "wt_delta"} or not delta["cache"]:
            raise AssertionError(
                f"write-then-read: region-cache outcome {delta['cache']}, "
                "expected a delta")
        emit("write_then_read", region=self.region_ids[k], rows_written=len(rows),
             byte_identical=True, answer_changed=True, from_device=True,
             cache=delta["cache"], rung=delta["rung"])

    # -- verdict -----------------------------------------------------------

    def verdict(self, baseline: dict) -> None:
        import jax

        from tikv_tpu.copr.breaker import PATHS
        from tikv_tpu.copr.observatory import OBSERVATORY
        from tikv_tpu.util.compile_cache import place_compile_cache

        ep = self.srv.copr
        now = self._counters()
        breakers = {p: ep.breaker.state_of(p) for p in PATHS}
        problems = []
        if ep.device_fallbacks or ep.last_device_error is not None:
            problems.append(f"device_fallbacks={ep.device_fallbacks} "
                            f"last_device_error={ep.last_device_error}")
        if any(s != "closed" for s in breakers.values()):
            problems.append(f"breakers {breakers}")
        for key in ("errors", "device_fallback_total"):
            if now[key] != baseline[key]:
                problems.append(f"{key} rose by {now[key] - baseline[key]}")
        if problems:
            raise AssertionError("verdict: " + "; ".join(problems))
        cache_dir = place_compile_cache()
        stats = jax.devices()[0].memory_stats() or {}
        ledger = OBSERVATORY.snapshot()["compiles"]["by_sig_path"].values()
        emit("verdict", device_fallbacks=0, last_device_error=None,
             breakers=breakers,
             cost_routed_cpu=int(now["cost_routed_cpu"] - baseline["cost_routed_cpu"]),
             peak_bytes_in_use=stats.get("peak_bytes_in_use"),
             region_cache=ep.region_cache.stats.to_dict(),
             compile_cache_dir=cache_dir,
             compile_cache_entries=(len(os.listdir(cache_dir))
                                    if os.path.isdir(cache_dir) else 0),
             ledger_compiles=sum(a["count"] for a in ledger),
             ledger_first_call_seconds=round(sum(a["wall_s"] for a in ledger), 2),
             xla_compiles=_XLA_COMPILES["count"],
             xla_compile_seconds=round(_XLA_COMPILES["seconds"], 2),
             wall_ms=self.timings,
             note="wall times are smoke readings, not benchmark results")

    # -- four chips --------------------------------------------------------

    def four_chip(self) -> None:
        """ONLY the mesh-sharded warm path and what it is compared with: the
        aggregation plans through the socket over images placed on all four
        devices, against a one-device endpoint on the same store and the
        CPU pipeline."""
        import jax

        from tikv_tpu.copr.dag_wire import dag_to_wire
        from tikv_tpu.copr.endpoint import CoprRequest, Endpoint, REQ_TYPE_DAG

        ep = self.srv.copr
        n = self.regions
        if ep.mesh is None or ep.mesh.size != 4 or not ep.region_cache.sharded:
            raise RuntimeError("the store built no four-device sharded cache")
        one_dev = Endpoint(self.srv.raftkv, enable_device=True)
        plans = plan_set()
        for name in AGG_PLANS:
            dag = plans[name]
            wire_dag = dag_to_wire(dag)
            ts0 = self.pd.get_tso()
            want = self.each_region(lambda k: self.reference(dag, k))
            single = [one_dev.handle_request(CoprRequest(
                REQ_TYPE_DAG, dag, [self.ranges[k]], ts0,
                {"region_id": self.region_ids[k]})).data for k in range(n)]
            if single != want:
                raise AssertionError(f"{name}: the one-device answer differs "
                                     "from the CPU pipeline")
            line: dict = {"plan": name}
            # cold fills place the images; then one task at a time, so each
            # rides the endpoint's mesh rung, then all at once, so they
            # coalesce into the scheduler's sharded batch
            for pss, concurrent in (("cold", True), ("warm_each", False),
                                    ("warm_batch", True)):
                got, from_dev, wall, delta = self.serve_pass(
                    wire_dag, ts0 if pss == "cold" else self.pd.get_tso(),
                    concurrent=concurrent)
                self._check_pass(name, "cold" if pss == "cold" else "warm",
                                 got, want, from_dev, delta)
                line[pss] = {"wall_ms": round(wall * 1e3, 1),
                             "cache": delta["cache"], "rung": delta["rung"],
                             "mesh_cache_hit": delta["mesh_cache_hit"],
                             "mesh_declined": delta["mesh_declined"]}
                if pss == "cold":
                    continue
                if name in MESH_DECLINES:
                    # the documented decline: one device serves
                    ok = (not delta["mesh_cache_hit"]
                          and "mesh" not in delta["rung"])
                else:
                    ok = delta["rung"] == {"mesh": n} and (
                        pss != "warm_each" or delta["mesh_cache_hit"] == n)
                if not ok:
                    raise AssertionError(f"{name}/{pss}: {line[pss]}")
            emit("four_chip", byte_identical_to="one device and CPU pipeline",
                 **line)
        placement = ep.region_cache.placement()
        in_use = {str(d.id): (d.memory_stats() or {}).get("bytes_in_use")
                  for d in jax.devices()}
        if len(placement) != 4 or not all(placement.values()):
            raise AssertionError(f"image bytes per device: {placement}")
        if jax.devices()[0].platform == "tpu" and not all(in_use.values()):
            raise AssertionError(f"a device holds nothing: {in_use}")
        if ep.device_fallbacks or ep.last_device_error is not None:
            raise AssertionError(f"device_fallbacks={ep.device_fallbacks} "
                                 f"last_device_error={ep.last_device_error}")
        emit("four_chip_verdict", image_bytes_per_device=placement,
             bytes_in_use_per_device=in_use, device_fallbacks=0,
             breakers={p: ep.breaker.state_of(p) for p in ("mesh", "unary")})


# every backend compile of the process, persistent-cache hits left out: the
# ledger sees only the programs behind timed_jit
_XLA_COMPILES = {"count": 0, "seconds": 0.0}


def _count_xla_compiles() -> None:
    import jax.monitoring

    def on_duration(event: str, seconds: float, **_kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            _XLA_COMPILES["count"] += 1
            _XLA_COMPILES["seconds"] += seconds

    jax.monitoring.register_event_duration_secs_listener(on_duration)


def device_phase(chips: int) -> dict:
    """What JAX runs on; anything but ``chips`` TPU devices ends the script."""
    import jax

    devices = jax.devices()
    found = {"platform": devices[0].platform, "kind": devices[0].device_kind,
             "count": len(devices)}
    if found["platform"] != "tpu" or found["count"] != chips:
        print(f"chip_smoke: needs {chips} TPU device(s), JAX found {found}",
              file=sys.stderr)
        sys.exit(1)
    emit("device", **found)
    return found


def run_phases(smoke: Smoke, chips: int = 1) -> None:
    """Every phase after the device check, on whatever backend JAX has: the
    script's body on the chip, and the tier-1 rehearsal's on the CPU."""
    try:
        smoke.start()
        baseline = smoke._counters()
        smoke.load()
        if chips == 4:
            smoke.four_chip()
        else:
            smoke.serve()
            smoke.write_then_read()
            smoke.verdict(baseline)
    except BaseException:
        # a failed phase can leave requests in flight, and closing the
        # engines under them ends in a use-after-free: only the files go
        shutil.rmtree(smoke.tmp, ignore_errors=True)
        raise
    smoke.stop()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4 runs ONLY the mesh-sharded warm path")
    ap.add_argument("--regions", type=int, default=None,
                    help="rehearsal only")
    ap.add_argument("--rows-per-region", type=int, default=None,
                    help="rehearsal only")
    args = ap.parse_args(argv)

    from tikv_tpu.util.compile_cache import place_compile_cache

    place_compile_cache()
    _count_xla_compiles()
    device = device_phase(args.chips)
    if args.regions is not None or args.rows_per_region is not None:
        emit("rehearsal_size", regions=args.regions,
             rows_per_region=args.rows_per_region)
    smoke = Smoke(args.seed, args.regions or REGIONS,
                  args.rows_per_region or (ROWS_PER_REGION if args.chips == 1
                                           else ROWS_PER_REGION_4CHIP))
    try:
        run_phases(smoke, args.chips)
    except BaseException:
        # the store was left running under whatever is still in flight:
        # say why and leave at once, without waiting for its threads
        traceback.print_exc()
        sys.stdout.flush()
        sys.stderr.flush()
        os._exit(1)
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
