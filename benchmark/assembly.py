"""The deployment under test, assembled as ``chip_smoke.py``'s ``Smoke`` does
(copied: ``start``, ``call``, ``load``, ``_put_rows``, ``_region_of``): an
in-process PD service, ONE durable ``StoreServer(1, ..., enable_device=True)``
with every other argument at its default, regions made with
``kv_split_region``, rows loaded through the socket with ``kv_prewrite`` /
``kv_commit``.  Only what a configuration file lists under ``held`` is set on
the store.
"""

from __future__ import annotations

import os
import shutil
import tempfile
import threading
import time

from . import table as tbl


class Deployment:
    def __init__(self, config: dict, seed: int):
        self.config = config
        self.seed = seed
        self.table_id = int(config["table_id"])
        self.regions = int(config["regions"])
        self.rows_per_region = int(config["rows_per_region"])
        self.tmp = tempfile.mkdtemp(prefix="tikv-bench-")
        self.srv = None
        self.pd = None
        self.pd_server = None
        self.client = None
        self.region_ids: list[int] = []
        self.ranges: list[tuple[bytes, bytes]] = []
        self.base: list[tbl.Table] = []   # per region, the rows loaded
        self.timings: dict = {}

    # -- lifecycle ---------------------------------------------------------

    def start(self) -> None:
        from tikv_tpu.pd.client import MockPd
        from tikv_tpu.pd.service import PdService, RemotePd
        from tikv_tpu.server.server import Client, Server
        from tikv_tpu.server.standalone import StoreServer

        t0 = time.perf_counter()
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
        for path, value in self.config["held"].items():
            obj = self.srv.copr
            *parents, leaf = path.split(".")
            for p in parents:
                obj = getattr(obj, p)
            if not hasattr(obj, leaf):
                raise RuntimeError(f"held: the endpoint has no {path}")
            setattr(obj, leaf, value)
        self.timings["start_s"] = time.perf_counter() - t0

    def stop(self) -> None:
        if self.client is not None:
            self.client.close()
        if self.srv is not None:
            self.srv.stop()
        if self.pd_server is not None:
            self.pd_server.stop()

    def remove_files(self) -> None:
        shutil.rmtree(self.tmp, ignore_errors=True)

    @property
    def store_addr(self) -> tuple[str, int]:
        return tuple(self.srv.server.addr)

    @property
    def pd_addr(self) -> tuple[str, int]:
        return tuple(self.pd_server.addr)

    def call(self, region_id: int, method: str, req: dict,
             timeout: float = 120.0) -> dict:
        """One RPC to the region's leader, retried while a freshly split
        region elects; any other error is the caller's."""
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

    # -- load --------------------------------------------------------------

    def _region_of(self, raw_key: bytes) -> int:
        from tikv_tpu.storage.txn_types import Key

        enc = Key.from_raw(raw_key).encoded
        for rid, r in self.pd.regions.items():
            if enc >= (r.start_key or b"") and (not r.end_key or enc < r.end_key):
                return rid
        raise RuntimeError(f"no region holds {raw_key!r}")

    def _put_rows(self, region_id: int, kvs) -> None:
        batch = int(self.config["load_batch_rows"])
        for s in range(0, len(kvs), batch):
            muts = [{"op": "put", "key": k, "value": v} for k, v in kvs[s:s + batch]]
            ts = self.pd.get_tso()
            self.call(region_id, "kv_prewrite", {
                "mutations": muts, "primary_lock": muts[0]["key"],
                "start_version": ts})
            self.call(region_id, "kv_commit", {
                "keys": [m["key"] for m in muts], "start_version": ts,
                "commit_version": self.pd.get_tso()})

    def split(self, to_table_end: bool = False) -> None:
        """``to_table_end``: the last region's task covers the table's record
        space to its end, where rows a writer appends land (a mix with a
        refresh stream); otherwise it ends past the last loaded row."""
        from tikv_tpu.copr.table import record_key

        n, rpr = self.regions, self.rows_per_region
        first = [k * rpr + 1 for k in range(n + 1)]   # row i has handle i + 1
        for k in range(1, n):
            split = record_key(self.table_id, first[k])
            self.call(self._region_of(split), "kv_split_region",
                      {"split_key": split})
            # PD learns the new boundaries from the next region heartbeat
            deadline = time.monotonic() + 30.0
            while len(self.pd.regions) < k + 1:
                if time.monotonic() > deadline:
                    raise RuntimeError("PD never saw the split")
                time.sleep(0.05)
        self.region_ids = [self._region_of(record_key(self.table_id, first[k]))
                           for k in range(n)]
        if len(set(self.region_ids)) != n:
            raise RuntimeError(f"split left {self.region_ids} for {n} ranges")
        self.ranges = self.task_ranges(to_table_end)

    def task_ranges(self, to_table_end: bool) -> list[tuple[bytes, bytes]]:
        """One task per region covers the region's whole range of handles."""
        from tikv_tpu.copr.table import record_key, record_range

        n, rpr = self.regions, self.rows_per_region
        first = [k * rpr + 1 for k in range(n + 1)]
        ranges = [(record_key(self.table_id, first[k]),
                   record_key(self.table_id, first[k + 1])) for k in range(n)]
        if to_table_end:
            ranges[-1] = (ranges[-1][0], record_range(self.table_id)[1])
        return ranges

    def load(self) -> None:
        """Regions grow together, batch by batch (``chip_smoke.load_order``)."""
        n, rpr = self.regions, self.rows_per_region
        t0 = time.perf_counter()
        whole = tbl.build_table(n * rpr, self.seed)
        self.base = [whole.take(slice(k * rpr, (k + 1) * rpr)) for k in range(n)]
        kvs = [tbl.encode_kvs(self.table_id, b) for b in self.base]
        self.timings["generate_s"] = time.perf_counter() - t0
        t0 = time.perf_counter()
        # one loading session for each region, all at once
        loaders = [threading.Thread(target=self._put_rows,
                                    args=(self.region_ids[k], kvs[k])) for k in range(n)]
        for t in loaders:
            t.start()
        for t in loaders:
            t.join()
        self.timings["load_s"] = time.perf_counter() - t0
        self.read_back_sample(kvs)

    def read_back_sample(self, kvs, sample: int = 2000) -> None:
        """An acknowledged write is read back: keys spread over each region,
        through the socket at a fresh timestamp."""
        for k in range(self.regions):
            want = kvs[k][::max(1, len(kvs[k]) // sample)]
            r = self.call(self.region_ids[k], "kv_batch_get", {
                "keys": [key for key, _v in want], "version": self.pd.get_tso()})
            if [tuple(p) for p in r["pairs"]] != want:
                raise RuntimeError(f"region {self.region_ids[k]} does not read "
                                   "back what was written")

    def read_back(self, want: dict[int, dict[bytes, bytes | None]],
                  batch: int = 2000) -> int:
        """Every key of ``want`` (per region: the value a read must find, or
        ``None`` for a key that must be absent) read through the socket at one
        fresh timestamp; returns how many read otherwise."""
        ts = self.pd.get_tso()
        missing = 0
        for k, keys in sorted(want.items()):
            items = sorted(keys.items())
            for s in range(0, len(items), batch):
                part = items[s:s + batch]
                r = self.call(self.region_ids[k], "kv_batch_get", {
                    "keys": [key for key, _v in part], "version": ts})
                got = {bytes(p[0]): bytes(p[1]) for p in r["pairs"] if p and p[1]}
                missing += sum(got.get(key) != v for key, v in part)
        return missing

    def cold_fill(self, dags) -> None:
        """One pass of each of ``dags`` over every region builds the image of
        the columns it scans."""
        from tikv_tpu.copr.dag_wire import dag_to_wire

        t0 = time.perf_counter()
        for dag in dags:
            wire_dag = dag_to_wire(dag)
            ts = self.pd.get_tso()
            for k in range(self.regions):
                r = self.call(self.region_ids[k], "coprocessor", {
                    "dag": wire_dag, "ranges": [list(self.ranges[k])], "start_ts": ts})
                if not r.get("from_device"):
                    raise RuntimeError(f"cold fill of region {self.region_ids[k]} "
                                       "was not answered from the device")
        self.timings["fill_s"] = time.perf_counter() - t0
        self.timings["fill_rows"] = len(dags) * self.regions * self.rows_per_region

    def job(self) -> dict:
        """What the client process needs to find the deployment."""
        return {
            "store": list(self.store_addr), "pd": list(self.pd_addr),
            "table_id": self.table_id,
            "region_ids": self.region_ids,
            "ranges": [[a.hex(), b.hex()] for a, b in self.ranges],
            "rows_per_region": self.rows_per_region,
        }
