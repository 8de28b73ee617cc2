#!/usr/bin/env python3
"""The client process: one general traffic generator, driven by a traffic
file's parameters, that talks to the store and to PD only through their
sockets and writes down what it saw.

Started by ``run.py`` with ``JAX_PLATFORMS=cpu`` so that it can never reach for
the chip the parent holds; it computes nothing with JAX.  Being a process of
its own keeps its encoding, decoding and timers off the store's interpreter
lock.

    python3 benchmark/client.py <job.json>

The job names the deployment's sockets and regions, the traffic parameters,
the seed, the window's length and where to write the log.  A line ``START``
on standard output marks the window's first instant.

Traffic parameters (``benchmark/traffic/<mix>.json``):

- ``query_streams``: closed streams; each waits for its reply.  A query is one
  ``coprocessor`` task per region, all sent together on the stream's one
  connection over each region's whole range at one fresh ``start_ts``; it
  completes when the last task answers.
- ``plans``: run by each stream in a seed-permuted order, again and again.
- ``params``: per plan, ``{name: [low, high]}``: substitution parameters drawn
  from the seed and laid over the plan's ``DEFAULTS``; ``substitute`` says how
  often: ``"stream"`` once for each stream, which keeps them for the run (as
  qgen makes one set for each query stream), ``"query"`` anew for each query.
- ``prewarm``: ``{"alone": n, "together": m}``, what a run's first client
  sends before its window starts (``Traffic.prewarm``).
- ``refresh`` (optional): ``{"orders": n, "transactions": t}``, TPC-H's
  refresh stream beside the query streams: one writer, its own connections,
  RF1/RF2 pairs (``refresh.py``) one after another until the window closes,
  each transaction ``kv_prewrite`` then ``kv_commit`` with timestamps from PD,
  its mutations made just before it is sent.  The job's ``first_pair`` is the
  first pair's number: a run's clients go on where the last one stopped.

A task answered ``locked`` is sent again at the same ``start_ts`` after TiDB's
lock back-off, until the grace runs out; its ``lock_retries`` counts them.
"""

from __future__ import annotations

import base64
import hashlib
import importlib
import json
import os
import socket
import sys
import threading
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np  # noqa: E402


CALL_TIMEOUT_S = 180.0  # a call that no deadline governs
ANSWER_GRACE_S = 75.0   # an answer is waited for this long past the window's close
# TiDB 5.1's back-off for a read that met a lock (client-go ``BoTxnLockFast``:
# ``tidb_backoff_lock_fast`` = 10 ms, doubled each attempt, capped at 3 s;
# its jitter is left out)
LOCK_BACKOFF_S = (0.010, 3.0)


class Conn:
    """One connection to a server of the program's framed protocol, with as
    many requests in flight as the caller sends."""

    def __init__(self, addr):
        from tikv_tpu.server import wire
        from tikv_tpu.server.server import read_frame, write_frame

        self._wire, self._read, self._write = wire, read_frame, write_frame
        self.sock = socket.create_connection(tuple(addr))
        self.sock.settimeout(CALL_TIMEOUT_S)
        self.sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
        self._next = 0

    def send(self, method: str, req: dict) -> int:
        self._next += 1
        self._write(self.sock, self._wire.dumps([self._next, method, req]))
        return self._next

    def recv(self, until: float | None = None):
        """The next answer; ``TimeoutError`` once ``until`` (on
        ``time.perf_counter``'s clock) has passed."""
        if until is not None:
            self.sock.settimeout(max(0.05, until - time.perf_counter()))
        frame = self._read(self.sock)
        if frame is None:
            raise ConnectionError("the server closed the connection")
        return self._wire.loads(frame)

    def call(self, method: str, req: dict):
        rid = self.send(method, req)
        got, resp = self.recv()
        if got != rid:
            raise ConnectionError(f"{method}: answer to {got}, asked {rid}")
        return resp

    def close(self) -> None:
        self.sock.close()


def tso(pd: Conn) -> int:
    return pd.call("pd_get_tso", {})["ts"]


def error_of(resp) -> dict | None:
    if not isinstance(resp, dict):
        return {"other": f"not a response: {type(resp).__name__}"}
    return resp.get("error") or resp.get("errors") or None


def lock_backoff(attempt: int) -> float:
    base, cap = LOCK_BACKOFF_S
    return min(cap, base * 2 ** attempt)


class Traffic:
    def __init__(self, job: dict):
        self.job = job
        self.mix = job["traffic"]
        self.seed = int(job["seed"])
        self.seconds = float(job["seconds"])
        self.region_ids = job["region_ids"]
        self.ranges = [[bytes.fromhex(a), bytes.fromhex(b)] for a, b in job["ranges"]]
        self.table_id = job["table_id"]
        self.plans = {p: importlib.import_module(f"benchmark.plans.{p}")
                      for p in self.mix["plans"]}
        self._wire_dags: dict = {}
        self.mu = threading.Lock()
        self.stop = False
        self.queries: list[dict] = []
        self.answers: dict[str, str] = {}
        self.errors: list[str] = []
        self.txns: list[dict] = []
        self.pairs: list[dict] = []
        self.t0 = 0.0
        self.give_up_at = float("inf")

    def wire_dag(self, plan: str, params: dict):
        from tikv_tpu.copr.dag_wire import dag_to_wire

        key = (plan, tuple(sorted(params.items())))
        w = self._wire_dags.get(key)
        if w is None:
            p = dict(self.plans[plan].DEFAULTS, **params)
            w = dag_to_wire(self.plans[plan].dag(self.table_id, p))
            if len(self._wire_dags) < 4096:
                self._wire_dags[key] = w
        return w

    def draw(self, rng, plan: str) -> dict:
        ranges = (self.mix.get("params") or {}).get(plan) or {}
        return {k: int(rng.integers(lo, hi + 1)) for k, (lo, hi) in sorted(ranges.items())}

    def stream_start(self, s: int):
        """Stream ``s``'s generator, its order of plans and the substitution
        parameters it keeps: the same at every start of the same seed."""
        rng = np.random.default_rng([self.seed, 1, s])
        order = [str(p) for p in rng.permutation(list(self.plans))]
        kept = {p: self.draw(rng, p) for p in sorted(self.plans)}
        return rng, order, kept

    def prewarm(self) -> None:
        """Before a run's first warm-up, in a fixed order: each stream's
        queries with each region's task sent alone (the store serves it by
        itself) and with all regions' tasks sent together (the read scheduler
        batches them), so that every program either way asks for is built
        before any timing depends on it."""
        steps = self.mix.get("prewarm") or {}
        if self.mix.get("substitute") == "query" or not steps:
            return
        regions = range(len(self.region_ids))
        store, pd = Conn(self.job["store"]), Conn(self.job["pd"])
        try:
            for s in range(int(self.mix["query_streams"])):
                _rng, order, kept = self.stream_start(s)
                for plan in order:
                    dag = self.wire_dag(plan, kept[plan])
                    batches = ([[k] for k in regions] * int(steps.get("alone", 0))
                               + [list(regions)] * int(steps.get("together", 0)))
                    for batch in batches:
                        ts = tso(pd)
                        for k in batch:
                            store.send("coprocessor", {
                                "dag": dag, "ranges": [self.ranges[k]], "start_ts": ts,
                                "context": {"region_id": self.region_ids[k]}})
                        for _k in batch:
                            _rid, resp = store.recv()
                            if error_of(resp) is not None:
                                raise RuntimeError(f"prewarm of {plan}: {error_of(resp)!r}")
        finally:
            store.close()
            pd.close()

    def query_stream(self, s: int) -> None:
        rng, order, kept = self.stream_start(s)
        per_query = self.mix.get("substitute") == "query"
        store, pd = Conn(self.job["store"]), Conn(self.job["pd"])
        try:
            i = 0
            while True:
                plan = order[i % len(order)]
                i += 1
                params = self.draw(rng, plan) if per_query else kept[plan]
                dag = self.wire_dag(plan, params)
                t_issue = time.perf_counter()
                if t_issue - self.t0 >= self.seconds or self.stop:
                    return
                ts = tso(pd)
                tasks = [{"region": k} for k in range(len(self.region_ids))]
                waiting: dict[int, dict] = {}

                def send(task):
                    k = task["region"]
                    rid = store.send("coprocessor", {
                        "dag": dag, "ranges": [self.ranges[k]], "start_ts": ts,
                        "context": {"region_id": self.region_ids[k]}})
                    waiting[rid] = task

                for task in tasks:
                    send(task)
                while waiting:
                    try:
                        rid, resp = store.recv(until=self.give_up_at)
                    except TimeoutError:
                        # never answered: the tasks count as unanswered and
                        # the stream, whose connection still owes them, ends
                        for task in waiting.values():
                            task["error"] = "no answer by the end of the grace"
                        self.record(s, plan, params, ts, t_issue, tasks)
                        return
                    task = waiting.pop(rid)
                    err = error_of(resp)
                    n = task.get("lock_retries", 0)
                    pause = lock_backoff(n)
                    if (isinstance(err, dict) and "locked" in err
                            and time.perf_counter() + pause < self.give_up_at):
                        # as TiDB's client does: back off, then the same task
                        # again at the same start_ts (the query waits for it
                        # anyway; the other answers wait in the socket)
                        task["lock_retries"] = n + 1
                        time.sleep(pause)
                        send(task)
                        continue
                    if err is not None:
                        task["error"] = repr(err)[:300]
                        continue
                    parts = resp.get("data_parts")
                    data = (b"".join(bytes(p) for p in parts) if parts is not None
                            else bytes(resp["data"]))
                    digest = hashlib.blake2b(data, digest_size=16).hexdigest()
                    task.update(
                        digest=digest, from_device=bool(resp.get("from_device")),
                        done=time.perf_counter() - self.t0)
                    if digest not in self.answers:
                        with self.mu:
                            self.answers[digest] = json.dumps({
                                "encode_type": resp.get("encode_type", 0),
                                "data": base64.b64encode(data).decode()})
                self.record(s, plan, params, ts, t_issue, tasks)
        except Exception as e:  # noqa: BLE001 - reported in the log, run fails
            with self.mu:
                self.errors.append(f"query stream {s}: {e!r}")
                self.stop = True
        finally:
            store.close()
            pd.close()

    def refresh_stream(self, ref) -> None:
        """RF1/RF2 pairs one after another, as TPC-H's refresh stream runs
        them, from the job's ``first_pair`` until the window closes.  A failed
        write ends the run."""
        store, pd = Conn(self.job["store"]), Conn(self.job["pd"])
        try:
            i = int(self.job["first_pair"])
            while time.perf_counter() - self.t0 < self.seconds and not self.stop:
                started = time.perf_counter() - self.t0
                for txn in ref.pair(i):
                    self.commit(store, pd, txn, ref.mutations(txn))
                with self.mu:
                    self.pairs.append({"pair": i, "started": started,
                                       "done": time.perf_counter() - self.t0})
                i += 1
        except Exception as e:  # noqa: BLE001 - reported in the log, run fails
            with self.mu:
                self.errors.append(f"refresh stream: {e!r}")
                self.stop = True
        finally:
            store.close()
            pd.close()

    def commit(self, store: Conn, pd: Conn, txn: dict, muts: list) -> None:
        """Percolator's two phases as a TiDB session sends them for one
        region's transaction: prewrite every key (the first is the primary),
        a commit timestamp from PD, commit every key."""
        ctx = {"region_id": self.region_ids[int(txn["region"])]}
        start_ts = tso(pd)
        sent = time.perf_counter() - self.t0
        r = store.call("kv_prewrite", {
            "mutations": muts, "primary_lock": muts[0]["key"],
            "start_version": start_ts, "context": ctx})
        if error_of(r) is not None:
            raise RuntimeError(f"prewrite of {txn['function']} pair {txn['pair']} "
                               f"txn {txn['txn']}: {error_of(r)!r}"[:400])
        commit_ts = tso(pd)
        r = store.call("kv_commit", {
            "keys": [m["key"] for m in muts], "start_version": start_ts,
            "commit_version": commit_ts, "context": ctx})
        if error_of(r) is not None:
            raise RuntimeError(f"commit of {txn['function']} pair {txn['pair']} "
                               f"txn {txn['txn']}: {error_of(r)!r}"[:400])
        acked = time.perf_counter() - self.t0
        with self.mu:
            self.txns.append({
                "function": txn["function"], "pair": int(txn["pair"]),
                "txn": int(txn["txn"]), "region": int(txn["region"]),
                "start_ts": start_ts, "commit_ts": commit_ts,
                "sent": sent, "acked": acked,
                "handles": [int(h) for h in txn["handles"]]})

    def record(self, s, plan, params, ts, t_issue, tasks) -> None:
        with self.mu:
            self.queries.append({
                "stream": s, "plan": plan, "params": params, "start_ts": ts,
                "issued": t_issue - self.t0,
                "done": time.perf_counter() - self.t0, "tasks": tasks})

    def run(self) -> dict:
        threads = [threading.Thread(target=self.query_stream, args=(s,))
                   for s in range(int(self.mix["query_streams"]))]
        refresh = "refresh" in self.mix
        if refresh:
            from benchmark import table as tbl
            from benchmark.refresh import Refresh

            rpr = int(self.job["rows_per_region"])
            ref = Refresh(self.mix["refresh"], self.seed, self.table_id,
                          len(self.region_ids) * rpr, rpr, len(self.region_ids))
            tbl.text_pool()  # RF1's comments are cut from it
            threads.append(threading.Thread(target=self.refresh_stream, args=(ref,)))
        if self.job.get("prewarm"):
            self.prewarm()
        print("START", flush=True)
        self.t0 = time.perf_counter()
        self.give_up_at = self.t0 + self.seconds + ANSWER_GRACE_S
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        log = {
            "seed": self.seed, "seconds": self.seconds,
            "closed_after": time.perf_counter() - self.t0,
            "queries": self.queries,
            "answers": {d: json.loads(a) for d, a in self.answers.items()},
            "errors": self.errors,
        }
        if refresh:
            log.update(txns=self.txns, pairs=self.pairs)
        return log


def main(argv) -> int:
    with open(argv[1]) as f:
        job = json.load(f)
    log = Traffic(job).run()
    tmp = job["log"] + ".part"
    with open(tmp, "w") as f:
        json.dump(log, f)
    os.replace(tmp, job["log"])
    return 1 if log["errors"] else 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
