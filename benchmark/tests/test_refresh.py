"""The refresh stream (``refresh.py``, ``client.py``), the comparison under
writes (``check.History``) and the read-back, at a size a CPU holds.

The whole-run tests skip the harness's look for a chip and drive the rest of a
run (``run.run_cell``) over an in-memory copy of ``BENCHMARK.json`` that holds
a cell for ``traffic/refresh-streams.json``, which the file does not hold yet.
A sound run is correct; the control and each planted fault are not: a region
cache that never folds a write in, a read served at a later timestamp than
its ``start_ts``, a commit acknowledged for a key that is then dropped."""

import numpy as np
import pytest

from benchmark import check, run
from benchmark import table as tbl
from benchmark.refresh import Refresh

ROWS = 4000
SEED = 2147484011
BENCH = run.load_json(run.ROOT, "BENCHMARK.json")
CELL = "refresh-streams.test"
# 25 orders a function (not SF x 1,500) so that RF2 stays inside a region of
# 4,000 rows for the whole run
SMALL = {"orders": 25, "transactions": 8}
TRAFFICS = ("tpch-power", "tpch-throughput", "param-streams")


def bench_with_cell():
    bench = dict(BENCH, workloads=[*BENCH["workloads"], {
        "name": CELL, "config": "tpch-lineitem-2x200k-s2",
        "traffic": "refresh-streams", "chips": 1, "why": "test"}])
    return bench


def cell(seed=SEED, control=0, seconds=5, workload=CELL, logs=None):
    import jax

    args = run.parse(["--workload", workload, "--seed", str(seed), "--seconds",
                      str(seconds), "--trace", "0", "--control", str(control)])
    d = jax.devices()
    device = {"platform": d[0].platform, "kind": d[0].device_kind, "count": len(d)}
    run.Run.peaks = lambda self: {"hbm_bytes_per_s": float("inf")}
    if logs is not None:
        sound = run.Run.client

        def keep(self, seconds, on_start=None):
            log = sound(self, seconds, on_start)
            logs.append(log)
            return log

        run.Run.client = keep
    try:
        traffic = {"warmup_seconds": 2, "max_warmups": 1}
        if workload == CELL:
            traffic["refresh"] = SMALL
        return run.run_cell(args, device, bench_with_cell(), {
            "rehearsal": True, "config": {"rows_per_region": ROWS}, "traffic": traffic})
    finally:
        if logs is not None:
            run.Run.client = sound


def known_right(monkeypatch):
    """Every task served by the device over a fresh MVCC scan at its own
    ``start_ts``: no region image, so nothing that could hold a stale row."""
    from tikv_tpu.copr.endpoint import Endpoint

    monkeypatch.setattr(Endpoint, "_region_cache_for",
                        lambda self, req, snap, tracker: (None, ""))


# -- the stream itself -------------------------------------------------------

def refresh(seed=SEED, n=2 * ROWS):
    loaded = tbl.build_table(n, seed)
    return loaded, Refresh(SMALL, seed, 101, n, n // 2, 2)


def test_the_stream_is_the_same_for_the_same_seed():
    loaded, a = refresh()
    _l, b = refresh()
    _l, other = refresh(seed=SEED + 1)
    for i in (0, 1, 5):
        pa, pb = a.pair(i), b.pair(i)
        assert [(t["function"], t["txn"], t["region"], t["handles"].tolist()) for t in pa] \
            == [(t["function"], t["txn"], t["region"], t["handles"].tolist()) for t in pb]
        assert [a.mutations(t) for t in pa] == [b.mutations(t) for t in pb]
        assert [a.mutations(t) for t in pa] != [other.mutations(t) for t in other.pair(i)]


def test_rf1_and_rf2_are_tpch_shaped():
    loaded, r = refresh()
    seen_orders, next_handle = set(), len(loaded) + 1
    for i in range(4):
        txns = r.pair(i)
        assert [t["function"] for t in txns] == ["RF1"] * 8 + ["RF2"] * 8
        rf1 = r.rf1_table(i)
        # new orders, keys from the gaps of the sparse numbering, 1-7 lines
        assert not np.isin(rf1.orderkey, loaded.orderkey).any()
        assert ((rf1.orderkey - 1) % 32 // 8 == 1).all()
        _k, lines = np.unique(rf1.orderkey, return_counts=True)
        assert len(lines) == SMALL["orders"] and lines.min() >= 1 and lines.max() <= 7
        # handles above every handle used so far, in the last region
        assert rf1.handle[0] == next_handle
        assert (np.diff(rf1.handle) == 1).all()
        next_handle = int(rf1.handle[-1]) + 1
        got = np.concatenate([t["handles"] for t in txns[:8]])
        assert np.array_equal(got, rf1.handle)
        assert {t["region"] for t in txns[:8]} == {1}
        # RF2: every line of the oldest orders still present, in region 0
        gone = np.concatenate([t["handles"] for t in txns[8:]])
        orders = set(loaded.orderkey[gone - 1].tolist())
        assert len(orders) == SMALL["orders"] and not orders & seen_orders
        assert np.array_equal(np.sort(gone), np.flatnonzero(np.isin(loaded.orderkey, list(orders))) + 1)
        assert {t["region"] for t in txns[8:]} == {0}
        seen_orders |= orders
        # a transaction holds whole orders
        for t in txns:
            keys = (rf1 if t["function"] == "RF1" else loaded).orderkey
            idx = np.flatnonzero(np.isin(
                (rf1 if t["function"] == "RF1" else loaded).handle, t["handles"]))
            mine = set(keys[idx].tolist())
            assert np.isin(keys, list(mine)).sum() == len(t["handles"])
    # oldest first: the first 4 x 25 orders of the load
    assert seen_orders == set(np.unique(loaded.orderkey)[:4 * SMALL["orders"]].tolist())


def test_rf2_ends_where_the_first_region_runs_out_of_whole_orders():
    loaded, r = refresh()
    gone = np.concatenate([t["handles"] for i in range(r.pairs_held)
                           for t in r.pair(i)[8:]])
    # every order wholly in region 0, and no more, is deleted once
    first = loaded.orderkey[:ROWS]
    whole = np.unique(first[~np.isin(first, loaded.orderkey[ROWS:])])
    assert r.pairs_held == len(whole) // SMALL["orders"]
    assert len(np.unique(gone)) == len(gone) and gone.max() <= ROWS
    with pytest.raises(ValueError, match="first region"):
        r.pair(r.pairs_held)


# -- the reference at each task's own start_ts ----------------------------------

def test_each_state_is_the_rows_committed_below_its_start_ts():
    loaded, r = refresh()
    base = [loaded.take(slice(0, ROWS)), loaded.take(slice(ROWS, 2 * ROWS))]
    txns, ts = [], 100
    for i in range(3):
        for t in r.pair(i):
            ts += 10
            txns.append(dict(t, commit_ts=ts, handles=t["handles"].tolist()))
    rng = np.random.default_rng(0)
    shuffled = [txns[j] for j in rng.permutation(len(txns))]  # any order in the log
    hist = check.History(base, 2000, shuffled, r)
    for start_ts in (50, 111, 185, 186, 205, 301, 10_000):
        for k in (0, 1):
            below = [t for t in txns if t["commit_ts"] < start_ts and t["region"] == k]
            s = hist.state(k, start_ts)
            assert s == len(below)
            added = [r.rows(t) for t in below if t["function"] == "RF1"]
            gone = [h for t in below if t["function"] == "RF2" for h in t["handles"]]
            naive = tbl.Table.concat([base[k], *added])
            naive = naive.take(~np.isin(naive.handle, gone))
            got = hist.rows(k, s)
            assert np.array_equal(got.handle, naive.handle)
            assert np.array_equal(got.extendedprice, naive.extendedprice)
            # RF1's rows are in, RF2's are out
            assert np.isin(np.concatenate([a.handle for a in added] or [[]]), got.handle).all()
            assert not np.isin(gone, got.handle).any()
    # the control's state -1: the load's last batch left out
    assert len(hist.rows(0, -1)) == ROWS - 2000


def test_a_mix_that_writes_nothing_is_held_as_before():
    loaded, _r = refresh()
    base = [loaded.take(slice(0, ROWS)), loaded.take(slice(ROWS, 2 * ROWS))]
    hist = check.History(base, 2000)
    assert hist.state(0, 2**62) == 0 and hist.rows(1, 0) is base[1]
    assert check.judge({"compared": 4, "wrong_answers": 0, "unanswered": 0,
                        "device_answered": 4})[0].keys() == {
        "wrong_answers", "unanswered", "device_answered"}
    compared, ok = check.judge({"compared": 4, "wrong_answers": 0, "unanswered": 0,
                                "device_answered": 4, "acked_rows_missing": 1},
                               check.WRITE_LIMITS)
    assert not ok and compared["acked_rows_missing"] == {"value": 1, "limit": 0}
    # every limit is compared, whether or not its number came
    with pytest.raises(KeyError):
        check.judge({"compared": 4, "wrong_answers": 0, "device_answered": 4})
    with pytest.raises(KeyError):
        check.judge({"compared": 4, "wrong_answers": 0, "unanswered": 0,
                     "device_answered": 4}, check.WRITE_LIMITS)


# -- the three traffic files that write nothing --------------------------------

@pytest.mark.parametrize("mix", TRAFFICS)
def test_a_mix_without_refresh_keeps_its_ranges(mix):
    from benchmark.assembly import Deployment
    from tikv_tpu.copr.table import record_key

    writes = "refresh" in run.load_json(run.HERE, "traffic", mix + ".json")
    assert not writes
    dep = Deployment({"table_id": 101, "regions": 2, "rows_per_region": 200_000}, 1)
    try:
        first = [1, 200_001, 400_001]
        assert dep.task_ranges(writes) == [
            (record_key(101, first[k]), record_key(101, first[k + 1])) for k in range(2)]
        assert dep.task_ranges(True)[0] == dep.task_ranges(False)[0]
        assert dep.task_ranges(True)[1][1] > record_key(101, 2**63 - 1)
    finally:
        dep.remove_files()


# the result line of a mix that writes nothing, key for key, as the parent
# commit (6712143) prints it
PARENT_KEYS = {
    "": ["correct", "attempted", "failed", "metrics", "device", "workload", "seed",
         "detail", "compared"],
    "detail": ["queries", "by_plan_ms", "closed_after_s", "reference_s",
               "disk_bytes_at_close", "setup", "faults", "compiles", "numbers",
               "wrong_detail", "moved"],
    "numbers": ["compared", "wrong_answers", "unanswered", "device_answered"],
    "compared": ["wrong_answers", "unanswered", "device_answered"],
}


def test_a_mix_without_refresh_writes_nothing_and_prints_the_same_keys():
    logs = []
    r = cell(seed=2147484101, workload=BENCH["workloads"][0]["name"], seconds=3, logs=logs)
    assert r["correct"]
    assert list(r) == PARENT_KEYS[""]
    assert list(r["detail"]) == PARENT_KEYS["detail"]
    assert list(r["detail"]["numbers"]) == PARENT_KEYS["numbers"]
    assert list(r["compared"]) == PARENT_KEYS["compared"]
    assert logs and all(set(log) == {"seed", "seconds", "closed_after", "queries",
                                     "answers", "errors"} for log in logs)
    assert not any("lock_retries" in t for log in logs for q in log["queries"]
                   for t in q["tasks"])


# -- whole runs with the refresh stream ----------------------------------------

def test_a_sound_run_is_correct_and_the_control_is_not(monkeypatch):
    known_right(monkeypatch)
    logs = []
    r = cell(control=1, logs=logs)
    n = r["detail"]["numbers"]
    assert r["correct"], r["detail"]["wrong_detail"]
    assert n["wrong_answers"] == 0 and n["unanswered"] == 0 and n["acked_rows_missing"] == 0
    assert r["control_correct"] is False and r["control"]["wrong_answers"]["value"] > 0
    assert list(r["compared"]) == ["wrong_answers", "unanswered", "acked_rows_missing",
                                   "device_answered"]
    window = logs[-1]
    ref = r["detail"]["refresh"]
    # pairs committed inside the window, beside the warm-up's
    assert ref["pairs"] >= 3 and ref["txns"] == 16 * ref["pairs"]
    assert ref["txns_before_window"] > 0
    assert all(t["sent"] < t["acked"] for t in window["txns"])
    # each task was held to its own state: tasks of the window saw states
    # that RF1 (region 1) and RF2 (region 0) had moved, several of them
    assert ref["states_compared"] > 6
    # the stream is the refresh module's, in order: the window went on
    # from the warm-up's last pair
    _l, again = refresh(seed=SEED)
    first = window["pairs"][0]["pair"]
    assert first == len(logs[0]["pairs"])
    want = [(t["function"], t["pair"], t["txn"], t["handles"].tolist())
            for i in range(first, first + ref["pairs"]) for t in again.pair(i)]
    assert want == [(t["function"], t["pair"], t["txn"], t["handles"])
                    for t in window["txns"]]


def test_a_task_that_meets_a_lock_is_retried_then_answered(monkeypatch):
    from tikv_tpu.server.service import KvService

    known_right(monkeypatch)
    sound = KvService._coprocessor_local
    seen = {"n": 0, "window": False}
    sound_client = run.Run.client

    def client(self, seconds, on_start=None):
        # the window's client is the second: the set-up's calls stay sound
        seen["window"] = self.n_clients >= 1
        return sound_client(self, seconds, on_start)

    def locked_once(self, req):
        seen["n"] += 1
        if seen["window"] and seen["n"] % 3 == 0:
            start = bytes(req["ranges"][0][0])
            return {"error": {"locked": {"key": start, "primary": start,
                                         "lock_ts": req["start_ts"] - 1, "ttl": 3000}}}
        return sound(self, req)

    monkeypatch.setattr(KvService, "_coprocessor_local", locked_once)
    monkeypatch.setattr(run.Run, "client", client)
    r = cell(seed=SEED + 2)
    ref = r["detail"]["refresh"]
    assert ref["tasks_retried"] > 0 and ref["lock_retries"] >= ref["tasks_retried"]
    assert r["correct"] and r["detail"]["numbers"]["unanswered"] == 0


def test_a_region_cache_that_never_folds_a_write_in(monkeypatch):
    from tikv_tpu.copr.region_cache import RegionColumnCache

    monkeypatch.setattr(RegionColumnCache, "_hit_fresh_locked",
                        lambda self, img, *a, **k: True)
    r = cell(seed=SEED + 3)
    assert not r["correct"]
    assert r["detail"]["numbers"]["wrong_answers"] > 0


def test_a_read_served_above_its_start_ts(monkeypatch):
    from tikv_tpu.server.service import KvService

    known_right(monkeypatch)
    sound = KvService._parse_copr_request

    def later(self, req):
        # two seconds of PD's physical clock later: later pairs' writes
        return sound(self, dict(req, start_ts=req["start_ts"] + (2000 << 18)))

    monkeypatch.setattr(KvService, "_parse_copr_request", later)
    r = cell(seed=SEED + 4)
    assert not r["correct"]
    assert r["detail"]["numbers"]["wrong_answers"] > 0


def test_a_commit_acknowledged_for_a_key_then_dropped(monkeypatch):
    from tikv_tpu.server.service import KvService

    known_right(monkeypatch)
    sound_commit = KvService.kv_commit
    seen = {"n": 0}

    def drops_one(self, req):
        seen["n"] += 1
        # a refresh transaction's, not the load's batches of 2,000
        if seen["n"] % 5 or not 2 <= len(req["keys"]) < 500:
            return sound_commit(self, req)
        *kept, dropped = req["keys"]
        out = sound_commit(self, dict(req, keys=kept))
        self.kv_batch_rollback({"keys": [dropped], "start_version": req["start_version"],
                                "context": req.get("context")})
        return out

    monkeypatch.setattr(KvService, "kv_commit", drops_one)
    r = cell(seed=SEED + 5)
    assert not r["correct"]
    assert r["detail"]["numbers"]["acked_rows_missing"] > 0
    assert r["compared"]["acked_rows_missing"]["value"] > 0
