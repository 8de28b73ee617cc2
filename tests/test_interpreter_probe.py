"""The two waits no stage can see (docs/tracing.md, "Waits no stage can see"):
the interpreter probe's wake lag, interpreter wait and stalls
(``util/trace.py``), and what a queued rider of the read scheduler waited
behind (``copr/scheduler.py``, ``trace.WaitClock``).

The interpreter is held by a pure-Python loop with the switch interval
raised past the hold, so that no other thread can take the lock from it:
what a long native call that keeps the lock, or a collector pass, does to a
store.  Timings on a shared host can be disturbed from outside, so the
tests that hold the interpreter take the best of a few tries."""

import gc
import os
import sys
import threading
import time

import pytest

from tikv_tpu.copr.aggr import AggDescriptor
from tikv_tpu.copr.dag import Aggregation, DagRequest, Selection, TableScan
from tikv_tpu.copr.datatypes import ColumnInfo, FieldType
from tikv_tpu.copr.endpoint import CoprRequest, Endpoint
from tikv_tpu.copr.rpn import call, col, const_int
from tikv_tpu.copr.scheduler import BEHIND as ACTIVITIES, CoprReadScheduler
from tikv_tpu.copr.table import encode_row, record_key
from tikv_tpu.copr.tracker import SlowLog
from tikv_tpu.storage.btree_engine import BTreeEngine
from tikv_tpu.storage.engine import CF_WRITE
from tikv_tpu.storage.kv import LocalEngine
from tikv_tpu.storage.txn_types import Key, Write, WriteType
from tikv_tpu.util import trace
from tikv_tpu.util.metrics import REGISTRY

WAIT = "tikv_process_interpreter_wait_seconds"
LAG = "tikv_process_wake_lag_seconds"
STALLS = "tikv_process_stall_total"
BEHIND = "tikv_coprocessor_sched_wait_behind_seconds_total"
NEW_SERIES = (WAIT, LAG, STALLS, "tikv_process_stall_seconds_total", BEHIND)

TABLE_ID = 83
ROWS = 600
COLS = [
    ColumnInfo(1, FieldType.int64(), is_pk_handle=True),
    ColumnInfo(2, FieldType.int64()),
    ColumnInfo(3, FieldType.int64()),
]


def _hold(seconds: float) -> None:
    """Keep the interpreter's lock for ``seconds`` in a pure-Python loop."""
    old = sys.getswitchinterval()
    sys.setswitchinterval(10.0)
    try:
        end = time.perf_counter() + seconds
        while time.perf_counter() < end:
            pass
    finally:
        sys.setswitchinterval(old)


def _hist(name: str) -> tuple:
    h = REGISTRY.histogram(name)
    return h.total(), h.count()


def _probe_threads() -> list:
    return [t for t in threading.enumerate() if t.name == "interpreter-probe"]


def _wait_for(pred, timeout=5.0, msg="condition"):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if pred():
            return
        time.sleep(0.01)
    raise AssertionError(f"timed out waiting for {msg}")


def _series_lines() -> list:
    return [ln for ln in REGISTRY.render().splitlines()
            if ln.startswith(NEW_SERIES)]


@pytest.fixture
def rate():
    old = trace.sample_rate()
    trace.set_sample_rate(1.0)
    yield
    trace.set_sample_rate(old)


@pytest.fixture
def probe(rate):
    """The probe running at rate 1, its stalls written to a slow log of its
    own; stopped afterwards."""
    trace.install_gc_hook()
    log = SlowLog()
    trace.install_interpreter_probe(log)
    _wait_for(lambda: len(_probe_threads()) == 1, msg="the probe")
    time.sleep(0.02)  # past the first wakes
    yield log
    trace.release_interpreter_probe(log)
    _wait_for(lambda: not _probe_threads(), msg="the probe to stop")


def test_a_held_interpreter_is_waited_for_and_an_idle_one_is_not(probe):
    # idle: the probe's own wakes, each on time and with the lock free
    for _ in range(3):
        (w0, n0), (l0, _m0) = _hist(WAIT), _hist(LAG)
        time.sleep(0.2)
        (w1, n1), (l1, _m1) = _hist(WAIT), _hist(LAG)
        assert n1 - n0 >= 10  # a wake every PROBE_PERIOD_S
        idle_wait, idle_lag = (w1 - w0) / (n1 - n0), (l1 - l0) / (n1 - n0)
        if idle_wait < 1e-3 and idle_lag < 2e-3:
            break
    else:
        raise AssertionError(("an idle process waits", idle_wait, idle_lag))
    # a 50 ms hold: the wake that falls in it waits for the lock, not the OS
    for _ in range(3):
        (w0, n0), (l0, _m0) = _hist(WAIT), _hist(LAG)
        _hold(0.05)
        time.sleep(0.03)  # the waiting wake returns and records
        (w1, n1), (l1, _m1) = _hist(WAIT), _hist(LAG)
        waited, lagged = w1 - w0, l1 - l0
        if waited >= 0.04 and lagged / (n1 - n0) < 2e-3:
            return
    raise AssertionError(("a 50 ms hold", waited, lagged, n1 - n0))


def test_a_stall_is_blamed_on_the_stage_that_held_the_interpreter(probe):
    stalls = REGISTRY.counter(STALLS)
    for _ in range(3):
        n0 = stalls.get(during="t.held")
        with trace.stage("t.held"):
            _hold(0.3)
        time.sleep(0.03)
        if stalls.get(during="t.held") == n0 + 1:
            break
    else:
        raise AssertionError("no stall blamed on the held stage")
    line = [e for e in probe.entries if e["tag"] == "process.stall"][-1]
    assert line["during"] == "t.held"
    assert line["ms"] >= 250.0
    assert line["interpreter_wait_ms"] > line["wake_lag_ms"]
    mine = [t for t in line["threads"]
            if t["thread"] == threading.current_thread().name]
    assert [t["stage"] for t in mine] == ["t.held"]
    # open across the larger part of the stall, as the blame asks (on a
    # loaded host the probe's wake can fall just before the stage opened)
    assert mine[0]["opened_ms"] < line["ms"] / 2
    assert line["start_wall"] == pytest.approx(time.time(), abs=5.0)


class _SlowFinalizer:
    """A cycle only the collector frees, whose finalizer holds the
    interpreter inside the collector's pass."""

    def __init__(self):
        self.me = self

    def __del__(self):
        _hold(0.3)


def test_a_stall_under_a_collector_pass_is_blamed_on_the_collector(probe):
    stalls = REGISTRY.counter(STALLS)
    for _ in range(3):
        n0 = stalls.get(during="host.gc")
        _SlowFinalizer()
        gc.collect()
        time.sleep(0.03)
        if stalls.get(during="host.gc") == n0 + 1:
            return
    raise AssertionError("no stall blamed on the collector")


def _engine() -> BTreeEngine:
    eng = BTreeEngine()
    items = []
    for i in range(2 * ROWS):
        val = encode_row(COLS[1:], [i % 50, i])
        items.append((Key.from_raw(record_key(TABLE_ID, i)).append_ts(20).encoded,
                      Write(WriteType.PUT, 10, short_value=val).to_bytes()))
    eng.bulk_load(CF_WRITE, items)
    return eng


def _req(region: int) -> CoprRequest:
    dag = DagRequest(executors=[
        TableScan(TABLE_ID, COLS),
        Selection([call("lt", col(1), const_int(40))]),
        Aggregation([], [AggDescriptor("sum", col(2)),
                         AggDescriptor("count", None)]),
    ])
    lo = record_key(TABLE_ID, region * ROWS)
    hi = record_key(TABLE_ID, (region + 1) * ROWS)
    return CoprRequest(103, dag, [(lo, hi)], 100,
                       context={"region_id": region + 1, "region_epoch": (1, 1),
                                "apply_index": 7})


@pytest.fixture(scope="module")
def endpoint():
    ep = Endpoint(LocalEngine(_engine()), enable_device=True, block_rows=1024)
    for r in range(2):  # images and programs
        ep.handle_request(_req(r))
    return ep


def _behind() -> list:
    c = REGISTRY.counter(BEHIND)
    return [c.get(behind=b) for b in ACTIVITIES]


def test_a_rider_behind_a_repair_is_told_so_and_its_parts_tile_its_wait(
        endpoint, rate, monkeypatch):
    sched = CoprReadScheduler(endpoint)
    repairing = threading.Event()
    serve = sched._serve

    def planted(items):
        # the first batch's dispatcher sits in a repair that lasts 30 ms
        # past the second rider's enqueue
        if not repairing.is_set():
            with trace.stage("cache.repair"):
                repairing.set()
                while not any(sched._queues.values()):
                    time.sleep(0.0005)
                time.sleep(0.03)
        return serve(items)

    monkeypatch.setattr(sched, "_serve", planted)
    lane = REGISTRY.histogram("tikv_coprocessor_sched_lane_wait_seconds")
    sched.start()
    try:
        behind0, waited0 = _behind(), lane.total(lane="normal")
        first = threading.Thread(target=sched.execute, args=(_req(0), 30.0))
        first.start()
        assert repairing.wait(10.0)
        sched.execute(_req(1), 30.0)  # enqueued while the repair runs
        first.join(30.0)
        parts = [b - a for a, b in zip(behind0, _behind())]
        waited = lane.total(lane="normal") - waited0
    finally:
        assert sched.stop()
    by = dict(zip(ACTIVITIES, parts))
    assert by["repair"] >= 0.025, by
    assert min(parts) >= 0.0, by
    # the first rider waited out its linger alone
    assert by["linger"] > 0.0, by
    assert sum(parts) == pytest.approx(waited, rel=0.01, abs=5e-5), (by, waited)


def test_a_rider_handed_back_to_its_own_thread_stops_sharing_the_batch(
        endpoint, rate, monkeypatch):
    """A rider the dispatcher hands back to be served on its own thread
    (``direct``) is not charged the batch's later stages: only the rider
    still waiting gets the planted 30 ms of the batch's admission."""
    sched = CoprReadScheduler(endpoint)
    held = threading.Event()
    serve, resolve, bind = sched._serve, sched._resolve_slot, endpoint._bind

    def planted_serve(items):
        # the first batch holds the dispatcher until both riders queue
        if not held.is_set():
            held.set()
            while sum(len(q) for q in sched._queues.values()) < 2:
                time.sleep(0.0005)
        return serve(items)

    def planted_resolve(slot):
        if slot.items[0].req.context["region_id"] == 2:
            return False  # region 1 sheds back to its rider's thread
        return resolve(slot)

    def slow_bind(*args, **kwargs):
        time.sleep(0.03)  # inside the batch's admission, after the shed
        return bind(*args, **kwargs)

    monkeypatch.setattr(sched, "_serve", planted_serve)
    monkeypatch.setattr(sched, "_resolve_slot", planted_resolve)
    monkeypatch.setattr(endpoint, "_bind", slow_bind)
    ids = {}

    def rider(region):
        with trace.start_trace("rpc.x", method="x") as root:
            ids[region] = root.rec.trace_id
            sched.execute(_req(region), 30.0)

    sched.start()
    try:
        first = threading.Thread(target=sched.execute, args=(_req(0), 30.0))
        first.start()
        assert held.wait(10.0)
        riders = [threading.Thread(target=rider, args=(r,)) for r in (0, 1)]
        riders[0].start()
        _wait_for(lambda: sum(len(q) for q in sched._queues.values()) == 1,
                  msg="the first rider queued")
        riders[1].start()
        for th in [first] + riders:
            th.join(30.0)
        assert not any(th.is_alive() for th in [first] + riders)
    finally:
        assert sched.stop()
    waited, handed = (trace.TRACER.get(ids[r]) for r in (0, 1))
    # the admission's stage lands in the waiting rider's own trace
    assert waited["attributed_ms"] >= 30.0, waited
    assert handed["shared_ms"] < 30.0, handed["shared_ms"]
    assert handed["attributed_ms"] <= handed["duration_ms"]


def test_rate_zero_starts_no_probe_and_moves_no_new_series(endpoint):
    old = trace.sample_rate()
    sched = CoprReadScheduler(endpoint)
    trace.set_sample_rate(0.0)
    _wait_for(lambda: not _probe_threads(), msg="no probe at rate 0")
    try:
        trace.install_gc_hook()
        trace.install_interpreter_probe()
        before, opened = _series_lines(), dict(trace.TRACER._open_stages)
        sched.start()
        try:
            sched.execute(_req(0), 30.0)
        finally:
            assert sched.stop()
        with trace.stage("cache.repair"):
            _hold(0.01)
        _SlowFinalizer()
        gc.collect()
        time.sleep(0.05)
        assert not _probe_threads()
        assert _series_lines() == before
        assert trace.TRACER._open_stages == opened
        # the probe runs while the rate is above 0 and a store that asked
        # for it runs, and only then
        trace.set_sample_rate(0.01)
        _wait_for(lambda: len(_probe_threads()) == 1, msg="the probe")
        trace.set_sample_rate(0.0)
        _wait_for(lambda: not _probe_threads(), msg="the probe to stop")
        trace.set_sample_rate(0.01)
        _wait_for(lambda: len(_probe_threads()) == 1, msg="the probe again")
    finally:
        trace.release_interpreter_probe()
        trace.set_sample_rate(old)
    _wait_for(lambda: not _probe_threads(), msg="the probe let go")


def test_the_wait_clock_reads_whole_while_it_switches():
    """Readers on many threads see the clock whole while its thread switches
    activity: its totals as of any moment sum to the time since it was made,
    which a torn read (totals of one state, start of another) breaks."""
    clock = trace.WaitClock(4, lambda stage: 2 if stage == "d" else None, 3)
    t = time.perf_counter()
    start = t - sum(clock.read(t))
    stop = threading.Event()
    worst = []

    def switch():
        while not stop.is_set():
            clock.switch(0)
            clock.switch(3)
            clock.enter("d", time.perf_counter())
            clock.leave("d", time.perf_counter())

    def read():
        off = 0.0
        while not stop.is_set():
            t = time.perf_counter()
            off = max(off, abs(sum(clock.read(t)) - (t - start)))
        worst.append(off)

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    threads = [threading.Thread(target=switch)] + [
        threading.Thread(target=read) for _ in range(os.cpu_count() or 4)]
    try:
        for t in threads:
            t.start()
        time.sleep(0.2)
    finally:
        stop.set()
        for t in threads:
            t.join(10.0)
        sys.setswitchinterval(old)
    assert not any(t.is_alive() for t in threads)
    assert len(worst) == len(threads) - 1 and max(worst) < 1e-6, worst


def test_a_bound_clock_counts_its_threads_stages_by_their_class(rate):
    """The stages of the thread a clock is bound to move it: a classified
    stage's time goes to its class, any other stage's to what the thread
    was doing around it; another thread's stages move nothing."""
    clock = trace.WaitClock(
        3, lambda stage: 1 if stage.startswith("t.cls") else None, 0)

    def other():
        with trace.stage("t.cls.other"):
            time.sleep(0.02)

    trace.bind_clock(clock)
    try:
        t0 = time.perf_counter()
        before = clock.read(t0)
        clock.switch(2)
        with trace.stage("t.cls.a"):
            time.sleep(0.02)
            th = threading.Thread(target=other)
            th.start()
            th.join(5.0)
        with trace.stage("t.plain"):
            time.sleep(0.02)
        clock.switch(0)
        t1 = time.perf_counter()
        parts = [b - a for a, b in zip(before, clock.read(t1))]
    finally:
        trace.unbind_clock()
    assert sum(parts) == pytest.approx(t1 - t0, abs=1e-9)
    assert parts[1] >= 0.04 and parts[2] >= 0.02, parts
    # unbound: a classified stage no longer moves it
    t2 = time.perf_counter()
    with trace.stage("t.cls.b"):
        time.sleep(0.01)
    t3 = time.perf_counter()
    assert clock.read(t3)[0] - clock.read(t2)[0] == pytest.approx(t3 - t2)


def test_every_user_of_the_probe_logs_its_stalls_until_it_lets_go(rate):
    trace.install_gc_hook()
    first, second = SlowLog(), SlowLog()
    trace.install_interpreter_probe(first)
    trace.install_interpreter_probe(second)
    try:
        _wait_for(lambda: len(_probe_threads()) == 1, msg="one probe")
        time.sleep(0.02)
        for _ in range(3):
            with trace.stage("t.logged"):
                _hold(0.3)
            time.sleep(0.03)
            if first.entries and second.entries:
                break
        else:
            raise AssertionError("no stall logged")
        assert [e["during"] for e in first.entries] \
            == [e["during"] for e in second.entries]
        trace.release_interpreter_probe(first)
        time.sleep(0.02)
        n_first, n_second = len(first.entries), len(second.entries)
        with trace.stage("t.logged"):
            _hold(0.3)
        time.sleep(0.03)
        assert len(first.entries) == n_first
        assert len(second.entries) > n_second
    finally:
        trace.release_interpreter_probe(first)
        trace.release_interpreter_probe(second)
    _wait_for(lambda: not _probe_threads(), msg="the probe let go")
    # the table of open stages lives while the probe runs, and no longer
    assert trace.TRACER._open_stages == {}
    with trace.stage("t.after"):
        pass
    assert trace.TRACER._open_stages == {}
