"""The bench device-worker wedge watchdog (ISSUE 8 satellite).

The failure shape it answers: a worker that heartbeats ``init_wait``
while the parent builds CPU fixtures and never comes up, ending as
``worker_killed`` / ``init_budget_exhausted`` with no cause.  Wedge
detection sits on a monitor thread that runs from spawn and kills the worker with a NAMED
cause at BENCH_INIT_STALL seconds — these tests drive the monitor's
verdict logic directly on a harness-free DeviceWorker instance (no real
subprocess, no jax backend)."""

import queue
import threading
import time

import bench


class _FakeProc:
    """Just enough of subprocess.Popen for the monitor + kill paths."""

    def __init__(self):
        self.pid = -1  # os.killpg(-1, ...) raises OSError -> .kill() path
        self.killed = threading.Event()

    def poll(self):
        return None  # "still running" — the wedge monitor's case

    def kill(self):
        self.killed.set()


def _bare_worker(stall_s: float, *, spawned_ago: float = 0.0,
                 silent_for: float = 0.0) -> bench.DeviceWorker:
    """A DeviceWorker with the spawn side effects (subprocess, reader and
    monitor threads) stripped: only the state the verdict logic reads."""
    w = bench.DeviceWorker.__new__(bench.DeviceWorker)
    now = time.time()
    w.timeline = []
    w.t0 = now
    w.proc = _FakeProc()
    w.platform = None
    w._q = queue.Queue()
    w._seq = 0
    w._stall_s = stall_s
    w._spawned_at = now - spawned_ago
    w._last_msg = now - silent_for
    w._ready_seen = False
    w._wedged = None
    w._wedge_mu = threading.Lock()
    return w


def _events(w):
    return [e["ev"] for e in w.timeline]


def test_monitor_declares_backend_init_stall():
    """Zero progress for BENCH_INIT_STALL of worker uptime -> the monitor
    kills the worker and records worker_wedged with the stall cause (one
    5s monitor cycle; the r05 shape burned 900s here).  The heartbeat is
    fresh, so the silence detector stays quiet and the verdict names the
    uptime budget."""
    w = _bare_worker(stall_s=20.0, spawned_ago=30.0)
    t0 = time.monotonic()
    w._monitor_loop()  # first cycle: age >= stall -> verdict, returns
    assert time.monotonic() - t0 < 30.0
    assert w._wedged == "backend_init_stall"
    assert w.proc.killed.is_set()
    ev = [e for e in w.timeline if e["ev"] == "worker_wedged"]
    assert len(ev) == 1 and ev[0]["cause"] == "backend_init_stall"


def test_monitor_declares_heartbeat_silence():
    """A worker whose heartbeat went quiet (backend init holding the GIL)
    wedges on SILENCE even though its uptime is under the stall budget."""
    w = _bare_worker(stall_s=20.0, spawned_ago=0.0, silent_for=30.0)
    w._monitor_loop()
    assert w._wedged == "heartbeat_silent"
    assert w.proc.killed.is_set()


def test_ready_worker_never_wedges():
    """The verdict is init-scoped: once ready has been seen, neither
    detector may kill the worker (a slow OP is the op timeout's job)."""
    w = _bare_worker(stall_s=1.0, spawned_ago=30.0, silent_for=30.0)
    w._ready_seen = True
    w._monitor_loop()
    assert w._wedged is None
    assert not w.proc.killed.is_set()
    w._ready_seen = False
    w._wedged = "backend_init_stall"  # already decided: at most one verdict
    w._declare_wedged("heartbeat_silent")
    assert w._wedged == "backend_init_stall"
    assert not w.proc.killed.is_set()


def test_wait_ready_returns_timeout_on_wedge_without_burning_budget():
    """wait_ready surfaces the monitor's verdict immediately — its budget
    is NOT waited out, and the monitor-kill eof is not mistaken for a
    worker that died by itself."""
    w = _bare_worker(stall_s=1.0)
    w._wedged = "backend_init_stall"
    t0 = time.monotonic()
    assert w.wait_ready(900.0) == "timeout"
    assert time.monotonic() - t0 < 5.0
    assert "init_budget_exhausted" not in _events(w)

    w2 = _bare_worker(stall_s=1.0)
    w2._wedged = "heartbeat_silent"
    w2._q.put({"ev": "eof"})  # the kill EOFs the pipe
    assert w2.wait_ready(900.0) == "timeout"
    assert "worker_died_at_init" not in _events(w2)


def test_init_wait_heartbeats_coalesce_into_one_timeline_event():
    """BENCH_r05 logged one worker_init_wait event every 10s for 900s — 90
    near-identical lines drowning the JSON tail.  Repeats now fold into a
    SINGLE timeline entry carrying first_t/last_t/count, and the eventual
    ready/backend_probe verdicts are untouched."""
    w = _bare_worker(stall_s=900.0)
    for t in (10.0, 20.0, 30.0, 40.0):
        w._q.put({"ev": "init_wait", "t": t})
    w._q.put({"ev": "ready", "platform": "cpu", "t": 45.0})
    assert w.wait_ready(900.0) == "ready"
    waits = [e for e in w.timeline if e["ev"] == "worker_init_wait"]
    assert len(waits) == 1
    assert waits[0]["first_t"] == 10.0
    assert waits[0]["last_t"] == 40.0
    assert waits[0]["count"] == 4
    # the ready verdict still lands as its own event
    assert _events(w).count("ready") == 1


def test_init_wait_coalescing_keeps_stall_backstop():
    """Folding the heartbeat spam must not disable wait_ready's stale-
    heartbeat backstop: a beat whose worker clock passed the stall budget
    still earns the named wedge verdict."""
    w = _bare_worker(stall_s=2.0)
    w._q.put({"ev": "init_wait", "t": 1.0})
    w._q.put({"ev": "init_wait", "t": 5.0})
    assert w.wait_ready(900.0) == "timeout"
    assert w._wedged == "backend_init_stall"
    waits = [e for e in w.timeline if e["ev"] == "worker_init_wait"]
    assert len(waits) == 1 and waits[0]["count"] == 2


def test_wait_ready_backstop_wedges_on_stale_init_wait():
    """Even if the monitor thread never ran, an init_wait heartbeat whose
    own worker-side clock passed the stall budget triggers the verdict in
    wait_ready's drain loop."""
    w = _bare_worker(stall_s=2.0)
    w._q.put({"ev": "init_wait", "t": 5.0})
    assert w.wait_ready(900.0) == "timeout"
    assert w._wedged == "backend_init_stall"
    assert w.proc.killed.is_set()


def test_worker_runs_where_the_caller_says(monkeypatch):
    """The worker inherits the caller's platform; only the explicit rehearsal
    (BENCH_FORCE_CPU=1) pins it to the CPU.  There is no demotion path: the
    parent ends the run when the worker is not where it should be."""
    spawned = []

    class _Popen(_FakeProc):
        def __init__(self, argv, env=None, **kw):
            super().__init__()
            self.stdout = iter(())
            spawned.append(env)

    monkeypatch.setattr(bench.subprocess, "Popen", _Popen)
    monkeypatch.setenv("JAX_PLATFORMS", "tpu")
    bench.DeviceWorker([])
    bench.DeviceWorker([], force_cpu=True)
    assert [env["JAX_PLATFORMS"] for env in spawned] == ["tpu", "cpu"]
    assert not hasattr(bench, "LocalDevice")
