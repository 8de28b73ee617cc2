"""Worker framework + unified multilevel read pool.

Re-expression of ``tikv_util/src/worker`` (LazyWorker/Runnable: a named
single-thread worker draining a channel of tasks, with optional periodic
timer) and the yatp multilevel pool behind the unified read pool
(``tikv_util/src/yatp_pool/mod.rs:12`` — queue levels, per-task-group
elapsed accounting, demotion; ``src/read_pool.rs`` build_yatp_read_pool).

Scheduling model (yatp's multilevel queue, re-derived):

* Three levels.  New task groups start at L0.  A group is demoted as its
  *accumulated* CPU time crosses thresholds (default 5ms → L1, 100ms → L2),
  so cheap point-gets never sit behind a long analytical scan — the exact
  property the reference's unified read pool exists for.
* Workers prefer L0 but visit lower levels on a fixed ratio so nothing
  starves (level_time_ratio in yatp; a deterministic 8:2:1 cycle here).
* ``TaskPriority.HIGH`` pins a task to L0 regardless of history (the
  reference's resource-control override).
"""

from __future__ import annotations

import enum
import threading
import time
from collections import deque

from ..analysis.sanitizer import make_condition


class Runnable:
    """Task handler for a Worker (worker/mod.rs Runnable)."""

    def run(self, task) -> None:
        raise NotImplementedError

    def on_timeout(self) -> None:
        """Periodic tick (RunnableWithTimer)."""

    def shutdown(self) -> None:
        """Called once when the worker stops."""


class Worker:
    """Named single-thread worker: schedule() enqueues, the thread drains.

    ``LazyWorker`` semantics: created stopped; ``start(runnable)`` spins the
    thread; schedule() before start() buffers.
    """

    def __init__(self, name: str, timer_interval: float | None = None):
        self.name = name
        self._queue: deque = deque()
        self._cv = make_condition("util.worker", label=name)
        self._runnable: Runnable | None = None
        self._thread: threading.Thread | None = None
        self._stopped = False
        self._timer_interval = timer_interval
        self.handled = 0

    def start(self, runnable: Runnable) -> None:
        assert self._thread is None, "worker already started"
        self._runnable = runnable
        self._thread = threading.Thread(target=self._loop, name=self.name, daemon=True)
        self._thread.start()

    def schedule(self, task) -> bool:
        with self._cv:
            if self._stopped:
                return False
            self._queue.append(task)
            self._cv.notify()
        return True

    def _loop(self) -> None:
        interval = self._timer_interval
        next_tick = time.monotonic() + interval if interval else None
        while True:
            # the tick is checked on EVERY iteration so a continuously-fed
            # queue cannot starve the periodic flush/heartbeat
            if next_tick is not None and time.monotonic() >= next_tick:
                try:
                    self._runnable.on_timeout()
                except Exception:  # noqa: BLE001
                    pass
                next_tick = time.monotonic() + interval
            with self._cv:
                while not self._queue and not self._stopped:
                    timeout = 0.5
                    if next_tick is not None:
                        timeout = max(0.0, min(timeout, next_tick - time.monotonic()))
                        if timeout == 0.0:
                            break
                    self._cv.wait(timeout)
                if self._stopped and not self._queue:
                    break
                task = self._queue.popleft() if self._queue else None
            if task is None:
                continue  # woke for a tick; handled at loop top
            try:
                self._runnable.run(task)
            except Exception:  # noqa: BLE001 — a task must not kill the worker
                pass
            self.handled += 1

    def stop(self, wait: bool = True) -> bool:
        """False where the thread was given up on: it is still inside a task
        and goes on using whatever that task uses."""
        with self._cv:
            self._stopped = True
            self._cv.notify_all()
        if wait and self._thread is not None:
            self._thread.join(timeout=5.0)
            if self._thread.is_alive():
                # A wedged task still owns run(); calling shutdown() now would
                # race with it.  Leave the runnable alive and let the daemon
                # thread die with the process.
                return False
        if self._runnable is not None:
            self._runnable.shutdown()
        return True

    def pending(self) -> int:
        with self._cv:
            return len(self._queue)


class TaskPriority(enum.IntEnum):
    HIGH = 0
    NORMAL = 1


class _Future:
    __slots__ = ("_ev", "_result", "_exc")

    def __init__(self):
        self._ev = threading.Event()
        self._result = None
        self._exc: BaseException | None = None

    def set(self, result=None, exc: BaseException | None = None) -> None:
        self._result, self._exc = result, exc
        self._ev.set()

    def result(self, timeout: float | None = None):
        if not self._ev.wait(timeout):
            raise TimeoutError("read pool task timed out")
        if self._exc is not None:
            raise self._exc
        return self._result

    def done(self) -> bool:
        return self._ev.is_set()


# demotion thresholds: accumulated group CPU seconds crossing these moves the
# group down a level (yatp multilevel defaults are 5ms/100ms task-elapsed)
_LEVEL_THRESHOLDS = (0.005, 0.100)
# deterministic visit cycle — 8 L0 slots, 2 L1, 1 L2 (≈ yatp level_time_ratio)
_VISIT_CYCLE = (0, 0, 1, 0, 0, 2, 0, 1, 0, 0, 0)


class UnifiedReadPool:
    """The unified read pool: N workers over one 3-level queue.

    ``submit(fn, group=...)`` → future.  ``group`` identifies the logical
    request stream (e.g. a txn's start_ts or a connection id); the group's
    accumulated elapsed time decides its level, so one heavy consumer sinks
    to L2 while light traffic keeps L0 latency.
    """

    def __init__(self, workers: int = 4, name: str = "unified-read-pool"):
        self._levels: tuple[deque, deque, deque] = (deque(), deque(), deque())
        self._cv = make_condition("util.read_pool", label=name)
        # group → (accumulated elapsed seconds, last activity monotonic time)
        self._group_elapsed: dict[object, tuple[float, float]] = {}
        self._stopped = False
        self.name = name
        self._threads = [
            threading.Thread(target=self._worker_loop, args=(i,), name=f"{name}-{i}", daemon=True)
            for i in range(workers)
        ]
        for t in self._threads:
            t.start()

    # -- submission ---------------------------------------------------------

    def level_of(self, group) -> int:
        e, _ = self._group_elapsed.get(group, (0.0, 0.0))
        if e < _LEVEL_THRESHOLDS[0]:
            return 0
        if e < _LEVEL_THRESHOLDS[1]:
            return 1
        return 2

    def submit(self, fn, *args, group=None, priority: TaskPriority = TaskPriority.NORMAL):
        fut = _Future()
        with self._cv:
            if self._stopped:
                raise RuntimeError("read pool is stopped")
            level = 0 if priority == TaskPriority.HIGH else self.level_of(group)
            self._levels[level].append((fn, args, group, fut))
            self._cv.notify()
        return fut

    # -- workers ------------------------------------------------------------

    def _pick_locked(self, slot: int):
        preferred = _VISIT_CYCLE[slot % len(_VISIT_CYCLE)]
        for lvl in (preferred, 0, 1, 2):
            if self._levels[lvl]:
                return self._levels[lvl].popleft()
        return None

    def _worker_loop(self, seed: int) -> None:
        slot = seed
        while True:
            with self._cv:
                task = self._pick_locked(slot)
                while task is None and not self._stopped:
                    self._cv.wait(0.5)
                    task = self._pick_locked(slot)
                if task is None:
                    return
            slot += 1
            fn, args, group, fut = task
            start = time.monotonic()
            try:
                fut.set(fn(*args))
            except BaseException as e:  # noqa: BLE001 — delivered via future
                fut.set(exc=e)
            if group is not None:
                now = time.monotonic()
                elapsed = now - start
                with self._cv:
                    prev, _ = self._group_elapsed.get(group, (0.0, 0.0))
                    self._group_elapsed[group] = (prev + elapsed, now)
                    # bound the stats map by evicting *idle* groups only — a
                    # wholesale clear would re-promote still-running heavy
                    # groups to L0 (yatp recycles idle records the same way)
                    if len(self._group_elapsed) > 4096:
                        cutoff = now - 30.0
                        evict = [g for g, (_, last) in self._group_elapsed.items() if last < cutoff]
                        if not evict:
                            # all recent: drop the *cheapest* half — losing a
                            # light group's record is free (it re-enters at
                            # L0 anyway), while a heavy group's demotion
                            # state is exactly what must survive
                            by_cost = sorted(self._group_elapsed.items(), key=lambda kv: kv[1][0])
                            evict = [g for g, _ in by_cost[: len(by_cost) // 2]]
                        for g in evict:
                            del self._group_elapsed[g]

    # -- introspection ------------------------------------------------------

    def queue_depths(self) -> tuple[int, int, int]:
        with self._cv:
            return tuple(len(q) for q in self._levels)

    def stop(self) -> None:
        with self._cv:
            self._stopped = True
            self._cv.notify_all()
        for t in self._threads:
            t.join(timeout=5.0)
