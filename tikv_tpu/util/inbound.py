"""The store's inbound read pipeline: is anybody still on the way?

A read request is *on its way* to the read scheduler from the moment the
connection's thread hands its frame to the read pool until it is either
parked in one of the scheduler's lanes or has left its handler without
parking (bypass, shed, reject, error, any read the scheduler never sees).
One count says so, exact in both directions: the connection's thread counts
a read up (``arrived``), the pool thread that serves it owes the count down
(``handling``), and the scheduler pays it early, under its own lock, as the
request enters a lane (``parked``).

With riders queued, none of which would be served alone, and a count of zero,
nobody else can join the batch, so the dispatcher stops lingering
(``copr/scheduler.py:_dispatch_loop``).

The store that owns both hands one instance to its ``Server`` and to its
scheduler (``watch_inbound``); neither imports the other.  A count that leaks
upward only brings the linger back; a count below zero would end every linger
early, so ``low`` keeps the lowest reading for the tests to hold at zero.
"""

from __future__ import annotations

import threading
from contextlib import contextmanager

from ..analysis.sanitizer import make_lock
from .metrics import REGISTRY

_GAUGE = REGISTRY.gauge(
    "tikv_coprocessor_sched_inbound",
    "Read requests off the socket and not yet parked in the read scheduler",
)


class InboundReads:
    def __init__(self):
        self._mu = make_lock("copr.inbound")
        self._n = 0
        self._owed = threading.local()
        self.low = 0
        # called, outside this object's lock, by whoever takes the count to
        # zero without parking: the scheduler's wake-up
        self.on_drained = None

    def pending(self) -> int:
        return self._n

    def _move(self, by: int) -> int:
        with self._mu:
            n = self._n = self._n + by
            if n < self.low:
                self.low = n
            _GAUGE.set(n)  # under the lock: the last write is the last move
        return n

    def arrived(self) -> None:
        """A read is off the socket and about to be handed to the read pool
        (the connection's thread)."""
        self._move(1)

    def left(self) -> None:
        """A counted read goes no further towards the scheduler."""
        if self._move(-1) == 0 and self.on_drained is not None:
            self.on_drained()

    @contextmanager
    def handling(self):
        """Around the whole of a counted request's handler, on the thread
        that runs it: however the handler ends, the request is counted down
        exactly once, here unless ``parked`` did it."""
        self._owed.yes = True
        try:
            yield
        finally:
            if self._owed.yes:
                self._owed.yes = False
                self.left()

    def parked(self) -> None:
        """The calling thread's request entered a scheduler lane (called
        under the scheduler's lock, whose holder wakes the dispatcher
        itself).  A thread that carries no counted request moves nothing."""
        if getattr(self._owed, "yes", False):
            self._owed.yes = False
            self._move(-1)
