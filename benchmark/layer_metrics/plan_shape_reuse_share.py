"""endpoint and router: device-served tasks whose plan's SHAPE found its
evaluator built (``outcome=reused``) over all tasks counted by
``tikv_coprocessor_plan_shape_total{outcome}`` in the window (reused and
built; ``copr/endpoint.py:_bind``, counted once per device-served task where
the evaluator is resolved).  A shape is a plan without its selection's
literals (``copr/plan_shape.py``): at 100 no literal of the window cost an
evaluator, whatever it was.  A program without the counter (the parent of the
PR that brought it, which keys evaluators by the plan's bytes) moves nothing,
and the reader gives None."""

from benchmark.counters import moved

SERIES = "tikv_coprocessor_plan_shape_total"


def read(ctx):
    n = moved(ctx["before"], ctx["after"], SERIES)
    if not n:
        return None
    return 100.0 * moved(ctx["before"], ctx["after"], SERIES, outcome="reused") / n
