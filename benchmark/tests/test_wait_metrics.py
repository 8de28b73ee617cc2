"""The four per-layer metrics that read the waits no stage can see: the
interpreter probe's two histograms and the read scheduler's split of each
rider's queue wait by what the dispatcher did meanwhile.  Each reader, found
by its name, on made-up counter snapshots: a series that moved, one that did
not, and a program without the series."""

import importlib

import pytest

from benchmark import run

NAMES = ("interpreter_wait_ms", "wake_lag_ms", "sched_wait_repair_share",
         "sched_wait_linger_share")
BEHIND = "tikv_coprocessor_sched_wait_behind_seconds_total"


def reader(name):
    return importlib.import_module(f"benchmark.layer_metrics.{name}").read


def snap(wait=None, lag=None, behind=None):
    """A counter snapshot as ``counters.snapshot`` gives it: ``wait`` and
    ``lag`` are ``(count, seconds)`` of the two histograms, ``behind``
    ``{activity: seconds}``."""
    out = {"tikv_grpc_msg_duration_seconds_count":
           {(("method", "coprocessor"),): 100.0}}
    for name, hist in (("tikv_process_interpreter_wait_seconds", wait),
                       ("tikv_process_wake_lag_seconds", lag)):
        if hist is not None:
            out[f"{name}_count"] = {(): hist[0]}
            out[f"{name}_sum"] = {(): hist[1]}
    if behind is not None:
        out[BEHIND] = {(("behind", b),): s for b, s in behind.items()} or {(): 0.0}
    return out


def ctx(before, after):
    return {"before": before, "after": after}


@pytest.mark.parametrize("name,arg", [("interpreter_wait_ms", "wait"),
                                      ("wake_lag_ms", "lag")])
def test_mean_of_the_probe_histogram(name, arg):
    read = reader(name)
    before = snap(**{arg: (1000.0, 0.5)})
    # 10,000 wakes in the window that waited 2 s in all: 0.2 ms each
    after = snap(**{arg: (11000.0, 2.5)})
    assert read(ctx(before, after)) == pytest.approx(0.2)
    assert read(ctx(after, after)) is None          # no wake in the window
    assert read(ctx(snap(), snap())) is None        # no probe in the program


@pytest.mark.parametrize("name,behind", [("sched_wait_repair_share", "repair"),
                                         ("sched_wait_linger_share", "linger")])
def test_share_of_the_riders_wait(name, behind):
    read = reader(name)
    before = snap(behind={"linger": 1.0, "repair": 2.0, "device": 0.5,
                          "serve": 0.5})
    after = snap(behind={"linger": 3.0, "repair": 8.0, "device": 1.0,
                         "serve": 1.5})
    # 2 + 6 + 0.5 + 1 = 9.5 s of queue wait in the window
    moved = {"linger": 2.0, "repair": 6.0}[behind]
    assert read(ctx(before, after)) == pytest.approx(100.0 * moved / 9.5)
    assert read(ctx(after, after)) is None          # no rider picked up
    assert read(ctx(snap(), snap())) is None        # no such counter
    # a rider that waited behind nothing of that activity reads 0
    nothing = snap(behind={"serve": 1.5, "linger": 1.0, "repair": 2.0})
    more = dict(nothing)
    more[BEHIND] = dict(nothing[BEHIND])
    more[BEHIND][(("behind", "serve"),)] = 2.5
    assert read(ctx(nothing, more)) == 0.0


def test_every_wait_metric_is_declared_and_found_by_name():
    bench = run.load_json(run.ROOT, "BENCHMARK.json")
    declared = {m["name"]: m for m in bench["per_layer"]}
    for name in NAMES:
        assert declared[name]["source"] == "program_counter"
        assert callable(reader(name))
    assert declared["sched_wait_repair_share"]["workloads"] == [
        "refresh-streams.2x200k"]
    for name in ("interpreter_wait_ms", "wake_lag_ms", "sched_wait_linger_share"):
        assert "workloads" not in declared[name]
