"""``plan_shape_reuse_share`` and ``plan_bind_ms_per_task``: the readers on
made-up snapshots (a program without the counter or the stage gives nothing),
their declarations, the new cell's files against the ones they were made
from, and one whole run of ``param-streams.2x200k`` at a size a CPU holds:
every task compared at its own query's parameters, no program built inside
the window, every task's shape found built."""

import json
import os

import pytest

from benchmark import run
from benchmark.layer_metrics import plan_bind_ms_per_task, plan_shape_reuse_share

CELL = "param-streams.2x200k"
SERIES = plan_shape_reuse_share.SERIES


def labels(**kv):
    return tuple(sorted(kv.items()))


def snap(tasks=0.0, bind_runs=0.0, bind_s=0.0, **outcomes):
    out = {"tikv_grpc_msg_duration_seconds_count": {labels(method="coprocessor"): tasks}}
    if bind_runs:
        out["tikv_trace_stage_seconds_count"] = {labels(stage="copr.bind"): bind_runs}
        out["tikv_trace_stage_seconds_sum"] = {labels(stage="copr.bind"): bind_s}
    for outcome, n in outcomes.items():
        out.setdefault(SERIES, {})[labels(outcome=outcome)] = float(n)
    return out


@pytest.mark.parametrize("before,after,share", [
    (snap(built=4), snap(built=4, reused=1000), 100.0),
    (snap(built=4, reused=10), snap(built=6, reused=16), 75.0),
    (snap(), snap(built=2), 0.0),
])
def test_share_of_tasks_whose_shape_was_known(before, after, share):
    assert plan_shape_reuse_share.read({"before": before, "after": after}) \
        == pytest.approx(share)


def test_nothing_on_a_program_without_them():
    for before, after in ((snap(), snap(tasks=100)), ({}, {}),
                          (snap(built=4, reused=9), snap(tasks=5, built=4, reused=9))):
        ctx = {"before": before, "after": after}
        assert plan_shape_reuse_share.read(ctx) is None
        assert plan_bind_ms_per_task.read(ctx) is None


def test_bind_time_per_task():
    ctx = {"before": snap(tasks=10, bind_runs=15, bind_s=0.5),
           "after": snap(tasks=110, bind_runs=165, bind_s=0.51)}
    assert plan_bind_ms_per_task.read(ctx) == pytest.approx(0.1)


def test_declared_for_the_new_cell_alone():
    bench = run.load_json(run.ROOT, "BENCHMARK.json")
    want = {"plan_shape_reuse_share": ("%", "higher", "query_p95_ms"),
            "plan_bind_ms_per_task": ("ms", "lower", "scan_rows_per_s")}
    for name, (unit, better, moves) in want.items():
        (m,) = [m for m in bench["per_layer"] if m["name"] == name]
        assert m == {"name": name, "unit": unit, "better": better,
                     "source": "program_counter", "layer": "endpoint and router",
                     "moves": moves, "workloads": [CELL]}
        for cell in bench["workloads"]:
            assert (m in run.metrics_of(bench, "per_layer", cell["name"])) \
                == (cell["name"] == CELL)


def test_the_cells_files_differ_from_their_models_where_they_say():
    load = lambda *p: json.load(open(os.path.join(run.HERE, *p)))
    old, new = load("traffic", "tpch-throughput.json"), load("traffic", "param-streams.json")
    assert new["substitute"] == "query" and old["substitute"] == "stream"
    assert {k for k in old if old[k] != new[k]} == {"what", "source", "substitute"}
    assert {k for k in old["source"] if old["source"][k] != new["source"][k]} == {"substitute"}
    old = load("configs", "tpch-lineitem-2x200k-s2.json")
    new = load("configs", "tpch-lineitem-2x200k-s2-qsub.json")
    assert set(new) - set(old) == {"substitution", "substitution_source"}
    assert {k for k in old if old[k] != new[k]} == {
        "deployment", "guarantees", "assumed", "held", "held_why", "reduced_from"}
    assert set(new["guarantees"]) - set(old["guarantees"]) == {"parameters"}
    # the same store held the same way; the one key more departs from nothing
    # (the count starts at 0) and is in no list of cuts
    assert new["held"] == dict(old["held"], plan_shapes_built=0)
    assert new["held_why"].startswith(old["held_why"])
    assert {k for k in old["reduced_from"]
            if old["reduced_from"][k] != new["reduced_from"][k]} == {"held"}
    bench = run.load_json(run.ROOT, "BENCHMARK.json")
    by_name = {c["name"]: c for c in bench["configs"]}
    assert by_name["tpch-lineitem-2x200k-s2-qsub"]["reduced"] \
        == by_name["tpch-lineitem-2x200k-s2"]["reduced"]


def test_a_store_that_finds_programs_by_plan_bytes_is_refused_at_once(monkeypatch):
    """What the parent commit does under this configuration: ``assembly.py``
    finds no ``plan_shapes_built`` on the endpoint and ends the run before
    split and load; the store of this tree starts, its count at 0."""
    from benchmark.assembly import Deployment
    from tikv_tpu.copr.endpoint import Endpoint

    bench = run.load_json(run.ROOT, "BENCHMARK.json")
    _cell, cfg = run.find_cell(bench, CELL)
    dep = Deployment(run.load_json(run.ROOT, cfg["file"]), 1)
    try:
        dep.start()
        assert dep.srv.copr.plan_shapes_built == 0
    finally:
        dep.stop()
        dep.remove_files()

    class Before(Endpoint):
        def __getattribute__(self, name):
            if name == "plan_shapes_built":
                raise AttributeError(name)
            return super().__getattribute__(name)

    from tikv_tpu.server import standalone
    monkeypatch.setattr(standalone, "Endpoint", Before)
    dep = Deployment(run.load_json(run.ROOT, cfg["file"]), 1)
    try:
        with pytest.raises(RuntimeError,
                           match="held: the endpoint has no plan_shapes_built"):
            dep.start()
    finally:
        dep.stop()
        dep.remove_files()


def test_a_traced_rehearsal_finds_every_program_by_its_shape():
    import jax

    bench = run.load_json(run.ROOT, "BENCHMARK.json")
    args = run.parse(["--workload", CELL, "--seed", "2147483934",
                      "--seconds", "6", "--trace", "1"])
    d = jax.devices()
    device = {"platform": d[0].platform, "kind": d[0].device_kind, "count": len(d)}
    run.Run.peaks = lambda self: {"hbm_bytes_per_s": float("inf")}
    r = run.run_cell(args, device, bench, {
        "rehearsal": True, "config": {"rows_per_region": 4000},
        "traffic": {"warmup_seconds": 3, "max_warmups": 2, "trace_seconds": 2}})
    assert r["correct"] and r["failed"] == 0
    n = r["detail"]["numbers"]
    assert n["compared"] == n["device_answered"] > 50
    m = r["metrics"]
    assert m["window_compiles"]["value"] == 0
    assert m["plan_shape_reuse_share"]["value"] == pytest.approx(100.0)
    assert 0 < m["plan_bind_ms_per_task"]["value"] < 5
    assert m["cache_hit_share"]["value"] == m["device_served_share"]["value"] == 100.0
