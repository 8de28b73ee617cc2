"""The eight per-layer metrics that read the store's stage totals: each reader
on made-up snapshots (a series that moved, one that did not, the shares'
arithmetic), and one whole run at a size a CPU holds whose traced result
holds all eight."""

import importlib

import pytest

from benchmark import run

NAMES = ("lock_check_ms_per_task", "cache_lookup_ms_per_task",
         "dispatch_ms_per_task", "readback_ms_per_task",
         "finalize_encode_ms_per_task", "execute_unattributed_share",
         "host_off_cpu_share", "gc_pause_ms_per_task")


def reader(name):
    return importlib.import_module(f"benchmark.layer_metrics.{name}").read


def labels(**kv):
    return tuple(sorted(kv.items()))


def snap(tasks=0.0, stages=None, cpu=None, requests=None, gc=None):
    """A counter snapshot as ``counters.snapshot`` gives it.  ``stages`` is
    ``{stage: (count, seconds)}``, ``cpu`` ``{stage: seconds}``, ``requests``
    ``(total, attributed)`` seconds of coprocessor traces, ``gc``
    ``{generation: seconds}``."""
    out = {"tikv_grpc_msg_duration_seconds_count":
           {labels(method="coprocessor"): tasks, labels(method="kv_get"): 7.0}}
    for stage, (n, s) in (stages or {}).items():
        out.setdefault("tikv_trace_stage_seconds_count", {})[labels(stage=stage)] = n
        out.setdefault("tikv_trace_stage_seconds_sum", {})[labels(stage=stage)] = s
    for stage, s in (cpu or {}).items():
        out.setdefault("tikv_trace_stage_cpu_seconds_total", {})[labels(stage=stage)] = s
    if requests is not None:
        out["tikv_trace_request_seconds_total"] = {
            labels(method="coprocessor"): requests[0], labels(method="kv_get"): 9.0}
        out["tikv_trace_request_attributed_seconds_total"] = {
            labels(method="coprocessor"): requests[1], labels(method="kv_get"): 1.0}
    if gc is not None:
        out["tikv_process_gc_pause_seconds_total"] = {
            labels(generation=g): s for g, s in gc.items()} or {(): 0.0}
    return out


def ctx(before, after):
    return {"before": before, "after": after}


@pytest.mark.parametrize("name,stages", [
    ("lock_check_ms_per_task", ("cache.lock_check",)),
    ("cache_lookup_ms_per_task", ("cache.lookup",)),
    ("dispatch_ms_per_task", ("device.launch",)),
    ("readback_ms_per_task", ("device.pull",)),
    ("finalize_encode_ms_per_task", ("device.finalize", "copr.encode")),
])
def test_stage_time_per_task(name, stages):
    before = snap(tasks=10, stages={s: (5, 1.0) for s in stages})
    # 100 tasks in the window; each stage ran 50 times (a batch's stage is
    # observed once for its two riders) for 2 s in all
    after = snap(tasks=110, stages={s: (55, 3.0) for s in stages})
    assert reader(name)(ctx(before, after)) == pytest.approx(
        len(stages) * 2.0 / 100 * 1e3)
    # the stages did not run in the window, or no task was served: nothing
    still = snap(tasks=110, stages={s: (5, 1.0) for s in stages})
    assert reader(name)(ctx(before, still)) is None
    assert reader(name)(ctx(after, after)) is None
    # a program without the series (the parent commit): nothing, no error
    assert reader(name)(ctx(snap(tasks=10), snap(tasks=110))) is None


def test_finalize_encode_adds_whichever_part_ran():
    before = snap(tasks=0, stages={"device.finalize": (0, 0.0)})
    after = snap(tasks=10, stages={"device.finalize": (5, 0.5),
                                   "copr.encode": (10, 0.1)})
    assert reader("finalize_encode_ms_per_task")(ctx(before, after)) == \
        pytest.approx(60.0)


def test_execute_unattributed_share():
    read = reader("execute_unattributed_share")
    before = snap(requests=(10.0, 9.0))
    after = snap(requests=(50.0, 45.0))       # 40 s of requests, 36 attributed
    assert read(ctx(before, after)) == pytest.approx(10.0)
    assert read(ctx(after, after)) is None    # no request finished
    assert read(ctx(snap(), snap())) is None  # no such series


def test_host_off_cpu_share_leaves_the_recorded_waits_out():
    read = reader("host_off_cpu_share")
    before = snap(stages={"cache.lock_check": (1, 1.0), "sched.wait": (1, 5.0)},
                  cpu={"cache.lock_check": 0.5})
    after = snap(stages={"cache.lock_check": (11, 5.0), "device.pull": (10, 4.0),
                         "sched.wait": (11, 50.0), "host.gc": (3, 1.0)},
                 cpu={"cache.lock_check": 1.5, "device.pull": 1.0})
    # wall 4 + 4 = 8 s, CPU 1 + 1 = 2 s: sched.wait and host.gc have no CPU
    # series and stay out
    assert read(ctx(before, after)) == pytest.approx(75.0)
    assert read(ctx(after, after)) is None
    assert read(ctx(snap(), snap())) is None


def test_gc_pause_per_task():
    read = reader("gc_pause_ms_per_task")
    before = snap(tasks=10, gc={"0": 0.5, "2": 1.0})
    after = snap(tasks=110, gc={"0": 0.7, "1": 0.1, "2": 1.2})
    assert read(ctx(before, after)) == pytest.approx(0.5 / 100 * 1e3)
    # the hook is in and the collector never ran: a reading of 0, not nothing
    assert read(ctx(snap(tasks=10, gc={}), snap(tasks=110, gc={}))) == 0.0
    assert read(ctx(snap(tasks=10), snap(tasks=110))) is None   # no hook
    assert read(ctx(after, after)) is None                      # no task


def test_every_new_metric_is_declared_and_found_by_name():
    bench = run.load_json(run.ROOT, "BENCHMARK.json")
    declared = {m["name"]: m for m in bench["per_layer"]}
    for name in NAMES:
        assert declared[name]["source"] == "program_counter"
        assert callable(reader(name))


def test_a_traced_rehearsal_reports_all_eight():
    import jax

    bench = run.load_json(run.ROOT, "BENCHMARK.json")
    cell = bench["workloads"][0]["name"]
    args = run.parse(["--workload", cell, "--seed", "2147483900",
                      "--seconds", "6", "--trace", "1"])
    d = jax.devices()
    device = {"platform": d[0].platform, "kind": d[0].device_kind, "count": len(d)}
    run.Run.peaks = lambda self: {"hbm_bytes_per_s": float("inf")}
    r = run.run_cell(args, device, bench, {
        "rehearsal": True, "config": {"rows_per_region": 4000},
        "traffic": {"warmup_seconds": 2, "max_warmups": 1, "trace_seconds": 2}})
    assert r["correct"] and r["failed"] == 0
    got = r["metrics"]
    assert set(NAMES) <= set(got), sorted(set(NAMES) - set(got))
    for name in NAMES:
        assert got[name]["value"] >= 0.0
    assert got["execute_unattributed_share"]["value"] < 50.0
    assert 0.0 <= got["host_off_cpu_share"]["value"] <= 100.0
