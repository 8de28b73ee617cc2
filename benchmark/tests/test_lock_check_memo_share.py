"""``lock_check_memo_share``: the reader on made-up snapshots (a counter that
moved, one that did not, a program without it), its declaration, and one whole
run at a size a CPU holds whose traced result holds it."""

import pytest

from benchmark import run
from benchmark.layer_metrics.lock_check_memo_share import SERIES, read

NAME = "lock_check_memo_share"


def labels(**kv):
    return tuple(sorted(kv.items()))


def snap(memo=None, scan=None):
    """A counter snapshot as ``counters.snapshot`` gives it."""
    out = {"tikv_coprocessor_region_cache_total": {labels(outcome="hit"): 50.0}}
    for how, n in (("memo", memo), ("scan", scan)):
        if n is not None:
            out.setdefault(SERIES, {})[labels(how=how)] = n
    return out


def ctx(before, after):
    return {"before": before, "after": after}


@pytest.mark.parametrize("before,after,share", [
    (snap(memo=4, scan=6), snap(memo=103, scan=7), 99.0),   # 99 of 100
    (snap(scan=6), snap(memo=30, scan=6), 100.0),           # the window wrote nothing
    (snap(memo=4, scan=6), snap(memo=4, scan=26), 0.0),     # every check scanned
    (snap(), snap(scan=8), 0.0),                            # first series of the run
])
def test_share_of_checks_the_memo_answered(before, after, share):
    assert read(ctx(before, after)) == pytest.approx(share)


def test_nothing_where_the_counter_did_not_move():
    both = snap(memo=4, scan=6)
    assert read(ctx(both, both)) is None
    # a program without the series (the parent commit): nothing, no error
    assert read(ctx(snap(), snap())) is None
    assert read(ctx({}, {})) is None


def test_declared_and_found_by_name():
    bench = run.load_json(run.ROOT, "BENCHMARK.json")
    (m,) = [m for m in bench["per_layer"] if m["name"] == NAME]
    assert m == {"name": NAME, "unit": "%", "better": "higher",
                 "source": "program_counter", "layer": "region column cache",
                 "moves": "scan_rows_per_s", "workloads": ["tpch-power.2x200k"]}


def test_a_traced_rehearsal_reports_it():
    import jax

    bench = run.load_json(run.ROOT, "BENCHMARK.json")
    cell = bench["workloads"][0]["name"]
    args = run.parse(["--workload", cell, "--seed", "2147483901",
                      "--seconds", "6", "--trace", "1"])
    d = jax.devices()
    device = {"platform": d[0].platform, "kind": d[0].device_kind, "count": len(d)}
    run.Run.peaks = lambda self: {"hbm_bytes_per_s": float("inf")}
    r = run.run_cell(args, device, bench, {
        "rehearsal": True, "config": {"rows_per_region": 4000},
        "traffic": {"warmup_seconds": 2, "max_warmups": 1, "trace_seconds": 2}})
    assert r["correct"] and r["failed"] == 0
    got = r["metrics"]
    # the window writes nothing and every image was scanned once in set-up
    assert got[NAME]["value"] == pytest.approx(100.0)
    assert got["lock_check_ms_per_task"]["value"] >= 0.0  # the stage still runs
