"""``fill_vector_decode_share``: the reader on made-up snapshots (totals since
the store started, not what moved in the window; a program without the
counter), its declaration, and one whole run at a size a CPU holds whose
traced result holds it."""

import pytest

from benchmark import run
from benchmark.layer_metrics.fill_vector_decode_share import SERIES, read

NAME = "fill_vector_decode_share"


def labels(**kv):
    return tuple(sorted(kv.items()))


def snap(**paths):
    """A counter snapshot as ``counters.snapshot`` gives it."""
    out = {"tikv_coprocessor_region_cache_total": {labels(outcome="hit"): 50.0}}
    for path, n in paths.items():
        out.setdefault(SERIES, {})[labels(path=path)] = float(n)
    return out


def ctx(before, after):
    return {"before": before, "after": after}


@pytest.mark.parametrize("before,after,share", [
    # the fills lie before the window's first snapshot: nothing moved, all counts
    (snap(vector=800000), snap(vector=800000), 100.0),
    (snap(vector=792000, walk=8000), snap(vector=792000, walk=8000), 99.0),
    (snap(), snap(uniform=300, walk=100), 0.0),
    (snap(vector=10), snap(vector=30, uniform=10), 75.0),
])
def test_share_of_rows_the_arrays_decoded(before, after, share):
    assert read(ctx(before, after)) == pytest.approx(share)


def test_nothing_without_the_series():
    # a program without the counter (the parent commit): nothing, no error
    assert read(ctx(snap(), snap())) is None
    assert read(ctx({}, {})) is None
    # registered, and nothing decoded yet
    assert read(ctx(snap(), snap(vector=0))) is None


def test_declared_and_found_by_name():
    bench = run.load_json(run.ROOT, "BENCHMARK.json")
    (m,) = [m for m in bench["per_layer"] if m["name"] == NAME]
    assert m == {"name": NAME, "unit": "%", "better": "higher",
                 "source": "program_counter", "layer": "cold fill",
                 "moves": "setup_s"}
    # no `workloads` key: every cell reports setup_s, so every cell reports it
    for cell in bench["workloads"]:
        assert m in run.metrics_of(bench, "per_layer", cell["name"])


def test_a_traced_rehearsal_reports_it():
    import jax

    bench = run.load_json(run.ROOT, "BENCHMARK.json")
    cell = bench["workloads"][0]["name"]
    args = run.parse(["--workload", cell, "--seed", "2147483907",
                      "--seconds", "6", "--trace", "1"])
    d = jax.devices()
    device = {"platform": d[0].platform, "kind": d[0].device_kind, "count": len(d)}
    run.Run.peaks = lambda self: {"hbm_bytes_per_s": float("inf")}
    r = run.run_cell(args, device, bench, {
        "rehearsal": True, "config": {"rows_per_region": 4000},
        "traffic": {"warmup_seconds": 2, "max_warmups": 1, "trace_seconds": 2}})
    assert r["correct"] and r["failed"] == 0
    # LINEITEM's rows are of mixed length: every block of every image, and of
    # every scrub of one, goes through the arrays
    assert r["metrics"][NAME]["value"] == pytest.approx(100.0)
    assert r["metrics"]["fill_rows_per_s"]["value"] > 0
