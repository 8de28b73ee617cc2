"""The trace reduction on a small recorded trace: 400 ms cut out of a v5e
trace of the first cell (PR 25's first chip run), names cut to 64 characters,
times moved so that the cut starts at 0."""

import json
import os

from benchmark import trace

DATA = os.path.join(os.path.dirname(__file__), "data", "trace_events.json")


def events():
    with open(DATA) as f:
        return json.load(f)


def test_busy_is_the_union_of_operation_intervals():
    r = trace.reduce(events(), 0.4)
    # by a sweep over interval ends, done by hand when the cut was made;
    # operations nest (a while holds its body), so their sum is larger
    assert abs(r["busy_s"] - 3722395e-9) < 1e-12
    ops = events()["/device:TPU:0"]["XLA Ops"]
    assert sum(d for _n, _s, d in ops) / 1e9 > r["busy_s"]
    assert r["window_s"] == 0.4 and r["devices"] == 1


def test_modules_are_told_apart_by_name():
    r = trace.reduce(events(), 0.4)
    assert r["module_runs"] == 9
    assert set(r["by_module"]) == {"jit_step", "jit__lambda", "jit_scan_fn",
                                   "jit_xregion_fn", "jit_agg_fn"}
    assert abs(r["by_module"]["jit_agg_fn"] - (727194 + 726763) / 1e9) < 1e-12
    assert abs(r["by_module"]["jit_xregion_fn"] - (156263 + 155443 + 243961) / 1e9) < 1e-12


def test_breakdown_names_operations_by_their_module():
    r = trace.reduce(events(), 0.4)
    assert len(r["device_ops"]) == trace.TOP
    top, seconds = r["device_ops"][0]
    assert top.startswith("jit_step/") and 0 < seconds < r["busy_s"]
    assert all("?" not in name.split("/")[0] for name, _s in r["device_ops"])
    assert len(r["idle_gaps"]) <= trace.TOP
    assert r["idle_gaps"][0][1] >= r["idle_gaps"][-1][1]
    # the longest gaps of this cut are the host between two dispatches
    assert sum(g for _n, g in r["idle_gaps"]) < 0.4 - r["busy_s"] + 1e-9


def test_no_device_plane_gives_nothing():
    e = {p: l for p, l in events().items() if not p.startswith("/device:")}
    assert trace.reduce(e, 0.4) is None
    assert trace.reduce({"/device:TPU:0": {"XLA Ops": []}}, 0.4) is None
