"""``cache_stale_share`` and ``below_snapshot_served_share``: the readers on
made-up snapshots (counters that moved, that did not, a program without the
series), their declarations, the configuration that carries them held to the
one it copies, and a commit without the counter's slot refused at once."""

import pytest

from benchmark import run
from benchmark.layer_metrics import below_snapshot_served_share as served
from benchmark.layer_metrics import cache_stale_share as stale

CELL = "tpch-throughput.2x200k"


def labels(**kv):
    return tuple(sorted(kv.items()))


def snap(hit=0.0, stale_n=None, served_n=None, refused_n=None):
    """A counter snapshot as ``counters.snapshot`` gives it."""
    out = {stale.SERIES: {labels(outcome="hit"): hit}}
    if stale_n is not None:
        out[stale.SERIES][labels(outcome="stale")] = stale_n
    for outcome, n in (("served", served_n), ("refused", refused_n)):
        if n is not None:
            out.setdefault(served.SERIES, {})[labels(outcome=outcome)] = n
    return out


def ctx(before, after):
    return {"before": before, "after": after}


@pytest.mark.parametrize("before,after,share", [
    (snap(hit=10), snap(hit=110), 0.0),                        # no series yet
    (snap(hit=10, stale_n=2), snap(hit=108, stale_n=4), 2.0),  # 2 of 100
    (snap(hit=10, stale_n=2), snap(hit=10, stale_n=12), 100.0),
])
def test_stale_share_of_all_lookups(before, after, share):
    assert stale.read(ctx(before, after)) == pytest.approx(share)
    same = ctx(before, before)
    assert stale.read(same) is None and stale.read(ctx({}, {})) is None


@pytest.mark.parametrize("before,after,share", [
    (snap(served_n=3), snap(served_n=43), 100.0),
    (snap(served_n=3, refused_n=1), snap(served_n=33, refused_n=11), 75.0),
    (snap(), snap(refused_n=8), 0.0),
])
def test_share_of_readers_below_a_snapshot_that_were_served(before, after, share):
    assert served.read(ctx(before, after)) == pytest.approx(share)


def test_nothing_where_no_reader_came_below_a_snapshot():
    both = snap(hit=50, served_n=4, refused_n=1)
    assert served.read(ctx(both, both)) is None
    # a program without the series (the parent commit): nothing, no error
    assert served.read(ctx(snap(hit=1), snap(hit=99))) is None
    assert served.read(ctx({}, {})) is None


def test_declared_for_the_cell_and_found_by_name():
    bench = run.load_json(run.ROOT, "BENCHMARK.json")
    by_name = {m["name"]: m for m in bench["per_layer"]}
    common = {"unit": "%", "source": "program_counter",
              "layer": "region column cache", "workloads": [CELL]}
    assert by_name["cache_stale_share"] == dict(
        common, name="cache_stale_share", better="lower", moves="query_p95_ms")
    assert by_name["below_snapshot_served_share"] == dict(
        common, name="below_snapshot_served_share", better="higher",
        moves="scan_rows_per_s")
    got = {m["name"] for m in run.metrics_of(bench, "per_layer", CELL)}
    assert {"cache_stale_share", "below_snapshot_served_share",
            "cache_hit_share", "kernels_roofline"} <= got
    # each names only the other cell: read there, not here
    assert not {"sched_batch_occupancy", "lock_check_memo_share"} & got
    cell, cfg = run.find_cell(bench, CELL)
    assert (cell["chips"], cell["traffic"]) == (1, "tpch-throughput")
    assert run.load_json(run.HERE, "traffic", "tpch-throughput.json")["query_streams"] == 2


def test_the_configuration_is_the_other_one_plus_its_sessions():
    bench = run.load_json(run.ROOT, "BENCHMARK.json")
    files = {c["name"]: run.load_json(run.ROOT, c["file"]) for c in bench["configs"]}
    one, two = files["tpch-lineitem-2x200k"], files["tpch-lineitem-2x200k-s2"]
    for key in ("table_id", "regions", "rows_per_region", "columns", "key_bytes",
                "replicas", "stores", "chips", "load_batch_rows", "assumed",
                "row_bytes_mean"):
        assert two[key] == one[key], key
    assert two["sessions"] == 2
    # the same store, with the two defaults more that two streams cannot be
    # measured under (held_why); the one key that departs from nothing is in
    # no list of cuts
    more = {"scrubber.per_round": 0, "cost_router.enabled": False}
    assert two["held"] == dict(one["held"], **more,
                               **{"region_cache.stats.below_snapshot": 0})
    assert dict(two["guarantees"], sessions=None) == dict(one["guarantees"], sessions=None)
    reduced = {c["name"]: c["reduced"] for c in bench["configs"]}
    assert reduced["tpch-lineitem-2x200k-s2"] == (
        reduced["tpch-lineitem-2x200k"] + list(more))


def test_a_store_without_the_slot_is_refused_at_once(monkeypatch):
    """What the parent commit does under this configuration: ``assembly.py``
    finds no ``below_snapshot`` on the endpoint's cache and ends the run
    before split and load."""
    from benchmark.assembly import Deployment
    from tikv_tpu.copr import region_cache

    class Before(region_cache.RegionCacheStats):
        __slots__ = ()

        def __getattribute__(self, name):
            if name == "below_snapshot":
                raise AttributeError(name)
            return super().__getattribute__(name)

    monkeypatch.setattr(region_cache, "RegionCacheStats", Before)
    bench = run.load_json(run.ROOT, "BENCHMARK.json")
    _cell, cfg = run.find_cell(bench, CELL)
    dep = Deployment(run.load_json(run.ROOT, cfg["file"]), 1)
    try:
        with pytest.raises(RuntimeError, match="held: the endpoint has no "
                                               "region_cache.stats.below_snapshot"):
            dep.start()
    finally:
        dep.stop()
        dep.remove_files()
