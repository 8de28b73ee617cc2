"""The traffic generator's draws: the same seed gives the same stream, and
substitution parameters stay inside the mix's ranges."""

from benchmark import client, run

MIX = run.load_json(run.HERE, "traffic",
                    run.load_json(run.ROOT, "BENCHMARK.json")["workloads"][0]["traffic"] + ".json")


def traffic(seed, **mix):
    return client.Traffic({
        "traffic": dict(MIX, **mix), "seed": seed, "seconds": 1.0, "table_id": 101,
        "region_ids": [1, 2], "ranges": [["00", "01"], ["01", "02"]]})


def test_a_stream_starts_the_same_for_the_same_seed():
    _r, order, kept = traffic(2**31 + 11).stream_start(0)
    _r, order2, kept2 = traffic(2**31 + 11).stream_start(0)
    assert (order, kept) == (order2, kept2)
    assert sorted(order) == sorted(MIX["plans"])
    seen = {repr(traffic(s).stream_start(0)[2]) for s in range(20)}
    assert len(seen) > 10                      # other seeds, other literals
    for plan, ranges in MIX["params"].items():
        for name, (lo, hi) in ranges.items():
            assert lo <= kept[plan][name] <= hi


def test_every_plan_builds_its_request_for_every_draw():
    t = traffic(5)
    rng, _order, kept = t.stream_start(0)
    for plan in MIX["plans"]:
        assert t.wire_dag(plan, kept[plan]) is t.wire_dag(plan, kept[plan])
        # drawn anew for a query ("substitute": "query"), still inside the ranges
        again = t.draw(rng, plan)
        assert set(again) == set(kept[plan])
        assert t.wire_dag(plan, again)
