"""The percentile and rate arithmetic on a synthetic client log with a stall
in it: the rate and the tail must move."""

from benchmark import reduce


def make_log(stall_at=None, stall_s=0.0, seconds=10.0, step=0.1):
    """One stream, one query every ``step`` seconds, two tasks each; at
    ``stall_at`` one query takes ``stall_s`` longer and the stream waits."""
    queries, t = [], 0.0
    while t < seconds:
        took = step + (stall_s if stall_at is not None and
                       stall_at <= t < stall_at + step else 0.0)
        queries.append({
            "plan": "whole" if len(queries) % 2 else "limited", "params": {},
            "start_ts": len(queries), "issued": t, "done": t + took,
            "tasks": [{"region": k, "digest": "d", "from_device": True,
                       "done": t + took} for k in range(2)]})
        t += took
    return {"queries": queries}


WORK = {"whole": (1000, 8000), "limited": (10, 80)}


def test_percentile_interpolates():
    assert reduce.percentile([1, 2, 3, 4], 50) == 2.5
    assert reduce.percentile([5], 95) == 5
    assert reduce.percentile(list(range(101)), 95) == 95


def test_rate_counts_only_right_tasks_inside_the_window():
    log = make_log()
    n = len(log["queries"])
    full = reduce.rows_per_s(log, set(), WORK, 10.0)
    assert abs(full - (n // 2) * 2 * 1010 / 10.0) < 1010 * 2 / 10.0 + 1e-9
    wrong = {(1, 0)}                      # one task of a "whole" query
    assert reduce.rows_per_s(log, wrong, WORK, 10.0) == full - 1000 / 10.0
    # a task answered after the close is late, not in the rate
    log["queries"][5]["tasks"][0]["done"] = 10.5
    late = reduce.rows_per_s(log, set(), WORK, 10.0)
    assert late < full


def test_a_stall_moves_the_rate_and_the_tail():
    calm, stalled = make_log(), make_log(stall_at=5.0, stall_s=2.0)
    assert (reduce.rows_per_s(stalled, set(), WORK, 10.0)
            < 0.85 * reduce.rows_per_s(calm, set(), WORK, 10.0))
    assert max(reduce.query_ms(stalled)) > 2000
    # one stalled query in ~80 moves the maximum and the mean, and the p95
    # only when there are enough of them: the tail is of ALL queries
    many = make_log(stall_at=5.0, stall_s=0.5, step=0.5)
    many["queries"] += make_log(stall_at=2.0, stall_s=0.5, step=0.5)["queries"]
    assert reduce.percentile(reduce.query_ms(many), 95) > \
        reduce.percentile(reduce.query_ms(make_log(step=0.5)), 95)


def test_least_bytes_counts_device_answers_in_the_span():
    log = make_log()
    log["queries"][0]["tasks"][0]["from_device"] = False
    n, b = reduce.least_bytes(log, set(), WORK, 0.0, 1.0)
    in_span = [q for q in log["queries"] if q["done"] < 1.0]
    assert n == 2 * len(in_span) - 1
    assert b == sum(WORK[q["plan"]][1] * 2 for q in in_span) - WORK["limited"][1]
