"""From the client's log to numbers: the arithmetic behind every rate and
tail, kept here so that no later PR to the program can move it."""

from __future__ import annotations


def percentile(values, q: float) -> float:
    """Linear interpolation between closest ranks, ``q`` in [0, 100]."""
    v = sorted(values)
    if not v:
        raise ValueError("no samples")
    pos = (len(v) - 1) * q / 100.0
    lo = int(pos)
    hi = min(lo + 1, len(v) - 1)
    return v[lo] + (v[hi] - v[lo]) * (pos - lo)


def query_ms(log: dict) -> list[float]:
    """Issue to the last task's answer, every query of the window."""
    return [(q["done"] - q["issued"]) * 1e3 for q in log["queries"]]


def commit_ms(log: dict) -> list[float]:
    """``kv_prewrite`` sent to ``kv_commit`` acknowledged, every transaction."""
    return [(t["acked"] - t["sent"]) * 1e3 for t in log["txns"]]


def tasks(log: dict):
    """``(query index, query, task)`` of every task of the window."""
    for i, q in enumerate(log["queries"]):
        for t in q["tasks"]:
            yield i, q, t


def good_tasks(log: dict, wrong: set, start: float, end: float):
    """Tasks answered, and answered right, inside ``[start, end)`` seconds of
    the window."""
    for i, q, t in tasks(log):
        if ("digest" in t and (i, t["region"]) not in wrong
                and start <= t["done"] < end):
            yield q, t


def rows_per_s(log: dict, wrong: set, work: dict, seconds: float) -> float:
    """Rows that the right tasks completed inside the window had to read,
    over the whole window."""
    rows = sum(work[q["plan"]][0] for q, _t in good_tasks(log, wrong, 0.0, seconds))
    return rows / seconds


def least_bytes(log: dict, wrong: set, work: dict, start: float,
                end: float) -> tuple[int, int]:
    """(tasks, bytes the plans of those tasks have to read) for the tasks the
    device answered in ``[start, end)``."""
    n = nbytes = 0
    for q, t in good_tasks(log, wrong, start, end):
        if t.get("from_device"):
            n += 1
            nbytes += work[q["plan"]][1]
    return n, nbytes
