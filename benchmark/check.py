"""The comparison that decides ``correct``.

Every answer of the window is held to the plain reference: the plan's
``reference`` (numpy over the generated columns, nothing of the program) run
over the rows committed below the task's own ``start_ts``: the region's loaded
rows, plus the rows of every RF1 transaction, less those of every RF2
transaction, acknowledged with a ``commit_ts`` below it.  In a mix that writes
nothing that is the loaded rows.  Served bytes are decoded with the program's
client-side codec, as any client reads them, and compared value for value,
decimals by unscaled digits and scale.  The comparison is exact: each limit is
0.  Where the mix writes, every key a transaction acknowledged is also read back
after the window (``assembly.Deployment.read_back``): ``acked_rows_missing``.

The control is the reference put in the program's place with one stated
guarantee broken, "an acknowledged write is in the next snapshot's answer":
the newest write transaction acknowledged in the region below the task's
``start_ts`` is left out (a refresh transaction, or where there is none the
load's last batch).  ``judge`` turns either side's numbers into ``correct``.
"""

from __future__ import annotations

import base64
import bisect
import importlib
import os
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from . import table as tbl
from .reduce import tasks as all_tasks

REFERENCE_THREADS = 8
LIMITS = {"wrong_answers": 0, "unanswered": 0}
# where the mix writes: every acknowledged key read back after the window
WRITE_LIMITS = {"acked_rows_missing": 0}


def plan_module(name: str):
    return importlib.import_module(f"benchmark.plans.{name}")


def canonical(mod, rows) -> list:
    out = [tuple(_value(v) for v in row) for row in rows]
    return sorted(out, key=repr) if getattr(mod, "UNORDERED", False) else out


def _value(v):
    if isinstance(v, tuple) and len(v) == 2:
        return ("dec", int(v[0]), int(v[1]))
    if isinstance(v, (bytes, bytearray, memoryview)):
        return bytes(v)
    if isinstance(v, (int, np.integer)):
        return int(v)
    return v


def decode_answer(mod, answer: dict) -> list:
    """The rows a client reads out of one served answer."""
    from tikv_tpu.copr.dag import SelectResponse

    enc = int(answer.get("encode_type") or 0)
    sr = SelectResponse.decode(base64.b64decode(answer["data"]), encode_type=enc)
    return canonical(mod, sr.iter_rows())


class History:
    """What a region holds at a timestamp: its loaded rows and the refresh
    transactions acknowledged below it.  State ``s`` of region ``k`` is the
    loaded rows with the region's first ``s`` transactions in commit order
    applied; state ``-1`` is the loaded rows less the load's last batch."""

    def __init__(self, base: list[tbl.Table], load_batch_rows: int,
                 txns=(), refresh=None):
        self.base = base
        self.load_batch_rows = load_batch_rows
        self.txns: dict[int, list[dict]] = {k: [] for k in range(len(base))}
        for t in sorted(txns, key=lambda x: x["commit_ts"]):
            self.txns[int(t["region"])].append(t)
        self.commit_ts = {k: [t["commit_ts"] for t in v] for k, v in self.txns.items()}
        # per region that was written: every row it ever held, in handle
        # order, with the state that inserted it and the one that deleted it
        self.lives: dict[int, tuple] = {}
        for k, done in self.txns.items():
            if done:
                self.lives[k] = self._lives(base[k], done, refresh)

    @staticmethod
    def _lives(b: tbl.Table, done: list[dict], refresh) -> tuple:
        added = [(s, refresh.rows(t)) for s, t in enumerate(done, 1)
                 if t["function"] == "RF1"]
        rows = tbl.Table.concat([b, *(r for _s, r in added)])
        born = np.concatenate([np.zeros(len(b), dtype=np.int64),
                               *(np.full(len(r), s) for s, r in added)])
        order = np.argsort(rows.handle, kind="stable")
        rows, born = rows.take(order), born[order]
        died = np.full(len(rows), len(done) + 1, dtype=np.int64)
        for s, t in enumerate(done, 1):
            if t["function"] == "RF2":
                h = np.asarray(t["handles"], dtype=np.int64)
                at = np.searchsorted(rows.handle, h)
                if (at >= len(rows)).any() or (rows.handle[np.minimum(at, len(rows) - 1)] != h).any():
                    raise RuntimeError("an RF2 transaction deleted a row no state held")
                died[at] = np.minimum(died[at], s)
        return rows, born, died

    def state(self, k: int, start_ts: int) -> int:
        return bisect.bisect_left(self.commit_ts[k], start_ts)

    def rows(self, k: int, s: int) -> tbl.Table:
        b = self.base[k]
        if s < 0:
            return b.take(slice(0, len(b) - self.load_batch_rows))
        if k not in self.lives:
            return b
        rows, born, died = self.lives[k]
        keep = np.flatnonzero((born <= s) & (died > s))
        if len(keep) and keep[-1] - keep[0] + 1 == len(keep):
            return rows.take(slice(keep[0], keep[-1] + 1))
        return rows.take(keep)

    def which_state(self, mod, q: dict, k: int, s: int, served, reach: int = 4):
        """The nearest state of region ``k`` whose reference equals an
        answer that was wrong at state ``s`` (above ``s``: an answer from a
        newer snapshot), or ``None``."""
        params = dict(mod.DEFAULTS, **q["params"])
        for d in sorted(range(-reach, reach + 1), key=abs):
            if d and 0 <= s + d <= len(self.txns[k]):
                if canonical(mod, mod.reference(self.rows(k, s + d), params)) == served:
                    return s + d
        return None


def compare(log: dict, base: list[tbl.Table], load_batch_rows: int,
            txns=(), refresh=None) -> dict:
    """Holds every task of the log to the reference over its region's rows
    at its ``start_ts`` (``txns``: every refresh transaction the run
    acknowledged, warm-ups' too; ``refresh`` rebuilds what RF1 inserted).
    Returns the program's numbers, the control's, and the set of wrong
    ``(query index, region)``."""
    hist = History(base, load_batch_rows, txns, refresh)
    served: dict = {}     # (plan, params, digest) -> canonical rows
    jobs = []             # (query index, query, task, params key, state, control state)
    wrong: set = set()
    wrong_detail: list = []
    unanswered = from_device = compared = control_wrong = 0
    for i, q, t in all_tasks(log):
        k = t["region"]
        if "digest" not in t:
            unanswered += 1
            wrong.add((i, k))
            continue
        s = hist.state(k, q["start_ts"])
        jobs.append((i, q, t, (q["plan"], tuple(sorted(q["params"].items()))),
                     s, s - 1 if s > 0 else -1))
    # each state of a region is built once, for every plan that reads it
    need: dict = {}
    for _i, q, t, pkey, s, c in jobs:
        for state in (s, c):
            need.setdefault((t["region"], state), {})[pkey] = q

    def reference(item):
        (k, state), plans = item
        rows = hist.rows(k, state)
        out = {}
        for pkey, q in plans.items():
            mod = plan_module(q["plan"])
            out[pkey + (k, state)] = canonical(
                mod, mod.reference(rows, dict(mod.DEFAULTS, **q["params"])))
        return out

    wanted: dict = {}     # (plan, params, region, state) -> canonical rows
    # numpy lets go of the interpreter: the states in parallel, once the
    # store has stopped
    with ThreadPoolExecutor(min(REFERENCE_THREADS, os.cpu_count() or 1)) as pool:
        for out in pool.map(reference, sorted(need.items())):
            wanted.update(out)
    for i, q, t, pkey, s, c in jobs:
        k = t["region"]
        mod = plan_module(q["plan"])
        skey = pkey + (t["digest"],)
        if skey not in served:
            try:
                served[skey] = decode_answer(mod, log["answers"][t["digest"]])
            except Exception as e:  # noqa: BLE001 - bytes no client can read are wrong
                served[skey] = ["undecodable", repr(e)]
        compared += 1
        from_device += bool(t.get("from_device"))
        want = wanted[pkey + (k, s)]
        if served[skey] != want:
            wrong.add((i, k))
            if len(wrong_detail) < 5:
                wrong_detail.append({
                    "plan": q["plan"], "params": q["params"], "region": k,
                    "start_ts": q["start_ts"], "from_device": t.get("from_device"),
                    "served": repr(served[skey])[:400],
                    "wanted": repr(want)[:400]})
                if hist.txns[k]:
                    wrong_detail[-1].update(
                        state=s, of_states=len(hist.txns[k]),
                        served_is_state=hist.which_state(mod, q, k, s, served[skey]))
        control_wrong += wanted[pkey + (k, c)] != want
    numbers = {"compared": compared, "wrong_answers": len(wrong) - unanswered,
               "unanswered": unanswered, "device_answered": from_device}
    control = dict(numbers, wrong_answers=control_wrong)
    return {"numbers": numbers, "control": control, "wrong": wrong,
            "wrong_detail": wrong_detail,
            "states": len(need)}


def judge(numbers: dict, extra_limits: dict | None = None) -> tuple[dict, bool]:
    """Each number compared beside its limit (``LIMITS`` and
    ``extra_limits``), and whether all hold."""
    limits = dict(LIMITS, **(extra_limits or {}))
    compared = {k: {"value": numbers[k], "limit": lim} for k, lim in limits.items()}
    compared["device_answered"] = {"value": numbers["device_answered"], "at_least": 1}
    correct = (all(numbers[k] <= lim for k, lim in limits.items())
               and numbers["device_answered"] >= 1)
    return compared, bool(correct)
