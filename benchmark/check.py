"""The comparison that decides ``correct``.

Every answer of the window is held to the plain reference: the plan's
``reference`` (numpy over the generated columns, nothing of the program) run
over the rows that were acknowledged before the query's ``start_ts``, which in
a mix that writes nothing are the loaded rows.  Served bytes are decoded with
the program's client-side codec, as any client reads them, and compared value
for value, decimals by unscaled digits and scale.  The comparison is exact:
each limit is 0.

The control is the reference put in the program's place with one stated
guarantee broken, "an acknowledged write is in the next snapshot's answer":
the last write transaction acknowledged in the region before the task's
``start_ts``, the load's last batch, is left out.  ``judge`` turns either
side's numbers into ``correct``.
"""

from __future__ import annotations

import base64
import importlib

import numpy as np

from . import table as tbl
from .reduce import tasks as all_tasks

LIMITS = {"wrong_answers": 0, "unanswered": 0}


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


def compare(log: dict, base: list[tbl.Table], load_batch_rows: int) -> dict:
    """Holds every task of the log to the reference over its region's loaded
    rows.  Returns the program's numbers, the control's, and the set of wrong
    ``(query index, region)``."""
    served: dict = {}     # (plan, params, digest) -> canonical rows
    wanted: dict = {}     # (plan, params, region, control) -> canonical rows
    wrong: set = set()
    wrong_detail: list = []
    unanswered = from_device = compared = control_wrong = 0
    for i, q, t in all_tasks(log):
        k = t["region"]
        if "digest" not in t:
            unanswered += 1
            wrong.add((i, k))
            continue
        mod = plan_module(q["plan"])
        params = dict(mod.DEFAULTS, **q["params"])
        pkey = (q["plan"], tuple(sorted(q["params"].items())))

        def want(control: bool):
            key = pkey + (k, control)
            if key not in wanted:
                rows = base[k]
                if control:
                    rows = rows.take(slice(0, len(rows) - load_batch_rows))
                wanted[key] = canonical(mod, mod.reference(rows, params))
            return wanted[key]

        skey = pkey + (t["digest"],)
        if skey not in served:
            try:
                served[skey] = decode_answer(mod, log["answers"][t["digest"]])
            except Exception as e:  # noqa: BLE001 - bytes no client can read are wrong
                served[skey] = ["undecodable", repr(e)]
        compared += 1
        from_device += bool(t.get("from_device"))
        if served[skey] != want(False):
            wrong.add((i, k))
            if len(wrong_detail) < 5:
                wrong_detail.append({
                    "plan": q["plan"], "params": q["params"], "region": k,
                    "start_ts": q["start_ts"], "from_device": t.get("from_device"),
                    "served": repr(served[skey])[:400],
                    "wanted": repr(want(False))[:400]})
        control_wrong += want(True) != want(False)
    numbers = {"compared": compared, "wrong_answers": len(wrong) - unanswered,
               "unanswered": unanswered, "device_answered": from_device}
    control = dict(numbers, wrong_answers=control_wrong)
    return {"numbers": numbers, "control": control, "wrong": wrong,
            "wrong_detail": wrong_detail}


def judge(numbers: dict) -> tuple[dict, bool]:
    """Each number compared beside its limit, and whether all hold."""
    compared = {k: {"value": numbers[k], "limit": lim} for k, lim in LIMITS.items()}
    compared["device_answered"] = {"value": numbers["device_answered"], "at_least": 1}
    correct = (all(numbers[k] <= lim for k, lim in LIMITS.items())
               and numbers["device_answered"] >= 1)
    return compared, bool(correct)
