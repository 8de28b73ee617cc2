"""The control and the planted faults, at a size a CPU holds.

Each test skips the harness's look for a chip and drives the rest of a run
(``run.run_cell``: the same set-up, client process, window and comparison).
The control, the reference with "an acknowledged write is in the next
snapshot's answer" broken, goes through the same judgement as the program and
must come out as not correct; so must a store whose answers are altered where
they are produced, and one that leaves out half of the rows a task asked for."""

from benchmark import run

ROWS = 4000
BENCH = run.load_json(run.ROOT, "BENCHMARK.json")
CELL = BENCH["workloads"][0]["name"]
OVERRIDES = {"rehearsal": True, "config": {"rows_per_region": ROWS},
             "traffic": {"warmup_seconds": 2, "max_warmups": 1}}


def cell(seed, control=0, seconds=4):
    import jax

    args = run.parse(["--workload", CELL, "--seed", str(seed), "--seconds",
                      str(seconds), "--trace", "0", "--control", str(control)])
    d = jax.devices()
    device = {"platform": d[0].platform, "kind": d[0].device_kind, "count": len(d)}
    run.Run.peaks = lambda self: {"hbm_bytes_per_s": float("inf")}
    return run.run_cell(args, device, BENCH, OVERRIDES)


def test_sound_run_is_correct_and_the_control_is_not():
    r = cell(seed=2147483700, control=1)
    n = r["detail"]["numbers"]
    assert r["correct"] and n["wrong_answers"] == 0 and n["unanswered"] == 0
    assert r["failed"] == 0 and r["attempted"] == n["compared"] > 0
    assert list(r)[-1] == "compared"
    assert r["control_correct"] is False
    assert r["control"]["wrong_answers"]["value"] >= n["compared"] // 2


def test_an_answer_altered_where_it_is_produced(monkeypatch):
    from tikv_tpu.server.service import KvService

    sound = KvService._copr_resp_dict
    calls = {"n": 0}

    def altered(r, requested_chunk, declined):
        out = sound(r, requested_chunk, declined)
        calls["n"] += 1
        if calls["n"] % 7 == 0 and "data" in out:
            data = bytes(out["data"])
            out["data"] = data[:-1] + bytes([data[-1] ^ 1])
        return out

    monkeypatch.setattr(KvService, "_copr_resp_dict", staticmethod(altered))
    r = cell(seed=7)
    assert not r["correct"]
    assert r["detail"]["numbers"]["wrong_answers"] > 0
    assert r["failed"] >= r["detail"]["numbers"]["wrong_answers"]


def test_half_of_a_tasks_rows_left_out(monkeypatch):
    from tikv_tpu.copr.table import decode_record_key, record_key
    from tikv_tpu.server.service import KvService

    sound = KvService._parse_copr_request
    seen = {"n": 0}

    def halved(self, req):
        seen["n"] += 1
        if seen["n"] % 3 == 0:
            start, end = (bytes(k) for k in req["ranges"][0])
            table_id, first = decode_record_key(start)
            req = dict(req, ranges=[[start, record_key(table_id, first + ROWS // 2)]])
        return sound(self, req)

    monkeypatch.setattr(KvService, "_parse_copr_request", halved)
    r = cell(seed=8)
    assert seen["n"] > 3
    assert not r["correct"]
    assert r["detail"]["numbers"]["wrong_answers"] > 0
