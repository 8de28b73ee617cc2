"""``chip_smoke.py`` rehearsed on the CPU (ISSUE 22 satellites).

The script itself refuses anything but a TPU; its phases do not care, so the
tier-1 rehearsal runs them in this process at a tiny size: the one-process
assembly (PD service, durable device store, socket client), the load through
``kv_prewrite``/``kv_commit``, every plan cold and warm against the CPU
pipeline's bytes, the write read back, the verdict.  On this suite's eight
virtual CPU devices the store builds a mesh by itself, so the aggregation
plans also ride the sharded warm path.
"""

import json
import os
import subprocess
import sys

import pytest

import chip_smoke

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _phases(out: str) -> list[dict]:
    return [json.loads(line) for line in out.splitlines()
            if line.startswith('{"phase"')]


def test_phases_byte_identical_on_cpu(capsys):
    chip_smoke.run_phases(chip_smoke.Smoke(seed=0, regions=2, rows_per_region=2000))
    lines = _phases(capsys.readouterr().out)
    by_phase: dict = {}
    for line in lines:
        by_phase.setdefault(line["phase"], []).append(line)
    assert by_phase["start"][0]["engines"] == {
        "kv": "NativeEngine", "raft_log": "NativeRaftLog"}
    load = by_phase["load"][0]
    assert (load["rows"], load["regions"], load["cut"]) == (4000, 2, None)
    assert by_phase["read_back"][0]["equal_to_written"]
    served = {s["plan"]: s for s in by_phase["serve"] if "skipped" not in s}
    assert set(served) == set(chip_smoke.plan_set())
    for name, s in served.items():
        assert s["byte_identical"], name
        for pss in chip_smoke.PASSES:
            assert s[pss]["from_device"] == 2, (name, pss)
        last = f"warm{s['warm_passes']}"
        assert set(s[last]["cache"]) == {"hit"}, (name, s[last])
        assert s[last]["ledger_compiles"] == 0, (name, s[last])
    wtr = by_phase["write_then_read"][0]
    assert wtr["byte_identical"] and wtr["answer_changed"] and wtr["from_device"]
    assert set(wtr["cache"]) <= {"delta", "wt_delta"} and wtr["cache"]
    verdict = by_phase["verdict"][0]
    assert verdict["device_fallbacks"] == 0
    assert verdict["last_device_error"] is None
    assert set(verdict["breakers"].values()) == {"closed"}


def test_four_chip_path_on_virtual_devices(capsys, monkeypatch):
    """``--chips 4``'s phase over a four-device mesh: mesh hits equal warm
    requests, every device holds image bytes, the documented decline
    (integer group columns) stays on one device."""
    import jax

    from tikv_tpu.parallel.mesh import make_mesh
    from tikv_tpu.server import standalone

    # this suite has eight virtual devices; the host in question has four
    monkeypatch.setattr(standalone, "_default_mesh",
                        lambda devices: make_mesh(devices[:4], groups=2))
    chip_smoke.run_phases(
        chip_smoke.Smoke(seed=0, regions=4, rows_per_region=1500), chips=4)
    lines = _phases(capsys.readouterr().out)
    plans = {line["plan"]: line for line in lines if line["phase"] == "four_chip"}
    assert set(plans) == set(chip_smoke.AGG_PLANS)
    for name, line in plans.items():
        if name in chip_smoke.MESH_DECLINES:
            assert "mesh" not in line["warm_each"]["rung"]
        else:
            assert line["warm_each"]["mesh_cache_hit"] == 4
            assert line["warm_batch"]["rung"] == {"mesh": 4}
    verdict = [line for line in lines if line["phase"] == "four_chip_verdict"][0]
    assert len(verdict["image_bytes_per_device"]) == 4
    assert all(verdict["image_bytes_per_device"].values())
    assert verdict["device_fallbacks"] == 0
    assert len(jax.devices()) == 8  # the rest of the suite's mesh is untouched


def test_script_refuses_anything_but_a_tpu(tmp_path):
    """``JAX_PLATFORMS=cpu python chip_smoke.py`` exits non-zero, says what
    it found, and never prints a result line."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path))
    env.pop("XLA_FLAGS", None)
    r = subprocess.run([sys.executable, os.path.join(_ROOT, "chip_smoke.py")],
                       env=env, capture_output=True, text=True, timeout=120)
    assert r.returncode != 0
    assert '"ok"' not in r.stdout
    assert "needs 1 TPU device(s)" in r.stderr
    assert "'platform': 'cpu'" in r.stderr


@pytest.mark.parametrize("seconds,want", [
    (240, (4, 600_000)),   # everything fits
    (200, (3, 600_000)),   # regions go first
    (150, (2, 600_000)),
    (90, (2, 450_000)),    # never below two: then rows, evenly
])
def test_a_load_budget_cuts_regions_first_then_rows(seconds, want):
    """10,000 rows a batch, a second each: what the order has put in when
    the budget runs out settles to whole regions first."""
    loaded = [0, 0, 0, 0]
    for t, (k, _s, e) in enumerate(chip_smoke.load_order(4, 600_000, 10_000)):
        if t >= seconds:
            break
        loaded[k] = e
    assert chip_smoke.settle(loaded, 600_000) == want
    assert sum(loaded) == seconds * 10_000
