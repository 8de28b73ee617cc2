"""``write_batch_read_share`` and ``txn_actions_us_per_key``: the readers on
made-up snapshots (totals since the store started; a program without the
counters), their declarations, and the counters a real prewrite and commit
leave, read through the registry's own text as a run reads them."""

import pytest

from benchmark import counters, run
from benchmark.layer_metrics import txn_actions_us_per_key, write_batch_read_share

NAMES = ("write_batch_read_share", "txn_actions_us_per_key")


def labels(**kv):
    return tuple(sorted(kv.items()))


def snap(batch=None, walk=None, seconds=None, keys=None):
    """A counter snapshot as ``counters.snapshot`` gives it; each argument
    ``{cmd: number}``."""
    out = {"tikv_coprocessor_region_cache_total": {labels(outcome="hit"): 50.0}}
    for how, by_cmd in (("batch", batch), ("walk", walk)):
        for cmd, n in (by_cmd or {}).items():
            out.setdefault(write_batch_read_share.SERIES, {})[labels(cmd=cmd, how=how)] = n
    for series, by_cmd in ((txn_actions_us_per_key.SECONDS, seconds),
                           (txn_actions_us_per_key.KEYS, keys)):
        for cmd, n in (by_cmd or {}).items():
            out.setdefault(series, {})[labels(cmd=cmd)] = n
    return out


def ctx(after):
    # both are totals since the store started: `before` is never read
    return {"before": {}, "after": after}


@pytest.mark.parametrize("batch,walk,share", [
    ({"prewrite": 400000, "commit": 400000}, {"prewrite": 0, "commit": 0}, 100.0),
    ({"prewrite": 300, "commit": 100}, {"prewrite": 100}, 80.0),
    ({"commit": 0}, {"prewrite": 8}, 0.0),
])
def test_share_of_keys_the_batch_read(batch, walk, share):
    assert write_batch_read_share.read(ctx(snap(batch=batch, walk=walk))) == pytest.approx(share)


@pytest.mark.parametrize("seconds,keys,us", [
    ({"prewrite": 12.0, "commit": 8.0}, {"prewrite": 400000, "commit": 400000}, 25.0),
    ({"prewrite": 0.5}, {"prewrite": 10000}, 50.0),
])
def test_microseconds_a_key(seconds, keys, us):
    assert txn_actions_us_per_key.read(ctx(snap(seconds=seconds, keys=keys))) == pytest.approx(us)


@pytest.mark.parametrize("name", NAMES)
def test_nothing_without_the_series(name):
    read = {"write_batch_read_share": write_batch_read_share.read,
            "txn_actions_us_per_key": txn_actions_us_per_key.read}[name]
    # a program without the counters (the parent commit): nothing, no error
    assert read(ctx(snap())) is None
    assert read(ctx({})) is None
    # registered, and nothing loaded yet
    assert read(ctx(snap(batch={"prewrite": 0}, walk={"prewrite": 0},
                         seconds={"prewrite": 0.0}, keys={"prewrite": 0}))) is None


@pytest.mark.parametrize("name", NAMES)
def test_declared_and_found_by_name(name):
    bench = run.load_json(run.ROOT, "BENCHMARK.json")
    (m,) = [m for m in bench["per_layer"] if m["name"] == name]
    assert m == {"name": name, "unit": "%" if name.endswith("share") else "us",
                 "better": "higher" if name.endswith("share") else "lower",
                 "source": "program_counter", "layer": "write path", "moves": "setup_s"}
    # no `workloads` key: every cell loads its rows in set-up and reports setup_s
    for cell in bench["workloads"]:
        assert m in run.metrics_of(bench, "per_layer", cell["name"])


def test_read_off_a_real_prewrite_and_commit():
    from tikv_tpu.storage.storage import Storage
    from tikv_tpu.storage.txn.commands import Commit, Prewrite
    from tikv_tpu.storage.txn_types import Key, Mutation

    store = Storage()
    ks = [Key.from_raw(b"bench-row%04d" % i) for i in range(100)]
    store.sched_txn_command(Prewrite([Mutation.put(k, b"v") for k in ks], ks[0].to_raw(), 5))
    store.sched_txn_command(Commit(ks, 5, 6))
    after = counters.snapshot()
    assert write_batch_read_share.read(ctx(after)) > 0
    assert txn_actions_us_per_key.read(ctx(after)) > 0
