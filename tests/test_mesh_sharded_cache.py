"""Mesh-sharded warm serving (ISSUE 3): the region column cache spread over
a simulated 8-device CPU mesh must serve cross-region batches as ONE
shard_map program, byte-identical to the single-device scheduler path and
the per-request CPU pipeline — through uneven region→device assignment,
fewer regions than devices, block-spread huge regions, and mid-batch
eviction of a sharded image."""

import numpy as np
import pytest

import jax

from tikv_tpu.copr.aggr import AggDescriptor
from tikv_tpu.copr.dag import Aggregation, DagRequest, Selection, TableScan
from tikv_tpu.copr.datatypes import ColumnInfo, FieldType
from tikv_tpu.copr.endpoint import CoprRequest, Endpoint
from tikv_tpu.copr.region_cache import RegionColumnCache, notify_region_epoch_change
from tikv_tpu.copr.rpn import call, col, const_int
from tikv_tpu.copr.table import encode_row, record_key
from tikv_tpu.parallel.mesh import make_mesh
from tikv_tpu.storage.btree_engine import BTreeEngine
from tikv_tpu.storage.engine import CF_WRITE
from tikv_tpu.storage.kv import LocalEngine
from tikv_tpu.storage.txn_types import Key, Write, WriteType
from tikv_tpu.util.metrics import REGISTRY

TABLE_ID = 88

COLS = [
    ColumnInfo(1, FieldType.int64(), is_pk_handle=True),
    ColumnInfo(2, FieldType.int64()),
    ColumnInfo(3, FieldType.varchar()),
    ColumnInfo(4, FieldType.decimal_type(2)),
]

ROWS_PER = 500


def _engine(n: int, seed: int = 3) -> BTreeEngine:
    rng = np.random.default_rng(seed)
    a = rng.integers(0, 100, n)
    price = rng.integers(100, 100000, n)
    names = (b"x", b"y", b"z")
    eng = BTreeEngine()
    items = []
    for i in range(n):
        rk = record_key(TABLE_ID, i)
        val = encode_row(COLS[1:], [int(a[i]), names[i % 3], int(price[i])])
        items.append((Key.from_raw(rk).append_ts(20).encoded,
                      Write(WriteType.PUT, 10, short_value=val).to_bytes()))
    eng.bulk_load(CF_WRITE, items)
    return eng


def _sum_dag(cut: int) -> DagRequest:
    return DagRequest(executors=[
        TableScan(TABLE_ID, COLS),
        Selection([call("lt", col(1), const_int(cut))]),
        Aggregation([], [AggDescriptor("sum", col(3)),
                         AggDescriptor("count", None),
                         AggDescriptor("max", col(1))]),
    ])


def _group_dag() -> DagRequest:
    return DagRequest(executors=[
        TableScan(TABLE_ID, COLS),
        Aggregation([col(2)], [AggDescriptor("sum", col(1)),
                               AggDescriptor("count", None)]),
    ])


def _req(region: int, dag: DagRequest, rows_per: int = ROWS_PER,
         apply_index: int = 7) -> CoprRequest:
    lo = record_key(TABLE_ID, region * rows_per)
    hi = record_key(TABLE_ID, (region + 1) * rows_per)
    return CoprRequest(103, dag, [(lo, hi)], 100,
                       context={"region_id": region + 1,
                                "region_epoch": (1, 1),
                                "apply_index": apply_index})


N_REGIONS = 5  # deliberately fewer than the 8 conftest devices AND not a divisor


@pytest.fixture(scope="module")
def endpoints():
    assert len(jax.devices()) == 8, "conftest must provide 8 virtual devices"
    eng = _engine(ROWS_PER * max(N_REGIONS, 10))
    mesh = make_mesh(groups=2)
    sharded = Endpoint(LocalEngine(eng), enable_device=True, block_rows=256,
                       mesh=mesh)
    single = Endpoint(LocalEngine(eng), enable_device=True, block_rows=256)
    cpu = Endpoint(LocalEngine(eng), enable_device=False)
    return sharded, single, cpu


def _sweep(dags, n_regions=N_REGIONS):
    return [_req(r, d()) for d in dags for r in range(n_regions)]


def test_sharded_batch_byte_identical_uneven_assignment(endpoints):
    """5 regions over 8 devices (uneven, region count < device count): the
    batch runs the SHARDED program and responses are byte-identical to both
    the single-device scheduler path and the per-request CPU pipeline."""
    sharded, single, cpu = endpoints
    dags = [lambda: _sum_dag(60), lambda: _sum_dag(90), _group_dag]
    sharded.handle_batch(_sweep(dags))  # warm: fill + compile
    single.handle_batch(_sweep(dags))
    before = REGISTRY.counter(
        "tikv_coprocessor_sched_batches_total", "").get(kind="xregion_sharded")
    got = sharded.handle_batch(_sweep(dags))
    after = REGISTRY.counter(
        "tikv_coprocessor_sched_batches_total", "").get(kind="xregion_sharded")
    assert after >= before + 3, "one sharded batch per plan signature"
    ref = single.handle_batch(_sweep(dags))
    assert all(g.from_device for g in got)
    for q, g, s in zip(_sweep(dags), got, ref):
        want = cpu.handle_request(
            CoprRequest(103, q.dag, q.ranges, q.start_ts, dict(q.context)))
        assert g.data == s.data == want.data
    # placement metadata: images actually spread over more than one device
    used = [b for b in sharded.region_cache.placement().values() if b > 0]
    assert len(used) >= min(N_REGIONS, 2)


def test_sharded_batch_more_regions_than_devices(endpoints):
    """10 regions on 8 devices: some devices own two slabs-worth of regions;
    results still match the oracle byte-for-byte."""
    sharded, _single, cpu = endpoints
    reqs = [_req(r, _sum_dag(75)) for r in range(10)]
    sharded.handle_batch([_req(r, _sum_dag(75)) for r in range(10)])  # warm
    got = sharded.handle_batch(reqs)
    for q, g in zip(reqs, got):
        want = cpu.handle_request(
            CoprRequest(103, q.dag, q.ranges, q.start_ts, dict(q.context)))
        assert g.data == want.data
    assert all(g.from_device for g in got)


def test_mid_batch_eviction_of_sharded_image(endpoints):
    """An invalidation between batches (raft epoch change on a sharded
    image) must not poison serving: the invalidated region rebuilds (cold
    fill) while the others keep their shards; bytes stay identical."""
    sharded, _single, cpu = endpoints
    dags = [lambda: _sum_dag(60)]
    sharded.handle_batch(_sweep(dags))  # ensure warm
    notify_region_epoch_change(3, reason="split")  # region_id 3 == region 2
    got = sharded.handle_batch(_sweep(dags))
    for q, g in zip(_sweep(dags), got):
        want = cpu.handle_request(
            CoprRequest(103, q.dag, q.ranges, q.start_ts, dict(q.context)))
        assert g.data == want.data
    # and the dropped image's bytes left the placement ledger (no leak)
    total_placed = sum(sharded.region_cache.placement().values())
    assert total_placed <= sharded.region_cache.total_bytes() + 1


def test_unary_warm_request_rides_mesh(endpoints):
    """A warm unary aggregation request serves through the sharded launcher
    (mesh_cache_hit) — the PR-2 cache→mesh bypass is gone."""
    sharded, _single, cpu = endpoints
    q = _req(1, _sum_dag(60))
    sharded.handle_request(_req(1, _sum_dag(60)))  # warm
    before = REGISTRY.counter("tikv_coprocessor_mesh_cache_hit_total", "").get()
    r = sharded.handle_request(q)
    after = REGISTRY.counter("tikv_coprocessor_mesh_cache_hit_total", "").get()
    assert r.from_device and r.from_cache
    assert after == before + 1
    assert r.data == cpu.handle_request(_req(1, _sum_dag(60))).data


@pytest.mark.parametrize("exc,surfaces", [(TypeError, True), (ValueError, False)])
def test_mesh_rung_program_error_is_not_absorbed(endpoints, monkeypatch, exc, surfaces):
    """A type/shape error while tracing the sharded program is a bug every
    call repeats: it reaches the caller (RungProgramError) instead of being
    counted as a device fault while the next rung answers — the jax 0.9.0
    scan-carry break hid behind that for a release.  ValueError stays the
    documented decline onto the single-device warm path."""
    from tikv_tpu.copr import jax_eval
    from tikv_tpu.copr.endpoint import RungProgramError

    sharded, _single, cpu = endpoints
    sharded.handle_request(_req(2, _sum_dag(70)))  # warm the image

    def boom(*_a, **_kw):
        raise exc("scan body function carry input and carry output must "
                  "have equal types")

    monkeypatch.setattr(jax_eval, "launch_xregion_sharded", boom)
    fallbacks = sharded.device_fallbacks
    if surfaces:
        with pytest.raises(RungProgramError, match="mesh rung"):
            sharded.handle_request(_req(2, _sum_dag(70)))
    else:
        r = sharded.handle_request(_req(2, _sum_dag(70)))
        assert r.from_device
        assert r.data == cpu.handle_request(_req(2, _sum_dag(70))).data
    assert sharded.device_fallbacks == fallbacks
    assert sharded.breaker.state_of("mesh") == "closed"


def test_min_max_merge_over_64_bit_lanes_without_pmin_pmax():
    """The TPU lowers only SUM all-reduces over 64-bit lanes, so min/max
    partials merge by gather + local fold + psum: exact on int64 extremes
    and on float signed zeros and infinities."""
    from jax.sharding import PartitionSpec as P

    from tikv_tpu.parallel.mesh import _collective, _smap

    mesh = make_mesh(groups=1)
    n = mesh.shape["regions"]
    info = np.iinfo(np.int64)
    ints = np.arange(n * 3, dtype=np.int64).reshape(n, 3) - 7
    ints[0, 0], ints[1, 1] = info.min, info.max
    flts = np.full((n, 3), 5.5)
    flts[:, 0] = -0.0
    flts[2, 1], flts[3, 2] = -np.inf, np.inf

    def merged(kind, x):
        f = _smap(mesh, (P("regions"),), P())(
            lambda a: _collective(kind, a[0], "regions"))
        return np.asarray(jax.jit(f)(x))

    assert np.array_equal(merged("min", ints), ints.min(axis=0))
    assert np.array_equal(merged("max", ints), ints.max(axis=0))
    lo, hi = merged("min", flts), merged("max", flts)
    assert np.array_equal(lo, flts.min(axis=0)) and np.signbit(lo[0])
    assert np.array_equal(hi, flts.max(axis=0)) and np.signbit(hi[0])
    text = jax.jit(_smap(mesh, (P("regions"),), P())(
        lambda a: _collective("min", a[0], "regions"))).lower(ints).as_text()
    assert "all_gather" in text


def test_huge_region_block_spread():
    """A single region bigger than the per-device budget block-spreads over
    the mesh; the sharded program merges per-device partials with the
    collective rules and the answer matches the CPU pipeline."""
    eng = _engine(4000, seed=9)
    mesh = make_mesh(groups=2)
    ep = Endpoint(LocalEngine(eng), enable_device=True, block_rows=256,
                  mesh=mesh)
    # force "huge": a tiny per-device budget makes any image block-spread
    ep.region_cache = RegionColumnCache(block_rows=256, mesh=mesh,
                                        per_device_budget=1)
    cpu = Endpoint(LocalEngine(eng), enable_device=False)
    q = lambda: _req(0, _sum_dag(2000), rows_per=4000)
    ep.handle_request(q())  # fill (miss)
    img = next(iter(ep.region_cache._images.values()))
    owners = img.block_cache.owner_devices
    assert owners is not None and len(set(owners)) > 1, \
        "huge region must spread its blocks over several devices"
    r = ep.handle_request(q())
    assert r.from_device and r.from_cache
    assert r.data == cpu.handle_request(q()).data


def test_rebalance_after_eviction():
    """Evicting/invalidating images rebalances placement: the device-load
    spread shrinks and the ledger matches resident bytes."""
    eng = _engine(ROWS_PER * 6, seed=4)
    mesh = make_mesh(groups=1)
    ep = Endpoint(LocalEngine(eng), enable_device=True, block_rows=256,
                  mesh=mesh)
    for r in range(6):
        ep.handle_request(_req(r, _sum_dag(60)))
    rc = ep.region_cache
    assert sum(rc.placement().values()) == rc.total_bytes()
    for rid in (1, 2):
        rc.invalidate_region(rid)
    assert sum(rc.placement().values()) == rc.total_bytes()
    loads = list(rc.placement().values())
    resident = [i.nbytes for i in rc._images.values()]
    if resident:
        # no device holds more than the max image above the mean — the
        # rebalance moved what it could
        spread = max(loads) - min(loads)
        assert spread <= max(resident), (loads, resident)


def test_sharded_responses_match_with_first_agg_fallback(endpoints):
    """A batch whose plan has no mesh merge rule (`first`) falls back off
    the sharded program but still answers correctly."""
    sharded, _single, cpu = endpoints
    first_dag = lambda: DagRequest(executors=[
        TableScan(TABLE_ID, COLS),
        Aggregation([], [AggDescriptor("first", col(1)),
                         AggDescriptor("count", None)]),
    ])
    reqs = [_req(r, first_dag()) for r in range(N_REGIONS)]
    sharded.handle_batch([_req(r, first_dag()) for r in range(N_REGIONS)])
    got = sharded.handle_batch(reqs)
    for q, g in zip(reqs, got):
        want = cpu.handle_request(
            CoprRequest(103, q.dag, q.ranges, q.start_ts, dict(q.context)))
        assert g.data == want.data
