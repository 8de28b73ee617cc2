"""The served path's device programs, compiled for a DESCRIBED TPU v5e.

No chip is attached here: ``jax.experimental.topologies`` describes a v5e
2x2 and the TPU compiler (libtpu is installed) compiles for it, so what the
chip's compiler would refuse is refused here, at no chip time.  A compile
that passes is not a chip run and says nothing about results or speed —
``chip_smoke.py`` is the run.

Every test steers ``jax_eval._scatter_ok`` to ``False`` with ``monkeypatch``
(on this CPU backend the code would otherwise take its scatter branch), so
the forms compiled are the ones the chip executes.  Programs are captured
from the real evaluators at the shapes the served path uses — the default
``block_rows``, the evaluators' own power-of-two group-capacity buckets, a
region of ``ROWS`` rows as ``chip_smoke.py`` loads it — by swapping
``observatory.timed_jit`` for a recorder that notes the jitted function and
its argument shapes and answers with zeros; nothing runs on the CPU either.

The topology, shardings and mesh are built in module-scoped fixtures, after
a test of this file has started: only one process may load libtpu, and every
xdist worker imports every test file.
"""

from __future__ import annotations

import os

os.environ.setdefault("TPU_LOG_DIR", "disabled")

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P, SingleDeviceSharding

import chip_smoke
import lineitem_fixture as fx
from tikv_tpu.copr import encoding, jax_eval, observatory, plan_shape
from tikv_tpu.copr.aggr import AggDescriptor
from tikv_tpu.copr.dag import Aggregation, DagRequest, Selection, TableScan
from tikv_tpu.copr.datatypes import ColumnInfo, FieldType
from tikv_tpu.copr.executors import FixtureScanSource
from tikv_tpu.copr.rpn import call, col, const_int, const_real
from tikv_tpu.copr.table import encode_row, record_key
from tikv_tpu.parallel import mesh as pmesh

ROWS = chip_smoke.ROWS_PER_REGION  # a region right after a split
BLOCK = jax_eval.DEFAULT_BLOCK_ROWS
HBM_BYTES = 16 << 30
CAPACITIES = (1, 64, 1024, 4096)


# ---------------------------------------------------------------------------
# fixtures: the described chip, and region images shaped like the smoke's
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        desc = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no libtpu / no such topology here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep these out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def four_chips(topo):
    return Mesh(np.array(topo.devices[:4]), axis_names=("regions",))


def _image(encoded: bool, shared_dicts: bool = True):
    """A filled block cache shaped like one region's warm image."""
    cache = fx.build_cache(ROWS, BLOCK)
    if not shared_dicts:
        # a dictionary object per block: the group keys are no longer
        # "stable", so the host assigns group ids (jax_eval.scan)
        for blk in cache.blocks:
            for c in blk.cols:
                if c.dictionary is not None:
                    c.dictionary = c.dictionary.copy()
    if encoded:
        encoding.encode_blocks(cache, fx._lineitem())
    return cache


@pytest.fixture(scope="module")
def images():
    made: dict = {}

    def get(encoded: bool, **kw):
        key = (encoded, tuple(sorted(kw.items())))
        if key not in made:
            made[key] = _image(encoded, **kw)
        return made[key]

    return get


# ---------------------------------------------------------------------------
# capture and compile
# ---------------------------------------------------------------------------


class Recorder:
    """Stands in for ``observatory.timed_jit``: the wrapped program is noted
    with its argument shapes and answers zeros of its output shapes."""

    def __init__(self):
        self.programs: list = []  # (site, jitted fn, argument specs)

    def timed_jit(self, fn, site, path, sig=""):
        def call_(*args):
            specs = jax.tree.map(
                lambda a: jax.ShapeDtypeStruct(np.shape(a), a.dtype)
                if hasattr(a, "dtype") else a, args)
            self.programs.append((site, fn, specs))
            return jax.tree.map(lambda s: np.zeros(s.shape, s.dtype),
                                jax.eval_shape(fn, *args))

        return call_


@pytest.fixture
def recorder(monkeypatch):
    rec = Recorder()
    monkeypatch.setattr(jax_eval, "_scatter_ok", lambda: False)
    monkeypatch.setattr(observatory, "timed_jit", rec.timed_jit)
    return rec


def compile_for(fn, specs, place):
    """Lower ``fn`` at ``specs`` with every array placed by ``place(spec)``
    and compile; returns the bytes the program needs on one device."""
    args = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=place(s))
        if isinstance(s, jax.ShapeDtypeStruct) else s, specs)
    compiled = fn.lower(*args).compile()
    mem = compiled.memory_analysis()
    need = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes + mem.generated_code_size_in_bytes)
    assert need < HBM_BYTES, f"{need} bytes do not fit one v5e's 16 GiB"
    return need


def compile_captured(rec: Recorder, one_chip, expect: str) -> None:
    sites = [site for site, _fn, _specs in rec.programs]
    assert expect in sites, f"{expect} not among the programs run: {sites}"
    seen = set()
    for site, fn, specs in rec.programs:
        key = (id(fn), str(specs))
        if key in seen:
            continue
        seen.add(key)
        compile_for(fn, specs, lambda _s: one_chip)


# ---------------------------------------------------------------------------
# plans: chip_smoke's own, so what is compiled here is what it will run
# ---------------------------------------------------------------------------


def _evaluator(name: str) -> jax_eval.JaxDagEvaluator:
    return jax_eval.JaxDagEvaluator(chip_smoke.plan_set()[name])


# ---------------------------------------------------------------------------
# the segment forms
# ---------------------------------------------------------------------------


_FORMS = {
    "seg_sum_i64": (lambda x, g, c: jax_eval._seg_sum(x, g, c), jnp.int64),
    "seg_sum_f64": (lambda x, g, c: jax_eval._seg_sum(x, g, c), jnp.float64),
    "limb_matmul_i64": (
        lambda x, g, c: jax_eval._limb_matmul_seg_sum(x, g, c), jnp.int64),
    "seg_min_i64": (
        lambda x, g, c: jax_eval._seg_extreme(
            x, g, c, True, np.iinfo(np.int64).max), jnp.int64),
    "seg_max_f64": (
        lambda x, g, c: jax_eval._seg_extreme(x, g, c, False, -np.inf),
        jnp.float64),
}


@pytest.mark.parametrize("capacity", CAPACITIES)
@pytest.mark.parametrize("form", sorted(_FORMS))
def test_segment_form_compiles(form, capacity, one_chip, monkeypatch):
    monkeypatch.setattr(jax_eval, "_scatter_ok", lambda: False)
    f, dtype = _FORMS[form]
    specs = (jax.ShapeDtypeStruct((BLOCK,), dtype),
             jax.ShapeDtypeStruct((BLOCK,), jnp.int64))
    compile_for(jax.jit(lambda x, g: f(x, g, capacity)), specs,
                lambda _s: one_chip)


# ---------------------------------------------------------------------------
# JaxDagEvaluator's programs, by timed_jit site
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("plan", ["q6", "q1", "g550", "minmax"])
def test_cold_block_step_compiles(plan, one_chip, recorder):
    """``jax_eval.agg_step`` (and the packed pull): what a cold fill's own
    request runs per block; Q1's host-assigned group ids start in the
    1024-slot bucket, so this is the limb-matmul form."""
    kvs = fx.build_kvs(BLOCK + 1000, seed=1)
    _evaluator(plan).run(FixtureScanSource(kvs))
    compile_captured(recorder, one_chip, "jax_eval.agg_step")


@pytest.mark.parametrize("encoded", [False, True], ids=["plain", "encoded"])
@pytest.mark.parametrize("plan", ["q6", "q1", "minmax"])
def test_warm_scan_coded_compiles(plan, encoded, one_chip, recorder, images):
    """``jax_eval.scan_coded``: one ``lax.scan`` over a region's resident
    blocks, group ids from resident dictionary codes."""
    ev = _evaluator(plan)
    ev.route_hint = "unary"  # past the zone rung, which has its own test
    ev.run(None, cache=images(encoded))
    compile_captured(recorder, one_chip, "jax_eval.scan_coded")


@pytest.mark.parametrize("encoded", [False, True], ids=["plain", "encoded"])
@pytest.mark.parametrize("plan", ["q1", "g550"])
def test_warm_scan_host_gids_compiles(plan, encoded, one_chip, recorder, images):
    """``jax_eval.scan``: the same scan with group ids assigned on the host,
    which is how integer group columns (g550) and unstable dictionaries (Q1
    over per-block dictionaries) run, in the 1024-slot bucket."""
    ev = _evaluator(plan)
    ev.route_hint = "unary"
    ev.run(None, cache=images(encoded, shared_dicts=False))
    compile_captured(recorder, one_chip, "jax_eval.scan")


@pytest.mark.parametrize("source", ["cold", "plain", "encoded"])
def test_topn_step_compiles(source, one_chip, recorder, images):
    ev = _evaluator("topn")
    if source == "cold":
        ev.run(FixtureScanSource(fx.build_kvs(BLOCK + 1000, seed=1)))
    else:
        ev.run(None, cache=images(source == "encoded"))
    compile_captured(recorder, one_chip, "jax_eval.topn")


@pytest.mark.parametrize("encoded", [False, True], ids=["plain", "encoded"])
def test_selection_mask_compiles(encoded, one_chip, recorder, images):
    """``jax_eval.mask``: the Selection+Limit scan's device half."""
    _evaluator("scan_chunk").run(None, cache=images(encoded))
    compile_captured(recorder, one_chip, "jax_eval.mask")


@pytest.mark.parametrize("regions", [4, 8])
@pytest.mark.parametrize("plan", ["q6", "q1", "minmax"])
def test_xregion_compiles(plan, regions, one_chip, recorder):
    """``jax_eval.xregion``: what serves by default, since the scheduler
    coalesces a query's per-region tasks into one vmapped program."""
    caches = [_image(True) for _ in range(regions)]
    jax_eval.launch_xregion_cached(_evaluator(plan), caches).finalize()
    compile_captured(recorder, one_chip, "jax_eval.xregion")


@pytest.mark.parametrize("plan,site", [("q1", "jax_zone.full"),
                                       ("q6", "jax_zone.partial")])
def test_zone_tile_programs_compile(plan, site, one_chip, recorder, images):
    """The zone rung, the first a lone warm request tries: Q1's one range
    predicate leaves whole tiles inside it (``jax_zone.full``), Q6's five
    leave every tile straddling one (``jax_zone.partial``)."""
    assert _evaluator(plan)._try_zone(images(False)) is not None
    compile_captured(recorder, one_chip, site)


def _bound(name: str):
    """``(evaluator of the plan's shape, the plan's literals)``, as
    ``Endpoint._bind`` hands them to the rungs: the programs take the literals
    as an int64 and a float64 vector (64-bit scalars as inputs)."""
    dag = chip_smoke.plan_set()[name]
    _shape, params = plan_shape.split(dag)
    assert params, f"{name} has no literal to hoist"
    return jax_eval.JaxDagEvaluator(plan_shape.shape_dag(dag)), params


def _param_inputs(rec: Recorder, site: str) -> list:
    """The (int64, float64) parameter vectors among ``site``'s arguments."""
    specs = next(specs for s, _fn, specs in rec.programs if s == site)
    return [x for x in jax.tree.leaves(specs)
            if getattr(x, "ndim", 0) == 1 and x.dtype in (np.int64, np.float64)]


@pytest.mark.parametrize("plan", ["q6", "q1"])
def test_parameterised_xregion_compiles(plan, one_chip, recorder):
    """``jax_eval.xregion`` of a plan's SHAPE: the selection's literals are
    an argument of the program, one vector for all regions of the group."""
    ev, params = _bound(plan)
    caches = [_image(True) for _ in range(4)]
    jax_eval.launch_xregion_cached(ev, caches, params).finalize()
    compile_captured(recorder, one_chip, "jax_eval.xregion")
    # dates and decimals ride the int64 lane; no slot is REAL, so no float64
    # vector is handed over
    vecs = _param_inputs(recorder, "jax_eval.xregion")
    assert any(v.shape == (len(params),) and v.dtype == np.int64 for v in vecs)
    assert not any(v.shape == (len(params),) and v.dtype == np.float64 for v in vecs)


def test_parameterised_real_literal_compiles(one_chip, recorder):
    """A REAL literal beside an INT one: the float64 vector as an input of
    the cold block step."""
    cols = [ColumnInfo(1, FieldType.int64(), is_pk_handle=True),
            ColumnInfo(2, FieldType.double()),
            ColumnInfo(3, FieldType.int64())]
    kvs = [(record_key(fx.TABLE_ID, i),
            encode_row(cols[1:], [i * 0.25, i % 7])) for i in range(256)]
    dag = DagRequest(executors=[
        TableScan(fx.TABLE_ID, cols),
        Selection([call("ge", col(1), const_real(1.5)),
                   call("lt", col(2), const_int(5))]),
        Aggregation([], [AggDescriptor("sum", col(1)),
                         AggDescriptor("count", None)])])
    _shape, params = plan_shape.split(dag)
    assert params == (1.5, 5)
    ev = jax_eval.JaxDagEvaluator(plan_shape.shape_dag(dag))
    ev.run(FixtureScanSource(kvs), params=params)
    compile_captured(recorder, one_chip, "jax_eval.agg_step")
    vecs = _param_inputs(recorder, "jax_eval.agg_step")
    assert {v.dtype for v in vecs if v.shape == (2,)} == {
        np.dtype(np.int64), np.dtype(np.float64)}


def test_parameterised_partial_tile_program_compiles(one_chip, recorder, images):
    """``jax_zone.partial`` of Q6's shape: the tiles that straddle a
    predicate are evaluated row by row against literals read from the
    program's parameter vectors."""
    ev, params = _bound("q6")
    ev._params = params
    assert ev._try_zone(images(False)) is not None
    compile_captured(recorder, one_chip, "jax_zone.partial")
    assert any(v.shape == (len(params),)
               for v in _param_inputs(recorder, "jax_zone.partial"))


@pytest.mark.parametrize("encoded", [False, True], ids=["plain", "encoded"])
def test_parameterised_warm_scan_compiles(encoded, one_chip, recorder, images):
    """``jax_eval.scan_coded`` of Q6's shape: the lone rider's program past
    the zone rung."""
    ev, params = _bound("q6")
    ev.route_hint = "unary"
    ev.run(None, cache=images(encoded), params=params)
    compile_captured(recorder, one_chip, "jax_eval.scan_coded")


def test_real_aggregate_compiles(one_chip, recorder):
    """A float64 lane end to end: the block step over a REAL column and the
    packed pull that carries it beside the int64 matrix."""
    cols = [ColumnInfo(1, FieldType.int64(), is_pk_handle=True),
            ColumnInfo(2, FieldType.double()),
            ColumnInfo(3, FieldType.int64())]
    kvs = [(record_key(fx.TABLE_ID, i),
            encode_row(cols[1:], [i * 0.25, i % 7])) for i in range(256)]
    dag = DagRequest(executors=[
        TableScan(fx.TABLE_ID, cols),
        Selection([call("ge", col(2), const_int(1))]),
        Aggregation([col(2)], [AggDescriptor("sum", col(1)),
                               AggDescriptor("avg", col(1)),
                               AggDescriptor("max", col(1))])])
    jax_eval.JaxDagEvaluator(dag).run(FixtureScanSource(kvs))
    compile_captured(recorder, one_chip, "jax_eval.agg_step")
    packs = [specs for site, _fn, specs in recorder.programs
             if site == "jax_eval.pack"]
    assert packs and any(
        leaf.dtype == np.float64 for leaf in jax.tree.leaves(packs[0]))


# ---------------------------------------------------------------------------
# four chips: the sharded warm program
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("plan", ["q6", "q1", "minmax"])
def test_mesh_xshard_compiles(plan, four_chips, monkeypatch):
    """``mesh.xshard`` over a 4-device mesh of described chips, four regions
    of ``ROWS`` rows with one image per device: every argument carries the
    ``NamedSharding`` the launcher gives it."""
    monkeypatch.setattr(jax_eval, "_scatter_ok", lambda: False)
    monkeypatch.setattr(observatory, "timed_jit", lambda fn, *a, **kw: fn)
    ev = _evaluator(plan)
    caches = [_image(True) for _ in range(4)]
    _specs, group_cols, capacity = jax_eval.xregion_specs(ev, caches)
    ship = tuple(ev._ship_cols(group_cols))
    nullable = tuple(ev.nullable_cols)
    plans = encoding.batch_plan(caches, list(ship), list(nullable),
                                "mesh_sharded", allow_rle=False)
    enc = plans[0].sig
    fn = pmesh._xshard_program(ev, four_chips, len(caches), capacity, ship,
                               nullable, group_cols, enc)
    slabs = 4 * len(caches[0].blocks)  # N devices x S slabs each
    lanes = tuple(jax.ShapeDtypeStruct((slabs, BLOCK), np.dtype(enc[j][1]))
                  for j in range(len(ship)))
    nulls = tuple(jax.ShapeDtypeStruct((slabs, BLOCK), np.bool_)
                  for _ in nullable)
    per_slab = [jax.ShapeDtypeStruct((slabs,), dt)
                for dt in (np.int32, np.int64, np.int64)]
    radices = jax.ShapeDtypeStruct((4, len(group_cols)), np.int64)
    refs = jax.ShapeDtypeStruct((4, len(ship)), np.int64)
    sharded = NamedSharding(four_chips, P("regions"))
    replicated = NamedSharding(four_chips, P())
    compile_for(fn, (lanes, nulls, *per_slab, radices, refs),
                lambda s: replicated if s in (radices, refs) else sharded)
