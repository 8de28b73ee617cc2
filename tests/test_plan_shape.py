"""One program per plan shape; a query's literals are its arguments.

TiDB inlines a statement's literals into every DAG it pushes down, so a store
sees new plan bytes with every execution.  ``copr/plan_shape.py`` takes a plan
apart into its shape (what programs and memos are keyed by) and its parameters
(what the programs are given); these tests hold the served path to it over
TPC-H's own literal sets: Q1's 61 values of DELTA and Q6's 5 x 8 x 2 = 80
combinations of DATE, DISCOUNT and QUANTITY (cl. 2.4.1.3, 2.4.6.3), over a small
LINEITEM made by ``benchmark/table.py``, through ``Endpoint`` on the ``xregion``,
``zone`` and ``unary`` rungs.

(a) every literal set: the response bytes equal the CPU pipeline's and the
    decoded rows equal ``benchmark/plans/q*.reference`` at those parameters;
(b) the same sweep builds each program once (JAX's compile events, as
    ``benchmark/counters.CompileCount`` reads them) and keeps one evaluator;
(c) the stale-literal hazard: two queries of one shape whose dates put a block
    on opposite sides of a zone-map decision, in both orders and in one
    scheduler pass, each answered for its own date; with the hazard planted
    the same test sees wrong answers;
(d) the rule's edges: NULL literal, BYTES constant, decimals of different
    frac, a negative and a > 2^31 constant against a narrowed column;
(e) lives in test_copr_scheduler.py (slots and groups).
"""

import itertools

import numpy as np
import pytest

from benchmark import check
from benchmark import table as tbl
from benchmark.counters import CompileCount
from benchmark.plans import q1, q6
from fixtures import put_committed

from tikv_tpu.copr import jax_zone, plan_shape
from tikv_tpu.copr import zone_maps as Z
from tikv_tpu.copr.aggr import AggDescriptor
from tikv_tpu.copr.breaker import BreakerConfig
from tikv_tpu.copr.dag import (
    Aggregation, BatchExecutorsRunner, DagRequest, SelectResponse, Selection, TableScan,
)
from tikv_tpu.copr.datatypes import ColumnInfo, FieldType
from tikv_tpu.copr.endpoint import CoprRequest, Endpoint
from tikv_tpu.copr.jax_eval import JaxDagEvaluator
from tikv_tpu.copr.executors import FixtureScanSource
from tikv_tpu.copr.rpn import call, col, const_bytes, const_decimal, const_int, const_real
from tikv_tpu.copr.table import encode_row, record_key
from tikv_tpu.storage.btree_engine import BTreeEngine
from tikv_tpu.storage.kv import LocalEngine

TABLE_ID = 101
ROWS_PER = 2048
BLOCK_ROWS = 1024
RUNGS = ("xregion", "zone", "unary")

Q1_SETS = [{"delta_days": d} for d in range(60, 121)]
Q6_SETS = [{"year": y, "discount_pct": p, "quantity": q}
           for y, p, q in itertools.product(range(1993, 1998), range(2, 10), (24, 25))]
SETS = {"q1": (q1, Q1_SETS), "q6": (q6, Q6_SETS)}

COMPILES = CompileCount()
COMPILES.listen()


@pytest.fixture(autouse=True)
def small_tiles(monkeypatch):
    """Small tiles, so that a block of a thousand rows has full, empty and
    partial tiles and the zone rung serves."""
    monkeypatch.setattr(jax_zone, "TILE_ROWS", 64)


class Store:
    """Two regions of LINEITEM rows in one engine, with the CPU pipeline's
    answers remembered by (plan, parameters, region)."""

    def __init__(self, table: tbl.Table):
        self.parts = [table.take(slice(k * ROWS_PER, (k + 1) * ROWS_PER)) for k in range(2)]
        kvs = tbl.encode_kvs(TABLE_ID, table)
        eng = BTreeEngine()
        for k, v in kvs:
            put_committed(eng, k, v, 90, 100)
        self.engine = LocalEngine(eng)
        self.ranges = [(kvs[0][0], kvs[ROWS_PER][0]),
                       (kvs[ROWS_PER][0], kvs[-1][0] + b"\x00")]
        self.cold = Endpoint(self.engine, enable_device=False, enable_region_cache=False)
        self._cpu: dict = {}

    def req(self, dag, k: int) -> CoprRequest:
        return CoprRequest(103, dag, [self.ranges[k]], 200, context={
            "region_id": k + 1, "region_epoch": (1, 1), "apply_index": 3})

    def cpu_bytes(self, name: str, p: dict, k: int) -> bytes:
        key = (name, tuple(sorted(p.items())), k)
        if key not in self._cpu:
            mod = SETS[name][0]
            self._cpu[key] = bytes(self.cold.handle_request(
                self.req(mod.dag(TABLE_ID, dict(mod.DEFAULTS, **p)), k)).data)
        return self._cpu[key]

    def warm(self, rung: str) -> Endpoint:
        return warm_endpoint(self.engine, rung, BLOCK_ROWS)

    def serve(self, ep: Endpoint, rung: str, dag_of) -> list:
        """One query (a task per region) on ``rung``; ``dag_of()`` makes a
        fresh DagRequest per task, as the wire does."""
        if rung == "xregion":
            return ep.handle_batch([self.req(dag_of(), k) for k in range(2)])
        return [ep.handle_request(self.req(dag_of(), k)) for k in range(2)]


def warm_endpoint(engine, rung: str, block_rows: int) -> Endpoint:
    """A fresh device endpoint that serves on ``rung``: a pair of tasks
    through the read scheduler rides ``xregion``; a lone task is served by
    ``zone``, or by ``unary`` once the zone path's breaker is open."""
    ep = Endpoint(engine, enable_device=True, block_rows=block_rows,
                  breaker_config=BreakerConfig(threshold=1, cooldown_s=1e9,
                                               max_cooldown_s=1e9))
    if rung == "unary":
        ep.breaker.record_failure("zone")
    return ep


def _rung(resp) -> str:
    """Which rung answered: the scheduler's batch kind, or the path a lone
    request's tracker was stamped with."""
    md = resp.metrics
    return md["sched_batch"] if "sched_batch" in md else md["path"]


def _bytes(resp) -> bytes:
    return (bytes(resp.data) if resp.data is not None
            else b"".join(bytes(p) for p in resp.data_parts))


def _rows(mod, data: bytes) -> list:
    return check.canonical(mod, SelectResponse.decode(data).iter_rows())


@pytest.fixture(scope="module")
def store():
    return Store(tbl.build_table(2 * ROWS_PER, seed=34))


@pytest.fixture(scope="module")
def clustered():
    """The same rows in ship-date order, so that blocks and tiles cover
    disjoint dates and a date decides which of them a query reads."""
    t = tbl.build_table(2 * ROWS_PER, seed=35)
    by_date = t.take(np.argsort(t.shipdate, kind="stable"))
    by_date.handle = t.handle  # rows are stored in handle order
    return Store(by_date)


# ---------------------------------------------------------------------------
# (a) every literal set, every rung: the CPU pipeline's bytes, the reference's rows
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("rung", RUNGS)
@pytest.mark.parametrize("name", ["q1", "q6"])
def test_every_literal_set_is_answered_exactly(store, name, rung):
    mod, sets = SETS[name]
    ep = store.warm(rung)
    for p in sets:
        full = dict(mod.DEFAULTS, **p)
        got = store.serve(ep, rung, lambda: mod.dag(TABLE_ID, full))
        for k, resp in enumerate(got):
            assert resp.from_device, (p, k)
            data = _bytes(resp)
            assert data == store.cpu_bytes(name, p, k), (p, k)
            assert _rows(mod, data) == check.canonical(
                mod, mod.reference(store.parts[k], full)), (p, k)
    # the first query filled the images on the cold path; every later one was
    # served by the rung asked for
    assert _rung(got[0]) == _rung(got[1]) == rung
    assert len(ep._evaluators) == 1


# ---------------------------------------------------------------------------
# (b) the sweep builds each program once
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("rung", RUNGS)
@pytest.mark.parametrize("name", ["q1", "q6"])
def test_sweep_builds_each_program_once(store, name, rung):
    mod, sets = SETS[name]
    ep = store.warm(rung)
    # the first literal of the shape builds what the rung needs (twice: the
    # first pass fills the images through the cold path's programs)
    first = dict(mod.DEFAULTS, **sets[0])
    for _ in range(2):
        store.serve(ep, rung, lambda: mod.dag(TABLE_ID, first))
    built, shapes = COMPILES.programs, ep._evaluators.copy()
    for p in sets[1:]:
        full = dict(mod.DEFAULTS, **p)
        got = store.serve(ep, rung, lambda: mod.dag(TABLE_ID, full))
        assert _rung(got[0]) == rung, p
    assert COMPILES.programs == built, "a literal built a program"
    # and the memos hold what they held: one evaluator, the same programs
    assert ep._evaluators == shapes and len(shapes) == 1
    assert ep.plan_shapes_built == 1
    [ev] = shapes.values()
    assert ev.n_params == (1 if name == "q1" else 5)
    assert sum(1 for key in ev._agg_fn_cache
               if isinstance(key, tuple) and key[0] == "xregion") == (rung == "xregion")


# ---------------------------------------------------------------------------
# (c) the stale-literal hazard
# ---------------------------------------------------------------------------


def _year(y: int) -> dict:
    return dict(q6.DEFAULTS, year=y, discount_pct=5, quantity=25)


def _hazard_sequence(st: Store, ep: Endpoint, rung: str) -> list:
    """(parameters, region, response bytes) of a run of Q6-shaped queries
    whose years alternate, alone and in one scheduler pass."""
    out = []
    for y in (1993, 1996, 1993, 1997, 1996):
        p = _year(y)
        for k, resp in enumerate(st.serve(ep, rung, lambda: q6.dag(TABLE_ID, p))):
            out.append((p, k, _bytes(resp)))
    # one scheduler pass, two groups of one shape and different literals
    a, b = _year(1994), _year(1995)
    reqs = [st.req(q6.dag(TABLE_ID, p), k) for p in (a, b, a, b) for k in range(2)]
    for (p, k), resp in zip([(p, k) for p in (a, b, a, b) for k in range(2)],
                            ep.handle_batch(reqs)):
        out.append((p, k, _bytes(resp)))
    return out


@pytest.mark.parametrize("rung", RUNGS)
def test_each_query_is_pruned_and_tiled_by_its_own_date(clustered, rung):
    st = clustered
    ep = st.warm(rung)
    st.serve(ep, rung, lambda: q6.dag(TABLE_ID, _year(1995)))  # fill
    for p, k, data in _hazard_sequence(st, ep, rung):
        assert _rows(q6, data) == check.canonical(
            q6, q6.reference(st.parts[k], p)), (p, k)
    # the decisions really differ between the dates: per block ...
    [ev] = ep._evaluators.values()
    images = sorted(ep.region_cache._images.items(), key=lambda kv: kv[0][0])
    cache = images[0][1].block_cache
    keeps = []
    for y in (1993, 1996):
        _shape, params = plan_shape.split(q6.dag(TABLE_ID, _year(y)))
        keep = Z.prune_blocks(cache, ev.bound_sel_rpns(params), count=False)
        keeps.append(None if keep is None else keep.tolist())
    assert keeps[0] != keeps[1], keeps
    # ... and per tile, where the zone rung served
    if rung == "zone":
        assert ev._zone.served > 0


@pytest.mark.parametrize("rung", RUNGS)
def test_a_planted_stale_literal_is_seen(clustered, rung, monkeypatch):
    """The control: host-side readers handed the FIRST request's literals for
    ever (the fault the split must not have) answer some query for another
    query's date, and the comparison above would say so."""
    st = clustered
    ep = st.warm(rung)
    st.serve(ep, rung, lambda: q6.dag(TABLE_ID, _year(1995)))  # fill
    real = JaxDagEvaluator.bound_sel_rpns
    first: dict = {}

    def stale(self, params):
        return real(self, first.setdefault(id(self), params))

    monkeypatch.setattr(JaxDagEvaluator, "bound_sel_rpns", stale)
    wrong = sum(
        _rows(q6, data) != check.canonical(q6, q6.reference(st.parts[k], p))
        for p, k, data in _hazard_sequence(st, ep, rung))
    assert wrong > 0


# ---------------------------------------------------------------------------
# (d) the rule's edges
# ---------------------------------------------------------------------------

EDGE_TABLE = 55
EDGE_COLS = [
    ColumnInfo(1, FieldType.int64(), is_pk_handle=True),
    ColumnInfo(2, FieldType.varchar()),
    ColumnInfo(3, FieldType.int64()),          # 0..119: narrowed to a byte
    ColumnInfo(4, FieldType.decimal_type(2)),
]
EDGE_ROWS = 1500


@pytest.fixture(scope="module")
def edge_store():
    rng = np.random.default_rng(3)
    eng = BTreeEngine()
    for i in range(2 * EDGE_ROWS):
        put_committed(eng, record_key(EDGE_TABLE, i), encode_row(EDGE_COLS[1:], [
            (b"alpha", b"beta", b"gamma")[i % 3], int(rng.integers(0, 120)),
            int(rng.integers(0, 5000))]), 90, 100)
    return LocalEngine(eng)


def _edge_dag(*conds) -> DagRequest:
    return DagRequest(executors=[
        TableScan(EDGE_TABLE, EDGE_COLS), Selection(list(conds)),
        Aggregation([col(1)], [AggDescriptor("count", None),
                               AggDescriptor("sum", col(2)),
                               AggDescriptor("sum", col(3))])])


def _edge_req(dag, k: int) -> CoprRequest:
    lo, hi = (record_key(EDGE_TABLE, (k + j) * EDGE_ROWS) for j in (0, 1))
    return CoprRequest(103, dag, [(lo, hi)], 200, context={
        "region_id": k + 1, "region_epoch": (1, 1), "apply_index": 3})


def test_the_rule_hoists_numeric_lanes_and_nothing_else():
    small, dec = col(2), col(3)
    split = plan_shape.split
    five = split(_edge_dag(call("lt", small, const_int(5))))
    # same shape whatever the value: negative, past 2^31, past 2^40
    for v in (-7, (1 << 31) + 5, 1 << 40):
        shape, params = split(_edge_dag(call("lt", small, const_int(v))))
        assert shape == five[0] and params == (v,)
    # a NULL literal stays in the shape
    null = split(_edge_dag(call("lt", small, const_int(None))))
    assert null[1] == () and null[0] != five[0]
    # a BYTES constant stays in the shape, and its value tells shapes apart
    beta = split(_edge_dag(call("eq", col(1), const_bytes(b"beta"))))
    gamma = split(_edge_dag(call("eq", col(1), const_bytes(b"gamma"))))
    assert beta[1] == gamma[1] == () and beta[0] != gamma[0]
    # a decimal's frac is shape; its digits are the parameter
    d52 = split(_edge_dag(call("ge", dec, const_decimal(5, 2))))
    d72 = split(_edge_dag(call("ge", dec, const_decimal(7, 2))))
    d50 = split(_edge_dag(call("ge", dec, const_decimal(5, 0))))
    assert d52[0] == d72[0] != d50[0]
    assert (d52[1], d72[1], d50[1]) == ((5,), (7,), (5,))
    # an int no lane holds is left where it is
    huge = split(_edge_dag(call("lt", small, const_int(1 << 70))))
    assert huge[1] == ()
    # aggregates keep their constants: the rule reads Selections only
    agg = DagRequest(executors=[
        TableScan(EDGE_TABLE, EDGE_COLS),
        Aggregation([], [AggDescriptor("sum", call("plus", small, const_int(3)))])])
    assert split(agg)[1] == ()
    # the whole identity is the pair, and says what plan_signature says
    a, b = (_edge_dag(call("lt", small, const_int(v))) for v in (5, 6))
    assert (split(a) == split(b)) == (
        plan_shape.plan_signature(a) == plan_shape.plan_signature(b)) == False  # noqa: E712
    # a shape's own signature is the shape: slots number as the split counts
    for dag in (a, _edge_dag(call("ge", dec, const_decimal(5, 2)),
                             call("lt", small, const_int(9)))):
        shape, params = split(dag)
        assert split(plan_shape.shape_dag(dag))[0] == shape
        assert JaxDagEvaluator(plan_shape.shape_dag(dag)).n_params == len(params)


EDGE_CONDS = {
    "negative": lambda: [call("gt", col(2), const_int(-7))],
    "past_2_31": lambda: [call("lt", col(2), const_int((1 << 31) + 5))],
    "past_2_40": lambda: [call("lt", col(2), const_int(1 << 40)),
                          call("ge", col(2), const_int(-(1 << 40)))],
    "inside": lambda: [call("lt", col(2), const_int(60))],
    "null_literal": lambda: [call("lt", col(2), const_int(None))],
    "bytes_const": lambda: [call("eq", col(1), const_bytes(b"beta")),
                            call("ge", col(2), const_int(17))],
    "decimal_frac_2": lambda: [call("ge", col(3), const_decimal(2550, 2))],
    "decimal_frac_0": lambda: [call("ge", col(3), const_decimal(25, 0))],
    "decimal_frac_2_again": lambda: [call("ge", col(3), const_decimal(999, 2))],
}


@pytest.mark.parametrize("rung", RUNGS)
def test_edge_literals_are_answered_right_on_every_rung(edge_store, rung):
    """Each edge through one endpoint, so that later shapes meet the memos the
    earlier ones left: bytes equal the CPU pipeline's."""
    ep = warm_endpoint(edge_store, rung, 512)
    cold = Endpoint(edge_store, enable_device=False, enable_region_cache=False)
    for _round in range(2):  # the second round finds every shape known
        for name, conds in EDGE_CONDS.items():
            reqs = [_edge_req(_edge_dag(*conds()), k) for k in range(2)]
            got = (ep.handle_batch(reqs) if rung == "xregion"
                   else [ep.handle_request(r) for r in reqs])
            for k, resp in enumerate(got):
                want = cold.handle_request(_edge_req(_edge_dag(*conds()), k))
                assert _bytes(resp) == bytes(want.data), (name, k)
    # nine plans, seven shapes: the two ``lt`` of one column are one, and so
    # are the two decimals of frac 2
    shapes = {plan_shape.split(_edge_dag(*c()))[0] for c in EDGE_CONDS.values()}
    assert len(shapes) == 7
    assert len(ep._evaluators) <= len(shapes)


def test_real_literals_ride_the_float_lane_of_one_program():
    """REAL and INT literals in one selection: one evaluator, built once from
    the shape, answers every pair of them as the CPU pipeline does; the INT
    slot reads the int64 vector, the REAL slot the float64 one."""
    cols = [ColumnInfo(1, FieldType.int64(), is_pk_handle=True),
            ColumnInfo(2, FieldType.double()),
            ColumnInfo(3, FieldType.int64())]
    kvs = [(record_key(EDGE_TABLE, i),
            encode_row(cols[1:], [i * 0.25 - 40.0, i % 11])) for i in range(700)]

    def dag(x: float, n: int) -> DagRequest:
        return DagRequest(executors=[
            TableScan(EDGE_TABLE, cols),
            Selection([call("ge", col(1), const_real(x)),
                       call("lt", col(2), const_int(n))]),
            Aggregation([col(2)], [AggDescriptor("count", None),
                                   AggDescriptor("max", col(1))])])

    shape, _ = plan_shape.split(dag(0.0, 0))
    ev = JaxDagEvaluator(plan_shape.shape_dag(dag(0.0, 0)), block_rows=256)
    assert ev.param_float_slots == (True, False)
    for x, n in ((-39.75, 11), (0.5, 3), (1e300, 11), (-1e300, 0), (17.25, 7), (2, 9)):
        s2, params = plan_shape.split(dag(x, n))
        assert s2 == shape and params == (x, n)
        want = BatchExecutorsRunner(dag(x, n), FixtureScanSource(kvs)).handle_request()
        assert ev.run(FixtureScanSource(kvs), params=params).encode() == want.encode(), (x, n)
    with pytest.raises(ValueError, match="parameter slots"):
        ev.run(FixtureScanSource(kvs))
