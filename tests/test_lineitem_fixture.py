"""``lineitem_fixture.py``: the narrow lineitem table that ``chip_smoke.py``,
``test_jax_eval.py`` and ``test_tpu_compile.py`` share.  A fixture that
drifts takes every byte comparison built on it along, so it is held here:
rows follow the seed, keys are ordered and stay inside their region, the
columnar image equals a real decode, and the plans over it answer the same
bytes on the device evaluator as on the CPU pipeline.
"""

import numpy as np
import pytest

import lineitem_fixture as fx
from tikv_tpu.copr.executors import FixtureScanSource
from tikv_tpu.copr.jax_eval import JaxDagEvaluator, supports
from tikv_tpu.copr.table import (
    RowBatchDecoder, decode_record_handles, record_key, record_range,
)

BLOCK = 1024


def test_same_seed_same_rows():
    assert fx.build_kvs(300, seed=4) == fx.build_kvs(300, seed=4)
    a, b = fx.build_kvs(300, seed=4), fx.build_kvs(300, seed=5)
    assert [k for k, _ in a] == [k for k, _ in b]
    assert [v for _, v in a] != [v for _, v in b]
    # a shorter table is a prefix in its keys, not in its draws: the seed
    # and the size together name a table
    assert [k for k, _ in fx.build_kvs(100, seed=4)] == [k for k, _ in a[:100]]


@pytest.mark.parametrize("regions,rows_per_region", [(2, 500), (4, 257)])
def test_keys_ascend_and_stay_inside_their_region(regions, rows_per_region):
    """``chip_smoke.py`` splits at ``record_key(TABLE_ID, k * rows)`` and
    loads rows ``[k * rows, (k + 1) * rows)`` into region ``k``."""
    kvs = fx.build_kvs(regions * rows_per_region, seed=2)
    keys = [k for k, _ in kvs]
    assert keys == sorted(set(keys))
    lo, hi = record_range(fx.TABLE_ID)
    assert lo <= keys[0] and keys[-1] < hi
    assert decode_record_handles(keys).tolist() == list(range(len(keys)))
    for k in range(regions):
        part = keys[k * rows_per_region:(k + 1) * rows_per_region]
        assert record_key(fx.TABLE_ID, k * rows_per_region) == part[0]
        assert part[-1] < record_key(fx.TABLE_ID, (k + 1) * rows_per_region)


def test_cache_is_the_decoded_image_of_the_kvs():
    n = 2 * BLOCK + 77
    kvs = fx.build_kvs(n, seed=0)
    decoded = RowBatchDecoder(fx._lineitem()).decode(
        decode_record_handles([k for k, _ in kvs]), [v for _, v in kvs])
    built = fx.build_cache(n, block_rows=n, seed=0).blocks[0].cols
    assert len(decoded) == len(built) == 7
    for i, (c, d) in enumerate(zip(decoded, built)):
        assert c.eval_type == d.eval_type, i
        assert np.array_equal(np.asarray(c.data), np.asarray(d.data)), i
        assert np.array_equal(np.asarray(c.nulls), np.asarray(d.nulls)), i
        assert c.frac == d.frac, i
        assert (c.dictionary is None) == (d.dictionary is None), i
        if c.dictionary is not None:
            assert list(c.dictionary) == list(d.dictionary), i
    blocks = fx.build_cache(n, block_rows=BLOCK, seed=0).blocks
    assert [b.n_valid for b in blocks] == [BLOCK, BLOCK, 77]


@pytest.mark.parametrize("rows", [700, 2 * BLOCK + 300])
@pytest.mark.parametrize("plan", [fx.q1_dag, fx.q6_dag],
                         ids=lambda f: f.__name__)
def test_plans_answer_run_cpu_bytes_on_the_device_evaluator(plan, rows):
    """Cold from KV bytes and warm from the columnar image, one block and
    several: the device evaluator's answer is ``run_cpu``'s, and ``run_cpu``
    says the same over either form of the table."""
    dag = plan()
    assert supports(dag)
    kvs = fx.build_kvs(rows, seed=3)
    cache = fx.build_cache(rows, BLOCK, seed=3)
    want = fx.run_cpu(dag, kvs=kvs).encode()
    assert fx.run_cpu(dag, cache=cache).encode() == want
    ev = JaxDagEvaluator(plan(), block_rows=BLOCK)
    assert ev.run(FixtureScanSource(kvs)).encode() == want
    assert ev.run(None, cache=cache).encode() == want


@pytest.mark.parametrize("kind", ["scan", "selection"])
def test_filter_plans_answer_run_cpu_bytes(kind):
    dag = fx._filter_dag(kind, limit=900)
    kvs = fx.build_kvs(BLOCK + 500, seed=6)
    want = fx.run_cpu(dag, kvs=kvs).encode()
    assert supports(dag)
    got = JaxDagEvaluator(fx._filter_dag(kind, limit=900), block_rows=BLOCK).run(
        FixtureScanSource(kvs))
    assert got.encode() == want


def test_topn_endpoint_serves_the_cpu_endpoints_bytes_from_the_device():
    dev, dag, req = fx._topn_endpoint(1500, enable_device=True)
    cpu, _dag, cpu_req = fx._topn_endpoint(1500, enable_device=False)
    assert supports(dag())
    got = dev.handle_request(req())
    assert got.from_device and dev.device_fallbacks == 0
    assert got.data == cpu.handle_request(cpu_req()).data
