"""The roofline arithmetic of each plan file, each plain reference on a table
small enough to check by hand, and the generator's dbgen shapes."""

import glob
import importlib
import os

import numpy as np
import pytest

from benchmark import table as tbl

PLANS = sorted(os.path.basename(p)[:-3] for p in glob.glob(
    os.path.join(os.path.dirname(__file__), "..", "plans", "*.py"))
    if not p.endswith("__init__.py"))


def plan(name):
    return importlib.import_module(f"benchmark.plans.{name}")


@pytest.mark.parametrize("name", PLANS)
def test_work_is_rows_and_least_bytes(name):
    rows, nbytes = plan(name).work(200_000)
    assert 0 < rows <= 200_000
    # no plan can be answered from less than a byte a row looked at, and none
    # needs more than the widest row (192 bytes) plus its 19-byte key
    assert rows <= nbytes <= 200_000 * (192 + tbl.KEY_BYTES)
    # half the rows: no more work
    assert plan(name).work(100_000)[1] <= nbytes


@pytest.mark.parametrize("name, per_row", [("q6", 8), ("q1", 11)])
def test_whole_region_plans_read_every_row(name, per_row):
    assert plan(name).work(200_000) == (200_000, 200_000 * per_row)


def small():
    """Four rows: quantity, price, discount, tax, flag, status, ship date."""
    d = tbl.day
    cols = dict(
        quantity=[1000, 3000, 2000, 1000], extendedprice=[100000, 200000, 10400000, 300000],
        discount=[6, 6, 9, 5], tax=[0, 8, 2, 1], returnflag=[0, 0, 1, 2],
        linestatus=[0, 0, 1, 1],
        shipdate=[d(1994, 1, 1), d(1994, 12, 31), d(1994, 6, 1), d(1998, 11, 30)])
    t = tbl.build_table(4, 1)
    for k, v in cols.items():
        setattr(t, k, np.array(v, dtype=np.int64))
    return t


def test_q6_by_hand():
    t, q6 = small(), plan("q6")
    # row 0 passes; row 1: quantity 30; row 2: discount 0.09; row 3: 1998
    assert q6.reference(t, q6.DEFAULTS) == [(("dec", 600000, 4),)]
    # the year's first day is in, the next year's first day is out
    assert q6.reference(t, dict(q6.DEFAULTS, quantity=31)) == [
        (("dec", 600000 + 1200000, 4),)]
    assert q6.reference(t, dict(q6.DEFAULTS, year=1995)) == [(None,)]


def test_q1_by_hand():
    t, q1 = small(), plan("q1")
    # delta 90: ship date <= 1998-09-02, so row 3 is out
    got = q1.reference(t, q1.DEFAULTS)
    assert got == [
        (("dec", 4000, 2), ("dec", 300000, 2),
         ("dec", 100000 * 94 + 200000 * 94, 4),
         ("dec", 100000 * 94 * 100 + 200000 * 94 * 108, 6),
         2, ("dec", 4000, 2), 2, ("dec", 300000, 2), 2, ("dec", 12, 2), 2, b"A", b"F"),
        (("dec", 2000, 2), ("dec", 10400000, 2), ("dec", 10400000 * 91, 4),
         ("dec", 10400000 * 91 * 102, 6),
         1, ("dec", 2000, 2), 1, ("dec", 10400000, 2), 1, ("dec", 9, 2), 1, b"N", b"O"),
    ]
    assert q1.cutoff_day({"delta_days": 1}) == tbl.day(1998, 11, 30)
    assert len(q1.reference(t, {"delta_days": 1})) == 3


def test_generator_follows_dbgen():
    t = tbl.build_table(60_000, 5, scale_factor=1.0)
    assert (np.diff(t.handle) == 1).all() and t.handle[0] == 1
    # 8 of every 32 order keys, 1-7 lines an order, numbered from 1
    assert ((t.orderkey - 1) % 32 < 8).all()
    assert t.linenumber.min() == 1 and t.linenumber.max() == 7
    assert 3.8 < len(t) / len(np.unique(t.orderkey)) < 4.2
    assert t.partkey.min() >= 1 and t.partkey.max() <= 200_000
    assert t.suppkey.min() >= 1 and t.suppkey.max() <= 10_000
    retail = 90000 + (t.partkey // 10) % 20001 + 100 * (t.partkey % 1000)
    assert (t.extendedprice == t.quantity // 100 * retail).all()
    assert set(np.unique(t.quantity)) == {q * 100 for q in range(1, 51)}
    assert set(np.unique(t.discount)) == set(range(11))
    assert set(np.unique(t.tax)) == set(range(9))
    # dates hang on the order's date; flag and status follow 1995-06-17
    assert ((t.receiptdate - t.shipdate >= 1) & (t.receiptdate - t.shipdate <= 30)).all()
    assert t.shipdate.min() > tbl.START_DAY and t.shipdate.max() <= tbl.END_DAY - 151 + 121
    assert ((t.linestatus == 1) == (t.shipdate > tbl.CURRENT_DAY)).all()
    assert ((t.returnflag == 1) == (t.receiptdate > tbl.CURRENT_DAY)).all()
    assert t.comment_len.min() == 10 and t.comment_len.max() == 43
    # the same seed gives the same rows, another seed other rows
    again = tbl.build_table(60_000, 5, scale_factor=1.0)
    assert all((getattr(t, c) == getattr(again, c)).all() for c in ("partkey", "shipdate"))
    assert (tbl.build_table(60_000, 6, scale_factor=1.0).partkey != t.partkey).any()


def test_rows_are_what_the_program_encodes_and_decodes():
    tbl.selfcheck(101, n=512, seed=2**31 + 5)
    values = tbl.encode_values(tbl.build_table(2000, 9))
    assert 137 <= min(map(len, values)) and max(map(len, values)) <= 192
    assert tbl.pack_days([tbl.day(1995, 6, 17)])[0] == ((1995 * 13 + 6) << 46) | (17 << 41)
