"""TPC-H Q6 (cl. 2.4.6) as TiDB pushes it down: one coprocessor task per
region that scans the four columns the query reads and answers
sum(l_extendedprice * l_discount) where l_shipdate is in the year that starts
on DATE, l_discount is between DISCOUNT - 0.01 and DISCOUNT + 0.01 and
l_quantity < QUANTITY.  Substitution parameters (cl. 2.4.6.3): DATE is
January 1st of a year in [1993, 1997], DISCOUNT in [0.02, 0.09], QUANTITY 24
or 25; 1994, 0.06 and 24 validate."""

import numpy as np

from benchmark import table as tbl

READS = ("quantity", "extendedprice", "discount", "shipdate")
DEFAULTS = {"year": 1994, "discount_pct": 6, "quantity": 24}
# the narrowest fixed widths the value ranges need: quantity 1, price 4,
# discount 1, ship date 2 bytes a row
BYTES_PER_ROW = 8


def dag(table_id, p):
    from tikv_tpu.copr.aggr import AggDescriptor
    from tikv_tpu.copr.dag import Aggregation, DagRequest, Selection, TableScan
    from tikv_tpu.copr.datatypes import EvalType
    from tikv_tpu.copr.rpn import Constant, call, col, const_decimal

    qty, price, disc, ship = (col(i) for i in range(len(READS)))
    year, pct = int(p["year"]), int(p["discount_pct"])

    def date(y):
        return Constant(int(tbl.pack_days(tbl.day(y, 1, 1))), EvalType.DATETIME)

    conds = [
        call("ge", ship, date(year)),
        call("lt", ship, date(year + 1)),
        call("ge", disc, const_decimal(pct - 1, 2)),
        call("le", disc, const_decimal(pct + 1, 2)),
        call("lt", qty, const_decimal(int(p["quantity"]), 0)),
    ]
    aggs = [AggDescriptor("sum", call("multiply", price, disc))]
    return DagRequest(executors=[
        TableScan(table_id, tbl.schema(READS)), Selection(conds),
        Aggregation([], aggs)])


def work(n_rows):
    return n_rows, n_rows * BYTES_PER_ROW


def reference(t, p):
    year, pct = int(p["year"]), int(p["discount_pct"])
    m = ((t.shipdate >= tbl.day(year, 1, 1)) & (t.shipdate < tbl.day(year + 1, 1, 1))
         & (t.discount >= pct - 1) & (t.discount <= pct + 1)
         & (t.quantity < int(p["quantity"]) * 100))
    if not m.any():
        return [(None,)]
    return [(("dec", int(np.sum(t.extendedprice[m] * t.discount[m])), 4),)]
