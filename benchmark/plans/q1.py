"""TPC-H Q1 (cl. 2.4.1) as TiDB pushes it down: one coprocessor task per
region that scans the seven columns the query reads, keeps
``l_shipdate <= date '1998-12-01' - interval DELTA day`` and aggregates by
(l_returnflag, l_linestatus): sum(l_quantity), sum(l_extendedprice),
sum(l_extendedprice * (1 - l_discount)),
sum(l_extendedprice * (1 - l_discount) * (1 + l_tax)), avg(l_quantity),
avg(l_extendedprice), avg(l_discount), count(*).  A pushed-down avg answers
with its count and its sum.  Substitution parameter (cl. 2.4.1.3): DELTA in
[60, 120]; 90 validates."""

from benchmark import table as tbl

READS = ("quantity", "extendedprice", "discount", "tax", "returnflag",
         "linestatus", "shipdate")
DEFAULTS = {"delta_days": 90}
# the narrowest fixed widths the value ranges need: quantity 1, price 4,
# discount 1, tax 1, return flag 1, line status 1, ship date 2 bytes a row
BYTES_PER_ROW = 11
# groups come in no stated order
UNORDERED = True


def cutoff_day(p) -> int:
    return tbl.day(1998, 12, 1) - int(p["delta_days"])


def dag(table_id, p):
    from tikv_tpu.copr.aggr import AggDescriptor
    from tikv_tpu.copr.dag import Aggregation, DagRequest, Selection, TableScan
    from tikv_tpu.copr.datatypes import EvalType
    from tikv_tpu.copr.rpn import Constant, call, col, const_decimal

    qty, price, disc, tax, rf, ls, ship = (col(i) for i in range(len(READS)))
    cutoff = Constant(int(tbl.pack_days(cutoff_day(p))), EvalType.DATETIME)
    disc_price = call("multiply", price, call("minus", const_decimal(1, 0), disc))
    charge = call("multiply", disc_price, call("plus", const_decimal(1, 0), tax))
    aggs = [
        AggDescriptor("sum", qty), AggDescriptor("sum", price),
        AggDescriptor("sum", disc_price), AggDescriptor("sum", charge),
        AggDescriptor("avg", qty), AggDescriptor("avg", price),
        AggDescriptor("avg", disc), AggDescriptor("count", None),
    ]
    return DagRequest(executors=[
        TableScan(table_id, tbl.schema(READS)),
        Selection([call("le", ship, cutoff)]),
        Aggregation([rf, ls], aggs)])


def work(n_rows):
    return n_rows, n_rows * BYTES_PER_ROW


def reference(t, p):
    m = t.shipdate <= cutoff_day(p)
    out = []
    for rf, flag in enumerate(tbl.WORDS["returnflag"]):
        for ls, status in enumerate(tbl.WORDS["linestatus"]):
            g = m & (t.returnflag == rf) & (t.linestatus == ls)
            n = int(g.sum())
            if not n:
                continue
            qty, price = t.quantity[g], t.extendedprice[g]
            disc_price = price * (100 - t.discount[g])
            qty_sum, price_sum = int(qty.sum()), int(price.sum())
            out.append((
                ("dec", qty_sum, 2), ("dec", price_sum, 2),
                ("dec", int(disc_price.sum()), 4),
                ("dec", int((disc_price * (100 + t.tax[g])).sum()), 6),
                n, ("dec", qty_sum, 2), n, ("dec", price_sum, 2),
                n, ("dec", int(t.discount[g].sum()), 2), n, flag, status))
    return out
