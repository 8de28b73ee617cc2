"""cold fill: rows of row format 2 that the array decoder took
(``tikv_coprocessor_rowv2_decode_rows_total{path="vector"}``,
``tikv_tpu/copr/rowv2.py``) over all rows decoded, by any path (``uniform``: a
block of one layout, a reshape; ``walk``: per row), since the store started:
the fills lie in the set-up, before the window's first snapshot, so the total
is read and not what moved.  A program without the counter (the parent of the
PR that brought it) has no such series, and the reader gives None."""

from benchmark.counters import total

SERIES = "tikv_coprocessor_rowv2_decode_rows_total"


def read(ctx):
    n = total(ctx["after"], SERIES)
    if not n:
        return None
    return 100.0 * total(ctx["after"], SERIES, path="vector") / n
