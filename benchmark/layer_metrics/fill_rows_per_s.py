"""cold fill: rows over the wall time of the set-up's first pass of each of
the mix's plans, which builds the image of the columns that plan scans in
every region (MVCC resolve, row decode, placement) and compiles or fetches the
plan's program."""


def read(ctx):
    s = ctx["setup"]
    return s["fill_rows"] / s["fill_s"] if s.get("fill_s") else None
