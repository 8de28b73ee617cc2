"""Rows that the right tasks completed inside the window had to read (a
region's rows for a whole-region plan, the limit for a limited scan), over the
whole window, on the client's clock."""

from benchmark import reduce


def read(ctx):
    return reduce.rows_per_s(ctx["log"], ctx["wrong"], ctx["work"], ctx["seconds"])
