"""device programs: programs the backend built or fetched from the persistent
cache between the window's first instant and its close (expected 0)."""


def read(ctx):
    return float(ctx["compiles"]["after"] - ctx["compiles"]["before"])
