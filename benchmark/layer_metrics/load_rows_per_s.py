"""write path: rows loaded through kv_prewrite/kv_commit over the wall time
of the set-up's load, on the loading client's clock."""


def read(ctx):
    s = ctx["setup"]
    return s["rows"] / s["load_s"] if s.get("load_s") else None
