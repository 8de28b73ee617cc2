"""device: memory_stats()["peak_bytes_in_use"] of the fullest chip, read once
the window has closed."""


def read(ctx):
    b = ctx.get("memory_peak_bytes")
    return b / 1e6 if b else None
