"""Process start to the window's first instant: engine build, store start,
split, load, cold fill, compile or cache load, warm-up."""


def read(ctx):
    return ctx["setup"]["setup_s"]
