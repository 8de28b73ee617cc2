"""endpoint and router: tasks answered with from_device true over tasks
answered, as the client saw them.  A task that the cost router chose to serve
on the CPU is an answer the system's users get, and lowers this."""

from benchmark import reduce


def read(ctx):
    answered = [t for _i, _q, t in reduce.tasks(ctx["log"]) if "digest" in t]
    if not answered:
        return None
    return 100.0 * sum(1 for t in answered if t.get("from_device")) / len(answered)
