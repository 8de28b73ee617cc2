"""endpoint and router: host unpacking of pulled states into a SelectResponse
(stage ``device.finalize``) plus the response's serialisation (stage
``copr.encode``, ``copr/endpoint.py:_encode_response``), per coprocessor task.
A batch is unpacked in one stage, so for batched tasks the first part is the
batch's time over its riders."""

from benchmark.layer_metrics._stages import stage_ms_per_task


def read(ctx):
    return stage_ms_per_task(ctx, "device.finalize", "copr.encode")
