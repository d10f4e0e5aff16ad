"""``input_wait_ms`` (layer ``data``): time per step the training thread
spent blocked on the next batch, the sum of the ``bf.data.wait`` spans of
``data.prefetch_to_device`` in the free stretch over its steps.  None where
the pool is on the device and nothing goes through the input pipeline."""

from benchmark import spec


def read(ctx):
    waits = spec.load_module("layer_metrics/program_common.py").spans_in_free(
        ctx, "bf.data.wait")
    if not waits or not ctx.free_steps:
        return None
    return sum(s.duration for s in waits) / ctx.free_steps * 1e-6
