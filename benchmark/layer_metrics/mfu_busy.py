"""``mfu_busy`` (layer ``device``): the operations the forward and backward
passes of a step require (``benchmark/flops.py``: no recomputation, the
embedding lookup not a matmul) over the device-busy time of a step in the
free stretch times the chip's bfloat16 peak, in percent.  What the model
code achieves while the device runs; plain MFU also pays for idle time."""


def read(ctx):
    if not ctx.free_steps or not ctx.busy_s:
        return None
    busy_per_step = ctx.busy_s / ctx.free_steps
    return 100.0 * ctx.step_flops["flops"] / (
        busy_per_step * ctx.peaks["bf16_flops_per_s"])
