"""``flash_kernel_ms`` (layer ``ops.flash_attention``): summed device time of
the flash attention forward and backward kernels per step, free stretch,
first chip.  With per-block remat the forward runs twice a layer."""

from benchmark import spec


def read(ctx):
    events = spec.load_module("layer_metrics/flash_common.py").kernel_events(
        ctx)
    if not events or not ctx.free_steps:
        return None
    return sum(e.duration for e, _ in events) / ctx.free_steps * 1e-6
