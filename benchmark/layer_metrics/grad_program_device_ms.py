"""``grad_program_device_ms`` (layer ``models``): device-busy time inside the
executions of the gradient program (``jit_bf_rank_map_*`` on the device's
module line) per step, free stretch, first chip.  The inside twin of
``grad_device_ms``, which needs the blocked stretch to tell the programs
apart."""

from benchmark import spec


def read(ctx):
    common = spec.load_module("layer_metrics/program_common.py")
    return common.program_device_ms(ctx, common.GRAD_PROGRAM)
