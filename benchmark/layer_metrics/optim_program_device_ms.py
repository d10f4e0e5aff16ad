"""``optim_program_device_ms`` (layer ``optim``): device-busy time inside the
executions of ``jit_bf_optim_step`` per step, free stretch, first chip.  The
inside twin of ``optim_device_ms``."""

from benchmark import spec


def read(ctx):
    common = spec.load_module("layer_metrics/program_common.py")
    return common.program_device_ms(ctx, common.STEP_PROGRAM)
