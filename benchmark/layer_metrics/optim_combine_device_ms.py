"""``optim_combine_device_ms`` (layer ``ops.collective``): self time per step
of the optimizer program's device operations under ``bf.optim.combine``: the
``collective-permute`` from its start to the end of its done, and the scaling
and adding of ``collective._apply_rounds``; free stretch, first chip."""

from benchmark import spec


def read(ctx):
    common = spec.load_module("layer_metrics/program_common.py")
    return common.scope_device_ms(ctx, common.STEP_PROGRAM,
                                  "bf.optim.combine")
