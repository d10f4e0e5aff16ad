"""``optim_fuse_device_ms`` (layer ``optim``): self time per step of the
optimizer program's device operations under ``bf.optim.fuse`` (the tree
raveled into the flat buffer) and ``bf.optim.unfuse`` (the buffer split back
into leaves), with the copies the compiler makes for either; free stretch,
first chip."""

from benchmark import spec


def read(ctx):
    common = spec.load_module("layer_metrics/program_common.py")
    return common.scope_device_ms(ctx, common.STEP_PROGRAM,
                                  "bf.optim.fuse", "bf.optim.unfuse")
