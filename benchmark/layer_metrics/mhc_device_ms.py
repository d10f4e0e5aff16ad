"""``mhc_device_ms`` (layer ``models``): self time per step of the gradient
program's device operations under ``bf.mhc.*`` (forward, remat recompute and
transpose of the hyper-connection maps around every sub-layer: the float32
norm and map, the Sinkhorn rounds, the read-in and the write-back of the
residual streams), free stretch, first chip.  The line it prints gives the
three parts."""

from benchmark import spec


def read(ctx):
    common = spec.load_module("layer_metrics/xing_common.py")
    return common.parts_ms(ctx, "mhc_device_ms", common.MHC)
