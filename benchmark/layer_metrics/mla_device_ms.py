"""``mla_device_ms`` (layer ``models``): self time per step of the gradient
program's device operations under ``bf.mla.*`` (forward, remat recompute and
transpose of the latent-attention sub-layers: the query bottleneck, the
latent and its expansion, the rotary part, the key's assembly with the flash
kernels, the output projection), free stretch, first chip.  The line it
prints gives the five parts."""

from benchmark import spec


def read(ctx):
    common = spec.load_module("layer_metrics/xing_common.py")
    return common.parts_ms(ctx, "mla_device_ms", common.MLA)
