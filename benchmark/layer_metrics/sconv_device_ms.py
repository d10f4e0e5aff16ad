"""``sconv_device_ms`` (layer ``models``): self time per step of the gradient
program's device operations under ``bf.sconv.*`` (forward, remat recompute
and transpose of the gated short convolutions: the input projection, both
gates with the taps, the output projection), free stretch, first chip.  The
line it prints gives the three parts."""

from benchmark import spec


def read(ctx):
    common = spec.load_module("layer_metrics/lfm2_common.py")
    return common.parts_ms(ctx, "sconv_device_ms", common.SCONV)
