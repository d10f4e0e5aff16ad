"""``gqa_attn_device_ms`` (layer ``models``): self time per step of the
gradient program's device operations under ``bf.attn.*`` (forward, remat
recompute and transpose of the plain attention branch: the q and packed k/v
projections, the per-head QK norm, the rotary embedding, the K/V fan-out
with the flash kernels, the output projection), free stretch, first chip.
The line it prints gives the five parts."""

from benchmark import spec


def read(ctx):
    common = spec.load_module("layer_metrics/lfm2_common.py")
    return common.parts_ms(ctx, "gqa_attn_device_ms", common.ATTN)
