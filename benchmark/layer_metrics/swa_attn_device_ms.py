"""``swa_attn_device_ms`` (layer ``models``): self time per step of the
gradient program's device operations under ``bf.swa.*`` (forward, remat
recompute and transpose of the sliding-window attention layers: the q and
packed k/v projections at the window layers' own head count, the rotary
embedding over the whole head, the K/V fan-out with the windowed flash
kernels, the per-head gate, the output projection), free stretch, first
chip.  The line it prints gives the parts."""

from benchmark import spec


def read(ctx):
    common = spec.load_module("layer_metrics/laguna_common.py")
    return common.parts_ms(ctx, "swa_attn_device_ms", common.SWA)
