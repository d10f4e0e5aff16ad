"""``moe_expert_device_ms`` (layer ``parallel.moe``): self time per step of
the gradient program's device operations under ``bf.moe.experts``: the three
grouped products over the ragged groups and the SwiGLU between them;
forward, remat recompute and transpose alike; free stretch, first chip."""

from benchmark import spec


def read(ctx):
    return spec.load_module("layer_metrics/moe_common.py").part_ms(
        ctx, "experts")
