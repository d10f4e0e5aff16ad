"""``moe_permute_device_ms`` (layer ``parallel.moe``): self time per step of
the gradient program's device operations under ``bf.moe.dispatch`` and
``bf.moe.combine``: the rows gathered into expert order and back into token
order, and the weighted sum over each token's experts; forward, remat
recompute and transpose alike; free stretch, first chip."""

from benchmark import spec


def read(ctx):
    return spec.load_module("layer_metrics/moe_common.py").part_ms(
        ctx, "permute")
