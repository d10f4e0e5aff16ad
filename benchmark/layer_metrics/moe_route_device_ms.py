"""``moe_route_device_ms`` (layer ``parallel.moe``): self time per step of
the gradient program's device operations under ``bf.moe.route``: the router
matmul, softmax, top-k, the stable sort of the assignments by expert and the
per-expert counts; forward, remat recompute and transpose alike; free
stretch, first chip."""

from benchmark import spec


def read(ctx):
    return spec.load_module("layer_metrics/moe_common.py").part_ms(
        ctx, "route")
