"""``small_moe_device_ms`` (layer ``parallel.moe``): self time per step of
the gradient program's device operations under ``bf.moe`` where the layer
holds a share of softmax-routed experts of width 512 beside a shared expert
(forward, remat recompute and transpose of routing over all 256 experts, the
permutations, the grouped products over the held experts' window and the
shared expert's dense products), free stretch, first chip.  The line it
prints gives route, permute, experts, shared and unattributed."""

from benchmark import spec


def read(ctx):
    parts = spec.load_module(
        "layer_metrics/laguna_common.py").moe_parts_ms(ctx)
    if parts is None:
        return None
    print("  small_moe_device_ms: ms a step: " + ", ".join(
        f"{part} {ms:.3f}" for part, ms in parts.items())
        + f"; sum {sum(parts.values()):.3f}")
    return sum(parts.values())
