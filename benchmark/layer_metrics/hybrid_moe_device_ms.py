"""``hybrid_moe_device_ms`` (layer ``parallel.moe``): self time per step of
the gradient program's device operations under ``bf.moe`` where the layer
holds a share of the experts and has no shared expert (forward, remat
recompute and transpose of routing over all experts, the permutations and
the grouped products over the held experts' rows), free stretch, first chip.
The line it prints gives route, permute, experts and unattributed."""

from benchmark import spec


def read(ctx):
    parts = spec.load_module("layer_metrics/lfm2_common.py").moe_parts_ms(ctx)
    if parts is None:
        return None
    print("  hybrid_moe_device_ms: ms a step: " + ", ".join(
        f"{part} {ms:.3f}" for part, ms in parts.items())
        + f"; sum {sum(parts.values()):.3f}")
    return sum(parts.values())
