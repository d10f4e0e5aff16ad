"""``relu2_moe_device_ms`` (layer ``parallel.moe``): self time per step of
the gradient program's device operations under ``bf.moe`` where the layer
holds a share of sigmoid-routed un-gated experts (``down(relu(up x)^2)``,
width 1856) beside an un-gated shared expert of 3712 (forward, remat
recompute and transpose of routing over all 128 experts, the permutations,
two grouped products a pass over the held experts' window and the shared
expert's dense products), free stretch, first chip.  The line it prints
gives route, permute, experts, shared and unattributed."""

from benchmark import spec


def read(ctx):
    parts = spec.load_module(
        "layer_metrics/twotower_common.py").moe_parts_ms(ctx)
    if parts is None:
        return None
    print("  relu2_moe_device_ms: ms a step: " + ", ".join(
        f"{part} {ms:.3f}" for part, ms in parts.items())
        + f"; sum {sum(parts.values()):.3f}")
    return sum(parts.values())
