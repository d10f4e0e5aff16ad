"""``eighth_moe_device_ms`` (layer ``parallel.moe``): self time per step of
the gradient program's device operations under ``bf.moe`` where the layer
holds an eighth of 128 sigmoid-routed SwiGLU experts of 768 beside two
shared experts as one SwiGLU of 1536 (forward, remat recompute and
transpose of routing over all 128 experts, the permutations with the
window's rows summed into their tokens by ``bf_moe_token_sum``, three
grouped products a pass over the held experts' window and the shared
experts' dense products), free stretch, first chip.  The line it prints
gives route, permute, experts, shared and unattributed."""

from benchmark import spec


def read(ctx):
    parts = spec.load_module(
        "layer_metrics/kanana_common.py").moe_parts_ms(ctx)
    if parts is None:
        return None
    print("  eighth_moe_device_ms: ms a step: " + ", ".join(
        f"{part} {ms:.3f}" for part, ms in parts.items())
        + f"; sum {sum(parts.values()):.3f}")
    return sum(parts.values())
