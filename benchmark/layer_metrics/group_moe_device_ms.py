"""``group_moe_device_ms`` (layer ``parallel.moe``): self time per step of
the gradient program's device operations under ``bf.moe`` where the layer
holds 8 of 512 sigmoid-routed SwiGLU experts of 768 whose choice is limited
to 4 of 8 groups, beside one shared expert (forward, remat recompute and
transpose of routing over all 512 experts with the groups' scores under
``bf.moe.route``, the permutations with the window's rows summed into their
tokens by ``bf_moe_token_sum``, three grouped products a pass over the held
experts' window and the shared expert's dense products), free stretch, first
chip.  The line it prints gives route, permute, experts, shared and
unattributed and, from ``bf_moe_route_groups_total``, the groups the traced
routes scored.  None where no traced route scored groups."""

from benchmark import spec


def read(ctx):
    common = spec.load_module("layer_metrics/ling_common.py")
    program = spec.load_module("layer_metrics/program_common.py")
    kept = ctx.cell.config["topk_group"]
    groups = program.counter(ctx, "bf_moe_route_groups_total",
                             kept=str(kept))
    parts = common.moe_parts_ms(ctx)
    if parts is None or not groups:
        return None
    print("  group_moe_device_ms: ms a step: " + ", ".join(
        f"{part} {ms:.3f}" for part, ms in parts.items())
        + f"; sum {sum(parts.values()):.3f}; bf_moe_route_groups_total"
        f"{{kept={kept}}} {groups:.0f} (the groups of every traced route)")
    return sum(parts.values())
