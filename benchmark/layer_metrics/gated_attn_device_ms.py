"""``gated_attn_device_ms`` (layer ``models``): self time per step of the
gradient program's device operations under ``bf.attn.*`` where the layer
gates its heads (forward, remat recompute and transpose of the full
attention layers: the projections at 48 query heads of 128, YaRN's rotary
embedding over half a head, the K/V fan-out with the causal flash kernels,
the per-head gate, the output projection), free stretch, first chip.  The
line it prints gives the parts, and a second one the full layers' flash
kernels' own time and share of their roofline.  None where the program has
no ``bf.attn.gate``."""

from benchmark import spec


def read(ctx):
    common = spec.load_module("layer_metrics/laguna_common.py")
    if "bf.attn.gate" not in common._xing.grad_scope_ms(ctx):
        return None
    total = common.parts_ms(ctx, "gated_attn_device_ms", common.ATTN)
    share = common.flash_share(ctx, "full_attention", "gated_attn_device_ms")
    if share is not None:
        print(f"  gated_attn_device_ms: the full layers' flash kernels "
              f"{share[1]:.3f} ms a step at {share[0]:.1f}% of their "
              "roofline")
    return total
