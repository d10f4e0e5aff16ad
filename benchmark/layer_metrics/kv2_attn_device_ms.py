"""``kv2_attn_device_ms`` (layer ``models``): self time per step of the
gradient program's device operations under ``bf.attn.*`` where 32 query
heads of 128 read 2 K/V heads and nothing encodes a position (forward,
remat recompute and transpose of the one attention block: the q projection
from 2688 to 4096 and the packed k/v projection to 512, the sixteen-fold K/V
fan-out with the causal flash kernels, the output projection), free
stretch, first chip.  The line it prints gives the parts (``norm`` and
``rope`` read 0: the block has neither), and a second one the causal flash
kernels' own time and share of their roofline.  None where the program has
no ``bf.attn.*`` scope."""

from benchmark import spec


def read(ctx):
    common = spec.load_module("layer_metrics/twotower_common.py")
    total = common.parts_ms(ctx, "kv2_attn_device_ms", common.ATTN)
    share = common.flash_share(ctx, "kv2_attn_device_ms")
    if total is not None and share is not None:
        print(f"  kv2_attn_device_ms: the causal flash kernels "
              f"{share[1]:.3f} ms a step at {share[0]:.1f}% of their "
              "roofline")
    return total
