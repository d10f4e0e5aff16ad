"""``ssm_device_ms`` (layer ``models``): self time per step of the gradient
program's device operations under ``bf.ssm.*`` (forward, remat recompute and
transpose of the Mamba-2 mixers: the in-projection to 10304 columns, the
causal depthwise convolution with its bias and SiLU, the time steps and the
chunked scan with its skip, the gated norm in groups, the out-projection),
free stretch, first chip.  The line it prints gives the five parts and,
from ``bf_ssm_chunks_total``, the chunks the scan calls were traced over."""

from benchmark import spec


def read(ctx):
    common = spec.load_module("layer_metrics/twotower_common.py")
    total = common.parts_ms(ctx, "ssm_device_ms", common.SSM)
    chunks = spec.load_module("layer_metrics/program_common.py").counter(
        ctx, "bf_ssm_chunks_total")
    if total is not None and chunks:
        print(f"  ssm_device_ms: bf_ssm_chunks_total {chunks:.0f} (the "
              "chunks of every traced scan call: primal, the remat "
              "recompute and every retrace)")
    return total
