"""``kda_device_ms`` (layer ``models``): self time per step of the gradient
program's device operations under ``bf.kda.*`` (forward, remat recompute and
transpose of the Kimi Delta Attention mixers: the q, k and v matrix to 12288
columns, the three causal depthwise convolutions with SiLU and the lengths
of q and k, the decay gate's matrix with the bounded decays and ``beta``,
the chunked delta rule, the gated norm a head with its matrix, the output
projection), free stretch, first chip.  The line it prints gives the six
parts and, from ``bf_kda_chunks_total``, the chunks the rule's calls were
traced over.  None where the program has no ``bf.kda.*`` scope."""

from benchmark import spec


def read(ctx):
    common = spec.load_module("layer_metrics/ling_common.py")
    total = common.parts_ms(ctx, "kda_device_ms", common.KDA)
    chunks = spec.load_module("layer_metrics/program_common.py").counter(
        ctx, "bf_kda_chunks_total")
    if total is not None and chunks:
        print(f"  kda_device_ms: bf_kda_chunks_total {chunks:.0f} (the "
              "chunks of every traced call of the rule: primal, the remat "
              "recompute and every retrace)")
    return total
