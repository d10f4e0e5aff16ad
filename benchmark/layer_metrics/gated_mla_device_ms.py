"""``gated_mla_device_ms`` (layer ``models``): self time per step of the
gradient program's device operations under ``bf.mla.*`` where latent
attention has no query bottleneck and a head-wise output gate (forward,
remat recompute and transpose of the one query matrix to 32 heads of 192,
the latent of 512 with its rotary key and its expansion, the rotary part,
the key's assembly with the causal flash kernels, the gate ``sigmoid(y
W_gate)`` a head under ``bf.mla.gate``, the output projection from 4096),
free stretch, first chip.  The line it prints gives the six parts.  None
where the program has no ``bf.mla.gate`` scope: latent attention without
the gate is another cell's."""

from benchmark import spec


def read(ctx):
    common = spec.load_module("layer_metrics/ling_common.py")
    if "bf.mla.gate" not in common.grad_scope_ms(ctx):
        return None
    return common.parts_ms(ctx, "gated_mla_device_ms", common.MLA)
