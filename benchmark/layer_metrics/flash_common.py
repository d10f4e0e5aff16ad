"""What the two flash attention readers share: which device events are the
kernel's, and which of its three kernels each is.

The Mosaic kernels are ``tpu_custom_call`` instructions of the gradient
program, named after their scope (``block_0.4``) and not after the kernel.
The harness hands over every such instruction with its operand and result
counts, and the kernels of ``ops/flash_attention.py`` tell themselves apart
by those: forward (q, k, v -> o, lse), dq (q, k, v, do, lse, delta -> dq),
dk/dv (the same six -> dk, dv).
"""

from benchmark import flops

KINDS = {(3, 2): "fwd", (6, 1): "dq", (6, 2): "dkv"}


def kernel_events(ctx) -> list:
    """``(event, kind)`` of every flash kernel call of the free stretch on
    the first chip."""
    kinds = {name: KINDS.get((call["operands"], call["results"]))
             for name, call in ctx.mosaic_calls.items()
             if call["op_name"].endswith("pallas_call")}
    return [(e, kinds[e.name]) for e in ctx.free_ops() if kinds.get(e.name)]


def call_cost(ctx, kind: str) -> dict:
    """Operations and bytes of one call, from the cell's shapes: keys and
    values are repeated to the query heads before the kernel."""
    config, batch = ctx.cell.config, ctx.cell.traffic["batch"]
    heads = config["num_attention_heads"]
    return flops.flash_kernel(
        kind, batch=batch["sequences"], seq=batch["seq_len"], heads=heads,
        head_dim=config["hidden_size"] // heads, causal=True, itemsize=2)
