"""What the four readers of the ``ling3-kda-s4096-1chip`` cell share: the
gradient program's device time under the scopes of Kimi Delta Attention
(``bf.kda.*``), of gated latent attention (``bf.mla.*``) and of a held share
of group-limited experts with a shared expert (``bf.moe*``), and the cost of
the chunked rule and of the kernel calls at this configuration's shapes
(``benchmark/flops_ling.py``).

The scopes are those of ``models/transformer.py`` (``KimiDeltaMixer``:
``bf.kda.qkv``, ``bf.kda.conv``, ``bf.kda.gate``, ``bf.kda.chunk``,
``bf.kda.norm``, ``bf.kda.out``; ``LatentAttention``: ``xing_common.MLA``
and ``bf.mla.gate``) and of ``parallel/moe.py`` (the four of
``moe_common.py`` and ``bf.moe.shared``; the router's group step runs under
``bf.moe.route``); forward, remat recompute and transpose carry the names
alike.  ``program_common.py`` assigns each device operation of the gradient
program to a scope, ``moe_common.py`` tells the bare ``bf.moe`` apart and
``xing_common.py`` keeps the reduction on the context; none is edited.  A
program without these scopes (the parent of PR 50) yields None everywhere.

The rule is no kernel: its time is the self time under ``bf.kda.chunk``,
held to ``flops_ling.kda_chunk`` (a KDA mixer's forward, its remat recompute
and its transpose, each one chunked pass).  The grouped products
(``bf_moe_gmm_*``) are held to ``flops_ling.grouped_product`` at the rows an
even router sends to the experts held here (``tokens * num_experts_per_tok *
num_experts / router_width``: 64 an expert at 4096 tokens; the kernels visit
the held experts' window only) and at the matrices of the held experts.  Off
the TPU (the rehearsal) the kernels run in the Pallas interpreter and no
event is a kernel call.
"""

from __future__ import annotations

from benchmark import flops, flops_ling, spec

KDA = ("bf.kda.qkv", "bf.kda.conv", "bf.kda.gate", "bf.kda.chunk",
       "bf.kda.norm", "bf.kda.out")

_xing = spec.load_module("layer_metrics/xing_common.py")
_moe = spec.load_module("layer_metrics/moe_common.py")
MLA = _xing.MLA + ("bf.mla.gate",)
# the reduction by scope, kept on the context; the expert layer's parts with
# the shared expert; the kernels' events
grad_scope_ms, parts_ms = _xing.grad_scope_ms, _xing.parts_ms
moe_parts_ms, flash_events = _xing.moe_parts_ms, _xing.flash_events
product_events = _moe.product_events


def tokens(ctx) -> int:
    batch = ctx.cell.traffic["batch"]
    return batch["sequences"] * batch["seq_len"]


def chunk_least_s(ctx) -> tuple:
    """``(seconds, bound)``: the least a step's chunked rules can take, every
    KDA mixer's forward, its remat recompute where the model recomputes its
    blocks, and its transpose, each a chunked pass at its operations and
    bytes; ``bound`` names what sets the forward pass."""
    config, batch = ctx.cell.config, ctx.cell.traffic["batch"]
    args = config["model"]["args"]
    passes = ["fwd", "bwd"] + (["fwd"] if args.get("remat") else [])
    least = [flops.roofline_seconds(flops_ling.kda_chunk(
        kind, config=config, tokens=batch["seq_len"],
        chunk=args["kda_chunk"]), ctx.peaks) for kind in passes]
    return (flops_ling.layer_types(config).count("kda") * batch["sequences"]
            * sum(seconds for seconds, _ in least), least[0][1])


def product_cost(ctx, event) -> tuple:
    """``(kind, cost)`` of one grouped product from its name, its own
    result and the cell's sizes, or ``(None, None)`` where the result is no
    product of this cell's sizes."""
    config = ctx.cell.config
    hidden, width = config["hidden_size"], config["moe_intermediate_size"]
    m = _moe._RESULT.match(event.what)     # its names for event and result
    if not m:
        return None, None
    dims = [int(d) for d in m.group(2).split(",")]
    out_itemsize = _moe._ITEMSIZE.get(m.group(1), 4)
    by_rows = _moe._PRODUCT.match(event.name).group(1) != "drhs"
    if by_rows and len(dims) == 2 and dims[1] in (hidden, width):
        kind, outer = "rows", dims[1]
        inner = width if outer == hidden else hidden
    elif not by_rows and dims[:1] == [config["num_experts"]] \
            and sorted(dims[1:]) == sorted((hidden, width)):
        kind, inner, outer = "weights", dims[1], dims[2]
    else:
        return None, None
    return kind, flops_ling.grouped_product(
        kind, config=config, tokens=tokens(ctx), inner=inner, outer=outer,
        out_itemsize=out_itemsize)
