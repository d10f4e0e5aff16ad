"""What the six readers of the ``lfm2-s8192-1chip`` cell share: the gradient
program's device time under the scopes of the gated short convolution
(``bf.sconv.*``), of plain attention (``bf.attn.*``) and of a held expert
share without a shared expert (``bf.moe*``), and the cost of the kernel
calls at this configuration's shapes (``benchmark/flops_lfm2.py``).

The scopes are those of ``models/transformer.py`` (``ShortConv``:
``bf.sconv.in``, ``bf.sconv.conv``, ``bf.sconv.out``; ``Block``'s plain
attention branch: ``bf.attn.qkv``, ``bf.attn.norm``, ``bf.attn.rope``,
``bf.attn.attend``, ``bf.attn.out``) and of ``parallel/moe.py`` (the four of
``moe_common.py``); forward, remat recompute and transpose carry the names
alike.  ``program_common.py`` assigns each device operation of the gradient
program to a scope, ``moe_common.py`` tells the bare ``bf.moe`` apart and
``xing_common.py`` keeps the reduction on the context; none is edited.  A
program without these scopes (the parent of PR 34) yields None everywhere.

The flash kernels are told apart by the names the library gives them
(``bf_flash_fwd.<n>``, ``bf_flash_dq.<n>``, ``bf_flash_dkv.<n>``) and held
to ``flops_lfm2.flash_kernel``: 32 heads of 64 (the 8 K/V heads are repeated
before the kernel).  The grouped products (``bf_moe_gmm_*``) are held to
``flops_lfm2.grouped_product`` at the rows an even router sends to the
experts held here (``tokens * num_experts_per_tok * num_experts /
router_width``: the kernels visit the held experts' rows only, and the
event's own result has all ``tokens * num_experts_per_tok`` rows) and at the
matrices of the held experts.  Off the TPU (the rehearsal) the kernels run
in the Pallas interpreter and no event is a kernel call.
"""

from __future__ import annotations

from benchmark import flops_lfm2, spec

SCONV = ("bf.sconv.in", "bf.sconv.conv", "bf.sconv.out")
ATTN = ("bf.attn.qkv", "bf.attn.norm", "bf.attn.rope", "bf.attn.attend",
        "bf.attn.out")
MOE_PARTS = ("route", "permute", "experts", "unattributed")


_xing = spec.load_module("layer_metrics/xing_common.py")
_moe = spec.load_module("layer_metrics/moe_common.py")
# the reduction by scope, kept on the context, and the kernels' events
grad_scope_ms, parts_ms = _xing.grad_scope_ms, _xing.parts_ms
flash_events, product_events = _xing.flash_events, _moe.product_events


def moe_parts_ms(ctx) -> dict | None:
    """``{part: ms a step}`` of the expert layer's share; this layer has no
    shared expert, and anything under ``bf.moe.shared`` would be counted as
    unattributed."""
    parts = _xing.moe_parts_ms(ctx)
    if parts is None:
        return None
    out = {part: parts[part] for part in MOE_PARTS}
    out["unattributed"] += parts.get("shared", 0.0)
    return out


def tokens(ctx) -> int:
    batch = ctx.cell.traffic["batch"]
    return batch["sequences"] * batch["seq_len"]


def sconv_gate_least_s(ctx) -> float:
    """The least seconds a step's gates and convolutions can take: every
    conv layer's forward, its remat recompute where the model recomputes
    its blocks, and its transpose, each at its bytes over the HBM peak (the
    operations are two orders of magnitude under the compute peak)."""
    config = ctx.cell.config
    passes = ["fwd", "bwd"] + (
        ["fwd"] if config["model"]["args"].get("remat") else [])
    layers = config["layer_types"].count("conv")
    return layers * sum(
        max(cost["bytes"] / ctx.peaks["hbm_bytes_per_s"],
            cost["flops"] / ctx.peaks["bf16_flops_per_s"])
        for cost in (flops_lfm2.sconv_gate(kind, tokens=tokens(ctx),
                                           config=config)
                     for kind in passes))


def flash_cost(ctx, kind: str) -> dict:
    batch = ctx.cell.traffic["batch"]
    return flops_lfm2.flash_kernel(kind, config=ctx.cell.config,
                                   batch=batch["sequences"],
                                   seq=batch["seq_len"])


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
    return kind, flops_lfm2.grouped_product(
        kind, config=config, tokens=tokens(ctx), inner=inner, outer=outer,
        out_itemsize=out_itemsize)
