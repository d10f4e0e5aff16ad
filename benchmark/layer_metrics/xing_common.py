"""What the five readers of the ``xing4-s4096-1chip`` cell share: the
gradient program's device time under the scopes of latent attention
(``bf.mla.*``), of the hyper-connection maps (``bf.mhc.*``) and of the held
expert share (``bf.moe*``), and the cost of the two kinds of kernel calls at
this configuration's shapes.

The scopes are those of ``models/transformer.py`` (``LatentAttention``:
``bf.mla.q``, ``bf.mla.kv``, ``bf.mla.rope``, ``bf.mla.attend``,
``bf.mla.out``; ``HyperConnection``: ``bf.mhc.map``, ``bf.mhc.sinkhorn``,
``bf.mhc.mix``) and of ``parallel/moe.py`` (the four of ``moe_common.py``
and ``bf.moe.shared``); forward, remat recompute and transpose carry the
names alike.  ``program_common.py`` assigns each device operation of the
gradient program to a scope and ``moe_common.py`` tells the bare ``bf.moe``
apart; neither is edited.  A program without these scopes (the parent of
PR 31) yields None everywhere.

The flash kernels are told apart by the names the library gives them
(``bf_flash_fwd.<n>``, ``bf_flash_dq.<n>``, ``bf_flash_dkv.<n>``) and are
held to ``flops_mla.flash_kernel`` at query-key heads of ``qk_nope_head_dim +
qk_rope_head_dim`` and value heads of ``v_head_dim``.  The grouped products
(``bf_moe_gmm_*``) are held to ``flops_moe.grouped_matmul`` at the rows an
even router sends to the experts held here (``tokens * num_experts_per_tok *
n_routed_experts / router_width``: the kernels visit the held experts' rows
only, and the event's own result has all ``tokens * num_experts_per_tok``
rows) and at the matrices of the held experts.  Off the TPU (the rehearsal)
the kernels run in the Pallas interpreter and no event is a kernel call.
"""

from __future__ import annotations

import re

from benchmark import flops_mla, flops_moe, spec

MLA = ("bf.mla.q", "bf.mla.kv", "bf.mla.rope", "bf.mla.attend", "bf.mla.out")
MHC = ("bf.mhc.map", "bf.mhc.sinkhorn", "bf.mhc.mix")
MOE = {"route": ("bf.moe.route",),
       "permute": ("bf.moe.dispatch", "bf.moe.combine"),
       "experts": ("bf.moe.experts",),
       "shared": ("bf.moe.shared",),
       "unattributed": ("bf.moe.layer",)}
_FLASH = re.compile(r"^bf_flash_(fwd|dq|dkv)\b")


def _common():
    return spec.load_module("layer_metrics/program_common.py")


def _moe():
    return spec.load_module("layer_metrics/moe_common.py")


def grad_scope_ms(ctx) -> dict:
    """Self time per step of the gradient program's device operations by
    scope, ``{}`` where nothing can be read; made once a traced run."""
    if not hasattr(ctx, "xing_scope_ms"):
        common = _common()
        ctx.xing_scope_ms = common.scope_ms(ctx, common.GRAD_PROGRAM) or {}
    return ctx.xing_scope_ms


def parts_ms(ctx, label: str, scopes) -> float | None:
    """The sum over ``scopes`` and a printed line of the parts; None where
    the program has none of them."""
    by_scope = grad_scope_ms(ctx)
    if not any(s in by_scope for s in scopes):
        return None
    parts = {s: by_scope.get(s, 0.0) for s in scopes}
    print(f"  {label}: ms a step by scope: " + ", ".join(
        f"{s} {ms:.3f}" for s, ms in parts.items())
        + f"; sum {sum(parts.values()):.3f}")
    return sum(parts.values())


def moe_parts_ms(ctx) -> dict | None:
    """``{part: ms a step}`` of the expert layer's share, the shared expert
    and what runs under the bare ``bf.moe`` among them."""
    by_scope = _moe().scope_ms(ctx)
    if by_scope is None:
        return None
    return {part: sum(by_scope.get(s, 0.0) for s in scopes)
            for part, scopes in MOE.items()}


def flash_events(ctx) -> list:
    """``(event, kind)`` of every flash kernel call of the free stretch on
    the first chip."""
    found = []
    for e in ctx.free_ops():
        m = _FLASH.match(e.name)
        if m:
            found.append((e, m.group(1)))
    return found


def flash_cost(ctx, kind: str) -> dict:
    config, batch = ctx.cell.config, ctx.cell.traffic["batch"]
    return flops_mla.flash_kernel(
        kind, batch=batch["sequences"], seq=batch["seq_len"],
        heads=config["num_attention_heads"],
        qk_dim=config["qk_nope_head_dim"] + config["qk_rope_head_dim"],
        v_dim=config["v_head_dim"], causal=True, itemsize=2)


def product_events(ctx) -> list:
    return _moe().product_events(ctx)


def product_cost(ctx, event) -> tuple:
    """``(kind, cost)`` of one grouped product from its name, its own
    result and the cell's sizes, or ``(None, None)`` where the result is no
    product of this cell's sizes."""
    config, batch = ctx.cell.config, ctx.cell.traffic["batch"]
    hidden, width = config["hidden_size"], config["moe_intermediate_size"]
    held = config["n_routed_experts"]
    assignments = (batch["sequences"] * batch["seq_len"]
                   * config["num_experts_per_tok"])
    rows = round(assignments * flops_mla.held_share(config))
    moe = _moe()      # its names for a product's event and result
    m = moe._RESULT.match(event.what)
    if not m:
        return None, None
    dims = [int(d) for d in m.group(2).split(",")]
    out_itemsize = moe._ITEMSIZE.get(m.group(1), 4)
    by_rows = moe._PRODUCT.match(event.name).group(1) != "drhs"
    if by_rows and len(dims) == 2 and dims[1] in (hidden, width):
        kind, outer = "rows", dims[1]
        inner = width if outer == hidden else hidden
    elif not by_rows and dims[:1] == [held] \
            and sorted(dims[1:]) == sorted((hidden, width)):
        kind, inner, outer = "weights", dims[1], dims[2]
    else:
        return None, None
    return kind, flops_moe.grouped_matmul(
        kind, rows=rows, inner=inner, outer=outer, groups=held, itemsize=2,
        out_itemsize=out_itemsize)
