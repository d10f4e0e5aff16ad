"""What the five ``moe_*`` readers share: the gradient program's device
time under the scopes of ``parallel/moe.py``, and which device events are
the grouped products of the experts.

The layer runs under ``bf.moe``, and inside it under ``bf.moe.route``
(router matmul, softmax, top-k, the sort and the counts), ``bf.moe.dispatch``
(rows gathered into expert order), ``bf.moe.experts`` (three grouped
products and the SwiGLU between them) and ``bf.moe.combine`` (rows back in
token order, weighted and summed); forward, remat recompute and transpose
carry the names alike.  ``program_common.py`` knows scopes of the form
``bf.<layer>.<name>`` only, so here the bare ``bf.moe`` of an ``op_name`` is
read as ``bf.moe.layer`` before its rules are applied: an operation under
``bf.moe`` and under none of the four comes out as ``bf.moe.layer``, which
the readers print as unattributed.

The grouped products are the Pallas kernels of
``parallel.moe.grouped_matmul``, told apart by the names the library gives
their instructions: ``bf_moe_gmm_fwd.<n>`` (forward and remat recompute) and
``bf_moe_gmm_dlhs.<n>`` (the rows' gradient) are products of the rows,
``bf_moe_gmm_drhs.<n>`` is the gradient of the matrices.  Off the TPU (the
rehearsal) the kernels run in the Pallas interpreter as ordinary
instructions under the same scope, and no event is a grouped product.
"""

from __future__ import annotations

import copy
import dataclasses
import re

from benchmark import flops_moe, spec

SCOPES = {"route": ("bf.moe.route",),
          "permute": ("bf.moe.dispatch", "bf.moe.combine"),
          "experts": ("bf.moe.experts",)}
UNATTRIBUTED = "bf.moe.layer"
_BARE = re.compile(r"bf\.moe(?![.\w])")
_PRODUCT = re.compile(r"^bf_moe_gmm_(fwd|dlhs|drhs)\b")
_RESULT = re.compile(r"^\(?(\w+)\[([\d,]+)\]")
_ITEMSIZE = {"bf16": 2, "f16": 2, "f32": 4}


def _common():
    return spec.load_module("layer_metrics/program_common.py")


def _grad_scopes(ctx) -> dict:
    """``{module name: {instruction: scope}}`` of the live gradient
    programs, the bare ``bf.moe`` told apart; made once a traced run."""
    if getattr(ctx, "moe_scopes", None) is None:
        import jax
        common, out = _common(), {}
        if common.program(ctx).spans:
            for executable in jax.devices()[0].client.live_executables():
                for module in executable.hlo_modules():
                    if module.name.startswith(common.GRAD_PROGRAM) \
                            and module.name not in out:
                        out[module.name] = common.instruction_scopes(
                            _BARE.sub(UNATTRIBUTED, module.to_string()))
        ctx.moe_scopes = out
    return ctx.moe_scopes


def scope_ms(ctx) -> dict | None:
    """Self time per step of the gradient program's device operations, by
    ``bf.moe.*`` scope (``program_common.scope_ms`` over the scopes made
    here); None where the program has no such scope.  Made once a traced
    run: four readers ask."""
    if not hasattr(ctx, "moe_scope_ms"):
        common = _common()
        view = copy.copy(ctx)
        view.program = dataclasses.replace(common.program(ctx),
                                           scopes=_grad_scopes(ctx))
        by_scope = common.scope_ms(view, common.GRAD_PROGRAM) or {}
        ctx.moe_scope_ms = {k: v for k, v in by_scope.items()
                            if k and k.startswith("bf.moe.")} or None
    return ctx.moe_scope_ms


def part_ms(ctx, part: str):
    by_scope = scope_ms(ctx)
    if by_scope is None:
        return None
    return sum(by_scope.get(s, 0.0) for s in SCOPES[part])


def product_events(ctx) -> list:
    """The grouped products of the free stretch on the first chip."""
    return [e for e in ctx.free_ops() if _PRODUCT.match(e.name)]


def product_cost(ctx, event) -> tuple:
    """``(kind, cost)`` of one grouped product from its name, its own
    result (the event carries type and shape) and the cell's sizes: a
    product of the rows gives ``(rows, n)``, the gradient of the matrices
    ``(experts, k, n)``, contracted over all the assignments."""
    config, batch = ctx.cell.config, ctx.cell.traffic["batch"]
    hidden, width = config["hidden_size"], config["intermediate_size"]
    m = _RESULT.match(event.what)
    if not m:
        return None, None
    dims = [int(d) for d in m.group(2).split(",")]
    out_itemsize = _ITEMSIZE.get(m.group(1), 4)
    by_rows = _PRODUCT.match(event.name).group(1) != "drhs"
    if by_rows and len(dims) == 2 and dims[1] in (hidden, width):
        kind, rows, outer = "rows", dims[0], dims[1]
        inner = width if outer == hidden else hidden
    elif not by_rows and len(dims) == 3 \
            and sorted(dims[1:]) == sorted((hidden, width)):
        kind, inner, outer = "weights", dims[1], dims[2]
        rows = (batch["sequences"] * batch["seq_len"]
                * config["num_experts_per_tok"])
    else:
        return None, None
    return kind, flops_moe.grouped_matmul(
        kind, rows=rows, inner=inner, outer=outer,
        groups=config["num_experts"], itemsize=2, out_itemsize=out_itemsize)
