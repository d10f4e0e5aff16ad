"""What the five readers of the ``laguna-s8192-1chip`` cell share: the
gradient program's device time under the scopes of a sliding-window
attention layer (``bf.swa.*``), of a full attention layer with its gate
(``bf.attn.*``) and of a held expert share with a shared expert
(``bf.moe*``), and the cost of the kernel calls at this configuration's
shapes (``benchmark/flops_laguna.py``).

The scopes are those of ``models/transformer.py`` (``Block``'s plain
attention branch, the family chosen by the layer's type: ``qkv``, ``norm``,
``rope``, ``attend``, ``gate``, ``out``) and of ``parallel/moe.py`` (the
four of ``moe_common.py`` and ``bf.moe.shared``); forward, remat recompute
and transpose carry the names alike.  ``program_common.py`` assigns each
device operation of the gradient program to a scope, ``moe_common.py`` tells
the bare ``bf.moe`` apart and ``xing_common.py`` keeps the reduction on the
context; none is edited.  A program without these scopes (the parent of
PR 40) yields None everywhere.

The flash kernels are told apart by the names the library gives them: a
window layer's are ``bf_flash_win_fwd / dq / dkv.<n>`` and a full layer's
``bf_flash_fwd / dq / dkv.<n>``, in one program.  Each call is held to
``flops_laguna.flash_kernel`` at the query heads of its layer type (64
window, 48 full: the 8 K/V heads are repeated before the kernel) and at the
pairs its type shows: a window's ``S x W`` band and never the triangle.  The
grouped products (``bf_moe_gmm_*``) are held to
``flops_laguna.grouped_product`` at the rows an even router sends to the
experts held here; the configuration names its sizes with ``lfm2-24b-a2b``'s
keys, so ``lfm2_common.product_cost`` reads an event's kind and shape.  Off
the TPU (the rehearsal) the kernels run in the Pallas interpreter and no
event is a kernel call.
"""

from __future__ import annotations

import re

from benchmark import flops, flops_laguna, spec

PARTS = ("qkv", "norm", "rope", "attend", "gate", "out")
SWA = tuple(f"bf.swa.{part}" for part in PARTS)
ATTN = tuple(f"bf.attn.{part}" for part in PARTS)
_FLASH = {"sliding_attention": re.compile(r"^bf_flash_win_(fwd|dq|dkv)\b"),
          "full_attention": re.compile(r"^bf_flash_(fwd|dq|dkv)\b")}

_xing = spec.load_module("layer_metrics/xing_common.py")
_lfm2 = spec.load_module("layer_metrics/lfm2_common.py")
# the reduction by scope, kept on the context; the expert layer's parts with
# the shared expert; a grouped product's kind and cost from its event
parts_ms, moe_parts_ms = _xing.parts_ms, _xing.moe_parts_ms
product_events, product_cost = _lfm2.product_events, _lfm2.product_cost


def flash_events(ctx, layer_type: str) -> list:
    """``(event, kind)`` of every flash kernel call of a layer type in the
    free stretch on the first chip."""
    found = []
    for e in ctx.free_ops():
        m = _FLASH[layer_type].match(e.name)
        if m:
            found.append((e, m.group(1)))
    return found


def flash_cost(ctx, layer_type: str, kind: str) -> dict:
    batch = ctx.cell.traffic["batch"]
    return flops_laguna.flash_kernel(
        kind, config=ctx.cell.config, layer_type=layer_type,
        batch=batch["sequences"], seq=batch["seq_len"])


def flash_share(ctx, layer_type: str, label: str):
    """``(percent, taken_ms a step)`` of a layer type's flash kernel calls:
    the least time the chip's peaks allow for them over the time they took,
    and a printed line by kind; None without such calls."""
    events = flash_events(ctx, layer_type)
    if not events:
        return None
    least = {kind: flops.roofline_seconds(flash_cost(ctx, layer_type, kind),
                                          ctx.peaks)
             for kind in {k for _, k in events}}
    taken = {kind: sum(e.duration for e, k in events if k == kind) * 1e-9
             for kind in least}
    print(f"  {label}: " + "; ".join(
        f"{kind} {sum(1 for _, k in events if k == kind)} calls, least "
        f"{seconds * 1e3:.3f} ms each ({bound}-bound), "
        f"{taken[kind] * 1e3:.3f} ms taken"
        for kind, (seconds, bound) in sorted(least.items())))
    return (100.0 * sum(least[k][0] for _, k in events)
            / sum(taken.values()),
            sum(taken.values()) * 1e3 / max(ctx.free_steps, 1))


def window_tiles(ctx) -> str:
    """The tiles the windowed grids compute a call and the visible pairs
    they are computed for, from ``bf_flash_tiles_total`` and
    ``bf_kernel_stagings_total`` (a staging counts its call's tiles once:
    the forward's are a query block by a piece of the band's keys, the
    backward's a query block by a key block); '' where the program has no
    such counter."""
    common = spec.load_module("layer_metrics/program_common.py")
    out = []
    for kind in ("fwd", "dq", "dkv"):
        name = f"bf_flash_win_{kind}"
        stagings = common.counter(ctx, "bf_kernel_stagings_total",
                                  kernel=name)
        tiles = {k: common.counter(ctx, "bf_flash_tiles_total", kernel=name,
                                   kind=k) for k in ("crossed", "interior",
                                                     "skipped")}
        if not stagings or None in tiles.values():
            continue
        pairs = flash_cost(ctx, "sliding_attention", kind)["pairs"]
        out.append(f"{kind} {tiles['crossed'] / stagings:.0f} crossed and "
                   f"{tiles['interior'] / stagings:.0f} interior tiles "
                   f"computed a call ({tiles['skipped'] / stagings:.0f} dead "
                   f"steps) for {pairs / 2 ** 20:.0f} Mi visible pairs")
    return "; ".join(out)
