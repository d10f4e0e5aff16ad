"""What the four readers of the ``kanana2-packed-s8192-1chip`` cell share:
the gradient program's device time under the scopes of latent attention
(``bf.mla.*``) and of a held eighth of the experts with two shared
(``bf.moe*``), the document-masked flash kernels' events, and the cost of
the two kinds of kernel calls at this configuration's shapes and this
traffic's documents (``benchmark/flops_kanana.py``).

The scopes are ``xing_common.py``'s (``LatentAttention`` and
``parallel/moe.py`` carry the same names whatever the model); that file
keeps the reduction by scope on the context and is not edited.  The masked
kernels are told apart from the causal ones by the names the library gives
them (``bf_flash_seg_fwd.<n>``, ``bf_flash_seg_dq.<n>``,
``bf_flash_seg_dkv.<n>``: ``xing_common``'s pattern for ``bf_flash_fwd``
does not match them) and are held to ``flops_kanana.flash_kernel`` at the
**visible pairs** of the traffic's ``documents``.  The grouped products are
held to ``flops_kanana.grouped_product``; the configuration names its sizes
with ``xing4.0-29b-a4b``'s keys, so ``xing_common.product_cost`` reads an
event's kind and shape and arrives at the same cost (the selftest holds the
two equal).  A program without these scopes or kernels (the parent of
PR 47) yields None everywhere; off the TPU (the rehearsal) the kernels run
in the Pallas interpreter and no event is a kernel call.
"""

from __future__ import annotations

import re

import numpy as np

from benchmark import flops_kanana, spec

_xing = spec.load_module("layer_metrics/xing_common.py")
_moe = spec.load_module("layer_metrics/moe_common.py")
MLA = _xing.MLA
grad_scope_ms, parts_ms = _xing.grad_scope_ms, _xing.parts_ms
moe_parts_ms = _xing.moe_parts_ms
product_events, product_cost = _moe.product_events, _xing.product_cost

_SEG_FLASH = re.compile(r"^bf_flash_seg_(fwd|dq|dkv)\b")
CHUNK = 256     # of a tile that a boundary crosses (ops/flash_attention.py)


def flash_events(ctx) -> list:
    """``(event, kind)`` of every document-masked flash kernel call of the
    free stretch on the first chip."""
    found = []
    for e in ctx.free_ops():
        m = _SEG_FLASH.match(e.name)
        if m:
            found.append((e, m.group(1)))
    return found


def flash_cost(ctx, kind: str) -> dict:
    batch = ctx.cell.traffic["batch"]
    return flops_kanana.flash_kernel(
        kind, config=ctx.cell.config, batch=batch["sequences"],
        documents=batch["documents"])


def chunk_ceiling(documents, chunk: int = CHUNK) -> float:
    """The visible pairs of a row of these documents over the pairs of the
    ``chunk x chunk`` squares at or under the diagonal that hold one: what
    share of its roofline a kernel that runs or skips whole squares can
    reach at most.  Host arithmetic on the layout."""
    ids = np.repeat(np.arange(len(documents)), documents)
    first, last = ids[::chunk], ids[chunk - 1::chunk]
    at = np.arange(len(first))
    live = (at[None, :] <= at[:, None]) & (last[None, :] >= first[:, None])
    return flops_kanana.visible_pairs(documents) / (
        int(live.sum()) * chunk * chunk)


def layout_tiles(documents, block_q: int, block_k: int) -> dict | None:
    """The library's own count of a row's dead, crossed and inside tiles
    (``ops.flash_attention.segment_tiles``), None where the program has no
    such function."""
    try:
        from bluefog_tpu.ops.flash_attention import segment_tiles
    except ImportError:
        return None
    ids = np.repeat(np.arange(len(documents)), documents)[None]
    return segment_tiles(ids, block_q, block_k)
