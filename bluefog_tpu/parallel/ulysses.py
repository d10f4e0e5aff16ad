"""Ulysses-style all-to-all sequence parallelism.

The second long-context strategy (complement of ``ring_attention``): instead
of rotating K/V, transpose the sharding with two all-to-alls — from
sequence-sharded/head-replicated to head-sharded/sequence-replicated, run
plain (flash) attention per head group, and transpose back.  Cheaper than the
ring when ``num_heads >= axis_size`` and sequence blocks are short; the ring
wins at very long context (O(S/n) memory vs O(S) here during attention).

Per-device blocks ``(B, S_local, H, D)``; requires ``H % axis_size == 0``.
"""

from __future__ import annotations

from functools import partial

from jax import lax

from bluefog_tpu.models.transformer import local_attention

__all__ = ["ulysses_attention", "ulysses_attention_impl"]


def ulysses_attention(q, k, v, *, axis_name: str, causal: bool = True,
                      inner_attention=None, window: int = None,
                      segment_ids=None):
    """All-to-all head-parallel attention over ``axis_name``.  A ``window``
    (a model's sliding-attention layer) is not supported, nor are
    ``segment_ids`` (a packed row's documents).

    ``inner_attention(q, k, v, causal=...)`` runs on the gathered-sequence /
    sharded-head layout.  Default: the compiled flash kernel when the
    devices in use are TPUs (the gathered sequence is exactly where O(S)
    memory matters), dense ``local_attention`` elsewhere (the Pallas
    interpreter would dominate CPU-mesh test time).
    """
    if segment_ids is not None:
        raise NotImplementedError(
            "ulysses_attention: segment_ids are not supported: the document "
            "ids are sharded over the sequence like q and would have to be "
            "gathered to the whole sequence before the inner attention, "
            "which is called without them; run packed rows with "
            "ops.flash_attention on one device")
    if window is not None:
        raise NotImplementedError(
            "ulysses_attention: a sliding window is not supported: the "
            "inner attention is called without one; run the window layers "
            "with ops.flash_attention on one device")
    n = lax.axis_size(axis_name)
    H = q.shape[2]
    assert H % n == 0, f"num_heads {H} must be divisible by axis size {n}"
    inner = inner_attention
    if inner is None:
        from bluefog_tpu.ops.flash_attention import (flash_attention,
                                                     platform_in_use)
        inner = (flash_attention if platform_in_use(q) == "tpu"
                 else local_attention)

    def scatter_heads(x):  # (B, S/n, H, D) -> (B, S, H/n, D)
        return lax.all_to_all(x, axis_name, split_axis=2, concat_axis=1,
                              tiled=True)

    def gather_seq(x):     # (B, S, H/n, D) -> (B, S/n, H, D)
        return lax.all_to_all(x, axis_name, split_axis=1, concat_axis=2,
                              tiled=True)

    qh, kh, vh = scatter_heads(q), scatter_heads(k), scatter_heads(v)
    out = inner(qh, kh, vh, causal=causal)
    return gather_seq(out)


def ulysses_attention_impl(axis_name: str, inner_attention=None):
    """An ``attn_impl`` for ``models.TransformerLM`` (see ring_attention)."""
    return partial(ulysses_attention, axis_name=axis_name,
                   inner_attention=inner_attention)
