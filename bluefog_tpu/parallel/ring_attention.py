"""Ring attention: exact attention over sequence-sharded Q/K/V.

Long-context is absent from the reference (SURVEY §5.7) but first-class here:
the ring schedule is the same one-peer ``ppermute`` primitive as the
decentralized gossip ops (``mpi_controller.cc:418-454`` is the reference's
structural cousin), applied to K/V blocks instead of parameters.

Algorithm (blockwise online softmax, a la Ring Attention / FlashAttention
accumulation): each device owns a sequence chunk of Q, K, V.  For ``n`` steps,
compute the partial attention of the local Q block against the currently-held
K/V block while accumulating a numerically-stable running (output, logsumexp)
pair, then rotate K/V one hop around the ring.  Communication rides ICI
concurrently with the block matmuls; memory is O(S/n) per device, so sequence
length scales linearly with the mesh axis.

The per-hop block attention is the Pallas flash kernel
(``ops.flash_attention.flash_attention_lse``), so the local chunk itself
never materializes its S_local x S_local logits either: with contiguous
sharding a hop is all-visible (non-causal flash), on-diagonal (causal flash),
or fully masked (skipped) — selected by ``lax.switch`` on the rotating source
index.  Partials merge by logsumexp weighting, and the lse cotangent flows
back through the kernel's VJP, keeping the whole op differentiable.

All inputs/outputs are per-device blocks ``(B, S_local, H, D)`` — call inside
``shard_map`` with the sequence axis sharded over ``axis_name``.  Compiled
Mosaic kernels on TPU work under the default ``check_vma=True`` (the kernels
declare the axes their inputs vary over); on CPU (Pallas interpreter) pass
``check_vma=False`` to that ``shard_map``: the interpreter's in-kernel
constants are not vma-tracked.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import lax

from bluefog_tpu.ops.flash_attention import flash_attention_lse

__all__ = ["ring_attention", "ring_attention_impl"]

_NEG = -1e30  # finite "minus infinity": logaddexp/exp stay NaN-free


def _pvary(x, axis_name):
    return lax.pcast(x, axis_name, to="varying")


def _merge(o, lse, o_h, lse_h):
    """Logsumexp-weighted merge of two normalized partial attentions.

    ``o``: (B, S, H, D) f32; ``lse``: (B, S, H) f32.  Rows that saw no keys
    carry lse ~ -1e30 and weight out to ~0.
    """
    lse_new = jnp.logaddexp(lse, lse_h)
    safe = jnp.maximum(lse_new, _NEG / 2)
    w, w_h = jnp.exp(lse - safe), jnp.exp(lse_h - safe)
    return o * w[..., None] + o_h * w_h[..., None], lse_new


def ring_attention(q, k, v, *, axis_name: str, causal: bool = True,
                   window: int = None, segment_ids=None):
    """Exact attention with K/V rotating around the ``axis_name`` ring.

    Per-device blocks ``(B, S_local, H, D)``; the global sequence is the
    concatenation of blocks in axis-index order.  Returns the local output
    block, bit-for-bit a blockwise-stable evaluation of full attention.
    A ``window`` (a model's sliding-attention layer) is not supported, nor
    are ``segment_ids`` (a packed row's documents).
    """
    if segment_ids is not None:
        raise NotImplementedError(
            "ring_attention: segment_ids are not supported: a hop's K/V "
            "block would have to travel with its document ids, and a hop "
            "whose documents all end before the local queries' begin be "
            "skipped; run packed rows with ops.flash_attention on one "
            "device")
    if window is not None:
        raise NotImplementedError(
            "ring_attention: a sliding window is not supported: a hop's "
            "block lies whole inside the window, across its edge or whole "
            "outside it, and the hops have no such kinds yet; run the "
            "window layers with ops.flash_attention on one device")
    n = lax.axis_size(axis_name)
    me = lax.axis_index(axis_name)
    B, S, H, D = q.shape
    perm = [(i, (i + 1) % n) for i in range(n)]

    def flash(q, k_blk, v_blk, hop_causal):
        o, lse = flash_attention_lse(q, k_blk, v_blk, causal=hop_causal)
        return o.astype(jnp.float32), lse

    def hop_partial(q, k_blk, v_blk, src):
        """(o, lse) of the local Q against this hop's K/V block."""
        if not causal:
            return flash(q, k_blk, v_blk, False)
        skip = lambda q, k_blk, v_blk: (
            _pvary(jnp.zeros((B, S, H, D), jnp.float32), axis_name),
            _pvary(jnp.full((B, S, H), _NEG, jnp.float32), axis_name))
        # src < me: fully visible; src == me: on-diagonal; src > me: masked.
        mode = jnp.where(src == me, 1, jnp.where(src < me, 0, 2))
        return lax.switch(
            mode,
            [partial(flash, hop_causal=False),
             partial(flash, hop_causal=True), skip],
            q, k_blk, v_blk)

    # Accumulators enter the loop carry device-varying (they mix with
    # ppermuted data inside), so mark the fresh constants as varying too.
    o = _pvary(jnp.zeros((B, S, H, D), jnp.float32), axis_name)
    lse = _pvary(jnp.full((B, S, H), _NEG, jnp.float32), axis_name)

    # Unrolled ring (n = mesh axis size, static and small): XLA overlaps
    # each hop's ppermute with the previous hop's kernel, and unrolling
    # keeps the pallas_call out of a fori_loop body (which also trips a
    # lowering bug in current JAX when switch+pallas nest under vma).
    k_blk, v_blk = k, v
    for t in range(n):
        src = (me - t) % n                      # who produced this K/V block
        o_h, lse_h = hop_partial(q, k_blk, v_blk, src)
        o, lse = _merge(o, lse, o_h, lse_h)
        if t + 1 < n:                           # final rotation is dead
            k_blk, v_blk = jax.tree.map(
                lambda x: lax.ppermute(x, axis_name, perm), (k_blk, v_blk))
    return o.astype(q.dtype)


def ring_attention_impl(axis_name: str):
    """An ``attn_impl`` for ``models.TransformerLM``: same signature as
    ``models.local_attention`` but sequence-parallel over ``axis_name``."""
    return partial(ring_attention, axis_name=axis_name)
