"""Chunked softmax cross-entropy: O(chunk x vocab) memory lm-head loss.

Long-context training on a single chip is bounded by the lm-head logits, not
attention (flash attention is O(S); the ``(S, vocab)`` f32 logits are not —
8.4 GB at S=64k, vocab=32k).  This computes the standard next-token loss
without ever materializing the full logits: a ``lax.scan`` over sequence
chunks projects each chunk, reduces it to its per-row ``logsumexp`` and the
correct-token logit, and drops the chunk logits immediately.
``jax.checkpoint`` on the chunk body extends the same economy to the
backward (each chunk's logits are recomputed, never stored).

The result is bit-comparable to
``optax.softmax_cross_entropy_with_integer_labels(h @ W, targets)`` up to
f32 reduction order.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from bluefog_tpu.utils import timeline

__all__ = ["chunked_softmax_cross_entropy"]


def chunked_softmax_cross_entropy(hidden, lm_head, targets, *,
                                  chunk: int = 2048):
    """Mean next-token cross-entropy over ``(B, S)`` without full logits.

    ``hidden``: (B, S, E) final-layer activations; ``lm_head``: (E, V)
    projection (pass ``params["lm_head"]["kernel"]``); ``targets``: (B, S)
    int labels in ``[0, V)``.  ``chunk`` is the rows of logits that exist at
    a time over the whole batch: a chunk takes ``c`` positions of every batch
    row, ``c`` the largest divisor of ``S`` not above ``chunk // B`` (at
    least 1).  So the memory the function needs does not grow with ``B``,
    and one long row is cut into as few chunks (passes over the head's
    float32 gradient) as several short ones.

    The target's logit is taken as ``sum(where(iota == target, logits, 0))``
    over the vocabulary, not with a gather: the gather's transpose is a
    scatter-add, which the TPU compiler at ``B == 1`` serves by writing the
    chunk's float32 logit gradient out, copying it into a flat buffer and
    back (three passes over ``c x V`` that compute nothing).  The compare's
    transpose is ``hit * g``, elementwise, and fuses into the two backward
    products at every ``B``.  Value and gradient are the same numbers.
    """
    # One device scope over forward, remat recompute and transpose alike
    # (their metadata reads checkpoint/.../bf.loss.chunked and
    # transpose(jvp(bf.loss.chunked))): a trace reader matches the substring.
    with timeline.device_scope("bf.loss.chunked"):
        return _chunked_loss(hidden, lm_head, targets, chunk)


def _positions_per_chunk(chunk: int, B: int, S: int) -> int:
    """Positions of each batch row in a chunk of ``chunk`` rows over ``B``
    batch rows: the largest divisor of ``S`` not above ``chunk // B``, so
    awkward S (odd, prime factors) still gets the biggest legal chunk instead
    of degrading to 1 via halving."""
    if chunk < 1:
        raise ValueError(f"chunk must be >= 1, got {chunk}")
    c = min(max(1, chunk // B), S)
    while S % c:
        c -= 1
    return c


def _chunked_loss(hidden, lm_head, targets, chunk: int):
    B, S, E = hidden.shape
    c = _positions_per_chunk(chunk, B, S)
    n_chunks = S // c

    h = hidden.reshape(B, n_chunks, c, E).transpose(1, 0, 2, 3)  # (n,B,c,E)
    t = targets.reshape(B, n_chunks, c).transpose(1, 0, 2)       # (n,B,c)

    @jax.checkpoint
    def chunk_loss(h_c, t_c):
        logits = jnp.einsum("bce,ev->bcv", h_c.astype(jnp.float32),
                            lm_head.astype(jnp.float32))
        lse = jax.nn.logsumexp(logits, axis=-1)                  # (B, c)
        vocab = lax.broadcasted_iota(t_c.dtype, logits.shape, 2)
        correct = jnp.sum(jnp.where(vocab == t_c[..., None], logits, 0.0),
                          axis=-1)
        return jnp.sum(lse - correct)

    def body(acc, xs):
        h_c, t_c = xs
        return acc + chunk_loss(h_c, t_c), None

    total, _ = lax.scan(body, jnp.zeros((), jnp.float32), (h, t))
    return total / (B * S)
