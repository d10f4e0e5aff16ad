"""A chunked state-space scan: Mamba-2's recurrence in its SSD form.

The recurrence, a head ``h`` of ``P`` values with a state of ``P x N`` (the
time steps ``dt`` are positive, ``A`` negative, so every decay ``a_t`` lies
in ``(0, 1]``; ``B`` and ``C`` belong to the head's group)::

    a_t = exp(dt_t * A)
    h_t = a_t * h_{t-1} + dt_t * x_t B_t^T          (P x N)
    y_t = h_t C_t                                    (P,)

Step by step that is ``S`` dependent updates of a small state, which a TPU
does badly.  ``ssd_scan`` computes the same ``y`` chunk by chunk
(arXiv:2405.21060, section 6): with ``s_i = sum_{k <= i} dt_k A`` inside a
chunk of ``Q`` positions,

* inside a chunk ``y_i += sum_{j <= i} (C_i . B_j) exp(s_i - s_j) dt_j
  x_j``: one ``Q x Q`` matrix ``(L o C B^T) dt`` a head and one product with
  the chunk's ``x``;
* one state a chunk, ``S_c = sum_j exp(s_Q - s_j) dt_j x_j B_j^T``: what
  the chunk adds to the state that leaves it;
* a recurrence over the ``S / Q`` chunk states, ``h_{c+1} = exp(s_Q) h_c +
  S_c`` (``lax.scan``: 64 steps at 8192 positions, not 8192);
* the entering state's part of each output, ``y_i += exp(s_i) C_i . h_c``.

Every product is a batched matmul over chunks and heads in the operands'
dtype with float32 accumulation; the decays, their sums inside a chunk and
the chunk states are float32 throughout (a bfloat16 sum of 128 steps of
``dt A`` would lose the small ones).  Nothing here is a kernel: the
transpose is autodiff's of the same chunked form, so the backward pass works
chunk by chunk too and holds a recurrence over the chunk states only.

The heads are worked group by group (``lax.map`` over the ``G`` groups of
``B`` and ``C``), each group behind a ``jax.checkpoint`` of its own: the
``Q x Q`` matrices, the chunk states and the float32 outputs exist for the
``H / G`` heads of one group at a time, forward and transposed, and a
group's transpose computes them again from its inputs.  At 64 heads in 8
groups over 8192 positions that is 461 MB less of the step's temporaries
and 26 ms less of the step than all heads at once (PERF.md, PR 42).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from bluefog_tpu.utils import telemetry

__all__ = ["ssd_scan"]


def ssd_scan(x, dt, A, B, C, *, chunk: int = 128, D=None):
    """``y`` of the recurrence above over ``S`` positions, chunk by chunk.

    ``x``: ``(b, S, H, P)`` in the compute dtype; ``dt``: ``(b, S, H)``
    float32 time steps (already positive); ``A``: ``(H,)`` float32,
    negative; ``B``, ``C``: ``(b, S, G, N)`` with ``H`` a multiple of ``G``
    (head ``h`` reads group ``h // (H / G)``).  ``D``: ``(H,)`` adds the
    skip ``D_h x_t``.  Returns ``(b, S, H, P)`` in ``x``'s dtype; the state
    starts at zero.  ``S`` need not be a multiple of ``chunk``: the tail is
    padded with steps of ``dt = 0``, which leave the state as it is and add
    nothing to it.

    ``bf_ssm_chunks_total`` counts the chunks a call covers, at trace
    time."""
    b, S, H, P = x.shape
    G, N = B.shape[2:]
    if H % G or dt.shape != (b, S, H) or C.shape != B.shape \
            or B.shape[:2] != (b, S):
        raise ValueError(
            f"ssd_scan: x {x.shape}, dt {dt.shape}, B {B.shape}, C "
            f"{C.shape}: need (b, S, H, P), (b, S, H) and twice (b, S, G, "
            "N) with H a multiple of G")
    R, Q = H // G, chunk
    pad = -S % Q
    n = (S + pad) // Q
    telemetry.inc("bf_ssm_chunks_total", b * n)
    dtype, f32 = x.dtype, jnp.float32

    def chunks(v, *last):
        """``v`` ``(b, S, ...)`` with its tail padded, by group: ``(G, b, n,
        Q) + last``."""
        if pad:
            v = jnp.pad(v, ((0, 0), (0, pad)) + ((0, 0),) * (v.ndim - 2))
        return jnp.moveaxis(v.reshape((b, n, Q, G) + last), 3, 0)
    at = jnp.arange(Q)
    seen = at[:, None] >= at[None, :]

    def group(of):
        """One group's ``R`` heads: ``xg`` ``(b, n, Q, R, P)``, ``dtg``
        ``(b, n, Q, R)``, ``Ag``, ``Dg`` ``(R,)``, ``Bg``, ``Cg`` ``(b, n,
        Q, N)``; returns ``(b, n, Q, R, P)``."""
        xg, dtg, Ag, Dg, Bg, Cg = of
        # s_i, the sum of dt A over the chunk up to and with position i
        s = jnp.cumsum(dtg * Ag, axis=2)
        s_end = s[:, :, -1]                                 # (b, n, R)

        # inside a chunk: (L o C B^T) dt, rounded once, times x
        scores = jnp.einsum("bcin,bcjn->bcij", Cg, Bg,
                            preferred_element_type=f32)
        sh = s.transpose(0, 1, 3, 2)                        # (b, n, R, Q)
        decay = jnp.exp(jnp.where(
            seen, sh[..., :, None] - sh[..., None, :], -jnp.inf))
        mix = (scores[:, :, None] * decay
               * dtg.transpose(0, 1, 3, 2)[..., None, :]).astype(dtype)
        y = jnp.einsum("bcrij,bcjrp->bcirp", mix, xg,
                       preferred_element_type=f32)

        # one state a chunk: what the chunk adds to the state that leaves it
        left = (jnp.exp(s_end[:, :, None] - s) * dtg)[..., None]
        added = jnp.einsum("bcjrp,bcjn->bcrpn",
                           (xg.astype(f32) * left).astype(dtype), Bg,
                           preferred_element_type=f32)

        # the recurrence over the chunk states; ``entering[c]`` is h before c
        def carry(h, step):
            keep, new = step
            return keep[..., None, None] * h + new, h
        _, entering = lax.scan(
            carry, jnp.zeros((b, R, P, N), f32),
            (jnp.exp(s_end).swapaxes(0, 1), added.swapaxes(0, 1)))
        entering = entering.swapaxes(0, 1)                  # (b, n, R, P, N)

        # the entering state's part of each output
        y = y + jnp.einsum("bcin,bcrpn->bcirp", Cg, entering.astype(dtype),
                           preferred_element_type=f32) * jnp.exp(s)[..., None]
        if Dg is not None:
            y = y + Dg[:, None] * xg.astype(f32)
        return y.astype(dtype)

    # Group after group, each behind a checkpoint of its own: a group's
    # transpose computes its Q x Q matrices and chunk states again, so a
    # pass holds those of R heads and never those of all H.
    y = lax.map(jax.checkpoint(group), (
        chunks(x, R, P), chunks(dt.astype(f32), R),
        A.astype(f32).reshape(G, R),
        None if D is None else D.astype(f32).reshape(G, R),
        chunks(B, N), chunks(C, N)))                        # (G,b,n,Q,R,P)
    return jnp.moveaxis(y, 0, 3).reshape(b, S + pad, H, P)[:, :S]
