"""The gated delta rule with a decay per channel (Kimi Delta Attention,
arXiv:2510.26692, section 3), in chunked form: plain ``jax.numpy``.

The recurrence, a head with keys of ``K`` and values of ``V`` values and a
state ``S`` of ``K x V`` (``g_t <= 0`` are log decays a channel of the key,
``beta_t`` in ``(0, 1)`` one value a head, the state zero before the first
token)::

    S'  = Diag(exp(g_t)) S_{t-1}
    S_t = S' + beta_t k_t (v_t - S'^T k_t)^T        (K x V)
    o_t = S_t^T q_t                                  (V,)

The state is *corrected*: what it already predicts for the current key,
``S'^T k_t``, is taken off the value before the key writes it.  Step by step
that is ``S`` dependent updates of a small state (``kda_recurrent``, which
the tests hold the chunked form to).  ``kda_chunked`` computes the same
``o`` chunk by chunk (the paper's section 3.2, the WY form): with ``G_i =
sum_{l <= i} g_l`` inside a chunk of ``C`` positions and ``S_0`` the state
that enters it,

* the chunk's corrections solve one unit lower-triangular system, ``(I + A)
  U = Diag(beta) (V - (K o exp(G)) S_0)`` with ``A_ij = beta_i sum_c k_ic
  k_jc exp(G_ic - G_jc)`` for ``i > j``: ``T = (I + A)^-1`` is formed once
  (``_unit_lower_inverse``) and gives ``U_0 = T Diag(beta) V`` and ``W = T
  Diag(beta) (K o exp(G))``, so that ``U = U_0 - W S_0``;
* one state is handed from chunk to chunk, ``S_C = Diag(exp(G_C)) S_0 + (K
  o exp(G_C - G))^T U`` (``lax.scan``: 64 steps at 4096 positions, each two
  small products a head; the entering states and the ``U`` leave the scan);
* the outputs are ``O = (Q o exp(G)) S_0 + tril(P) U`` with ``P_ij = sum_c
  q_ic k_jc exp(G_ic - G_jc)`` for ``i >= j``, batched over the chunks.

**Exponentials.**  ``A`` and ``P`` are products over the channels of
``exp(G_i - G_j)``, which no single pair of factors gives without ``exp(-G)``
of a whole chunk: at the bound of ``-5`` a step that is ``e^320`` over 64
positions, beyond float32.  ``_decayed_products`` splits a chunk in two
halves again and again down to sub-blocks of ``SUB`` = 16 positions: the
rows of a later half against the columns of the earlier one take the later
half's first position as reference, so that both factors are decays (``<=
1``); inside a sub-block the reference is its first position and the second
factor is at most ``exp(15 * 5) = e^75``, inside float32's ``e^88``: what a
bound of ``-5`` a step is for.  Every other exponential here is of a
difference that is ``<= 0``.

Every product takes its operands in the dtype of ``q``, ``k`` and ``v`` and
accumulates in float32, but for the decayed products ``A`` and ``P`` (they
share their decayed keys, made once) and the inverse, which are float32 at
the highest precision (a fifth of the rule's operations; the system's
solution multiplies what ``A`` is off by).  The log decays, their sums and
the states are float32 throughout.  The transpose is autodiff's of this
form, but for the inverse, whose transpose is written out (``-T^T dT T^T``)
and keeps ``T`` alone.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from bluefog_tpu.utils import telemetry

__all__ = ["kda_chunked", "kda_recurrent", "SUB"]

# positions of a sub-block, inside which one factor of a decayed product is
# a growth: SUB - 1 steps at the bound have to stay inside float32
SUB = 16
_F32 = jnp.float32
_HIGHEST = lax.Precision.HIGHEST


def _check(q, k, v, g, beta):
    b, s, h, dk = q.shape
    if (k.shape != q.shape or g.shape != q.shape or v.shape[:3] != (b, s, h)
            or beta.shape != (b, s, h)):
        raise ValueError(
            f"kda: q {q.shape}, k {k.shape}, v {v.shape}, g {g.shape}, "
            f"beta {beta.shape}: need q, k and g (b, S, H, K), v (b, S, H, "
            "V) and beta (b, S, H)")


def kda_recurrent(q, k, v, g, beta):
    """The rule token by token, in float32: ``lax.scan`` over the ``S``
    positions exactly as the module docstring writes a step.  What
    ``kda_chunked`` is held to; ``S`` dependent steps, so for tests and short
    rows only."""
    _check(q, k, v, g, beta)
    b, _, h, dk = q.shape
    q, k, v, g, beta = (x.astype(_F32) for x in (q, k, v, g, beta))

    def step(state, at):
        q_t, k_t, v_t, g_t, beta_t = at                 # (b, h, .)
        decayed = jnp.exp(g_t)[..., None] * state       # (b, h, K, V)
        told = jnp.einsum("bhkv,bhk->bhv", decayed, k_t, precision=_HIGHEST)
        state = decayed + (beta_t[..., None, None] * k_t[..., None]
                           * (v_t - told)[..., None, :])
        return state, jnp.einsum("bhkv,bhk->bhv", state, q_t,
                                 precision=_HIGHEST)
    _, o = lax.scan(step, jnp.zeros((b, h, dk, v.shape[-1]), _F32),
                    tuple(x.swapaxes(0, 1) for x in (q, k, v, g, beta)))
    return o.swapaxes(0, 1)


def _dot(a, b):
    """``a @ b^T`` over the last dim of both, the leading dims a batch, in
    float32 at the highest precision (one bfloat16 pass is the TPU's default
    for float32 operands)."""
    return jnp.einsum("...ic,...jc->...ij", a, b, precision=_HIGHEST,
                      preferred_element_type=_F32)


def _decayed_products(lefts, right, G):
    """``X_ij = sum_c left_ic right_jc exp(G_ic - G_jc)`` for the pairs ``i
    >= j`` of a chunk, one ``(..., C, C)`` float32 for each of ``lefts``,
    from ``left``, ``right`` and ``G`` ``(..., C, c)``; what it holds above
    the diagonal is finite and means nothing.  The decayed ``right`` and the
    decays are made once for all of ``lefts``.  ``C`` is ``SUB`` times a
    power of two, or less than ``SUB`` (module docstring: no factor is
    larger than ``exp(-(SUB - 1) * min g)``)."""
    *lead, C, c = right.shape
    lead = tuple(lead)
    m, blocks = min(SUB, C), C // min(SUB, C)

    def cut(x, *dims):
        return x.reshape(lead + dims + (c,))
    r, Gb = cut(right, blocks, m), cut(G, blocks, m)
    ref = Gb[..., :1, :]
    toward, decayed = jnp.exp(Gb - ref), r * jnp.exp(ref - Gb)
    Xs = [_dot(cut(left, blocks, m) * toward, decayed) for left in lefts]
    while blocks > 1:
        # neighbours in pairs: the later one's rows against the earlier
        # one's columns, both decayed to the later one's first position
        r, Gb = cut(right, blocks // 2, 2, m), cut(G, blocks // 2, 2, m)
        ref = Gb[..., 1, :1, :]
        toward = jnp.exp(Gb[..., 1, :, :] - ref)
        decayed = r[..., 0, :, :] * jnp.exp(ref - Gb[..., 0, :, :])
        for at, left in enumerate(lefts):
            cross = _dot(cut(left, blocks // 2, 2, m)[..., 1, :, :] * toward,
                         decayed)
            X = Xs[at].reshape(lead + (blocks // 2, 2, m, m))
            Xs[at] = jnp.concatenate([
                jnp.concatenate([X[..., 0, :, :], jnp.zeros_like(cross)], -1),
                jnp.concatenate([cross, X[..., 1, :, :]], -1)], -2)
        m, blocks = 2 * m, blocks // 2
    return [X[..., 0, :, :] for X in Xs]


def _on_lanes(a, b):
    """``a @ b`` of ``(i, j, N)`` and ``(j, k, N)``, the ``N`` systems along
    the lanes: ``j`` multiply-adds of whole vector registers.  (Batched
    products this small leave the array idle: 2048 of 32 x 32 took over a
    millisecond each at the highest precision on a v5e.)"""
    out = a[:, 0, None, :] * b[0][None]
    for j in range(1, a.shape[1]):
        out = out + a[:, j, None, :] * b[j][None]
    return out


def _inverse(A):
    """``(I + A)^-1`` of strictly lower ``A`` ``(..., C, C)`` float32, on
    the vector unit with the systems along the lanes (``(C, C, systems)``):
    forward substitution inside the ``SUB x SUB`` diagonal blocks, row after
    row, ``X_i = e_i - sum_{j < i} A_ij X_j``, then the blocks merged in
    pairs, ``[[L, 0], [M, N]]^-1 = [[L^-1, 0], [-N^-1 M L^-1, N^-1]]``."""
    *lead, C, _ = A.shape
    At = jnp.moveaxis(A.reshape((-1, C, C)), 0, -1)         # (C, C, N)
    N, m = At.shape[-1], min(SUB, C)
    at = [slice(i, i + m) for i in range(0, C, m)]
    # every diagonal block at once: they lie side by side along the lanes
    D = jnp.concatenate([At[s, s] for s in at], axis=-1)
    eye = jnp.eye(m, dtype=A.dtype)
    X = jnp.zeros_like(D)
    for i in range(m):
        X = X.at[i].set(eye[i][:, None]
                        - (D[i, :i, None, :] * X[:i]).sum(axis=0))
    X = [X[..., i * N:(i + 1) * N] for i in range(len(at))]
    while len(X) > 1:
        merged = []
        for p in range(0, len(X), 2):
            lo, hi = slice(p * m, (p + 1) * m), slice((p + 1) * m, (p + 2) * m)
            cross = -_on_lanes(X[p + 1], _on_lanes(At[hi, lo], X[p]))
            merged.append(jnp.concatenate([
                jnp.concatenate([X[p], jnp.zeros_like(cross)], 1),
                jnp.concatenate([cross, X[p + 1]], 1)], 0))
        X, m = merged, 2 * m
    return jnp.moveaxis(X[0], -1, 0).reshape(tuple(lead) + (C, C))


@jax.custom_vjp
def _unit_lower_inverse(A):
    """``T = (I + A)^-1`` of strictly lower ``A`` ``(..., C, C)`` float32
    (``_inverse``).  Its transpose keeps ``T`` alone: ``dA = -T^T dT
    T^T``."""
    return _inverse(A)


def _inverse_fwd(A):
    T = _inverse(A)
    return T, T


def _inverse_bwd(T, dT):
    return (-jnp.einsum("...ji,...jk,...lk->...il", T, dT, T,
                        precision=_HIGHEST),)


_unit_lower_inverse.defvjp(_inverse_fwd, _inverse_bwd)


def kda_chunked(q, k, v, g, beta, *, chunk: int = 64):
    """``o`` of the recurrence above over ``S`` positions, chunk by chunk.

    ``q``, ``k``: ``(b, S, H, K)`` and ``v``: ``(b, S, H, V)`` in the compute
    dtype (``q`` and ``k`` as the rule takes them: the caller normalises and
    scales); ``g``: ``(b, S, H, K)`` float32 log decays, ``<= 0`` and no
    smaller than ``-80 / (SUB - 1)`` a step (``-5`` with room: a smaller one
    overflows float32 inside a sub-block); ``beta``: ``(b, S, H)``.  Returns
    ``(b, S, H, V)`` in ``v``'s dtype; the state starts at zero and is not
    handed back.  ``chunk`` is ``SUB`` = 16 times a power of two, or less
    than 16.  ``S`` need not be a multiple of it: the tail is padded with
    positions of ``g = 0`` and ``beta = 0``, which leave the state as it is.

    ``bf_kda_chunks_total`` counts the chunks a call covers, at trace
    time."""
    _check(q, k, v, g, beta)
    b, S, H, dk = q.shape
    dv, C, dtype = v.shape[-1], chunk, v.dtype
    if C < 1 or (C > SUB and (C % SUB or (C // SUB) & (C // SUB - 1))):
        raise ValueError(f"kda_chunked: chunk {C} is neither under {SUB} "
                         f"nor {SUB} times a power of two")
    pad = -S % C
    n = (S + pad) // C
    telemetry.inc("bf_kda_chunks_total", b * n)

    def chunks(x):
        """``(b, S, H, d)`` -> ``(b, H, n, C, d)``, the tail padded."""
        if pad:
            x = jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2))
        return x.reshape((b, n, C) + x.shape[2:]).transpose(0, 3, 1, 2, 4)
    q, k, v, g = chunks(q), chunks(k), chunks(v), chunks(g.astype(_F32))
    beta = chunks(beta.astype(_F32)[..., None])             # (b, H, n, C, 1)
    at = jnp.arange(C)
    # G_i: the sum of g over the chunk up to and with position i (a product
    # with a triangle of ones: XLA's cumsum is slow on a TPU)
    G = jnp.einsum("ij,bhnjc->bhnic", (at[:, None] >= at[None, :]).astype(
        _F32), g, precision=_HIGHEST)
    G_end = G[..., -1:, :]
    k32, q32 = k.astype(_F32), q.astype(_F32)

    # inside a chunk: the system, its inverse, and what the inverse gives
    A, P = _decayed_products((k32, q32), k32, G)
    A = jnp.where(at[:, None] > at[None, :], beta * A, 0.0)
    T = _unit_lower_inverse(A).astype(dtype)
    P = jnp.where(at[:, None] >= at[None, :], P, 0.0).astype(dtype)
    U0 = jnp.einsum("bhnij,bhnjd->bhnid", T, (v.astype(_F32) * beta).astype(
        dtype), preferred_element_type=_F32)
    W = jnp.einsum("bhnij,bhnjc->bhnic", T, (k32 * jnp.exp(G) * beta).astype(
        dtype), preferred_element_type=_F32).astype(dtype)
    leaving_k = (k32 * jnp.exp(G_end - G)).astype(dtype)    # K o exp(G_C - G)

    # the state from chunk to chunk
    def carry(state, step):
        u0, w, lk, keep = step
        u = u0 - jnp.einsum("bhic,bhcd->bhid", w, state.astype(dtype),
                            preferred_element_type=_F32)
        new = keep[..., None] * state + jnp.einsum(
            "bhic,bhid->bhcd", lk, u.astype(dtype),
            preferred_element_type=_F32)
        return new, (state, u.astype(dtype))
    by_chunk = lambda x: jnp.moveaxis(x, 2, 0)  # noqa: E731
    _, (states, U) = lax.scan(
        carry, jnp.zeros((b, H, dk, dv), _F32),
        (by_chunk(U0), by_chunk(W), by_chunk(leaving_k),
         by_chunk(jnp.exp(G_end[..., 0, :]))))
    states, U = jnp.moveaxis(states, 0, 2), jnp.moveaxis(U, 0, 2)

    # the outputs, all chunks at once
    o = jnp.einsum("bhnic,bhncd->bhnid", (q32 * jnp.exp(G)).astype(dtype),
                   states.astype(dtype), preferred_element_type=_F32) \
        + jnp.einsum("bhnij,bhnjd->bhnid", P, U, preferred_element_type=_F32)
    return o.astype(dtype).transpose(0, 2, 3, 1, 4).reshape(
        b, S + pad, H, dv)[:, :S]
