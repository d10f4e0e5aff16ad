"""The gated delta rule with a decay per channel (Kimi Delta Attention,
arXiv:2510.26692, section 3), in chunked form: two Pallas TPU kernels behind
one custom VJP.

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
the tests hold the kernels to).  ``kda_chunked`` computes the same ``o``
chunk by chunk (the paper's section 3.2, the WY form): with ``G_i = sum_{l
<= i} g_l`` inside a chunk of ``C`` positions and ``S_0`` the state that
enters it,

* the chunk's corrections solve one unit lower-triangular system, ``(I + A)
  U = Diag(beta) (V - (K o exp(G)) S_0)`` with ``A_ij = beta_i sum_c k_ic
  k_jc exp(G_ic - G_jc)`` for ``i > j``: ``T = (I + A)^-1`` is formed once
  and gives ``U_0 = T Diag(beta) V`` and ``W = T Diag(beta) (K o exp(G))``,
  so that ``U = U_0 - W S_0``;
* the outputs are ``O = (Q o exp(G)) S_0 + tril(P) U`` with ``P_ij = sum_c
  q_ic k_jc exp(G_ic - G_jc)`` for ``i >= j``;
* one state is handed from chunk to chunk, ``S_C = Diag(exp(G_C)) S_0 + (K
  o exp(G_C - G))^T U``.

Forward (``bf_kda_fwd``): grid (batch, heads, steps), the last axis
sequential.  A grid step holds up to ``_STEP_CHUNKS`` chunks of one head:
``q``, ``k``, ``v`` and the float32 ``g`` as ``(C, K)`` blocks of the ``(b,
S, H K)`` arrays (the block's index map picks the head's columns: nothing is
moved to a by-head layout), ``beta`` as a ``(1, C)`` row a chunk.  ``G``,
``A``, ``P``, the inverse, ``U_0``, ``W``, ``U`` and the decayed copies of
``q`` and ``k`` are made in VMEM, and the head's state, kept turned as
``(V, K)`` float32 so that a channel's decay runs along the lanes, is
carried from chunk to chunk in a VMEM scratch.  What does not wait for the
state (all but ``U``, ``O`` and the state that leaves) is made for two
chunks at a time, written stage by stage side by side (``_together``) so
that one's chain of products fills the other's waits, and two systems of
64 are inverted side by side in the 128 lanes (``_inverses``).  Nothing
leaves but ``o`` and, where the forward rule of the VJP asks, the
*entering* states of the chunks, ``(b, H, n, V, K)`` float32, for the
backward kernel (the plain call does not write them; under
``jax.checkpoint`` the first forward runs the rule too and its copy is
dropped unread: a custom call's output cannot be cut away).

Backward (``bf_kda_bwd``): the same grid walked from the last chunk to the
first with the gradient of the leaving state in the VMEM scratch.  A chunk's
matrices are made again from ``q``, ``k``, ``v``, ``g``, ``beta`` and the
entering state, and the transpose is written out: first what the chunk
before waits for (``dU`` and the gradient of the entering state), then the
rest, two chunks side by side again.  The system's transpose is ``dA =
-(T^T dU)(T Diag(beta) V)^T - (T^T dW) W^T`` (``-T^T dT T^T`` with ``dT``
put in, so that no product of three matrices is formed; ``T^T dW = -(T^T
dU) S_0^T``), the decayed products' goes row block by row block as the
products went, and the sums of ``g`` transpose to a product with the
triangle's transpose inside the chunk plus what the state and the leaving
decays send back to the chunk's last position.  The step writes ``dq``,
``dk``, ``dv``, ``dg`` (float32) and ``dbeta``.  The residuals are the five
inputs and the entering states: nothing of ``(S, S)`` and no chunk matrix
is kept.

**Exponentials.**  ``A`` and ``P`` are products over the channels of
``exp(G_i - G_j)``, which no single pair of factors gives without ``exp(-G)``
of a whole chunk: at the bound of ``-5`` a step that is ``e^320`` over 64
positions, beyond float32.  A chunk's rows are taken in blocks of ``SUB`` =
16 positions, each against the columns at or before it with the block's
first position as reference: toward a column before the block both factors
are decays (``<= 1``); inside the block the second factor is at most
``exp(15 * 5) = e^75``, inside float32's ``e^88``: what a bound of ``-5`` a
step is for.  Every other exponential here is of a difference that is ``<=
0``.

**Precision.**  Every product takes its operands in the dtype of ``q``,
``k`` and ``v`` and accumulates in float32, but for the decayed products
``A`` and ``P`` (they share their decayed keys), their transposes, the
inverse (substitution in blocks: a block of two's is ``I - A``, and two
neighbours' with what lies between them make the next size's, ``[[L, 0],
[M, N]]^-1 = [[L^-1, 0], [-N^-1 M L^-1, N^-1]]``), which are float32 at the
highest precision (six passes of the array: the system's solution
multiplies what ``A`` is off by), and the sums of ``g``, exact
products of a float32's three bfloat16 pieces with a triangle of ones;
``T`` and ``P`` are rounded once, after they are formed.  The log decays,
their sums and the states are
float32 throughout.  Off the TPU the kernels run in the Pallas interpreter
(``flash_attention.platform_in_use``); on it, shapes that Mosaic cannot
tile raise (``check_tileable``).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from bluefog_tpu.ops.flash_attention import platform_in_use
from bluefog_tpu.utils import telemetry

__all__ = ["kda_chunked", "kda_recurrent", "check_tileable", "SUB"]

# positions of a row block, inside which one factor of a decayed product is
# a growth: SUB - 1 steps at the bound have to stay inside float32
SUB = 16
_LANES = 128
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


def check_tileable(chunk: int, dk: int, dv: int):
    """Raise the ``ValueError`` of a head that the compiled kernels cannot
    take.  Mosaic tiles the last two dims of a block by (8, 128), (16, 128)
    for bfloat16: a head's keys and values are cut out of the ``(b, S, H
    K)`` arrays along the lanes, a chunk along the rows.  The interpreter
    takes any shape."""
    if dk % _LANES or dv % _LANES or chunk % SUB:
        raise ValueError(
            f"kda_chunked: a head of {dk} keys and {dv} values in chunks of "
            f"{chunk} cannot be tiled on a TPU: a head has to be a multiple "
            f"of {_LANES} and a chunk a multiple of {SUB}")


# --- a chunk, on values: what both kernels run ------------------------------------

def _dot(a, b, contract=(1, 0)):
    """``a @ b`` contracting dim ``contract[0]`` of ``a`` with
    ``contract[1]`` of ``b``, float32 out; float32 operands multiply at the
    highest precision (one bfloat16 pass is the TPU's default for them)."""
    return lax.dot_general(
        a, b, (((contract[0],), (contract[1],)), ((), ())),
        precision=_HIGHEST if a.dtype == _F32 else lax.Precision.DEFAULT,
        preferred_element_type=_F32)


def _sums(ones, x, contract=(1, 0)):
    """``_dot(ones, x)`` of a 0/1 matrix and a float32 ``x``, to float32's
    own rounding in three passes of the array and not six: a float32 is the
    sum of three bfloat16 pieces, and a product of a piece with 0 or 1 is
    exact."""
    ones, total, rest = ones.astype(jnp.bfloat16), None, x
    for _ in range(3):
        piece = rest.astype(jnp.bfloat16)
        rest = rest - piece.astype(_F32)
        part = _dot(ones, piece, contract)
        total = part if total is None else total + part
    return total


_NT, _TN = (1, 1), (0, 0)       # a @ b^T and a^T @ b


def _grid(C):
    """Row and column numbers of a ``(C, C)`` matrix."""
    return (lax.broadcasted_iota(jnp.int32, (C, C), 0),
            lax.broadcasted_iota(jnp.int32, (C, C), 1))


def _turned(x):
    """A ``(1, C)`` row as a ``(C, 1)`` column or the other way round, to
    the bit: the one value a row and column of the diagonal keeps."""
    C = max(x.shape)
    i, j = _grid(C)
    return jnp.sum(jnp.where(i == j, x, 0.0), axis=int(x.shape[0] == 1),
                   keepdims=True)


def _row_blocks(C):
    m = min(SUB, C)
    return [(lo, lo + m) for lo in range(0, C, m)]


def _decays(G, lo, hi):
    """A row block's two factors of ``exp(G_i - G_j)``, the block's first
    position as reference: ``toward`` ``(hi - lo, K)`` for its rows and
    ``since`` ``(C, K)`` for the columns before ``hi``, zero from there."""
    ref = G[lo:lo + 1]
    at = lax.broadcasted_iota(jnp.int32, G.shape, 0)
    return (jnp.exp(G[lo:hi] - ref),
            jnp.exp(jnp.where(at < hi, ref - G, -jnp.inf)))


def _inverses(systems):
    """``(I + A)^-1`` of each strictly lower ``A`` ``(C, C)`` float32 of
    ``systems``, by substitution in blocks: the inverse of a block of two
    is ``I - A`` itself, and two neighbouring blocks' inverses ``L^-1`` and
    ``N^-1`` with the part ``M`` of ``A`` between them make the next size's,
    ``[[L, 0], [M, N]]^-1 = [[L^-1, 0], [-N^-1 M L^-1, N^-1]]``: two
    products a level on the whole matrix, every block of the level at once,
    and no power of ``A`` is formed (with keys that nearly repeat the
    powers reach 1e8 before they cancel).  The systems go level by level
    together, so that the array takes one's product while another's is on
    its way, and where two fit the 128 lanes they go side by side, ``[X |
    Y] Diag(P, R) = [X P | Y R]``: one product of full tiles for two of
    half-empty ones.  Returns ``(T, at)`` a system: its inverse is the
    columns ``at`` of ``T``."""
    C, n = systems[0].shape[0], len(systems)
    per = 2 if n % 2 == 0 and 2 * C <= _LANES else 1
    if per == 2:
        systems = [jnp.concatenate(two, axis=1)
                   for two in zip(systems[::2], systems[1::2])]
    i = lax.broadcasted_iota(jnp.int32, (C, per * C), 0)
    j = lax.broadcasted_iota(jnp.int32, (C, per * C), 1)
    same = lambda m: (i ^ (j % C)) < m  # one block of m  # noqa: E731

    def diag(X):
        """``[P | R]`` as ``Diag(P, R)``."""
        if per == 1:
            return X
        return jnp.concatenate([jnp.where(j < C, X, 0.0),
                                jnp.where(j < C, 0.0, X)])
    Ts = [same(1).astype(_F32) - jnp.where(same(2), A, 0.0) for A in systems]
    m = 2
    while m < C:
        between = same(2 * m) & ~same(m)
        right = [_dot(jnp.where(between, A, 0.0), diag(T))
                 for A, T in zip(systems, Ts)]
        Ts = [T - _dot(T, diag(M)) for T, M in zip(Ts, right)]
        m *= 2
    return [(Ts[at // per], slice(at % per * C, (at % per + 1) * C))
            for at in range(n)]


def _together(stages):
    """Run the generators ``stages`` a stage at a time, one's after the
    other's, and return what each returns.  The compiler's scheduler keeps
    close to the order the operations were written in: chunks written
    stage by stage side by side fill the waits of one's chain of products
    with the other's."""
    out, live = [None] * len(stages), dict(enumerate(stages))
    while live:
        for at, stage in list(live.items()):
            try:
                next(stage)
            except StopIteration as done:
                out[at] = done.value
                del live[at]
    return out


class _Chunk:
    """What a chunk's inputs give, as both passes need it: ``q``, ``k``:
    ``(C, K)``, ``v``: ``(C, V)``, ``g``: ``(C, K)`` float32, ``beta``:
    ``(C, 1)`` float32.  ``decayed`` makes the sums of ``g`` and the
    decayed products, ``solve`` takes the system's inverse (``_chunks`` runs
    both for a few chunks together) and ``enter`` the state ``(V, K)``
    float32 (turned) that enters."""

    def __init__(self, q, k, v, g, beta):
        self.dtype, self.g, self.beta = v.dtype, g, beta
        self.q32, self.k32, self.v32 = (x.astype(_F32) for x in (q, k, v))

    def decayed(self):
        """The stages of ``G``, ``A`` and ``P``."""
        C = self.g.shape[0]
        i, j = _grid(C)
        self.lower, self.strict = i >= j, i > j
        # G_i: the sum of g over the chunk up to and with position i
        self.G = G = _sums(self.lower, self.g)
        self.grown = jnp.exp(G)                             # exp(G)
        self.last = G[C - 1:]                               # G_C: (1, K)
        self.fall = jnp.exp(self.last - G)                  # exp(G_C - G)
        self.kg, self.qg = self.k32 * self.grown, self.q32 * self.grown
        self.leaving = self.k32 * self.fall
        yield
        A, P = [], []
        for lo, hi in _row_blocks(C):
            toward, since = _decays(G, lo, hi)
            both = _dot(jnp.concatenate([self.k32[lo:hi] * toward,
                                         self.q32[lo:hi] * toward]),
                        self.k32 * since, _NT)              # (2 m, C)
            A.append(both[:hi - lo])
            P.append(both[hi - lo:])
            yield
        self.A_raw = jnp.concatenate(A)
        self.A = jnp.where(self.strict, self.beta * self.A_raw, 0.0)
        self.P = jnp.where(self.lower, jnp.concatenate(P), 0.0).astype(
            self.dtype)

    def solve(self, T, at):
        """``T`` ``(C, C)`` or ``(C, 2 C)`` float32 holds the system's
        inverse in its columns ``at``: what it multiplies goes to those
        rows of as many, the rest zero."""
        C, width = T.shape
        rows = lax.broadcasted_iota(jnp.int32, (width, 1), 0)

        def placed(x):
            return x if width == C else jnp.where(
                (rows >= at.start) & (rows < at.stop),
                jnp.concatenate([x] * (width // C)), 0.0).astype(x.dtype)
        self.T, self.at = T.astype(self.dtype), at
        self.vb = (self.v32 * self.beta).astype(self.dtype)
        self.kb = (self.kg * self.beta).astype(self.dtype)
        self.U0 = _dot(self.T, placed(self.vb))
        self.W = _dot(self.T, placed(self.kb)).astype(self.dtype)

    def enter(self, state):
        self.state = state.astype(self.dtype)
        self.U = (self.U0 - _dot(self.W, self.state, _NT)).astype(self.dtype)

    def out(self):
        """``O`` ``(C, V)`` float32."""
        return (_dot(self.qg.astype(self.dtype), self.state, _NT)
                + _dot(self.P, self.U))

    def leaves(self, state):
        """The state ``(V, K)`` float32 that leaves, of the float32 one
        that entered."""
        return (state * jnp.exp(self.last)
                + _dot(self.U, self.leaving.astype(self.dtype), _TN))

    # the transpose: first what the next chunk waits for, then the rest
    def back(self, state, do, dstate):
        """``dstate_in`` ``(V, K)`` float32 of ``do`` ``(C, V)`` and
        ``dstate``, the gradient of the state that leaves; ``state``
        entered."""
        self.enter(state)
        low = lambda x: x.astype(self.dtype)  # noqa: E731
        self.do, self.dleft = low(do), low(dstate)
        # O = (Q o exp(G)) S_0 + P U and S_C = exp(G_C) S_0 + (K o fall)^T U
        self.dU = low(_dot(self.P, self.do, _TN)
                      + _dot(low(self.leaving), self.dleft, _NT))
        # G_C: the entering state's decay
        kept = jnp.exp(self.last)
        self.dkept = jnp.sum(dstate * state, axis=0, keepdims=True) * kept
        return (dstate * kept + _dot(self.do, low(self.qg), _TN)
                - _dot(self.dU, self.W, _TN))

    def gradients(self):
        """The stages of ``(dq, dk, dv, dg, dbeta)``, after ``back``."""
        c, C, beta = self, self.g.shape[0], self.beta
        low = lambda x: x.astype(self.dtype)  # noqa: E731
        dqg = _dot(c.do, c.state)                           # (C, K)
        dP = jnp.where(c.lower, low(_dot(c.do, c.U, _NT)).astype(_F32), 0.0)
        dleaving = _dot(c.U, c.dleft)                       # (C, K)
        # U = U_0 - W S_0, [U_0 | W] = T Diag(beta) [V | K o exp(G)]: dW =
        # -dU S_0^T reaches K o exp(G) as T^T dW = -(T^T dU) S_0^T
        dvb = _dot(c.T, c.dU, _TN)[c.at]
        yield
        dkb = -_dot(low(dvb), c.state)
        dA = -(_dot(low(dvb), low(c.U0), _NT) + _dot(low(dkb), c.W, _NT))
        dA = jnp.where(c.strict, dA, 0.0)
        yield
        dbeta = (jnp.sum(dA * c.A_raw, axis=1, keepdims=True)
                 + jnp.sum(dvb * c.v32, axis=1, keepdims=True)
                 + jnp.sum(dkb * c.kg, axis=1, keepdims=True))
        dkg = dkb * beta
        # the decayed products, row block by row block: to the rows by
        # their decays toward the reference, to the columns by theirs since
        dA = beta * dA
        dq_rows, dk_rows, dk_cols = [], [], jnp.zeros_like(c.k32)
        for lo, hi in _row_blocks(C):
            toward, since = _decays(c.G, lo, hi)
            m = hi - lo
            both = jnp.concatenate([dA[lo:hi], dP[lo:hi]])  # (2 m, C)
            rows = _dot(both, c.k32 * since)                # (2 m, K)
            dk_rows.append(rows[:m] * toward)
            dq_rows.append(rows[m:] * toward)
            dk_cols = dk_cols + since * _dot(both, jnp.concatenate(
                [c.k32[lo:hi] * toward, c.q32[lo:hi] * toward]), _TN)
            yield
        dq_rows, dk_rows = jnp.concatenate(dq_rows), jnp.concatenate(dk_rows)
        dq = dq_rows + dqg * c.grown
        dk = dk_rows + dk_cols + dkg * c.grown + dleaving * c.fall
        dG = (c.q32 * dq_rows + c.k32 * (dk_rows - dk_cols) + dqg * c.qg
              + dkg * c.kg - dleaving * c.leaving)
        # G_C: the leaving keys' decays beside the entering state's
        dlast = jnp.sum(dleaving * c.leaving, axis=0, keepdims=True) + c.dkept
        at = lax.broadcasted_iota(jnp.int32, dG.shape, 0)
        dG = dG + jnp.where(at == C - 1, dlast, 0.0)
        yield
        dg = _sums(c.lower, dG, _TN)                        # sum_{l >= i}
        return dq, dk, dvb * beta, dg, dbeta


def _chunks(operands):
    """The ``_Chunk`` of each ``(q, k, v, g, beta)`` of ``operands``, their
    systems solved."""
    chunks = [_Chunk(*x) for x in operands]
    _together([c.decayed() for c in chunks])
    for c, (T, at) in zip(chunks, _inverses([c.A for c in chunks])):
        c.solve(T, at)
    return chunks


# --- the kernels ------------------------------------------------------------------

# chunks written out in one pass of a grid step's loop (those of them that
# divide the step's): two pairs of systems keep the array busy where one
# pair's chain of ten products leaves it waiting half the time (the TPU
# compiler's scheduled bundles a chunk and head for a v5e, forward /
# backward: 1141 / 2028 at four; with an inverse of six products, which
# float32 did not survive, 1212 / 2152 at two, 1011 / 1905 at four and 962
# forward at eight)
_TOGETHER = 4


def _each_chunk(step_chunks: int, body, reverse: bool = False):
    """``body(us)`` for the chunks of a grid step, ``_TOGETHER`` at a time
    in the order they are to be taken: written out, a loop over the rest."""
    some = math.gcd(step_chunks, _TOGETHER)
    turn = (lambda u, n: n - 1 - u) if reverse else (lambda u, n: u)

    def written_out(t, _=None):
        body([turn(t, step_chunks // some) * some + turn(j, some)
              for j in range(some)])
    if step_chunks == some:
        return written_out(0)
    lax.fori_loop(0, step_chunks // some, written_out, None)


def _rows(ref, step_chunks: int):
    """``u -> `` the rows of chunk ``u`` in a grid step's block ``ref``."""
    C = ref.shape[0] // step_chunks
    return lambda u: pl.ds(pl.multiple_of(u * C, C), C)


def _fwd_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, o_ref, *rest,
                save: bool, step_chunks: int):
    """A grid step: ``step_chunks`` chunks of one head.  ``rest``: the
    entering states' block where the backward pass wants them, then the
    carried state."""
    h_ref, st_ref = rest if save else (None,) + rest

    @pl.when(pl.program_id(2) == 0)
    def _start():
        st_ref[:] = jnp.zeros_like(st_ref)

    rows = _rows(q_ref, step_chunks)

    def some(us):
        chunks = _chunks([(q_ref[rows(u), :], k_ref[rows(u), :],
                           v_ref[rows(u), :], g_ref[rows(u), :],
                           _turned(beta_ref[u])) for u in us])
        for u, c in zip(us, chunks):
            state = st_ref[:]
            if save:
                h_ref[u] = state
            c.enter(state)
            o_ref[rows(u), :] = c.out().astype(o_ref.dtype)
            st_ref[:] = c.leaves(state)
    _each_chunk(step_chunks, some)


def _bwd_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, h_ref, do_ref, dq_ref,
                dk_ref, dv_ref, dg_ref, dbeta_ref, dst_ref, *,
                step_chunks: int):
    """A grid step: ``step_chunks`` chunks of one head, from the last to the
    first as the grid's steps are."""
    @pl.when(pl.program_id(2) == 0)
    def _start():
        dst_ref[:] = jnp.zeros_like(dst_ref)

    rows = _rows(q_ref, step_chunks)

    def some(us):
        chunks = _chunks([(q_ref[rows(u), :], k_ref[rows(u), :],
                           v_ref[rows(u), :], g_ref[rows(u), :],
                           _turned(beta_ref[u])) for u in us])
        for u, c in zip(us, chunks):
            dst_ref[:] = c.back(h_ref[u], do_ref[rows(u), :], dst_ref[:])
        for u, (dq, dk, dv, dg, dbeta) in zip(us, _together(
                [c.gradients() for c in chunks])):
            dq_ref[rows(u), :] = dq.astype(dq_ref.dtype)
            dk_ref[rows(u), :] = dk.astype(dk_ref.dtype)
            dv_ref[rows(u), :] = dv.astype(dv_ref.dtype)
            dg_ref[rows(u), :] = dg
            dbeta_ref[u] = _turned(dbeta)
    _each_chunk(step_chunks, some, reverse=True)


# A grid step holds up to ``_STEP_CHUNKS`` chunks, as many as divide a
# sequence's chunks: a step costs about 0.35 us of its own, and the blocks
# of eight chunks of 64 (the backward kernel's: 14 of (512, 128), both
# buffers) are 3 of the 16 MiB of VMEM a kernel gets.
_STEP_CHUNKS = 8


def _step_chunks(n: int) -> int:
    """The chunks of one grid step for a sequence of ``n``."""
    return max(u for u in range(1, _STEP_CHUNKS + 1) if n % u == 0)


def _specs(dims, reverse: bool):
    """The block specs both kernels share, by operand.  ``dims`` is ``(n,
    U, C, K, V)``: a sequence's chunks, those of a grid step, a chunk's
    positions and a head's keys and values; ``reverse`` walks the steps
    from the last to the first."""
    n, U, C, K, V = dims
    at = (lambda c: n // U - 1 - c) if reverse else (lambda c: c)
    return dict(
        k=pl.BlockSpec((None, U * C, K), lambda i, h, c: (i, at(c), h)),
        v=pl.BlockSpec((None, U * C, V), lambda i, h, c: (i, at(c), h)),
        beta=pl.BlockSpec((None, None, U, 1, C),
                          lambda i, h, c: (i, h, at(c), 0, 0)),
        state=pl.BlockSpec((None, None, U, V, K),
                           lambda i, h, c: (i, h, at(c), 0, 0)))


_PARAMS = dict(compiler_params=pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "arbitrary")))
# Each kernel call sits behind ``jax.jit``: a shape is traced, counted and
# staged once, however many mixers, recomputes and calls use it.
_STATIC = ("dims", "interpret", "vma")


@functools.partial(jax.jit, static_argnames=_STATIC + ("save",))
def _fwd_call(q, k, v, g, beta, *, dims, save, interpret, vma):
    """``bf_kda_fwd``: ``o``, and with ``save`` the chunks' entering
    states."""
    telemetry.inc("bf_kernel_stagings_total", kernel="bf_kda_fwd")
    n, U, C, K, V = dims
    b, H = beta.shape[:2]
    spec = _specs(dims, reverse=False)
    shape = lambda s, dtype: jax.ShapeDtypeStruct(s, dtype, vma=vma)
    out = pl.pallas_call(
        functools.partial(_fwd_kernel, save=save, step_chunks=U),
        name="bf_kda_fwd", grid=(b, H, n // U),
        in_specs=[spec["k"], spec["k"], spec["v"], spec["k"], spec["beta"]],
        out_specs=[spec["v"]] + [spec["state"]] * save,
        out_shape=[shape(v.shape, v.dtype)]
        + [shape((b, H, n, V, K), _F32)] * save,
        scratch_shapes=[pltpu.VMEM((V, K), _F32)],
        interpret=interpret, **_PARAMS,
    )(q, k, v, g, beta)
    return tuple(out) if save else (out[0], None)


@functools.partial(jax.jit, static_argnames=_STATIC)
def _bwd_call(q, k, v, g, beta, states, do, *, dims, interpret, vma):
    """``bf_kda_bwd``: the gradients of ``_rule``'s five operands."""
    telemetry.inc("bf_kernel_stagings_total", kernel="bf_kda_bwd")
    n, U, C, K, V = dims
    b, H = beta.shape[:2]
    spec = _specs(dims, reverse=True)
    shape = lambda like, dtype=None: jax.ShapeDtypeStruct(  # noqa: E731
        like.shape, dtype or like.dtype, vma=vma)
    return tuple(pl.pallas_call(
        functools.partial(_bwd_kernel, step_chunks=U),
        name="bf_kda_bwd", grid=(b, H, n // U),
        in_specs=[spec["k"], spec["k"], spec["v"], spec["k"], spec["beta"],
                  spec["state"], spec["v"]],
        out_specs=[spec["k"], spec["k"], spec["v"], spec["k"], spec["beta"]],
        out_shape=[shape(q), shape(k), shape(v), shape(g, _F32),
                   shape(beta, _F32)],
        scratch_shapes=[pltpu.VMEM((V, K), _F32)],
        interpret=interpret, **_PARAMS,
    )(q, k, v, g, beta, states, do))


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7))
def _rule(q, k, v, g, beta, dims, interpret, vma):
    """``o`` ``(b, S, H V)`` of ``q``, ``k``, ``g`` ``(b, S, H K)`` (``g``
    float32), ``v`` ``(b, S, H V)`` and ``beta`` ``(b, H, n, 1, C)``
    float32, ``S = n C``."""
    return _fwd_call(q, k, v, g, beta, dims=dims, save=False,
                     interpret=interpret, vma=vma)[0]


def _rule_fwd(q, k, v, g, beta, dims, interpret, vma):
    o, states = _fwd_call(q, k, v, g, beta, dims=dims, save=True,
                          interpret=interpret, vma=vma)
    return o, (q, k, v, g, beta, states)


def _rule_bwd(dims, interpret, vma, res, do):
    return _bwd_call(*res, do, dims=dims, interpret=interpret, vma=vma)


_rule.defvjp(_rule_fwd, _rule_bwd)


def kda_chunked(q, k, v, g, beta, *, chunk: int = 64):
    """``o`` of the recurrence above over ``S`` positions, chunk by chunk.

    ``q``, ``k``: ``(b, S, H, K)`` and ``v``: ``(b, S, H, V)`` in the compute
    dtype (``q`` and ``k`` as the rule takes them: the caller normalises and
    scales); ``g``: ``(b, S, H, K)`` float32 log decays, ``<= 0`` and no
    smaller than ``-80 / (SUB - 1)`` a step (``-5`` with room: a smaller one
    overflows float32 inside a row block); ``beta``: ``(b, S, H)``.  Returns
    ``(b, S, H, V)`` in ``v``'s dtype; the state starts at zero and is not
    handed back.  ``chunk`` is ``SUB`` = 16 times a power of two, or less
    than 16.  ``S`` need not be a multiple of it: the tail is padded with
    positions of ``g = 0`` and ``beta = 0``, which leave the state as it is.

    On a TPU (``platform_in_use``) the kernels are compiled and
    ``check_tileable`` raises on a head that is no multiple of 128 or a
    chunk under 16; anywhere else they run in the Pallas interpreter at any
    shape.  ``bf_kda_chunks_total`` counts the chunks a call covers, at
    trace time; ``bf_kernel_stagings_total{kernel="bf_kda_fwd" |
    "bf_kda_bwd"}`` the shapes a kernel was staged for."""
    _check(q, k, v, g, beta)
    b, S, H, dk = q.shape
    dv, C = v.shape[-1], chunk
    if C < 1 or (C > SUB and (C % SUB or (C // SUB) & (C // SUB - 1))):
        raise ValueError(f"kda_chunked: chunk {C} is neither under {SUB} "
                         f"nor {SUB} times a power of two")
    interpret = platform_in_use(q) != "tpu"
    if not interpret:
        check_tileable(C, dk, dv)
    pad = -S % C
    n = (S + pad) // C
    telemetry.inc("bf_kda_chunks_total", b * n)

    def padded(x):
        """``(b, S, H, d)`` -> ``(b, n C, H d)``: a reshape, and the tail."""
        x = x.reshape(x.shape[:2] + (-1,))
        return jnp.pad(x, ((0, 0), (0, pad), (0, 0))) if pad else x
    vma = frozenset().union(*(jax.typeof(t).vma for t in (q, k, v, g, beta)))
    o = _rule(padded(q), padded(k), padded(v),
              padded(g.astype(_F32)),
              padded(beta.astype(_F32)).transpose(0, 2, 1).reshape(
                  b, H, n, 1, C),
              (n, _step_chunks(n), C, dk, dv), interpret, vma)
    return o.reshape(b, S + pad, H, dv)[:, :S]
