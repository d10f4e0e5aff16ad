"""A chunked state-space scan: Mamba-2's recurrence in its SSD form, as two
Pallas TPU kernels behind one custom VJP.

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
  S_c``;
* the entering state's part of each output, ``y_i += exp(s_i) C_i . h_c``.

Forward (``bf_ssd_fwd``): grid (batch, group, chunks), the chunk axis
sequential.  A grid step holds up to four chunks of one group of ``R = H /
G`` heads and takes them one after the other: ``x`` and ``y`` as ``(Q, R
P)`` blocks of the ``(b, S, H P)`` arrays (the block's index map picks the
group's columns: nothing is moved to a by-group layout), ``B`` and ``C`` as
``(Q, N)`` blocks.  ``C B^T`` is formed once a chunk, each head's masked
``exp(s_i - s_j) dt_j`` matrix, its product with ``x``, the entering
state's part and the skip ``D x`` in VMEM, and the state of the group's
heads, ``(N, R P)`` float32, is carried from chunk to chunk in a VMEM
scratch.  No ``Q x Q`` matrix and no chunk state goes to HBM but the
*entering* states of the chunks, ``(b, G, n, N, R P)`` float32, which the
forward rule of the VJP writes for the backward kernel (the plain call does
not; under ``jax.checkpoint`` the first forward runs the rule too and its
copy is dropped unread: a custom call's output cannot be cut away).

Backward (``bf_ssd_bwd``): the same grid walked from the last chunk to the
first with the gradient of the leaving state in the VMEM scratch.  A chunk's
matrices are formed again from its inputs, transposed (keys along the rows,
so that no ``Q x Q`` tile is turned), and the step writes ``dx``, ``dB`` and
``dC`` (summed over the group's heads) and the gradients of the time steps
and of their running sums.

The positions' scalars are float32 and tiny beside ``x``.  Plain XLA makes
``s`` (a product of ``dt A`` with a triangle of ones inside each chunk) and
hands ``s`` and ``dt`` over by group with the chunk along the lanes, ``(b,
G, 2 R, S)``; ``exp(s_i)``, ``exp(s_Q - s_j) dt_j`` and ``exp(s_Q)`` are
formed in the kernels on those rows, a vector register an operation, and
reach the lanes of ``x`` by an exact product (``_on_lanes``).  The gradients
of ``s`` and ``dt`` leave the backward kernel in the same layout and
autodiff takes them to ``dt`` and ``A``.

Every product takes its operands in ``x``'s dtype and accumulates in
float32; the time steps, decays, their sums inside a chunk and the carried
states are float32 throughout (a bfloat16 sum of 128 steps of ``dt A`` would
lose the small ones).  Off the TPU the kernels run in the Pallas
interpreter (``flash_attention.platform_in_use``); on it, shapes that
Mosaic cannot tile raise (``check_tileable``).
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

__all__ = ["ssd_scan", "check_tileable"]

_LANES = 128
_F32 = jnp.float32


def check_tileable(chunk: int, heads: int, head_dim: int, state: int):
    """Raise the ``ValueError`` of a group that the compiled kernels cannot
    take: ``heads`` (a group's ``R``) of ``head_dim`` with a state of
    ``state`` in chunks of ``chunk``.  Mosaic tiles the last two dims of a
    block by (8, 128): the chunk runs along the lanes of the ``Q x Q``
    matrices and of the time steps' rows, ``R P`` and ``N`` along the lanes
    of ``x`` and of ``B`` and ``C``, and a lane tile has to hold whole
    heads.  The interpreter takes any shape."""
    width = _heads_a_tile(heads, head_dim) * head_dim
    if chunk % _LANES or state % _LANES or width % _LANES:
        raise ValueError(
            f"ssd_scan: chunk {chunk}, a group of {heads} heads of "
            f"{head_dim} (R P = {heads * head_dim}) and a state of {state} "
            "cannot be tiled on a TPU: the chunk, R P and N have to be "
            "multiples of 128, and a head has to divide 128 or be a "
            "multiple of it")


def _heads_a_tile(heads: int, head_dim: int) -> int:
    """Heads of one lane tile: as many as fill 128 lanes where a head
    divides them (2 of 64), one otherwise."""
    if head_dim >= _LANES or _LANES % head_dim:
        return 1
    return math.gcd(heads, _LANES // head_dim)


def _dot(a, b, contract=(1, 0)):
    """``a @ b`` contracting dim ``contract[0]`` of ``a`` with
    ``contract[1]`` of ``b``; float32 out of operands as they are."""
    return lax.dot_general(a, b, (((contract[0],), (contract[1],)), ((), ())),
                           preferred_element_type=_F32)


class _Tiles:
    """The lane tiles of a group's ``(Q, R P)`` block: tile ``t`` holds the
    heads ``t * per .. (t + 1) * per`` side by side.  A product with a
    tile's ``x`` is right in one head's own lanes only; ``pick`` puts the
    heads' results together lane by lane."""

    def __init__(self, heads: int, head_dim: int):
        self.per = _heads_a_tile(heads, head_dim)
        self.width = self.per * head_dim
        self.count = heads // self.per
        self.which = lax.broadcasted_iota(
            jnp.int32, (1, self.width), 1) // head_dim

    def lanes(self, t: int) -> slice:
        return slice(t * self.width, (t + 1) * self.width)

    def own(self, k: int):
        """The lanes of the tile's ``k``-th head, ``(1, width)`` bool."""
        return self.which == k

    def pick(self, k: int, new, old):
        return new if old is None else jnp.where(self.own(k), new, old)


def _decay(seen, rows, cols):
    """``exp(rows - cols)`` where ``seen``, else 0: a ``Q x Q`` matrix of
    decays from a ``(1, Q)`` row and a ``(Q, 1)`` column of ``s`` (or the
    other way round for its transpose: the sign is the caller's)."""
    return jnp.exp(jnp.where(seen, rows - cols, -jnp.inf))


def _positions(rows_ref, heads: int):
    """A chunk's float32 scalars from ``rows`` ``(2 R, Q)`` (``s`` then
    ``dt``, a head a row, the chunk along the lanes): ``s``, ``dt``, ``fall
    = exp(s_Q - s)`` and ``last = s_Q`` ``(R, 1)`` as rows, and ``col(j)``,
    row ``j`` of the block as a ``(Q, 1)`` column (the block is turned
    once)."""
    rows = rows_ref[:]
    cols = rows.T
    s, dt = rows[:heads], rows[heads:]
    last = s[:, s.shape[1] - 1:]
    return s, dt, jnp.exp(last - s), last, lambda j: cols[:, j:j + 1]


def _on_lanes(values, head_dim: int):
    """``(R, Q)`` float32, a head a row, as ``(Q, R P)`` float32 with a
    head's value on each of its ``P`` lanes, to the bit: a float32 is the
    sum of three bfloat16 pieces of 8 bits each, and a product of the
    pieces with a constant 0/1 matrix puts them on the lanes and adds them
    in float32.  (A broadcast and a select a head, from ``(Q, 1)`` columns,
    cost a sixth of the forward kernel's time on a v5e.)"""
    R, pieces, rest = values.shape[0], [], values
    for _ in range(3):
        pieces.append(rest.astype(jnp.bfloat16).astype(_F32))
        rest = rest - pieces[-1]
    spread = (lax.broadcasted_iota(jnp.int32, (3 * R, R * head_dim), 0) % R
              == lax.broadcasted_iota(
                  jnp.int32, (3 * R, R * head_dim), 1) // head_dim)
    return _dot(jnp.concatenate(pieces, axis=0).T.astype(jnp.bfloat16),
                spread.astype(jnp.bfloat16))


def _fwd_kernel(x_ref, b_ref, c_ref, rows_ref, skip_ref, y_ref, *rest,
                heads: int, head_dim: int, save: bool, step_chunks: int):
    """A grid step: ``step_chunks`` chunks of one group, one after the
    other.  ``rest``: the entering states' block where the backward pass
    wants them, then the carried state."""
    h_ref, st_ref = rest if save else (None,) + rest

    @pl.when(pl.program_id(2) == 0)
    def _start():
        st_ref[:] = jnp.zeros_like(st_ref)

    Q = x_ref.shape[0] // step_chunks
    for u in range(step_chunks):
        at = pl.ds(u * Q, Q)
        _fwd_chunk(x_ref.at[at], b_ref.at[at], c_ref.at[at],
                   rows_ref.at[:, at], y_ref.at[at],
                   h_ref.at[u] if save else None, skip_ref, st_ref, heads,
                   head_dim)


def _fwd_chunk(x_ref, b_ref, c_ref, rows_ref, y_ref, h_ref, skip_ref, st_ref,
               heads: int, head_dim: int):
    """One chunk of one group.  ``rows`` ``(2 R, Q)``: ``s`` then ``dt``, a
    head a row; ``skip`` ``(1, R P)``: ``D`` on each head's lanes; ``h``
    (or None) takes the state that enters, ``st`` carries it."""
    R, P = heads, head_dim
    state = st_ref[:]                                       # (N, R P)
    if h_ref is not None:
        h_ref[:] = state
    dtype, Q = x_ref.dtype, x_ref.shape[0]
    Bm, Cm = b_ref[:], c_ref[:]
    s, dt, fall, _, col = _positions(rows_ref, R)
    row = lambda of, r: of[r:r + 1, :]                      # (1, Q)
    grown = _on_lanes(jnp.exp(s), P)                        # exp(s_i)
    left = _on_lanes(fall * dt, P)                          # exp(s_Q - s_j) dt_j
    scores = _dot(Cm, Bm, (1, 1))                           # C B^T: (i, j)
    seen = (lax.broadcasted_iota(jnp.int32, (Q, Q), 0)
            >= lax.broadcasted_iota(jnp.int32, (Q, Q), 1))
    entered = _dot(Cm, state.astype(dtype))                 # (Q, R P)
    tiles, weighted = _Tiles(R, P), []
    for t in range(tiles.count):
        lanes = tiles.lanes(t)
        xt = x_ref[:, lanes]
        inside = None
        for k in range(tiles.per):
            r = t * tiles.per + k
            # (L o C B^T) dt, rounded once, times x
            mix = (scores * _decay(seen, col(r), row(s, r))
                   * row(dt, r)).astype(dtype)
            inside = tiles.pick(k, _dot(mix, xt), inside)
        xf = xt.astype(_F32)
        y_ref[:, lanes] = (inside + grown[:, lanes] * entered[:, lanes]
                           + skip_ref[:, lanes] * xf).astype(y_ref.dtype)
        weighted.append((xf * left[:, lanes]).astype(dtype))
    # the state that leaves: exp(s_Q) of the one that entered and what the
    # chunk adds
    st_ref[:] = (state * grown[Q - 1:, :]
                 + _dot(Bm, jnp.concatenate(weighted, axis=1), (0, 0)))


def _bwd_kernel(x_ref, b_ref, c_ref, dy_ref, rows_ref, skip_ref, h_ref,
                dx_ref, db_ref, dc_ref, drows_ref, dskip_ref, dst_ref, *,
                heads: int, head_dim: int, step_chunks: int):
    """A grid step: ``step_chunks`` chunks of one group, from the last to
    the first as the grid's steps are."""
    @pl.when(pl.program_id(2) == 0)
    def _start():
        dst_ref[:] = jnp.zeros_like(dst_ref)
        dskip_ref[:] = jnp.zeros_like(dskip_ref)

    Q = x_ref.shape[0] // step_chunks
    for u in reversed(range(step_chunks)):
        at = pl.ds(u * Q, Q)
        _bwd_chunk(x_ref.at[at], b_ref.at[at], c_ref.at[at], dy_ref.at[at],
                   rows_ref.at[:, at], h_ref.at[u], dx_ref.at[at],
                   db_ref.at[at], dc_ref.at[at], drows_ref.at[:, at],
                   skip_ref, dskip_ref, dst_ref, heads, head_dim)


def _bwd_chunk(x_ref, b_ref, c_ref, dy_ref, rows_ref, h_ref, dx_ref, db_ref,
               dc_ref, drows_ref, skip_ref, dskip_ref, dst_ref, heads: int,
               head_dim: int):
    """One chunk of one group: ``h`` is the state that entered the chunk,
    ``dst`` (scratch) the gradient of the state that leaves it.  The ``Q x
    Q`` matrices are formed transposed, ``(j, i)`` with the keys ``j``
    along the rows.  ``drows`` ``(2 R, Q)``: the gradients of ``s`` and of
    ``dt`` as ``rows`` has them; ``dskip`` ``(1, R P)``: that of ``skip``
    lane by lane, summed over the chunks.  A sum along the lanes (over a
    matrix's ``i``, over a head's ``P`` values) is a product with a
    constant 0/1 matrix that puts it into a column of ``sums`` ``(Q, 3
    R)``, which is turned once."""
    R, P = heads, head_dim
    dtype, Q = x_ref.dtype, x_ref.shape[0]
    Bm, Cm = b_ref[:], c_ref[:]
    s, dt, fall, last, col = _positions(rows_ref, R)
    row = lambda of, r: of[r:r + 1, :]
    grown_rows, left_rows = jnp.exp(s), fall * dt
    grown, left = _on_lanes(grown_rows, P), _on_lanes(left_rows, P)
    state, dstate = h_ref[:], dst_ref[:]                    # (N, R P)
    state_in, dstate_in = state.astype(dtype), dstate.astype(dtype)
    scores = _dot(Bm, Cm, (1, 1))                           # B C^T: (j, i)
    seen = (lax.broadcasted_iota(jnp.int32, (Q, Q), 0)
            <= lax.broadcasted_iota(jnp.int32, (Q, Q), 1))
    entered = _dot(Cm, state_in)                            # C h: (Q, R P)
    leaving = _dot(Bm, dstate_in)                           # B dh': (Q, R P)
    dkept = jnp.sum(dstate * state, axis=0, keepdims=True)  # (1, R P)
    tiles = _Tiles(R, P)
    head_at = lax.broadcasted_iota(jnp.int32, (R, 1), 0)
    to_column = lambda at: (lax.broadcasted_iota(
        jnp.int32, (Q, 3 * R), 1) == at).astype(dtype)
    dscores = sums = None
    dkeep = jnp.zeros((R, 1), _F32)
    grown_dy, weighted = [], []
    add = lambda total, part: part if total is None else total + part
    for t in range(tiles.count):
        lanes = tiles.lanes(t)
        xt, dyt = x_ref[:, lanes], dy_ref[:, lanes]
        xf, dyf = xt.astype(_F32), dyt.astype(_F32)
        inside = None
        for k in range(tiles.per):
            r = t * tiles.per + k
            dt_j = col(R + r)
            decay = _decay(seen, row(s, r), col(r))         # (j, i)
            held = scores * decay
            inside = tiles.pick(
                k, _dot((held * dt_j).astype(dtype), dyt), inside)
            own = xt if tiles.per == 1 else jnp.where(
                tiles.own(k), xf, 0.0).astype(dtype)
            dheld = _dot(own, dyt, (1, 1)) * decay          # x dy^T: (j, i)
            dscores = add(dscores, dheld * dt_j)
            # d s_i by rows; sum_i into column r: it is d dt_j and, times
            # -dt_j, d s_j.  Both sums take the matrix as the product on
            # the array takes it: from t on the two cancel pair by pair,
            # what is left is the gradient of A, and a matrix rounded on
            # one side only left 2% of error in it
            dmix = (dheld * scores).astype(dtype)
            drows_ref[r:r + 1, :] = jnp.sum(dmix.astype(_F32) * dt_j, axis=0,
                                            keepdims=True)
            sums = add(sums, _dot(dmix, to_column(r)))
            dkeep = jnp.where(head_at == r, jnp.sum(jnp.where(
                tiles.own(k), dkept[:, lanes], 0.0), axis=1, keepdims=True),
                dkeep)
        dx_ref[:, lanes] = (inside + skip_ref[:, lanes] * dyf
                            + left[:, lanes] * leaving[:, lanes]
                            ).astype(dx_ref.dtype)
        dskip_ref[:, lanes] += jnp.sum(dyf * xf, axis=0, keepdims=True)
        # the sums over a head's lanes, into columns R + r and 2 R + r: the
        # gradients of exp(s_i) and of exp(s_Q - s_j) dt_j
        per_head = lambda first: (lax.broadcasted_iota(
            jnp.int32, (tiles.width, 3 * R), 1) == first + t * tiles.per
            + lax.broadcasted_iota(
                jnp.int32, (tiles.width, 3 * R), 0) // P).astype(dtype)
        sums = (sums
                + _dot((dyf * entered[:, lanes]).astype(dtype), per_head(R))
                + _dot((xf * leaving[:, lanes]).astype(dtype),
                       per_head(2 * R)))
        grown_dy.append((grown[:, lanes] * dyf).astype(dtype))
        weighted.append((left[:, lanes] * xf).astype(dtype))
    grown_dy = jnp.concatenate(grown_dy, axis=1)            # d (C h)
    weighted = jnp.concatenate(weighted, axis=1)
    dscores = dscores.astype(dtype)
    db_ref[:] = (_dot(dscores, Cm) + _dot(weighted, dstate_in, (1, 1))
                 ).astype(db_ref.dtype)
    dc_ref[:] = (_dot(dscores, Bm, (0, 0)) + _dot(grown_dy, state_in, (1, 1))
                 ).astype(dc_ref.dtype)
    dst_ref[:] = dstate * grown[Q - 1:, :] + _dot(Cm, grown_dy, (0, 0))
    # by rows again: s_j of the matrices, exp(s_i), exp(s_Q - s_j) dt_j,
    # and at the chunk's last position what s_Q carries
    sums = sums.T                                           # (3 R, Q)
    dmix_j, dgrown, dleft = sums[:R], sums[R:2 * R], sums[2 * R:]
    dlast = (jnp.sum(dleft * left_rows, axis=1, keepdims=True)
             + dkeep * jnp.exp(last))
    at_last = lax.broadcasted_iota(jnp.int32, (R, Q), 1) == Q - 1
    drows_ref[:R, :] += (dgrown * grown_rows
                         - (dmix_j * dt + dleft * left_rows)
                         + jnp.where(at_last, dlast, 0.0))
    drows_ref[R:, :] = dmix_j + dleft * fall


# A grid step holds up to ``_STEP_CHUNKS`` chunks, as many as divide a
# sequence's chunks and keep both buffers of the backward kernel's blocks
# inside ``_STEP_BYTES`` of the 16 MiB of VMEM a kernel gets (a chunk's own
# values take the rest).  A step costs 0.3 us of its own beside a chunk's 0.8
# to 1.9: one v5e chip, PR 43, the published group, forward / backward in ms
# a pass at 1, 2 and 4 chunks a step 0.57 / 1.10, 0.48 / 1.01, 0.42 / 0.98.
_STEP_CHUNKS = 4
_STEP_BYTES = 8 << 20


def _step_chunks(dims, itemsize: int) -> int:
    """The chunks of one grid step at ``dims`` ``(n, Q, R, P, N)``."""
    n, Q, R, P, N = dims
    blocks = 2 * (itemsize * Q * (3 * R * P + 4 * N)
                  + 4 * (N * R * P + 4 * R * Q))
    return max(u for u in range(1, _STEP_CHUNKS + 1)
               if n % u == 0 and (u == 1 or u * blocks <= _STEP_BYTES))


def _specs(dims, itemsize: int, reverse: bool):
    """``(steps, U, specs)``: the grid's steps along a sequence, the chunks
    a step holds and the block specs both kernels share, by operand.
    ``dims`` is ``(n, Q, R, P, N)``; ``reverse`` walks the steps from the
    last to the first."""
    n, Q, R, P, N = dims
    U = _step_chunks(dims, itemsize)
    steps = n // U
    at = (lambda c: steps - 1 - c) if reverse else (lambda c: c)
    return steps, U, dict(
        x=pl.BlockSpec((None, U * Q, R * P), lambda i, g, c: (i, at(c), g)),
        bc=pl.BlockSpec((None, U * Q, N), lambda i, g, c: (i, at(c), g)),
        rows=pl.BlockSpec((None, None, 2 * R, U * Q),
                          lambda i, g, c: (i, g, 0, at(c))),
        skip=pl.BlockSpec((1, R * P), lambda i, g, c: (0, g)),
        state=pl.BlockSpec((None, None, U, N, R * P),
                           lambda i, g, c: (i, g, at(c), 0, 0)))


_PARAMS = dict(compiler_params=pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "arbitrary")))
# Each kernel call sits behind ``jax.jit``: a shape is traced, counted and
# staged once, however many mixers, recomputes and calls use it.
_STATIC = ("dims", "interpret", "vma")


@functools.partial(jax.jit, static_argnames=_STATIC + ("save",))
def _fwd_call(x, B, C, rows, skip, *, dims, save, interpret, vma):
    """``bf_ssd_fwd``: ``y``, and with ``save`` the chunks' entering
    states."""
    telemetry.inc("bf_kernel_stagings_total", kernel="bf_ssd_fwd")
    n, Q, R, P, N = dims
    b, G = rows.shape[:2]
    steps, U, spec = _specs(dims, x.dtype.itemsize, reverse=False)
    shape = lambda s, dtype: jax.ShapeDtypeStruct(s, dtype, vma=vma)
    out = pl.pallas_call(
        functools.partial(_fwd_kernel, heads=R, head_dim=P, save=save,
                          step_chunks=U),
        name="bf_ssd_fwd", grid=(b, G, steps),
        in_specs=[spec["x"], spec["bc"], spec["bc"], spec["rows"],
                  spec["skip"]],
        out_specs=[spec["x"]] + [spec["state"]] * save,
        out_shape=[shape(x.shape, x.dtype)]
        + [shape((b, G, n, N, R * P), _F32)] * save,
        scratch_shapes=[pltpu.VMEM((N, R * P), _F32)],
        interpret=interpret, **_PARAMS,
    )(x, B, C, rows, skip)
    return tuple(out) if save else (out[0], None)


@functools.partial(jax.jit, static_argnames=_STATIC)
def _bwd_call(x, B, C, dy, rows, skip, states, *, dims, interpret, vma):
    """``bf_ssd_bwd``: the gradients of ``_scan``'s five operands."""
    telemetry.inc("bf_kernel_stagings_total", kernel="bf_ssd_bwd")
    n, Q, R, P, N = dims
    b, G = rows.shape[:2]
    steps, U, spec = _specs(dims, x.dtype.itemsize, reverse=True)
    shape = lambda s, dtype: jax.ShapeDtypeStruct(s, dtype, vma=vma)
    dx, dB, dC, drows, dskip = pl.pallas_call(
        functools.partial(_bwd_kernel, heads=R, head_dim=P, step_chunks=U),
        name="bf_ssd_bwd", grid=(b, G, steps),
        in_specs=[spec["x"], spec["bc"], spec["bc"], spec["x"], spec["rows"],
                  spec["skip"], spec["state"]],
        out_specs=[spec["x"], spec["bc"], spec["bc"], spec["rows"],
                   pl.BlockSpec((None, 1, R * P), lambda i, g, c: (i, 0, g))],
        out_shape=[shape(x.shape, x.dtype), shape(B.shape, B.dtype),
                   shape(C.shape, C.dtype), shape(rows.shape, _F32),
                   shape((b,) + skip.shape, _F32)],
        scratch_shapes=[pltpu.VMEM((N, R * P), _F32)],
        interpret=interpret, **_PARAMS,
    )(x, B, C, dy, rows, skip, states)
    return dx, dB, dC, drows, dskip.sum(axis=0)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7))
def _scan(x, B, C, rows, skip, dims, interpret, vma):
    """``y`` ``(b, S, H P)`` of ``x`` likewise, ``B`` and ``C`` ``(b, S, G
    N)``, ``rows`` ``(b, G, 2 R, S)`` float32 (a group's ``s`` then its
    ``dt``, a head a row) and ``skip`` ``(1, H P)`` float32."""
    return _fwd_call(x, B, C, rows, skip, dims=dims, save=False,
                     interpret=interpret, vma=vma)[0]


def _scan_fwd(x, B, C, rows, skip, dims, interpret, vma):
    y, states = _fwd_call(x, B, C, rows, skip, dims=dims, save=True,
                          interpret=interpret, vma=vma)
    return y, (x, B, C, rows, skip, states)


def _scan_bwd(dims, interpret, vma, res, dy):
    x, B, C, rows, skip, states = res
    return _bwd_call(x, B, C, dy, rows, skip, states, dims=dims,
                     interpret=interpret, vma=vma)


_scan.defvjp(_scan_fwd, _scan_bwd)


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

    On a TPU (``platform_in_use``) the kernels are compiled and
    ``check_tileable`` raises on a chunk, a group's ``R P`` or an ``N``
    that is no multiple of 128; anywhere else they run in the Pallas
    interpreter at any shape.  ``bf_ssm_chunks_total`` counts the chunks a
    call covers, at trace time; ``bf_kernel_stagings_total{kernel=
    "bf_ssd_fwd" | "bf_ssd_bwd"}`` the shapes a kernel was staged for."""
    b, S, H, P = x.shape
    G, N = B.shape[2:]
    if H % G or dt.shape != (b, S, H) or C.shape != B.shape \
            or B.shape[:2] != (b, S):
        raise ValueError(
            f"ssd_scan: x {x.shape}, dt {dt.shape}, B {B.shape}, C "
            f"{C.shape}: need (b, S, H, P), (b, S, H) and twice (b, S, G, "
            "N) with H a multiple of G")
    R, Q = H // G, chunk
    interpret = platform_in_use(x) != "tpu"
    if not interpret:
        check_tileable(Q, R, P, N)
    pad = -S % Q
    n = (S + pad) // Q
    telemetry.inc("bf_ssm_chunks_total", b * n)

    def padded(v):
        v = v.reshape(v.shape[:2] + (-1,))
        return jnp.pad(v, ((0, 0), (0, pad), (0, 0))) if pad else v
    # the positions' float32 scalars with the chunk along the lanes, (b, G,
    # R, n, Q): dt and s, its running sum inside a chunk (a product with a
    # triangle of ones: XLA's cumsum takes 1.9 ms for these 2 MB on a v5e)
    dt = padded(dt.astype(_F32)).transpose(0, 2, 1).reshape(b, G, R, n, Q)
    upto = (jnp.arange(Q)[:, None] <= jnp.arange(Q)[None, :]).astype(_F32)
    s = jnp.einsum("bgrnj,ji->bgrni", dt * A.astype(_F32).reshape(G, R, 1, 1),
                   upto, precision=lax.Precision.HIGHEST)
    rows = jnp.concatenate([s, dt], axis=2).reshape(b, G, 2 * R, n * Q)
    skip = jnp.zeros((H,), _F32) if D is None else D.astype(_F32)
    vma = frozenset().union(*(jax.typeof(t).vma for t in (x, dt, B, C)))
    y = _scan(padded(x), padded(B), padded(C), rows,
              jnp.repeat(skip, P)[None], (n, Q, R, P, N), interpret, vma)
    return y.reshape(b, S + pad, H, P)[:, :S]
