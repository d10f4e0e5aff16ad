"""Flash attention: Pallas TPU kernel (forward) + blockwise custom VJP.

The hot op of the long-context path.  ``parallel.ring_attention`` and
``parallel.ulysses`` shard the *sequence*; this kernel makes the per-device
block attention itself O(S) in memory by streaming K/V blocks through VMEM
with the online-softmax recurrence — logits never materialize in HBM.

Forward: grid (batch*head, q-block, k-block) with the online-softmax state
(acc, m, l) carried in f32 VMEM scratch across the sequential k dimension —
every operand is a block, so VMEM stays O(block) regardless of S.  Causal
tiles above the diagonal are skipped (``pl.when``) and their K/V DMAs elided
by clamping the index map to the frontier.

Backward: two Pallas kernels recomputing probabilities blockwise from the
saved per-row logsumexp (the standard flash backward) — a dq kernel over
(batch*head, q-block) scanning K blocks, and a dk/dv kernel over
(batch*head, k-block) scanning Q blocks from the causal frontier.  All
accumulation in f32 in VMEM; nothing S x S ever touches HBM.

Layout: ``(B, S, H, D)`` like ``models.local_attention``; internally
``(B*H, S, D)``.  The values may have a last dim ``Dv`` of their own (latent
attention: query-key heads of 192, value heads of 128): ``o``, the
accumulator, ``do`` and ``dv`` take it.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from bluefog_tpu.utils import telemetry

__all__ = ["flash_attention", "flash_attention_lse",
           "flash_attention_impl", "platform_in_use"]

_NEG_INF = -1e30


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, acc_ref, m_ref, l_ref,
                *, scale: float, causal: bool, block_q: int, block_k: int):
    """Grid (bh, q-block, k-block): online-softmax recurrence with the
    running (acc, m, l) state in f32 VMEM scratch across the sequential
    innermost k dimension.  Every operand is a block — VMEM stays O(block),
    so sequence length is bounded by HBM, not VMEM."""
    qi, kb = pl.program_id(1), pl.program_id(2)

    @pl.when(kb == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)

    def tile():
        q = q_ref[:].astype(jnp.float32)                   # (BQ, D)
        k = k_ref[:].astype(jnp.float32)                   # (BK, D)
        v = v_ref[:].astype(jnp.float32)
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
        if causal:
            q_pos = qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, 1), 0)
            k_pos = kb * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (1, block_k), 1)
            s = jnp.where(k_pos <= q_pos, s, _NEG_INF)
        m_prev = m_ref[:]
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_ref[:] = l_ref[:] * corr + p.sum(axis=-1, keepdims=True)
        acc_ref[:] = acc_ref[:] * corr + jnp.dot(
            p, v, preferred_element_type=jnp.float32)
        m_ref[:] = m_new

    if causal:
        # Skip tiles entirely above the diagonal.
        pl.when(kb * block_k <= (qi + 1) * block_q - 1)(tile)
    else:
        tile()

    @pl.when(kb == pl.num_programs(2) - 1)
    def _store():
        l = jnp.maximum(l_ref[:], 1e-30)
        o_ref[:] = (acc_ref[:] / l).astype(o_ref.dtype)
        # (block_q, 1): the trailing singleton keeps the block's minor dim
        # equal to the array's (Mosaic requires minor block dims be
        # (8,128)-tiled or full) — a flat (block_q,) lse block fails to
        # lower on TPU.
        lse_ref[:] = m_ref[:] + jnp.log(l)


def _fit_block(want: int, seq_len: int) -> int:
    """Largest block <= ``want`` that divides ``seq_len`` (halving down), so
    the default 1024 still serves S=768/1280/... by dropping to 256/128.
    Raises when the fit degrades past Mosaic's tiling floor (second-minor
    block dims must be multiples of 8, or the full dimension)."""
    b = min(want, seq_len)
    while seq_len % b:
        b //= 2
    if b % 8 and b != seq_len:
        raise ValueError(
            f"seq len {seq_len} has no TPU-tileable block <= {want}: the "
            f"largest power-of-two divisor is {b}, below Mosaic's multiple-"
            "of-8 floor. Pad the sequence or pass explicit block sizes.")
    return b


def _fwd(q, k, v, *, causal, block_q, block_k, interpret, vma=None,
         scale=None):
    B, S, H, D = q.shape
    Dv = v.shape[-1]
    if scale is None:
        scale = 1.0 / np.sqrt(D)
    bh = B * H
    fold = lambda t: t.transpose(0, 2, 1, 3).reshape(bh, S, t.shape[-1])
    qf, kf, vf = fold(q), fold(k), fold(v)
    block_q = _fit_block(block_q, S)
    block_k = _fit_block(block_k, S)

    if causal:
        # Clamp the k index into this q-block's un-masked range: skipped
        # steps repeat the previous block index and Pallas elides the DMA.
        kv_idx = lambda b, i, j: (
            b, jnp.minimum(j, ((i + 1) * block_q - 1) // block_k), 0)
    else:
        kv_idx = lambda b, i, j: (b, j, 0)

    kernel = functools.partial(
        _fwd_kernel, scale=scale, causal=causal, block_q=block_q,
        block_k=block_k)
    # A wrapper's Python runs at trace time: once a shape behind ``jax.jit``,
    # once a call for a bare kernel, and each staging is a Mosaic lowering.
    telemetry.inc("bf_kernel_stagings_total", kernel="bf_flash_fwd")
    o, lse = pl.pallas_call(
        kernel, name="bf_flash_fwd",
        grid=(bh, S // block_q, S // block_k),
        in_specs=[
            pl.BlockSpec((None, block_q, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((None, block_k, D), kv_idx),
            pl.BlockSpec((None, block_k, Dv), kv_idx),
        ],
        out_specs=[
            pl.BlockSpec((None, block_q, Dv), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((None, block_q, 1), lambda b, i, j: (b, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, S, Dv), q.dtype, vma=vma),
            jax.ShapeDtypeStruct((bh, S, 1), jnp.float32, vma=vma),
        ],
        scratch_shapes=[pltpu.VMEM((block_q, Dv), jnp.float32),
                        pltpu.VMEM((block_q, 1), jnp.float32),
                        pltpu.VMEM((block_q, 1), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(qf, kf, vf)
    lse = lse[..., 0]
    unfold = lambda t: t.reshape(B, H, S, Dv).transpose(0, 2, 1, 3)
    return unfold(o), (qf, kf, vf, o, lse, (B, S, H, D, scale, causal))


def _bwd_tile(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, *,
              scale: float, causal: bool, block_q: int, block_k: int,
              qi, kb):
    """Shared (BQ, BK) tile math of the flash backward: recompute P from the
    saved logsumexp, return (p, ds)."""
    q = q_ref[:].astype(jnp.float32)                       # (BQ, D)
    k = k_ref[:].astype(jnp.float32)                       # (BK, D)
    s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
    if causal:
        q_pos = qi * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, 1), 0)
        k_pos = kb * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (1, block_k), 1)
        s = jnp.where(k_pos <= q_pos, s, _NEG_INF)
    p = jnp.exp(s - lse_ref[:])                            # masked -> 0
    do = do_ref[:].astype(jnp.float32)                     # (BQ, D)
    v = v_ref[:].astype(jnp.float32)
    dp = jnp.dot(do, v.T, preferred_element_type=jnp.float32)
    ds = p * (dp - delta_ref[:]) * scale
    return p, ds


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
               acc_ref, *, scale: float, causal: bool, block_q: int,
               block_k: int):
    """Grid (bh, q-block, k-block): accumulate ds @ K into a f32 VMEM scratch
    across the (sequential, innermost) k dimension; one cast-and-store to the
    output block on the last step.  Every operand is a block — VMEM stays
    O(block), never O(S)."""
    qi, kb = pl.program_id(1), pl.program_id(2)

    @pl.when(kb == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    def tile():
        _, ds = _bwd_tile(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                          scale=scale, causal=causal, block_q=block_q,
                          block_k=block_k, qi=qi, kb=kb)
        acc_ref[:] += jnp.dot(ds, k_ref[:].astype(jnp.float32),
                              preferred_element_type=jnp.float32)

    if causal:
        # Skip tiles entirely above the diagonal.
        pl.when(kb * block_k <= (qi + 1) * block_q - 1)(tile)
    else:
        tile()

    @pl.when(kb == pl.num_programs(2) - 1)
    def _store():
        dq_ref[:] = acc_ref[:].astype(dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                dk_ref, dv_ref, dk_acc, dv_acc, *, scale: float,
                causal: bool, block_q: int, block_k: int):
    """Grid (bh, k-block, q-block): accumulate ds.T @ Q and P.T @ dO into f32
    VMEM scratches across the (sequential, innermost) q dimension."""
    kb, qi = pl.program_id(1), pl.program_id(2)

    @pl.when(qi == 0)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    def tile():
        p, ds = _bwd_tile(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                          scale=scale, causal=causal, block_q=block_q,
                          block_k=block_k, qi=qi, kb=kb)
        do = do_ref[:].astype(jnp.float32)
        q = q_ref[:].astype(jnp.float32)
        dv_acc[:] += jnp.dot(p.T, do, preferred_element_type=jnp.float32)
        dk_acc[:] += jnp.dot(ds.T, q, preferred_element_type=jnp.float32)

    if causal:
        pl.when((qi + 1) * block_q - 1 >= kb * block_k)(tile)
    else:
        tile()

    @pl.when(qi == pl.num_programs(2) - 1)
    def _store():
        dk_ref[:] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[:] = dv_acc[:].astype(dv_ref.dtype)


def _bwd(block_q, block_k, interpret, vma, res, cotangents):
    """Flash backward as two Pallas kernels (dq accumulating over k-blocks;
    dk/dv accumulating over q-blocks) — O(block) VMEM, O(S) HBM, and no
    S x S materialization anywhere.

    Takes cotangents for BOTH outputs ``(do, dlse)``.  A non-zero ``dlse``
    (sequence-parallel consumers weight partial results by their logsumexp,
    e.g. the ring-attention merge) folds into the delta term:
    ``d lse_i / d s_ij = p_ij``, so ``ds += dlse_i * p_ij`` — i.e.
    ``delta_eff = delta - dlse``."""
    qf, kf, vf, o, lse, (B, S, H, D, scale, causal) = res
    Dv = vf.shape[-1]
    do, dlse = cotangents
    bh = B * H
    dof = do.transpose(0, 2, 1, 3).reshape(bh, S, Dv)
    delta = jnp.sum(dof.astype(jnp.float32) * o.astype(jnp.float32),
                    axis=-1, keepdims=True)               # (bh, S, 1)
    delta = delta - dlse.astype(jnp.float32).transpose(0, 2, 1) \
        .reshape(bh, S)[..., None]
    lse3 = lse[..., None]                                 # (bh, S, 1)

    block_q = _fit_block(block_q, S)
    block_k = _fit_block(block_k, S)
    if D > 128 and block_q * block_k > 512 * 1024:
        # Heads wider than one tile of 128 lanes take two: the float32
        # (block_q, block_k) score tiles beside q, k and their accumulators
        # at 1024 x 1024 then overflow the 16 MiB of scoped VMEM (the v5e
        # compiler refuses the dq kernel at D = 192), so the backward takes
        # half as many keys a tile.
        block_k = _fit_block(512, S)
    n_qb, n_kb = S // block_q, S // block_k

    # index helpers: i = this kernel's "own" block dim, j = reduction dim.
    # For causal runs the reduction index is clamped into the un-masked
    # range: on skipped (pl.when'd-out) steps the map then repeats the
    # previous block index, so Pallas elides the DMA — without this, masked
    # tiles would still stream their blocks from HBM (~2x input traffic).
    at = lambda block, dim: lambda sel: pl.BlockSpec(
        (None, block, dim), lambda b, i, j: (b, sel(i, j), 0))
    q_at, k_at = at(block_q, D), at(block_k, D)
    do_at, v_at = at(block_q, Dv), at(block_k, Dv)
    r_at = lambda sel: pl.BlockSpec((None, block_q, 1),
                                    lambda b, i, j: (b, sel(i, j), 0))
    own = lambda i, j: i
    if causal:
        # dq grid: j = k-block; never past this q-block's diagonal.
        red_dq = lambda i, j: jnp.minimum(
            j, ((i + 1) * block_q - 1) // block_k)
        # dkv grid: j = q-block; never before this k-block's frontier.
        red_kv = lambda i, j: jnp.maximum(j, (i * block_k) // block_q)
    else:
        red_dq = red_kv = lambda i, j: j

    params = dict(compiler_params=pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary")))

    telemetry.inc("bf_kernel_stagings_total", kernel="bf_flash_dq")
    dq = pl.pallas_call(
        functools.partial(_dq_kernel, scale=scale, causal=causal,
                          block_q=block_q, block_k=block_k),
        name="bf_flash_dq", grid=(bh, n_qb, n_kb),
        in_specs=[q_at(own), k_at(red_dq), v_at(red_dq), do_at(own),
                  r_at(own), r_at(own)],
        out_specs=q_at(own),
        out_shape=jax.ShapeDtypeStruct((bh, S, D), qf.dtype, vma=vma),
        scratch_shapes=[pltpu.VMEM((block_q, D), jnp.float32)],
        interpret=interpret, **params,
    )(qf, kf, vf, dof, lse3, delta)

    telemetry.inc("bf_kernel_stagings_total", kernel="bf_flash_dkv")
    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, scale=scale, causal=causal,
                          block_q=block_q, block_k=block_k),
        name="bf_flash_dkv", grid=(bh, n_kb, n_qb),
        in_specs=[q_at(red_kv), k_at(own), v_at(own), do_at(red_kv),
                  r_at(red_kv), r_at(red_kv)],
        out_specs=[k_at(own), v_at(own)],
        out_shape=[
            jax.ShapeDtypeStruct((bh, S, D), kf.dtype, vma=vma),
            jax.ShapeDtypeStruct((bh, S, Dv), vf.dtype, vma=vma),
        ],
        scratch_shapes=[pltpu.VMEM((block_k, D), jnp.float32),
                        pltpu.VMEM((block_k, Dv), jnp.float32)],
        interpret=interpret, **params,
    )(qf, kf, vf, dof, lse3, delta)

    unfold = lambda t, dt: t.reshape(B, H, S, t.shape[-1]) \
        .transpose(0, 2, 1, 3).astype(dt)
    return (unfold(dq, qf.dtype), unfold(dk, kf.dtype), unfold(dv, vf.dtype))


def _lse_bsh(lse, B, S, H):
    return lse.reshape(B, H, S).transpose(0, 2, 1)         # -> (B, S, H)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def _flash(q, k, v, causal, block_q, block_k, interpret, vma=None,
           scale=None):
    out, (_, _, _, _, lse, (B, S, H, _, _, _)) = _fwd(
        q, k, v, causal=causal, block_q=block_q, block_k=block_k,
        interpret=interpret, vma=vma, scale=scale)
    return out, _lse_bsh(lse, B, S, H)


def _flash_fwd(q, k, v, causal, block_q, block_k, interpret, vma=None,
               scale=None):
    out, res = _fwd(q, k, v, causal=causal, block_q=block_q,
                    block_k=block_k, interpret=interpret, vma=vma,
                    scale=scale)
    B, S, H = res[5][0], res[5][1], res[5][2]
    return (out, _lse_bsh(res[4], B, S, H)), res


def _flash_bwd(causal, block_q, block_k, interpret, vma, scale, res,
               cotangents):
    del scale       # the residuals carry the one the forward used
    return _bwd(block_q, block_k, interpret, vma, res, cotangents)


_flash.defvjp(_flash_fwd, _flash_bwd)


def platform_in_use(x) -> str:
    """Platform of the devices a computation on ``x`` will run on: a
    concrete array's own devices; under tracing (no devices to read), those
    of the ``bf.init()`` mesh, and jax's default devices before that.  Never
    the process default alone — ``bf.init(devices=jax.devices("cpu"))`` on a
    TPU host and a TPU mesh in a CPU-default process must both resolve to
    the mesh."""
    if isinstance(x, jax.Array) and not isinstance(x, jax.core.Tracer):
        return next(iter(x.devices())).platform
    from bluefog_tpu import basics
    return basics._mesh_platform()


def flash_attention(q, k, v, *, causal: bool = True, block_q: int = 1024,
                    block_k: int = 1024, interpret: bool = None, vma=None,
                    scale: float = None):
    """Memory-O(S) exact attention; ``q`` and ``k`` ``(B, S, H, D)``, ``v``
    ``(B, S, H, Dv)`` (``Dv`` is ``D`` unless the values have a head dim of
    their own), result ``(B, S, H, Dv)``.  ``scale`` multiplies the scores
    before the softmax; ``None`` means ``1 / sqrt(D)``.

    ``interpret=None`` compiles the Mosaic kernel when the devices in use
    (:func:`platform_in_use`) are TPUs and runs the Pallas interpreter
    anywhere else; pass it explicitly to pin either.  ``vma``: the mesh axis
    names the outputs vary over inside ``shard_map``; default: those the
    inputs vary over.

    Block sizes default to 1024 (fitted down to divide S): tall tiles
    amortize the per-program overhead (one v5e chip, PR 21 probe, causal
    S=8192 H=8 D=64 bf16: forward 1.4 / 2.2 / 4.4 ms and backward 4.6 / 5.7 /
    10.5 ms at 1024 / 512 / 256 blocks)."""
    return flash_attention_lse(q, k, v, causal=causal, block_q=block_q,
                               block_k=block_k, interpret=interpret,
                               vma=vma, scale=scale)[0]


def flash_attention_lse(q, k, v, *, causal: bool = True, block_q: int = 1024,
                        block_k: int = 1024, interpret: bool = None,
                        vma=None, scale: float = None):
    """Like :func:`flash_attention` but also returns the per-row logsumexp
    ``(B, S, H)`` — the merge weight sequence-parallel consumers need
    (``parallel.ring_attention`` combines per-hop partials with it).
    Differentiable in both outputs (the lse cotangent folds into the
    backward's delta term).

    ``vma``: frozenset of mesh axis names the outputs vary over inside
    ``shard_map(..., check_vma=True)`` (Pallas outputs must declare their
    varying axes); default: the axes the inputs vary over."""
    if interpret is None:
        interpret = platform_in_use(q) != "tpu"
    if vma is None:
        vma = frozenset().union(*(jax.typeof(t).vma for t in (q, k, v)))
    return _flash(q, k, v, causal, block_q, block_k, interpret, vma,
                  None if scale is None else float(scale))


def flash_attention_impl(block_q: int = 1024, block_k: int = 1024,
                         interpret: bool = None):
    """``attn_impl`` for ``models.TransformerLM`` / ``parallel.ulysses``."""
    def impl(q, k, v, *, causal=True, scale=None):
        return flash_attention(q, k, v, causal=causal, block_q=block_q,
                               block_k=block_k, interpret=interpret,
                               scale=scale)
    return impl
