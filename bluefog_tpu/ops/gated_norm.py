"""Mamba-2's gated norm, ``RMSNorm(o * silu(z))`` in groups, as two Pallas
TPU kernels behind one custom VJP.

With ``x = o * silu(z)`` and a row's ``I`` values in ``G`` groups of ``W = I
/ G``, each group on its own::

    r = rsqrt(mean(x^2 over the group) + eps)
    y = x * r * scale                       (one ``scale`` of ``I``)

Plain XLA walks float32 copies of the ``(S, I)`` arrays through HBM for it
(the gated product, its relayout for the group sums, the ``rsqrt`` broadcast
back out; twice over in the transpose).  The kernels read the operands once
and write the results once; everything between stays in VMEM, in float32.

Forward (``bf_gated_norm_fwd``): the rows of ``(b, S)`` side by side, a grid
step a block of rows with all ``I`` columns of ``o`` and ``z``.  A step
takes its rows a tile at a time and a tile group by group, on static column
slices of ``W`` lanes: the gate, the group's mean of squares along the
lanes, ``rsqrt``, the scale, one store in the operands' dtype.

Backward (``bf_gated_norm_bwd``): the same grid over ``o``, ``z`` and
``dy``.  ``x``, ``r`` and ``n = x r`` are formed again (nothing is kept for
the transpose but the operands), and with ``g = dy * scale``::

    dx = r * (g - n * mean(g n over the group))
    do = dx * silu(z)
    dz = dx * o * silu'(z),     silu'(z) = s + silu(z) (1 - s), s = sigmoid(z)

leave in the operands' dtype, and a block's sum over its rows of ``dy * n``
in float32, ``(blocks, 1, I)``, which is summed outside into ``d scale``.
Rows past the end of the arrays (the last block of a row count that the
block does not divide) are kept out of that sum; what they give elsewhere is
never stored.

Off the TPU the kernels run in the Pallas interpreter
(``flash_attention.platform_in_use``); on it, a group width that Mosaic
cannot slice raises (``check_tileable``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from bluefog_tpu.ops.flash_attention import platform_in_use
from bluefog_tpu.utils import telemetry

__all__ = ["gated_rms_norm", "check_tileable"]

_LANES = 128
_F32 = jnp.float32


def check_tileable(width: int, groups: int):
    """Raise the ``ValueError`` of a row the compiled kernels cannot take:
    ``width`` values in ``groups`` groups.  A group is a static slice of
    the block's lanes, which Mosaic tiles by 128.  The interpreter takes
    any width that the groups divide."""
    if width % groups or (width // groups) % _LANES:
        raise ValueError(
            f"gated_rms_norm: {groups} groups over a width of {width} "
            "cannot be tiled on a TPU: a group has to be a multiple of 128 "
            "values")


# A grid step's block holds as many rows as keep one operand's block inside
# ``_BLOCK_BYTES`` (the backward kernel has five of them in two buffers
# each, inside the 16 MiB of VMEM a kernel gets), and takes them
# ``_TILE_ROWS`` at a time, so that a group's float32 values stay in vector
# registers between the load and the store.
_BLOCK_BYTES = 1 << 20
_TILE_ROWS = 16


def _block_rows(total: int, width: int, itemsize: int) -> int:
    """Rows of one block: a multiple of the tile, or all ``total`` of an
    array that is shorter than a block."""
    rows = max(_BLOCK_BYTES // (width * itemsize) // _TILE_ROWS, 1) \
        * _TILE_ROWS
    return total if total <= rows else rows


def _tiles(rows: int, body):
    """``body(first, count)`` for every tile of a block of ``rows`` rows.  A
    block that is not whole tiles is a whole array (``_block_rows``) and
    one tile."""
    if rows % _TILE_ROWS:
        body(0, rows)
        return

    def step(t, _):
        body(pl.multiple_of(t * _TILE_ROWS, _TILE_ROWS), _TILE_ROWS)
    lax.fori_loop(0, rows // _TILE_ROWS, step, None)


def _gated(o_ref, z_ref, rows, lanes, eps: float):
    """A group's float32 values on a tile: ``o``, ``s = sigmoid(z)``,
    ``silu(z)``, ``r`` and ``n = o silu(z) r``."""
    o, z = o_ref[rows, lanes].astype(_F32), z_ref[rows, lanes].astype(_F32)
    s = jax.nn.sigmoid(z)
    silu = z * s
    x = o * silu
    r = lax.rsqrt(jnp.mean(x * x, axis=1, keepdims=True) + eps)
    return o, s, silu, r, x * r


def _fwd_kernel(o_ref, z_ref, scale_ref, y_ref, *, groups: int, eps: float):
    width = o_ref.shape[1] // groups

    def tile(first, count):
        rows = pl.ds(first, count)
        for g in range(groups):
            lanes = slice(g * width, (g + 1) * width)
            n = _gated(o_ref, z_ref, rows, lanes, eps)[-1]
            y_ref[rows, lanes] = (n * scale_ref[:, lanes]).astype(y_ref.dtype)
    _tiles(o_ref.shape[0], tile)


def _bwd_kernel(o_ref, z_ref, scale_ref, dy_ref, do_ref, dz_ref, dscale_ref,
                *, groups: int, eps: float, total: int):
    """``total``: the rows of the arrays; a block that reaches past them
    keeps its rows from there on out of ``dscale``."""
    block, width = o_ref.shape[0], o_ref.shape[1] // groups
    dscale_ref[:] = jnp.zeros_like(dscale_ref)
    left = total - pl.program_id(0) * block     # rows from the block's first

    def tile(first, count):
        rows = pl.ds(first, count)
        for g in range(groups):
            lanes = slice(g * width, (g + 1) * width)
            o, s, silu, r, n = _gated(o_ref, z_ref, rows, lanes, eps)
            dy = dy_ref[rows, lanes].astype(_F32)
            dyn = dy * n
            if total % block:
                inside = first + lax.broadcasted_iota(
                    jnp.int32, dyn.shape, 0) < left
                dyn = jnp.where(inside, dyn, 0.0)
            dscale_ref[:, lanes] += jnp.sum(dyn, axis=0, keepdims=True)
            g_ = dy * scale_ref[:, lanes]
            dx = r * (g_ - n * jnp.mean(g_ * n, axis=1, keepdims=True))
            do_ref[rows, lanes] = (dx * silu).astype(do_ref.dtype)
            dz_ref[rows, lanes] = (dx * o * (s + silu * (1.0 - s))
                                   ).astype(dz_ref.dtype)
    _tiles(block, tile)


_PARAMS = dict(compiler_params=pltpu.CompilerParams(
    dimension_semantics=("parallel",)))
# Each kernel call sits behind ``jax.jit``: a shape is traced, counted and
# staged once, however many mixers, recomputes and calls use it.
_STATIC = ("groups", "eps", "rows", "interpret", "vma")


def _specs(total: int, width: int, rows: int):
    """The grid and the block specs of the ``(total, width)`` arrays and of
    ``scale`` ``(1, width)``."""
    return ((pl.cdiv(total, rows),),
            pl.BlockSpec((rows, width), lambda i: (i, 0)),
            pl.BlockSpec((1, width), lambda i: (0, 0)))


@functools.partial(jax.jit, static_argnames=_STATIC)
def _fwd_call(o, z, scale, *, groups, eps, rows, interpret, vma):
    """``bf_gated_norm_fwd``: ``y`` ``(total, I)`` in ``o``'s dtype."""
    telemetry.inc("bf_kernel_stagings_total", kernel="bf_gated_norm_fwd")
    grid, block, whole = _specs(*o.shape, rows)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, groups=groups, eps=eps),
        name="bf_gated_norm_fwd", grid=grid,
        in_specs=[block, block, whole], out_specs=block,
        out_shape=jax.ShapeDtypeStruct(o.shape, o.dtype, vma=vma),
        interpret=interpret, **_PARAMS,
    )(o, z, scale)


@functools.partial(jax.jit, static_argnames=_STATIC)
def _bwd_call(o, z, scale, dy, *, groups, eps, rows, interpret, vma):
    """``bf_gated_norm_bwd``: the gradients of ``_norm``'s three
    operands."""
    telemetry.inc("bf_kernel_stagings_total", kernel="bf_gated_norm_bwd")
    total, width = o.shape
    grid, block, whole = _specs(total, width, rows)
    shape = lambda s, dtype: jax.ShapeDtypeStruct(s, dtype, vma=vma)
    do, dz, dscale = pl.pallas_call(
        functools.partial(_bwd_kernel, groups=groups, eps=eps, total=total),
        name="bf_gated_norm_bwd", grid=grid,
        in_specs=[block, block, whole, block],
        out_specs=[block, block,
                   pl.BlockSpec((None, 1, width), lambda i: (i, 0, 0))],
        out_shape=[shape(o.shape, o.dtype), shape(z.shape, z.dtype),
                   shape(grid + (1, width), _F32)],
        interpret=interpret, **_PARAMS,
    )(o, z, scale, dy)
    return do, dz, dscale.sum(axis=0)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _norm(o, z, scale, groups, eps, rows, interpret, vma):
    """``y`` ``(total, I)`` of ``o`` and ``z`` likewise and ``scale`` ``(1,
    I)`` float32."""
    return _fwd_call(o, z, scale, groups=groups, eps=eps, rows=rows,
                     interpret=interpret, vma=vma)


def _norm_fwd(o, z, scale, groups, eps, rows, interpret, vma):
    return _fwd_call(o, z, scale, groups=groups, eps=eps, rows=rows,
                     interpret=interpret, vma=vma), (o, z, scale)


def _norm_bwd(groups, eps, rows, interpret, vma, res, dy):
    return _bwd_call(*res, dy, groups=groups, eps=eps, rows=rows,
                     interpret=interpret, vma=vma)


_norm.defvjp(_norm_fwd, _norm_bwd)


def gated_rms_norm(o, z, scale, *, groups: int = 1, eps: float = 1e-5):
    """``RMSNorm(o * silu(z)) * scale`` with the mean of squares taken over
    each of ``groups`` groups of a row's values on its own.

    ``o``, ``z``: ``(..., I)`` in the compute dtype; ``scale``: ``(I,)``.
    The gate, the mean and the ``rsqrt`` are float32 whatever the operands
    are; the result is ``o``'s shape and dtype, and the gradients of ``o``
    and ``z`` theirs.  Nothing is kept for the transpose but the operands.

    On a TPU (``platform_in_use``) the kernels are compiled and
    ``check_tileable`` raises on a group that is no multiple of 128 values;
    anywhere else they run in the Pallas interpreter at any width the
    groups divide.  ``bf_kernel_stagings_total{kernel="bf_gated_norm_fwd" |
    "bf_gated_norm_bwd"}`` counts the shapes a kernel was staged for."""
    width = o.shape[-1]
    if z.shape != o.shape or z.dtype != o.dtype or scale.shape != (width,) \
            or width % groups:
        raise ValueError(
            f"gated_rms_norm: o {o.shape} {o.dtype}, z {z.shape} {z.dtype}, "
            f"scale {scale.shape}, {groups} groups: need o and z alike, a "
            "scale of their last dim and groups that divide it")
    interpret = platform_in_use(o) != "tpu"
    if not interpret:
        check_tileable(width, groups)
    total = o.size // width
    vma = frozenset().union(*(jax.typeof(t).vma for t in (o, z, scale)))
    y = _norm(o.reshape(total, width), z.reshape(total, width),
              scale.astype(_F32)[None], groups, float(eps),
              _block_rows(total, width, o.dtype.itemsize), interpret, vma)
    return y.reshape(o.shape)
