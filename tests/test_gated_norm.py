"""``ops.gated_norm``: the Mamba-2 mixer's gate and grouped RMS norm as two
Pallas kernels behind one custom VJP, in the interpreter on the CPU, against
the plain ``jax.numpy`` formula that ``Mamba2Mixer`` held before them
(``tests/test_ssm_moe_model.py`` holds the whole mixer to the configuration's
reference)."""

import functools
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import bluefog_tpu as bf  # noqa: E402
from bluefog_tpu.ops import gated_norm  # noqa: E402
from bluefog_tpu.ops.gated_norm import gated_rms_norm  # noqa: E402
from bluefog_tpu.utils import telemetry  # noqa: E402
import twins  # noqa: E402
from twins import rel  # noqa: E402

KEY = jax.random.PRNGKey(49)
normal = functools.partial(twins.normal, KEY)
EPS = 1e-5


def plain(o, z, scale, groups):
    """What the mixer computed in ``jax.numpy``: float32 throughout, one
    cast at the end."""
    x = (o.astype(jnp.float32) * jax.nn.silu(z.astype(jnp.float32))).reshape(
        o.shape[:-1] + (groups, -1))
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + EPS)
    return (x.reshape(o.shape) * scale).astype(o.dtype)


def operands(shape, dtype=jnp.float32):
    scale = 1.0 + 0.1 * normal(3, shape[-1:])
    return (normal(1, shape).astype(dtype), normal(2, shape).astype(dtype),
            scale, normal(4, shape))


def value_and_grads(fn, weight, wrap=lambda f: f):
    """One program: ``fn``'s value and the gradients of ``sum(fn *
    weight)`` in ``o``, ``z`` and ``scale``."""
    def loss(*a):
        return (wrap(fn)(*a).astype(jnp.float32) * weight).sum()
    return jax.jit(lambda *a: (fn(*a), jax.grad(loss, (0, 1, 2))(*a)))


def both(shape, groups, dtype=jnp.float32, wrap=lambda f: f):
    o, z, scale, weight = operands(shape, dtype)
    mine = lambda o, z, s: gated_rms_norm(  # noqa: E731
        o, z, s, groups=groups, eps=EPS)
    theirs = lambda o, z, s: plain(o, z, s, groups)  # noqa: E731
    return (value_and_grads(mine, weight, wrap)(o, z, scale),
            value_and_grads(theirs, weight)(o, z, scale))


@pytest.fixture
def small_blocks(monkeypatch):
    """Blocks of 32 rows at 64 float32 columns, two tiles each."""
    monkeypatch.setattr(gated_norm, "_BLOCK_BYTES", 32 * 64 * 4)


@pytest.mark.parametrize("shape,groups", [
    ((2, 64, 64), 8),       # whole blocks
    ((2, 64, 64), 1),       # one group: a plain RMS norm of the gated row
    ((2, 37, 64), 8),       # 74 rows: two blocks and a third of 10
    ((1, 100, 64), 2),      # a last block of 4 rows, one group a half row
    ((24, 64), 4),          # shorter than a block, no batch
    ((3, 64), 8),           # shorter than a tile
], ids=str)
def test_values_and_gradients_in_float32(small_blocks, shape, groups):
    (got, g_mine), (want, g_theirs) = both(shape, groups)
    assert got.shape == shape and got.dtype == jnp.float32
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    for name, a, b in zip("o z scale".split(), g_mine, g_theirs):
        assert a.shape == b.shape and a.dtype == b.dtype, name
        assert rel(a, b) < 1e-5, name


@pytest.mark.parametrize("groups", [8, 1])
def test_bfloat16_operands_keep_float32_inside(groups):
    """The published group width (512 at 8 groups) in the cell's dtype:
    results in bfloat16, ``d scale`` in float32, all within bfloat16's
    rounding of the formula's."""
    (got, g_mine), (want, g_theirs) = both((1, 40, 4096), groups,
                                           jnp.bfloat16)
    assert got.dtype == jnp.bfloat16
    assert [g.dtype for g in g_mine] == [jnp.bfloat16, jnp.bfloat16,
                                         jnp.float32]
    f32 = lambda t: t.astype(jnp.float32)  # noqa: E731
    assert rel(f32(got), f32(want)) < 1e-2
    for name, a, b in zip("o z scale".split(), g_mine, g_theirs):
        assert rel(f32(a), f32(b)) < 1e-2, name
    # the sums over the rows are float32: no bfloat16 step shows in them
    assert rel(g_mine[2], g_theirs[2]) < 1e-5


def _under_a_checkpoint():
    """``jax.checkpoint`` runs the forward kernel again and hands the
    backward kernel the operands: nothing else was kept."""
    (got, g_mine), (want, g_theirs) = both((2, 37, 64), 8,
                                           wrap=jax.checkpoint)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    for a, b in zip(g_mine, g_theirs):
        assert rel(a, b) < 1e-5


def _under_rank_map():
    """Four ranks, each on its own operands: the calls stand under
    ``shard_map`` with the operands' ``vma`` handed on."""
    n = 4
    bf.init(devices=jax.devices()[:n])
    o, z, scale, weight = operands((n, 2, 37, 64))
    scale = jnp.stack([scale * (1.0 + r) for r in range(n)])

    def one(fn):
        def loss(o, z, s, w):
            return (fn(o, z, s) * w).sum()
        return jax.value_and_grad(loss, (0, 1, 2))
    mine = bf.rank_map(one(lambda o, z, s: gated_rms_norm(
        o, z, s, groups=8, eps=EPS)))(o, z, scale, weight)
    theirs = jax.vmap(one(lambda o, z, s: plain(o, z, s, 8)))(
        o, z, scale, weight)
    for a, b in zip(jax.tree.leaves(mine), jax.tree.leaves(theirs)):
        assert a.shape == b.shape
        assert rel(a, b) < 1e-5


def _staged_once_a_shape():
    """Forward and backward kernels are staged once for a shape: a second
    call and a second program that uses the shape stage nothing new
    (``jax.checkpoint`` traces in a context of its own: the forward once
    more, whatever the number of recomputes); a new shape stages both
    again."""
    staged = lambda: {  # noqa: E731
        k: v for k, v in telemetry.snapshot().items()
        if k.startswith("bf_kernel_stagings_total") and "bf_gated_norm" in k}
    name = 'bf_kernel_stagings_total{kernel="bf_gated_norm_%s"}'
    o, z, scale, weight = operands((2, 21, 48))
    norm = lambda o, z, s: gated_rms_norm(  # noqa: E731
        o, z, s, groups=3, eps=EPS)
    grad = lambda f: jax.grad(  # noqa: E731
        lambda *a: (f(*a) * weight[:, :a[0].shape[1]]).sum(), (0, 1, 2))
    before = staged()
    norm(o, z, scale)
    grad(norm)(o, z, scale)
    first = staged()
    assert first[name % "fwd"] - before.get(name % "fwd", 0.0) == 1
    assert first[name % "bwd"] - before.get(name % "bwd", 0.0) == 1
    grad(norm)(o, z, scale)
    jax.jit(lambda *a: norm(*a) * 2.0 + norm(*a))(o, z, scale)
    assert staged() == first
    grad(jax.checkpoint(norm))(o, z, scale)
    grad(jax.checkpoint(lambda *a: norm(*a) * 2.0))(o, z, scale)
    assert staged() == {name % "fwd": first[name % "fwd"] + 1,
                        name % "bwd": first[name % "bwd"]}
    grad(norm)(o[:, :20], z[:, :20], scale)
    assert staged() == {name % "fwd": first[name % "fwd"] + 2,
                        name % "bwd": first[name % "bwd"] + 1}


@pytest.mark.parametrize("case", [
    _under_a_checkpoint, _under_rank_map, _staged_once_a_shape],
    ids=lambda f: f.__name__.strip("_"))
def test_the_norm_kernels(small_blocks, case):
    case()


def test_widths_a_tpu_cannot_tile_raise_and_the_interpreter_takes_them():
    """The compiled kernels cut a group out of a block's lanes, whole tiles
    of 128; the check needs no TPU, and off the TPU the same widths run.
    Operands that do not belong together raise anywhere."""
    gated_norm.check_tileable(4096, 8)          # the published mixer
    gated_norm.check_tileable(256, 1)
    for width, groups in ((4096, 64), (192, 1), (512, 8), (100, 3)):
        with pytest.raises(ValueError, match=f"{groups} groups over a width "
                                             f"of {width}"):
            gated_norm.check_tileable(width, groups)
    o, z, scale, _ = operands((2, 5, 12))
    out = gated_rms_norm(o, z, scale, groups=4, eps=EPS)
    assert out.shape == o.shape and bool(jnp.isfinite(out).all())
    for bad in ((o, z[:, :4], scale, 4), (o, z, scale[:6], 4),
                (o, z, scale, 5), (o, z.astype(jnp.bfloat16), scale, 4)):
        with pytest.raises(ValueError, match="gated_rms_norm"):
            gated_rms_norm(*bad[:3], groups=bad[3], eps=EPS)
