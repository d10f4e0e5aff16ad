"""``models.transformer.apply_rope``: a head is turned in one pass over its
whole width, forward and transposed, and the values are those of the form it
replaced (two half-width slices, the rotation, a concatenate), to the bit.

The comparisons run op by op (no ``jax.jit`` around either side): compiled
for the CPU, XLA contracts ``a * b + c * d`` into a fused multiply-add on
whichever side it likes, which moves a last bit and is no property of
either form.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bluefog_tpu.models import transformer as T

YARN = {"factor": 64, "original_max_position_embeddings": 4096}


def sliced_rope(x, positions, theta=10000.0, freq=None, scale=1.0, rot=None):
    """The reference: ``apply_rope`` as it was before PR 41."""
    rot = x.shape[-1] if rot is None else rot
    d2 = rot // 2
    if freq is None:
        freq = theta ** (-jnp.arange(d2, dtype=jnp.float32) / d2)
    ang = positions[..., None].astype(jnp.float32) * freq
    cos = jnp.cos(ang)[:, :, None, :]
    sin = jnp.sin(ang)[:, :, None, :]
    if scale != 1.0:
        cos, sin = cos * scale, sin * scale
    x1 = x[..., :d2].astype(jnp.float32)
    x2 = x[..., d2:rot].astype(jnp.float32)
    turned = jnp.concatenate([x1 * cos - x2 * sin,
                              x1 * sin + x2 * cos], -1).astype(x.dtype)
    if rot == x.shape[-1]:
        return turned
    return jnp.concatenate([turned, x[..., rot:]], -1)


def bits(a):
    a = np.asarray(a)
    return a.view({2: np.uint16, 4: np.uint32}[a.dtype.itemsize])


def case(dim, heads, seq, dtype, seed=0):
    """``x``, a cotangent and positions: a decode step far along for ``seq``
    of one, else rows that start at different offsets."""
    kx, kd = jax.random.split(jax.random.PRNGKey(seed + dim + heads + seq))
    x = jax.random.normal(kx, (2, seq, heads, dim), jnp.float32).astype(dtype)
    dy = jax.random.normal(kd, x.shape, jnp.float32).astype(dtype)
    start = jnp.asarray([[5000], [3]]) if seq == 1 else jnp.asarray(
        [[0], [700]])
    return x, dy, start + jnp.arange(seq)[None, :]


SHAPES = [(128, 128, 1.0, 64), (128, 64, 1.2079, 48), (64, 64, 1.0, 32),
          (64, 64, 1.0, 1)]


@pytest.mark.parametrize("given", [False, True], ids=["theta", "freq"])
@pytest.mark.parametrize("seq", [1, 37], ids=["decode", "odd"])
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bfloat16", "float32"])
@pytest.mark.parametrize("dim,rot,scale,heads", SHAPES)
def test_forward_and_transpose_equal_the_sliced_form_to_the_bit(
        dim, rot, scale, heads, dtype, seq, given):
    x, dy, positions = case(dim, heads, seq, dtype)
    freq = T.yarn_frequencies(rot, 1e4, YARN) if given else None
    y, vjp = jax.vjp(
        lambda x: T.apply_rope(x, positions, 1e4, freq, scale, rot), x)
    want, want_vjp = jax.vjp(
        lambda x: sliced_rope(x, positions, 1e4, freq, scale, rot), x)
    assert y.dtype == want.dtype == dtype and y.shape == x.shape
    np.testing.assert_array_equal(bits(y), bits(want))
    (dx,), (want_dx,) = vjp(dy), want_vjp(dy)
    assert dx.dtype == dtype
    np.testing.assert_array_equal(bits(dx), bits(want_dx))


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bfloat16", "float32"])
@pytest.mark.parametrize("dim,rot,scale,heads", SHAPES)
def test_a_zero_position_is_the_identity_and_the_tail_passes_through(
        dim, rot, scale, heads, dtype):
    x, dy, positions = case(dim, heads, 5, dtype, seed=1)
    still = T.apply_rope(x, jnp.zeros_like(positions), rot=rot)
    np.testing.assert_array_equal(bits(still), bits(x))
    y, vjp = jax.vjp(
        lambda x: T.apply_rope(x, positions, scale=scale, rot=rot), x)
    np.testing.assert_array_equal(bits(y[..., rot:]), bits(x[..., rot:]))
    np.testing.assert_array_equal(bits(vjp(dy)[0][..., rot:]),
                                  bits(dy[..., rot:]))
    # and what is rotated is: no row but the first of the first sequence
    # (position 0) comes back as it went in
    assert not np.any(np.all(
        bits(y[1, :, :, :rot]) == bits(x[1, :, :, :rot]), axis=-1))


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bfloat16", "float32"])
@pytest.mark.parametrize("dim,rot,first,heads",
                         [(192, 64, 128, 32), (128, 32, 64, 4),
                          (96, 32, 16, 1)])
def test_rotary_lanes_that_start_past_the_heads_first_equal_a_slice_rotated(
        dim, rot, first, heads, dtype):
    """``first=``: the latent path's 64 rotary lanes after 128 plain ones
    go through as one whole head, and come out as the slice rotated alone
    and put back between what surrounds it, forward and transposed."""
    x, dy, positions = case(dim, heads, 7, dtype, seed=4)
    freq = T.yarn_frequencies(rot, 1e4, YARN)

    def put_back(x):
        return jnp.concatenate(
            [x[..., :first],
             sliced_rope(x[..., first:first + rot], positions, 1e4, freq),
             x[..., first + rot:]], -1)
    y, vjp = jax.vjp(lambda x: T.apply_rope(
        x, positions, 1e4, freq, rot=rot, first=first), x)
    want, want_vjp = jax.vjp(put_back, x)
    np.testing.assert_array_equal(bits(y), bits(want))
    np.testing.assert_array_equal(bits(vjp(dy)[0]), bits(want_vjp(dy)[0]))
    if first + rot == dim:  # rot left out: all that follows first
        np.testing.assert_array_equal(bits(y), bits(T.apply_rope(
            x, positions, 1e4, freq, first=first)))
    with pytest.raises(ValueError, match="even and within the head"):
        T.apply_rope(x, positions, rot=rot, first=dim - rot + 2)


def test_the_transpose_is_the_rotation_back():
    """Rotating by ``-sin`` undoes a rotation (in float32, to rounding), and
    that is what ``jax.vjp`` hands back: the adjoint of an orthogonal map
    is its inverse, so the cotangent of ``y`` itself is ``x`` times the
    factor squared."""
    x, _, positions = case(128, 4, 9, jnp.float32, seed=2)
    y, vjp = jax.vjp(lambda x: T.apply_rope(
        x, positions, scale=1.25, rot=64), x)
    back = np.asarray(vjp(y)[0])
    np.testing.assert_allclose(back[..., :64],
                               1.25 ** 2 * np.asarray(x)[..., :64],
                               rtol=0, atol=2e-6)
    np.testing.assert_array_equal(back[..., 64:], np.asarray(x)[..., 64:])


@pytest.mark.parametrize("rot", [63, 7, 130, 256])
def test_an_odd_rotary_part_or_one_wider_than_the_head_raises(rot):
    """Where the sliced form raised (its halves did not match cos and sin)
    this one does, and says what is wrong."""
    x, _, positions = case(128, 2, 3, jnp.float32)
    with pytest.raises((ValueError, TypeError)):
        sliced_rope(x, positions, rot=rot)
    with pytest.raises(ValueError, match="even and within the head"):
        T.apply_rope(x, positions, rot=rot)


@pytest.mark.parametrize("dim,rot,scale,heads", SHAPES)
def test_the_gradient_program_names_no_half_width_array(dim, rot, scale,
                                                        heads):
    """The shape of the program, not its values: under ``value_and_grad``
    nothing with a head axis has a last dimension of ``rot / 2``.  Those
    intermediates are what cost on a TPU (a minor dimension of 64 fills
    half a tile of 128 lanes, so each half moves the bytes of the whole,
    and the concatenate is a pass of its own); cos and sin, ``(B, S, rot /
    2)`` with no head axis, are a ``heads``-th of that."""
    x, _, positions = case(dim, heads, 16, jnp.bfloat16)

    def loss(x):
        y = T.apply_rope(x, positions, scale=scale, rot=rot)
        return (y.astype(jnp.float32) ** 2).sum()

    def avals(jaxpr):
        for eqn in jaxpr.eqns:
            yield from (v.aval for v in eqn.outvars)
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from avals(sub)

    closed = jax.make_jaxpr(jax.value_and_grad(loss))(x)
    seen = list(avals(closed.jaxpr))
    assert sum(a.shape == x.shape for a in seen) >= 4
    narrow = [a for a in seen
              if a.ndim == x.ndim and a.shape[-1] == rot // 2]
    assert not narrow, narrow
    # the reference is what this case is there to catch
    was = jax.make_jaxpr(jax.value_and_grad(
        lambda x: (sliced_rope(x, positions, scale=scale, rot=rot)
                   .astype(jnp.float32) ** 2).sum()))(x)
    assert any(a.shape == x.shape[:-1] + (rot // 2,)
               for a in avals(was.jaxpr))


def test_under_jit_and_remat_the_values_hold():
    """Through ``jax.jit`` and ``jax.checkpoint`` (how ``Block`` runs it)
    the result is the sliced form's to float32 rounding, forward and
    gradient, and the written transpose goes through a second
    differentiation (the transpose of the transpose is the rotation)."""
    x, dy, positions = case(64, 3, 11, jnp.float32, seed=3)

    def through(fn):
        def loss(x):
            return (jax.checkpoint(lambda x: fn(x, positions, rot=32))(x)
                    * dy).sum()
        return jax.jit(jax.value_and_grad(loss))(x)
    (got, dgot), (want, dwant) = through(T.apply_rope), through(sliced_rope)
    np.testing.assert_allclose(got, want, rtol=1e-5)
    np.testing.assert_allclose(dgot, dwant, rtol=0, atol=1e-6)

    def twice(fn):
        g = jax.grad(lambda x: (fn(x, positions) * dy).sum())
        return jax.grad(lambda x: (g(x) ** 2).sum())(x)
    # the map is linear, so its gradient does not depend on x and the
    # second derivative is zero on both sides
    np.testing.assert_array_equal(twice(T.apply_rope), twice(sliced_rope))
