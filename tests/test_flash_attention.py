"""Flash attention kernel tests (interpreter mode on CPU) vs dense oracle."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bluefog_tpu.models.transformer import local_attention
from bluefog_tpu.ops.flash_attention import flash_attention

B, S, H, D = 2, 64, 2, 16


@pytest.fixture
def qkv():
    rng = np.random.RandomState(0)
    mk = lambda: jnp.asarray(rng.randn(B, S, H, D), jnp.float32)
    return mk(), mk(), mk()


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("block", [16, 32, 64])
def test_flash_matches_dense(qkv, causal, block):
    q, k, v = qkv
    ref = local_attention(q, k, v, causal=causal)
    out = flash_attention(q, k, v, causal=causal, block_q=block,
                          block_k=block)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("causal", [True, False])
def test_flash_grads_match_dense(qkv, causal):
    q, k, v = qkv

    def loss_dense(q, k, v):
        return jnp.sum(local_attention(q, k, v, causal=causal) ** 2)

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=causal, block_q=16,
                                       block_k=16) ** 2)

    g_ref = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    g_out = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_out, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-3, atol=2e-3)


def test_flash_uneven_blocks(qkv):
    q, k, v = qkv
    ref = local_attention(q, k, v, causal=True)
    out = flash_attention(q, k, v, causal=True, block_q=32, block_k=16)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)


def test_flash_inside_ulysses(devices, qkv):
    """flash kernel as the inner attention of Ulysses sequence parallelism."""
    from jax.sharding import Mesh, PartitionSpec as P

    from bluefog_tpu.ops.flash_attention import flash_attention_impl
    from bluefog_tpu.parallel import ulysses_attention

    q, k, v = qkv
    ref = local_attention(q, k, v, causal=True)
    mesh = Mesh(np.asarray(devices[:2]), ("sp",))
    out = jax.jit(jax.shard_map(
        lambda a, b, c: ulysses_attention(
            a, b, c, axis_name="sp", causal=True,
            inner_attention=flash_attention_impl(block_q=16, block_k=16)),
        mesh=mesh, in_specs=(P(None, "sp"),) * 3,
        out_specs=P(None, "sp"), check_vma=False))(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)


def test_flash_bf16(qkv):
    q, k, v = (t.astype(jnp.bfloat16) for t in qkv)
    ref = local_attention(q, k, v, causal=True)
    out = flash_attention(q, k, v, causal=True, block_q=32, block_k=32)
    assert out.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=5e-2, atol=5e-2)
