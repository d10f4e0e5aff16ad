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


# ---------------------------------------------------------------------------
# The three kinds of tile (skipped, crossed, interior), each with gradients
# ---------------------------------------------------------------------------

def _dense_lse(q, k, v, *, causal, scale=None):
    """Dense float32 attention with its per-row logsumexp ``(B, S, H)``."""
    q, k, v = (t.astype(jnp.float32) for t in (q, k, v))
    if scale is None:
        scale = 1.0 / np.sqrt(q.shape[-1])
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    if causal:
        mask = jnp.tril(jnp.ones(logits.shape[-2:], bool))
        logits = jnp.where(mask, logits, -jnp.inf)
    lse = jax.nn.logsumexp(logits, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", jnp.exp(logits - lse[..., None]), v)
    return out, lse.transpose(0, 2, 1)


def _assert_grads_match(loss_out, loss_ref, args, *, rtol, atol):
    g_out = jax.grad(loss_out, argnums=(0, 1, 2))(*args)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(*args)
    for a, b in zip(g_out, g_ref):
        assert a.dtype == b.dtype and a.shape == b.shape
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   rtol=rtol, atol=atol)


# 32/16 and 16/32: tiles that the diagonal crosses off their own corners,
# in both directions; 64/64: a sequence of one block, no interior tile;
# 16/64 and 64/16: one block along one side only.
@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("blocks", [(32, 16), (16, 32), (64, 64), (16, 64),
                                    (64, 16)])
def test_flash_grads_at_uneven_blocks(qkv, causal, blocks):
    block_q, block_k = blocks

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=causal,
                                       block_q=block_q, block_k=block_k) ** 2)

    def loss_dense(q, k, v):
        return jnp.sum(local_attention(q, k, v, causal=causal) ** 2)

    _assert_grads_match(loss_flash, loss_dense, qkv, rtol=2e-3, atol=2e-3)


@pytest.fixture
def qkv_wide_keys():
    """Query-key heads of 24 and value heads of 16."""
    rng = np.random.RandomState(1)
    mk = lambda d: jnp.asarray(rng.randn(B, S, H, d), jnp.float32)
    return mk(24), mk(24), mk(16)


@pytest.mark.parametrize("causal", [True, False])
def test_flash_value_dim_and_scale_of_their_own(qkv_wide_keys, causal):
    q, k, v = qkv_wide_keys
    scale = 0.173                       # no power of two, not 1 / sqrt(24)
    ref = local_attention(q, k, v, causal=causal, scale=scale)
    out = flash_attention(q, k, v, causal=causal, block_q=32, block_k=16,
                          scale=scale)
    assert out.shape == (B, S, H, 16)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("causal", [True, False])
def test_flash_grads_value_dim_and_scale_of_their_own(qkv_wide_keys, causal):
    scale = 0.173

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=causal, block_q=16,
                                       block_k=32, scale=scale) ** 2)

    def loss_dense(q, k, v):
        return jnp.sum(local_attention(q, k, v, causal=causal,
                                       scale=scale) ** 2)

    _assert_grads_match(loss_flash, loss_dense, qkv_wide_keys,
                        rtol=2e-3, atol=2e-3)


@pytest.mark.parametrize("blocks", [(32, 32), (32, 16), (16, 32)])
def test_flash_bf16_grads(qkv, blocks):
    """bfloat16 inputs: the gradients come back in bfloat16 and agree with
    the dense float32 reference on the same (rounded) inputs at the
    forward's bfloat16 tolerance."""
    block_q, block_k = blocks
    q, k, v = (t.astype(jnp.bfloat16) for t in qkv)

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(
            q, k, v, causal=True, block_q=block_q,
            block_k=block_k).astype(jnp.float32))

    def loss_dense(q, k, v):
        out, _ = _dense_lse(q, k, v, causal=True)
        return jnp.sum(out)

    g_out = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_out, g_ref):
        assert a.dtype == jnp.bfloat16
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   rtol=5e-2, atol=5e-2)


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("blocks", [(16, 16), (32, 16), (16, 32)])
def test_flash_lse_and_its_cotangent(qkv, causal, blocks):
    """A non-zero ``dlse`` (the ring-attention merge weights partials by
    their logsumexp) folds into the backward's delta term."""
    from bluefog_tpu.ops.flash_attention import flash_attention_lse
    block_q, block_k = blocks
    w = jnp.asarray(np.random.RandomState(2).randn(B, S, H), jnp.float32)

    def loss_flash(q, k, v):
        out, lse = flash_attention_lse(q, k, v, causal=causal,
                                       block_q=block_q, block_k=block_k)
        return jnp.sum(out ** 2) + jnp.sum(w * lse)

    def loss_dense(q, k, v):
        out, lse = _dense_lse(q, k, v, causal=causal)
        return jnp.sum(out ** 2) + jnp.sum(w * lse)

    np.testing.assert_allclose(float(loss_flash(*qkv)),
                               float(loss_dense(*qkv)), rtol=1e-5)
    _assert_grads_match(loss_flash, loss_dense, qkv, rtol=2e-3, atol=2e-3)


# Blocks whose crossed tiles take a body per static offset (equal; one twice
# the other, chunked from 512 rows up) and blocks whose crossed tiles take
# the offset as it comes (more than two crossings).
PLANNED_BLOCKS = [(16, 16), (32, 16), (16, 32), (64, 64), (8, 64), (64, 8),
                  (512, 512), (1024, 512), (512, 1024), (1024, 1024),
                  (256, 1024), (24, 40)]


@pytest.mark.parametrize("blocks", PLANNED_BLOCKS)
def test_every_tile_is_skipped_crossed_or_interior(blocks):
    """The offset of a tile says which of its scores are kept: none
    (skipped), some (a crossing the kernel has a body for), all (interior)."""
    from bluefog_tpu.ops.flash_attention import _crossings
    block_q, block_k = blocks
    seq = 240 if blocks == (24, 40) else 4 * max(block_q, block_k)
    crossings = _crossings(block_q, block_k)
    assert len(crossings) == len(set(crossings))
    for qi in range(seq // block_q):
        for kb in range(seq // block_k):
            offset = qi * block_q - kb * block_k
            rows = np.arange(block_q)[:, None] + offset
            keep = np.arange(block_k)[None, :] <= rows
            if offset <= -block_q:
                assert not keep.any()
            elif offset >= block_k - 1:
                assert keep.all()
            else:
                assert keep.any() and not keep.all()
            assert (offset in crossings) == (keep.any() and not keep.all())


@pytest.mark.parametrize("blocks", PLANNED_BLOCKS)
def test_a_crossed_tiles_chunks_cover_what_the_diagonal_keeps(blocks):
    """Per chunk of a crossed tile: every kept pair is inside
    the keys (queries) the chunk computes, and a chunk that builds no mask
    keeps every pair it computes."""
    from bluefog_tpu.ops.flash_attention import (
        _BWD_CHUNK, _FWD_CHUNK, _chunk_rows, _crossings, _keys_of,
        _queries_of)
    block_q, block_k = blocks
    for offset in _crossings(block_q, block_k):
        keep = (np.arange(block_k)[None, :]
                <= np.arange(block_q)[:, None] + offset)
        for want in (_FWD_CHUNK, _BWD_CHUNK):
            rows = _chunk_rows(offset, block_q, want)
            for q0 in range(0, block_q, rows):
                hi, masked = _keys_of(offset, q0, rows, block_k)
                part = keep[q0:q0 + rows]
                assert 0 <= hi <= block_k and not part[:, hi:].any()
                assert masked == (not part[:, :hi].all())
            rows = _chunk_rows(offset, block_k, want)
            for k0 in range(0, block_k, rows):
                lo, masked = _queries_of(offset, k0, rows, block_q)
                part = keep[:, k0:k0 + rows]
                assert 0 <= lo <= block_q and not part[:lo].any()
                assert masked == (not part[lo:].all())
    # an interior tile computes the whole tile, in one piece
    assert _chunk_rows(None, block_q, _FWD_CHUNK) == block_q
    assert _keys_of(None, 0, block_q, block_k) == (block_k, False)
    assert _queries_of(None, 0, block_k, block_q) == (0, False)


@pytest.fixture(scope="module")
def long_qkv():
    """One head of 16 over 2048 positions: blocks of 512 and 1024 chunk
    their crossed tiles as the cells' do."""
    rng = np.random.RandomState(3)
    mk = lambda: jnp.asarray(rng.randn(1, 2048, 1, 16), jnp.float32)
    return mk(), mk(), mk()


# (1024, 1024) and (512, 512): one crossing, chunks of 512 and of 256 rows
# with keys skipped; (1024, 512) and (512, 1024): two crossings (the
# backward at heads over 128); (256, 1024): four crossings, a body each.
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("blocks", [(1024, 1024), (512, 512), (1024, 512),
                                    (512, 1024), (256, 1024)])
def test_flash_grads_where_crossed_tiles_are_chunked(long_qkv, blocks,
                                                     dtype):
    from bluefog_tpu.ops.flash_attention import flash_attention_lse
    block_q, block_k = blocks
    q, k, v = (t.astype(dtype) for t in long_qkv)
    w = jnp.asarray(np.random.RandomState(4).randn(1, 2048, 1), jnp.float32)

    def loss_flash(q, k, v):
        out, lse = flash_attention_lse(q, k, v, causal=True,
                                       block_q=block_q, block_k=block_k)
        return jnp.sum(out.astype(jnp.float32) ** 2) + jnp.sum(w * lse)

    def loss_dense(q, k, v):
        out, lse = _dense_lse(q, k, v, causal=True)
        return jnp.sum(out ** 2) + jnp.sum(w * lse)

    tol = 2e-3 if dtype == jnp.float32 else 5e-2
    np.testing.assert_allclose(float(loss_flash(q, k, v)),
                               float(loss_dense(q, k, v)), rtol=tol)
    _assert_grads_match(loss_flash, loss_dense, (q, k, v), rtol=tol,
                        atol=tol)


# ---------------------------------------------------------------------------
# What the benchmark's roofline readers hold the kernels to: names, grids,
# blocks, operands and results at the cells' shapes (traced, nothing runs).
# The expectations are the trace of the tree before PR 37.
# ---------------------------------------------------------------------------

def _pallas_calls(jaxpr, found):
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            found.append(eqn)
        for param in eqn.params.values():
            for sub in (param if isinstance(param, (list, tuple))
                        else [param]):
                if hasattr(sub, "jaxpr"):
                    _pallas_calls(sub.jaxpr, found)
                elif hasattr(sub, "eqns"):
                    _pallas_calls(sub, found)
    return found


# cell: (B, S, H, D, Dv), then per kernel (grid, q-side block, k-side block)
CELL_KERNELS = {
    "lfm2-s8192-1chip": ((2, 8192, 32, 64, 64), {
        "bf_flash_fwd": ((64, 8, 8), 1024, 1024),
        "bf_flash_dq": ((64, 8, 8), 1024, 1024),
        "bf_flash_dkv": ((64, 8, 8), 1024, 1024)}),
    "lm-s16384-1chip": ((1, 16384, 16, 128, 128), {
        "bf_flash_fwd": ((16, 16, 16), 1024, 1024),
        "bf_flash_dq": ((16, 16, 16), 1024, 1024),
        "bf_flash_dkv": ((16, 16, 16), 1024, 1024)}),
    "lm-s4096-gossip-4chip": ((2, 4096, 16, 128, 128), {
        "bf_flash_fwd": ((32, 4, 4), 1024, 1024),
        "bf_flash_dq": ((32, 4, 4), 1024, 1024),
        "bf_flash_dkv": ((32, 4, 4), 1024, 1024)}),
    "xing4-s4096-1chip": ((1, 4096, 32, 192, 128), {
        "bf_flash_fwd": ((32, 4, 4), 1024, 1024),
        "bf_flash_dq": ((32, 4, 8), 1024, 512),
        "bf_flash_dkv": ((32, 8, 4), 1024, 512)}),
}


@pytest.mark.parametrize("cell", sorted(CELL_KERNELS))
def test_cells_kernels_keep_names_grids_blocks_and_operands(cell):
    (b, s, h, d, dv), kernels = CELL_KERNELS[cell]
    q = jax.ShapeDtypeStruct((b, s, h, d), jnp.bfloat16)
    v = jax.ShapeDtypeStruct((b, s, h, dv), jnp.bfloat16)

    def loss(q, k, v):
        return flash_attention(q, k, v).astype(jnp.float32).sum()

    calls = _pallas_calls(
        jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2)))(q, q, v).jaxpr, [])
    assert [c.params["name"] for c in calls] == list(kernels)
    for call in calls:
        grid, bq, bk = kernels[call.params["name"]]
        mapping = call.params["grid_mapping"]
        assert mapping.grid == grid
        blocks = [tuple(getattr(dim, "block_size", None)
                        for dim in m.block_shape)
                  for m in mapping.block_mappings]
        results = [(a.shape, a.dtype) for a in call.params["out_avals"]]
        bf16, rows = jnp.bfloat16, (None, bq, 1)
        expected = {
            "bf_flash_fwd": (
                [(None, bq, d), (None, bk, d), (None, bk, dv),
                 (None, bq, dv), rows],
                [((b * h, s, dv), bf16), ((b * h, s, 1), jnp.float32)]),
            "bf_flash_dq": (
                [(None, bq, d), (None, bk, d), (None, bk, dv),
                 (None, bq, dv), rows, rows, (None, bq, d)],
                [((b * h, s, d), bf16)]),
            "bf_flash_dkv": (
                [(None, bq, d), (None, bk, d), (None, bk, dv),
                 (None, bq, dv), rows, rows, (None, bk, d), (None, bk, dv)],
                [((b * h, s, d), bf16), ((b * h, s, dv), bf16)]),
        }[call.params["name"]]
        assert (blocks, results) == expected
