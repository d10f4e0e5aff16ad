"""Flash attention kernel tests (interpreter mode on CPU) vs dense oracle."""

import functools

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


def _chunks_cover(block_q, block_k, offset, keep, window=None):
    """Per chunk of a tile at ``offset`` whose visible pairs are ``keep``:
    every kept pair is inside the keys (queries) the chunk computes, and a
    chunk that builds no mask keeps every pair it computes."""
    from bluefog_tpu.ops.flash_attention import (
        _BWD_CHUNK, _FWD_CHUNK, _chunk_rows, _keys_of, _queries_of)
    for want in (_FWD_CHUNK, _BWD_CHUNK):
        rows = _chunk_rows(offset, block_q, want)
        for q0 in range(0, block_q, rows):
            lo, hi, masked = _keys_of(offset, q0, rows, block_k, window)
            part = keep[q0:q0 + rows]
            assert 0 <= lo <= hi <= block_k
            assert not part[:, :lo].any() and not part[:, hi:].any()
            assert masked == (not part[:, lo:hi].all())
        rows = _chunk_rows(offset, block_k, want)
        for k0 in range(0, block_k, rows):
            lo, hi, masked = _queries_of(offset, k0, rows, block_q, window)
            part = keep[:, k0:k0 + rows]
            assert 0 <= lo <= hi <= block_q
            assert not part[:lo].any() and not part[hi:].any()
            assert masked == (not part[lo:hi].all())


@pytest.mark.parametrize("blocks", PLANNED_BLOCKS)
def test_a_crossed_tiles_chunks_cover_what_the_diagonal_keeps(blocks):
    from bluefog_tpu.ops.flash_attention import (
        _FWD_CHUNK, _chunk_rows, _crossings, _keys_of, _queries_of)
    block_q, block_k = blocks
    for offset in _crossings(block_q, block_k):
        keep = (np.arange(block_k)[None, :]
                <= np.arange(block_q)[:, None] + offset)
        _chunks_cover(block_q, block_k, offset, keep)
    # an interior tile computes the whole tile, in one piece
    assert _chunk_rows(None, block_q, _FWD_CHUNK) == block_q
    assert _keys_of(None, 0, block_q, block_k) == (0, block_k, False)
    assert _queries_of(None, 0, block_k, block_q) == (0, block_q, False)


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


# ---------------------------------------------------------------------------
# A window: query i sees the keys i - W < j <= i.  The grid's reduction
# dimension covers the blocks a window reaches and no more.
# ---------------------------------------------------------------------------

def _band(n_q, n_k, offset, window):
    """Visible pairs of a tile: ``0 <= query - key < window``."""
    back = np.arange(n_q)[:, None] + offset - np.arange(n_k)[None, :]
    return (back >= 0) & (back < window)


def _dense_window(q, k, v, window):
    """Float32 attention over a full ``(S, S)`` score matrix with the
    window as a mask, and its per-row logsumexp ``(B, S, H)``."""
    q, k, v = (t.astype(jnp.float32) for t in (q, k, v))
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(q.shape[-1])
    seq = logits.shape[-1]
    logits = jnp.where(_band(seq, seq, 0, window), logits, -jnp.inf)
    lse = jax.nn.logsumexp(logits, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", jnp.exp(logits - lse[..., None]), v)
    return out, lse.transpose(0, 2, 1)


WINDOW_BLOCKS = [(64, 64), (64, 32), (32, 64), (128, 64), (256, 256)]
# one key; no multiple of any block; a multiple; both edges in one tile
WINDOWS = [1, 37, 64, 100, 128, 200]


@pytest.mark.parametrize("window", WINDOWS)
@pytest.mark.parametrize("blocks", WINDOW_BLOCKS)
def test_a_windowed_tile_is_dead_crossed_or_interior(blocks, window,
                                                     monkeypatch):
    """Every step of a windowed grid is a dead step (past its block's
    reach), a tile some edge crosses (a body at its static offset whose
    chunks cover the band) or an interior tile; no visible pair lies in a
    tile outside the grid.  The same of the pieces of a banded forward."""
    from bluefog_tpu.ops import flash_attention as fa
    block_q, block_k = blocks
    seq = 4 * max(block_q, block_k)
    crossings = fa._crossings(block_q, block_k, window)
    assert len(crossings) == len(set(crossings))
    n_qb, n_kb = seq // block_q, seq // block_k
    whole = _band(seq, seq, 0, window)

    def check(offsets, tiles_of):
        """``tiles_of(own block)``: the blocks of the other side its steps
        load, dead ones included."""
        seen = np.zeros((n_qb, n_kb), bool)
        for at, o in enumerate(offsets):
            own, step = divmod(at, len(offsets) // len(tiles_of))
            if o is None:
                continue
            qi, kb = tiles_of[own](step)
            assert o == qi * block_q - kb * block_k and not seen[qi, kb]
            seen[qi, kb] = True
            keep = _band(block_q, block_k, o, window)
            assert keep.any()
            if block_k - 1 <= o <= window - block_q:
                assert keep.all() and o not in crossings
            else:
                assert not keep.all() and o in crossings
                _chunks_cover(block_q, block_k, o, keep, window)
        for qi in range(n_qb):
            for kb in range(n_kb):
                tile = whole[qi * block_q:(qi + 1) * block_q,
                             kb * block_k:(kb + 1) * block_k]
                assert tile.any() == seen[qi, kb]

    pieces = fa._band_pieces(window, block_q, block_k)
    by_queries, by_keys = fa._Band.pair(window, block_q, block_k, seq)
    if pieces:
        n, first = pieces
        offsets = fa._grid_offsets(n_qb, n_kb, block_q, block_k, by_queries)
        assert len(offsets) == n_qb * n
        check(offsets, [lambda j, qi=qi: (
            qi, qi * (block_q // block_k) + first + j) for qi in range(n_qb)])
    monkeypatch.setattr(fa, "_BAND_KEYS", 0)        # through the grid
    for band in (by_queries, by_keys):
        offsets = fa._grid_offsets(n_qb, n_kb, block_q, block_k, band)
        own = n_kb if band.by_keys else n_qb
        assert len(offsets) == own * band.steps
        assert band.steps <= -(-(window - 1 + band.own) // band.other) + 1
        firsts = [int(band.reach(np, i)[0]) for i in range(own)]
        check(offsets, [lambda j, i=i, f=f: (
            (f + j, i) if band.by_keys else (i, f + j))
            for i, f in enumerate(firsts)])


@pytest.fixture(scope="module")
def window_qkv():
    """Two heads over 512 positions at head sizes 64 and 128."""
    rng = np.random.RandomState(5)
    mk = lambda d: jnp.asarray(rng.randn(1, 512, 2, d), jnp.float32)
    return {d: (mk(d), mk(d), mk(d)) for d in (64, 128)}


# W of 1, no multiple of the blocks, a multiple, and the whole sequence and
# more; blocks that differ; heads of 64 and 128
@pytest.mark.parametrize("head_dim", [64, 128])
@pytest.mark.parametrize("window, blocks", [
    (1, (128, 128)), (100, (128, 64)), (100, (64, 128)), (128, (128, 128)),
    (200, (128, 128)), (256, (64, 128)), (300, (256, 128)), (512, (128, 64)),
    (4096, (128, 64))])
def test_windowed_kernels_match_a_masked_softmax(window_qkv, window, blocks,
                                                 head_dim):
    """``o``, ``lse``, ``dq``, ``dk`` and ``dv`` against a float32 softmax
    over the full score matrix with the window as a mask, and its
    ``jax.grad``."""
    from bluefog_tpu.ops.flash_attention import flash_attention_lse
    block_q, block_k = blocks
    q, k, v = window_qkv[head_dim]
    w = jnp.asarray(np.random.RandomState(6).randn(1, 512, 2), jnp.float32)

    def flash(q, k, v):
        return flash_attention_lse(q, k, v, window=window, block_q=block_q,
                                   block_k=block_k)

    def loss(fn):
        def of(q, k, v):
            out, lse = fn(q, k, v)
            return jnp.sum(out ** 2) + jnp.sum(w * lse)
        return of

    out, lse = flash(q, k, v)
    want, want_lse = _dense_window(q, k, v, window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(np.asarray(lse), np.asarray(want_lse),
                               rtol=2e-5, atol=2e-5)
    _assert_grads_match(loss(flash), loss(functools.partial(
        _dense_window, window=window)), (q, k, v), rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("window", [None, 512, 10 ** 6])
def test_no_window_and_a_window_over_the_sequence_are_the_causal_kernel(
        window_qkv, window, dtype):
    """Bit for bit: ``window=None`` and ``window >= S`` run the kernels
    ``causal=True`` always ran, under their names."""
    from bluefog_tpu.ops.flash_attention import flash_attention_lse
    from bluefog_tpu.utils import telemetry
    q, k, v = (t.astype(dtype) for t in window_qkv[64])

    def run(**kw):
        def loss(q, k, v):
            out, lse = flash_attention_lse(q, k, v, block_q=128, block_k=64,
                                           **kw)
            return jnp.sum(out.astype(jnp.float32) ** 2) + jnp.sum(lse)
        return (flash_attention_lse(q, k, v, block_q=128, block_k=64, **kw),
                jax.grad(loss, argnums=(0, 1, 2))(q, k, v))

    before = dict(telemetry.snapshot())
    got = run(window=window)
    staged = {key: n - before.get(key, 0)
              for key, n in telemetry.snapshot().items()
              if key.startswith("bf_kernel_stagings_total") and "flash" in key
              and n != before.get(key, 0)}
    assert staged and not any("win" in key for key in staged), staged
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(run())):
        assert a.dtype == b.dtype
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))


def test_a_window_needs_causal_and_a_key():
    q = jnp.zeros((1, 64, 1, 16))
    with pytest.raises(ValueError, match="causal=True"):
        flash_attention(q, q, q, causal=False, window=8)
    with pytest.raises(ValueError, match="at least one key"):
        flash_attention(q, q, q, window=0)
    with pytest.raises(ValueError, match="causal=True"):
        local_attention(q, q, q, causal=False, window=8)


@pytest.mark.parametrize("window", [5, 16, 40])
def test_local_attention_takes_the_window(qkv, window):
    """The tests' plain twin masks the same band."""
    q, k, v = qkv
    want, _ = _dense_window(q, k, v, window)
    np.testing.assert_allclose(
        np.asarray(local_attention(q, k, v, window=window)),
        np.asarray(want), rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(
        np.asarray(flash_attention(q, k, v, window=window, block_q=16,
                                   block_k=32)),
        np.asarray(want), rtol=2e-5, atol=2e-5)


# the cell's window layers: (B, S, H, D) = (1, 8192, 64, 128), W 512: the
# forward takes a query block's band in three pieces of 512 keys, the
# backward two steps a block; narrower and wider windows, whose forward goes
# through the grid as the backward does
WINDOWED_GRIDS = [
    ((1, 8192, 64, 128), 512, (64, 8), 3, (64, 8, 2)),
    ((1, 8192, 8, 128), 300, (8, 8), 3, (8, 8, 2)),
    ((1, 8192, 8, 128), 4096, (8, 8, 5), 1, (8, 8, 5)),
    ((2, 2048, 4, 64), 100, (8, 2), 9, (8, 2, 2)),
]


@pytest.mark.parametrize("shape, window, grid_fwd, pieces, grid_bwd",
                         WINDOWED_GRIDS)
def test_windowed_calls_have_names_grids_and_tile_counts_of_their_own(
        shape, window, grid_fwd, pieces, grid_bwd):
    """Traced, nothing runs: the windowed kernels' names, the grids that
    grow with ``S x W`` (a narrow band's forward has no reduction dimension
    but ``pieces`` key blocks a step), and ``bf_flash_tiles_total``'s kinds
    adding up to each grid."""
    from bluefog_tpu.utils import telemetry
    x = jax.ShapeDtypeStruct(shape, jnp.bfloat16)

    def loss(q, k, v):
        return flash_attention(q, k, v, window=window).astype(
            jnp.float32).sum()

    def tiles():
        return {key: n for key, n in telemetry.snapshot().items()
                if key.startswith("bf_flash_tiles_total")}

    before = tiles()
    calls = _pallas_calls(
        jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2)))(x, x, x).jaxpr, [])
    after = tiles()
    assert [c.params["name"] for c in calls] == [
        "bf_flash_win_fwd", "bf_flash_win_dq", "bf_flash_win_dkv"]
    for call, grid, steps in zip(calls, (grid_fwd, grid_bwd, grid_bwd),
                                 (pieces, 1, 1)):
        mapping = call.params["grid_mapping"]
        assert mapping.grid == grid
        assert mapping.block_mappings[0].block_shape[1].block_size == 1024
        # q, the pieces of k and of v, then what the kernel's kind adds
        assert len(mapping.block_mappings) == 2 * steps + {
            "bf_flash_win_fwd": 3, "bf_flash_win_dq": 5,
            "bf_flash_win_dkv": 6}[call.params["name"]]
        name = call.params["name"]
        counted = {kind: after.get(key, 0) - before.get(key, 0)
                   for kind in ("skipped", "crossed", "interior")
                   for key in [f'bf_flash_tiles_total{{kernel="{name}",'
                               f'kind="{kind}"}}']}
        assert sum(counted.values()) == int(np.prod(grid)) * steps, counted
        # dead steps: the first blocks' windows, cut by the sequence's start
        assert counted["skipped"] * 3 < sum(counted.values())
        assert counted["crossed"] > 0
        assert (counted["interior"] > 0) == (window >= 2048)


@pytest.mark.parametrize("window, blocks", [(100, (128, 64)),
                                            (200, (128, 128)),
                                            (300, (256, 128))])
def test_a_wide_bands_forward_goes_through_the_grid(window_qkv, window,
                                                    blocks, monkeypatch):
    """The forward has two ways through a window: a narrow band in one
    softmax pass a row chunk (``_fwd_band_kernel``), a band of more than
    ``_BAND_KEYS`` keys block by block with the running state.  With the
    limit at nothing every band is wide: the same ``o`` and ``lse`` to
    rounding, and the gradients (whose kernels take ``lse`` from it)."""
    from bluefog_tpu.ops import flash_attention as fa
    block_q, block_k = blocks
    q, k, v = window_qkv[64]

    def run():
        jax.clear_caches()
        def loss(q, k, v):
            out, lse = fa.flash_attention_lse(
                q, k, v, window=window, block_q=block_q, block_k=block_k)
            return jnp.sum(out ** 2) + jnp.sum(lse), (out, lse)
        return jax.grad(loss, argnums=(0, 1, 2), has_aux=True)(q, k, v)

    assert fa._band_pieces(window, block_q, min(
        block_k, fa._window_block(block_k, window))) is not None
    banded = run()
    monkeypatch.setattr(fa, "_BAND_KEYS", 0)
    gridded = run()
    jax.clear_caches()
    want, want_lse = _dense_window(q, k, v, window)
    for got in (banded, gridded):
        np.testing.assert_allclose(got[1][0], want, rtol=2e-5, atol=2e-5)
        np.testing.assert_allclose(got[1][1], want_lse, rtol=2e-5, atol=2e-5)
    for a, b in zip(banded[0], gridded[0]):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# A document mask (``segment_ids``): query i sees the keys j <= i of its own
# document.  A tile is dead, crossed by a boundary or inside one document by
# the ids, which are data.
# ---------------------------------------------------------------------------

def _ids(lengths, batch):
    """``(batch, sum(lengths))`` document ids; row ``b``'s documents are the
    lengths rotated by ``b``, so the rows' boundaries differ, or, where
    ``lengths`` is a tuple of lists, row ``b``'s are its ``b``-th list."""
    if isinstance(lengths, tuple):
        rows = [lengths[b % len(lengths)] for b in range(batch)]
    else:
        rows = [lengths[b % len(lengths):] + lengths[:b % len(lengths)]
                for b in range(batch)]
    return jnp.asarray(np.stack([np.repeat(np.arange(len(row)), row)
                                 for row in rows]), jnp.int32)


def _dense_documents(q, k, v, ids, scale=None):
    """Float32 attention over a full ``(S, S)`` score matrix with the
    causal and the document mask written out, and its logsumexp."""
    q, k, v = (t.astype(jnp.float32) for t in (q, k, v))
    scale = 1 / np.sqrt(q.shape[-1]) if scale is None else scale
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    seq = logits.shape[-1]
    seen = jnp.tril(jnp.ones((seq, seq), bool))[None] & (
        ids[:, :, None] == ids[:, None, :])
    logits = jnp.where(seen[:, None], logits, -jnp.inf)
    lse = jax.nn.logsumexp(logits, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", jnp.exp(logits - lse[..., None]), v)
    return out, lse.transpose(0, 2, 1)


# the cell's row (kanana2-packed-s8192-1chip) and a head's tiles by hand:
# forward 1024 x 1024: 36 at or under the diagonal; 17 hold a visible pair
CELL_DOCUMENTS = [2961, 1734, 1207, 811, 562, 377, 243, 161, 89, 47]
CELL_DOCUMENTS_SHORT = [370, 217, 151, 101, 70, 47, 30, 20, 11, 7]

# (S, lengths, blocks): boundaries off every block edge; a document shorter
# than a block and than a chunk; blocks that differ (the backward at heads
# over 128 runs 1024 x 512); dead tiles (the last documents' keys start
# past the first key blocks); a sequence that is one block
PACKED = [
    (512, [130, 200, 60, 122], (128, 128)),
    (512, [300, 212], (256, 128)),
    (1024, [333, 5, 274, 412], (512, 256)),
    (1024, [1, 700, 323], (256, 512)),
    (200, [77, 123], (1024, 1024)),
    (768, [250, 250, 268], (1024, 1024)),
    # tiles of several chunks of 256 rows, each with a range of its own
    (2048, [700, 1, 333, 600, 414], (1024, 1024)),
    (2048, [1300, 748], (1024, 512)),
    (2048, [300, 300, 300, 300, 300, 300, 248], (512, 1024)),
    # boundaries on block edges: no tile is crossed, most are dead
    (512, [128, 256, 128], (128, 128)),
    # every document shorter than a block: every live tile is crossed
    (512, [100, 90, 110, 80, 70, 62], (128, 128)),
    # one document: the list is the whole triangle
    (512, [512], (128, 128)),
    # the cell's ten lengths over eight: a long head and a tail of short
    # documents in the last block, at small blocks and at the default ones
    (1024, CELL_DOCUMENTS_SHORT, (128, 128)),
    (1024, CELL_DOCUMENTS_SHORT, (1024, 1024)),
    # two rows whose lists differ in length: the shorter one is padded
    (512, ([512], [60, 70, 80, 90, 100, 112]), (128, 128)),
    (1024, ([1000, 24], CELL_DOCUMENTS_SHORT), (256, 128)),
]


@pytest.fixture(scope="module")
def packed_qkv():
    rng = np.random.RandomState(7)

    def make(seq, d, dv):
        mk = lambda dim: jnp.asarray(rng.randn(2, seq, 2, dim), jnp.float32)
        return mk(d), mk(d), mk(dv)
    return make


@pytest.mark.parametrize("dims", [(192, 128), (128, 128)])
@pytest.mark.parametrize("seq, lengths, blocks", PACKED)
def test_documents_match_a_masked_softmax(packed_qkv, seq, lengths, blocks,
                                          dims):
    """Value, logsumexp and all three gradients against the plain masked
    softmax, at the latent heads (``D`` 192, ``Dv`` 128) and at 128 / 128."""
    from bluefog_tpu.ops.flash_attention import flash_attention_lse
    q, k, v = packed_qkv(seq, *dims)
    ids = _ids(lengths, 2)
    block_q, block_k = blocks

    def loss(fn):
        def f(q, k, v):
            out, lse = fn(q, k, v)
            return jnp.sum(out ** 2) + jnp.sum(lse), (out, lse)
        return jax.grad(f, argnums=(0, 1, 2), has_aux=True)(q, k, v)

    with jax.default_matmul_precision("highest"):
        got, (out, lse) = loss(lambda q, k, v: flash_attention_lse(
            q, k, v, segment_ids=ids, block_q=block_q, block_k=block_k))
        want, (ref, ref_lse) = loss(
            lambda q, k, v: _dense_documents(q, k, v, ids))
    np.testing.assert_allclose(out, ref, rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(lse, ref_lse, rtol=2e-5, atol=2e-5)
    for a, b in zip(got, want):
        np.testing.assert_allclose(a, b, rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("seq, lengths, blocks", [
    (512, [130, 200, 60, 122], (128, 128)),
    (1024, [333, 5, 274, 412], (512, 256)),
    (2048, [700, 1, 333, 600, 414], (1024, 1024)),
    (512, [100, 90, 110, 80, 70, 62], (128, 128)),
    (1024, CELL_DOCUMENTS_SHORT, (1024, 1024)),
    (1024, ([1000, 24], CELL_DOCUMENTS_SHORT), (256, 128))])
def test_documents_match_under_checkpoint(packed_qkv, seq, lengths, blocks,
                                          dtype):
    """The forward and the gradients of a rematerialised packed call (the
    list is made again with the recomputed forward) against the plain
    masked softmax of the same operands in float32."""
    q, k, v = (t.astype(dtype) for t in packed_qkv(seq, 192, 128))
    ids = _ids(lengths, 2)

    def grads(fn):
        def f(q, k, v):
            out = fn(q, k, v).astype(jnp.float32)
            return jnp.sum(out ** 2), out
        return jax.grad(f, argnums=(0, 1, 2), has_aux=True)(q, k, v)

    got, out = grads(jax.checkpoint(lambda q, k, v: flash_attention(
        q, k, v, segment_ids=ids, block_q=blocks[0], block_k=blocks[1])))
    want, ref = grads(lambda q, k, v: _dense_documents(q, k, v, ids)[0])
    tol = 2e-3 if dtype == jnp.float32 else 5e-2
    np.testing.assert_allclose(out, ref, rtol=tol, atol=tol)
    for a, b in zip(got, want):
        np.testing.assert_allclose(a.astype(jnp.float32), b, rtol=tol,
                                   atol=tol * float(jnp.abs(b).max()))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_one_document_is_the_causal_kernel(packed_qkv, dtype):
    """Ids that name one document give what no ids give, to rounding: every
    tile is inside the document and runs the causal kernel's bodies."""
    q, k, v = (t.astype(dtype) for t in packed_qkv(512, 64, 64))

    def grads(**kw):
        def f(q, k, v):
            return jnp.sum(flash_attention(
                q, k, v, block_q=128, block_k=128, **kw).astype(
                    jnp.float32) ** 2)
        return jax.value_and_grad(f, argnums=(0, 1, 2))(q, k, v)

    (a, ga), (b, gb) = grads(), grads(
        segment_ids=jnp.full((2, 512), 3, jnp.int32))
    assert float(a) == float(b)
    for x, y in zip(ga, gb):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def test_local_attention_takes_the_documents(packed_qkv):
    q, k, v = packed_qkv(200, 16, 16)
    ids = _ids([77, 123], 2)
    want, _ = _dense_documents(q, k, v, ids)
    with jax.default_matmul_precision("highest"):
        got = local_attention(q, k, v, segment_ids=ids)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


def test_documents_raise_where_they_cannot_run(packed_qkv):
    from bluefog_tpu.parallel import ring_attention, ulysses_attention
    q, k, v = packed_qkv(200, 16, 16)
    ids = _ids([77, 123], 2)
    with pytest.raises(NotImplementedError, match="causal mask alone"):
        flash_attention(q, k, v, causal=False, segment_ids=ids)
    with pytest.raises(NotImplementedError, match="window=64"):
        flash_attention(q, k, v, window=64, segment_ids=ids)
    with pytest.raises(ValueError, match=r"\(B, S\)"):
        flash_attention(q, k, v, segment_ids=ids[:, :100])
    with pytest.raises(NotImplementedError, match="document"):
        ring_attention(q, k, v, axis_name="sp", segment_ids=ids)
    with pytest.raises(NotImplementedError, match="document"):
        ulysses_attention(q, k, v, axis_name="sp", segment_ids=ids)


def test_segment_tiles_counts_the_cells_row_by_hand():
    from bluefog_tpu.ops.flash_attention import segment_tiles
    ids = np.repeat(np.arange(10), CELL_DOCUMENTS)[None]
    tiles = segment_tiles(ids, 1024, 1024)
    assert sum(tiles.values()) == 36 and tiles["dead"] == 19
    # query blocks 0, 1 and 3 lie inside documents 0, 0 and 1: their tiles
    # over key blocks of the same document are the causal kernel's: (0, 0),
    # (1, 0), (1, 1) and (3, 3)
    assert tiles == {"dead": 19, "crossed": 13, "inside": 4}
    # the backward's 1024 x 512: 72 at or under the diagonal
    assert sum(segment_tiles(ids, 1024, 512).values()) == 72
    # two rows count twice; one document is all inside
    assert segment_tiles(np.concatenate([ids, ids]), 1024, 1024)[
        "dead"] == 38
    assert segment_tiles(np.zeros((1, 8192), int), 1024, 1024) == {
        "dead": 0, "crossed": 0, "inside": 36}


def test_segment_steps_counts_the_cells_row_by_hand():
    from bluefog_tpu.ops.flash_attention import segment_steps, segment_tiles
    ids = np.repeat(np.arange(10), CELL_DOCUMENTS)[None]
    # what the parent's grid walked, 36 steps a head, holds 17 live tiles
    assert segment_steps(ids, 1024, 1024) == {"listed": 17, "capacity": 36}
    assert segment_steps(ids, 512, 512) == {"listed": 49, "capacity": 136}
    # a packed call's own blocks: query blocks of 512 on key blocks of 1024.
    # A query block's steps run from the key block where its first query's
    # document begins to the diagonal's.  The documents begin at 0, 2961,
    # 4695, 5902, 6713, 7275, 7652, ...: query blocks 0 to 5 begin in the
    # first (key block 0: 1 + 1 + 2 + 2 + 3 + 3 steps), 6 to 9 in the second
    # (key block 2: 2 + 2 + 3 + 3), 10 and 11 in the third (key block 4: 2 +
    # 2), 12 and 13 in the fourth (key block 5: 2 + 2), 14 in the fifth
    # (key block 6: 2) and 15 in the seventh (key block 7: 1)
    assert segment_steps(ids) == {
        "listed": 1 + 1 + 2 + 2 + 3 + 3 + 2 + 2 + 3 + 3 + 2 + 2 + 2 + 2 + 2
        + 1, "capacity": 72}
    # the listed steps are the tiles that are not dead, at any blocks
    for blocks in ((1024, 1024), (512, 1024), (512, 512), (256, 512)):
        tiles = segment_tiles(ids, *blocks)
        assert segment_steps(ids, *blocks) == {
            "listed": tiles["crossed"] + tiles["inside"],
            "capacity": sum(tiles.values())}
    # two rows count twice; one document fills the list
    assert segment_steps(np.concatenate([ids, ids]), 512, 512) == {
        "listed": 98, "capacity": 272}
    assert segment_steps(np.zeros((1, 8192), int)) == {
        "listed": 72, "capacity": 72}


def test_the_devices_list_is_the_hosts_count():
    """``_doc_work`` (the list a packed call makes on the device) against
    ``segment_steps`` and a walk over the tiles by hand: every live tile
    once, own block after own block, the first and the last step of each
    marked, the steps past a shorter row's list with no bit and no range."""
    from bluefog_tpu.ops import flash_attention as fa
    ids = _ids(([1000, 24], CELL_DOCUMENTS_SHORT), 2)
    for by_keys in (False, True):
        steps, (own_of, other_of, kind_of, range_of), _ = fa._doc_work(
            ids, 128, 256, by_keys)
        capacity = fa._doc_capacity(128, 256, 1024)
        listed = [fa.segment_steps(np.asarray(ids[b:b + 1]), 128, 256)
                  for b in range(2)]
        assert capacity == listed[0]["capacity"] == 2 * (1 + 2 + 3 + 4)
        assert int(steps) == max(row["listed"] for row in listed)
        for b in range(2):
            own, other, kind, reach = (
                np.asarray(x).reshape(2, capacity, -1)[b].max(axis=-1)
                for x in (own_of, other_of, kind_of, range_of))
            n = listed[b]["listed"]
            # a listed tile lies inside a document or has a range to run
            assert (((kind[:n] & fa._INSIDE) != 0) != (reach[:n] != 0)).all()
            assert (kind[n:] == 0).all() and (reach[n:] == 0).all()
            qi, kb = (other, own) if by_keys else (own, other)
            row = np.asarray(ids[b])
            live = {(i, j) for i in range(8) for j in range(4)
                    if j * 256 <= i * 128 + 127
                    and row[j * 256 + 255] >= row[i * 128]}
            assert set(zip(qi[:n], kb[:n])) == live and len(live) == n
            assert (np.diff(own[:n]) >= 0).all()
            first, last = (kind & fa._FIRST) != 0, (kind & fa._LAST) != 0
            blocks = len(set(own[:n]))
            assert first.sum() == last.sum() == blocks == (4 if by_keys
                                                            else 8)
            assert (first[:n] == np.r_[True, np.diff(own[:n]) > 0]).all()
            assert (last[:n] == np.r_[np.diff(own[:n]) > 0, True]).all()


def test_packed_calls_have_names_grids_and_by_data_tiles_of_their_own():
    """Traced, nothing runs: the masked kernels' names at the cell's shapes;
    grids whose second dimension is data (the length of the list of live
    work) where the causal kernels' have 64 and 128 static steps; query
    blocks of 512 on the key blocks the caller asked for; four scalar
    arrays read ahead of the grid (the list: own block, other block, kind
    and the crossed chunks' ranges), each with room for the tiles at or
    under the diagonal, 72 a head; the documents' bounds beside the
    operands; and ``bf_flash_tiles_total``'s ``by_data``, that capacity."""
    from jax._src.pallas import core as pallas_core
    from bluefog_tpu.utils import telemetry
    q = jax.ShapeDtypeStruct((1, 8192, 32, 192), jnp.bfloat16)
    v = jax.ShapeDtypeStruct((1, 8192, 32, 128), jnp.bfloat16)
    ids = jax.ShapeDtypeStruct((1, 8192), jnp.int32)

    def loss(q, k, v, ids):
        return flash_attention(q, k, v, segment_ids=ids).astype(
            jnp.float32).sum()

    def tiles():
        return {key: n for key, n in telemetry.snapshot().items()
                if key.startswith("bf_flash_tiles_total")}

    before = tiles()
    calls = _pallas_calls(jax.make_jaxpr(
        jax.grad(loss, argnums=(0, 1, 2)))(q, q, v, ids).jaxpr, [])
    after = tiles()
    assert [c.params["name"] for c in calls] == [
        "bf_flash_seg_fwd", "bf_flash_seg_dq", "bf_flash_seg_dkv"]
    bq, bk, own_chunks = 512, 1024, {"bf_flash_seg_fwd": 2,
                                     "bf_flash_seg_dq": 2,
                                     "bf_flash_seg_dkv": 4}
    rows = (None, bq, 1)
    expected = {
        "bf_flash_seg_fwd": [rows, (None, bq, 192), (None, bk, 192),
                             (None, bk, 128), (None, bq, 128), rows],
        "bf_flash_seg_dq": [rows, (None, bq, 192), (None, bk, 192),
                            (None, bk, 128), (None, bq, 128), rows, rows,
                            (None, bq, 192)],
        "bf_flash_seg_dkv": [(None, bk, 1), (None, bq, 192), (None, bk, 192),
                             (None, bk, 128), (None, bq, 128), rows, rows,
                             (None, bk, 192), (None, bk, 128)],
    }
    for call in calls:
        mapping, name = call.params["grid_mapping"], call.params["name"]
        assert mapping.grid == (32, pallas_core.dynamic_grid_dim)
        assert mapping.num_dynamic_grid_bounds == 1
        assert mapping.num_index_operands == 4
        steps, *lists = call.invars[:5]
        assert steps.aval.shape == () and [x.aval.shape for x in lists] == [
            (72,), (72,), (72,), (72 * own_chunks[name],)]
        assert [tuple(getattr(dim, "block_size", None)
                      for dim in m.block_shape)
                for m in mapping.block_mappings] == expected[name]
        counted = {kind: after.get(key, 0) - before.get(key, 0)
                   for kind in ("skipped", "by_data", "crossed", "interior")
                   for key in [f'bf_flash_tiles_total{{kernel="{name}",'
                               f'kind="{kind}"}}']}
        assert counted == {"skipped": 0, "by_data": 32 * 72,
                           "crossed": 0, "interior": 0}
