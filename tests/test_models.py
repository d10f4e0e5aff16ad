"""Model zoo smoke tests: shapes, dtypes, jit-ability."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bluefog_tpu import models
from twins import (  # noqa: F401
    test_atc_on_four_devices_is_w_times_the_handwritten_update,
    test_float8_rounded_matrices_fail_the_bounds,
    test_toy_model_in_bfloat16_is_inside_the_twin_bounds,
    test_toy_model_loss_and_every_gradient_leaf_in_float32)

# the twins whose whole-model cases (``tests/twins.py``) run in this file
TWINS = ("tiny-lm", "tiny-resnet")


def test_lenet_forward():
    m = models.LeNet5()
    x = jnp.zeros((4, 28, 28, 1))
    params = m.init(jax.random.PRNGKey(0), x)
    out = jax.jit(lambda p, x: m.apply(p, x))(params, x)
    assert out.shape == (4, 10)
    assert out.dtype == jnp.float32


def test_mlp_and_logreg_forward():
    x = jnp.zeros((4, 28, 28, 1))
    m = models.MLP()
    out = m.apply(m.init(jax.random.PRNGKey(0), x), x)
    assert out.shape == (4, 10)
    lr = models.LogisticRegression(num_classes=3)
    x2 = jnp.zeros((5, 7))
    assert lr.apply(lr.init(jax.random.PRNGKey(0), x2), x2).shape == (5, 3)


@pytest.mark.slow
def test_resnet18_forward_and_bn_state():
    m = models.ResNet18(num_classes=10, dtype=jnp.float32)
    x = jnp.zeros((2, 32, 32, 3))
    variables = m.init(jax.random.PRNGKey(0), x)
    assert "batch_stats" in variables
    out, new_state = m.apply(variables, x, train=True,
                             mutable=["batch_stats"])
    assert out.shape == (2, 10)
    out_eval = m.apply(variables, x, train=False)
    assert out_eval.shape == (2, 10)


@pytest.mark.slow
def test_resnet50_param_count():
    """ResNet-50 must be the real thing: ~25.6M parameters."""
    m = models.ResNet50(num_classes=1000, dtype=jnp.float32)
    x = jnp.zeros((1, 64, 64, 3))
    variables = m.init(jax.random.PRNGKey(0), x)
    n_params = sum(np.prod(p.shape) for p in
                   jax.tree_util.tree_leaves(variables["params"]))
    assert 25.4e6 < n_params < 25.8e6, f"got {n_params/1e6:.2f}M params"


def test_transformer_forward():
    cfg = models.TransformerConfig(vocab_size=100, num_layers=2, num_heads=2,
                                   embed_dim=32, max_seq_len=16,
                                   dtype=jnp.float32)
    m = models.TransformerLM(cfg)
    tokens = jnp.zeros((2, 16), jnp.int32)
    params = m.init(jax.random.PRNGKey(0), tokens)
    logits = m.apply(params, tokens)
    assert logits.shape == (2, 16, 100)


@pytest.mark.slow
def test_transformer_gqa_and_mqa():
    """Grouped-query attention: fewer K/V projection params, same output
    shape, finite grads; flash kernel agrees with dense on GQA shapes."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from bluefog_tpu.models import TransformerLM, TransformerConfig
    from bluefog_tpu.ops.flash_attention import flash_attention_impl

    kw = dict(vocab_size=64, num_layers=2, num_heads=4, embed_dim=64,
              max_seq_len=32, dtype=jnp.float32)
    tokens = jnp.asarray(np.random.RandomState(0).randint(0, 64, (2, 32)))

    def count(m):
        v = m.init(jax.random.PRNGKey(0), tokens)
        return v, sum(int(np.prod(p.shape))
                      for p in jax.tree_util.tree_leaves(v["params"]))

    mha, n_mha = count(TransformerLM(TransformerConfig(**kw)))
    gqa_model = TransformerLM(TransformerConfig(num_kv_heads=2, **kw))
    gqa, n_gqa = count(gqa_model)
    mqa, n_mqa = count(TransformerLM(TransformerConfig(num_kv_heads=1, **kw)))
    assert n_mqa < n_gqa < n_mha  # K/V projections shrink with kv heads

    logits = gqa_model.apply(gqa, tokens)
    assert logits.shape == (2, 32, 64)

    def loss(p):
        return jnp.mean(gqa_model.apply(p, tokens) ** 2)
    grads = jax.grad(loss)(gqa)
    assert all(np.all(np.isfinite(g)) for g in
               jax.tree_util.tree_leaves(grads))

    # same params, flash vs dense attention on the grouped-head shapes
    flash_model = TransformerLM(TransformerConfig(num_kv_heads=2, **kw),
                                attn_impl=flash_attention_impl(block_q=16,
                                                               block_k=16))
    np.testing.assert_allclose(np.asarray(flash_model.apply(gqa, tokens)),
                               np.asarray(logits), rtol=2e-3, atol=2e-3)


def test_transformer_rope_relative_shift_invariance():
    """RoPE attends by relative position: shifting every position id by a
    constant must leave the logits unchanged (learned-wpe would not)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from bluefog_tpu.models import TransformerLM, TransformerConfig
    from bluefog_tpu.models.transformer import apply_rope

    # unit: position 0 is the identity rotation
    x = jnp.asarray(np.random.RandomState(0).randn(1, 3, 2, 8), jnp.float32)
    np.testing.assert_allclose(
        np.asarray(apply_rope(x, jnp.zeros((1, 3)))), np.asarray(x),
        rtol=1e-6)

    cfg = TransformerConfig(vocab_size=64, num_layers=2, num_heads=4,
                            embed_dim=64, max_seq_len=512,
                            pos_encoding="rope", dtype=jnp.float32)
    m = TransformerLM(cfg)
    tokens = jnp.asarray(np.random.RandomState(1).randint(0, 64, (2, 16)))
    params = m.init(jax.random.PRNGKey(0), tokens)
    assert not any("wpe" in "/".join(map(str, p)) for p, _ in
                   jax.tree_util.tree_flatten_with_path(params)[0])
    base = m.apply(params, tokens,
                   positions=jnp.arange(16)[None, :])
    shifted = m.apply(params, tokens,
                      positions=jnp.arange(16)[None, :] + 100)
    np.testing.assert_allclose(np.asarray(shifted), np.asarray(base),
                               rtol=1e-4, atol=1e-4)


def test_transformer_rope_flash_matches_dense():
    import jax
    import jax.numpy as jnp
    import numpy as np
    from bluefog_tpu.models import TransformerLM, TransformerConfig
    from bluefog_tpu.ops.flash_attention import flash_attention_impl

    kw = dict(vocab_size=64, num_layers=2, num_heads=4, embed_dim=64,
              max_seq_len=64, pos_encoding="rope", num_kv_heads=2,
              dtype=jnp.float32)
    tokens = jnp.asarray(np.random.RandomState(2).randint(0, 64, (2, 32)))
    dense = TransformerLM(TransformerConfig(**kw))
    params = dense.init(jax.random.PRNGKey(0), tokens)
    flash = TransformerLM(TransformerConfig(**kw),
                          attn_impl=flash_attention_impl(block_q=16,
                                                         block_k=16))
    np.testing.assert_allclose(np.asarray(flash.apply(params, tokens)),
                               np.asarray(dense.apply(params, tokens)),
                               rtol=2e-3, atol=2e-3)


@pytest.mark.slow
def test_transformer_swiglu_trains():
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax
    from bluefog_tpu.models import TransformerLM, TransformerConfig

    cfg = TransformerConfig(vocab_size=64, num_layers=2, num_heads=4,
                            embed_dim=32, max_seq_len=16, mlp="swiglu",
                            dtype=jnp.float32)
    m = TransformerLM(cfg)
    tokens = jnp.asarray(np.random.RandomState(3).randint(0, 64, (4, 16)))
    params = m.init(jax.random.PRNGKey(0), tokens)
    names = {"/".join(str(getattr(k, "key", k)) for k in p)
             for p, _ in jax.tree_util.tree_flatten_with_path(params)[0]}
    assert "params/block_0/gate/kernel" in names

    opt = optax.adam(1e-3)

    def loss(p):
        logits = m.apply(p, tokens)
        tgt = jnp.roll(tokens, -1, axis=1)
        return optax.softmax_cross_entropy_with_integer_labels(
            logits, tgt).mean()

    state = opt.init(params)
    l0 = float(loss(params))
    for _ in range(30):
        g = jax.grad(loss)(params)
        upd, state = opt.update(g, state, params)
        params = optax.apply_updates(params, upd)
    assert float(loss(params)) < l0


@pytest.mark.slow
@pytest.mark.parametrize("variant", ["mha", "gqa_rope_swiglu"])
def test_transformer_kv_cache_decode_matches_forward(variant):
    """Teacher-forced single-token decoding through the KV cache must
    reproduce the full training forward's logits position by position."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from bluefog_tpu.models import TransformerLM, TransformerConfig
    from bluefog_tpu.models.transformer import init_cache

    kw = dict(vocab_size=64, num_layers=2, num_heads=4, embed_dim=32,
              max_seq_len=16, dtype=jnp.float32)
    if variant == "gqa_rope_swiglu":
        kw.update(num_kv_heads=2, pos_encoding="rope", mlp="swiglu")
    m = TransformerLM(TransformerConfig(**kw))
    tokens = jnp.asarray(np.random.RandomState(5).randint(0, 64, (2, 10)))
    params = m.init(jax.random.PRNGKey(0), tokens)
    full = m.apply(params, tokens)  # (2, 10, 64)

    cache = init_cache(m.cfg, 2, 10)
    # GQA cache is kv_h-headed: h/kv_h smaller than num_heads
    kv_h = m.cfg.num_kv_heads or m.cfg.num_heads
    assert cache[0][0].shape == (2, 10, kv_h, 32 // 4)
    got = []
    for t in range(10):
        logits, cache = m.apply(
            params, tokens[:, t:t + 1],
            positions=jnp.broadcast_to(jnp.asarray(t), (2, 1)), cache=cache)
        got.append(logits[:, 0])
    np.testing.assert_allclose(np.asarray(jnp.stack(got, 1)),
                               np.asarray(full), rtol=1e-4, atol=1e-4)


@pytest.mark.slow
def test_transformer_generate_greedy_and_sampled():
    import jax
    import jax.numpy as jnp
    import numpy as np
    from bluefog_tpu.models import TransformerLM, TransformerConfig
    from bluefog_tpu.models.transformer import generate

    cfg = TransformerConfig(vocab_size=32, num_layers=2, num_heads=2,
                            embed_dim=32, max_seq_len=24,
                            dtype=jnp.float32)
    m = TransformerLM(cfg)
    prompt = jnp.asarray(np.random.RandomState(6).randint(0, 32, (2, 5)))
    params = m.init(jax.random.PRNGKey(0), prompt)

    out = generate(m, params, prompt, 6)
    assert out.shape == (2, 6) and out.dtype == prompt.dtype
    # greedy decoding is deterministic
    np.testing.assert_array_equal(np.asarray(out),
                                  np.asarray(generate(m, params, prompt, 6)))
    # greedy first token == argmax of the forward's last-prompt logits
    full = m.apply(params, prompt)
    np.testing.assert_array_equal(np.asarray(out[:, 0]),
                                  np.asarray(jnp.argmax(full[:, -1], -1)))
    sampled = generate(m, params, prompt, 6, temperature=1.0,
                       rng=jax.random.PRNGKey(1))
    assert sampled.shape == (2, 6)
    assert generate(m, params, prompt, 1).shape == (2, 1)
    with pytest.raises(ValueError, match="needs rng"):
        generate(m, params, prompt, 2, temperature=0.5)
    with pytest.raises(ValueError, match="exceeds"):
        generate(m, params, prompt, 100)
    with pytest.raises(ValueError, match="max_new_tokens"):
        generate(m, params, prompt, 0)
    # decode-contract violations are loud, not silently corrupting
    from bluefog_tpu.models.transformer import init_cache
    cache = init_cache(cfg, 2, 8)
    with pytest.raises(ValueError, match="ONE token"):
        m.apply(params, prompt[:, :3],
                positions=jnp.zeros((2, 3), jnp.int32), cache=cache)
    with pytest.raises(ValueError, match="explicit positions"):
        m.apply(params, prompt[:, :1], cache=cache)


def test_transformer_gqa_validates_divisibility():
    from bluefog_tpu.models import TransformerConfig
    with pytest.raises(ValueError, match="divisible"):
        TransformerConfig(num_heads=4, num_kv_heads=3)
    with pytest.raises(ValueError, match="even head dim"):
        TransformerConfig(embed_dim=90, num_heads=6, pos_encoding="rope")
    with pytest.raises(ValueError, match="contradictory"):
        # top-k > 1 belongs to the dropless SwiGLU experts; GELU is Switch
        TransformerConfig(mlp="gelu", num_experts=4, num_experts_per_tok=2)
    TransformerConfig(mlp="swiglu", num_experts=4)   # DroplessMoe, top-1
    with pytest.raises(ValueError, match="dots:<int>"):
        TransformerConfig(remat=True, remat_policy="dots:abc")
    with pytest.raises(ValueError, match="not in"):
        TransformerConfig(remat=True, remat_policy="mixed")
    TransformerConfig(remat=True, remat_policy="dots:8")  # valid mixed


@pytest.mark.slow
@pytest.mark.parametrize("policy", ["full", "dots", "dots:1"])
def test_transformer_remat_matches_plain(policy):
    """cfg.remat=True (jax.checkpoint per block, either policy) must not
    change outputs or gradients — only the backward's memory/recompute
    schedule."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from bluefog_tpu.models import TransformerLM, TransformerConfig

    kw = dict(vocab_size=64, num_layers=2, num_heads=4, embed_dim=32,
              max_seq_len=16, dtype=jnp.float32)
    tokens = jnp.asarray(np.random.RandomState(0).randint(0, 64, (2, 16)))
    plain = TransformerLM(TransformerConfig(**kw))
    remat = TransformerLM(TransformerConfig(remat=True, remat_policy=policy,
                                            **kw))
    params = plain.init(jax.random.PRNGKey(0), tokens)

    out_p = plain.apply(params, tokens)
    out_r = remat.apply(params, tokens)
    np.testing.assert_allclose(np.asarray(out_r), np.asarray(out_p),
                               rtol=1e-6, atol=1e-6)

    loss = lambda m: lambda p: jnp.sum(m.apply(p, tokens) ** 2)
    g_p = jax.grad(loss(plain))(params)
    g_r = jax.grad(loss(remat))(params)
    for a, b in zip(jax.tree_util.tree_leaves(g_p),
                    jax.tree_util.tree_leaves(g_r)):
        np.testing.assert_allclose(np.asarray(b), np.asarray(a),
                                   rtol=1e-5, atol=1e-5)


@pytest.fixture(scope="module")
def tiny_lm():
    """A float32 LM with 16 positions and 64 words, and its parameters."""
    from bluefog_tpu.models import TransformerLM, TransformerConfig
    cfg = TransformerConfig(vocab_size=64, num_layers=2, num_heads=4,
                            embed_dim=32, max_seq_len=16, dtype=jnp.float32)
    model = TransformerLM(cfg)
    return model, model.init(jax.random.PRNGKey(0),
                             jnp.zeros((1, 16), jnp.int32))


# chunk counts rows over the whole batch: 8 gives c = 8, 4, 2 at B = 1, 2, 3;
# 7 divides no 16 and fits down to c = 4, 2, 2.
@pytest.mark.parametrize("chunk", [8, 7])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B", [1, 2, 3])
def test_chunked_loss_matches_dense(tiny_lm, B, dtype, chunk):
    """chunked_softmax_cross_entropy == optax dense CE in value and in the
    gradients of hidden rows and head, on the model's return_hidden path."""
    import optax
    from bluefog_tpu.ops.chunked_loss import chunked_softmax_cross_entropy

    model, params = tiny_lm
    tokens = jnp.asarray(np.random.RandomState(B).randint(0, 64, (B, 16)))
    tgt = jnp.roll(tokens, -1, axis=1)
    hidden = model.apply(params, tokens, return_hidden=True).astype(dtype)
    head = params["params"]["lm_head"]["kernel"]

    def dense_loss(h, w):
        return optax.softmax_cross_entropy_with_integer_labels(
            h.astype(jnp.float32) @ w, tgt).mean()

    def chunked_loss(h, w):
        return chunked_softmax_cross_entropy(h, w, tgt, chunk=chunk)

    l_d, g_d = jax.value_and_grad(dense_loss, argnums=(0, 1))(hidden, head)
    l_c, g_c = jax.value_and_grad(chunked_loss, argnums=(0, 1))(hidden, head)
    np.testing.assert_allclose(float(l_c), float(l_d), rtol=1e-5)
    assert g_c[0].dtype == dtype and g_c[1].dtype == head.dtype
    # Both sides round the same float32 gradient of the hidden rows to
    # bfloat16 (8 bits of mantissa); the head's stays float32.
    h_rtol = 2e-4 if dtype == jnp.float32 else 2 ** -7
    for a, b, rtol in zip(g_d, g_c, (h_rtol, 2e-4)):
        np.testing.assert_allclose(np.asarray(b, np.float32),
                                   np.asarray(a, np.float32),
                                   rtol=rtol, atol=2e-5)


@pytest.mark.parametrize("B", [1, 2])
def test_chunked_loss_gradient_has_no_gather_or_scatter(B):
    """The target's logit is a compare and a sum: a gather's transpose is a
    scatter-add, which costs a TPU three passes over a chunk at B == 1."""
    from bluefog_tpu.ops.chunked_loss import chunked_softmax_cross_entropy
    h = jnp.zeros((B, 16, 8), jnp.bfloat16)
    w = jnp.zeros((8, 20), jnp.float32)
    t = jnp.zeros((B, 16), jnp.int32)
    text = jax.jit(jax.grad(
        lambda h, w: chunked_softmax_cross_entropy(h, w, t, chunk=8),
        argnums=(0, 1))).lower(h, w).as_text()
    assert "stablehlo.while" in text            # the scan is there to read
    assert "scatter" not in text and "gather" not in text


def test_chunked_loss_chunk_counts_rows_over_the_batch():
    from bluefog_tpu.ops import chunked_loss
    default = chunked_loss.chunked_softmax_cross_entropy.__kwdefaults__["chunk"]
    per_row = chunked_loss._positions_per_chunk
    # one long row and two short ones work on as many rows at a time
    assert per_row(default, 1, 16384) == 2 * per_row(default, 2, 4096)
    for chunk, B, S in [(8, 1, 12), (8, 3, 12), (7, 2, 30), (2048, 2, 4099),
                        (5, 1, 3)]:
        c = per_row(chunk, B, S)
        assert S % c == 0 and 1 <= c <= max(1, chunk // B), (chunk, B, S, c)
    assert per_row(8, 1, 12) == 6 and per_row(8, 3, 12) == 2
    assert per_row(4, 5, 12) == 1               # more batch rows than chunk
    h = jnp.zeros((1, 4, 2))
    with pytest.raises(ValueError, match="chunk must be >= 1"):
        chunked_loss.chunked_softmax_cross_entropy(
            h, jnp.zeros((2, 3)), jnp.zeros((1, 4), jnp.int32), chunk=0)


def test_chunked_loss_uneven_chunk_fits_down():
    import jax
    import jax.numpy as jnp
    import numpy as np
    from bluefog_tpu.ops.chunked_loss import chunked_softmax_cross_entropy
    h = jnp.asarray(np.random.RandomState(0).randn(1, 12, 8), jnp.float32)
    W = jnp.asarray(np.random.RandomState(1).randn(8, 20), jnp.float32)
    t = jnp.asarray(np.random.RandomState(2).randint(0, 20, (1, 12)))
    # chunk=8 does not divide 12 -> fits down to 6 (largest divisor)
    out = chunked_softmax_cross_entropy(h, W, t, chunk=8)
    ref = chunked_softmax_cross_entropy(h, W, t, chunk=12)
    np.testing.assert_allclose(float(out), float(ref), rtol=1e-6)


@pytest.mark.slow
def test_switch_moe_transformer_trains():
    """num_experts>0 swaps each block's MLP for a switch MoE; the model
    trains (loss falls) and router + expert weights all receive grads."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax
    from bluefog_tpu.models import TransformerLM, TransformerConfig

    cfg = TransformerConfig(vocab_size=64, num_layers=2, num_heads=4,
                            embed_dim=32, max_seq_len=16, dtype=jnp.float32,
                            num_experts=4)
    model = TransformerLM(cfg)
    tokens = jnp.asarray(np.random.RandomState(0).randint(0, 64, (4, 16)))
    params = model.init(jax.random.PRNGKey(0), tokens)
    names = [str(p) for p, _ in
             jax.tree_util.tree_flatten_with_path(params)[0]]
    assert any("experts_up" in n for n in names), names
    assert any("router" in n for n in names), names

    def loss(p):
        logits = model.apply(p, tokens)
        tgt = jnp.roll(tokens, -1, axis=1)
        return optax.softmax_cross_entropy_with_integer_labels(
            logits, tgt).mean()

    opt = optax.adam(1e-2)
    state = opt.init(params)
    l0 = float(loss(params))

    @jax.jit
    def train_step(p, s):
        g = jax.grad(loss)(p)
        upd, s = opt.update(g, s, p)
        return optax.apply_updates(p, upd), s
    for _ in range(30):
        params, state = train_step(params, state)
    l1 = float(loss(params))
    assert l1 < l0 * 0.7, (l0, l1)
    g = jax.grad(loss)(params)
    flat = jax.tree_util.tree_flatten_with_path(g)[0]
    for p, leaf in flat:
        if "experts" in str(p) or "router" in str(p):
            assert float(jnp.abs(leaf).max()) > 0, p
    # the Switch load-balance aux loss is sown per MoE layer
    _, inter = model.apply(params, tokens, mutable=["intermediates"])
    aux = [v for k, v in
           jax.tree_util.tree_flatten_with_path(inter)[0]
           if "moe_aux_loss" in str(k)]
    assert len(aux) == cfg.num_layers, inter
    assert all(np.isfinite(float(a)) and float(a) >= 1.0 - 1e-6
               for a in aux), aux  # >= 1 by Cauchy-Schwarz, = 1 if balanced


def test_switch_moe_expert_parallel_sharding_matches():
    """Expert weights sharded P('ep') under GSPMD: same outputs as the
    unsharded model."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from bluefog_tpu.models import TransformerLM, TransformerConfig
    from bluefog_tpu.parallel.tensor_parallel import (tp_param_specs,
                                                      tp_shard_params)

    cfg = TransformerConfig(vocab_size=64, num_layers=2, num_heads=4,
                            embed_dim=32, max_seq_len=16, dtype=jnp.float32,
                            num_experts=4)
    model = TransformerLM(cfg)
    tokens = jnp.asarray(np.random.RandomState(1).randint(0, 64, (4, 16)))
    params = model.init(jax.random.PRNGKey(0), tokens)
    ref = model.apply(params, tokens)

    # TP and EP composed on one mesh: attention/up/down shard over tp,
    # stacked expert weights over ep.
    mesh = Mesh(np.asarray(jax.devices()[:8]).reshape(2, 4), ("tp", "ep"))
    specs = tp_param_specs(params, axis="tp", ep_axis="ep")
    flat = jax.tree_util.tree_flatten_with_path(specs)[0]
    assert sum(1 for _, s in flat if s == P("ep", None, None)) == 4  # 2x2
    p_sh = tp_shard_params(params, mesh, axis="tp", ep_axis="ep")
    t_sh = jax.device_put(tokens, NamedSharding(mesh, P()))
    out = jax.jit(model.apply)(p_sh, t_sh)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.slow
def test_switch_moe_ragged_group_padding():
    """T not divisible by router_group_size: tokens pad to whole groups and
    the output slices back — no silent group-size collapse."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from bluefog_tpu.models import TransformerLM, TransformerConfig
    cfg = TransformerConfig(vocab_size=32, num_layers=1, num_heads=2,
                            embed_dim=16, max_seq_len=13, dtype=jnp.float32,
                            num_experts=2, router_group_size=5)
    model = TransformerLM(cfg)
    tokens = jnp.asarray(np.random.RandomState(0).randint(0, 32, (3, 13)))
    params = model.init(jax.random.PRNGKey(0), tokens)  # T=39, g=5 -> pad 1
    out = model.apply(params, tokens)
    assert out.shape == (3, 13, 32)
    assert np.isfinite(np.asarray(out)).all()


@pytest.mark.slow
def test_vgg16_forward_and_grad():
    import jax
    import jax.numpy as jnp
    import numpy as np
    from bluefog_tpu.models import VGG16
    model = VGG16(num_classes=10, hidden=64, dtype=jnp.float32)
    x = jnp.asarray(np.random.RandomState(0).randn(2, 32, 32, 3),
                    jnp.float32)
    params = model.init(jax.random.PRNGKey(0), x)
    out = model.apply(params, x)
    assert out.shape == (2, 10)
    g = jax.grad(lambda p: jnp.sum(model.apply(p, x) ** 2))(params)
    assert all(np.isfinite(np.asarray(l)).all()
               for l in jax.tree_util.tree_leaves(g))


def test_vit_forward_and_grad():
    """ViT: patchify + [CLS] + bidirectional encoder blocks; logits shape,
    gradient flow to every parameter group."""
    m = models.ViT(num_classes=10, image_size=32, patch_size=8,
                   embed_dim=64, num_layers=2, num_heads=4,
                   dtype=jnp.float32)
    x = jnp.asarray(np.random.RandomState(0).randn(2, 32, 32, 3),
                    jnp.float32)
    params = m.init(jax.random.PRNGKey(0), x)
    out = jax.jit(lambda p, x: m.apply(p, x))(params, x)
    assert out.shape == (2, 10)
    assert out.dtype == jnp.float32

    def loss(p):
        return jnp.sum(m.apply(p, x) ** 2)
    g = jax.grad(loss)(params)
    for path, leaf in jax.tree_util.tree_flatten_with_path(g)[0]:
        assert float(jnp.abs(leaf).sum()) > 0, \
            f"no gradient reached {jax.tree_util.keystr(path)}"


def test_vit_attention_is_bidirectional():
    """Information must flow from LATER patches into the [CLS] token's
    logits beyond what a causal mask would allow: perturbing the LAST
    patch changes the [CLS]-derived output (under a causal mask the CLS
    position, index 0, could never see it)."""
    m = models.ViT(num_classes=4, image_size=16, patch_size=8,
                   embed_dim=32, num_layers=1, num_heads=2,
                   dtype=jnp.float32)
    x = jnp.asarray(np.random.RandomState(1).randn(1, 16, 16, 3),
                    jnp.float32)
    params = m.init(jax.random.PRNGKey(0), x)
    base = np.asarray(m.apply(params, x))
    x2 = x.at[:, 8:, 8:, :].add(1.0)  # last patch only
    pert = np.asarray(m.apply(params, x2))
    assert np.abs(pert - base).max() > 1e-4, \
        "CLS logits blind to later patches — attention is causal"


def test_vit_validates_patch_divisibility():
    m = models.ViT(image_size=30, patch_size=16)
    with pytest.raises(ValueError, match="not divisible"):
        m.init(jax.random.PRNGKey(0), jnp.zeros((1, 30, 30, 3)))


def test_kv_cache_rejects_bidirectional_config():
    """causal=False (encoder mode) must not silently decode causally."""
    from bluefog_tpu.models import transformer as T
    cfg = models.TransformerConfig(vocab_size=32, num_layers=1, num_heads=2,
                                   embed_dim=16, max_seq_len=8,
                                   dtype=jnp.float32, causal=False)
    m = models.TransformerLM(cfg)
    tokens = jnp.zeros((1, 8), jnp.int32)
    params = m.init(jax.random.PRNGKey(0), tokens)
    cache = T.init_cache(cfg, batch=1, max_len=8)
    with pytest.raises(ValueError, match="causal=True"):
        m.apply(params, jnp.zeros((1, 1), jnp.int32),
                positions=jnp.zeros((1, 1), jnp.int32), cache=cache)
