"""The mechanisms ``lfm2-24b-a2b`` forced, at toy widths on the CPU, each
against the configuration's plain reference
(``benchmark/reference/lfm2-24b-a2b.py``, which imports nothing of
``bluefog_tpu``) or a hand-written line of it: the gated short convolution,
a per-layer choice of token mixer, the per-head QK norm, flash attention at
heads of 64 under grouped queries, the router's renormalisation epsilon, a
tied head; the whole toy model's cases are those of ``tests/twins.py``, and
the digests of every twin's gradient program are held here.  float32 to
1e-5."""

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

from bluefog_tpu import models  # noqa: E402
from bluefog_tpu.models import transformer as T  # noqa: E402
from bluefog_tpu.ops.flash_attention import flash_attention  # noqa: E402
from bluefog_tpu.parallel import moe  # noqa: E402
import twins  # noqa: E402
from twins import (  # noqa: E402,F401
    HIGHEST, rel, toy,
    test_atc_on_four_devices_is_w_times_the_handwritten_update,
    test_float8_rounded_matrices_fail_the_bounds,
    test_the_shares_add_up_to_the_uncut_layer,
    test_toy_model_in_bfloat16_is_inside_the_twin_bounds,
    test_toy_model_loss_and_every_gradient_leaf_in_float32)

TWINS = ("tiny-lfm2",)
KEY = jax.random.PRNGKey(34)
normal = functools.partial(twins.normal, KEY)


# --- (a) the gated short convolution ------------------------------------------

def _conv(d=16, taps=3):
    cfg = models.TransformerConfig(embed_dim=d, num_heads=2,
                                   conv_kernel=taps, dtype=jnp.float32)
    return T.ShortConv(cfg), {"conv_L_cache": taps, "conv_bias": False}


@pytest.mark.parametrize("seq", [2, 1, 13, 32])
def test_short_conv_against_the_three_shift_sum(toy, seq):
    """Forward and every gradient, at lengths that are no multiple of 8 and
    shorter than the kernel."""
    _, _, ref = toy
    layer, cfg = _conv()
    y = normal(1, (2, seq, 16))
    params = layer.init(KEY, y)["params"]
    assert {k: jax.tree.leaves(v)[0].shape for k, v in params.items()} == {
        "in": (16, 48), "w": (16, 3), "out": (16, 16)}
    mine = lambda p, y: (layer.apply({"params": p}, y) ** 2).sum()  # noqa
    theirs = lambda p, y: (ref._short_conv(y, p, cfg) ** 2).sum()  # noqa
    with HIGHEST():
        np.testing.assert_allclose(layer.apply({"params": params}, y),
                                   ref._short_conv(y, params, cfg),
                                   rtol=1e-5, atol=1e-5)
        got = jax.grad(mine, (0, 1))(params, y)
        want = jax.grad(theirs, (0, 1))(params, y)
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(g, w, rtol=1e-5, atol=1e-5)


def test_short_conv_by_hand_and_causal():
    """``c_t = w0 u_{t-2} + w1 u_{t-1} + w2 u_t`` with ``u = B * X``, gated
    by ``C``; a change at ``t + 1`` leaves position ``t`` as it was."""
    layer, _ = _conv(d=4)
    y = normal(2, (1, 6, 4))
    params = layer.init(KEY, y)["params"]
    with HIGHEST():
        b, c, x = np.split(np.asarray(y @ params["in"]["kernel"]), 3, -1)
        u = np.concatenate([np.zeros((1, 2, 4)), b * x], axis=1)
        w = np.asarray(params["w"])
        conv = sum(w[:, j] * u[:, j:j + 6] for j in range(3))
        want = (c * conv) @ np.asarray(params["out"]["kernel"])
        got = layer.apply({"params": params}, y)
        moved = layer.apply({"params": params}, y.at[:, 4].add(1.0))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
    np.testing.assert_array_equal(got[:, :4], moved[:, :4])
    assert float(jnp.abs(got[:, 4:] - moved[:, 4:]).min(axis=-1).min()) > 0


# --- (b) the mixer of each layer, and what takes a cache ------------------------

def test_layer_types_choose_the_mixer_and_are_checked():
    kw = dict(vocab_size=64, num_layers=3, num_heads=4, num_kv_heads=2,
              embed_dim=32, pos_encoding="rope", mlp="swiglu",
              qk_norm="head", dtype=jnp.float32)
    cfg = models.TransformerConfig(
        layer_types=["conv", "full_attention", "conv"], **kw)
    model = models.TransformerLM(cfg)
    params = model.init(KEY, jnp.zeros((1, 8), jnp.int32))["params"]
    assert set(params["block_0"]) == {"RMSNorm_0", "RMSNorm_1", "conv",
                                      "gate", "up", "down"}
    assert {"q", "kv", "proj", "q_norm", "k_norm"} <= set(params["block_1"])
    assert "conv" not in params["block_1"] and "q" not in params["block_2"]
    with pytest.raises(ValueError, match="layer_types"):
        models.TransformerConfig(layer_types=["conv"], **kw)
    with pytest.raises(ValueError, match="window"):
        models.TransformerConfig(
            layer_types=["conv", "window", "conv"], **kw)
    with pytest.raises(ValueError, match="conv_kernel"):
        models.TransformerConfig(conv_kernel=0, **kw)
    with pytest.raises(ValueError, match="'head'"):
        models.TransformerConfig(**dict(kw, qk_norm="heads"))
    from bluefog_tpu.utils import telemetry
    snap = telemetry.snapshot()
    assert snap['bf_model_layers_total{mixer="conv"}'] == 2
    assert snap['bf_model_layers_total{mixer="full_attention"}'] == 1


def test_a_conv_layer_with_a_cache_raises():
    cfg = models.TransformerConfig(
        vocab_size=64, num_layers=2, num_heads=2, embed_dim=16,
        layer_types=["full_attention", "conv"], dtype=jnp.float32)
    model = models.TransformerLM(cfg)
    tokens = jnp.zeros((1, 1), jnp.int32)
    params = model.init(KEY, jnp.zeros((1, 4), jnp.int32))
    with pytest.raises(NotImplementedError, match="convolution"):
        model.apply(params, tokens, positions=jnp.zeros((1, 1), jnp.int32),
                    cache=T.init_cache(cfg, 1, 8))
    block = T.Block(cfg, T.local_attention, 1)
    x = normal(3, (1, 1, 16))
    with pytest.raises(NotImplementedError, match="decode cache"):
        block.apply(block.init(KEY, normal(3, (1, 4, 16))), x,
                    jnp.zeros((1, 1), jnp.int32), T.init_cache(cfg, 1, 8)[1])
    # all attention: the cache path is what it was
    plain = models.TransformerLM(models.TransformerConfig(
        vocab_size=64, num_layers=2, num_heads=2, embed_dim=16,
        dtype=jnp.float32))
    out = T.generate(plain, plain.init(KEY, jnp.zeros((1, 4), jnp.int32)),
                     jnp.zeros((1, 4), jnp.int32), 3)
    assert out.shape == (1, 3)


# --- (c) the per-head QK norm ---------------------------------------------------

def test_per_head_qk_norm_against_the_reference_and_unlike_the_whole(toy):
    config, _, ref = toy
    kw = dict(num_layers=1, num_heads=4, num_kv_heads=2, embed_dim=64,
              pos_encoding="rope", rope_theta=1e6, mlp="swiglu",
              rms_norm_eps=config["norm_eps"], dtype=jnp.float32)
    block = T.Block(models.TransformerConfig(qk_norm="head", **kw),
                    T.local_attention)
    x = normal(4, (2, 24, 64))
    params = jax.tree.map(
        lambda p: p + 0.1 * jax.random.normal(KEY, p.shape),
        block.init(KEY, x)["params"])
    assert params["q_norm"]["scale"].shape == (16,)      # one head's dim
    assert params["k_norm"]["scale"].shape == (16,)
    assert params["kv"]["kernel"].shape == (64, 2 * 2 * 16)
    norm = lambda v, s: ref._rms_norm(v, s, config["norm_eps"])  # noqa: E731
    with HIGHEST():
        h = x + ref._attention(norm(x, params["RMSNorm_0"]["scale"]), params,
                               dict(config, hidden_size=64))
        want = h + ref._swiglu(norm(h, params["RMSNorm_1"]["scale"]),
                               *(params[n]["kernel"]
                                 for n in ("gate", "up", "down")))
        got = block.apply({"params": params}, x)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)
    # the whole-projection form is another function of the same q and k
    shapes = jax.eval_shape(T.Block(models.TransformerConfig(
        qk_norm=True, **kw), T.local_attention).init, KEY, x)["params"]
    assert shapes["q_norm"]["scale"].shape == (64,)
    assert shapes["k_norm"]["scale"].shape == (32,)
    q = normal(5, (1, 3, 4, 16)).at[..., 0, :].multiply(10.0)
    per_head = norm(q, jnp.ones(16))
    together = norm(q.reshape(1, 3, 64), jnp.ones(64)).reshape(q.shape)
    np.testing.assert_allclose((per_head ** 2).mean(-1), 1.0, rtol=1e-3)
    assert rel(per_head, together) > 0.5


# --- (d) flash attention at heads of 64 under grouped queries -----------------------

def test_flash_at_heads_of_64_with_32_over_8_heads():
    B, S, H, G, D = 1, 128, 32, 8, 64
    q = normal(6, (B, S, H, D))
    k1, v1 = normal(7, (B, S, G, D)), normal(8, (B, S, G, D))

    def through(attend):
        def f(q, k1, v1):       # each K/V head serves four query heads
            k, v = (jnp.repeat(t, H // G, axis=2) for t in (k1, v1))
            return (attend(q, k, v) ** 2).sum()
        return jax.value_and_grad(f, (0, 1, 2))(q, k1, v1)
    with HIGHEST():
        got = through(functools.partial(
            flash_attention, block_q=64, block_k=32, interpret=True))
        want = through(T.local_attention)
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert g.shape == w.shape
        np.testing.assert_allclose(g, w, rtol=2e-5, atol=2e-5)


def test_flash_keeps_its_blocks_at_heads_of_64():
    """Heads of 64 are half a lane tile; the v5e compiler takes the three
    kernels at the default 1024 x 1024 blocks (``compile_v5e.py --workload
    lfm2-s8192-1chip``), so no rule cuts them as it does past 128."""
    q = jax.ShapeDtypeStruct((1, 2048, 1, 64), jnp.bfloat16)
    f = lambda q, k, v: flash_attention(  # noqa: E731
        q, k, v, interpret=True).astype(jnp.float32).sum()
    text = str(jax.make_jaxpr(jax.grad(f, (0, 1, 2)))(q, q, q))
    assert text.count("grid=(1, 2, 2)") == 3


# --- (e) the router's renormalisation ---------------------------------------------

def test_route_topk_divides_by_the_sum_plus_its_epsilon():
    logits = normal(9, (32, 8), 3.0) - 12.0      # scores of order 1e-5
    bias = normal(10, (8,), 0.1)
    plan = moe.route_topk(logits, 4, renormalize=True, scoring="sigmoid",
                          bias=bias, scale=1.0, renorm_eps=1e-6)
    scores = jax.nn.sigmoid(logits)
    _, chosen = jax.lax.top_k(scores + bias, 4)
    top = jnp.take_along_axis(scores, chosen, axis=-1)
    np.testing.assert_array_equal(plan.experts, chosen)
    np.testing.assert_allclose(
        plan.weights, top / (top.sum(-1, keepdims=True) + 1e-6), rtol=1e-6)
    assert float(plan.weights.sum(-1).min()) < 0.99     # the 1e-6 shows
    tight = moe.route_topk(logits, 4, renormalize=True, scoring="sigmoid",
                           bias=bias)
    np.testing.assert_allclose(tight.weights.sum(-1), 1.0, rtol=1e-5)


def test_the_default_epsilon_is_the_program_it_was():
    """No ``renorm_eps`` is ``1e-20`` written out: the two sigmoid cells'
    routers trace to the text they had."""
    logits = jax.ShapeDtypeStruct((64, 8), jnp.float32)
    bias = jax.ShapeDtypeStruct((8,), jnp.float32)

    def text(**kw):
        return str(jax.make_jaxpr(lambda l, b: moe.route_topk(
            l, 2, renormalize=True, scoring="sigmoid", bias=b, scale=2.0,
            **kw))(logits, bias))
    assert text() == text(renorm_eps=1e-20)
    assert text() != text(renorm_eps=1e-6)
    x, gate = normal(11, (16, 8)), normal(12, (4, 8, 4))
    out = lambda **kw: str(jax.make_jaxpr(lambda x, l: moe.dropless_moe(  # noqa
        x, l, gate, gate, gate.swapaxes(1, 2), k=2, renormalize=True,
        scoring="sigmoid", **kw)[0])(x, normal(13, (16, 4))))
    assert out() == out(renorm_eps=1e-20)


# --- (f) the tied head ----------------------------------------------------------------

def test_the_tied_head_is_one_leaf_and_its_gradient_the_sum_of_both_uses():
    kw = dict(vocab_size=32, num_layers=1, num_heads=2, embed_dim=16,
              pos_encoding="rope", dtype=jnp.float32)
    tied = models.TransformerLM(models.TransformerConfig(
        tie_embeddings=True, **kw))
    tokens = jax.random.randint(KEY, (2, 12), 0, 32)
    params = tied.init(KEY, tokens)["params"]
    assert "lm_head" not in params
    assert params["wte"]["embedding"].shape == (32, 16)
    assert T.head_matrix(tied.cfg, params).shape == (16, 32)
    # the same numbers through an untied model whose head is the transpose
    free = models.TransformerLM(models.TransformerConfig(**kw))
    both = dict(params, lm_head={"kernel": params["wte"]["embedding"].T})
    assert T.head_matrix(free.cfg, both) is both["lm_head"]["kernel"]
    loss = lambda model, p: (model.apply({"params": p}, tokens)  # noqa: E731
                             ** 2).mean()
    with HIGHEST():
        np.testing.assert_allclose(
            tied.apply({"params": params}, tokens),
            free.apply({"params": both}, tokens), rtol=1e-5, atol=1e-6)
        np.testing.assert_allclose(
            tied.apply({"params": params}, tokens, return_hidden=True)
            @ T.head_matrix(tied.cfg, params),
            tied.apply({"params": params}, tokens), rtol=1e-5, atol=1e-6)
        got = jax.grad(functools.partial(loss, tied))(params)
        parts = jax.grad(functools.partial(loss, free))(both)
    np.testing.assert_allclose(
        got["wte"]["embedding"],
        parts["wte"]["embedding"] + parts["lm_head"]["kernel"].T,
        rtol=1e-5, atol=1e-6)
    assert float(jnp.abs(parts["lm_head"]["kernel"]).max()) > 0


# --- (h) the older configurations' gradient programs ------------------------------------

@pytest.mark.parametrize("config_name,task", sorted(twins.PARENT_JAXPR))
def test_an_older_configuration_traces_to_the_parents_jaxpr(config_name,
                                                            task):
    assert twins.grad_jaxpr_digest(config_name) \
        == twins.PARENT_JAXPR[config_name, task]
