"""The mechanisms ``laguna-xs.2`` forced, at toy widths on the CPU, each
against the configuration's plain reference
(``benchmark/reference/laguna-xs.2.py``, which imports nothing of
``bluefog_tpu``) or a hand-written line of it: a head size and head counts of
the layer's own, a sliding window handed to the attention, a rotary scheme by
layer type (YaRN over half of a head, plain over the whole), the per-head
output gate, softmax routing with a scaling factor over a held share with a
shared expert; the shares' sum and the whole toy model's cases are those of
``tests/twins.py``.  float32 to 1e-5."""

import copy
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
from bluefog_tpu.ops.flash_attention import flash_attention_impl  # noqa: E402
from bluefog_tpu.parallel import moe  # noqa: E402
from bluefog_tpu.utils import telemetry  # noqa: E402
import twins  # noqa: E402
from twins import (  # noqa: E402,F401
    HIGHEST, rel, toy, with_dtype,
    test_atc_on_four_devices_is_w_times_the_handwritten_update,
    test_float8_rounded_matrices_fail_the_bounds,
    test_the_shares_add_up_to_the_uncut_layer,
    test_toy_model_in_bfloat16_is_inside_the_twin_bounds,
    test_toy_model_loss_and_every_gradient_leaf_in_float32)

TWINS = ("tiny-laguna",)
KEY = jax.random.PRNGKey(40)
normal = functools.partial(twins.normal, KEY)



def lm_config(**kw):
    base = dict(vocab_size=64, num_layers=3, num_heads=6, num_kv_heads=2,
                embed_dim=32, head_dim=16, pos_encoding="rope",
                mlp="swiglu", dtype=jnp.float32,
                layer_types=["full_attention", "sliding_attention",
                             "sliding_attention"],
                num_heads_per_layer=[6, 8, 8], sliding_window=5)
    return models.TransformerConfig(**dict(base, **kw))


# --- (a) the sizes of a layer's own, and what is checked ----------------------

def test_head_dim_and_heads_by_layer_shape_the_projections():
    model = models.TransformerLM(lm_config(attn_gate="head"))
    params = model.init(KEY, jnp.zeros((1, 8), jnp.int32))["params"]
    shapes = {i: {k: v["kernel"].shape for k, v in params[f"block_{i}"].items()
                  if k in ("q", "kv", "proj", "attn_gate")}
              for i in range(3)}
    assert shapes[0] == {"q": (32, 6 * 16), "kv": (32, 2 * 2 * 16),
                         "proj": (6 * 16, 32), "attn_gate": (32, 6)}
    assert shapes[1] == shapes[2] == {
        "q": (32, 8 * 16), "kv": (32, 2 * 2 * 16), "proj": (8 * 16, 32),
        "attn_gate": (32, 8)}
    # no gate: no leaf; no head_dim: embed_dim // num_heads as it was
    plain = models.TransformerLM(models.TransformerConfig(
        vocab_size=64, num_layers=1, num_heads=4, num_kv_heads=2,
        embed_dim=32, pos_encoding="rope", dtype=jnp.float32))
    block = plain.init(KEY, jnp.zeros((1, 8), jnp.int32))["params"]["block_0"]
    assert "attn_gate" not in block
    assert block["q"]["kernel"].shape == (32, 32)
    assert block["kv"]["kernel"].shape == (32, 2 * 2 * 8)


def test_the_new_fields_are_checked():
    with pytest.raises(ValueError, match="num_heads_per_layer"):
        lm_config(num_heads_per_layer=[6, 8])
    with pytest.raises(ValueError, match="num_heads_per_layer"):
        lm_config(num_heads_per_layer=[6, 8, 7])       # 7 over 2 K/V heads
    with pytest.raises(ValueError, match="sliding_window"):
        lm_config(sliding_window=None)
    with pytest.raises(ValueError, match="sliding_window"):
        lm_config(causal=False)
    with pytest.raises(ValueError, match="attn_gate"):
        lm_config(attn_gate="element")
    with pytest.raises(ValueError, match="rope_type"):
        lm_config(rope_parameters={"full_attention": {"rope_type": "ntk"}})
    with pytest.raises(ValueError, match="even head dim"):
        lm_config(head_dim=15)
    with pytest.raises(ValueError, match="rotary part"):
        T.rope_scheme(lm_config(rope_parameters={"full_attention": {
            "rope_theta": 1e4, "partial_rotary_factor": 0.45}}),
            "full_attention", 16)


def test_a_window_layer_takes_no_decode_cache():
    cfg = lm_config()
    model = models.TransformerLM(cfg)
    tokens = jnp.zeros((1, 8), jnp.int32)
    params = model.init(KEY, tokens)
    cache = T.init_cache(cfg, 1, 8)
    assert cache[0][0].shape == (1, 8, 2, 16)           # head_dim, not 32 // 6
    with pytest.raises(NotImplementedError, match="sliding-window"):
        model.apply(params, tokens[:, :1], positions=jnp.zeros((1, 1), int),
                    cache=cache)
    block = T.Block(cfg, T.local_attention, 1)
    x = normal(1, (1, 1, 32))
    with pytest.raises(NotImplementedError, match="sliding window"):
        block.apply(block.init(KEY, normal(2, (1, 4, 32))), x,
                    jnp.zeros((1, 1), int), cache[1])


def test_heads_of_their_own_and_the_gate_decode_through_the_cache():
    """Full layers with ``head_dim``, heads by layer and the gate: decoding
    token by token gives the training forward's logits."""
    cfg = lm_config(layer_types=None, sliding_window=None,
                    attn_gate="head", mlp="gelu")
    model = models.TransformerLM(cfg)
    tokens = jax.random.randint(KEY, (2, 4), 0, 64)
    params = model.init(KEY, tokens)
    step = jax.jit(lambda t, pos, cache: model.apply(
        params, t, positions=pos, cache=cache))
    with HIGHEST():
        want = model.apply(params, tokens)
        cache = T.init_cache(cfg, 2, 4)
        for t in range(4):
            got, cache = step(tokens[:, t:t + 1], jnp.full((2, 1), t),
                              cache)
            np.testing.assert_allclose(got[:, 0], want[:, t], rtol=1e-4,
                                       atol=1e-4)


def test_the_sequence_parallel_attentions_raise_on_a_window(devices):
    from jax.sharding import Mesh, PartitionSpec as P
    from bluefog_tpu.parallel import ring_attention, ulysses_attention
    mesh = Mesh(np.asarray(devices[:2]), ("sp",))
    q = normal(3, (1, 16, 2, 8))
    for fn in (ring_attention, ulysses_attention):
        with pytest.raises(NotImplementedError, match="sliding window"):
            jax.shard_map(
                lambda a, b, c: fn(a, b, c, axis_name="sp", window=4),
                mesh=mesh, in_specs=(P(None, "sp"),) * 3,
                out_specs=P(None, "sp"))(q, q, q)


def test_the_layers_are_counted_by_mixer():
    model = models.TransformerLM(lm_config())
    model.init(KEY, jnp.zeros((1, 8), jnp.int32))
    got = telemetry.snapshot()
    assert got['bf_model_layers_total{mixer="sliding_attention"}'] == 2
    assert got['bf_model_layers_total{mixer="full_attention"}'] == 1
    assert got['bf_model_layers_total{mixer="conv"}'] == 0


# --- (b) the rotary scheme of a layer type --------------------------------------

SCHEMES = {
    "full_attention": {"rope_theta": 500000, "rope_type": "yarn",
                       "factor": 64, "original_max_position_embeddings": 4096,
                       "beta_slow": 1, "beta_fast": 64,
                       "attention_factor": 1.4158883083359672,
                       "partial_rotary_factor": 0.5},
    "sliding_attention": {"rope_type": "default", "rope_theta": 10000,
                          "partial_rotary_factor": 1},
    "original_max_position_embeddings": 4096}


def test_rope_scheme_reads_the_layer_types_entry(toy):
    _, _, ref = toy
    cfg = lm_config(rope_parameters=SCHEMES, rope_theta=123.0)
    rot, theta, freq, factor = T.rope_scheme(cfg, "full_attention", 128)
    assert (rot, theta, factor) == (64, 500000, 1.4158883083359672)
    assert factor == pytest.approx(0.1 * np.log(64) + 1)
    want = ref._inverse_frequencies(64, SCHEMES["full_attention"])
    np.testing.assert_allclose(freq, want, rtol=1e-6)
    plain = 500000.0 ** (-np.arange(0, 64, 2) / 64)
    # the fastest pairs keep their frequency, the slowest are divided by 64
    np.testing.assert_allclose(freq[:4], plain[:4], rtol=1e-6)
    np.testing.assert_allclose(freq[-4:], plain[-4:] / 64, rtol=1e-6)
    assert T.rope_scheme(cfg, "sliding_attention", 128) == (
        128, 10000, None, 1.0)
    # no entry: rope_theta and rope_scaling, for every layer
    bare = lm_config(rope_theta=123.0)
    assert T.rope_scheme(bare, "sliding_attention", 16) == (
        16, 123.0, None, 1.0)
    scaled = lm_config(rope_scaling={
        "type": "yarn", "factor": 8, "beta_fast": 8, "beta_slow": 1,
        "original_max_position_embeddings": 64})
    rot, _, freq, factor = T.rope_scheme(scaled, "full_attention", 16)
    assert rot == 16 and factor == pytest.approx(0.1 * np.log(8) + 1)
    np.testing.assert_allclose(
        freq, T.yarn_frequencies(16, 10000.0, scaled.rope_scaling))
    both = dict(scaled.rope_scaling, mscale=1.0, mscale_all_dim=1.0)
    assert T.rope_scheme(lm_config(rope_scaling=both), "full_attention",
                         16)[3] == 1.0


@pytest.mark.parametrize("kind", ["full_attention", "sliding_attention"])
def test_the_rotary_embedding_of_a_layer_type_is_the_references(toy, kind):
    """Half of a head under YaRN with its factor on cos and sin and the
    other half untouched; the whole head at the plain frequencies."""
    _, _, ref = toy
    scheme = dict(SCHEMES[kind], original_max_position_embeddings=32)
    x = normal(4, (2, 40, 3, 16))
    positions = jnp.broadcast_to(jnp.arange(40), (2, 40))
    cfg = lm_config(rope_parameters={kind: scheme})
    rot, theta, freq, factor = T.rope_scheme(cfg, kind, 16)
    got = jnp.concatenate(
        [T.apply_rope(x[..., :rot], positions, theta, freq, factor),
         x[..., rot:]], axis=-1)
    np.testing.assert_allclose(got, ref._rotary(x, scheme), rtol=1e-5,
                               atol=1e-5)
    if kind == "full_attention":
        np.testing.assert_array_equal(got[..., 8:], x[..., 8:])
        # position 0 turns nothing: the factor alone
        np.testing.assert_allclose(got[:, 0, :, :8], factor * x[:, 0, :, :8],
                                   rtol=1e-6)


# --- (c) one attention layer of each kind ----------------------------------------

def _block_case(toy, layer, attn_impl, seq=80):
    config, task, ref = toy
    cfg = task.make_model(with_dtype(config, "float32")).cfg
    block = T.Block(cfg, attn_impl, layer)
    x = normal(10 + layer, (2, seq, config["hidden_size"]))
    positions = jnp.broadcast_to(jnp.arange(seq), (2, seq))
    params = block.init(KEY, x, positions)["params"]
    params = jax.tree.map(lambda p: p + 0.1 * normal(p.size, p.shape),
                          params)
    return config, ref, block, params, x, positions


@pytest.mark.parametrize("attention", ["local", "flash"])
@pytest.mark.parametrize("layer", [0, 1])
def test_an_attention_layer_against_the_reference(toy, layer, attention):
    """Layer 0 (full, 6 heads, YaRN over half a head) and layer 1 (window
    of 48 over 80 positions, 8 heads, plain rotary), each with its gate:
    the mixer's result and every gradient."""
    impl = (T.local_attention if attention == "local"
            else flash_attention_impl(block_q=16, block_k=32))
    config, ref, block, params, x, positions = _block_case(toy, layer, impl)
    eps = config["rms_norm_eps"]

    def mixer(p, x):
        """The block less its feed-forward: the reference's attention on
        the block's own first norm."""
        y = ref._rms_norm(x, p["RMSNorm_0"]["scale"], eps)
        return x + ref._attention(y, p, layer, config)

    def mine(p, x):
        # the residual after the mixer is what the second norm is given
        _, seen = block.apply({"params": p}, x, positions,
                              capture_intermediates=lambda m, _: m.name
                              == "RMSNorm_1", mutable=["intermediates"])
        return seen["intermediates"]["RMSNorm_1"]["__call__"][0]

    def theirs(p, x):
        return ref._rms_norm(mixer(p, x), p["RMSNorm_1"]["scale"], eps)

    with HIGHEST():
        np.testing.assert_allclose(jax.jit(mine)(params, x),
                                   jax.jit(theirs)(params, x), rtol=2e-5,
                                   atol=2e-5)
        loss = lambda fn: lambda p, x: (fn(p, x) ** 2).sum()  # noqa: E731
        got = jax.jit(jax.grad(loss(mine), (0, 1)))(params, x)
        ref_g = jax.jit(jax.grad(loss(theirs), (0, 1)))(params, x)
    for name in ("q", "kv", "proj", "attn_gate", "RMSNorm_0"):
        assert rel(jax.tree.leaves(got[0][name])[0],
                   jax.tree.leaves(ref_g[0][name])[0]) < 1e-5, name
    assert rel(got[1], ref_g[1]) < 1e-5


def test_the_window_reaches_the_attention_and_cuts(toy):
    """A change more than a window back moves nothing; one inside it does;
    in the full layer both do."""
    seen = {}

    def spy(q, k, v, *, causal=True, window=None):
        seen[q.shape[2]] = window
        return T.local_attention(q, k, v, causal=causal, window=window)

    for layer, heads in ((0, 6), (1, 8)):
        config, _, block, params, x, positions = _block_case(toy, layer, spy)
        at = 70
        run = lambda x: block.apply({"params": params}, x, positions)  # noqa
        base = run(x)
        far = run(x.at[:, at - 48].add(1.0))       # 48 back: outside
        near = run(x.at[:, at - 47].add(1.0))      # the window's last key
        assert float(jnp.abs(near[:, at] - base[:, at]).max()) > 1e-6
        if layer == 1:
            np.testing.assert_array_equal(far[:, at], base[:, at])
        else:
            assert float(jnp.abs(far[:, at] - base[:, at]).max()) > 1e-6
    assert seen == {6: None, 8: 48}


def test_the_gate_is_one_sigmoid_a_head_before_the_output_projection(toy):
    config, ref, block, params, x, positions = _block_case(
        toy, 1, T.local_attention)
    shut = dict(params, attn_gate={"kernel": jnp.zeros_like(
        params["attn_gate"]["kernel"])})
    ungated = dict(params, proj={"kernel": 0.5 * params["proj"]["kernel"]})
    cfg = copy.copy(block.cfg)
    cfg.attn_gate = None
    bare = T.Block(cfg, T.local_attention, 1)
    with HIGHEST():
        # sigmoid(0) = 1/2 on every head: half the ungated projection
        got = block.apply({"params": shut}, x, positions)
        want = bare.apply({"params": {k: v for k, v in ungated.items()
                                      if k != "attn_gate"}}, x, positions)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


def test_the_blocks_scopes_are_the_layer_types_family(toy):
    config, task, _ = toy
    model = task.make_model(with_dtype(config, "float32"))
    tokens = jnp.zeros((1, 64), jnp.int32)
    params = jax.eval_shape(model.init, KEY, tokens)
    text = jax.jit(lambda p, t: model.apply(p, t, return_hidden=True)).lower(
        params, tokens).as_text(debug_info=True)
    for scope in ("bf.swa.qkv", "bf.swa.rope", "bf.swa.attend",
                  "bf.swa.gate", "bf.swa.out", "bf.attn.qkv", "bf.attn.rope",
                  "bf.attn.attend", "bf.attn.gate", "bf.attn.out",
                  "bf.moe.shared"):
        assert scope + "/" in text or scope + '"' in text, scope


# --- (d) softmax weights with a scaling factor -----------------------------------

def test_softmax_weights_take_the_scaling_factor():
    logits = normal(20, (32, 16))
    plain = moe.route_topk(logits, 4, renormalize=True)
    scaled = moe.route_topk(logits, 4, renormalize=True, scale=2.5)
    np.testing.assert_array_equal(plain.experts, scaled.experts)
    np.testing.assert_allclose(scaled.weights, 2.5 * plain.weights,
                               rtol=1e-6)
    np.testing.assert_allclose(scaled.weights.sum(axis=-1), 2.5, rtol=1e-5)
    assert float(scaled.balance_loss) == float(plain.balance_loss)
    with pytest.raises(ValueError, match="bias"):
        moe.route_topk(logits, 4, bias=jnp.zeros((16,)))
    # a factor of one is the program it was, to the text
    text = lambda **kw: str(jax.make_jaxpr(lambda l: moe.route_topk(  # noqa
        l, 4, renormalize=True, **kw))(logits))
    assert text() == text(scale=1.0) != text(scale=2.5)


def _moe_layer(config, task, held, first):
    cfg = task.make_model(with_dtype(dict(
        config, num_experts=held, experts_first=first), "float32")).cfg
    return T.DroplessMoe(cfg)


def test_a_held_share_with_softmax_scores_and_the_shared_expert(toy):
    """No configuration pairs them: 4 of 16 experts held (a window of 256
    of the 544 assignments), softmax top-4 renormalised and scaled by 2.5,
    a shared expert; output, load, both router terms and every gradient
    against the reference's expert layer."""
    config, task, ref = toy
    layer = _moe_layer(config, task, 4, 4)
    cfg = dict(config, experts_first=4)
    y = normal(21, (2, 68, 64))
    params = layer.init(KEY, y)["params"]
    params = jax.tree.map(lambda p: p + 0.1 * normal(p.size, p.shape),
                          params)
    assert moe.held_window(2 * 68 * 4, 4, 16) < 2 * 68 * 4
    assert params["gate"].shape == (4, 64, 16)
    assert params["shared_gate"]["kernel"].shape == (64, 16)

    def mine(p, y):
        out, sown = layer.apply({"params": p}, y, mutable=["intermediates"])
        return out, T.moe_stats(sown["intermediates"])

    def theirs(p, y):
        out, load, balance, z, _ = ref._experts(y, p, cfg)
        return out, {"load": load[None], "balance_loss": balance,
                     "z_loss": z}

    def total(fn):
        def of(p, y):
            out, stats = fn(p, y)
            loss = (out ** 2).sum() + stats["balance_loss"] + stats["z_loss"]
            return loss, (out, stats)
        return jax.jit(jax.value_and_grad(of, (0, 1), has_aux=True))

    with HIGHEST():
        (_, (out, stats)), got = total(mine)(params, y)
        (_, (want, ref_stats)), ref_g = total(theirs)(params, y)
    np.testing.assert_allclose(out, want, rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(stats["load"], ref_stats["load"])
    for term in ("balance_loss", "z_loss"):
        assert float(stats[term]) == pytest.approx(float(ref_stats[term]),
                                                   rel=1e-5)
    errs = jax.tree.map(rel, got, ref_g)
    worst = max(jax.tree_util.tree_leaves_with_path(errs),
                key=lambda kv: kv[1])
    assert worst[1] < 1e-5, jax.tree_util.keystr(worst[0])
