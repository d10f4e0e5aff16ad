"""The mechanisms ``nemotron-twotower-30b-a3b`` forced, at toy widths on the
CPU, each against the configuration's plain reference
(``benchmark/reference/nemotron-twotower-30b-a3b.py``, which imports nothing
of ``bluefog_tpu`` and walks the recurrence step by step) or a hand-written
line of it: the chunked state-space scan, the Mamba-2 mixer around it,
blocks that are one part alone, un-gated ``relu2`` experts through the one
expert path (whole and as a held share on its window), grouped-query
attention of 32 over 2 style without positions; the whole toy model's cases
are those of ``tests/twins.py``, run from ``tests/test_twins.py``.  float32 to
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

from benchmark import spec  # noqa: E402
from bluefog_tpu import models  # noqa: E402
from bluefog_tpu.models import transformer as T  # noqa: E402
from bluefog_tpu.ops.flash_attention import flash_attention_impl  # noqa: E402
from bluefog_tpu.ops import ssd  # noqa: E402
from bluefog_tpu.ops.ssd import ssd_scan  # noqa: E402
from bluefog_tpu.parallel import moe  # noqa: E402
from bluefog_tpu.utils import telemetry  # noqa: E402
import twins  # noqa: E402
from twins import HIGHEST, rel, toy, with_dtype  # noqa: E402,F401

# the twin of ``toy``; its whole-model cases run from tests/test_twins.py
TWINS = ("tiny-twotower",)
KEY = jax.random.PRNGKey(42)
normal = functools.partial(twins.normal, KEY)



def value_and_grads(fn, argnums, weight=None):
    """One jitted program that gives ``fn``'s value and the gradients of
    ``sum(fn * weight)`` (``sum(fn^2)`` without a weight)."""
    def loss(*a):
        out = fn(*a)
        return (out * (out if weight is None else weight)).sum()
    return jax.jit(lambda *a: (fn(*a), jax.grad(loss, argnums)(*a)))


# --- (a) the chunked scan against the step-by-step recurrence ---------------------

def _scan_inputs(seq, b=2, H=4, P=8, G=2, N=16):
    x = normal(1, (b, seq, H, P))
    dt = 0.2 * jax.nn.softplus(normal(2, (b, seq, H)))
    A = -jnp.exp(jax.random.uniform(jax.random.fold_in(KEY, 3), (H,),
                                    minval=0.0, maxval=2.7))
    return (x, dt, A, normal(4, (b, seq, G, N)), normal(5, (b, seq, G, N)),
            normal(6, (H,)))


def _stepwise(ref, x, dt, A, B, C, D):
    """The reference's recurrence, one step a position, and the skip."""
    share = x.shape[2] // B.shape[2]
    return ref._recurrence(
        x, dt, jnp.exp(dt * A), jnp.repeat(B, share, axis=2),
        jnp.repeat(C, share, axis=2)) + D[:, None] * x


def _chunked(chunk):
    """``ssd_scan`` at ``chunk`` with ``D`` as its sixth argument."""
    return lambda *a: ssd_scan(*a[:5], chunk=chunk, D=a[5])


@pytest.mark.parametrize("seq", [64, 32, 100, 257, 5])
def test_chunked_scan_against_the_recurrence(toy, seq):
    """Values and every gradient at lengths that are a multiple of the
    chunk (64, 32), that are not (100, 257: a tail of 4 and of 1) and
    shorter than one (5)."""
    ref = toy[2]
    args = _scan_inputs(seq)
    mine = _chunked(32)
    theirs = functools.partial(_stepwise, ref)
    weight = normal(7, args[0].shape)       # a loss that tells positions apart
    with HIGHEST():
        got, g_mine = value_and_grads(mine, range(6), weight)(*args)
        want, g_theirs = value_and_grads(theirs, range(6), weight)(*args)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)
    for name, a, b in zip("x dt A B C D".split(), g_mine, g_theirs):
        assert rel(a, b) < 1e-5, name


def test_the_scan_is_causal_and_carries_its_state_across_chunks():
    """A change at position t leaves every earlier output as it was and
    reaches outputs more than a chunk later (through the chunk states)."""
    x, dt, A, B, C, D = _scan_inputs(96)
    scan = jax.jit(ssd_scan, static_argnames="chunk")
    base = scan(x, dt, A, B, C, chunk=32, D=D)
    moved = scan(x.at[:, 40].add(1.0), dt, A, B, C, chunk=32, D=D)
    np.testing.assert_array_equal(base[:, :40], moved[:, :40])
    assert float(jnp.abs(base[:, 40] - moved[:, 40]).max()) > 0.1
    assert float(jnp.abs(base[:, 80:] - moved[:, 80:]).max()) > 1e-6
    # the chunk is a way to compute and no parameter of the result
    with HIGHEST():
        np.testing.assert_allclose(
            scan(x, dt, A, B, C, chunk=8, D=D),
            scan(x, dt, A, B, C, chunk=96, D=D), rtol=1e-4, atol=1e-4)


def test_the_scan_counts_its_chunks_and_checks_its_shapes():
    x, dt, A, B, C, D = _scan_inputs(100)
    before = telemetry.snapshot().get("bf_ssm_chunks_total", 0.0)
    ssd_scan(x, dt, A, B, C, chunk=32)
    assert telemetry.snapshot()["bf_ssm_chunks_total"] - before == 2 * 4
    with pytest.raises(ValueError, match="multiple of G"):
        ssd_scan(x[:, :, :3], dt[:, :, :3], A[:3], B, C)
    with pytest.raises(ValueError, match="ssd_scan"):
        ssd_scan(x, dt[:, :50], A, B, C)


def _published_group(ref):
    """The published shape of a group (8 heads of 64 over a state of 128) in
    the cell's dtype, 3 chunks of 128 and a tail of 5: values and the six
    gradients against the float32 recurrence within bfloat16's error."""
    x, dt, A, B, C, D = _scan_inputs(3 * 128 + 5, b=1, H=8, P=64, G=1, N=128)
    half = lambda t: t.astype(jnp.bfloat16)  # noqa: E731
    args = (half(x), 0.1 * dt, A, half(0.3 * B), half(0.3 * C), D)
    mine = _chunked(128)
    exact = lambda *a: _stepwise(ref, *[  # noqa: E731
        t.astype(jnp.float32) for t in a])
    weight = normal(7, args[0].shape)
    got, g_mine = value_and_grads(
        lambda *a: mine(*a).astype(jnp.float32), range(6), weight)(*args)
    with HIGHEST():
        want, g_exact = value_and_grads(exact, range(6), weight)(*args)
    assert got.dtype == jnp.float32 and mine(*args).dtype == jnp.bfloat16
    assert rel(got, want) < 1e-2
    for name, a, b in zip("x dt A B C D".split(), g_mine, g_exact):
        assert rel(a.astype(jnp.float32), b) < 2e-2, name


def _two_groups(ref):
    """A head reads the ``B`` and ``C`` of its own group: one head a group
    against the recurrence, and a change to the second group's ``B``
    reaches the second group's heads alone."""
    x, dt, A, B, C, D = _scan_inputs(70, b=1, H=2, P=16, G=2, N=8)
    scan = _chunked(32)
    with HIGHEST():
        got, grads = value_and_grads(scan, range(6))(x, dt, A, B, C, D)
        want, g_ref = value_and_grads(
            functools.partial(_stepwise, ref), range(6))(x, dt, A, B, C, D)
        moved = scan(x, dt, A, B.at[:, :, 1].multiply(2.0), C, D)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)
    for name, a, b in zip("x dt A B C D".split(), grads, g_ref):
        assert rel(a, b) < 1e-5, name
    np.testing.assert_array_equal(moved[:, :, 0], got[:, :, 0])
    assert float(jnp.abs(moved[:, :, 1] - got[:, :, 1]).max()) > 0.1


def _two_sequences(ref):
    """The state starts at zero in every sequence: the second row of a
    batch is what it is alone, values and gradients."""
    args = _scan_inputs(70)
    scan = _chunked(32)
    alone = tuple(a[1:] if a.ndim > 1 else a for a in args)
    with HIGHEST():
        both, g_both = value_and_grads(scan, (0, 1, 3, 4))(*args)
        one, g_one = value_and_grads(scan, (0, 1, 3, 4))(*alone)
    np.testing.assert_allclose(both[1:], one, rtol=1e-6, atol=1e-6)
    for a, b in zip(g_both, g_one):
        np.testing.assert_allclose(a[1:], b, rtol=1e-5, atol=1e-5)


def _without_a_skip(ref):
    """``D=None`` is the recurrence alone."""
    x, dt, A, B, C, D = _scan_inputs(40)
    mine = lambda x: ssd_scan(x, dt, A, B, C, chunk=32)  # noqa: E731
    theirs = lambda x: _stepwise(  # noqa: E731
        ref, x, dt, A, B, C, jnp.zeros_like(D))
    with HIGHEST():
        np.testing.assert_allclose(mine(x), theirs(x), rtol=1e-4, atol=1e-4)
        assert rel(jax.grad(lambda x: mine(x).sum())(x),
                   jax.grad(lambda x: theirs(x).sum())(x)) < 1e-5


def _staged_once_a_shape(ref):
    """Forward and backward kernels are staged once for a shape: a second
    call, and a second program that uses the shape, stage nothing new."""
    staged = lambda: {  # noqa: E731
        k: v for k, v in telemetry.snapshot().items()
        if k.startswith("bf_kernel_stagings_total") and "bf_ssd_" in k}
    x, dt, A, B, C, D = _scan_inputs(48, b=1, H=2, P=4, G=1, N=4)
    grad = lambda x: jax.grad(  # noqa: E731
        lambda x: ssd_scan(x, dt, A, B, C, chunk=16, D=D).sum())(x)
    before = staged()
    ssd_scan(x, dt, A, B, C, chunk=16, D=D)
    grad(x)
    first = staged()
    name = 'bf_kernel_stagings_total{kernel="bf_ssd_%s"}'
    # the plain call and the rule's forward (which also writes the chunks'
    # entering states) are two programs of the forward kernel
    assert first[name % "fwd"] - before.get(name % "fwd", 0.0) == 2
    assert first[name % "bwd"] - before.get(name % "bwd", 0.0) == 1
    ssd_scan(x, dt, A, B, C, chunk=16, D=D)
    grad(x)
    jax.jit(lambda x: ssd_scan(x, dt, A, B, C, chunk=16, D=D) * 2.0)(x)
    assert staged() == first


@pytest.mark.parametrize("case", [
    _published_group, _two_groups, _two_sequences, _without_a_skip,
    _staged_once_a_shape], ids=lambda f: f.__name__.strip("_"))
def test_the_scan_kernels(toy, case):
    case(toy[2])


def test_shapes_a_tpu_cannot_tile_raise_and_the_interpreter_takes_them():
    """The compiled kernels need the chunk, a group's ``R P`` and ``N`` in
    whole lane tiles of whole heads; the check needs no TPU, and off the
    TPU the same shapes run."""
    ssd.check_tileable(128, 8, 64, 128)         # the published group
    ssd.check_tileable(256, 2, 128, 256)
    for shape, named in (((32, 8, 64, 128), "chunk 32"),
                         ((128, 3, 64, 128), "R P = 192"),
                         ((128, 8, 64, 16), "a state of 16"),
                         ((128, 2, 192, 128), "2 heads of 192")):
        with pytest.raises(ValueError, match=named):
            ssd.check_tileable(*shape)
    x, dt, A, B, C, D = _scan_inputs(40, b=1, H=3, P=5, G=1, N=7)
    out = ssd_scan(x, dt, A, B, C, chunk=12, D=D)
    assert out.shape == x.shape and bool(jnp.isfinite(out).all())


# --- (b) the Mamba-2 mixer around it ---------------------------------------------------

def _mixer(toy, dtype=jnp.float32):
    config, task, ref = toy
    model = task.make_model(with_dtype(config, jnp.dtype(dtype).name))
    return T.Mamba2Mixer(model.cfg), config, ref


@pytest.mark.parametrize("seq", [96, 45, 3])
def test_mamba_mixer_against_the_reference(toy, seq):
    """Projection, taps with bias and SiLU, time steps, scan, skip, gated
    grouped norm and out-projection: forward and every gradient, at a
    length shorter than the taps too."""
    layer, config, ref = _mixer(toy)
    y = normal(10, (2, seq, 64))
    params = layer.init(KEY, y)["params"]
    assert {k: jax.tree.leaves(v)[0].shape for k, v in params.items()} == {
        "in": (64, 2 * 64 + 2 * 2 * 16 + 8), "conv_w": (64 + 64, 4),
        "conv_b": (128,), "dt_bias": (8,), "A_log": (8,), "D": (8,),
        "norm_scale": (64,), "out": (64, 64)}
    # seeded noise on the leaves that start at a constant
    params = jax.tree.map(lambda p: p + normal(p.size, p.shape, 0.1), params)
    mine = lambda p, y: layer.apply({"params": p}, y)  # noqa: E731
    theirs = lambda p, y: ref._mamba(y, p, config)  # noqa: E731
    with HIGHEST():
        out, got = value_and_grads(mine, (0, 1))(params, y)
        ref_out, want = value_and_grads(theirs, (0, 1))(params, y)
    np.testing.assert_allclose(out, ref_out, rtol=1e-4, atol=1e-5)
    errs = jax.tree.map(rel, got, want)
    worst = max(jax.tree_util.tree_leaves_with_path(errs),
                key=lambda kv: kv[1])
    assert worst[1] < 1e-4, jax.tree_util.keystr(worst[0])


def test_mamba_mixer_starts_where_mamba2_starts(toy):
    layer, config, _ = _mixer(toy)
    params = layer.init(KEY, jnp.zeros((1, 8, 64)))["params"]
    steps = jax.nn.softplus(params["dt_bias"])
    lo, hi = config["time_step_min"], config["time_step_max"]
    assert float(steps.min()) >= lo * 0.999 and float(steps.max()) <= hi * 1.001
    a = -jnp.exp(params["A_log"])
    assert float(a.max()) <= -1.0 and float(a.min()) >= -16.0
    np.testing.assert_array_equal(params["D"], 1.0)
    np.testing.assert_array_equal(params["conv_b"], 0.0)


# --- (c) blocks of one part, and what takes a cache ---------------------------------------

def _one_part(kind, **kw):
    cfg = models.TransformerConfig(
        vocab_size=64, num_layers=1, num_heads=4, num_kv_heads=2,
        head_dim=8, embed_dim=24, pos_encoding="none", mlp="relu2",
        layer_types=[kind], block_ffn=False, conv_kernel=4, ssm_heads=4,
        ssm_head_dim=8, ssm_groups=2, ssm_state=8, ssm_chunk=16,
        rms_norm_eps=1e-5, dtype=jnp.float32, **kw)
    block = T.Block(cfg, T.local_attention, 0)
    x = normal(20, (2, 24, 24))
    return cfg, block, x, block.init(KEY, x)["params"]


def _norm(x, scale, eps=1e-5):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def test_a_block_that_is_a_mixer_alone():
    """``x + Mamba(RMSNorm(x))`` and nothing behind it: one norm, no MLP
    leaf."""
    cfg, block, x, params = _one_part("mamba")
    assert set(params) == {"RMSNorm_0", "mamba"}
    y = _norm(x, params["RMSNorm_0"]["scale"])
    want = x + jax.jit(T.Mamba2Mixer(cfg).apply)(
        {"params": params["mamba"]}, y)
    np.testing.assert_allclose(jax.jit(block.apply)({"params": params}, x),
                               want, rtol=1e-5, atol=1e-6)
    # attention alone: the same residual path, no second norm
    _, block, x, params = _one_part("full_attention")
    assert set(params) == {"RMSNorm_0", "q", "kv", "proj"}
    # and with block_ffn left on, the MLP follows the mixer as ever
    cfg = models.TransformerConfig(
        num_layers=1, num_heads=4, embed_dim=24, layer_types=["mamba"],
        ssm_heads=4, ssm_head_dim=8, dtype=jnp.float32)
    both = T.Block(cfg, T.local_attention, 0).init(KEY, x)["params"]
    assert set(both) == {"RMSNorm_0", "mamba", "RMSNorm_1", "up", "down"}


def test_a_block_that_is_an_moe_alone():
    """``x + Experts(RMSNorm(x))`` with no mixer before it: one norm, the
    un-gated experts' leaves (no ``gate``, no ``shared_gate``)."""
    cfg, block, x, params = _one_part(
        "ffn", num_experts=8, num_experts_per_tok=2, expert_dim=16,
        num_shared_experts=2, router_scoring="sigmoid", norm_topk_prob=True)
    assert set(params) == {"RMSNorm_0", "moe"}
    assert set(params["moe"]) == {"router", "up", "down", "shared_up",
                                  "shared_down"}
    assert params["moe"]["shared_up"]["kernel"].shape == (24, 32)
    y = _norm(x, params["RMSNorm_0"]["scale"])
    variables = {"params": params["moe"],
                 "router_state": {"bias": jnp.zeros((8,))}}
    want = x + jax.jit(T.DroplessMoe(cfg).apply)(variables, y)
    got = jax.jit(block.apply)(
        {"params": params,
         "router_state": {"moe": {"bias": jnp.zeros((8,))}}}, x)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
    # the dense MLP of such a block is un-gated too: down(relu(up y)^2)
    _, block, x, params = _one_part("ffn", mlp_dim=40)
    assert set(params) == {"RMSNorm_0", "up", "down"}
    y = _norm(x, params["RMSNorm_0"]["scale"])
    with HIGHEST():
        want = x + jnp.square(jax.nn.relu(y @ params["up"]["kernel"])) \
            @ params["down"]["kernel"]
        np.testing.assert_allclose(block.apply({"params": params}, x), want,
                                   rtol=1e-5, atol=1e-6)


def test_layer_kinds_are_counted_and_checked():
    cfg = models.TransformerConfig(
        vocab_size=64, num_layers=4, num_heads=4, embed_dim=32,
        pos_encoding="none", mlp="relu2", block_ffn=False,
        layer_types=["mamba", "ffn", "full_attention", "ffn"], ssm_heads=4,
        ssm_head_dim=8, dtype=jnp.float32)
    model = models.TransformerLM(cfg)
    params = model.init(KEY, jnp.zeros((1, 8), jnp.int32))["params"]
    assert "wpe" not in params                  # no position table
    got = telemetry.snapshot()
    assert got['bf_model_layers_total{mixer="mamba"}'] == 1
    assert got['bf_model_layers_total{mixer="ffn"}'] == 2
    assert got['bf_model_layers_total{mixer="full_attention"}'] == 1
    with pytest.raises(ValueError, match="pos_encoding"):
        models.TransformerConfig(pos_encoding="alibi")
    with pytest.raises(ValueError, match="layer_types"):
        models.TransformerConfig(num_layers=1, layer_types=["mamba2"])
    with pytest.raises(ValueError, match="ssm_heads"):
        models.TransformerConfig(num_layers=1, layer_types=["mamba"])
    with pytest.raises(ValueError, match="ssm_groups"):
        models.TransformerConfig(num_layers=1, layer_types=["mamba"],
                                 ssm_heads=6, ssm_groups=4)
    with pytest.raises(ValueError, match="relu2"):
        models.TransformerConfig(mlp="relu")


def test_without_positions_a_permuted_prefix_permutes_the_values():
    """``pos_encoding="none"``: attention alone cannot tell the order of
    the keys, so one block's output at the last position is the same for
    any order of the positions before it."""
    cfg = models.TransformerConfig(
        vocab_size=64, num_layers=1, num_heads=4, num_kv_heads=2, head_dim=8,
        embed_dim=24, pos_encoding="none", mlp="relu2", dtype=jnp.float32)
    model = models.TransformerLM(cfg)
    tokens = jax.random.randint(KEY, (1, 12), 0, 64)
    params = model.init(KEY, tokens)
    mixed = jnp.concatenate([tokens[:, :11][:, ::-1], tokens[:, 11:]], axis=1)
    np.testing.assert_allclose(model.apply(params, tokens)[:, -1],
                               model.apply(params, mixed)[:, -1],
                               rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("kind", ["mamba", "ffn"])
def test_a_mamba_or_single_part_layer_with_a_cache_raises(kind):
    cfg, block, x, params = _one_part(kind)
    cache = (jnp.zeros((2, 8, 2, 8)),) * 2
    with pytest.raises(NotImplementedError, match="decode cache"):
        block.apply({"params": params}, x[:, :1], jnp.zeros((2, 1), int),
                    cache)
    cfg = models.TransformerConfig(
        vocab_size=64, num_layers=2, num_heads=4, embed_dim=32,
        pos_encoding="none", layer_types=["full_attention", kind],
        ssm_heads=4, ssm_head_dim=8, dtype=jnp.float32)
    model = models.TransformerLM(cfg)
    tokens = jnp.zeros((1, 1), jnp.int32)
    params = model.init(KEY, tokens)
    with pytest.raises(NotImplementedError, match="KV-cache decoding"):
        model.apply(params, tokens, positions=jnp.zeros((1, 1), int),
                    cache=T.init_cache(cfg, 1, 8))


def test_generate_stays_for_plain_attention_without_positions():
    cfg = models.TransformerConfig(
        vocab_size=64, num_layers=2, num_heads=4, num_kv_heads=2, head_dim=8,
        embed_dim=24, pos_encoding="none", mlp="relu2", dtype=jnp.float32)
    model = models.TransformerLM(cfg)
    prompt = jax.random.randint(KEY, (2, 6), 0, 64)
    variables = model.init(KEY, prompt)
    out = T.generate(model, variables, prompt, 4)
    assert out.shape == (2, 4)
    # greedy decoding through the cache is the full forward's argmax
    full = jnp.concatenate([prompt, out], axis=1)
    logits = model.apply(variables, full)
    np.testing.assert_array_equal(
        out, jnp.argmax(logits[:, 5:-1], axis=-1).astype(out.dtype))


# --- (d) un-gated and gated experts through the one path ----------------------------

def _layer(T_=512, d=32, f=16, E=8):
    return (normal(40, (T_, d)), normal(41, (T_, E)),
            normal(42, (E, d, f), 0.3), normal(43, (E, d, f), 0.3),
            normal(44, (E, f, d), 0.3))


def _dense_experts(x, logits, gate, up, down, k, first=0):
    """Every expert on every token, masked by the softmax top-k choice."""
    E = logits.shape[-1]
    probs = jax.nn.softmax(logits, axis=-1)
    top, chosen = jax.lax.top_k(probs, k)
    weight = (jax.nn.one_hot(chosen, E) * top[..., None]).sum(axis=1)
    out = 0.0
    for e in range(up.shape[0]):
        hidden = jnp.square(jax.nn.relu(x @ up[e])) if gate is None \
            else jax.nn.silu(x @ gate[e]) * (x @ up[e])
        out = out + weight[:, first + e, None] * (hidden @ down[e])
    return out


@pytest.mark.parametrize("gated", [False, True], ids=["relu2", "swiglu"])
@pytest.mark.parametrize("held", [None, (2, 2)], ids=["whole", "window"])
def test_gated_and_ungated_experts_through_the_one_path(gated, held):
    """``gate=None`` is ``down(relu(up x)^2)`` and a gate SwiGLU, with all
    experts held and as a share of two of eight on its window (512 of the
    1024 assignments): values and every gradient against every expert
    applied to every token."""
    x, logits, gate, up, down = _layer()
    first, count = held or (0, 8)
    mats = [m[first:first + count] for m in (gate, up, down)]
    if not gated:
        mats[0] = None
    if held:
        assert moe.held_window(512 * 2, 2, 8) == 512 < 1024
    mine = lambda x, l, g, u, d: moe.dropless_moe(  # noqa: E731
        x, l, g, u, d, k=2, held=held)[0]
    theirs = lambda x, l, g, u, d: _dense_experts(  # noqa: E731
        x, l, g, u, d, 2, first)
    weight = normal(45, x.shape)
    argnums = (0, 1, 2, 3, 4) if gated else (0, 1, 3, 4)
    with HIGHEST():
        out, got = value_and_grads(mine, argnums, weight)(x, logits, *mats)
        ref_out, want = value_and_grads(theirs, argnums, weight)(
            x, logits, *mats)
    np.testing.assert_allclose(out, ref_out, rtol=1e-4, atol=1e-4)
    for a, b in zip(got, want):
        assert rel(a, b) < 1e-5
    with pytest.raises(ValueError, match="held"):
        moe.dropless_moe(x, logits, mats[0], up[:3], down[:3], k=2,
                         held=(0, 2))


def test_an_ungated_share_that_overflows_its_window_loses_nothing():
    """All assignments to the two held experts: twice the window, covered
    window after window, forward and backward."""
    x, _, _, up, down = _layer()
    logits = jnp.zeros((512, 8)).at[:, 2:4].set(10.0) + normal(46, (512, 8),
                                                               0.1)
    mine = lambda x, u, d: moe.dropless_moe(  # noqa: E731
        x, logits, None, u, d, k=2, held=(2, 2))
    theirs = lambda x, u, d: _dense_experts(  # noqa: E731
        x, logits, None, u, d, 2, 2)
    with HIGHEST():
        y, plan = jax.jit(mine)(x, up[2:4], down[2:4])
        assert int(plan.load[2:4].sum()) == 1024 > moe.held_window(1024, 2, 8)
        np.testing.assert_allclose(y, theirs(x, up[2:4], down[2:4]),
                                   rtol=1e-4, atol=1e-4)
        got = jax.jit(jax.grad(lambda *a: (mine(*a)[0] ** 2).sum(),
                               (0, 1, 2)))(x, up[2:4], down[2:4])
        want = jax.jit(jax.grad(lambda *a: (theirs(*a) ** 2).sum(),
                                (0, 1, 2)))(x, up[2:4], down[2:4])
    for a, b in zip(got, want):
        assert rel(a, b) < 1e-5


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("held_rows", [300, 512, 1024],
                         ids=["under", "exactly-the-window", "over"])
def test_an_ungated_window_is_the_whole_path(monkeypatch, held_rows, dtype):
    """Un-gated experts (``gate=None``) as a share of two of eight on its
    window of 512 of 1024 rows, under it, filling it and over it (two
    windows), against the layer with no window: ``y`` and ``d x`` as a
    token's float32 sum is in another order of its terms, the matrices'
    and the router's gradients to float32 rounding."""
    x, _, _, up, down = _layer()
    t = np.arange(512)
    held = np.where(t % 2 == 0, 2, 3)
    absent = np.array([0, 1, 4, 5, 6, 7])
    # the first tokens send both choices to the held pair, the others none
    first = np.where(t < held_rows // 2, held, absent[t % 6])
    second = np.where(t < held_rows // 2, 5 - held, absent[(t + 1) % 6])
    logits = (normal(47, (512, 8), 0.1).at[t, first].add(8.0)
              .at[t, second].add(6.0))

    def run(x, logits, up, down):
        y, plan = moe.dropless_moe(x.astype(dtype), logits, None, up, down,
                                   k=2, held=(2, 2))
        return (y.astype(jnp.float32) ** 2).sum(), (y, plan.load)
    grad = lambda: jax.jit(jax.value_and_grad(  # noqa: E731
        run, (0, 1, 2, 3), has_aux=True))(x, logits, up[2:4], down[2:4])
    (_, (y, load)), got = grad()
    assert int(load[2:4].sum()) == held_rows // 2 * 2
    assert moe.held_window(1024, 2, 8) == 512
    monkeypatch.setattr(moe, "held_window", lambda n, count, E: n)
    (_, (y_whole, _)), want = grad()
    eps = float(jnp.finfo(dtype).eps)
    for a, b in ((y, y_whole), (got[0], want[0])):
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        assert np.abs(b).max() > 0
        np.testing.assert_allclose(a, b, rtol=eps,
                                   atol=2 ** -22 * np.abs(b).max())
    for a, b in zip(got[1:], want[1:]):
        assert rel(a, b) < 1e-5


# --- (d') a window's rows summed into their tokens: ``bf_moe_token_sum`` ------------

SUM_CASES = {       # tokens, k, window rows C, rows the run covers, width
    "random": (1000, 3, 300, 217, 40),
    "full-window": (64, 4, 256, 256, 16),
    "empty-window": (1024, 2, 512, 0, 16),
    "one-row": (1024, 2, 512, 1, 16),
    "several-token-tiles": (700, 2, 640, 600, 136),
}


def _window_rows(name, dtype):
    T_, k, C, rows, d = SUM_CASES[name]
    rng = np.random.default_rng(7)
    win = rng.permutation(T_ * k)[:C].astype(np.int32)
    values = normal(60, (C, d)).astype(dtype)
    # what the kernels left in the rows past the run: it reaches no sum
    values = values.at[rows:].set(jnp.nan)
    w = moe._Window(jnp.int32(0), jnp.int32(rows), None)
    return T_, k, rows, jnp.asarray(win), values, w


@pytest.mark.parametrize("weighted", [False, True], ids=["plain", "weighted"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("name", sorted(SUM_CASES))
def test_a_windows_rows_are_summed_into_their_tokens(name, dtype, weighted):
    """``_sum_to_tokens`` alone against ``jax.ops.segment_sum`` in float32
    on a random window: rows of ``dtype``, float32 weights, a float32
    result to float32 rounding of a token's at most ``k`` terms; NaN in
    the rows past ``w.rows`` and a token without rows gives zero."""
    T_, k, rows, win, values, w = _window_rows(name, dtype)
    weights = 0.5 + jax.random.uniform(KEY, (T_, k)) if weighted else None
    got = jax.jit(lambda v, win, w, weights: moe._sum_to_tokens(
        v, win, w, T_, k, jnp.float32, weights))(values, win, w, weights)
    terms = values[:rows].astype(jnp.float32)
    if weighted:
        terms = terms * weights.reshape(-1)[win[:rows]][:, None]
    want = jax.ops.segment_sum(terms, win[:rows] // k, num_segments=T_)
    assert got.shape == want.shape and got.dtype == jnp.float32
    np.testing.assert_allclose(got, want, rtol=2 ** -22,
                               atol=k * 2 ** -23 * float(
                                   jnp.abs(want).max(initial=0.0)))
    touched = np.zeros(T_, bool)
    touched[np.asarray(win[:rows]) // k] = True
    np.testing.assert_array_equal(got[~touched], 0.0)


def test_all_rows_of_a_window_to_one_token():
    """Every row of the run is one token's (more rows than any ``k``: the
    kernel asks nothing of how many rows a token has), the result rounded
    once to bfloat16 from the float32 sum."""
    T_, C, d = 256, 256, 24
    values = normal(61, (C, d)).astype(jnp.bfloat16)
    win = jnp.full((C,), 77 * 2, jnp.int32).at[1::2].add(1)   # token 77
    w = moe._Window(jnp.int32(0), jnp.int32(200), None)
    got = moe._sum_to_tokens(values, win, w, T_, 2, jnp.bfloat16)
    want = values[:200].astype(jnp.float32).sum(axis=0)
    np.testing.assert_allclose(got[77].astype(jnp.float32), want,
                               rtol=2 ** -7, atol=1e-6)
    np.testing.assert_array_equal(jnp.delete(got, 77, axis=0), 0.0)


def test_the_token_sum_is_staged_once_a_shape():
    """``bf_kernel_stagings_total{kernel="bf_moe_token_sum"}`` counts a
    staging a shape: the kernel sits behind a ``jax.jit`` of its own."""
    name = 'bf_kernel_stagings_total{kernel="bf_moe_token_sum"}'
    T_, k, rows, win, values, w = _window_rows("one-row", jnp.float32)
    values = values[:, :8]                      # a shape of this test's own
    call = lambda: moe._sum_to_tokens(values, win, w, T_, k,  # noqa: E731
                                      jnp.float32)
    before = telemetry.snapshot().get(name, 0)
    call(), call()
    jax.jit(lambda: call() + call())()
    assert telemetry.snapshot()[name] - before == 1


@pytest.mark.parametrize("count", [2, 4], ids=["window", "whole"])
def test_the_shares_add_up_to_the_uncut_layer_of_the_reference(toy, count):
    """Eight un-gated experts in shares of ``count`` (two: each on its
    window; four: the window would be the whole order), the shared expert
    counted once: the sum is what the reference gives when it is told that
    it holds all eight, and one share alone what it gives for that share."""
    config, _, ref = toy
    x, _, _, up, down = _layer(d=64, f=24)
    router = normal(50, (64, 8), 0.2)
    shared = {"shared_up": {"kernel": normal(51, (64, 48), 0.2)},
              "shared_down": {"kernel": normal(52, (48, 64), 0.2)}}
    bias = normal(53, (8,), 0.1)
    whole = dict(config, router_width=8, num_experts_per_tok=2,
                 n_routed_experts=8, experts_first=0)
    params = dict(shared, router={"kernel": router}, up=up, down=down)
    kw = dict(k=2, renormalize=True, scoring="sigmoid", bias=bias,
              scale=config["routed_scaling_factor"])
    with HIGHEST():
        want, load, _ = ref._experts(x[None], params, bias, whole)
        logits = x @ router
        parts = [moe.dropless_moe(x, logits, None, up[i:i + count],
                                  down[i:i + count], held=(i, count), **kw)
                 for i in range(0, 8, count)]
        once = ref._relu2(x, shared["shared_up"]["kernel"],
                          shared["shared_down"]["kernel"])
        share = dict(params, up=up[count:2 * count],
                     down=down[count:2 * count])
        alone, _, _ = ref._experts(x[None], share, bias, dict(
            whole, n_routed_experts=count, experts_first=count))
    got = sum(y for y, _ in parts) + once
    np.testing.assert_allclose(got, want[0], rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(parts[1][0] + once, alone[0], rtol=1e-4,
                               atol=1e-4)
    for _, plan in parts:       # every share counts all eight experts
        np.testing.assert_array_equal(plan.load, load)


# --- (e) 4 query heads over 2 K/V heads of a width the hidden size does not give ---

def test_grouped_attention_without_positions_against_the_reference(toy):
    """``head_dim * heads = 64`` is the toy's hidden size by accident only:
    here 4 heads of 16 over a hidden size of 40, through the flash kernels
    (interpreted), against the reference's plain scores."""
    config, _, ref = toy
    cfg = models.TransformerConfig(
        num_layers=1, num_heads=4, num_kv_heads=2, head_dim=16, embed_dim=40,
        pos_encoding="none", layer_types=["full_attention"], block_ffn=False,
        rms_norm_eps=1e-5, dtype=jnp.float32)
    block = T.Block(cfg, flash_attention_impl(), 0)
    x = normal(60, (2, 128, 40))
    params = block.init(KEY, x)["params"]
    assert params["q"]["kernel"].shape == (40, 64)
    assert params["kv"]["kernel"].shape == (40, 2 * 2 * 16)
    sizes = dict(config, hidden_size=40)

    def theirs(p, x):
        y = _norm(x, p["RMSNorm_0"]["scale"])
        return x + ref._attention(y, p, sizes)
    mine = lambda p, x: block.apply({"params": p}, x)  # noqa: E731
    with HIGHEST():
        np.testing.assert_allclose(jax.jit(mine)(params, x),
                                   theirs(params, x), rtol=1e-4, atol=1e-4)
        got = jax.jit(jax.grad(lambda p: (mine(p, x) ** 2).sum()))(params)
        want = jax.jit(jax.grad(lambda p: (theirs(p, x) ** 2).sum()))(params)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert rel(a, b) < 1e-4


# --- (f) the published widths ----------------------------------------------------

def test_the_published_widths_count_667_million_parameters():
    """``jax.eval_shape`` of the cell's own model: no array is made."""
    cell = spec.load_cell("twotower-s8192-1chip")
    task = spec.task_module(cell)
    model = task.make_model(cell.config)
    params, aux = jax.eval_shape(
        lambda key: task.init(model, key, cell.config,
                              cell.traffic["batch"]), KEY)
    count = lambda tree: sum(int(np.prod(p.shape))  # noqa: E731
                             for p in jax.tree.leaves(tree))
    assert count(params["block_0"]) == 38_744_896
    assert count(params["block_1"]) == 100_125_312
    assert count(params["block_5"]) == 23_399_040
    assert count(params) == 666_962_944
    assert "666,962,944" in cell.config["cut"]["why"]
    assert params["block_0"]["mamba"]["in"]["kernel"].shape == (2688, 10304)
    assert params["block_0"]["mamba"]["conv_w"].shape == (6144, 4)
    assert params["block_1"]["moe"]["up"].shape == (8, 2688, 1856)
    assert params["block_1"]["moe"]["shared_up"]["kernel"].shape == (2688,
                                                                     3712)
    assert params["block_5"]["q"]["kernel"].shape == (2688, 4096)
    assert params["block_5"]["kv"]["kernel"].shape == (2688, 512)
    assert aux["bias"].shape == (4, 128)
    assert all(p.dtype == jnp.float32 for p in jax.tree.leaves(params))
