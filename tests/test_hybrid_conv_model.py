"""The mechanisms ``lfm2-24b-a2b`` forced, at toy widths on the CPU, each
against the configuration's plain reference
(``benchmark/reference/lfm2-24b-a2b.py``, which imports nothing of
``bluefog_tpu``) or a hand-written line of it: the gated short convolution,
a per-layer choice of token mixer, the per-head QK norm, flash attention at
heads of 64 under grouped queries, the router's renormalisation epsilon, a
tied head, the whole toy model's loss and gradients, and the optimizer step
on its tree.  float32 to 1e-5; bfloat16 inside the toy's bounds;
float8-rounded matrices outside them."""

import copy
import functools
import hashlib
import os
import re
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
from bluefog_tpu.ops.flash_attention import flash_attention  # noqa: E402
from bluefog_tpu.parallel import moe  # noqa: E402

HIGHEST = functools.partial(jax.default_matmul_precision, "highest")
KEY = jax.random.PRNGKey(34)


def normal(i, shape, scale=1.0):
    return scale * jax.random.normal(jax.random.fold_in(KEY, i), shape)


@pytest.fixture(scope="module")
def toy():
    """The tiny twin's configuration, its task and the reference."""
    config = spec.read_json(os.path.join(
        spec.HERE, "selftest", "configs", "tiny-lfm2.json"))
    return (config, spec.load_module("tasks/hybrid_moe_causal_lm.py"),
            spec.load_module("reference/lfm2-24b-a2b.py"))


def with_dtype(config, dtype):
    config = copy.deepcopy(config)
    config["model"]["args"]["dtype"] = dtype
    return config


def rel(a, b):
    return float(jnp.linalg.norm((a - b).ravel())
                 / jnp.linalg.norm(b.ravel()))


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


# --- (g) the whole toy model ------------------------------------------------------------

def _model_case(toy, dtype, seq=64):
    config, task, ref = toy
    config = with_dtype(config, dtype)
    model = task.make_model(config)
    batch = {"sequences": 2, "seq_len": seq}
    params, aux = task.init(model, KEY, config, batch)
    params = jax.tree.map(lambda p: p + 0.02 * jax.random.uniform(
        jax.random.fold_in(KEY, p.size), p.shape, minval=-1.0, maxval=1.0),
        params)
    aux = dict(aux, bias=normal(30, aux["bias"].shape, 0.05))
    tokens, = task.make_batch(jax.random.fold_in(KEY, 31), config, batch)
    program = jax.jit(jax.value_and_grad(task.loss_fn(model, config),
                                         has_aux=True))
    reference = jax.jit(jax.value_and_grad(
        functools.partial(ref.loss, cfg=config), has_aux=True))
    return config, params, aux, tokens, program, reference


def test_toy_model_loss_and_every_gradient_leaf_in_float32(toy):
    config, params, aux, tokens, program, reference = _model_case(
        toy, "float32", seq=72)
    with HIGHEST():
        (loss, new), grads = program(params, aux, tokens)
        (want, ref_new), ref_grads = reference(params, aux, tokens)
    assert "lm_head" not in params and "conv" in params["block_0"]
    assert "moe" not in params["block_0"] and "moe" in params["block_1"]
    assert not any(k.startswith("shared") for k in params["block_1"]["moe"])
    assert params["block_1"]["moe"]["gate"].shape == (4, 64, 32)
    assert params["block_1"]["moe"]["router"]["kernel"].shape == (64, 8)
    assert abs(float(loss) - float(want)) / float(want) < 1e-5
    np.testing.assert_array_equal(new["load"], ref_new["load"])
    assert new["load"].shape == (4, 8)
    assert int(new["load"][0].sum()) == 2 * 72 * 2      # all eight counted
    np.testing.assert_allclose(new["bias"], ref_new["bias"], atol=1e-7)
    assert float(jnp.abs(new["bias"] - aux["bias"]).max()) == pytest.approx(
        config["router_bias_update_rate"], rel=1e-3)
    errs = jax.tree.map(rel, grads, ref_grads)
    worst = max(jax.tree_util.tree_leaves_with_path(errs),
                key=lambda kv: kv[1])
    assert worst[1] < 1e-4, jax.tree_util.keystr(worst[0])
    assert float(np.median(jax.tree.leaves(errs))) < 1e-5


def _sampled(errs, bound, draws=50):
    """How many of ``draws`` samples of 8 leaves the check would pass."""
    rng = np.random.default_rng(0)
    errs = np.asarray(errs)
    return sum(errs[rng.choice(len(errs), 8, replace=False)].max() <= bound
               for _ in range(draws))


def test_toy_model_in_bfloat16_is_inside_the_twin_bounds(toy):
    config, params, aux, tokens, program, reference = _model_case(
        toy, "bfloat16", seq=256)
    (loss, _), grads = program(params, aux, tokens)
    with HIGHEST():
        (want, _), ref_grads = reference(params, aux, tokens)
    bounds = config["model_check"]
    assert abs(float(loss) - float(want)) / float(want) < bounds["loss_rtol"]
    errs = jax.tree.leaves(jax.tree.map(rel, grads, ref_grads))
    assert max(errs) < bounds["grad_rtol"]
    assert float(np.median(errs)) < bounds["grad_rtol"] / 2


def test_float8_rounded_matrices_fail_the_bounds(toy):
    """The nearest precision below: the float32 reference with nothing but
    its matrices rounded to float8_e4m3fn, against itself unrounded, is
    outside the twin's gradient bound in so many leaves that hardly a sample
    of 8 passes; the cell's own bound was read on the chip
    (``model_check.why`` of ``lfm2-24b-a2b.json``)."""
    config, params, aux, tokens, _, reference = _model_case(
        toy, "float32", seq=256)
    bound = config["model_check"]["grad_rtol"]
    rounded = jax.tree.map(
        lambda p: p.astype(jnp.float8_e4m3fn).astype(p.dtype)
        if p.ndim >= 2 else p, params)
    with HIGHEST():
        (want, _), ref_grads = reference(params, aux, tokens)
        (loss, _), grads = reference(rounded, aux, tokens)
    errs = jax.tree.leaves(jax.tree.map(rel, grads, ref_grads))
    assert float(np.median(errs)) > bound
    assert sum(e > bound for e in errs) > 0.5 * len(errs)
    assert _sampled(errs, bound) <= 1


# --- (h) the older configurations' gradient programs ------------------------------------

# sha256 of ``str(jax.make_jaxpr(value_and_grad(loss)))`` (addresses cut) of
# the tiny twins, taken at the parent of PR 34 (commit 739c0c9) with
# ``benchmark.spec``'s own task and configuration files; the cells' own
# programs were compared at their full shapes the same way (``CHANGES.md``).
# A PR that means to change one of these programs replaces its digest: PR 37
# replaced the three that run the flash kernels (their tile bodies and the
# ``jax.jit`` around each call), PR 41 the four that run ``apply_rope`` (a
# product with a constant half-swap and a written transpose where two
# half-width slices and a concatenate were; ``tests/test_rope.py`` holds the
# values to the bit).  ``tiny-resnet`` is as at 739c0c9.
PARENT_JAXPR = {
    ("tiny-lm", "causal_lm"): "a4de682606a8a8cd",
    ("tiny-olmoe", "moe_causal_lm"): "88f1130aabf52fc8",
    ("tiny-xing", "latent_moe_causal_lm"): "8b4108d454904332",
    ("tiny-resnet", "image_classification"): "cd85047ddb144986",
    ("tiny-lfm2", "hybrid_moe_causal_lm"): "c593f86020260dce",
}


def grad_jaxpr_digest(config_name: str) -> str:
    config = spec.read_json(os.path.join(
        spec.HERE, "selftest", "configs", config_name + ".json"))
    task = spec.load_module(os.path.join("tasks", config["task"] + ".py"))
    model = task.make_model(config)
    batch = ({"images": 2} if config["task"] == "image_classification"
             else {"sequences": 2, "seq_len": 128})

    def shapes(key):
        params, aux = task.init(model, key, config, batch)
        return params, aux, task.make_batch(key, config, batch)
    params, aux, one = jax.eval_shape(shapes, KEY)
    text = str(jax.make_jaxpr(jax.value_and_grad(
        task.loss_fn(model, config), has_aux=True))(params, aux, *one))
    return hashlib.sha256(
        re.sub(r"0x[0-9a-f]+", "0x", text).encode()).hexdigest()[:16]


@pytest.mark.parametrize("config_name,task", sorted(PARENT_JAXPR))
def test_an_older_configuration_traces_to_the_parents_jaxpr(config_name,
                                                            task):
    assert grad_jaxpr_digest(config_name) == PARENT_JAXPR[config_name, task]


# --- (i) the optimizer step on the toy's tree --------------------------------------------

def test_atc_adamw_on_four_devices_is_w_times_the_handwritten_update(devices):
    """``bf.init`` + ``bf.rank_map`` + ``DistributedAdaptThenCombineOptimizer``
    over AdamW on four CPU devices, two steps on the toy's tree (its tied
    embedding, the taps, the held experts) from seeded values that differ by
    rank, against ``W_t @`` the update written out in
    ``reference/optim_adamw.py``: the benchmark's own ``step`` check."""
    from benchmark import checks
    from benchmark.build import Job
    from benchmark.selftest.test_lfm2_cell_cpu import twin_cell
    cell = twin_cell()
    job = Job(cell, spec.task_module(cell), devices[:4], 34)
    assert job.n == 4 and "lm_head" not in job.params
    report = checks.step(job, spec.optimizer_reference(cell),
                         spec.mixing_reference(cell))
    assert report["leaves"] == len(jax.tree.leaves(job.params))
    assert report["worst_share_of_update"] <= checks.STEP_TOL
    loss, grads = job.grad(job.next_batch())
    assert np.asarray(loss).shape == (4,) and np.isfinite(loss).all()
    assert jax.tree.structure(grads) == jax.tree.structure(job.params)
