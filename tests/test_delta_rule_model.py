"""A Ling-3.0-shaped ``TransformerLM`` (Kimi Delta Attention on the chunked
delta rule in five layers of six beside gated latent attention, a held
eighth of sigmoid-routed experts whose choice is limited to a token's best
groups, a shared expert, one leading dense layer) at toy widths on the CPU,
against the plain float32 reference (``benchmark/reference/ling-3.0-flash.py``,
which imports nothing of ``bluefog_tpu`` and runs the rule token by token).
The whole-model cases are ``tests/twins.py``'s on the twin ``tiny-ling3``;
here beside them each mechanism alone: the chunked rule against the rule
token by token (value and every gradient, lengths the chunk does not divide,
decays at the bound), the mixer against the reference's, the grouped choice
of experts against explicit group sums, latent attention with and without
its gate, what raises, and the published widths' 767,006,496 parameters."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from benchmark import spec  # noqa: E402
from bluefog_tpu import models  # noqa: E402
from bluefog_tpu.models import transformer as T  # noqa: E402
from bluefog_tpu.ops import kda  # noqa: E402
from bluefog_tpu.parallel import moe  # noqa: E402
from bluefog_tpu.utils import telemetry  # noqa: E402
import twins  # noqa: E402
from twins import (  # noqa: E402,F401
    HIGHEST, normal, rel, toy,
    test_atc_on_four_devices_is_w_times_the_handwritten_update,
    test_the_shares_add_up_to_the_uncut_layer,
    test_toy_model_in_bfloat16_is_inside_the_twin_bounds,
    test_toy_model_loss_and_every_gradient_leaf_in_float32, with_dtype)

TWINS = ("tiny-ling3",)
CELL = "ling3-kda-s4096-1chip"


# --- the chunked rule ---------------------------------------------------------------

def rule_inputs(seed, b, seq, heads, dk, dv, *, bound=None):
    """Unit keys, scaled unit queries, log decays in (-5, 0) (all at
    ``bound`` where one is given) and ``beta`` in (0, 1)."""
    key = jax.random.PRNGKey(seed)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)  # noqa
    q = unit(normal(key, 0, (b, seq, heads, dk))) * dk ** -0.5
    k = unit(normal(key, 1, (b, seq, heads, dk)))
    v = normal(key, 2, (b, seq, heads, dv))
    g = -5.0 * jax.nn.sigmoid(normal(key, 3, (b, seq, heads, dk), 2.0))
    if bound is not None:
        g = jnp.full_like(g, bound)
    return q, k, v, g, jax.nn.sigmoid(normal(key, 4, (b, seq, heads)))


def both(args, chunk, seed=9):
    """``(o, gradients)`` of the chunked rule and of the rule token by
    token, every gradient under one seeded cotangent."""
    w = normal(jax.random.PRNGKey(seed), 5, args[2].shape)

    def run(f):
        def weighed(*a):
            o = f(*a).astype(jnp.float32)
            return (o * w).sum(), o
        (_, o), grads = jax.jit(jax.value_and_grad(
            weighed, argnums=(0, 1, 2, 3, 4), has_aux=True))(*args)
        return o, grads
    with HIGHEST():
        return (run(lambda *a: kda.kda_chunked(*a, chunk=chunk)),
                run(kda.kda_recurrent))


@pytest.mark.parametrize("seq,chunk", [(128, 64), (100, 64), (37, 16),
                                       (50, 8), (256, 128), (96, 32)])
def test_chunked_rule_against_the_rule_token_by_token(seq, chunk):
    """Value and all five gradients, at lengths that are and are not
    multiples of the chunk, chunks of one sub-block, of less and of two
    levels of halves."""
    args = rule_inputs(0, 2, seq, 3, 16, 8)
    (got, grads), (want, ref_grads) = both(args, chunk)
    assert rel(got, want) < 1e-5
    for name, a, b in zip("q k v g beta".split(), grads, ref_grads):
        assert rel(a, b) < 2e-5, name


@pytest.mark.parametrize("bound", [-5.0, -4.999])
def test_decays_at_the_bound_stay_finite_and_equal(bound):
    """Every step at the bound: over a chunk of 64 the decays multiply to
    ``e^-320``, whose inverse no float32 holds, and the chunked form never
    asks for it: finite, and equal to the rule token by token (the decays'
    own gradient to the size of the largest, since each is a difference of
    sums that nearly cancel)."""
    args = rule_inputs(1, 1, 192, 2, 16, 16, bound=bound)
    assert not np.isfinite(np.exp(np.float32(-64 * bound)))
    (got, grads), (want, ref_grads) = both(args, 64)
    assert all(bool(jnp.isfinite(x).all()) for x in (got,) + grads)
    assert rel(got, want) < 1e-5
    for name, a, b in zip("q k v g beta".split(), grads, ref_grads):
        if name == "g":
            assert float(jnp.abs(a - b).max()) < 1e-4 * float(
                jnp.abs(ref_grads[2]).max())
        else:
            assert rel(a, b) < 2e-5, name


@pytest.mark.parametrize("apart,chunk", [(0.3, 64), (0.1, 64), (0.1, 32)])
def test_keys_that_nearly_repeat_leave_the_inverse_exact(apart, chunk):
    """A trained layer's keys lie close together (``k_i . k_j`` 0.93 and
    0.99 here) with ``beta`` near one and little decay: the system's
    powers reach 1e8 before they cancel, which float32 does not survive,
    so the inverse is made without them.  Value and gradients as for keys
    that lie anywhere."""
    key = jax.random.PRNGKey(11)
    shape = (1, 200, 2, 32)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)  # noqa
    common = normal(key, 10, (1, 1, 2, 32))
    k = unit(common + apart * normal(key, 1, shape))
    q = unit(common + apart * normal(key, 0, shape)) * 32 ** -0.5
    g = -0.05 * jax.nn.sigmoid(normal(key, 3, shape))
    beta = jax.nn.sigmoid(2.5 + normal(key, 4, shape[:3]))
    (got, grads), (want, ref_grads) = both(
        (q, k, normal(key, 2, shape), g, beta), chunk)
    assert rel(got, want) < 1e-5
    for name, a, b in zip("q k v g beta".split(), grads, ref_grads):
        assert rel(a, b) < 2e-5, name


def test_the_rule_is_causal_and_corrects_what_the_state_predicts():
    args = rule_inputs(2, 1, 96, 2, 16, 8)
    with HIGHEST():
        whole = kda.kda_chunked(*args, chunk=32)
        head = kda.kda_chunked(*(x[:, :50] for x in args), chunk=32)
        # beta = 0 writes nothing: the output is zero from a zero state
        idle = kda.kda_chunked(*args[:4], jnp.zeros_like(args[4]), chunk=32)
        # a key written twice with beta = 1 and no decay: the second write
        # finds its value already predicted and corrects by nothing
        k = jnp.zeros((1, 2, 1, 4)).at[..., 0].set(1.0)
        v = jnp.ones((1, 2, 1, 3))
        twice = kda.kda_chunked(k, k, v, jnp.zeros_like(k),
                                jnp.ones((1, 2, 1)), chunk=16)
    np.testing.assert_allclose(whole[:, :50], head, rtol=1e-5, atol=1e-6)
    assert float(jnp.abs(idle).max()) == 0.0
    np.testing.assert_allclose(twice, v, rtol=1e-6)


def test_the_rule_in_bfloat16_keeps_its_decays_and_states_in_float32():
    """bfloat16 operands move the result by a bfloat16 rounding or two, not
    by what a bfloat16 sum of 64 log decays or a bfloat16 state would."""
    q, k, v, g, beta = rule_inputs(3, 1, 512, 2, 64, 64)
    low = lambda x: x.astype(jnp.bfloat16)  # noqa: E731
    want = kda.kda_recurrent(low(q), low(k), low(v), g, beta)
    got = kda.kda_chunked(low(q), low(k), low(v), g, beta, chunk=64)
    assert got.dtype == jnp.bfloat16
    assert rel(got, want) < 0.01
    # the sums of a chunk's log decays rounded to bfloat16: ten times that
    G = jnp.cumsum(g.reshape(1, 8, 64, 2, 64), axis=2)
    coarse = jnp.diff(low(G).astype(jnp.float32), axis=2, prepend=0.0)
    assert rel(kda.kda_chunked(low(q), low(k), low(v), coarse.reshape(
        g.shape), beta, chunk=64), want) > 5 * rel(got, want)


def test_the_rule_counts_its_chunks_and_checks_its_shapes():
    args = rule_inputs(4, 2, 100, 2, 8, 8)
    before = telemetry.snapshot().get("bf_kda_chunks_total", 0.0)
    kda.kda_chunked(*args, chunk=32)
    assert telemetry.snapshot()["bf_kda_chunks_total"] - before == 2 * 4
    for chunk in (0, 24, 48, 96):
        with pytest.raises(ValueError, match="chunk"):
            kda.kda_chunked(*args, chunk=chunk)
    with pytest.raises(ValueError, match="need q, k and g"):
        kda.kda_chunked(args[0], args[1][:, :50], *args[2:])
    with pytest.raises(ValueError, match="beta"):
        kda.kda_recurrent(*args[:4], args[4][..., None])


STAGED = 'bf_kernel_stagings_total{kernel="bf_kda_%s"}'


@pytest.mark.parametrize("b,seq,step", [(1, 230, 8), (2, 230, 8),
                                        (2, 230, 1), (1, 384, 4)])
def test_the_rule_kernels(monkeypatch, b, seq, step):
    """The published head of 128 in bfloat16, chunks of 64 with and without
    a tail, one and two sequences, a grid step of all of a sequence's
    chunks, of some and of one: value and all five gradients against the
    rule token by token in float32 on the same operands, and each kernel
    staged once a shape."""
    monkeypatch.setattr(kda, "_STEP_CHUNKS", step)
    n = -(-seq // 64)
    assert kda._step_chunks(n) == {8: 4, 1: 1, 4: 3}[step]
    q, k, v, g, beta = rule_inputs(6, b, seq, 2, 128, 128)
    args = tuple(x.astype(jnp.bfloat16) for x in (q, k, v)) + (g, beta)
    count = lambda: [telemetry.snapshot().get(STAGED % kind, 0.0)  # noqa
                     for kind in ("fwd", "bwd")]
    before = count()
    (got, grads), (want, ref_grads) = both(args, 64)
    both(args, 64)
    assert [x - y for x, y in zip(count(), before)] == [1, 1]
    assert got.shape == want.shape
    assert rel(got, want) < 0.01
    for name, a, r in zip("q k v g beta".split(), grads, ref_grads):
        assert a.dtype == r.dtype and a.shape == r.shape, name
        assert rel(a, r) < 0.015, name


def test_the_backward_keeps_the_inputs_and_the_entering_states_alone():
    """The residuals of the rule's ``custom_vjp``: ``q``, ``k``, ``v``,
    ``g``, ``beta`` as they came and a ``(V, K)`` float32 state a chunk and
    head; no chunk matrix, nothing of ``(S, S)``."""
    b, seq, H, D, C = 2, 256, 3, 16, 64
    n = seq // C
    wide = jax.ShapeDtypeStruct((b, seq, H * D), jnp.bfloat16)
    operands = (wide, wide, wide,
                jax.ShapeDtypeStruct(wide.shape, jnp.float32),
                jax.ShapeDtypeStruct((b, H, n, 1, C), jnp.float32))
    o, res = jax.eval_shape(lambda *a: kda._rule_fwd(
        *a, (n, kda._step_chunks(n), C, D, D), True, frozenset()), *operands)
    assert (o.shape, o.dtype) == (wide.shape, wide.dtype)
    assert [(r.shape, r.dtype) for r in res[:5]] == [
        (x.shape, x.dtype) for x in operands]
    assert len(res) == 6
    assert (res[5].shape, res[5].dtype) == ((b, H, n, D, D), jnp.float32)
    # and the plain call writes no states at all
    assert kda._fwd_call(*(jnp.zeros(x.shape, x.dtype) for x in operands),
                         dims=(n, 1, C, D, D), save=False, interpret=True,
                         vma=frozenset())[1] is None


def test_shapes_a_tpu_cannot_tile_raise_and_the_interpreter_takes_them(
        monkeypatch):
    """Heads of 16 and 8 and chunks of 8 run in the interpreter; where the
    kernels would be compiled, the door names the shape and stages
    nothing."""
    narrow = rule_inputs(7, 1, 40, 2, 16, 8)
    short = rule_inputs(7, 1, 40, 1, 128, 128)
    assert kda.kda_chunked(*narrow, chunk=16).shape == (1, 40, 2, 8)
    assert kda.kda_chunked(*short, chunk=8).shape == (1, 40, 1, 128)
    monkeypatch.setattr(kda, "platform_in_use", lambda *_: "tpu")
    before = telemetry.snapshot().get(STAGED % "fwd", 0.0)
    with pytest.raises(ValueError, match="a head of 16 keys and 8 values in "
                       "chunks of 16 cannot be tiled on a TPU"):
        kda.kda_chunked(*narrow, chunk=16)
    with pytest.raises(ValueError, match="a head of 128 keys and 128 values "
                       "in chunks of 8 cannot be tiled"):
        kda.kda_chunked(*short, chunk=8)
    assert telemetry.snapshot().get(STAGED % "fwd", 0.0) == before


# --- the mixer ----------------------------------------------------------------------------

def toy_cfg(toy, dtype="float32", **over):
    config, task, _ = toy
    return task.make_model(with_dtype(dict(config, **over), dtype)).cfg


@pytest.mark.parametrize("seq", [96, 75])
def test_kimi_delta_mixer_against_the_reference(toy, seq):
    config, _, ref = toy
    mixer = T.KimiDeltaMixer(toy_cfg(toy))
    key = jax.random.PRNGKey(6)
    y = normal(key, 0, (2, seq, config["hidden_size"]))
    params = jax.tree.map(
        lambda p: p + 0.05 * normal(key, p.size, p.shape),
        mixer.init(key, y)["params"])
    w = normal(key, 1, y.shape)
    with HIGHEST():
        got, grads = jax.jit(jax.value_and_grad(lambda p, y: (
            mixer.apply({"params": p}, y) * w).sum(), argnums=(0, 1)))(
                params, y)
        want, ref_grads = jax.jit(jax.value_and_grad(lambda p, y: (
            ref._kda(y, p, config) * w).sum(), argnums=(0, 1)))(params, y)
        np.testing.assert_allclose(
            mixer.apply({"params": params}, y), ref._kda(y, params, config),
            rtol=2e-5, atol=2e-5)
    assert float(got) == pytest.approx(float(want), rel=1e-4)
    errs = jax.tree.map(rel, grads, ref_grads)
    assert max(jax.tree.leaves(errs)) < 1e-4, errs


def test_kimi_delta_mixer_starts_where_the_published_kernels_start(toy):
    """``exp(A_log)`` uniform in [1, 16], ``softplus(dt_bias)`` log-uniform
    in Mamba-2's (0.001, 0.1), the norm's scale one; every log decay of a
    fresh layer lies inside (-5, 0)."""
    cfg = toy_cfg(toy)
    y = normal(jax.random.PRNGKey(7), 0, (1, 64, cfg.embed_dim))
    p = T.KimiDeltaMixer(cfg).init(jax.random.PRNGKey(7), y)["params"]
    assert set(p) == {"qkv", "conv_w", "f", "dt_bias", "A_log", "beta",
                      "gate", "norm_scale", "out"}
    a = np.exp(np.asarray(p["A_log"]))
    assert a.min() >= 1.0 and a.max() <= 16.0
    step = np.log1p(np.exp(np.asarray(p["dt_bias"])))
    assert step.min() >= 1e-4 and step.max() <= 0.1 + 1e-6
    np.testing.assert_array_equal(p["norm_scale"], 1.0)
    g = cfg.kda_lower_bound * jax.nn.sigmoid(
        jnp.exp(p["A_log"])[:, None] * (y @ p["f"]["kernel"] + p[
            "dt_bias"]).reshape(1, 64, cfg.num_heads, -1))
    assert -5.0 < float(g.min()) and float(g.max()) <= 0.0


@pytest.mark.parametrize("why", ["segment_ids", "cache"])
def test_a_kda_layer_with_documents_or_a_cache_raises(why):
    cfg = models.TransformerConfig(
        vocab_size=64, num_layers=2, num_heads=2, embed_dim=32,
        pos_encoding="rope", mlp="swiglu", conv_kernel=4, kda_chunk=16,
        layer_types=("full_attention", "kda"))
    model = models.TransformerLM(cfg)
    tokens = jnp.zeros((1, 32), jnp.int32)
    variables = model.init(jax.random.PRNGKey(0), tokens)
    model.apply(variables, tokens)          # alone it runs
    if why == "segment_ids":
        from bluefog_tpu.data import document_layout
        ids, at = document_layout([20, 12])
        with pytest.raises(NotImplementedError,
                           match="'kda' block.*delta rule's state and taps"):
            model.apply(variables, tokens, positions=at[None],
                        segment_ids=ids[None])
        return
    with pytest.raises(NotImplementedError,
                       match="Kimi Delta Attention.*hands on no state"):
        model.apply(variables, tokens[:, :1], positions=jnp.zeros((1, 1), int),
                    cache=T.init_cache(cfg, 1, 8))
    block = T.Block(cfg, T.local_attention, 1)
    x = jnp.zeros((1, 1, 32))
    with pytest.raises(NotImplementedError, match="Kimi Delta Attention"):
        block.init(jax.random.PRNGKey(0), x, jnp.zeros((1, 1), int),
                   T.init_cache(cfg, 1, 8)[1])


def test_layer_kinds_and_config_errors_name_the_new_kind():
    assert "kda" in T.MIXERS and "kda" in T.LAYER_KINDS
    with pytest.raises(ValueError, match="'kda'.*'ffn'"):
        models.TransformerConfig(num_layers=1, layer_types=("delta",))
    with pytest.raises(ValueError, match="kda_lower_bound"):
        models.TransformerConfig(num_layers=1, layer_types=("kda",),
                                 kda_lower_bound=-6.0)
    with pytest.raises(ValueError, match="router_groups"):
        models.TransformerConfig(num_experts=16, mlp="swiglu",
                                 router_groups=4, router_groups_kept=2)
    with pytest.raises(ValueError, match="router_groups"):
        models.TransformerConfig(num_experts=16, mlp="swiglu",
                                 num_experts_per_tok=8,
                                 router_scoring="sigmoid", router_groups=8,
                                 router_groups_kept=2)
    cfg = models.TransformerConfig(
        vocab_size=64, num_layers=3, num_heads=2, embed_dim=32,
        pos_encoding="rope", layer_types=("kda", "kda", "full_attention"))
    models.TransformerLM(cfg).init(jax.random.PRNGKey(0),
                                   jnp.zeros((1, 16), jnp.int32))
    gauges = telemetry.snapshot()
    assert gauges['bf_model_layers_total{mixer="kda"}'] == 2
    assert gauges['bf_model_layers_total{mixer="full_attention"}'] == 1


# --- the grouped choice of experts ----------------------------------------------

def explicit_choice(scores, bias, k, groups, stay):
    """The choice written out with numpy: a group's score is the sum of its
    two largest score + bias, the ``stay`` best groups keep their experts,
    the ``k`` largest of those are chosen."""
    choice = np.asarray(scores + bias, np.float64)
    tokens, experts = choice.shape
    size, chosen = experts // groups, []
    for t in range(tokens):
        by_group = choice[t].reshape(groups, size)
        score = np.sort(by_group, axis=1)[:, -2:].sum(axis=1)
        kept = np.argsort(-score, kind="stable")[:stay]
        open_ = np.full(experts, -np.inf)
        for grp in kept:
            open_[grp * size:(grp + 1) * size] = choice[t, grp * size:
                                                        (grp + 1) * size]
        chosen.append(np.argsort(-open_, kind="stable")[:k])
    return np.array(chosen)


def test_route_topk_with_groups_against_explicit_group_sums():
    key = jax.random.PRNGKey(8)
    logits = normal(key, 0, (200, 64))
    bias = normal(key, 1, (64,), 0.2)
    plan = moe.route_topk(logits, 8, renormalize=True, scoring="sigmoid",
                          bias=bias, scale=2.5, n_group=8, topk_group=4)
    scores = jax.nn.sigmoid(logits)
    want = explicit_choice(scores, bias, 8, 8, 4)
    np.testing.assert_array_equal(np.sort(plan.experts, axis=1),
                                  np.sort(want, axis=1))
    # at most four groups a token, the weights the scores alone
    assert (np.asarray([len(set(row // 8)) for row in np.asarray(
        plan.experts)]) <= 4).all()
    picked = jnp.take_along_axis(scores, plan.experts, axis=1)
    np.testing.assert_allclose(
        plan.weights, picked / (picked.sum(1, keepdims=True) + 1e-20) * 2.5,
        rtol=1e-6)
    assert int(plan.load.sum()) == 200 * 8
    free = moe.route_topk(logits, 8, renormalize=True, scoring="sigmoid",
                          bias=bias, scale=2.5)
    assert (np.sort(free.experts, 1) != np.sort(plan.experts, 1)).any()


def test_a_token_whose_best_eight_lie_in_five_groups_keeps_to_four():
    """Five groups hold the eight largest scores; the fifth group's one
    large expert loses its group and cannot be chosen, whatever its score."""
    pairs = (0, 1, 8, 9, 16, 17, 24, 25)          # two each in groups 0..3
    logits = jnp.full((1, 64), -4.0)
    for expert in pairs:
        logits = logits.at[0, expert].set(3.0)
    logits = logits.at[0, 32].set(50.0)           # group 4's only one
    free = np.asarray(moe.route_topk(logits, 8, scoring="sigmoid").experts)
    assert 32 in free and len(set(free[0] // 8)) == 5
    # a group scores its two largest: 0.953 + 0.953 in groups 0 to 3, 1.0 +
    # 0.018 in group 4, which goes
    chosen = np.asarray(moe.route_topk(
        logits, 8, scoring="sigmoid", n_group=8, topk_group=4).experts)[0]
    assert sorted(chosen) == list(pairs)
    # with five groups kept it stays, and a bias moves the choice alone
    five = moe.route_topk(logits, 8, scoring="sigmoid", n_group=8,
                          topk_group=5)
    assert 32 in np.asarray(five.experts)
    bias = jnp.zeros((64,)).at[33].set(1.0)       # 0.018 + 1: group 4 stays
    biased = moe.route_topk(logits, 8, scoring="sigmoid", bias=bias,
                            renormalize=True, n_group=8, topk_group=4)
    assert {32, 33} <= set(np.asarray(biased.experts)[0])
    assert float(biased.weights.sum()) == pytest.approx(1.0, rel=1e-6)


def test_one_group_stages_the_program_it_staged_before():
    logits, bias = jnp.zeros((16, 32)), jnp.zeros((32,))

    def text(**kw):
        return str(jax.make_jaxpr(lambda l, b: moe.route_topk(
            l, 4, renormalize=True, scoring="sigmoid", bias=b, **kw))(
                logits, bias))
    assert text() == text(n_group=1, topk_group=1)
    assert text() != text(n_group=4, topk_group=2)
    before = telemetry.snapshot().get(
        'bf_moe_route_groups_total{kept="2"}', 0.0)
    text(n_group=4, topk_group=2)
    assert telemetry.snapshot()[
        'bf_moe_route_groups_total{kept="2"}'] - before == 4
    for bad in (dict(scoring="softmax", n_group=4, topk_group=2),
                dict(scoring="sigmoid", n_group=5, topk_group=2),
                dict(scoring="sigmoid", n_group=4, topk_group=5),
                dict(scoring="sigmoid", n_group=16, topk_group=1),
                dict(scoring="sigmoid", n_group=32, topk_group=8)):
        with pytest.raises(ValueError, match="n_group"):
            moe.route_topk(logits, 4, **bad)


def test_the_held_share_routes_by_groups_before_its_window():
    """``dropless_moe(held=, n_group=)``: the load counts the grouped choice
    over all experts, and the held experts' part is the reference's."""
    key = jax.random.PRNGKey(9)
    d, f, E = 16, 8, 32
    x = normal(key, 0, (64, d))
    logits = normal(key, 1, (64, E))
    gate, up = normal(key, 2, (4, d, f), 0.3), normal(key, 3, (4, d, f), 0.3)
    down = normal(key, 4, (4, f, d), 0.3)
    kw = dict(k=4, renormalize=True, scoring="sigmoid", n_group=4,
              topk_group=2)
    with HIGHEST():
        y, plan = moe.dropless_moe(x, logits, gate, up, down, held=(8, 4),
                                   **kw)
    route = moe.route_topk(logits, 4, renormalize=True, scoring="sigmoid",
                           n_group=4, topk_group=2)
    np.testing.assert_array_equal(plan.load, route.load)
    want = jnp.zeros_like(x)
    for j in range(4):
        w = ((route.experts == 8 + j) * route.weights).sum(axis=1)
        want = want + w[:, None] * (
            (jax.nn.silu(x @ gate[j]) * (x @ up[j])) @ down[j])
    np.testing.assert_allclose(y, want, rtol=2e-5, atol=2e-5)


# --- gated latent attention ----------------------------------------------------------

@pytest.mark.parametrize("gated", [True, False])
def test_latent_attention_with_and_without_its_gate(toy, gated):
    config, _, ref = toy
    cfg = toy_cfg(toy)
    if not gated:
        cfg.attn_gate = None
    layer = T.LatentAttention(cfg, T.local_attention)
    key = jax.random.PRNGKey(10)
    y = normal(key, 0, (2, 40, config["hidden_size"]))
    at = jnp.broadcast_to(jnp.arange(40), (2, 40))
    params = jax.tree.map(
        lambda p: p + 0.05 * normal(key, p.size, p.shape),
        layer.init(key, y, at)["params"])
    assert ("attn_gate" in params) == gated
    with HIGHEST():
        got = layer.apply({"params": params}, y, at)
        if gated:
            want = ref._attention(y, params, config)
            np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)
        else:
            # the gate at zero halves every head: without it, twice that
            zero = dict(params, attn_gate={"kernel": jnp.zeros((
                config["hidden_size"], config["num_attention_heads"]))})
            np.testing.assert_allclose(
                got, 2 * ref._attention(y, zero, config), rtol=2e-5,
                atol=2e-5)


# --- the cell ---------------------------------------------------------------------------------

def test_the_twin_holds_an_eighth_in_eight_shares():
    """``test_the_shares_add_up_to_the_uncut_layer`` cuts the router's
    width into shares of the experts the twin holds: eight of them, a whole
    number of shares a group."""
    config, _, _ = twins.load("tiny-ling3")
    assert config["router_width"] == 8 * config["num_experts"] == 16
    assert (config["n_group"], config["topk_group"]) == (4, 2)
    cell = spec.load_cell(CELL).config
    assert cell["router_width"] == 64 * cell["num_experts"] == 512
    for c in (config, cell):
        assert c["num_shared_experts"] == 1 and c["q_lora_rank"] is None
        assert c["first_k_dense_replace"] == 1 and c["layer_group_size"] == 6
        assert spec.load_module("tasks/" + c["task"] + ".py").make_model(
            c).cfg.layer_types == ("kda",) * 5 + ("full_attention",)


def test_the_published_widths_count_767006496_parameters():
    """``jax.eval_shape`` of the cell's own model: the issue's arithmetic,
    part by part."""
    cell = spec.load_cell(CELL)
    task = spec.task_module(cell)
    model = task.make_model(cell.config)
    params, aux = jax.eval_shape(
        lambda key: task.init(model, key, cell.config,
                              cell.traffic["batch"]), jax.random.PRNGKey(0))
    count = lambda tree: sum(int(np.prod(x.shape))  # noqa: E731
                             for x in jax.tree.leaves(tree))
    assert count(params["block_0"]["kda"]) == 63_049_888
    assert count(params["block_5"]["mla"]) == 31_965_696
    assert count(params["block_0"]) == 110_240_928      # dense, KDA
    assert count(params["block_1"]) == 117_449_888      # experts, KDA
    assert count(params["block_5"]) == 86_365_696       # experts, MLA
    assert count(params["block_1"]["moe"]["gate"]) == 8 * 2560 * 768
    assert count(params["block_1"]["moe"]["router"]) == 2560 * 512
    assert count(params["wte"]) + count(params["lm_head"]) == 100_597_760
    assert count(params) == 767_006_496
    assert all(x.dtype == jnp.float32 for x in jax.tree.leaves(params))
    assert aux["bias"].shape == aux["load"].shape == (5, 512)
    assert task.check_batch(cell.traffic["batch"]) == {
        "sequences": 1, "seq_len": 1024}
    assert 1024 // cell.config["model"]["args"]["kda_chunk"] >= 16
