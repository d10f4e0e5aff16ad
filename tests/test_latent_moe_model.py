"""The mechanisms ``xing4.0-29b-a4b`` forced, at toy widths on the CPU, each
against the configuration's plain reference
(``benchmark/reference/xing4.0-29b-a4b.py``, which imports nothing of
``bluefog_tpu``) or a hand-written line of it: flash attention with a value
dim and a scale of its own, the latent-attention sub-layer with YaRN
frequencies, the sigmoid router with its bias, the held share of the experts
(the shares add up), the Sinkhorn-normalised residual maps, and the toy
model on a window; the whole toy model's other cases are those of
``tests/twins.py``, run from ``tests/test_twins.py``.  float32 to 1e-5."""

import copy
import functools
import math
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
from bluefog_tpu.ops.flash_attention import (  # noqa: E402
    flash_attention, flash_attention_impl)
from bluefog_tpu.parallel import moe  # noqa: E402
import twins  # noqa: E402
from twins import HIGHEST, rel, toy, with_dtype  # noqa: E402,F401

# the twin of ``toy``; its whole-model cases run from tests/test_twins.py
TWINS = ("tiny-xing",)
KEY = jax.random.PRNGKey(31)
normal = functools.partial(twins.normal, KEY)


# --- (a) flash attention: value dim and scale -------------------------------

def test_flash_value_dim_and_scale_against_local_attention():
    B, S, H, D, Dv, scale = 2, 64, 2, 24, 16, 0.37
    q, k, v = (normal(i, (B, S, H, d)) for i, d in enumerate((D, D, Dv)))
    flash = lambda q, k, v: (flash_attention(  # noqa: E731
        q, k, v, block_q=16, block_k=32, interpret=True,
        scale=scale) ** 2).sum()
    plain = lambda q, k, v: (T.local_attention(  # noqa: E731
        q, k, v, scale=scale) ** 2).sum()
    with HIGHEST():
        got = jax.value_and_grad(flash, (0, 1, 2))(q, k, v)
        want = jax.value_and_grad(plain, (0, 1, 2))(q, k, v)
    assert flash_attention(q, k, v, interpret=True).shape == (B, S, H, Dv)
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert g.shape == w.shape
        np.testing.assert_allclose(g, w, rtol=1e-5, atol=1e-5)


def test_flash_without_a_scale_is_the_program_it_was():
    """``scale=None`` with ``Dv == D`` traces to the program that
    ``1 / sqrt(D)`` written out gives: no new operation on the old path."""
    q = jax.ShapeDtypeStruct((1, 128, 2, 64), jnp.bfloat16)

    def text(**kw):
        f = lambda q, k, v: flash_attention(  # noqa: E731
            q, k, v, interpret=True, **kw).astype(jnp.float32).sum()
        return str(jax.make_jaxpr(jax.grad(f, (0, 1, 2)))(q, q, q))
    assert text() == text(scale=1.0 / np.sqrt(64))
    assert text() != text(scale=0.2)


def test_flash_backward_takes_fewer_keys_a_tile_for_wide_heads():
    """Heads of 192 at 1024 x 1024 tiles overflow the v5e's scoped VMEM in
    the dq kernel; the backward then takes 512 keys a tile (the grid shows
    it), and heads of 128 keep theirs."""
    def grids(D):
        q = jax.ShapeDtypeStruct((1, 2048, 1, D), jnp.bfloat16)
        f = lambda q, k, v: flash_attention(  # noqa: E731
            q, k, v, interpret=True).astype(jnp.float32).sum()
        text = str(jax.make_jaxpr(jax.grad(f, (0, 1, 2)))(q, q, q))
        return text.count("grid=(1, 2, 4)") + text.count("grid=(1, 4, 2)")
    assert grids(192) == 2 and grids(128) == 0


# --- (b) latent attention ----------------------------------------------------

YARN = {"type": "yarn", "factor": 64, "beta_fast": 32, "beta_slow": 1,
        "mscale": 1, "mscale_all_dim": 1,
        "original_max_position_embeddings": 4096}


def test_yarn_frequencies_by_hand():
    """64 rotary dims, base 1e4, 4096 original positions: the pair that
    makes 32 turns is 10.47 -> 10 and below are left alone, the pair that
    makes one turn is 22.5 -> 23 and above are divided by 64, a linear blend
    between."""
    freq = T.yarn_frequencies(64, 10000.0, YARN)
    plain = 10000.0 ** (-np.arange(32) / 32)
    assert freq.shape == (32,) and freq.dtype == np.float32
    np.testing.assert_allclose(freq[:11], plain[:11], rtol=1e-6)
    np.testing.assert_allclose(freq[23:], plain[23:] / 64, rtol=1e-6)
    for i in (11, 16, 22):
        ramp = (i - 10) / 13
        np.testing.assert_allclose(
            freq[i], plain[i] * (1 - ramp) + plain[i] / 64 * ramp, rtol=1e-6)
    assert T.yarn_mscale(64, 1) == pytest.approx(0.1 * math.log(64) + 1)
    assert T.yarn_mscale(1, 1) == 1.0


def test_latent_attention_against_the_reference(toy):
    config, task, ref = toy
    cfg = task.make_model(with_dtype(config, "float32")).cfg
    layer = T.LatentAttention(cfg, T.local_attention)
    y = normal(3, (2, 32, config["hidden_size"]))
    pos = jnp.broadcast_to(jnp.arange(32)[None], (2, 32))
    params = layer.init(KEY, y, pos)["params"]
    assert params["q_b"]["kernel"].shape == (24, 4 * (16 + 8))
    assert params["kv_a"]["kernel"].shape == (64, 16 + 8)
    assert params["kv_b"]["kernel"].shape == (16, 4 * (16 + 16))
    assert params["proj"]["kernel"].shape == (4 * 16, 64)
    with HIGHEST():
        got = layer.apply({"params": params}, y, pos)
        want = ref._attention(y, params, config)
        flash = T.LatentAttention(cfg, flash_attention_impl()).apply(
            {"params": params}, y, pos)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(flash, want, rtol=1e-5, atol=1e-5)


def test_latent_attention_config_is_checked():
    base = dict(pos_encoding="rope", kv_lora_rank=16, qk_nope_head_dim=8,
                qk_rope_head_dim=4, v_head_dim=8)
    models.TransformerConfig(**base)
    with pytest.raises(ValueError, match="together"):
        models.TransformerConfig(pos_encoding="rope", kv_lora_rank=16)
    with pytest.raises(ValueError, match="rope"):
        models.TransformerConfig(**dict(base, pos_encoding="learned"))
    # since PR 40 the plain attention branch reads YaRN's dict too
    # (``transformer.rope_scheme``); another type is still refused
    models.TransformerConfig(pos_encoding="rope", rope_scaling=YARN)
    with pytest.raises(ValueError, match="only 'yarn'"):
        models.TransformerConfig(pos_encoding="rope",
                                 rope_scaling=dict(YARN, type="linear"))
    with pytest.raises(ValueError, match="experts_held"):
        models.TransformerConfig(num_experts=8, mlp="swiglu",
                                 experts_held=6, experts_first=4)


# --- (c) the router ------------------------------------------------------------

def test_sigmoid_router_bias_moves_the_choice_and_not_the_weights():
    logits = normal(5, (64, 8))
    plain = moe.route_topk(logits, 2, renormalize=True, scoring="sigmoid",
                           scale=2.0)
    bias = jnp.zeros(8).at[3].set(10.0)
    biased = moe.route_topk(logits, 2, renormalize=True, scoring="sigmoid",
                            bias=bias, scale=2.0)
    assert bool((biased.experts == 3).any(axis=1).all())
    assert not bool((plain.experts == 3).any(axis=1).all())
    scores = jax.nn.sigmoid(logits)
    for plan in (plain, biased):
        np.testing.assert_allclose(plan.weights.sum(axis=1), 2.0, rtol=1e-6)
        chosen = jnp.take_along_axis(scores, plan.experts, axis=1)
        np.testing.assert_allclose(
            plan.weights, 2.0 * chosen / chosen.sum(axis=1, keepdims=True),
            rtol=1e-6)
        assert int(plan.load.sum()) == 64 * 2
    with pytest.raises(ValueError, match="sigmoid"):
        moe.route_topk(logits, 2, bias=bias)


def test_no_gradient_reaches_the_bias_and_the_rule_moves_it():
    logits, bias = normal(6, (32, 8)), normal(7, (8,), 0.1)

    def total(logits, bias):
        return moe.route_topk(logits, 2, renormalize=True,
                              scoring="sigmoid", bias=bias).weights.sum()
    d_logits, d_bias = jax.grad(
        lambda l, b: (moe.route_topk(l, 2, scoring="sigmoid", bias=b)
                      .weights ** 2).sum(), (0, 1))(logits, bias)
    assert float(jnp.abs(d_logits).max()) > 0
    assert float(jnp.abs(d_bias).max()) == 0.0
    load = jnp.array([[9, 1, 4, 4, 0, 6, 4, 4], [4, 4, 4, 4, 4, 4, 4, 4]])
    moved = moe.update_router_bias(jnp.zeros((2, 8)), load, 1e-3)
    np.testing.assert_allclose(
        moved[0], 1e-3 * np.array([-1, 1, 0, 0, 1, -1, 0, 0]), atol=1e-9)
    np.testing.assert_allclose(moved[1], 0.0, atol=1e-9)


# --- (d), (e) the held share ---------------------------------------------------

def _layer(E=8, d=16, f=8, tokens=64):
    x, logits = normal(10, (tokens, d)), normal(11, (tokens, E))
    gate, up = normal(12, (E, d, f), 0.3), normal(13, (E, d, f), 0.3)
    return x, logits, gate, up, normal(14, (E, f, d), 0.3)


# (the configuration whose reference is asked, its keys for the experts held
# and their width, its renormalisation's epsilon, whether it has a shared
# expert)
SHARED_CASES = {
    "xing4.0-29b-a4b": dict(
        held_key="n_routed_experts", scale=2.0, eps=1e-20, shared=True,
        extra={}),
    "lfm2-24b-a2b": dict(
        held_key="num_experts", scale=1.0, eps=1e-6, shared=False,
        extra={"router_width": 8, "num_experts_per_tok": 2,
               "moe_intermediate_size": 32, "norm_topk_prob": True,
               "use_expert_bias": True, "router_renorm_eps": 1e-6,
               "routed_scaling_factor": 1.0}),
}


@pytest.mark.parametrize("name", sorted(SHARED_CASES))
def test_the_shares_add_up_to_the_uncut_layer_of_the_reference(toy, name):
    """Eight experts in four shares of two, a shared expert (where the
    configuration has one) counted once: the sum is what the configuration's
    reference gives when it is told that it holds all eight."""
    config, _, _ = toy
    case = SHARED_CASES[name]
    ref = spec.load_module(f"reference/{name}.py")
    held = case["held_key"]
    x, logits, gate, up, down = _layer(d=64, f=32)
    router = normal(15, (64, 8), 0.2)
    shared = {f"shared_{n}": {"kernel": normal(16 + i, s, 0.2)} for i, (n, s)
              in enumerate((("gate", (64, 32)), ("up", (64, 32)),
                            ("down", (32, 64))))} if case["shared"] else {}
    bias = normal(19, (8,), 0.1)
    whole = dict(config, **case["extra"], **{held: 8}, experts_first=0)
    params = dict(shared, router={"kernel": router}, gate=gate, up=up,
                  down=down)
    kw = dict(k=2, renormalize=True, scoring="sigmoid", bias=bias,
              scale=case["scale"], renorm_eps=case["eps"])
    with HIGHEST():
        want, load, _ = ref._experts(x[None], params, bias, whole)
        logits = x @ router
        parts = [moe.dropless_moe(x, logits, gate[i:i + 2], up[i:i + 2],
                                  down[i:i + 2], held=(i, 2), **kw)
                 for i in range(0, 8, 2)]
        once = ref._swiglu(x, *(shared[f"shared_{n}"]["kernel"]
                                for n in ("gate", "up", "down"))) \
            if case["shared"] else 0.0
        # one share alone is what the reference gives for that share
        share = dict(params, gate=gate[2:4], up=up[2:4], down=down[2:4])
        alone, _, _ = ref._experts(
            x[None], share, bias, dict(whole, **{held: 2}, experts_first=2))
    got = sum(y for y, _ in parts) + once
    np.testing.assert_allclose(got, want[0], rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(parts[1][0] + once, alone[0], rtol=1e-5,
                               atol=1e-5)
    for _, plan in parts:       # every share counts all eight experts
        np.testing.assert_array_equal(plan.load, load)


def test_held_none_is_the_layer_it_was():
    """``held=None`` is the program of before (the same jaxpr as a call
    that names no new argument), and holding every expert gives its result
    bit for bit, forward and backward."""
    x, logits, gate, up, down = _layer()

    def out(*a, **kw):
        return moe.dropless_moe(*a, k=2, **kw)[0]
    assert str(jax.make_jaxpr(out)(x, logits, gate, up, down)) == str(
        jax.make_jaxpr(functools.partial(
            out, held=None, scoring="softmax", bias=None, scale=1.0))(
                x, logits, gate, up, down))
    grad = lambda **kw: jax.value_and_grad(  # noqa: E731
        lambda *a: (out(*a, **kw) ** 2).sum(), (0, 1, 2, 3, 4))(
            x, logits, gate, up, down)
    for a, b in zip(jax.tree.leaves(grad()),
                    jax.tree.leaves(grad(held=(0, 8)))):
        np.testing.assert_array_equal(a, b)
    with pytest.raises(ValueError, match="held"):
        out(x, logits, gate, up, down, held=(4, 8))


def test_observe_load_counts_the_held_share():
    from bluefog_tpu.utils import telemetry
    before = telemetry.snapshot().get("bf_moe_held_assignments_total", 0.0)
    moe.observe_load(np.full((4, 64), 256), held=(0, 8))
    snap = telemetry.snapshot()
    assert snap["bf_moe_held_assignments_total"] - before == 4 * 8 * 256
    assert snap["bf_moe_held_share"] == pytest.approx(0.125)


def test_observe_load_counts_the_window_and_its_overflows():
    """An even load fills half the window; one layer of four over its
    window is one overflow; the rule is the layer's own."""
    from bluefog_tpu.utils import telemetry
    name = "bf_moe_held_window_overflow_total"
    assert moe.held_window(16384, 8, 64) == 4096
    assert moe.held_window(65536, 8, 64) == 16384
    assert moe.held_window(2048, 2, 16) == 512
    assert moe.held_window(2000, 3, 64) == 256      # whole tiles
    assert moe.held_window(96, 2, 8) == 96          # never more than all
    assert moe.held_window(16384, 32, 64) == 16384  # half held: no window
    moe.observe_load(np.full((4, 64), 256), held=(0, 8))
    before = telemetry.snapshot().get(name, 0.0)
    assert telemetry.snapshot()["bf_moe_held_window_fill"] == 0.5
    load = np.full((4, 64), 256)
    # 4104 held rows of the same 16384: the window is 4096
    load[2, :8], load[2, 8:16], load[2, 16] = 513, 0, 248
    moe.observe_load(load, held=(0, 8))
    snap = telemetry.snapshot()
    assert snap[name] - before == 1
    assert snap["bf_moe_held_window_fill"] == pytest.approx(4104 / 4096)
    moe.observe_load(np.full((4, 64), 256), held=(0, 8))
    assert telemetry.snapshot()[name] - before == 1


# 1024 tokens, top-2 of 16 experts, 2 held: a window of 512 of 2048 rows
WINDOW_CASES = {                # (first, held assignments, compute dtype)
    "well-under": (4, 100, jnp.float32),
    "well-under-bfloat16": (4, 100, jnp.bfloat16),
    "exactly-the-window": (4, 512, jnp.float32),
    "over-the-window": (4, 700, jnp.float32),
    "two-full-windows": (4, 1024, jnp.float32),
    "no-row-held": (4, 0, jnp.float32),
    "at-the-tail": (14, 100, jnp.float32),
    "exactly-the-window-at-the-tail": (14, 512, jnp.float32),
    "first-zero": (0, 100, jnp.float32),
    "poisoned": (4, 100, jnp.float32),
    # 60 of the 100 tokens send both choices: 160 rows, a token's two among
    # them, beside 924 tokens with none
    "both-slots-held": (4, 100, jnp.float32),
    "both-slots-held-bfloat16": (4, 100, jnp.bfloat16),
}
BOTH = 60


def _window_layer(first, n_held, tokens=1024, E=16, d=16, f=24, both=0):
    """A layer whose first ``n_held`` tokens (of a shuffled order) send
    their first choice to one of the two held experts, the first ``both``
    of them their second choice to the other, and nothing else goes
    there."""
    x, noise = normal(30, (tokens, d)), normal(31, (tokens, E), 0.1)
    absent = np.array([e for e in range(E) if not first <= e < first + 2])
    t = np.arange(tokens)
    top = np.where(t < n_held, first + t % 2, absent[t % len(absent)])
    second = np.where(t < both, first + (t + 1) % 2,
                      absent[(t + 3) % len(absent)])
    logits = noise.at[t, top].add(8.0).at[t, second].add(4.0)
    shuffle = np.random.default_rng(0).permutation(tokens)
    gate, up = normal(32, (2, d, f), 0.3), normal(33, (2, d, f), 0.3)
    return (x, logits[shuffle], gate, up, normal(34, (2, f, d), 0.3),
            shuffle < n_held)


def assert_sums_equal(got, want, k, dtype):
    """Equal as sums of a token's ``k`` float32 terms are whatever the order
    they are added in: within ``k`` roundings of float32 before the one
    rounding to ``dtype``, which is at most one unit in its last place."""
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    np.testing.assert_allclose(
        got, want, rtol=float(jnp.finfo(dtype).eps),
        atol=k * 2.0 ** -23 * float(np.abs(want).max(initial=0.0)))


@pytest.mark.parametrize("name", sorted(WINDOW_CASES))
def test_the_window_is_the_whole_path(monkeypatch, name):
    """A held share over its window against the path over all ``T * k``
    rows (the layer with no window: every row gathered, every assignment
    back to its token): ``y`` and ``d x`` as a token's float32 sum is in
    another order of its terms (the window's rows are summed by
    ``bf_moe_token_sum``, the whole path's in slot order), the router's and
    the matrices' gradients to float32 rounding (their sums run over other
    tiles); whatever lies in the window's rows past the held ones reaches
    nothing."""
    first, n_held, dtype = WINDOW_CASES[name]
    both = BOTH if name.startswith("both-slots-held") else 0
    x, logits, gate, up, down, holds = _window_layer(first, n_held,
                                                     both=both)
    assert moe.held_window(2048, 2, 16) == 512
    if name == "poisoned":      # the tokens whose rows fill the window's end
        x = jnp.where(holds[:, None], x, jnp.nan)

    def run(x, logits, gate, up, down):
        y, plan = moe.dropless_moe(x.astype(dtype), logits, gate, up, down,
                                   k=2, held=(first, 2))
        return (y.astype(jnp.float32) ** 2).sum(), (y, plan.load)
    grad = jax.jit(jax.value_and_grad(run, (0, 1, 2, 3, 4), has_aux=True))
    (_, (y, load)), got = grad(x, logits, gate, up, down)
    assert int(load[first:first + 2].sum()) == n_held + both
    monkeypatch.setattr(moe, "held_window", lambda n, count, E: n)
    (_, (y_whole, _)), want = jax.jit(jax.value_and_grad(
        run, (0, 1, 2, 3, 4), has_aux=True))(x, logits, gate, up, down)
    assert_sums_equal(y, y_whole, 2, dtype)
    assert np.isfinite(np.asarray(y, np.float32)).all()
    assert bool(jnp.abs(y[holds]).sum() > 0) == (n_held > 0)
    np.testing.assert_array_equal(y[~holds], 0.0)
    assert_sums_equal(got[0][holds], want[0][holds], 2, dtype)
    np.testing.assert_array_equal(got[0][~holds], 0.0)
    for a, b in zip(got[1:], want[1:]):
        assert np.isfinite(a).all()
        np.testing.assert_allclose(a, b, rtol=1e-5,
                                   atol=1e-6 * float(jnp.abs(b).max()))


@pytest.mark.parametrize("n_held", [100, 512])
def test_the_window_branch_is_the_overflow_branch(monkeypatch, n_held):
    """One load under the window (and one that fills it) through the window
    branch and through the overflow branch, which covers the same run with
    its first window: the same rows sorted and summed by the same kernel
    and added to zeros, so every result is the same to the bit."""
    x, logits, gate, up, down, holds = _window_layer(4, n_held, both=BOTH)

    def run(x, logits, gate, up, down):
        y = moe.dropless_moe(x, logits, gate, up, down, k=2, held=(4, 2))[0]
        return (y ** 2).sum(), y
    grad = lambda: jax.jit(jax.value_and_grad(  # noqa: E731
        run, (0, 1, 2, 3, 4), has_aux=True))(x, logits, gate, up, down)
    taken = []
    branch = moe._branch

    def spy(load, first, count, size, window, overflow):
        taken.append(int(size))
        return branch(load, first, count, size, window, overflow)
    monkeypatch.setattr(moe, "_branch", spy)
    (_, y), got = grad()
    assert taken == [512, 512] and bool(jnp.abs(y[holds]).sum() > 0)
    monkeypatch.setattr(moe, "_branch", lambda load, first, count, size,
                        window, overflow: overflow())
    (_, y_over), want = grad()
    np.testing.assert_array_equal(y, y_over)
    for a, b in zip(got, want):
        np.testing.assert_array_equal(a, b)


def _walk(jaxpr):
    """Every equation of a jaxpr and of the jaxprs in its parameters."""
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _walk(sub)


def test_a_held_share_names_no_row_of_the_order(monkeypatch):
    """The gradient program of a held share at ``twotower-s8192-1chip``'s
    shapes (8192 tokens, top-6 of 128 experts, 8 held, 2688 wide, un-gated
    experts of 1856: a window of 6144 of 49152 rows), both branches: no
    array of ``T * k`` or more rows as wide as the model, forward or
    backward, and one sort of ``T * k`` keys, the order's; ``inverse``, the
    second, has no reader and is dropped.  The whole path has both."""
    from jax._src.interpreters import partial_eval as pe
    T_, k, E, d, f = 8192, 6, 128, 2688, 1856
    assert moe.held_window(T_ * k, 8, E) == 6144
    shape = lambda *s: jax.ShapeDtypeStruct(s, jnp.bfloat16)  # noqa: E731
    args = (shape(T_, d), jax.ShapeDtypeStruct((T_, E), jnp.float32),
            shape(8, d, f), shape(8, f, d))

    def program(held):
        def loss(x, logits, up, down):
            y, plan = moe.dropless_moe(x, logits, None, up, down, k=k,
                                       held=held, scoring="sigmoid")
            return (y.astype(jnp.float32) ** 2).sum(), plan.load
        closed = jax.make_jaxpr(jax.value_and_grad(
            loss, (0, 1, 2, 3), has_aux=True))(*args)
        jaxpr, _ = pe.dce_jaxpr(closed.jaxpr, [True] * len(closed.out_avals))
        eqns = list(_walk(jaxpr))
        rows = [v.aval.shape for e in eqns for v in e.outvars
                if len(v.aval.shape) >= 2 and v.aval.shape[-1] == d
                and v.aval.shape[0] * (v.aval.shape[1] if len(v.aval.shape)
                                       > 2 else 1) >= T_ * k]
        sorts = [e for e in eqns if e.primitive.name == "sort"
                 and e.invars[0].aval.shape == (T_ * k,)]
        return rows, len(sorts), {e.params.get("name") for e in eqns
                                  if e.primitive.name == "pallas_call"}
    rows, sorts, kernels = program((0, 8))
    assert rows == [] and sorts == 1 and "bf_moe_token_sum" in kernels
    monkeypatch.setattr(moe, "held_window", lambda n, count, E: n)
    rows, sorts, kernels = program((0, 8))
    assert rows and sorts == 2 and "bf_moe_token_sum" not in kernels


def test_a_held_share_differentiates_one_branch(monkeypatch):
    """The gradient program of a held share keeps no ``(T * k, .)`` array
    between its forward and its transpose: what crosses is the window's
    rows.  ``held=(0, E)`` and a share of half the experts have no window
    and are the whole path's jaxpr."""
    x, logits, gate, up, down, _ = _window_layer(4, 100)

    def out(*a, **kw):
        return moe.dropless_moe(*a, k=2, **kw)[0]
    _, residuals = jax.vjp(functools.partial(out, held=(4, 2)), x, logits,
                           gate, up, down)
    assert max(leaf.shape[0] for leaf in jax.tree.leaves(residuals)
               if leaf.ndim == 2) == 1024       # x itself; the window is 512
    wide = normal(35, (8, 16, 24), 0.3), normal(36, (8, 16, 24), 0.3), \
        normal(37, (8, 24, 16), 0.3)
    half = str(jax.make_jaxpr(functools.partial(out, held=(8, 8)))(
        x, logits, *wide))
    monkeypatch.setattr(moe, "_held_share", None)   # not reached
    assert half == str(jax.make_jaxpr(functools.partial(out, held=(8, 8)))(
        x, logits, *wide))


# --- (f) the residual maps -------------------------------------------------------

def _hyper(n=4, d=32, **kw):
    cfg = models.TransformerConfig(embed_dim=d, hyper_streams=n,
                                   dtype=jnp.float32, **kw)
    return cfg, T.HyperConnection(cfg)


def test_sinkhorn_rows_and_columns_sum_to_one(toy):
    config, _, ref = toy
    cfg, layer = _hyper(d=64)
    x = normal(20, (2, 16, 4 * 64))
    fresh = layer.init(KEY, x)["params"]
    # logits of order one: Sinkhorn contracts by tanh(spread / 4) a round,
    # so twenty rounds settle these and not a matrix e^8 off the identity,
    # which a fresh map is and which one row division already balances
    params = dict(fresh, phi=jnp.concatenate(
        [50.0 * fresh["phi"][:-1], normal(25, (1, 24), 0.5)]))
    with HIGHEST():
        _, _, h_fresh = ref._hyper(x.reshape(2, 16, 4, 64), fresh, config)
        u, h_post, h_res = ref._hyper(x.reshape(2, 16, 4, 64), params, config)
        got_u, mix = layer.apply({"params": params}, x)
        f = normal(21, (2, 16, 64))
        want = (jnp.einsum("bsij,bsjd->bsid", h_res, x.reshape(2, 16, 4, 64))
                + h_post[..., None] * f[:, :, None, :])
    for h in (h_res, h_fresh):
        np.testing.assert_allclose(h.sum(axis=-1), 1.0, atol=1e-3)
        np.testing.assert_allclose(h.sum(axis=-2), 1.0, atol=1e-3)
        assert float(h.min()) > 0
    np.testing.assert_allclose(
        h_fresh, np.broadcast_to(np.eye(4), h_fresh.shape), atol=1e-2)
    np.testing.assert_allclose(got_u, u, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(mix(f), want.reshape(2, 16, 256), rtol=1e-5,
                               atol=1e-5)


def test_clamped_inputs_stay_finite():
    cfg, layer = _hyper()
    x = normal(22, (1, 8, 4 * 32), 50.0)
    params = layer.init(KEY, x)["params"]
    params["phi"] = params["phi"].at[:-1].multiply(1e5)    # far past +-30
    u, mix = layer.apply({"params": params}, x)
    out = mix(normal(23, (1, 8, 32)))
    assert bool(jnp.isfinite(u).all()) and bool(jnp.isfinite(out).all())
    grads = jax.grad(lambda p: layer.apply({"params": p}, x)[0].sum())(params)
    assert all(bool(jnp.isfinite(g).all()) for g in jax.tree.leaves(grads))


def test_a_fresh_block_is_the_plain_block_on_the_mean_stream():
    kw = dict(vocab_size=64, num_layers=1, num_heads=4, embed_dim=32,
              pos_encoding="rope", mlp="swiglu", dtype=jnp.float32)
    plain = T.Block(models.TransformerConfig(**kw), T.local_attention)
    hyper = T.Block(models.TransformerConfig(hyper_streams=4, **kw),
                    T.local_attention)
    x = normal(24, (2, 16, 32))
    params = hyper.init(KEY, jnp.tile(x, (1, 1, 4)))["params"]
    for hc in ("hc_attn", "hc_ffn"):
        assert set(params[hc]) == {"scale", "phi"}
        assert params[hc]["phi"].shape == (4 * 32 + 1, 24)
        bias = params[hc]["phi"][-1]
        assert float(jnp.std(params[hc]["phi"][:-1])) == pytest.approx(
            0.01 / math.sqrt(4 * 32), rel=0.1)
        np.testing.assert_allclose(jax.nn.sigmoid(bias[:4]), 0.25, rtol=1e-6)
        np.testing.assert_allclose(bias[4:8], 0.0)
        np.testing.assert_allclose(bias[8:].reshape(4, 4), 8 * np.eye(4))
    shared = {k: v for k, v in params.items() if not k.startswith("hc_")}
    with HIGHEST():
        want = plain.apply({"params": shared}, x)
        got = hyper.apply({"params": params}, jnp.tile(x, (1, 1, 4)))
    assert got.shape == (2, 16, 4 * 32)
    mean = got.reshape(2, 16, 4, 32).mean(axis=2)
    assert rel(mean, want) < 1e-2


# --- (g), (h) the whole toy model ---------------------------------------------------

def test_toy_model_with_a_window_against_the_reference(toy):
    """One expert of eight held, two rows of 128 tokens: the expert layers
    (under remat, their statistics sown) work on a window of 256 of 512
    assignments; loss and every gradient leaf against the float32
    reference."""
    config, task, ref = toy
    held_one = dict(copy.deepcopy(config), n_routed_experts=1,
                    experts_first=2)
    assert moe.held_window(2 * 128 * 2, 1, 8) == 256
    _, params, aux, batch, program, reference = twins.model_case(
        (held_one, task, ref), KEY, "float32",
        {"sequences": 2, "seq_len": 128})
    with HIGHEST():
        (loss, new), grads = program(params, aux, *batch)
        (want, ref_new), ref_grads = reference(params, aux, *batch)
    assert params["block_1"]["moe"]["gate"].shape == (1, 64, 32)
    assert abs(float(loss) - float(want)) / float(want) < 1e-5
    np.testing.assert_array_equal(new["load"], ref_new["load"])
    errs = jax.tree.map(rel, grads, ref_grads)
    worst = max(jax.tree_util.tree_leaves_with_path(errs),
                key=lambda kv: kv[1])
    assert worst[1] < 1e-3, jax.tree_util.keystr(worst[0])
