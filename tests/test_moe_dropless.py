"""The dropless top-k mixture of SwiGLU experts (``parallel.moe.dropless_moe``,
``models.transformer.DroplessMoe``), QK-norm, and the ``olmoe-1b-7b``
configuration at toy widths against its plain reference
(``benchmark/reference/olmoe-1b-7b.py``, loaded by path: it shares no code
with ``parallel/moe.py``).  Float32 on the CPU mesh unless a test says
otherwise."""

import functools
import importlib.util
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

import bluefog_tpu as bf
from bluefog_tpu import models
from bluefog_tpu.models.transformer import moe_stats
from bluefog_tpu.parallel import moe
from bluefog_tpu.utils import telemetry
from twins import (  # noqa: F401
    test_atc_on_four_devices_is_w_times_the_handwritten_update,
    test_float8_rounded_matrices_fail_the_bounds,
    test_toy_model_in_bfloat16_is_inside_the_twin_bounds,
    test_toy_model_loss_and_every_gradient_leaf_in_float32)

# the twins whose whole-model cases (``tests/twins.py``) run in this file
TWINS = ("tiny-olmoe",)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load(relpath, name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(ROOT, relpath))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@pytest.fixture(scope="module")
def reference():
    return _load("benchmark/reference/olmoe-1b-7b.py", "olmoe_reference")


def _olmoe_json():
    with open(os.path.join(ROOT, "benchmark/configs/olmoe-1b-7b.json")) as f:
        return json.load(f)


# --- the layer against every expert applied to every token ------------------

def _dense_moe(x, logits, gate, up, down, k):
    """All experts on all tokens, masked by the top-k of the softmax."""
    probs = jax.nn.softmax(logits, axis=-1)
    top, chosen = jax.lax.top_k(probs, k)
    weight = (jax.nn.one_hot(chosen, logits.shape[-1]) * top[..., None]
              ).sum(axis=1)                                     # (T, E)
    h = jax.nn.silu(jnp.einsum("td,edf->tef", x, gate)) \
        * jnp.einsum("td,edf->tef", x, up)
    return jnp.einsum("te,ted->td", weight,
                      jnp.einsum("tef,efd->ted", h, down))


def _layer_inputs(n_experts, tokens=96, d=16, f=24, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    return {"x": jax.random.normal(ks[0], (tokens, d)),
            "router": jax.random.normal(ks[1], (d, n_experts)),
            "gate": 0.3 * jax.random.normal(ks[2], (n_experts, d, f)),
            "up": 0.3 * jax.random.normal(ks[3], (n_experts, d, f)),
            "down": 0.3 * jax.random.normal(ks[4], (n_experts, f, d)),
            }, jax.random.normal(ks[5], (tokens, d))


def _assert_close(got, want, tol, what):
    got, want = np.asarray(got), np.asarray(want)
    scale = max(float(np.abs(want).max()), 1e-30)
    assert float(np.abs(got - want).max()) <= tol * scale, what


@pytest.mark.parametrize("n_experts,k,tokens", [
    (8, 2, 96), (4, 1, 96), (64, 8, 96),
    (4, 3, 33)])        # 99 rows: padded to a whole row tile for the kernels
def test_dropless_layer_equals_dense_all_experts(n_experts, k, tokens):
    p, ct = _layer_inputs(n_experts, tokens=tokens)

    def ours(p):
        y, _ = moe.dropless_moe(p["x"], p["x"] @ p["router"], p["gate"],
                                p["up"], p["down"], k=k)
        return jnp.sum(y * ct)

    def dense(p):
        return jnp.sum(_dense_moe(p["x"], p["x"] @ p["router"], p["gate"],
                                  p["up"], p["down"], k) * ct)

    got, got_grads = jax.jit(jax.value_and_grad(ours))(p)
    want, want_grads = jax.jit(jax.value_and_grad(dense))(p)
    assert abs(float(got) - float(want)) <= 1e-5 * abs(float(want))
    for name in p:
        _assert_close(got_grads[name], want_grads[name], 1e-5, name)


def test_nothing_is_dropped_when_every_token_picks_the_same_experts():
    n_experts, k = 8, 2
    p, _ = _layer_inputs(n_experts, tokens=64)
    # experts 5 and 2 win for every token, whatever the token
    logits = p["x"] @ p["router"] * 0.01 + jnp.zeros(n_experts).at[5].set(
        9.0).at[2].set(7.0)
    y, plan = jax.jit(functools.partial(moe.dropless_moe, k=k))(
        p["x"], logits, p["gate"], p["up"], p["down"])
    load = np.asarray(plan.load)
    assert load.sum() == 64 * k
    assert load[5] == 64 and load[2] == 64 and load[[0, 1, 3, 4, 6, 7]].sum() == 0
    np.testing.assert_array_equal(np.asarray(plan.experts),
                                  np.tile([5, 2], (64, 1)))
    _assert_close(y, _dense_moe(p["x"], logits, p["gate"], p["up"],
                                p["down"], k), 1e-5, "skewed output")
    # every one of the 128 assignments has a row of its own in expert order
    assert sorted(np.asarray(plan.order)) == list(range(64 * k))
    np.testing.assert_array_equal(
        np.asarray(plan.order)[np.asarray(plan.inverse)], np.arange(64 * k))


def test_route_topk_renormalises_only_when_asked():
    logits = jax.random.normal(jax.random.PRNGKey(1), (32, 8))
    raw = moe.route_topk(logits, 3)
    unit = moe.route_topk(logits, 3, renormalize=True)
    probs = np.asarray(jax.nn.softmax(logits))
    want = -np.sort(-probs, axis=1)[:, :3]
    np.testing.assert_allclose(np.asarray(raw.weights), want, rtol=1e-6)
    np.testing.assert_allclose(np.asarray(unit.weights),
                               want / want.sum(1, keepdims=True), rtol=1e-6)
    # sorted by expert, ties in token order (stable)
    flat = np.asarray(raw.experts).reshape(-1)
    order = np.asarray(raw.order)
    assert (np.diff(flat[order]) >= 0).all()
    same = np.diff(flat[order]) == 0
    assert (np.diff(order)[same] > 0).all()


def test_router_losses_against_hand_written_formulas():
    tokens, n_experts, k = 40, 8, 2
    logits = 2.0 * jax.random.normal(jax.random.PRNGKey(2),
                                     (tokens, n_experts))
    plan = moe.route_topk(logits, k)
    x = np.asarray(logits, np.float64)
    probs = np.exp(x) / np.exp(x).sum(1, keepdims=True)
    chosen = np.argsort(-probs, axis=1)[:, :k]
    counts = np.bincount(chosen.reshape(-1), minlength=n_experts)
    balance = n_experts * sum(
        counts[e] / (tokens * k) * probs[:, e].mean()
        for e in range(n_experts))
    z = np.mean(np.log(np.exp(x).sum(1)) ** 2)
    np.testing.assert_array_equal(np.asarray(plan.load), counts)
    assert abs(float(plan.balance_loss) - balance) < 1e-6
    assert abs(float(plan.z_loss) - z) < 1e-5 * z
    assert abs(float(moe.router_z_loss(logits)) - z) < 1e-5 * z
    # a uniform router sits at 1 for every k; top-1 is the Switch loss
    flat = moe.route_topk(jnp.zeros((tokens, n_experts)), k)
    assert abs(float(flat.balance_loss) - 1.0) < 1e-6
    one = moe.route_topk(logits, 1)
    assert abs(float(one.balance_loss)
               - float(moe.load_balance_loss(logits))) < 1e-6
    # the counts are constants: the gradient flows through P_e alone
    grad = jax.grad(lambda l: moe.route_topk(l, k).balance_loss)(logits)
    share = counts / (tokens * k)
    want = n_experts / tokens * probs * (share[None] - (probs * share).sum(
        1, keepdims=True))
    np.testing.assert_allclose(np.asarray(grad), want, atol=1e-6)


# --- QK-norm, both attention branches ----------------------------------------

@pytest.mark.parametrize("kv_heads", [None, 2], ids=["fused-qkv", "gqa"])
def test_qk_norm_spans_the_whole_projection_before_the_head_split(kv_heads):
    cfg = models.TransformerConfig(
        vocab_size=64, num_layers=1, num_heads=4, num_kv_heads=kv_heads,
        embed_dim=32, max_seq_len=16, dtype=jnp.float32, pos_encoding="rope",
        mlp="swiglu", qk_norm=True, rms_norm_eps=1e-5)
    model = models.TransformerLM(cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(0), (2, 16), 0, 64)
    params = model.init(jax.random.PRNGKey(1), tokens)["params"]
    block = params["block_0"]
    width_k = (kv_heads or 4) * 8
    assert block["q_norm"]["scale"].shape == (32,)
    assert block["k_norm"]["scale"].shape == (width_k,)
    noise = jax.random.split(jax.random.PRNGKey(3))
    block["q_norm"]["scale"] += 0.3 * jax.random.normal(noise[0], (32,))
    block["k_norm"]["scale"] += 0.3 * jax.random.normal(noise[1], (width_k,))
    logits, state = model.apply({"params": params}, tokens,
                                capture_intermediates=True)
    seen = state["intermediates"]["block_0"]
    if kv_heads is None:
        qkv = seen["qkv"]["__call__"][0].reshape(2, 16, 4, 3, 8)
        q_in, k_in = qkv[..., 0, :], qkv[..., 1, :]
    else:
        q_in = seen["q"]["__call__"][0]
        k_in = seen["kv"]["__call__"][0].reshape(2, 16, kv_heads, 2, 8)[
            ..., 0, :]

    def rms(x, scale):       # over all heads together, eps as configured
        x = x.reshape(2, 16, -1)
        return x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + 1e-5) * scale

    np.testing.assert_allclose(
        seen["q_norm"]["__call__"][0], rms(q_in, block["q_norm"]["scale"]),
        rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(
        seen["k_norm"]["__call__"][0], rms(k_in, block["k_norm"]["scale"]),
        rtol=1e-5, atol=1e-6)
    # and the norms feed attention: another scale, other logits
    block["k_norm"]["scale"] *= 2.0
    assert not np.allclose(model.apply({"params": params}, tokens), logits)
    # off by default: no such leaves, and the configured epsilon is the old one
    plain = models.TransformerConfig(
        vocab_size=64, num_layers=1, num_heads=4, num_kv_heads=kv_heads,
        embed_dim=32, max_seq_len=16, dtype=jnp.float32, pos_encoding="rope")
    assert plain.rms_norm_eps == 1e-6 and not plain.qk_norm
    assert "q_norm" not in models.TransformerLM(plain).init(
        jax.random.PRNGKey(1), tokens)["params"]["block_0"]


def test_config_says_which_experts():
    with pytest.raises(ValueError, match="1..num_experts"):
        models.TransformerConfig(mlp="swiglu", num_experts=4,
                                 num_experts_per_tok=5)
    with pytest.raises(ValueError, match="contradictory"):
        models.TransformerConfig(num_experts=4, num_experts_per_tok=2)
    cfg = models.TransformerConfig(mlp="swiglu", num_experts=4,
                                   num_experts_per_tok=2, expert_dim=24)
    assert (cfg.num_experts_per_tok, cfg.expert_dim) == (2, 24)


# --- a tiny OLMoE against the plain reference ---------------------------------

TINY = {"hidden_size": 64, "num_attention_heads": 4, "num_key_value_heads": 4,
        "intermediate_size": 32, "num_experts": 8, "num_experts_per_tok": 2,
        "norm_topk_prob": False, "vocab_size": 256, "num_hidden_layers": 2,
        "max_position_embeddings": 64, "rope_theta": 10000,
        "rms_norm_eps": 1e-5, "router_aux_loss_coef": 0.01,
        "router_z_loss_coef": 0.001}


def _tiny_config(dtype):
    """The published configuration file with the toy's sizes in place of its
    source keys: the model is built the way ``benchmark/build.py`` does."""
    config = dict(_olmoe_json(), **TINY)
    config["model"] = dict(config["model"], attention="local")
    config["model"]["args"] = dict(config["model"]["args"], dtype=dtype)
    return config


def _tiny_job(dtype, seed=0, batch=2, seq=32):
    task = _load("benchmark/tasks/moe_causal_lm.py", "moe_causal_lm_task")
    config = _tiny_config(dtype)
    model = task.make_model(config)
    sizes = {"sequences": batch, "seq_len": seq}
    key = jax.random.PRNGKey(seed)
    params, aux = task.init(model, key, config, sizes)
    # no leaf at its initial 1.0 or 0.0: a norm scale that the reference
    # skipped would otherwise go unnoticed
    leaves, treedef = jax.tree.flatten(params)
    keys = jax.random.split(jax.random.fold_in(key, 7), len(leaves))
    params = treedef.unflatten([
        x + 0.05 * jax.random.normal(k, x.shape) for x, k in zip(leaves, keys)])
    tokens, = task.make_batch(jax.random.fold_in(key, 9), config, sizes)
    return task, config, model, params, aux, tokens


def _rel(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


def test_tiny_olmoe_equals_the_reference_in_float32(reference):
    task, config, model, params, aux, tokens = _tiny_job("float32")
    assert model.cfg.qk_norm and model.cfg.num_experts_per_tok == 2
    assert params["block_0"]["moe"]["gate"].shape == (8, 64, 32)
    assert "qkv" in params["block_0"]               # the fused MHA branch
    ours = jax.jit(jax.value_and_grad(task.loss_fn(model, config),
                                      has_aux=True))
    ref = jax.jit(jax.value_and_grad(
        functools.partial(reference.loss, cfg=config), has_aux=True))
    (loss, stats), grads = ours(params, aux, tokens)
    (want_loss, want_stats), want_grads = ref(params, aux, tokens)
    assert abs(float(loss) - float(want_loss)) <= 1e-4 * float(want_loss)
    np.testing.assert_array_equal(stats["load"], want_stats["load"])
    assert int(np.asarray(stats["load"]).sum()) == 2 * 2 * 32 * 2
    for key in ("balance_loss", "z_loss"):
        assert abs(float(stats[key]) - float(want_stats[key])) < 1e-5
    # the auxiliary terms are in the loss, with the file's coefficients
    assert float(stats["balance_loss"]) > 0.9 and float(stats["z_loss"]) > 0
    flat = jax.tree_util.tree_leaves_with_path(grads)
    want_flat = jax.tree.leaves(want_grads)
    assert len(flat) == len(want_flat) == 2 * 10 + 3
    for (path, got), want in zip(flat, want_flat):
        assert _rel(got, want) <= 1e-4, jax.tree_util.keystr(path)


def test_tiny_olmoe_in_bfloat16_stays_inside_the_configured_bounds(reference):
    """A top-k choice is not continuous: where two probabilities nearly tie,
    bfloat16 rounding picks the other expert and that token's share of every
    gradient moves with it (at 8 experts and top-2 one flip in 128
    assignments is worth several percent).  The bounds are about rounding,
    so they are held on a sample on which both sides chose alike; that a
    flip drops nothing is held on every sample."""
    bounds = _olmoe_json()["model_check"]
    alike = 0
    for seed in range(1, 7):
        task, config, model, params, aux, tokens = _tiny_job("bfloat16",
                                                             seed=seed)
        (loss, stats), grads = jax.jit(jax.value_and_grad(
            task.loss_fn(model, config), has_aux=True))(params, aux, tokens)
        (want_loss, want_stats), want_grads = jax.jit(jax.value_and_grad(
            functools.partial(reference.loss, cfg=config), has_aux=True))(
                params, aux, tokens)
        assert int(np.asarray(stats["load"]).sum()) \
            == int(np.asarray(want_stats["load"]).sum()) == 2 * 2 * 32 * 2
        if not np.array_equal(stats["load"], want_stats["load"]):
            continue
        alike += 1
        assert abs(float(loss) - float(want_loss)) \
            <= bounds["loss_rtol"] * float(want_loss)
        errs = [_rel(g, w) for g, w in zip(jax.tree.leaves(grads),
                                           jax.tree.leaves(want_grads))]
        assert max(errs) <= bounds["grad_rtol"], errs
    assert alike >= 2


def test_remat_carries_the_sown_statistics_and_their_gradient():
    task, config, model, params, aux, tokens = _tiny_job("float32")
    assert config["model"]["args"]["remat"] is True
    plain = dict(config, model=dict(config["model"], args=dict(
        config["model"]["args"], remat=False)))
    with_remat = jax.jit(jax.value_and_grad(task.loss_fn(model, config),
                                            has_aux=True))
    without = jax.jit(jax.value_and_grad(
        task.loss_fn(task.make_model(plain), plain), has_aux=True))
    (a, stats_a), grads_a = with_remat(params, aux, tokens)
    (b, stats_b), grads_b = without(params, aux, tokens)
    assert abs(float(a) - float(b)) < 1e-6
    np.testing.assert_array_equal(stats_a["load"], stats_b["load"])
    router = lambda g: g["block_0"]["moe"]["router"]["kernel"]  # noqa: E731
    assert _rel(router(grads_a), router(grads_b)) < 1e-5
    assert float(jnp.abs(router(grads_a)).max()) > 0
    with pytest.raises(ValueError, match="no DroplessMoe layer"):
        moe_stats({})


# --- through bf.init, bf.rank_map and the ATC optimizer ---------------------

def test_tiny_olmoe_trains_through_rank_map_and_atc_on_four_devices():
    adamw = _load("benchmark/reference/optim_adamw.py", "adamw_reference")
    mixing = _load("benchmark/reference/mixing_one_peer_exp2.py",
                   "mixing_reference")
    n = 4
    bf.init(devices=jax.devices()[:n])
    task, config, model, params, aux, _ = _tiny_job("float32")
    hyper = {"learning_rate": 3e-4, "b1": 0.9, "b2": 0.95, "eps": 1e-8,
             "weight_decay": 0.1}
    opt = bf.optim.DistributedAdaptThenCombineOptimizer(
        optax.adamw(**hyper), bf.optim.CommunicationType.neighbor_allreduce,
        use_dynamic_topology=True)
    vgrad = bf.rank_map(jax.value_and_grad(task.loss_fn(model, config),
                                           has_aux=True))
    keys = jax.random.split(jax.random.PRNGKey(5), n)
    # rank-major trees whose rows differ by rank, and a batch a rank
    rank_major = lambda x: jnp.stack([  # noqa: E731
        x + 0.01 * r * jnp.sign(x) for r in range(n)])
    params = jax.tree.map(rank_major, params)
    aux = jax.tree.map(lambda x: jnp.stack([x] * n), aux)
    tokens = jnp.stack([task.make_batch(
        k, config, {"sequences": 2, "seq_len": 32})[0] for k in keys])
    state = opt.init(params)
    want, want_state = jax.tree.map(np.asarray, params), None
    t0 = int(np.asarray(state.step).reshape(-1)[0])
    for step in range(2):
        (loss, aux), grads = vgrad(params, aux, tokens)
        assert np.isfinite(np.asarray(loss)).all() and loss.shape == (n,)
        assert aux["load"].shape == (n, 2, 8)
        assert (np.asarray(aux["load"]).sum(axis=(1, 2)) == 2 * 2 * 32 * 2
                ).all()
        # the reference update on the same gradients, then W_t over ranks
        want_state = want_state or adamw.init(want)
        moved, want_state = adamw.update(
            want, jax.tree.map(np.asarray, grads), want_state, hyper)
        w = mixing.matrix(n, t0 + step)
        want = jax.tree.map(
            lambda x: np.einsum("ij,j...->i...", w, np.asarray(x)), moved)
        params, state = opt.step(params, grads, state)
    for (path, got), expect in zip(
            jax.tree_util.tree_leaves_with_path(params),
            jax.tree.leaves(want)):
        np.testing.assert_allclose(
            np.asarray(got), expect, rtol=0, atol=3e-6,
            err_msg=jax.tree_util.keystr(path))


# --- what a loop publishes, and what the benchmark counts ------------------

def test_observe_load_publishes_the_counter_and_the_gauge():
    telemetry.reset()
    try:
        ratio = moe.observe_load(np.array([[4, 0, 2, 2], [4, 2, 0, 2]]))
        assert ratio == pytest.approx(8 / 4)
        snap = telemetry.snapshot()
        assert snap['bf_moe_assignments_total{expert="0"}'] == 8
        assert snap['bf_moe_assignments_total{expert="1"}'] == 2
        assert snap["bf_moe_load_max_over_mean"] == pytest.approx(2.0)
        moe.observe_load(jnp.array([1, 1, 1, 1]))     # a counter adds up,
        snap = telemetry.snapshot()                     # a gauge is replaced
        assert snap['bf_moe_assignments_total{expert="0"}'] == 9
        assert snap["bf_moe_load_max_over_mean"] == pytest.approx(1.0)
    finally:
        telemetry.reset()


def test_step_flops_at_the_published_sizes_equal_the_hand_count():
    task = _load("benchmark/tasks/moe_causal_lm.py", "moe_causal_lm_task")
    config = _olmoe_json()
    assert config["num_hidden_layers"] == 1
    assert config["source_values"] == {"num_hidden_layers": 16}
    batch = {"sequences": 2, "seq_len": 4096}
    tokens = 2 * 4096
    block = 6 * (4 * 2048 * 2048 + 2048 * 64 + 8 * 3 * 2048 * 1024)
    head = 6 * 2048 * 50304
    attention = 12 * 1 * 4096 * 2048 // 2
    assert (block, head, attention) == (403439616, 618135552, 50331648)
    got = task.step_flops(config, batch)
    assert got["flops"] == (block + head + attention) * tokens \
        == 1071906816 * tokens
    assert got["experts"] == 6 * 8 * 3 * 2048 * 1024 * tokens
    assert got["head"] == head * tokens
    assert task.items_per_step(batch) == tokens
    # the grouped product: 2 m k n whatever the group sizes; bytes by kind
    flops_moe = _load("benchmark/flops_moe.py", "flops_moe")
    rows = flops_moe.grouped_matmul("rows", rows=65536, inner=2048,
                                    outer=1024, groups=64)
    assert rows["flops"] == 2 * 65536 * 2048 * 1024
    assert rows["bytes"] == 2 * (65536 * 2048 + 64 * 2048 * 1024
                                 + 65536 * 1024)
    weights = flops_moe.grouped_matmul(
        "weights", rows=65536, inner=2048, outer=1024, groups=64,
        out_itemsize=4)
    assert weights["flops"] == rows["flops"]
    assert weights["bytes"] == 2 * 65536 * (2048 + 1024) \
        + 4 * 64 * 2048 * 1024
