"""Sequence-parallel attention tests: ring / Ulysses vs dense reference."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from bluefog_tpu.models.transformer import local_attention
from bluefog_tpu.parallel import ring_attention, ulysses_attention

B, S, H, D = 2, 32, 8, 16
NDEV = 8


@pytest.fixture
def qkv():
    rng = np.random.RandomState(0)
    mk = lambda: jnp.asarray(rng.randn(B, S, H, D), jnp.float32)
    return mk(), mk(), mk()


def seq_sharded(fn, devices):
    # check_vma=False: the ring path calls Pallas kernels which on CPU run
    # under the interpreter, where in-kernel constants are not vma-tracked
    # (compiled Mosaic kernels on TPU work under check_vma=True:
    # chip_smoke.py's ring leg).
    mesh = Mesh(np.asarray(devices), ("sp",))
    return jax.jit(jax.shard_map(
        fn, mesh=mesh,
        in_specs=(P(None, "sp"), P(None, "sp"), P(None, "sp")),
        out_specs=P(None, "sp"), check_vma=False))


@pytest.mark.parametrize("causal", [True, False])
def test_ring_attention_matches_dense(devices, qkv, causal):
    q, k, v = qkv
    ref = local_attention(q, k, v, causal=causal)
    out = seq_sharded(
        lambda a, b, c: ring_attention(a, b, c, axis_name="sp", causal=causal),
        devices)(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("causal", [True, False])
def test_ulysses_attention_matches_dense(devices, qkv, causal):
    q, k, v = qkv
    ref = local_attention(q, k, v, causal=causal)
    out = seq_sharded(
        lambda a, b, c: ulysses_attention(a, b, c, axis_name="sp",
                                          causal=causal),
        devices)(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)


@pytest.mark.slow
def test_ring_attention_grad_matches_dense(devices, qkv):
    """Differentiability: ring attention must backprop like dense."""
    q, k, v = qkv

    def loss_dense(q, k, v):
        return jnp.sum(local_attention(q, k, v, causal=True) ** 2)

    def loss_ring(q, k, v):
        mesh = Mesh(np.asarray(devices), ("sp",))
        out = jax.shard_map(
            lambda a, b, c: ring_attention(a, b, c, axis_name="sp"),
            mesh=mesh, in_specs=(P(None, "sp"),) * 3,
            out_specs=P(None, "sp"), check_vma=False)(q, k, v)
        return jnp.sum(out ** 2)

    g_ref = jax.grad(loss_dense)(q, k, v)
    g_ring = jax.jit(jax.grad(loss_ring))(q, k, v)
    np.testing.assert_allclose(np.asarray(g_ring), np.asarray(g_ref),
                               rtol=5e-3, atol=5e-3)


@pytest.mark.slow
def test_transformer_with_ring_attention(devices):
    """End-to-end: TransformerLM forward with sequence-parallel attention
    equals the single-device model."""
    from bluefog_tpu.models import TransformerLM, TransformerConfig
    from bluefog_tpu.parallel import ring_attention_impl

    cfg = TransformerConfig(vocab_size=128, num_layers=2, num_heads=4,
                            embed_dim=64, max_seq_len=64, dtype=jnp.float32)
    tokens = jnp.asarray(np.random.RandomState(1).randint(0, 128, (2, 64)))
    model_ref = TransformerLM(cfg)
    params = model_ref.init(jax.random.PRNGKey(0), tokens)
    ref = model_ref.apply(params, tokens)

    mesh = Mesh(np.asarray(devices), ("sp",))
    model_sp = TransformerLM(cfg, attn_impl=ring_attention_impl("sp"))
    positions = jnp.arange(64)[None, :].repeat(2, axis=0)

    def fwd(tokens, positions):
        return model_sp.apply(params, tokens, positions=positions)

    out = jax.jit(jax.shard_map(
        fwd, mesh=mesh, in_specs=(P(None, "sp"), P(None, "sp")),
        out_specs=P(None, "sp"), check_vma=False))(tokens, positions)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-3, atol=2e-3)


def test_tensor_parallel_sharded_forward_matches(devices):
    """Megatron-layout TP via GSPMD: the sharded forward equals the
    single-device forward, with XLA placing the collectives."""
    from jax.sharding import NamedSharding
    from bluefog_tpu.models import TransformerLM, TransformerConfig
    from bluefog_tpu.parallel.tensor_parallel import (tp_param_specs,
                                                      tp_shard_params)

    cfg = TransformerConfig(vocab_size=128, num_layers=2, num_heads=4,
                            embed_dim=32, max_seq_len=16, dtype=jnp.float32)
    model = TransformerLM(cfg)
    tokens = jnp.asarray(np.random.RandomState(0).randint(0, 128, (4, 16)))
    params = model.init(jax.random.PRNGKey(0), tokens)
    ref = model.apply(params, tokens)

    mesh = Mesh(np.asarray(devices).reshape(2, 4), ("dp", "tp"))
    specs = tp_param_specs(params, axis="tp")
    # every block kernel got a sharded spec
    flat = jax.tree_util.tree_flatten_with_path(specs)[0]
    sharded = [p for p, s in flat if s != P()]
    assert len(sharded) >= 2 * 4 + 1, flat  # 4 kernels/block x 2 + lm_head
    p_sh = tp_shard_params(params, mesh, axis="tp")
    t_sh = jax.device_put(tokens, NamedSharding(mesh, P("dp")))
    out = jax.jit(model.apply)(p_sh, t_sh)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_tensor_parallel_gqa_sharded_forward_matches(devices):
    """GQA's separate q/kv projections get column-parallel specs and the
    sharded forward still equals the single-device one."""
    from jax.sharding import NamedSharding
    from bluefog_tpu.models import TransformerLM, TransformerConfig
    from bluefog_tpu.parallel.tensor_parallel import (tp_param_specs,
                                                      tp_shard_params)

    cfg = TransformerConfig(vocab_size=128, num_layers=2, num_heads=4,
                            num_kv_heads=2, embed_dim=32, max_seq_len=16,
                            dtype=jnp.float32)
    model = TransformerLM(cfg)
    tokens = jnp.asarray(np.random.RandomState(1).randint(0, 128, (4, 16)))
    params = model.init(jax.random.PRNGKey(0), tokens)
    ref = model.apply(params, tokens)

    specs = tp_param_specs(params, axis="tp")
    flat = {"/".join(str(getattr(k, "key", k)) for k in path): s
            for path, s in jax.tree_util.tree_flatten_with_path(specs)[0]}
    assert flat["params/block_0/q/kernel"] == P(None, "tp")
    assert flat["params/block_0/kv/kernel"] == P(None, "tp")

    mesh = Mesh(np.asarray(devices).reshape(2, 4), ("dp", "tp"))
    p_sh = tp_shard_params(params, mesh, axis="tp")
    t_sh = jax.device_put(tokens, NamedSharding(mesh, P("dp")))
    out = jax.jit(model.apply)(p_sh, t_sh)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_tensor_parallel_grad_step_matches(devices):
    """TP + batch-DP sharded loss/grad equals the unsharded computation —
    one jit, layouts only."""
    from jax.sharding import NamedSharding
    from bluefog_tpu.models import TransformerLM, TransformerConfig
    from bluefog_tpu.parallel.tensor_parallel import tp_shard_params

    cfg = TransformerConfig(vocab_size=64, num_layers=2, num_heads=4,
                            embed_dim=32, max_seq_len=16, dtype=jnp.float32)
    model = TransformerLM(cfg)
    tokens = jnp.asarray(np.random.RandomState(1).randint(0, 64, (4, 16)))
    params = model.init(jax.random.PRNGKey(0), tokens)

    def loss(p, t):
        logits = model.apply(p, t)
        tgt = jnp.roll(t, -1, axis=1)
        logp = jax.nn.log_softmax(logits)
        return -jnp.take_along_axis(logp, tgt[..., None], -1).mean()

    ref_loss, ref_grads = jax.value_and_grad(loss)(params, tokens)

    mesh = Mesh(np.asarray(devices).reshape(2, 4), ("dp", "tp"))
    p_sh = tp_shard_params(params, mesh)
    t_sh = jax.device_put(tokens, NamedSharding(mesh, P("dp")))
    out_loss, out_grads = jax.jit(jax.value_and_grad(loss))(p_sh, t_sh)
    np.testing.assert_allclose(float(out_loss), float(ref_loss), rtol=1e-5)
    for a, b in zip(jax.tree_util.tree_leaves(ref_grads),
                    jax.tree_util.tree_leaves(out_grads)):
        np.testing.assert_allclose(np.asarray(b), np.asarray(a),
                                   rtol=5e-4, atol=1e-5)


def _mlp_stage(w, x):
    return jnp.tanh(x @ w)


def test_pipeline_matches_sequential(devices):
    """GPipe schedule over 4 stages: outputs equal applying the stages
    sequentially; every rank receives the full result."""
    from bluefog_tpu.parallel.pipeline import pipeline_apply
    n_pp, M, mb, d = 4, 6, 3, 8
    rng = np.random.RandomState(0)
    Ws = jnp.asarray(rng.randn(n_pp, d, d) * 0.5, jnp.float32)
    x = jnp.asarray(rng.randn(M, mb, d), jnp.float32)

    ref = x
    for i in range(n_pp):
        ref = _mlp_stage(Ws[i], ref)

    mesh = Mesh(np.asarray(devices[:n_pp]), ("pp",))
    out = jax.jit(jax.shard_map(
        lambda W, x: pipeline_apply(
            lambda w, xb: _mlp_stage(w[0], xb), W, x, axis_name="pp"),
        mesh=mesh, in_specs=(P("pp"), P()), out_specs=P(),
        check_vma=False))(Ws, x)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-6)


def test_pipeline_grads_match_sequential(devices):
    """Reverse-mode AD through the scan+ppermute schedule equals sequential
    backprop — training-capable pipelining with no hand-written backward."""
    from bluefog_tpu.parallel.pipeline import pipeline_apply
    n_pp, M, mb, d = 4, 5, 2, 6
    rng = np.random.RandomState(1)
    Ws = jnp.asarray(rng.randn(n_pp, d, d) * 0.5, jnp.float32)
    x = jnp.asarray(rng.randn(M, mb, d), jnp.float32)
    mesh = Mesh(np.asarray(devices[:n_pp]), ("pp",))

    def loss_seq(Ws):
        h = x
        for i in range(n_pp):
            h = _mlp_stage(Ws[i], h)
        return jnp.sum(h ** 2)

    def loss_pp(Ws):
        out = jax.shard_map(
            lambda W, xb: pipeline_apply(
                lambda w, z: _mlp_stage(w[0], z), W, xb, axis_name="pp"),
            mesh=mesh, in_specs=(P("pp"), P()), out_specs=P(),
            check_vma=False)(Ws, x)
        return jnp.sum(out ** 2)

    g_ref = jax.grad(loss_seq)(Ws)
    g_pp = jax.jit(jax.grad(loss_pp))(Ws)
    np.testing.assert_allclose(np.asarray(g_pp), np.asarray(g_ref),
                               rtol=1e-4, atol=1e-5)


@pytest.mark.slow
def test_pipeline_transformer_blocks(devices):
    """Pipeline the TransformerLM's blocks across 2 stages: equals the
    single-device model applied to the same microbatches."""
    from bluefog_tpu.models.transformer import Block, local_attention
    from bluefog_tpu.models import TransformerConfig
    from bluefog_tpu.parallel.pipeline import pipeline_apply

    cfg = TransformerConfig(vocab_size=64, num_layers=2, num_heads=4,
                            embed_dim=32, max_seq_len=8, dtype=jnp.float32)
    block = Block(cfg, local_attention)
    rng = np.random.RandomState(2)
    M, mb, S = 4, 2, 8
    x = jnp.asarray(rng.randn(M, mb, S, cfg.embed_dim), jnp.float32)
    p0 = block.init(jax.random.PRNGKey(0), x[0])
    p1 = block.init(jax.random.PRNGKey(1), x[0])

    ref = jax.vmap(lambda xb: block.apply(
        p1, block.apply(p0, xb)))(x)

    stacked = jax.tree.map(lambda a, b: jnp.stack([a, b]), p0, p1)
    mesh = Mesh(np.asarray(devices[:2]), ("pp",))
    out = jax.jit(jax.shard_map(
        lambda W, xb: pipeline_apply(
            lambda w, z: block.apply(jax.tree.map(lambda a: a[0], w), z),
            W, xb, axis_name="pp"),
        mesh=mesh, in_specs=(P("pp"), P()), out_specs=P(),
        check_vma=False))(stacked, x)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_moe_expert_parallel_matches_dense(devices):
    """Switch-routed MoE over a 4-rank ep axis == the dense single-device
    evaluation of the same routing plan (incl. capacity drops)."""
    from bluefog_tpu.parallel.moe import moe_apply, switch_dispatch
    E, T, d, C = 4, 12, 8, 4
    rng = np.random.RandomState(0)
    Ws = jnp.asarray(rng.randn(E, d, d) * 0.5, jnp.float32)
    x = jnp.asarray(rng.randn(T, d), jnp.float32)
    logits = jnp.asarray(rng.randn(T, E), jnp.float32)

    # dense reference from the same dispatch plan
    combine, dispatch = switch_dispatch(logits, E, C)
    ref = jnp.zeros_like(x)
    for e in range(E):
        ye = jnp.tanh((dispatch[e] @ x) @ Ws[e])
        ref = ref + jnp.moveaxis(combine, 1, 0)[e] @ ye

    mesh = Mesh(np.asarray(devices[:E]), ("ep",))
    out = jax.jit(jax.shard_map(
        lambda W, x, lg: moe_apply(
            lambda w, z: jnp.tanh(z @ w[0]), W, x, lg,
            axis_name="ep", capacity=C),
        mesh=mesh, in_specs=(P("ep"), P(), P()), out_specs=P(),
        check_vma=False))(Ws, x, logits)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-6)


def test_moe_grads_flow_to_router_and_experts(devices):
    """Router and expert parameters both receive nonzero gradients through
    the gated combine (Switch-style differentiability)."""
    from bluefog_tpu.parallel.moe import moe_apply
    E, T, d = 4, 8, 6
    rng = np.random.RandomState(1)
    Ws = jnp.asarray(rng.randn(E, d, d) * 0.5, jnp.float32)
    Wr = jnp.asarray(rng.randn(d, E) * 0.5, jnp.float32)
    x = jnp.asarray(rng.randn(T, d), jnp.float32)
    mesh = Mesh(np.asarray(devices[:E]), ("ep",))

    def loss(Ws, Wr):
        out = jax.shard_map(
            lambda W, x, lg: moe_apply(
                lambda w, z: jnp.tanh(z @ w[0]), W, x, lg, axis_name="ep"),
            mesh=mesh, in_specs=(P("ep"), P(), P()), out_specs=P(),
            check_vma=False)(Ws, x, x @ Wr)
        return jnp.sum(out ** 2)

    g_w, g_r = jax.jit(jax.grad(loss, argnums=(0, 1)))(Ws, Wr)
    assert float(jnp.abs(g_w).max()) > 0
    assert float(jnp.abs(g_r).max()) > 0


@pytest.mark.parametrize("split_backward", [False, True])
def test_1f1b_pipeline_matches_sequential_grads(split_backward):
    """pipeline_train_step (1F1B, manual in-scan VJP; with and without the
    ZB-H1 split backward) must reproduce the loss and per-stage gradients
    of running the stages sequentially."""
    n, M, mb, d = 4, 8, 3, 5
    mesh = Mesh(np.asarray(jax.devices()[:n]), ("pp",))
    rng = np.random.RandomState(0)
    Ws = jnp.asarray(rng.randn(n, d, d) * 0.5, jnp.float32)
    bs = jnp.asarray(rng.randn(n, d) * 0.1, jnp.float32)
    x = jnp.asarray(rng.randn(M, mb, d), jnp.float32)
    tgt = jnp.asarray(rng.randn(M, mb, d), jnp.float32)

    def stage_fn(p, xb):
        W, b = p
        return jnp.tanh(xb @ W[0] + b[0])

    def loss_fn(y, t):
        return jnp.mean((y - t) ** 2)

    from bluefog_tpu.parallel import pipeline_train_step
    loss_pp, grads_pp = jax.jit(jax.shard_map(
        lambda p, xb, tb: pipeline_train_step(
            stage_fn, p, xb, tb, loss_fn, axis_name="pp",
            split_backward=split_backward),
        mesh=mesh, in_specs=((P("pp"), P("pp")), P(), P()),
        out_specs=(P(), (P("pp"), P("pp"))), check_vma=False))(
            (Ws, bs), x, tgt)

    def sequential_loss(params):
        Ws, bs = params
        def per_mb(xb, tb):
            h = xb
            for s in range(n):
                h = jnp.tanh(h @ Ws[s] + bs[s])
            return loss_fn(h, tb)
        return jnp.mean(jax.vmap(per_mb)(x, tgt))

    loss_ref, grads_ref = jax.value_and_grad(sequential_loss)((Ws, bs))
    np.testing.assert_allclose(float(loss_pp), float(loss_ref), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(grads_pp[0]),
                               np.asarray(grads_ref[0]), rtol=1e-4,
                               atol=1e-6)
    np.testing.assert_allclose(np.asarray(grads_pp[1]),
                               np.asarray(grads_ref[1]), rtol=1e-4,
                               atol=1e-6)


def test_1f1b_memory_below_gpipe_autodiff():
    """The 1F1B step's compiled temp memory must undercut jax.grad through
    the GPipe scan (whose residuals grow with M) at M >> n."""
    n, M, mb, d = 4, 32, 8, 64
    mesh = Mesh(np.asarray(jax.devices()[:n]), ("pp",))
    rng = np.random.RandomState(1)
    Ws = jnp.asarray(rng.randn(n, d, d) * 0.3, jnp.float32)
    bs = jnp.zeros((n, d), jnp.float32)
    x = jnp.asarray(rng.randn(M, mb, d), jnp.float32)
    tgt = jnp.asarray(rng.randn(M, mb, d), jnp.float32)

    def stage_fn(p, xb):
        W, b = p
        return jnp.tanh(xb @ W[0] + b[0])

    def loss_fn(y, t):
        return jnp.mean((y - t) ** 2)

    from bluefog_tpu.parallel import pipeline_apply, pipeline_train_step

    onef1b = jax.jit(jax.shard_map(
        lambda p, xb, tb: pipeline_train_step(
            stage_fn, p, xb, tb, loss_fn, axis_name="pp"),
        mesh=mesh, in_specs=((P("pp"), P("pp")), P(), P()),
        out_specs=(P(), (P("pp"), P("pp"))), check_vma=False))

    def gpipe_loss(params, xb, tb):
        y = jax.shard_map(
            lambda p, xb: pipeline_apply(stage_fn, p, xb, axis_name="pp"),
            mesh=mesh, in_specs=((P("pp"), P("pp")), P()), out_specs=P(),
            check_vma=False)(params, xb)
        return jnp.mean((y - tb) ** 2)

    gpipe = jax.jit(jax.value_and_grad(gpipe_loss))

    def temp_bytes(fn, *args):
        mem = fn.lower(*args).compile().memory_analysis()
        if mem is None:
            pytest.skip("backend exposes no memory analysis")
        return mem.temp_size_in_bytes

    t_1f1b = temp_bytes(onef1b, (Ws, bs), x, tgt)
    t_gpipe = temp_bytes(gpipe, (Ws, bs), x, tgt)
    assert t_1f1b < t_gpipe, (t_1f1b, t_gpipe)


def test_1f1b_composes_with_decentralized_dp():
    """dp x pp composition: each dp rank runs its own 1F1B pipeline (pp
    axis) and the stage parameters are then combined across dp — the
    reference's decentralized data parallelism layered OVER pipeline
    parallelism in one jitted program.

    Oracle: with identical data on every dp rank and an allreduce combine,
    the composed run must stay replica-identical across dp and match the
    plain single-pipeline 1F1B run exactly.  With per-rank data and a
    dynamic one-peer combine, replicas must converge toward consensus."""
    from bluefog_tpu.ops import collective as C
    from bluefog_tpu.ops import schedule as S
    from bluefog_tpu import topology as topo
    from bluefog_tpu.parallel import pipeline_train_step

    dp, pp, M, mb, d = 4, 2, 4, 3, 5
    mesh = Mesh(np.asarray(jax.devices()[:dp * pp]).reshape(dp, pp),
                ("dp", "pp"))
    rng = np.random.RandomState(0)
    Ws = jnp.asarray(rng.randn(dp, pp, d, d) * 0.5, jnp.float32)
    bs = jnp.asarray(rng.randn(dp, pp, d) * 0.1, jnp.float32)
    x_same = jnp.asarray(rng.randn(1, M, mb, d).repeat(dp, 0), jnp.float32)
    t_same = jnp.asarray(rng.randn(1, M, mb, d).repeat(dp, 0), jnp.float32)

    def stage_fn(p, xb):
        W, b = p
        return jnp.tanh(xb @ W[0, 0] + b[0, 0])

    def loss_fn(y, t):
        return jnp.mean((y - t) ** 2)

    lr = 0.1
    dyn = S.compile_dynamic(topo.one_peer_exp2_phases(dp), dp)

    def make_step(combine):
        def body(p, xb, tb, step):
            loss, g = pipeline_train_step(
                stage_fn, p, xb[0], tb[0], loss_fn, axis_name="pp")
            new = jax.tree.map(lambda a, b_: a - lr * b_, p, g)
            new = jax.tree.map(lambda a: combine(a, step), new)
            return new, loss
        return jax.jit(jax.shard_map(
            body, mesh=mesh,
            in_specs=((P("dp", "pp"), P("dp", "pp")), P("dp"), P("dp"),
                      P()),
            out_specs=((P("dp", "pp"), P("dp", "pp")), P()),
            check_vma=False))

    # -- oracle: identical data + allreduce over dp == plain 1F1B ---------
    ar_step = make_step(lambda a, step: C.allreduce(a, "dp", average=True))
    params = (Ws[:1].repeat(dp, 0), bs[:1].repeat(dp, 0))  # same init
    for step in range(3):
        params, loss = ar_step(params, x_same, t_same,
                               jnp.asarray(step, jnp.int32))
    W_out = np.asarray(params[0])
    np.testing.assert_allclose(W_out, W_out[:1].repeat(dp, 0),
                               rtol=1e-6, atol=1e-7)  # replica-identical

    pp_mesh = Mesh(np.asarray(jax.devices()[:pp]), ("pp",))
    # per-device stage params must be (1, 1, d, d) exactly as in the
    # composed mesh, so stage_fn's W[0, 0] indexing matches.
    ref = (Ws[0][:, None], bs[0][:, None])  # (pp, 1, d, d) / (pp, 1, d)

    def ref_body(p, xb, tb):
        loss, g = pipeline_train_step(
            stage_fn, p, xb, tb, loss_fn, axis_name="pp")
        return jax.tree.map(lambda a, b_: a - lr * b_, p, g), loss
    ref_step = jax.jit(jax.shard_map(
        ref_body, mesh=pp_mesh,
        in_specs=((P("pp"), P("pp")), P(), P()),
        out_specs=((P("pp"), P("pp")), P()), check_vma=False))
    rp = ref
    for _ in range(3):
        rp, _ = ref_step(rp, x_same[0], t_same[0])
    np.testing.assert_allclose(W_out[0], np.asarray(rp[0])[:, 0],
                               rtol=1e-5, atol=1e-6)

    # -- decentralized: per-rank data + one-peer combine -> consensus -----
    dyn_step = make_step(
        lambda a, step: C.dynamic_neighbor_allreduce(a, step, dyn, "dp"))
    x_diff = jnp.asarray(rng.randn(dp, M, mb, d), jnp.float32)
    t_diff = jnp.asarray(rng.randn(dp, M, mb, d), jnp.float32)
    params = (Ws, bs)
    first_spread = None
    for step in range(8):
        params, loss = dyn_step(params, x_diff, t_diff,
                                jnp.asarray(step, jnp.int32))
        W_now = np.asarray(params[0])
        spread = np.abs(W_now - W_now.mean(0, keepdims=True)).max()
        if first_spread is None:
            first_spread = spread
    assert np.isfinite(float(loss))
    assert spread < first_spread, (spread, first_spread)


@pytest.mark.parametrize("split_backward", [False, True])
def test_interleaved_1f1b_matches_sequential_grads(split_backward):
    """Interleaved 1F1B (v virtual stage chunks per rank; plain and ZB-H1
    split-backward): loss and per-chunk gradients must reproduce the
    sequential n*v-stage stack."""
    n, v, M, mb, d = 4, 2, 6, 3, 5
    S = n * v
    mesh = Mesh(np.asarray(jax.devices()[:n]), ("pp",))
    rng = np.random.RandomState(0)
    # Global stage s = c*n + r lives at chunk_params[r][c]: build from a
    # flat (S, d, d) stack so the sequential oracle is unambiguous.
    Wflat = jnp.asarray(rng.randn(S, d, d) * 0.4, jnp.float32)
    bflat = jnp.asarray(rng.randn(S, d) * 0.1, jnp.float32)
    # rank-major (n, v, ...) layout: [r][c] = stage c*n + r
    Ws = jnp.stack([jnp.stack([Wflat[c * n + r] for c in range(v)])
                    for r in range(n)])
    bs = jnp.stack([jnp.stack([bflat[c * n + r] for c in range(v)])
                    for r in range(n)])
    x = jnp.asarray(rng.randn(M, mb, d), jnp.float32)
    tgt = jnp.asarray(rng.randn(M, mb, d), jnp.float32)

    def stage_fn(p, xb):
        W, b = p
        return jnp.tanh(xb @ W + b)

    def loss_fn(y, t):
        return jnp.mean((y - t) ** 2)

    from bluefog_tpu.parallel import pipeline_train_step_interleaved

    def body(p, xb, tb):
        # strip the shard axis: per-device leaves are (1, v, ...)
        loss, g = pipeline_train_step_interleaved(
            stage_fn, jax.tree.map(lambda a: a[0], p), xb, tb, loss_fn,
            axis_name="pp", split_backward=split_backward)
        return loss, jax.tree.map(lambda a: a[None], g)

    loss_pp, grads_pp = jax.jit(jax.shard_map(
        body, mesh=mesh, in_specs=((P("pp"), P("pp")), P(), P()),
        out_specs=(P(), (P("pp"), P("pp"))), check_vma=False))(
            (Ws, bs), x, tgt)

    def sequential_loss(flat):
        Wf, bf = flat
        def per_mb(xb, tb):
            h = xb
            for s in range(S):
                h = jnp.tanh(h @ Wf[s] + bf[s])
            return loss_fn(h, tb)
        return jnp.mean(jax.vmap(per_mb)(x, tgt))

    loss_ref, grads_ref = jax.value_and_grad(sequential_loss)((Wflat, bflat))
    np.testing.assert_allclose(float(loss_pp), float(loss_ref), rtol=1e-5)
    gW = np.asarray(grads_pp[0])   # (n, v, d, d)
    gb = np.asarray(grads_pp[1])
    for r in range(n):
        for c in range(v):
            s = c * n + r
            np.testing.assert_allclose(gW[r, c], np.asarray(grads_ref[0])[s],
                                       rtol=1e-4, atol=1e-6,
                                       err_msg=f"stage {s} W grads")
            np.testing.assert_allclose(gb[r, c], np.asarray(grads_ref[1])[s],
                                       rtol=1e-4, atol=1e-6,
                                       err_msg=f"stage {s} b grads")


def test_interleaved_v1_degenerates_to_plain_1f1b():
    """v=1 chunk per rank must reproduce pipeline_train_step exactly."""
    n, M, mb, d = 4, 5, 2, 4
    mesh = Mesh(np.asarray(jax.devices()[:n]), ("pp",))
    rng = np.random.RandomState(3)
    Ws = jnp.asarray(rng.randn(n, d, d) * 0.4, jnp.float32)
    bs = jnp.asarray(rng.randn(n, d) * 0.1, jnp.float32)
    x = jnp.asarray(rng.randn(M, mb, d), jnp.float32)
    tgt = jnp.asarray(rng.randn(M, mb, d), jnp.float32)

    def stage_fn(p, xb):
        W, b = p
        return jnp.tanh(xb @ W + b)

    def loss_fn(y, t):
        return jnp.mean((y - t) ** 2)

    from bluefog_tpu.parallel import (pipeline_train_step,
                                      pipeline_train_step_interleaved)

    def plain(p, xb, tb):
        loss, g = pipeline_train_step(
            stage_fn, jax.tree.map(lambda a: a[0], p), xb, tb, loss_fn,
            axis_name="pp")
        return loss, jax.tree.map(lambda a: a[None], g)

    def inter(p, xb, tb):
        loss, g = pipeline_train_step_interleaved(
            stage_fn, jax.tree.map(lambda a: a[0][None], p), xb, tb,
            loss_fn, axis_name="pp")
        return loss, jax.tree.map(lambda a: a[0][None], g)

    run = lambda body: jax.jit(jax.shard_map(
        body, mesh=mesh, in_specs=((P("pp"), P("pp")), P(), P()),
        out_specs=(P(), (P("pp"), P("pp"))), check_vma=False))(
            (Ws, bs), x, tgt)
    l1, g1 = run(plain)
    l2, g2 = run(inter)
    np.testing.assert_allclose(float(l1), float(l2), rtol=1e-6)
    for a, b in zip(jax.tree_util.tree_leaves(g1),
                    jax.tree_util.tree_leaves(g2)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-6)


def test_decentralized_combine_over_tp_sharded_params(devices):
    """The decentralized neighbor combine composes with Megatron-sharded
    parameters: rank-major replicas whose weight matrices are column-
    sharded over a tp axis are averaged over the dp axis shard-by-shard —
    each (dp, tp) device exchanges ONLY its own tp slice (no tp
    collectives, no resharding), and the result matches the dense
    per-replica oracle."""
    from jax.sharding import NamedSharding
    from bluefog_tpu.ops import collective as C
    from bluefog_tpu.ops import schedule as S
    from bluefog_tpu import topology as topo

    dp, tp, d = 4, 2, 8
    mesh = Mesh(np.asarray(devices[:dp * tp]).reshape(dp, tp),
                ("dp", "tp"))
    rng = np.random.RandomState(0)
    # rank-major replicas of a column-parallel weight: (dp, d, 4d),
    # sharded P("dp", None, "tp") — the Megatron qkv/up-proj layout.
    W = jnp.asarray(rng.randn(dp, d, 4 * d), jnp.float32)
    W = jax.device_put(W, NamedSharding(mesh, P("dp", None, "tp")))

    G = topo.ExponentialTwoGraph(dp)
    sched = S.compile_static(G, use_topo_weights=False)

    def combine(w):
        return C.neighbor_allreduce(w[0], sched, "dp")[None]

    fn = jax.jit(jax.shard_map(
        combine, mesh=mesh,
        in_specs=P("dp", None, "tp"), out_specs=P("dp", None, "tp"),
        check_vma=False))
    out = fn(W)
    # The exchange must ride dp ONLY: in the (dp, tp) device grid, dp
    # neighbors are tp devices apart, so every collective-permute pair in
    # the compiled HLO must differ by a multiple of tp.  A tp-axis
    # collective (implicit gather/reshard regression) would pair adjacent
    # device ids.
    import re
    hlo = fn.lower(W).compile().as_text()
    pairs = re.findall(r"source_target_pairs=\{([^}]*(?:\},\{[^}]*)*)\}",
                       hlo)
    found = re.findall(r"\{(\d+),(\d+)\}", " ".join(pairs))
    assert found, "expected ppermute pairs in the compiled HLO"
    for a, b in found:
        assert (int(b) - int(a)) % tp == 0, \
            f"collective pairs devices {a}->{b}: not a dp-axis hop"
    w_uni = S.uniform_weights(topo.weight_matrix(G))
    expected = np.einsum("sd,s...->d...", w_uni, np.asarray(W))
    np.testing.assert_allclose(np.asarray(out), expected, rtol=1e-5,
                               atol=1e-6)


def test_moe_composes_with_decentralized_dp(devices):
    """ep x dp in ONE shard_map program: each dp rank trains its own
    replica of a router + an ep-sharded expert bank, the Switch
    load-balance aux loss in the objective, and the decentralized combine
    on the dp axis (VERDICT r3 next-round #5).

    Oracles: (a) one composed train step with identical data and an
    allreduce dp-combine matches the DENSE single-device step (task +
    aux gradients, incl. the 1/E psum scaling for replicated-router
    grads) exactly; (b) with per-rank data and a static neighbor combine,
    replicas move toward consensus and losses stay finite."""
    from bluefog_tpu.ops import collective as C
    from bluefog_tpu.ops import schedule as S
    from bluefog_tpu import topology as topo
    from bluefog_tpu.parallel.moe import (load_balance_loss, moe_apply,
                                          switch_dispatch)
    from jax import lax

    dp, E, T, d, CAP = 2, 4, 16, 6, 8
    AUXW = 0.01
    lr = 0.1
    mesh = Mesh(np.asarray(jax.devices()[:dp * E]).reshape(dp, E),
                ("dp", "ep"))
    rng = np.random.RandomState(0)
    Ws0 = jnp.asarray(rng.randn(E, d, d) * 0.5, jnp.float32)
    Wr0 = jnp.asarray(rng.randn(d, E) * 0.5, jnp.float32)
    x1 = jnp.asarray(rng.randn(T, d), jnp.float32)
    t1 = jnp.asarray(rng.randn(T, d), jnp.float32)

    # -- dense single-device reference step -------------------------------
    def dense_loss(Ws, Wr, x, t):
        lg = x @ Wr
        combine, dispatch = switch_dispatch(lg, E, CAP)
        y = jnp.zeros_like(x)
        for e in range(E):
            ye = jnp.tanh((dispatch[e] @ x) @ Ws[e])
            y = y + jnp.moveaxis(combine, 1, 0)[e] @ ye
        return jnp.mean((y - t) ** 2) + AUXW * load_balance_loss(lg)

    dWs, dWr = jax.grad(dense_loss, argnums=(0, 1))(Ws0, Wr0, x1, t1)
    ref_Ws = np.asarray(Ws0 - lr * dWs)
    ref_Wr = np.asarray(Wr0 - lr * dWr)

    # -- composed ep x dp step --------------------------------------------
    def body(Ws, Wr, x, t, step, combine):
        # shapes inside: Ws (1, 1, d, d) [dp, ep sharded]; Wr (1, d, E);
        # x/t (1, T, d) [dp sharded].
        def loss_fn(Ws, Wr):
            lg = x[0] @ Wr[0]
            y, aux = moe_apply(lambda w, z: jnp.tanh(z @ w[0, 0]),
                               Ws, x[0], lg, axis_name="ep",
                               capacity=CAP, with_aux=True)
            # Per-rank objective = global loss / E (the moe_apply gradient
            # convention: the psum transpose otherwise inflates every
            # grad by E).
            return ((jnp.mean((y - t[0]) ** 2) + AUXW * aux)
                    / lax.axis_size("ep"))
        loss, (gWs, gWr) = jax.value_and_grad(loss_fn,
                                              argnums=(0, 1))(Ws, Wr)
        gWr = lax.psum(gWr, "ep")  # replicated router: sum ep partials
        loss = lax.psum(loss, "ep")  # true global loss for reporting
        Ws = Ws - lr * gWs
        Wr = Wr - lr * gWr
        # Decentralized combine over the dp axis (replica mixing).
        Ws = combine(Ws, step)
        Wr = combine(Wr, step)
        return Ws, Wr, loss[None]  # (1,): this dp rank's loss

    def make_step(combine):
        return jax.jit(jax.shard_map(
            lambda Ws, Wr, x, t, step: body(Ws, Wr, x, t, step, combine),
            mesh=mesh,
            in_specs=(P("dp", "ep"), P("dp"), P("dp"), P("dp"), P()),
            out_specs=(P("dp", "ep"), P("dp"), P("dp")),
            check_vma=False))

    # (a) identical data + allreduce over dp == the dense step
    ar = make_step(lambda a, s: C.allreduce(a, "dp", average=True))
    Ws = Ws0[None].repeat(dp, 0)                       # (dp, E, d, d)
    Wr = Wr0[None].repeat(dp, 0)                       # (dp, d, E)
    xs = x1[None].repeat(dp, 0)
    ts = t1[None].repeat(dp, 0)
    Ws1, Wr1, loss = ar(Ws, Wr, xs, ts, jnp.asarray(0, jnp.int32))
    for r in range(dp):
        np.testing.assert_allclose(np.asarray(Ws1[r]), ref_Ws,
                                   rtol=2e-5, atol=2e-6)
        np.testing.assert_allclose(np.asarray(Wr1[r]), ref_Wr,
                                   rtol=2e-5, atol=2e-6)

    # (b) per-rank data + neighbor combine: finite, converging replicas
    sched = S.compile_static(topo.RingGraph(dp), use_topo_weights=False)
    nar = make_step(lambda a, s: C.neighbor_allreduce(a, sched, "dp"))
    xs2 = jnp.asarray(rng.randn(dp, T, d), jnp.float32)
    ts2 = jnp.asarray(rng.randn(dp, T, d), jnp.float32)
    Ws, Wr = Ws0[None].repeat(dp, 0), Wr0[None].repeat(dp, 0)
    Ws = Ws + jnp.asarray(rng.randn(dp, E, d, d) * 0.1, jnp.float32)
    for s in range(5):
        Ws, Wr, loss = nar(Ws, Wr, xs2, ts2, jnp.asarray(s, jnp.int32))
        assert np.isfinite(float(loss.sum())), s
    spread0 = float(np.abs(np.asarray(Ws)[0] - np.asarray(Ws)[1]).max())
    assert spread0 < 0.1 * 2  # replicas pulled together by the combine


def test_switch_dispatch_mask_excludes_padding():
    """Padding tokens (all-zero logits, argmax -> expert 0) must not occupy
    capacity slots, receive routing, or skew the load-balance statistic
    when the validity mask is supplied."""
    from bluefog_tpu.parallel.moe import load_balance_loss, switch_dispatch
    E, C = 2, 2
    logits = jnp.concatenate([jnp.zeros((3, E), jnp.float32),
                              jnp.asarray([[2.0, 0.0]] * 3, jnp.float32)])
    valid = jnp.asarray([0.0, 0.0, 0.0, 1.0, 1.0, 1.0])
    cm, dm = switch_dispatch(logits, E, C, valid)
    _, du = switch_dispatch(logits, E, C)
    # UNMASKED: the pads fill expert 0's queue, real tokens are dropped.
    assert float(du[0, :, 3:].sum()) == 0.0
    # MASKED: pads route nowhere; the first two real tokens get the slots.
    assert float(dm[0, :, :3].sum()) == 0.0
    assert float(dm[0, :, 3:5].sum()) == 2.0
    assert float(cm[:3].sum()) == 0.0
    # The masked aux loss equals the loss over the real tokens alone.
    np.testing.assert_allclose(float(load_balance_loss(logits, valid)),
                               float(load_balance_loss(logits[3:])),
                               rtol=1e-6)


def test_dp_tp_pp_composed_in_one_program(devices):
    """dp x tp x pp in ONE shard_map program (VERDICT r3 next-round #10):
    each dp replica runs a pp-deep pipeline whose stages are tp-sharded
    Megatron MLPs (column-parallel in, row-parallel out, one psum), with
    the decentralized combine on the dp axis after the optimizer step.

    Oracle at (dp, tp, pp) = (2, 2, 2): identical data + the uniform
    2-ring neighbor combine (== the exact average at dp=2) must reproduce
    the DENSE sequential stack's loss and updated parameters exactly (the
    tp replicated-loss convention — divide the microbatch loss by the tp
    axis size — keeps gradients unscaled)."""
    from bluefog_tpu.ops import collective as C
    from bluefog_tpu.parallel import pipeline_train_step
    from jax import lax

    dp, tp, pp, M, mb, d, hid = 2, 2, 2, 4, 3, 6, 8
    lr = 0.1
    mesh = Mesh(np.asarray(jax.devices()[:dp * tp * pp]).reshape(dp, tp, pp),
                ("dp", "tp", "pp"))
    rng = np.random.RandomState(0)
    Wi = jnp.asarray(rng.randn(pp, d, hid) * 0.4, jnp.float32)
    Wo = jnp.asarray(rng.randn(pp, hid, d) * 0.4, jnp.float32)
    x = jnp.asarray(rng.randn(M, mb, d), jnp.float32)
    tgt = jnp.asarray(rng.randn(M, mb, d), jnp.float32)

    # -- dense sequential reference --------------------------------------
    def seq_loss(params):
        Wi, Wo = params
        def per_mb(xb, tb):
            h = xb
            for s in range(pp):
                h = jnp.maximum(h @ Wi[s], 0.0) @ Wo[s]
            return jnp.mean((h - tb) ** 2)
        return jnp.mean(jax.vmap(per_mb)(x, tgt))

    loss_ref, g_ref = jax.value_and_grad(seq_loss)((Wi, Wo))
    ref_Wi = np.asarray(Wi - lr * g_ref[0])
    ref_Wo = np.asarray(Wo - lr * g_ref[1])

    # -- composed program -------------------------------------------------
    def stage_fn(p, xb):
        wi, wo = p  # local: (1, 1, 1, d, hid/tp), (1, 1, 1, hid/tp, d)
        h = jnp.maximum(xb @ wi[0, 0, 0], 0.0)    # column-parallel
        return lax.psum(h @ wo[0, 0, 0], "tp")    # row-parallel + combine

    def mb_loss(y, t):
        # tp replicated-loss convention: every tp rank computes the same
        # loss from the psum'd activation; dividing by the axis size keeps
        # the psum-transposed gradients exact.
        return jnp.mean((y - t) ** 2) / lax.axis_size("tp")

    # The DECENTRALIZED combine on dp: at dp=2 on a uniform-weight ring,
    # neighbor averaging equals the exact average, so the dense oracle
    # covers the real gossip path (schedule + ppermute pairing on a
    # 3-axis mesh), not just C.allreduce.
    from bluefog_tpu.ops import schedule as S
    from bluefog_tpu import topology as topo
    sched = S.compile_static(topo.RingGraph(dp), use_topo_weights=False)

    def body(p, xb, tb):
        loss, g = pipeline_train_step(
            stage_fn, p, xb[0], tb[0], mb_loss, axis_name="pp")
        p = jax.tree.map(lambda a, b: a - lr * b, p, g)
        p = jax.tree.map(
            lambda a: C.neighbor_allreduce(a, sched, "dp"), p)
        return p, (loss * lax.axis_size("tp"))[None]

    step = jax.jit(jax.shard_map(
        body, mesh=mesh,
        in_specs=((P("dp", "tp", "pp"), P("dp", "tp", "pp")),
                  P("dp"), P("dp")),
        out_specs=((P("dp", "tp", "pp"), P("dp", "tp", "pp")), P("dp")),
        check_vma=False))

    # Layouts: Wi (dp, tp, pp, d, hid/tp) — tp shards the HIDDEN axis; the
    # shard_map in_spec shards the leading replica axes, so pre-split the
    # hidden axis into the tp position.
    Wi_l = jnp.stack([Wi[:, :, k * (hid // tp):(k + 1) * (hid // tp)]
                      for k in range(tp)])               # (tp, pp, d, h/tp)
    Wo_l = jnp.stack([Wo[:, k * (hid // tp):(k + 1) * (hid // tp), :]
                      for k in range(tp)])               # (tp, pp, h/tp, d)
    Wi_g = Wi_l[None].repeat(dp, 0)                      # (dp, tp, pp, ...)
    Wo_g = Wo_l[None].repeat(dp, 0)
    xs = x[None].repeat(dp, 0)
    ts = tgt[None].repeat(dp, 0)

    (Wi1, Wo1), loss = step((Wi_g, Wo_g), xs, ts)
    np.testing.assert_allclose(float(loss[0]), float(loss_ref), rtol=1e-5)
    # Reassemble the tp shards and compare every dp replica to the dense
    # sequential update.
    for r in range(dp):
        got_Wi = np.concatenate([np.asarray(Wi1[r, k]) for k in range(tp)],
                                axis=-1)
        got_Wo = np.concatenate([np.asarray(Wo1[r, k]) for k in range(tp)],
                                axis=-2)
        np.testing.assert_allclose(got_Wi, ref_Wi, rtol=2e-5, atol=2e-6)
        np.testing.assert_allclose(got_Wo, ref_Wo, rtol=2e-5, atol=2e-6)


def test_dp_tp_pp_ep_composed_in_one_program(devices):
    """ALL FOUR parallelism forms in ONE shard_map program (VERDICT r4
    next-round #8): each dp replica runs a pipeline (pp) of stages whose
    dense sublayer is tensor-parallel and whose switch-MoE sublayer is
    expert-parallel — on 8 devices tp and ep share the model-parallel
    'mp' mesh axis (a real deployment pattern; the 16+-device dryrun uses
    distinct axes) — and the decentralized ring combine mixes the dp
    replicas after the update.  Oracle: with identical data, one composed
    step equals the DENSE sequential step exactly (loss and all four
    parameter families: tp-sharded dense in/out, expert-local, replicated
    router), pinning every gradient psum in the composition."""
    from jax import lax

    from bluefog_tpu.ops import collective as C
    from bluefog_tpu.ops import schedule as S
    from bluefog_tpu import topology as topo
    from bluefog_tpu.parallel import moe_apply, pipeline_train_step
    from bluefog_tpu.parallel.moe import switch_dispatch

    dp, mp, pp = 2, 2, 2
    d, hid, E, M, mb, CAP = 6, 8, 2, 4, 4, 4
    lr = 0.1
    mesh = Mesh(np.asarray(devices[:8]).reshape(dp, mp, pp),
                ("dp", "mp", "pp"))
    rng = np.random.RandomState(0)
    Wi = jnp.asarray(rng.randn(pp, d, hid) * 0.4, jnp.float32)
    Wo = jnp.asarray(rng.randn(pp, hid, d) * 0.4, jnp.float32)
    We = jnp.asarray(rng.randn(pp, E, d, d) * 0.4, jnp.float32)
    Wr = jnp.asarray(rng.randn(pp, d, E) * 0.4, jnp.float32)
    x = jnp.asarray(rng.randn(M, mb, d), jnp.float32)
    tgt = jnp.asarray(rng.randn(M, mb, d), jnp.float32)

    # -- dense sequential reference ---------------------------------------
    def dense_loss(Wi, Wo, We, Wr):
        def stage(s, z):
            y = jnp.maximum(z @ Wi[s], 0.0) @ Wo[s]
            lg = y @ Wr[s]
            combine, dispatch = switch_dispatch(lg, E, CAP)
            y2 = jnp.zeros_like(y)
            for e in range(E):
                ye = jnp.tanh((dispatch[e] @ y) @ We[s, e])
                y2 = y2 + jnp.moveaxis(combine, 1, 0)[e] @ ye
            return y + y2
        losses = []
        for m in range(M):
            z = x[m]
            for s in range(pp):
                z = stage(s, z)
            losses.append(jnp.mean((z - tgt[m]) ** 2))
        return jnp.mean(jnp.asarray(losses))

    loss_ref, g_ref = jax.value_and_grad(dense_loss, argnums=(0, 1, 2, 3))(
        Wi, Wo, We, Wr)
    refs = [np.asarray(w - lr * g)
            for w, g in zip((Wi, Wo, We, Wr), g_ref)]

    # -- composed program --------------------------------------------------
    NL = 3  # leading (dp, mp, pp) mesh dims on every param leaf

    def stage_fn(p, xb):
        wi, wo, we, wr = (a.reshape(a.shape[NL:]) for a in p)
        h = jnp.maximum(xb @ wi, 0.0)             # column-parallel
        y = lax.psum(h @ wo, "mp")                # row-parallel + combine
        y2 = moe_apply(lambda w, z: jnp.tanh(z @ w), we, y, y @ wr,
                       axis_name="mp", capacity=CAP)
        return y + y2

    def mb_loss(y, t):
        # Replicated-loss convention: the output is psum-replicated over
        # mp, so divide the per-rank objective by the axis size.
        return jnp.mean((y - t) ** 2) / lax.axis_size("mp")

    sched = S.compile_static(topo.RingGraph(dp), use_topo_weights=False)

    def body(p, xb, tb):
        loss, g = pipeline_train_step(stage_fn, p, xb[0], tb[0], mb_loss,
                                      axis_name="pp")
        gwi, gwo, gwe, gwr = g
        gwr = lax.psum(gwr, "mp")    # replicated router: sum partials
        p = jax.tree.map(lambda a, b: a - lr * b, p, (gwi, gwo, gwe, gwr))
        p = jax.tree.map(lambda a: C.neighbor_allreduce(a, sched, "dp"), p)
        return p, (loss * lax.axis_size("mp"))[None]

    P4 = P("dp", "mp", "pp")
    step = jax.jit(jax.shard_map(
        body, mesh=mesh,
        in_specs=((P4, P4, P4, P4), P("dp"), P("dp")),
        out_specs=((P4, P4, P4, P4), P("dp")), check_vma=False))

    hs = hid // mp
    Wi_l = jnp.stack([Wi[:, :, k * hs:(k + 1) * hs] for k in range(mp)])
    Wo_l = jnp.stack([Wo[:, k * hs:(k + 1) * hs, :] for k in range(mp)])
    We_l = jnp.stack([We[:, k] for k in range(mp)])   # expert k on mp rank k
    Wr_l = jnp.stack([Wr for _ in range(mp)])         # replicated router
    lead = lambda a: jnp.broadcast_to(a[None], (dp,) + a.shape)
    params = tuple(lead(a) for a in (Wi_l, Wo_l, We_l, Wr_l))
    xs = jnp.broadcast_to(x[None], (dp,) + x.shape)
    ts = jnp.broadcast_to(tgt[None], (dp,) + tgt.shape)

    newp, loss = step(params, xs, ts)
    np.testing.assert_allclose(float(loss[0]), float(loss_ref), rtol=1e-5)
    for r in range(dp):
        got = (
            np.concatenate([np.asarray(newp[0][r, k]) for k in range(mp)],
                           axis=-1),
            np.concatenate([np.asarray(newp[1][r, k]) for k in range(mp)],
                           axis=-2),
            np.stack([np.asarray(newp[2][r, k]) for k in range(mp)],
                     axis=1),
            np.asarray(newp[3][r, 0]),
        )
        for name, g, w in zip(("Wi", "Wo", "We", "Wr"), got, refs):
            np.testing.assert_allclose(g, w, rtol=2e-4, atol=2e-5,
                                       err_msg=name)
