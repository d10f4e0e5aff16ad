"""Plain float32 reference of the ``nemotron-twotower-30b-a3b`` configuration.

The forward pass and training loss of the share of ONE tower of
Nemotron-Labs-TwoTower-30B-A3B-Base that one chip holds: the causal tower
that the published ``config.json`` (``model_type`` ``nemotron_h``) defines,
trained as a causal LM.  The second tower (a denoiser with adaLN,
bidirectional attention inside a block, cross-tower conditioning) and the
block-diffusion objective are not in the config and not here.  In
straightforward ``jax.numpy``; ``d`` the hidden size, ``x`` the residual,
``y = RMSNorm(x)`` with a learned scale and ``norm_eps`` (1e-5) throughout;
no bias but the convolution's.

1. *Block* ``l``: ``x <- x + mixer_l(RMSNorm_l(x))``, ONE mixer a block by
   character ``l`` of ``hybrid_override_pattern``: ``M`` a Mamba-2 mixer,
   ``E`` the experts, ``*`` attention.  After the last block one RMSNorm and
   the untied head over the ``vocab_size`` rows held (a slice of the
   published vocabulary; ids are drawn from the slice); mean next-token
   cross-entropy and nothing else (the source gives no auxiliary
   coefficient).
2. *Mamba-2* (``M``), ``H = mamba_num_heads`` heads of ``P =
   mamba_head_dim``, inner width ``I = H P``, ``G = n_groups`` groups with a
   state of ``N = ssm_state_size``: ``[z | xBC | dt] = y W_in`` with ``I +
   (I + 2 G N) + H`` columns.  ``xBC <- silu(conv(xBC) + b)``: causal,
   depthwise, ``conv_kernel`` taps, ``conv_t = sum_j w[:, j] xBC_{t - (L -
   1) + j}``, zeros left of the sequence.  ``xBC`` splits into ``x`` (``H``
   heads of ``P``), ``B`` and ``C`` (``G`` groups of ``N``; head ``h`` reads
   group ``h // (H / G)``).  ``dt <- softplus(dt + dt_bias)``
   (``time_step_limit`` (0, inf) clips nothing), ``A = -exp(A_log)`` a head,
   ``a_t = exp(dt_t A)``.  The recurrence, a head: ``h_t = a_t h_{t-1} +
   dt_t x_t B_t^T`` (``P x N``, from zero), ``o_t = h_t C_t + D x_t``.
   Then ``o <- RMSNorm_g(o * silu(z))``: each of the ``G`` groups of ``I /
   G`` values normed on its own, one scale of ``I``; the result is ``o
   W_out``.
3. *Experts* (``E``): ``s = sigmoid(y W_r)`` over all ``router_width``
   experts; the ``num_experts_per_tok`` largest of ``s + bias`` are chosen
   (``bias``: state that no gradient reaches; ``n_group`` 1 and
   ``topk_group`` 1 limit nothing); ``w = s[chosen] / (sum s[chosen] +
   1e-20) * routed_scaling_factor``; ``out = sum_{j chosen and held} w_j
   E_j(y) + E_shared(y)`` with ``E(y) = relu(y W_up)^2 W_down``: un-gated,
   width ``moe_intermediate_size``, the shared one
   ``moe_shared_expert_intermediate_size``, on every token.  **The chip's
   share**: the ``n_routed_experts`` experts from ``experts_first`` on are
   held here, and what the absent experts would have added is left out;
   that partial result goes on to the next block.  After the forward the
   bias moves by ``router_bias_update_rate * sign(mean(load) - load)``.
4. *Attention* (``*``): ``q = y W_q`` as ``num_attention_heads`` heads of
   ``head_dim``, ``k`` and ``v`` as ``num_key_value_heads`` heads; query
   head ``j`` reads K/V head ``j // (heads / kv_heads)``; **no positional
   encoding** (the ``nemotron_h`` modelling code applies none and does not
   read ``rope_theta``); scores ``q . k / sqrt(head_dim)``, softmax over the
   keys at or before the query; the result is ``concat_h(o_h) W_o``.

No kernels, no chunked scan, no sorting and no grouped product: the
recurrence runs **step by step** (``lax.scan`` over the positions), every
held expert is applied to every token and the result is masked by the
choice, the scores are full ``(S, S)`` matrices, the logits the full ``(S,
vocab)``.  Two concessions to the chip's memory, which give the same
numbers: each block is computed a second time in the backward pass
(``jax.checkpoint`` around ``_block``), and the scan over the positions is
two scans, stretches of ``_STRETCH`` steps inside a scan over the stretches,
each stretch computed a second time in the backward pass (a step's state is
2.1 MB a sequence at the published widths, so 2048 kept steps would be 4.3
GB a layer; kept are 32 states and one stretch's 64); the attention scores
are made one K/V head's query heads at a time.  The caller runs it under
``jax.default_matmul_precision("highest")``.  Written from the descriptions
above; it shares no code with ``bluefog_tpu``; sizes are read from the
configuration file's source keys and weights from the program's parameter
tree by name.

Departures from the ``nemotron_h`` modelling code, each shared with the
program so that the two can be compared:

* ``W_k`` and ``W_v`` are one matrix ``kv`` whose columns lie K/V head by
  head as ``[k_g v_g]`` (the source has two matrices): layout only;
* the gated norm, the time steps and the decays are float32 on both sides;
* the bias update is the sign rule of ``lfm2-24b-a2b``'s and
  ``xing4.0-29b-a4b``'s references (the configuration file's ``assumed``);
* the target of the last position is the first token (``roll``), as in the
  program's loss.

Returns ``(loss, aux)`` with the program's ``aux`` (per expert layer the
``load`` and the moved ``bias``) and, beside it, ``experts``: the chosen
experts ``(layers, B, S, k)``.
"""

import functools

import jax
import jax.numpy as jnp

_STRETCH = 64


def _rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * scale


def _recurrence(x, dt, a, b, c):
    """``o_t = h_t C_t`` of ``h_t = a_t h_{t-1} + dt_t x_t B_t^T``, one step
    a position: ``x`` ``(B, S, H, P)``, ``dt`` and ``a`` ``(B, S, H)``,
    ``b`` and ``c`` ``(B, S, H, N)`` (each head its group's)."""
    batch, seq, heads, dim = x.shape
    pad = -seq % _STRETCH

    def stretches(v, fill=0.0):
        # a padded step has a = 1 and dt = 0: the state passes through
        v = jnp.pad(v, ((0, 0), (0, pad)) + ((0, 0),) * (v.ndim - 2),
                    constant_values=fill)
        v = v.reshape((batch, -1, _STRETCH) + v.shape[2:])
        return jnp.moveaxis(v, (1, 2), (0, 1))      # (stretch, step, B, ...)

    def step(h, now):
        x_t, dt_t, a_t, b_t, c_t = now
        h = a_t[..., None, None] * h \
            + (dt_t[..., None] * x_t)[..., None] * b_t[..., None, :]
        return h, jnp.einsum("bhpn,bhn->bhp", h, c_t)

    @jax.checkpoint
    def stretch(h, steps):
        return jax.lax.scan(step, h, steps)

    _, out = jax.lax.scan(
        stretch, jnp.zeros((batch, heads, dim, b.shape[-1]), x.dtype),
        (stretches(x), stretches(dt), stretches(a, 1.0), stretches(b),
         stretches(c)))
    out = jnp.moveaxis(out, (0, 1), (1, 2)).reshape(batch, -1, heads, dim)
    return out[:, :seq]


def _mamba(y, p, cfg):
    """``y`` ``(B, S, d)``, already normed; returns the mixer's result."""
    batch, seq, _ = y.shape
    heads, dim = cfg["mamba_num_heads"], cfg["mamba_head_dim"]
    groups, state = cfg["n_groups"], cfg["ssm_state_size"]
    taps, inner = cfg["conv_kernel"], heads * dim
    wide = inner + 2 * groups * state
    assert p["in"]["kernel"].shape[1] == inner + wide + heads
    assert p["conv_w"].shape == (wide, taps) and cfg["use_conv_bias"]
    assert not cfg["mamba_proj_bias"] and cfg["mamba_hidden_act"] == "silu"
    zxbcdt = y @ p["in"]["kernel"]
    z, xbc, dt = (zxbcdt[..., :inner], zxbcdt[..., inner:inner + wide],
                  zxbcdt[..., inner + wide:])
    conv = p["conv_b"] + sum(
        p["conv_w"][:, taps - 1 - back]
        * jnp.pad(xbc, ((0, 0), (back, 0), (0, 0)))[:, :seq]
        for back in range(taps))
    xbc = jax.nn.silu(conv)
    x = xbc[..., :inner].reshape(batch, seq, heads, dim)
    share = heads // groups             # head h reads group h // share
    b = jnp.repeat(xbc[..., inner:inner + groups * state].reshape(
        batch, seq, groups, state), share, axis=2)
    c = jnp.repeat(xbc[..., inner + groups * state:].reshape(
        batch, seq, groups, state), share, axis=2)
    dt = jax.nn.softplus(dt + p["dt_bias"])
    a = jnp.exp(dt * -jnp.exp(p["A_log"]))
    out = _recurrence(x, dt, a, b, c) + p["D"][:, None] * x
    gated = (out.reshape(batch, seq, inner) * jax.nn.silu(z)).reshape(
        batch, seq, groups, inner // groups)
    gated = gated * jax.lax.rsqrt(
        jnp.mean(gated * gated, axis=-1, keepdims=True) + cfg["norm_eps"])
    return (gated.reshape(batch, seq, inner) * p["norm_scale"]) \
        @ p["out"]["kernel"]


def _attention(y, p, cfg):
    batch, seq, _ = y.shape
    heads, groups = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    dim = cfg["head_dim"]
    assert p["q"]["kernel"].shape[1] == heads * dim and not cfg[
        "attention_bias"]
    q = (y @ p["q"]["kernel"]).reshape(batch, seq, groups, heads // groups,
                                       dim)
    kv = (y @ p["kv"]["kernel"]).reshape(batch, seq, groups, 2, dim)
    seen = jnp.arange(seq)[:, None] >= jnp.arange(seq)[None, :]
    out = []
    for g in range(groups):     # query head j reads K/V head j // share
        k, v = kv[:, :, g, 0], kv[:, :, g, 1]
        scores = jnp.einsum("bqhd,bkd->bhqk", q[:, :, g], k) \
            / jnp.sqrt(jnp.float32(dim))
        probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
        out.append(jnp.einsum("bhqk,bkd->bqhd", probs, v))
    return jnp.concatenate(out, axis=2).reshape(
        batch, seq, heads * dim) @ p["proj"]["kernel"]


def _relu2(y, up, down):
    return jnp.square(jax.nn.relu(y @ up)) @ down


def _experts(y, p, bias, cfg):
    """``(out, load, chosen)`` of the expert layer's share on the normed
    input ``y`` ``(B, S, d)``: the held experts' part and the shared one."""
    width, k = cfg["router_width"], cfg["num_experts_per_tok"]
    held, first = cfg["n_routed_experts"], cfg["experts_first"]
    assert p["up"].shape == (held, y.shape[-1], cfg["moe_intermediate_size"])
    assert "gate" not in p and cfg["mlp_hidden_act"] == "relu2"
    assert cfg["norm_topk_prob"] and cfg["n_group"] == cfg["topk_group"] == 1
    scores = jax.nn.sigmoid(y @ p["router"]["kernel"])         # (B, S, E)
    assert scores.shape[-1] == width
    _, chosen = jax.lax.top_k(scores + jax.lax.stop_gradient(bias), k)
    top = jnp.take_along_axis(scores, chosen, axis=-1)
    top = top / (top.sum(axis=-1, keepdims=True) + 1e-20) \
        * cfg["routed_scaling_factor"]
    picked = jax.nn.one_hot(chosen, width, dtype=scores.dtype)  # (B,S,k,E)
    weight = (picked * top[..., None]).sum(axis=-2)             # (B, S, E)

    def add_expert(out, e):
        return out + weight[..., first + e, None] * _relu2(
            y, p["up"][e], p["down"][e]), None

    out, _ = jax.lax.scan(add_expert, jnp.zeros_like(y), jnp.arange(held))
    assert p["shared_up"]["kernel"].shape[1] == cfg[
        "moe_shared_expert_intermediate_size"] and cfg["n_shared_experts"] == 1
    out = out + _relu2(y, p["shared_up"]["kernel"],
                       p["shared_down"]["kernel"])
    return out, picked.sum(axis=(0, 1, 2)).astype(jnp.int32), chosen


def _block(x, p, bias, *, kind: str, cfg):
    """One block on the residual ``x``: ``(x, stats)``, ``stats`` the expert
    layer's ``(load, chosen)`` or None."""
    y = _rms_norm(x, p["RMSNorm_0"]["scale"], cfg["norm_eps"])
    assert "RMSNorm_1" not in p          # one part a block, one norm
    if kind == "M":
        return x + _mamba(y, p["mamba"], cfg), None
    if kind == "*":
        return x + _attention(y, p, cfg), None
    assert kind == "E"
    out, *stats = _experts(y, p["moe"], bias, cfg)
    return x + out, tuple(stats)


def loss(params, aux, tokens, *, cfg):
    """Training loss of ``tokens`` ``(B, S)``; returns ``(loss, aux)`` like
    the program's loss."""
    pattern = cfg["hybrid_override_pattern"]
    assert len(pattern) == cfg["num_hidden_layers"]
    assert not cfg["tie_word_embeddings"] and "wpe" not in params
    assert params["lm_head"]["kernel"].shape[1] == cfg["vocab_size"]
    assert cfg["norm_eps"] == cfg["layer_norm_epsilon"]
    x = params["wte"]["embedding"][tokens]
    stats, expert_layer = [], 0
    for i, kind in enumerate(pattern):
        bias = None
        if kind == "E":
            bias, expert_layer = aux["bias"][expert_layer], expert_layer + 1
        # the same numbers twice: a block's activations are not kept for the
        # backward pass but computed again
        x, found = jax.checkpoint(functools.partial(
            _block, kind=kind, cfg=cfg))(x, params[f"block_{i}"], bias)
        if found is not None:
            stats.append(found)
    loads, chosen = zip(*stats)
    x = _rms_norm(x, params["RMSNorm_0"]["scale"], cfg["norm_eps"])
    logp = jax.nn.log_softmax(x @ params["lm_head"]["kernel"], axis=-1)
    targets = jnp.roll(tokens, -1, axis=1)
    nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
    load = jnp.stack(loads)
    counts = load.astype(jnp.float32)
    moved = aux["bias"] + cfg["router_bias_update_rate"] * jnp.sign(
        counts.mean(axis=-1, keepdims=True) - counts)
    return jnp.mean(nll), {"load": load, "bias": moved,
                           "experts": jnp.stack(chosen)}
