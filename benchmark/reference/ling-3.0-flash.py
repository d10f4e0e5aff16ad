"""Plain float32 reference of the ``ling-3.0-flash`` configuration.

The forward pass and training loss of the share of Ling-3.0-flash
(``model_type`` ``bailing_hybrid``) that one chip holds, in straightforward
``jax.numpy``.  ``d`` is the hidden size, ``x`` the residual, ``y =
RMSNorm(x)`` with a learned scale and ``rms_norm_eps``; no bias anywhere.

1. *Block* ``i``: ``x <- x + Mixer_i(RMSNorm(x))``, ``x <- x + F_i(RMSNorm(
   x))``.  The mixer is gated latent attention where ``(i + 1) %
   layer_group_size == 0`` and Kimi Delta Attention otherwise; ``F`` is a
   dense SwiGLU of ``intermediate_size`` in the first
   ``first_k_dense_replace`` layers and the expert layer after.  A last
   RMSNorm; an untied head over the ``vocab_size`` rows held.
2. *Kimi Delta Attention* (arXiv:2510.26692, section 3), ``H =
   num_attention_heads`` heads of ``D = head_dim`` for queries, keys and
   values alike.  ``[q' | k' | v'] = y W_qkv`` (``3 H D`` columns); each of
   the ``3 H D`` channels passes its own causal convolution of
   ``short_conv_kernel_size`` taps, ``c_t = sum_j w[:, j] u_{t - (L - 1) +
   j}``, zeros left of the sequence, then SiLU (``linear_silu``); ``q = q' /
   sqrt(sum q'^2 + 1e-6)`` over each head's ``D`` values and the same for
   ``k``; ``q`` times ``D^-0.5``.  Log decays a channel of the key: ``g_t =
   kda_lower_bound * sigmoid(exp(A_log_h) * (y W_f + dt_bias)_t)``, in
   ``(kda_lower_bound, 0)``; ``beta_t = sigmoid(y W_beta)``, a value a head.
   **The rule**, a state ``S`` of ``D x D`` a head, zero before the first
   token, one step a token: ``S' = Diag(exp(g_t)) S_{t-1}``; ``S_t = S' +
   beta_t k_t (v_t - S'^T k_t)^T``; ``o_t = S_t^T q_t``.  Then ``o_t <-
   RMSNorm_D(o_t) * sigmoid(y W_g)`` a head and channel, one learned scale
   of ``D`` shared by the heads; the result is ``concat_h(o_t) W_o``.
3. *Gated latent attention* (``q_lora_rank`` null).  ``q = y W_q`` as ``(H,
   nope + rope)``; ``[c, k_r] = y W_kva`` (``kv_lora_rank + rope``), ``c <-
   RMSNorm(c)``; ``[k_n, v] = c W_kvb`` as ``(H, nope + v)``; ``q``'s last
   ``rope`` dims and the one ``k_r`` all heads share are rotated by plain
   RoPE at ``rope_theta``; ``k_h = [k_n,h ; k_r]``; scores ``q . k /
   sqrt(nope + rope)``, softmax over the keys at or before the query;
   ``o_h <- sigmoid(y W_gate)_h o_h``, one value a head
   (``gated_attention_proj_granularity_type`` ``head_wise``); the result is
   ``concat_h(o_h) W_o``.
4. *Experts* (``noaux_tc`` with groups).  ``s = sigmoid(y W_r)`` over all
   ``router_width`` experts; the choice is made on ``c = s + b``: the
   experts lie in ``n_group`` consecutive groups, a group's score is the sum
   of its two largest ``c``, the ``topk_group`` groups of the largest score
   stay (the lower group on a tie) and **no expert of another group can be
   chosen**; the ``num_experts_per_tok`` largest ``c`` of what stays are
   chosen; ``w = s[chosen] / (sum s[chosen] + 1e-20) *
   routed_scaling_factor``; ``out = sum_{j chosen and held} w_j E_j(y) +
   E_shared(y)``, ``E(y) = down(silu(gate y) * up y)``.  **The chip's
   share**: the ``num_experts`` experts from ``experts_first`` on are held
   here, and what the absent experts would have added is left out; that
   partial result goes on to the next layer.  ``b`` receives no gradient;
   the new ``b_e = b_e + router_bias_update_rate * sign(mean(load) -
   load_e)`` over the counts of all ``router_width`` experts is returned in
   ``aux``.
5. *Loss.*  Mean next-token cross-entropy over every position and the
   ``vocab_size`` rows held, targets ``roll(tokens, -1)``.  No auxiliary
   loss, no multi-token-prediction module (its published loss weight is 0).

No kernels, no chunks, no sorting and no grouped product: the rule runs
**token by token** (``lax.scan`` over the positions) exactly as 2 writes it,
the taps are explicit shifts, the groups' scores are explicit sums, every
held expert is applied to every token and the result is masked by the
choice, the scores are full ``(S, S)`` matrices and the logits the full
``(S, vocab)``.  Two concessions to the chip's memory, which give the same
numbers: each block is computed a second time in the backward pass
(``jax.checkpoint`` around a block), and the scan over the positions is two
scans, stretches of ``_STRETCH`` steps inside a scan over the stretches,
each stretch computed a second time in the backward pass (a step's state is
2.1 MB a sequence at the published widths: kept are one state a stretch and
one stretch's 64).  The caller runs it under
``jax.default_matmul_precision("highest")``.  Written from the descriptions
above; it shares no code with ``bluefog_tpu``; sizes are read from the
configuration file's source keys and weights from the program's parameter
tree by name.

Departures, each shared with the program so that the two can be compared:

* ``W_q``, ``W_k`` and ``W_v`` of a KDA layer are one matrix ``qkv`` whose
  columns lie ``[q | k | v]``, and their taps one leaf in the same order:
  layout only;
* the rotary pairs are ``(i, i + rope/2)`` (half-split) applied to the
  projections' columns as they lie; the source (``rope_interleave`` true)
  first permutes the columns from interleaved pairs: a fixed permutation of
  columns of ``W_q`` and ``W_kva``;
* the target of the last position is the first token (``roll``), as in the
  program's loss.

Returns ``(loss, aux)`` with the program's ``aux`` (per-layer ``load`` and
the new ``bias``) and, beside it, ``experts``: the chosen experts
``(layers, B, S, k)``.
"""

import functools

import jax
import jax.numpy as jnp

_STRETCH = 64


def _rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * scale


def _rope(x, theta):
    """Rotate pairs ``(i, i + D/2)`` of ``(B, S, H, D)`` by ``position *
    theta^(-2i/D)``."""
    half = x.shape[3] // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angle = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * freq
    cos, sin = jnp.cos(angle)[None, :, None, :], jnp.sin(angle)[None, :, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _delta_rule(q, k, v, g, beta):
    """``o`` of the rule, one step a token: ``q``, ``k``, ``g`` ``(B, S, H,
    D)``, ``v`` ``(B, S, H, D)``, ``beta`` ``(B, S, H)``."""
    batch, seq, heads, dim = q.shape
    pad = -seq % _STRETCH

    def stretches(x):
        # a padded step has g = 0 and beta = 0: the state passes through
        x = jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2))
        x = x.reshape((batch, -1, _STRETCH) + x.shape[2:])
        return jnp.moveaxis(x, (1, 2), (0, 1))      # (stretch, step, B, ...)

    def step(state, now):
        q_t, k_t, v_t, g_t, beta_t = now
        decayed = jnp.exp(g_t)[..., None] * state              # S'
        told = jnp.einsum("bhkv,bhk->bhv", decayed, k_t)       # S'^T k_t
        state = decayed + beta_t[..., None, None] * (
            k_t[..., :, None] * (v_t - told)[..., None, :])
        return state, jnp.einsum("bhkv,bhk->bhv", state, q_t)

    @jax.checkpoint
    def stretch(state, steps):
        return jax.lax.scan(step, state, steps)

    _, out = jax.lax.scan(
        stretch, jnp.zeros((batch, heads, dim, v.shape[-1]), q.dtype),
        tuple(stretches(x) for x in (q, k, v, g, beta)))
    out = jnp.moveaxis(out, (0, 1), (1, 2)).reshape(batch, -1, heads,
                                                    v.shape[-1])
    return out[:, :seq]


def _kda(y, p, cfg):
    """``y`` ``(B, S, d)``, already normed; returns the mixer's result."""
    batch, seq, _ = y.shape
    heads, dim = cfg["num_attention_heads"], cfg["head_dim"]
    taps, inner = cfg["short_conv_kernel_size"], heads * dim
    assert cfg["num_kv_heads_for_linear_attn"] == 0 and cfg["linear_silu"]
    assert cfg["no_kda_lora"] and not cfg["use_kda_lora"]
    assert cfg["kda_safe_gate"] and cfg["group_norm_size"] == 1
    assert not cfg["use_bias"] and not cfg["use_qkv_bias"]
    assert p["qkv"]["kernel"].shape[1] == 3 * inner
    assert p["conv_w"].shape == (3 * inner, taps)
    assert p["f"]["kernel"].shape[1] == inner == p["dt_bias"].shape[0]
    assert p["A_log"].shape == (heads,) and p["norm_scale"].shape == (dim,)
    qkv = y @ p["qkv"]["kernel"]
    conv = sum(p["conv_w"][:, taps - 1 - back]
               * jnp.pad(qkv, ((0, 0), (back, 0), (0, 0)))[:, :seq]
               for back in range(taps))
    qkv = jax.nn.silu(conv).reshape(batch, seq, 3, heads, dim)

    def unit(x):
        return x / jnp.sqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)
    q, k, v = unit(qkv[:, :, 0]) * dim ** -0.5, unit(qkv[:, :, 1]), \
        qkv[:, :, 2]
    f = (y @ p["f"]["kernel"] + p["dt_bias"]).reshape(batch, seq, heads, dim)
    g = cfg["kda_lower_bound"] * jax.nn.sigmoid(
        jnp.exp(p["A_log"])[:, None] * f)
    beta = jax.nn.sigmoid(y @ p["beta"]["kernel"])             # (B, S, H)
    o = _delta_rule(q, k, v, g, beta)
    o = _rms_norm(o, p["norm_scale"], cfg["rms_norm_eps"]) * jax.nn.sigmoid(
        (y @ p["gate"]["kernel"]).reshape(batch, seq, heads, dim))
    return o.reshape(batch, seq, inner) @ p["out"]["kernel"]


def _attention(y, p, cfg):
    """``y`` ``(B, S, d)``, already normed; returns the sub-layer's result."""
    batch, seq, _ = y.shape
    heads, eps = cfg["num_attention_heads"], cfg["rms_norm_eps"]
    nope, rope = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    vdim, rank = cfg["v_head_dim"], cfg["kv_lora_rank"]
    assert cfg["q_lora_rank"] is None and cfg["rope_scaling"] is None
    assert "q_a" not in p and not cfg["use_mla_nope"]
    assert cfg["gated_attention_proj_granularity_type"] == "head_wise"
    q = (y @ p["q"]["kernel"]).reshape(batch, seq, heads, nope + rope)
    latent = y @ p["kv_a"]["kernel"]
    assert latent.shape[-1] == rank + rope
    c = _rms_norm(latent[..., :rank], p["kv_a_norm"]["scale"], eps)
    kv = (c @ p["kv_b"]["kernel"]).reshape(batch, seq, heads, nope + vdim)
    q_r = _rope(q[..., nope:], cfg["rope_theta"])
    k_r = _rope(latent[..., rank:].reshape(batch, seq, 1, rope),
                cfg["rope_theta"])
    scores = (jnp.einsum("bqhd,bkhd->bhqk", q[..., :nope], kv[..., :nope])
              + jnp.einsum("bqhd,bkd->bhqk", q_r, k_r[:, :, 0])) \
        * (nope + rope) ** -0.5
    at = jnp.arange(seq)
    probs = jax.nn.softmax(
        jnp.where(at[None, :] <= at[:, None], scores, -jnp.inf), axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", probs, kv[..., nope:])
    assert p["attn_gate"]["kernel"].shape[1] == heads
    out = out * jax.nn.sigmoid(y @ p["attn_gate"]["kernel"])[..., None]
    return out.reshape(batch, seq, heads * vdim) @ p["proj"]["kernel"]


def _swiglu(y, gate, up, down):
    return (jax.nn.silu(y @ gate) * (y @ up)) @ down


def _experts(y, p, bias, cfg):
    """``(out, load, chosen)`` of the expert layer's share on the normed
    input ``y`` ``(B, S, d)``: the held experts' part plus the shared one."""
    width, k = cfg["router_width"], cfg["num_experts_per_tok"]
    held, first = cfg["num_experts"], cfg.get("experts_first", 0)
    groups, stay = cfg["n_group"], cfg["topk_group"]
    assert p["gate"].shape == (held, y.shape[-1],
                               cfg["moe_intermediate_size"])
    assert cfg["score_function"] == "sigmoid" and cfg["norm_topk_prob"]
    assert cfg["moe_router_enable_expert_bias"]
    scores = jax.nn.sigmoid(y @ p["router"]["kernel"])         # (B, S, E)
    assert scores.shape[-1] == width and width % groups == 0
    choice = scores + jax.lax.stop_gradient(bias)
    by_group = choice.reshape(choice.shape[:-1] + (groups, width // groups))
    # a group's score: its largest value and the largest of the rest
    first_at = jnp.argmax(by_group, axis=-1)
    largest = jnp.max(by_group, axis=-1)
    second = jnp.max(jnp.where(
        jnp.arange(width // groups) == first_at[..., None], -jnp.inf,
        by_group), axis=-1)
    group_score = largest + second                             # (B, S, G)
    _, kept = jax.lax.top_k(group_score, stay)
    stays = jax.nn.one_hot(kept, groups, dtype=scores.dtype).sum(axis=-2)
    eligible = jnp.repeat(stays > 0, width // groups, axis=-1)  # (B, S, E)
    _, chosen = jax.lax.top_k(jnp.where(eligible, choice, -jnp.inf), k)
    picked = jax.nn.one_hot(chosen, width, dtype=scores.dtype)  # (B,S,k,E)
    top = jnp.take_along_axis(scores, chosen, axis=-1)
    top = top / (top.sum(axis=-1, keepdims=True) + 1e-20) \
        * cfg["routed_scaling_factor"]
    weight = (picked * top[..., None]).sum(axis=-2)            # (B, S, E)

    def add_expert(out, e):
        return out + weight[..., first + e, None] * _swiglu(
            y, p["gate"][e], p["up"][e], p["down"][e]), None

    out, _ = jax.lax.scan(add_expert, jnp.zeros_like(y), jnp.arange(held))
    assert p["shared_gate"]["kernel"].shape[1] == cfg[
        "num_shared_experts"] * cfg["moe_shared_expert_intermediate_size"]
    out = out + _swiglu(y, p["shared_gate"]["kernel"],
                        p["shared_up"]["kernel"], p["shared_down"]["kernel"])
    return out, picked.sum(axis=(0, 1, 2)), chosen


def _block(x, p, bias, *, i, cfg):
    """Block ``i`` on the residual ``x``; ``(x, (load, chosen))``, the pair
    None in a dense layer."""
    eps = cfg["rms_norm_eps"]
    y = _rms_norm(x, p["RMSNorm_0"]["scale"], eps)
    if (i + 1) % cfg["layer_group_size"] == 0:
        assert "kda" not in p
        x = x + _attention(y, p["mla"], cfg)
    else:
        assert "mla" not in p
        x = x + _kda(y, p["kda"], cfg)
    y = _rms_norm(x, p["RMSNorm_1"]["scale"], eps)
    if i < cfg["first_k_dense_replace"]:
        assert p["gate"]["kernel"].shape[1] == cfg["intermediate_size"]
        return x + _swiglu(y, p["gate"]["kernel"], p["up"]["kernel"],
                           p["down"]["kernel"]), None
    out, load, chosen = _experts(y, p["moe"], bias, cfg)
    return x + out, (jax.lax.stop_gradient(load), chosen)


def loss(params, aux, tokens, *, cfg):
    """Training loss of the rows ``tokens`` ``(B, S)``; returns ``(loss,
    aux)`` like the program's loss."""
    assert cfg["num_nextn_predict_layers"] == 0
    x = params["wte"]["embedding"][tokens]
    loads, chosen, biases, layer = [], [], [], 0
    for i in range(cfg["num_hidden_layers"]):
        sparse = i >= cfg["first_k_dense_replace"]
        bias = aux["bias"][layer] if sparse else None
        x, found = jax.checkpoint(functools.partial(_block, i=i, cfg=cfg))(
            x, params[f"block_{i}"], bias)
        if not sparse:
            continue
        load, picks = found
        loads.append(load.astype(jnp.int32))
        chosen.append(picks)
        biases.append(bias + cfg["router_bias_update_rate"]
                      * jnp.sign(load.mean() - load))
        layer += 1
    x = _rms_norm(x, params["RMSNorm_0"]["scale"], cfg["rms_norm_eps"])
    logits = x @ params["lm_head"]["kernel"]
    assert logits.shape[-1] == cfg["vocab_size"]
    logp = jax.nn.log_softmax(logits, axis=-1)
    targets = jnp.roll(tokens, -1, axis=1)
    nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
    return jnp.mean(nll), {"load": jnp.stack(loads),
                           "bias": jnp.stack(biases),
                           "experts": jnp.stack(chosen)}
