"""Plain float32 reference of the ``xing4.0-29b-a4b`` configuration.

The forward pass and training loss of the share of Xing4.0-29B-A4B that one
chip holds, in straightforward ``jax.numpy``.  ``T`` tokens, ``d`` the hidden
size, ``H`` heads, ``n = hc_mult`` residual streams.

1. *Residual streams* (manifold-constrained hyper-connections,
   arXiv:2512.24880 on arXiv:2409.19606).  ``X_i = embed(token)`` for every
   ``i < n``.  Each block has two sub-layers ``F`` (attention, then the
   feed-forward), wrapped alike: ``z = RMSNorm_{hc_eps}(vec(X))`` over the
   ``n * d`` values of a token, with a learned scale; ``[l_pre (n), l_post
   (n), l_res (n * n)] = z @ Phi + b``; ``H_pre = sigmoid(l_pre)``;
   ``H_post = 2 sigmoid(l_post)``; ``M = exp(clip(l_res,
   mhc_h_res_clamp_min, ..._max))`` as ``(n, n)`` (the paper's gain
   ``alpha`` of each part is the scale of ``Phi``'s columns here, the same
   functions; ``Phi`` and ``b`` are one leaf ``phi``, ``b`` its last row),
   then ``hc_sinkhorn_iters`` times: each row divided by its sum
   ``+ hc_eps``, then each column by its sum ``+ hc_eps``; ``H_res = M``.
   ``u = sum_i H_pre[i] X_i``, ``f = F(RMSNorm_{rms_norm_eps}(u))``,
   ``X'_i = sum_j H_res[i, j] X_j + H_post[i] f``.  After the last block the
   streams are summed and normed.
2. *Latent attention* (DeepSeek-V3, arXiv:2412.19437).  ``c_q = RMSNorm(u
   W_qa)``; ``q = c_q W_qb`` as ``(H, nope + rope)``; ``[c_kv, k_r] = u
   W_kva``; ``[k_n, v] = RMSNorm(c_kv) W_kvb`` as ``(H, nope + v)``; rotary
   on ``q``'s last ``rope`` dims and on ``k_r`` (one head, shared by all),
   YaRN frequencies; ``k_h = [k_n,h ; k_r]``; causal softmax of ``q k^T *
   (nope + rope)^-0.5 * m^2``, ``m = 0.1 mscale_all_dim ln(factor) + 1``;
   ``out = concat_h(p v_h) W_o``.  The cos/sin factor
   ``yarn_mscale(factor, mscale) / yarn_mscale(factor, mscale_all_dim)`` is
   applied (1 for this configuration).  No bias anywhere.
3. *Feed-forward.*  The first ``first_k_dense_replace`` blocks: SwiGLU of
   width ``intermediate_size``.  The others: ``s = sigmoid(y W_r)`` over all
   ``router_width`` experts; the ``num_experts_per_tok`` largest of ``s + b``
   are chosen (``n_group = topk_group = 1``: no group limit); ``w =
   s[chosen] / (sum s[chosen] + 1e-20) * routed_scaling_factor``; ``out =
   sum_{j chosen and held} w_j E_j(y) + E_shared(y)``, ``E(y) =
   down(silu(gate y) * up y)``.  **The chip's share**: the ``n_routed_experts``
   experts from ``experts_first`` on are held here, and what the absent
   experts would have added is left out; that partial result goes on to the
   next layer.  ``b`` receives no gradient; the new ``b_e = b_e +
   router_bias_update_rate * sign(mean(load) - load_e)`` over the counts of
   all ``router_width`` experts is returned in ``aux``.
4. *Loss.*  Mean next-token cross-entropy over the ``vocab_size`` rows held
   (a slice of the published vocabulary; ids are drawn from the slice).

No kernels, no remat, no chunking, no sorting and no grouped product: every
held expert is applied to every token and the result is masked by the
choice; the full ``(S, S)`` scores and ``(S, vocab)`` logits.  The caller
runs it under ``jax.default_matmul_precision("highest")``.  It is written
from the descriptions above and shares no code with ``bluefog_tpu``; sizes
are read from the configuration file's source keys and weights from the
program's parameter tree by name.

Departures from ``modeling_deepseek.py``, each shared with the program so
that the two can be compared:

* the rotary pairs are ``(i, i + rope/2)`` (half-split) applied to the
  projections' columns as they lie; the source first permutes the columns
  from interleaved pairs ``(2i, 2i + 1)`` to that layout.  A fixed
  permutation of columns of ``W_qb`` and ``W_kva``: the same model family;
* ``W_kvb`` is one matrix whose columns lie head by head as ``[k_n,h v_h]``,
  as in the source; ``W_qb``'s as ``[q_n,h q_r,h]``, as in the source;
* the target of the last position is the first token (``roll``), as in the
  program's loss;
* the multi-token-prediction module is absent (``num_nextn_predict_layers``
  0): the configuration file says why.

Returns ``(loss, aux)`` with the program's ``aux`` (per-layer ``load`` and
the new ``bias``) and, beside it, ``experts``: the chosen experts
``(layers, B, S, k)``, for counting the assignments on which a rounding of
the program's flipped a near tie.
"""

import math

import jax
import jax.numpy as jnp


def _rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * scale


def _yarn_mscale(factor, mscale):
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def _yarn_frequencies(dim, theta, s):
    """``dim / 2`` frequencies: ``theta^(-2i/dim)`` divided by ``factor``
    for the pairs that turn less than ``beta_slow`` times over the original
    context, left alone above ``beta_fast`` turns, a linear blend between."""
    def pair_of(turns):
        return dim * math.log(s["original_max_position_embeddings"]
                              / (turns * 2 * math.pi)) / (2 * math.log(theta))
    low = max(math.floor(pair_of(s["beta_fast"])), 0)
    high = min(math.ceil(pair_of(s["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    plain = [theta ** (-2 * i / dim) for i in range(dim // 2)]
    ramp = [min(max((i - low) / (high - low), 0.0), 1.0)
            for i in range(dim // 2)]
    return jnp.asarray([f / s["factor"] * r + f * (1 - r)
                        for f, r in zip(plain, ramp)], jnp.float32)


def _rope(x, freq, factor):
    """Rotate pairs ``(i, i + D/2)`` of ``(B, S, H, D)`` by ``pos *
    freq[i]``; ``factor`` multiplies cos and sin."""
    half = x.shape[3] // 2
    angle = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * freq
    cos = jnp.cos(angle)[:, None, :] * factor
    sin = jnp.sin(angle)[:, None, :] * factor
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _attention(y, p, cfg):
    """``y`` ``(B, S, d)``, already normed; returns the sub-layer's result."""
    batch, seq, _ = y.shape
    heads, eps = cfg["num_attention_heads"], cfg["rms_norm_eps"]
    nope, rope = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    vdim, rank = cfg["v_head_dim"], cfg["kv_lora_rank"]
    s = cfg["rope_scaling"]
    assert p["q_a"]["kernel"].shape[1] == cfg["q_lora_rank"]
    c_q = _rms_norm(y @ p["q_a"]["kernel"], p["q_a_norm"]["scale"], eps)
    q = (c_q @ p["q_b"]["kernel"]).reshape(batch, seq, heads, nope + rope)
    latent = y @ p["kv_a"]["kernel"]
    assert latent.shape[-1] == rank + rope
    c_kv = _rms_norm(latent[..., :rank], p["kv_a_norm"]["scale"], eps)
    kv = (c_kv @ p["kv_b"]["kernel"]).reshape(batch, seq, heads, nope + vdim)
    freq = _yarn_frequencies(rope, cfg["rope_theta"], s)
    cs = _yarn_mscale(s["factor"], s["mscale"]) \
        / _yarn_mscale(s["factor"], s["mscale_all_dim"])
    q_r = _rope(q[..., nope:], freq, cs)
    k_r = _rope(latent[..., rank:].reshape(batch, seq, 1, rope), freq, cs)
    scale = (nope + rope) ** -0.5 \
        * _yarn_mscale(s["factor"], s["mscale_all_dim"]) ** 2
    scores = (jnp.einsum("bqhd,bkhd->bhqk", q[..., :nope], kv[..., :nope])
              + jnp.einsum("bqhd,bkd->bhqk", q_r, k_r[:, :, 0])) * scale
    causal = jnp.tril(jnp.ones((seq, seq), bool))
    probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", probs, kv[..., nope:])
    return out.reshape(batch, seq, heads * vdim) @ p["proj"]["kernel"]


def _swiglu(y, gate, up, down):
    return (jax.nn.silu(y @ gate) * (y @ up)) @ down


def _experts(y, p, bias, cfg):
    """``(out, load, chosen)`` of the expert layer's share on the normed
    input ``y`` ``(B, S, d)``: the held experts' part plus the shared one."""
    width, k = cfg["router_width"], cfg["num_experts_per_tok"]
    held, first = cfg["n_routed_experts"], cfg.get("experts_first", 0)
    assert p["gate"].shape == (held, y.shape[-1],
                               cfg["moe_intermediate_size"])
    assert cfg["n_group"] == cfg["topk_group"] == 1
    assert cfg["scoring_func"] == "sigmoid" and cfg["norm_topk_prob"]
    scores = jax.nn.sigmoid(y @ p["router"]["kernel"])        # (B, S, E)
    assert scores.shape[-1] == width
    _, chosen = jax.lax.top_k(scores + jax.lax.stop_gradient(bias), k)
    picked = jax.nn.one_hot(chosen, width, dtype=scores.dtype)  # (B,S,k,E)
    top = jnp.take_along_axis(scores, chosen, axis=-1)
    top = top / (top.sum(axis=-1, keepdims=True) + 1e-20) \
        * cfg["routed_scaling_factor"]
    weight = (picked * top[..., None]).sum(axis=-2)            # (B, S, E)

    def add_expert(out, e):
        return out + weight[..., first + e, None] * _swiglu(
            y, p["gate"][e], p["up"][e], p["down"][e]), None

    out, _ = jax.lax.scan(add_expert, jnp.zeros_like(y), jnp.arange(held))
    assert p["shared_gate"]["kernel"].shape[1] \
        == cfg["n_shared_experts"] * cfg["moe_intermediate_size"]
    out = out + _swiglu(y, p["shared_gate"]["kernel"],
                        p["shared_up"]["kernel"], p["shared_down"]["kernel"])
    return out, picked.sum(axis=(0, 1, 2)), chosen


def _hyper(x, p, cfg):
    """The three maps of one sub-layer on the streams ``x`` ``(B, S, n,
    d)``: ``(u, h_post, h_res)``."""
    n, eps = cfg["hc_mult"], cfg["hc_eps"]
    batch, seq = x.shape[:2]
    z = _rms_norm(x.reshape(batch, seq, -1), p["scale"], eps)
    # phi: Phi in its first n * d rows, [b_pre, b_post, b_res] in its last
    logit = z @ p["phi"][:-1] + p["phi"][-1]               # (B, S, 2n + n*n)
    h_pre = jax.nn.sigmoid(logit[..., :n])
    h_post = 2.0 * jax.nn.sigmoid(logit[..., n:2 * n])
    m = jnp.exp(jnp.clip(logit[..., 2 * n:].reshape(batch, seq, n, n),
                         cfg["mhc_h_res_clamp_min"],
                         cfg["mhc_h_res_clamp_max"]))
    for _ in range(cfg["hc_sinkhorn_iters"]):
        m = m / (m.sum(axis=-1, keepdims=True) + eps)      # rows
        m = m / (m.sum(axis=-2, keepdims=True) + eps)      # columns
    u = jnp.einsum("bsn,bsnd->bsd", h_pre, x)
    return u, h_post, m


def _sub_layer(x, hc, norm_scale, fn, cfg):
    u, h_post, h_res = _hyper(x, hc, cfg)
    f = fn(_rms_norm(u, norm_scale, cfg["rms_norm_eps"]))
    return (jnp.einsum("bsij,bsjd->bsid", h_res, x)
            + h_post[..., None] * f[:, :, None, :])


def loss(params, aux, tokens, *, cfg):
    """Training loss of ``tokens`` ``(B, S)``; returns ``(loss, aux)`` like
    the program's loss."""
    n = cfg["hc_mult"]
    x = params["wte"]["embedding"][tokens]
    x = jnp.broadcast_to(x[:, :, None, :], x.shape[:2] + (n, x.shape[-1]))
    loads, chosen, biases, layer = [], [], [], 0
    for i in range(cfg["num_hidden_layers"]):
        p = params[f"block_{i}"]
        x = _sub_layer(x, p["hc_attn"], p["RMSNorm_0"]["scale"],
                       lambda y: _attention(y, p["mla"], cfg), cfg)
        if i < cfg["first_k_dense_replace"]:
            assert p["gate"]["kernel"].shape[1] == cfg["intermediate_size"]
            ffn = lambda y: _swiglu(  # noqa: E731
                y, p["gate"]["kernel"], p["up"]["kernel"],
                p["down"]["kernel"])
        else:
            found = {}

            def ffn(y, bias=aux["bias"][layer]):
                out, found["load"], found["chosen"] = _experts(
                    y, p["moe"], bias, cfg)
                return out
        x = _sub_layer(x, p["hc_ffn"], p["RMSNorm_1"]["scale"], ffn, cfg)
        if i >= cfg["first_k_dense_replace"]:
            load = jax.lax.stop_gradient(found["load"])
            loads.append(load.astype(jnp.int32))
            chosen.append(found["chosen"])
            biases.append(aux["bias"][layer] + cfg["router_bias_update_rate"]
                          * jnp.sign(load.mean() - load))
            layer += 1
    x = _rms_norm(x.sum(axis=2), params["RMSNorm_0"]["scale"],
                  cfg["rms_norm_eps"])
    logits = x @ params["lm_head"]["kernel"]
    assert logits.shape[-1] == cfg["vocab_size"]
    logp = jax.nn.log_softmax(logits, axis=-1)
    targets = jnp.roll(tokens, -1, axis=1)
    nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
    return jnp.mean(nll), {"load": jnp.stack(loads),
                           "bias": jnp.stack(biases),
                           "experts": jnp.stack(chosen)}
