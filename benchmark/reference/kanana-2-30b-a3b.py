"""Plain float32 reference of the ``kanana-2-30b-a3b`` configuration.

The forward pass and training loss of the share of
kanana-2-30b-a3b-instruct-2601 (``model_type`` ``deepseek_v3``) that one
chip holds, on a packed row, in straightforward ``jax.numpy``.  ``y =
RMSNorm(x)`` with ``rms_norm_eps``; ``d(t)`` is token ``t``'s document
(``segment_ids``) and ``p_t`` its position counted from its document's first
token (``positions``).

1. *Block.*  ``x <- x + Attn(RMSNorm(x))``, ``x <- x + F(RMSNorm(x))``; ``F``
   is a dense SwiGLU of ``intermediate_size`` in the first
   ``first_k_dense_replace`` layers and the expert layer after; a last
   RMSNorm; an untied head.
2. *Latent attention* (``q_lora_rank`` null: no query bottleneck).  ``q = y
   W_q`` as ``(H, nope + rope)``; ``[c, k_r] = y W_kva`` (``kv_lora_rank +
   rope``), ``c <- RMSNorm(c)``; ``[k_n, v] = c W_kvb`` as ``(H, nope + v)``;
   ``q``'s last ``rope`` dims and the one ``k_r`` all heads share are
   rotated by plain RoPE at ``rope_theta`` **over** ``p_t`` (``rope_scaling``
   null: no YaRN, no ``mscale``); ``k_h = [k_n,h ; k_r]``; scores ``q . k /
   sqrt(nope + rope)``; **key j is visible to query i iff j <= i and d(j) =
   d(i)**; softmax; ``out = concat_h(p v_h) W_o``.  No bias anywhere.
3. *Experts* (``noaux_tc``).  ``s = sigmoid(y W_r)`` over all
   ``router_width`` experts; the ``num_experts_per_tok`` largest of ``s + b``
   are chosen (``n_group = topk_group = 1``: no group limit); ``w = s[chosen]
   / (sum s[chosen] + 1e-20) * routed_scaling_factor``; ``out = sum_{j chosen
   and held} w_j E_j(y) + E_shared(y)``, ``E(y) = down(silu(gate y) * up
   y)``, the shared one a single SwiGLU of ``n_shared_experts *
   moe_intermediate_size``.  **The chip's share**: the ``n_routed_experts``
   experts from ``experts_first`` on are held here, and what the absent
   experts would have added is left out; that partial result goes on to the
   next layer.  ``b`` receives no gradient; the new ``b_e = b_e +
   router_bias_update_rate * sign(mean(load) - load_e)`` over the counts of
   all ``router_width`` experts is returned in ``aux``.
4. *Loss.*  Mean next-token cross-entropy over every position of the row and
   the ``vocab_size`` rows held, targets ``roll(tokens, -1)``: across a
   boundary the target is the next document's first token, as a packed
   stream has it.  No loss mask, no auxiliary loss.

No kernels, no remat, no chunking, no sorting and no grouped product: every
held expert is applied to every token and the result is masked by the
choice; the full ``(S, S)`` scores under the ``(S, S)`` visibility written
out from the ids, and ``(S, vocab)`` logits.  The caller runs it under
``jax.default_matmul_precision("highest")``.  It is written from the
descriptions above and shares no code with ``bluefog_tpu``; sizes are read
from the configuration file's source keys and weights from the program's
parameter tree by name.

Departures from ``modeling_deepseek_v3.py``, each shared with the program so
that the two can be compared:

* the rotary pairs are ``(i, i + rope/2)`` (half-split) applied to the
  projections' columns as they lie; the source (``rope_interleave`` true)
  first permutes the columns from interleaved pairs ``(2i, 2i + 1)`` to that
  layout.  A fixed permutation of columns of ``W_q`` and ``W_kva``: the same
  model family;
* ``W_kvb``'s columns lie head by head as ``[k_n,h v_h]`` and ``W_q``'s as
  ``[q_n,h q_r,h]``, as in the source;
* the target of the last position is the first token (``roll``), as in the
  program's loss.

Returns ``(loss, aux)`` with the program's ``aux`` (per-layer ``load`` and
the new ``bias``) and, beside it, ``experts``: the chosen experts
``(layers, B, S, k)``, for counting the assignments on which a rounding of
the program's flipped a near tie.
"""

import jax
import jax.numpy as jnp


def _rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * scale


def _rope(x, positions, theta):
    """Rotate pairs ``(i, i + D/2)`` of ``(B, S, H, D)`` by ``positions *
    theta^(-2i/D)``; ``positions`` ``(B, S)``."""
    half = x.shape[3] // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angle = positions.astype(jnp.float32)[..., None] * freq    # (B, S, D/2)
    cos, sin = jnp.cos(angle)[:, :, None, :], jnp.sin(angle)[:, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _attention(y, p, segment_ids, positions, cfg):
    """``y`` ``(B, S, d)``, already normed; returns the sub-layer's result."""
    batch, seq, _ = y.shape
    heads, eps = cfg["num_attention_heads"], cfg["rms_norm_eps"]
    nope, rope = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    vdim, rank = cfg["v_head_dim"], cfg["kv_lora_rank"]
    assert cfg["q_lora_rank"] is None and cfg["rope_scaling"] is None
    assert "q_a" not in p and not cfg["attention_bias"]
    q = (y @ p["q"]["kernel"]).reshape(batch, seq, heads, nope + rope)
    latent = y @ p["kv_a"]["kernel"]
    assert latent.shape[-1] == rank + rope
    c = _rms_norm(latent[..., :rank], p["kv_a_norm"]["scale"], eps)
    kv = (c @ p["kv_b"]["kernel"]).reshape(batch, seq, heads, nope + vdim)
    q_r = _rope(q[..., nope:], positions, cfg["rope_theta"])
    k_r = _rope(latent[..., rank:].reshape(batch, seq, 1, rope), positions,
                cfg["rope_theta"])
    scores = (jnp.einsum("bqhd,bkhd->bhqk", q[..., :nope], kv[..., :nope])
              + jnp.einsum("bqhd,bkd->bhqk", q_r, k_r[:, :, 0])) \
        * (nope + rope) ** -0.5
    at = jnp.arange(seq)
    visible = (at[None, :] <= at[:, None])[None] & (
        segment_ids[:, None, :] == segment_ids[:, :, None])    # (B, q, k)
    probs = jax.nn.softmax(
        jnp.where(visible[:, None], scores, -jnp.inf), axis=-1)
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


def loss(params, aux, tokens, segment_ids, positions, *, cfg):
    """Training loss of the packed rows ``tokens`` ``(B, S)`` with their
    documents ``segment_ids`` and ``positions``; returns ``(loss, aux)``
    like the program's loss."""
    eps = cfg["rms_norm_eps"]
    x = params["wte"]["embedding"][tokens]
    loads, chosen, biases, layer = [], [], [], 0
    for i in range(cfg["num_hidden_layers"]):
        p = params[f"block_{i}"]
        x = x + _attention(_rms_norm(x, p["RMSNorm_0"]["scale"], eps),
                           p["mla"], segment_ids, positions, cfg)
        y = _rms_norm(x, p["RMSNorm_1"]["scale"], eps)
        if i < cfg["first_k_dense_replace"]:
            assert p["gate"]["kernel"].shape[1] == cfg["intermediate_size"]
            x = x + _swiglu(y, p["gate"]["kernel"], p["up"]["kernel"],
                            p["down"]["kernel"])
            continue
        out, load, picks = _experts(y, p["moe"], aux["bias"][layer], cfg)
        x = x + out
        load = jax.lax.stop_gradient(load)
        loads.append(load.astype(jnp.int32))
        chosen.append(picks)
        biases.append(aux["bias"][layer] + cfg["router_bias_update_rate"]
                      * jnp.sign(load.mean() - load))
        layer += 1
    x = _rms_norm(x, params["RMSNorm_0"]["scale"], eps)
    logits = x @ params["lm_head"]["kernel"]
    assert logits.shape[-1] == cfg["vocab_size"]
    logp = jax.nn.log_softmax(logits, axis=-1)
    targets = jnp.roll(tokens, -1, axis=1)
    nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
    return jnp.mean(nll), {"load": jnp.stack(loads),
                           "bias": jnp.stack(biases),
                           "experts": jnp.stack(chosen)}
