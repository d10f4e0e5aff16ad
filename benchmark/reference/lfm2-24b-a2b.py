"""Plain float32 reference of the ``lfm2-24b-a2b`` configuration.

The forward pass and training loss of the share of LFM2-24B-A2B that one
chip holds, in straightforward ``jax.numpy``.  ``d`` the hidden size, ``y =
RMSNorm(x)`` with a learned scale and ``norm_eps``.

1. *Block* ``i``: ``h = x + Mixer_i(RMSNorm_op(x))``, ``out = h +
   FFN_i(RMSNorm_ffn(h))``.
2. *Mixer*, ``layer_types[i] == "conv"`` (gated short convolution,
   ``conv_L_cache`` taps, no bias): ``[B, C, X] = split_3(y W_in)``; ``u = B
   * X``; ``c_t = sum_j w[:, j] * u_{t - (L - 1) + j}`` for ``j = 0 .. L -
   1``, each of the ``d`` channels on its own, zeros left of the sequence;
   the result is ``(C * c) W_out``.  No normalisation, no activation.
3. *Mixer*, ``layer_types[i] == "full_attention"``: ``q = y W_q`` as
   ``num_attention_heads`` heads, ``[k, v] = y W_kv`` as
   ``num_key_value_heads`` heads each; ``q`` and ``k`` pass an RMSNorm over
   each head's own values (one learned scale of the head dim for ``q``, one
   for ``k``), then the rotary embedding (``rope_theta``); causal softmax of
   ``q k^T / sqrt(head dim)``, query head ``j`` reading K/V head ``j //
   group``; the heads' results go through ``W_o``.  No bias anywhere.
4. *FFN*.  The first ``num_dense_layers`` blocks: ``(silu(y W_1) * (y W_3))
   W_2`` of width ``intermediate_size``.  The others: ``s = sigmoid(y W_g)``
   over all ``router_width`` experts; the ``num_experts_per_tok`` largest of
   ``s + b`` are chosen; ``w = s[chosen] / (sum s[chosen] +
   router_renorm_eps) * routed_scaling_factor``; ``out = sum_{j chosen and
   held} w_j E_j(y)``, ``E`` a SwiGLU of width ``moe_intermediate_size``.
   No shared expert.  **The chip's share**: the ``num_experts`` experts from
   ``experts_first`` on are held here, and what the absent experts would
   have added is left out; that partial result goes on to the next layer.
   ``b`` receives no gradient; the new ``b_e = b_e +
   router_bias_update_rate * sign(mean(load) - load_e)`` over the counts of
   all ``router_width`` experts is returned in ``aux``.
5. *Head and loss.*  One RMSNorm after the last block, then logits by the
   transposed embedding (tied); mean next-token cross-entropy over the
   ``vocab_size`` rows held (a slice of the published vocabulary; ids are
   drawn from the slice).

No kernels, no remat, no chunking, no sorting and no grouped product: every
held expert is applied to every token and the result is masked by the
choice; the convolution is an explicit sum over its shifts; the full ``(S,
S)`` scores and ``(S, vocab)`` logits.  The caller runs it under
``jax.default_matmul_precision("highest")``.  It is written from the
descriptions above and shares no code with ``bluefog_tpu``; sizes are read
from the configuration file's source keys and weights from the program's
parameter tree by name.

Departures from the family's modelling code, each shared with the program so
that the two can be compared:

* the rotary pairs are ``(i, i + D/2)`` (half-split) applied to the
  projections' columns as they lie;
* ``W_kv`` is one matrix whose columns lie K/V head by head as ``[k_g
  v_g]``; ``W_in``'s as ``[B C X]``, as in the source;
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


def _rope(x, theta):
    """Rotate pairs ``(i, i + D/2)`` of ``(B, S, H, D)`` by ``pos *
    theta^(-2i/D)``."""
    half = x.shape[3] // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angle = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * freq
    cos, sin = jnp.cos(angle)[:, None, :], jnp.sin(angle)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _short_conv(y, p, cfg):
    """``y`` ``(B, S, d)``, already normed; returns the mixer's result."""
    taps, seq = cfg["conv_L_cache"], y.shape[1]
    assert not cfg["conv_bias"] and p["w"].shape == (y.shape[-1], taps)
    gate_b, gate_c, x = jnp.split(y @ p["in"]["kernel"], 3, axis=-1)
    u = gate_b * x
    conv = jnp.zeros_like(u)
    for j in range(taps):
        back = taps - 1 - j               # u_{t - back}, zero before t = 0
        shifted = jnp.concatenate(
            [jnp.zeros_like(u[:, :back]), u], axis=1)[:, :seq]
        conv = conv + p["w"][:, j] * shifted
    return (gate_c * conv) @ p["out"]["kernel"]


def _attention(y, p, cfg):
    """``y`` ``(B, S, d)``, already normed; returns the mixer's result."""
    batch, seq, hidden = y.shape
    heads, groups = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    dim, eps = hidden // heads, cfg["norm_eps"]
    q = (y @ p["q"]["kernel"]).reshape(batch, seq, heads, dim)
    kv = (y @ p["kv"]["kernel"]).reshape(batch, seq, groups, 2, dim)
    k, v = kv[:, :, :, 0], kv[:, :, :, 1]
    assert p["q_norm"]["scale"].shape == p["k_norm"]["scale"].shape == (dim,)
    theta = cfg["rope_parameters"]["rope_theta"]
    q = _rope(_rms_norm(q, p["q_norm"]["scale"], eps), theta)
    k = _rope(_rms_norm(k, p["k_norm"]["scale"], eps), theta)
    share = heads // groups             # query head j reads K/V head j // share
    k, v = jnp.repeat(k, share, axis=2), jnp.repeat(v, share, axis=2)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(jnp.float32(dim))
    causal = jnp.tril(jnp.ones((seq, seq), bool))
    probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", probs, v)
    return out.reshape(batch, seq, hidden) @ p["proj"]["kernel"]


def _swiglu(y, gate, up, down):
    return (jax.nn.silu(y @ gate) * (y @ up)) @ down


def _experts(y, p, bias, cfg):
    """``(out, load, chosen)`` of the expert layer's share on the normed
    input ``y`` ``(B, S, d)``: the held experts' part, and nothing else."""
    width, k = cfg["router_width"], cfg["num_experts_per_tok"]
    held, first = cfg["num_experts"], cfg.get("experts_first", 0)
    assert p["gate"].shape == (held, y.shape[-1],
                               cfg["moe_intermediate_size"])
    assert cfg["use_expert_bias"] and cfg["norm_topk_prob"]
    assert not any(name.startswith("shared") for name in p)
    scores = jax.nn.sigmoid(y @ p["router"]["kernel"])        # (B, S, E)
    assert scores.shape[-1] == width
    _, chosen = jax.lax.top_k(scores + jax.lax.stop_gradient(bias), k)
    picked = jax.nn.one_hot(chosen, width, dtype=scores.dtype)  # (B,S,k,E)
    top = jnp.take_along_axis(scores, chosen, axis=-1)
    top = top / (top.sum(axis=-1, keepdims=True)
                 + cfg["router_renorm_eps"]) * cfg["routed_scaling_factor"]
    weight = (picked * top[..., None]).sum(axis=-2)            # (B, S, E)

    def add_expert(out, e):
        return out + weight[..., first + e, None] * _swiglu(
            y, p["gate"][e], p["up"][e], p["down"][e]), None

    out, _ = jax.lax.scan(add_expert, jnp.zeros_like(y), jnp.arange(held))
    return out, picked.sum(axis=(0, 1, 2)), chosen


def loss(params, aux, tokens, *, cfg):
    """Training loss of ``tokens`` ``(B, S)``; returns ``(loss, aux)`` like
    the program's loss."""
    eps, dense_layers = cfg["norm_eps"], cfg["num_dense_layers"]
    assert len(cfg["layer_types"]) == cfg["num_hidden_layers"]
    assert "lm_head" not in params and cfg["tie_word_embeddings"]
    embedding = params["wte"]["embedding"]
    assert embedding.shape[0] == cfg["vocab_size"]
    x = embedding[tokens]
    loads, chosen, biases = [], [], []
    for i, kind in enumerate(cfg["layer_types"]):
        p = params[f"block_{i}"]
        y = _rms_norm(x, p["RMSNorm_0"]["scale"], eps)
        if kind == "conv":
            x = x + _short_conv(y, p["conv"], cfg)
        else:
            assert kind == "full_attention"
            x = x + _attention(y, p, cfg)
        y = _rms_norm(x, p["RMSNorm_1"]["scale"], eps)
        if i < dense_layers:
            assert p["gate"]["kernel"].shape[1] == cfg["intermediate_size"]
            x = x + _swiglu(y, p["gate"]["kernel"], p["up"]["kernel"],
                            p["down"]["kernel"])
            continue
        bias = aux["bias"][i - dense_layers]
        out, load, picked = _experts(y, p["moe"], bias, cfg)
        x = x + out
        load = jax.lax.stop_gradient(load)
        loads.append(load.astype(jnp.int32))
        chosen.append(picked)
        biases.append(bias + cfg["router_bias_update_rate"]
                      * jnp.sign(load.mean() - load))
    x = _rms_norm(x, params["RMSNorm_0"]["scale"], eps)
    logp = jax.nn.log_softmax(x @ embedding.T, axis=-1)
    targets = jnp.roll(tokens, -1, axis=1)
    nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
    return jnp.mean(nll), {"load": jnp.stack(loads),
                           "bias": jnp.stack(biases),
                           "experts": jnp.stack(chosen)}
