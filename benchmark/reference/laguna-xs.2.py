"""Plain float32 reference of the ``laguna-xs.2`` configuration.

The forward pass and training loss of the share of Laguna-XS.2 that one chip
holds, in straightforward ``jax.numpy``.  ``d`` the hidden size, ``x`` the
residual, ``y = RMSNorm(x)`` with a learned scale and ``rms_norm_eps``; no
bias anywhere.

1. *Block* ``l``: ``h = x + Attention_l(RMSNorm_0(x))``, ``out = h +
   FFN_l(RMSNorm_1(h))``.
2. *Attention*, layer ``l`` of type ``t = layer_types[l]``: ``q = y W_q`` as
   ``H_l = num_attention_heads_per_layer[l]`` heads of ``head_dim``; ``k``
   and ``v`` as ``num_key_value_heads`` heads of ``head_dim``; query head
   ``j`` reads K/V head ``j // (H_l / num_key_value_heads)``.  Rotary on
   ``q`` and ``k`` by ``rope_parameters[t]``: the first ``head_dim *
   partial_rotary_factor`` dims of a head are rotated and the rest pass
   through untouched; ``rope_type`` ``default`` turns pair ``i`` at
   ``theta^(-2i/dim)``; ``yarn`` (arXiv:2309.00071) divides that frequency
   by ``factor`` where the pair makes fewer than ``beta_slow`` turns over
   ``original_max_position_embeddings``, leaves it where it makes more than
   ``beta_fast``, blends linearly over the pairs between, and multiplies cos
   and sin by ``attention_factor``.  Scores ``q_h . k_g(h) /
   sqrt(head_dim)``, softmax over the keys ``j`` with ``0 <= i - j``
   (``full_attention``) or ``0 <= i - j < sliding_window``
   (``sliding_attention``).  Gate (``gating``; one value a head,
   ``gating_type`` ``per_head``): ``g = sigmoid(y W_g)``, ``W_g`` of ``d x
   H_l``, ``o_h <- g_h o_h``.  The result is ``concat_h(o_h) W_o``.
3. *FFN*.  ``mlp_layer_types[l] == "dense"``: ``(silu(y W_1) * (y W_3))
   W_2`` of width ``intermediate_size``.  ``"sparse"``: ``p = softmax(y
   W_r)`` over all ``router_width`` experts in float32; the
   ``num_experts_per_tok`` largest are chosen; ``w = p[chosen] / sum
   p[chosen]`` (``norm_topk_prob``) ``* moe_routed_scaling_factor``, applied
   to the experts' outputs (``moe_apply_router_weight_on_input`` false);
   ``out = sum_{j chosen and held} w_j E_j(y) + E_shared(y)``, ``E`` a
   SwiGLU of width ``moe_intermediate_size`` and ``E_shared`` one of
   ``shared_expert_intermediate_size``, ungated, on every token.  **The
   chip's share**: the ``num_experts`` experts from ``experts_first`` on are
   held here, and what the absent experts would have added is left out;
   that partial result goes on to the next layer.  Per layer: ``balance =
   router_width * sum_e f_e P_e`` (``f_e`` the share of the ``T * k``
   assignments that chose expert ``e``, a constant for the gradient; ``P_e``
   the mean of ``p_e`` over the tokens) and ``z = mean_t logsumexp(y
   W_r)^2``.
4. *Head and loss.*  One RMSNorm after the last block, the untied head over
   the ``vocab_size`` rows held (a slice of the published vocabulary; ids
   are drawn from the slice); mean next-token cross-entropy ``+
   router_aux_loss_coef * mean_l balance_l + router_z_loss_coef * mean_l
   z_l``.

No kernels, no chunking, no sorting and no grouped product: every held
expert is applied to every token and the result is masked by the choice; the
scores are a full ``(S, S)`` matrix a head with the window as a mask; the
full ``(S, vocab)`` logits.  One concession to the chip's memory: each block
is computed a second time in the backward pass (``jax.checkpoint`` around
``_block``), which gives the same numbers.  Without it the scores of the 288
heads of a 2048-token sample (4.8 GB) and the blocks' other activations stand
beside 8.3 GB of weights and two trees of gradients, and the check asks the
v5e for 19.1 GiB by its compiler's count (12.1 with it; a sample of 1024
tokens, two windows, would ask for 15.1 of the chip's 15.75 without).  The
caller runs it under ``jax.default_matmul_precision("highest")``.  It is written from the
descriptions above and shares no code with ``bluefog_tpu``; sizes are read
from the configuration file's source keys and weights from the program's
parameter tree by name.

Departures from the family's modelling code, each shared with the program so
that the two can be compared:

* the rotary pairs are ``(i, i + rot/2)`` of the ``rot`` rotated dims
  (half-split), applied to the projections' columns as they lie;
* ``W_k`` and ``W_v`` are one matrix ``kv`` whose columns lie K/V head by
  head as ``[k_g v_g]`` (the source has two matrices): layout only;
* the balance loss takes ``f_e`` as a share of the ``T * k`` assignments, a
  layer at a time, and both router terms are means over the expert layers,
  as ``olmoe-1b-7b``'s reference has them (the source publishes no
  coefficient: the configuration file's ``assumed``);
* the target of the last position is the first token (``roll``), as in the
  program's loss.

Returns ``(loss, aux)`` with the program's ``aux`` (per-layer ``load`` and
the two router terms) and, beside it, ``experts``: the chosen experts
``(layers, B, S, k)``, for counting the assignments on which a rounding of
the program's flipped a near tie.
"""

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np


def _rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * scale


def _inverse_frequencies(rot: int, scheme: dict) -> np.ndarray:
    """The ``rot / 2`` frequencies of a rotary scheme over ``rot`` dims."""
    theta = scheme["rope_theta"]
    plain = theta ** (-np.arange(0, rot, 2, dtype=np.float64) / rot)
    if scheme["rope_type"] == "default":
        return plain.astype(np.float32)
    assert scheme["rope_type"] == "yarn"
    original = scheme["original_max_position_embeddings"]

    def pair_that_turns(times):
        """The (fractional) pair that makes ``times`` turns over the
        original context."""
        return rot * math.log(original / (times * 2 * math.pi)) \
            / (2 * math.log(theta))

    low = max(math.floor(pair_that_turns(scheme["beta_fast"])), 0)
    high = min(math.ceil(pair_that_turns(scheme["beta_slow"])), rot - 1)
    if low == high:
        high += 0.001
    slowed = np.clip((np.arange(rot // 2) - low) / (high - low), 0.0, 1.0)
    return (plain / scheme["factor"] * slowed
            + plain * (1.0 - slowed)).astype(np.float32)


def _rotary(x, scheme: dict):
    """``x`` ``(B, S, H, D)`` with the first ``D * partial_rotary_factor``
    dims of every head rotated by their position."""
    rot = int(x.shape[3] * scheme["partial_rotary_factor"])
    half = rot // 2
    angle = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] \
        * _inverse_frequencies(rot, scheme)                    # (S, rot / 2)
    factor = scheme.get("attention_factor", 1.0) \
        if scheme["rope_type"] == "yarn" else 1.0
    cos = factor * jnp.cos(angle)[:, None, :]
    sin = factor * jnp.sin(angle)[:, None, :]
    x1, x2, rest = x[..., :half], x[..., half:rot], x[..., rot:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos, rest],
                           axis=-1)


def _attention(y, p, layer: int, cfg):
    """``y`` ``(B, S, d)``, already normed; returns the mixer's result."""
    batch, seq, _ = y.shape
    kind = cfg["layer_types"][layer]
    heads = cfg["num_attention_heads_per_layer"][layer]
    groups, dim = cfg["num_key_value_heads"], cfg["head_dim"]
    assert p["q"]["kernel"].shape[1] == heads * dim and not cfg[
        "attention_bias"]
    q = (y @ p["q"]["kernel"]).reshape(batch, seq, heads, dim)
    kv = (y @ p["kv"]["kernel"]).reshape(batch, seq, groups, 2, dim)
    k, v = kv[:, :, :, 0], kv[:, :, :, 1]
    scheme = cfg["rope_parameters"][kind]
    q, k = _rotary(q, scheme), _rotary(k, scheme)
    share = heads // groups             # query head j reads K/V head j // share
    k, v = jnp.repeat(k, share, axis=2), jnp.repeat(v, share, axis=2)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(jnp.float32(dim))
    back = jnp.arange(seq)[:, None] - jnp.arange(seq)[None, :]  # i - j
    seen = back >= 0
    if kind == "sliding_attention":
        seen &= back < cfg["sliding_window"]
    else:
        assert kind == "full_attention"
    probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", probs, v)
    assert cfg["gating"] and cfg["gating_type"] == "per_head"
    assert p["attn_gate"]["kernel"].shape == (y.shape[-1], heads)
    out = out * jax.nn.sigmoid(y @ p["attn_gate"]["kernel"])[..., None]
    return out.reshape(batch, seq, heads * dim) @ p["proj"]["kernel"]


def _swiglu(y, gate, up, down):
    return (jax.nn.silu(y @ gate) * (y @ up)) @ down


def _experts(y, p, cfg):
    """``(out, load, balance, z, chosen)`` of the expert layer's share on
    the normed input ``y`` ``(B, S, d)``: the held experts' part and the
    shared expert."""
    width, k = cfg["router_width"], cfg["num_experts_per_tok"]
    held, first = cfg["num_experts"], cfg.get("experts_first", 0)
    assert p["gate"].shape == (held, y.shape[-1],
                               cfg["moe_intermediate_size"])
    assert cfg["router_scoring"] == "softmax" and cfg["norm_topk_prob"]
    assert not cfg["moe_apply_router_weight_on_input"]
    logits = y @ p["router"]["kernel"]                         # (B, S, E)
    assert logits.shape[-1] == width
    probs = jax.nn.softmax(logits, axis=-1)
    top, chosen = jax.lax.top_k(probs, k)                      # (B, S, k)
    top = top / top.sum(axis=-1, keepdims=True) \
        * cfg["moe_routed_scaling_factor"]
    picked = jax.nn.one_hot(chosen, width, dtype=probs.dtype)  # (B,S,k,E)
    weight = (picked * top[..., None]).sum(axis=-2)            # (B, S, E)

    def add_expert(out, e):
        return out + weight[..., first + e, None] * _swiglu(
            y, p["gate"][e], p["up"][e], p["down"][e]), None

    out, _ = jax.lax.scan(add_expert, jnp.zeros_like(y), jnp.arange(held))
    assert p["shared_gate"]["kernel"].shape[1] == cfg[
        "shared_expert_intermediate_size"]
    out = out + _swiglu(y, p["shared_gate"]["kernel"],
                        p["shared_up"]["kernel"], p["shared_down"]["kernel"])
    load = picked.sum(axis=(0, 1, 2))                          # (E,)
    share = jax.lax.stop_gradient(load) / load.sum()
    balance = width * jnp.sum(share * probs.mean(axis=(0, 1)))
    z = jnp.mean(jax.nn.logsumexp(logits, axis=-1) ** 2)
    return out, load.astype(jnp.int32), balance, z, chosen


def _block(x, p, *, layer: int, cfg):
    """Block ``layer`` on the residual ``x``: ``(x, stats)``, ``stats`` the
    expert layer's ``(load, balance, z, chosen)`` or None in a dense one."""
    eps = cfg["rms_norm_eps"]
    x = x + _attention(_rms_norm(x, p["RMSNorm_0"]["scale"], eps), p, layer,
                       cfg)
    y = _rms_norm(x, p["RMSNorm_1"]["scale"], eps)
    if cfg["mlp_layer_types"][layer] == "dense":
        assert p["gate"]["kernel"].shape[1] == cfg["intermediate_size"]
        return x + _swiglu(y, p["gate"]["kernel"], p["up"]["kernel"],
                           p["down"]["kernel"]), None
    assert cfg["mlp_layer_types"][layer] == "sparse"
    out, *stats = _experts(y, p["moe"], cfg)
    return x + out, tuple(stats)


def loss(params, aux, tokens, *, cfg):
    """Training loss of ``tokens`` ``(B, S)``; returns ``(loss, aux)`` like
    the program's loss."""
    del aux
    layers = cfg["num_hidden_layers"]
    assert len(cfg["layer_types"]) == len(cfg["mlp_layer_types"]) == len(
        cfg["num_attention_heads_per_layer"]) == layers
    assert not cfg["tie_word_embeddings"]
    assert params["lm_head"]["kernel"].shape[1] == cfg["vocab_size"]
    x = params["wte"]["embedding"][tokens]
    stats = []
    for i in range(layers):
        # the same numbers twice: a block's scores (16.8 MB a head at 2048
        # tokens) are not kept for the backward pass but computed again
        x, found = jax.checkpoint(functools.partial(
            _block, layer=i, cfg=cfg))(x, params[f"block_{i}"])
        if found is not None:
            stats.append(found)
    loads, balances, zs, chosen = zip(*stats)
    x = _rms_norm(x, params["RMSNorm_0"]["scale"], cfg["rms_norm_eps"])
    logp = jax.nn.log_softmax(x @ params["lm_head"]["kernel"], axis=-1)
    targets = jnp.roll(tokens, -1, axis=1)
    nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
    balance, z = jnp.mean(jnp.stack(balances)), jnp.mean(jnp.stack(zs))
    total = (jnp.mean(nll) + cfg["router_aux_loss_coef"] * balance
             + cfg["router_z_loss_coef"] * z)
    return total, {"load": jnp.stack(loads), "balance_loss": balance,
                   "z_loss": z, "experts": jnp.stack(chosen)}
