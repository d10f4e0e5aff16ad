"""Plain float32 reference of the ``olmoe-1b-7b`` configuration.

The forward pass and training loss of OLMoE in straightforward ``jax.numpy``:
a pre-norm decoder whose every block is multi-head causal attention with
RMSNorm over the whole projected q and the whole projected k (all heads
together, before the split into heads and the half-split rotary embedding),
followed by a mixture of ``num_experts`` SwiGLU experts of width
``intermediate_size``: ``p = softmax(y @ W_r)``, the ``num_experts_per_tok``
largest ``p`` of each token taken as they are (renormalised only if
``norm_topk_prob``), ``out = sum_j p_j * down_j(silu(gate_j y) * up_j y)``.
No shared expert, no capacity, no token dropped.  The loss is the mean
next-token cross-entropy plus ``router_aux_loss_coef *`` the load-balancing
loss ``E * sum_e f_e * P_e`` (``f_e`` the share of the ``T * k`` assignments
that went to expert ``e``, ``P_e`` the mean router probability) plus
``router_z_loss_coef *`` the mean of ``logsumexp(router_logits)^2``, both
taken per layer over all ``T = B * S`` tokens and averaged over the layers.

No kernels, no remat, no chunking, no sorting and no grouped product: every
expert is applied to every token and the result is masked by the top-k of
the softmax; the full ``(S, S)`` scores and ``(S, vocab)`` logits.  The
caller runs it under ``jax.default_matmul_precision("highest")``.  It is
written from the description of ``modeling_olmoe.py`` and shares no code
with ``bluefog_tpu/parallel/moe.py``; sizes are read from the configuration
file's source keys and weights from the program's parameter tree by name.

Departures from ``modeling_olmoe.py``, each shared with the program so that
the two can be compared:

* q, k and v of a layer are one matrix ``qkv`` whose columns are laid out
  head by head as ``[q_h k_h v_h]`` (the source keeps three matrices): layout
  only, the same products;
* the load-balancing loss counts ``f_e`` as a share of the ``T * k``
  assignments, as the OLMoE report writes it (1 at a uniform router); the
  source's ``load_balancing_loss_func`` sums the ``k`` slots' shares, which
  is ``k`` times this, and pools the layers' logits before the product where
  this averages the layers' losses (the same at one layer);
* the source's modelling file has no z-loss (the OLMoE trainer adds it); it
  is here with the report's coefficient;
* the target of the last position is the first token (``roll``), as in the
  program's loss.

Returns ``(loss, aux)`` with the program's ``aux`` (per-layer ``load``, the
two auxiliary losses) and, beside it, ``experts``: the chosen experts
``(layers, B, S, k)``, for counting the assignments on which a rounding of
the program's flipped a near tie.
"""

import jax
import jax.numpy as jnp


def _rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * scale


def _rope(x, theta):
    """Rotate pairs ``(i, i + D/2)`` of ``(B, S, H, D)`` by
    ``pos * theta^(-2i/D)``."""
    seq, dim = x.shape[1], x.shape[3]
    half = dim // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angle = jnp.arange(seq, dtype=jnp.float32)[:, None] * freq   # (S, D/2)
    cos, sin = jnp.cos(angle)[:, None, :], jnp.sin(angle)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _attention(x, p, cfg):
    batch, seq, hidden = x.shape
    heads, eps = cfg["num_attention_heads"], cfg["rms_norm_eps"]
    assert cfg["num_key_value_heads"] == heads
    dim = hidden // heads
    y = _rms_norm(x, p["RMSNorm_0"]["scale"], eps)
    qkv = (y @ p["qkv"]["kernel"]).reshape(batch, seq, heads, 3, dim)
    q, k, v = qkv[..., 0, :], qkv[..., 1, :], qkv[..., 2, :]
    q = _rms_norm(q.reshape(batch, seq, hidden), p["q_norm"]["scale"], eps)
    k = _rms_norm(k.reshape(batch, seq, hidden), p["k_norm"]["scale"], eps)
    q = _rope(q.reshape(batch, seq, heads, dim), cfg["rope_theta"])
    k = _rope(k.reshape(batch, seq, heads, dim), cfg["rope_theta"])
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(jnp.float32(dim))
    causal = jnp.tril(jnp.ones((seq, seq), bool))
    probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    attn = jnp.einsum("bhqk,bkhd->bqhd", probs, v).reshape(batch, seq, hidden)
    return x + attn @ p["proj"]["kernel"]


def _experts(x, p, cfg):
    """``(out, load, balance_loss, z_loss, chosen)`` of the expert layer on
    the block's residual stream ``x`` ``(B, S, d)``."""
    n, k = cfg["num_experts"], cfg["num_experts_per_tok"]
    moe = p["moe"]
    assert moe["gate"].shape == (n, x.shape[-1], cfg["intermediate_size"])
    y = _rms_norm(x, p["RMSNorm_1"]["scale"], cfg["rms_norm_eps"])
    logits = y @ moe["router"]["kernel"]                       # (B, S, E)
    probs = jax.nn.softmax(logits, axis=-1)
    top, chosen = jax.lax.top_k(probs, k)                      # (B, S, k)
    if cfg["norm_topk_prob"]:
        top = top / top.sum(axis=-1, keepdims=True)
    picked = jax.nn.one_hot(chosen, n, dtype=probs.dtype)      # (B, S, k, E)
    weight = (picked * top[..., None]).sum(axis=-2)            # (B, S, E)

    def add_expert(out, e):
        gate, up, down = moe["gate"][e], moe["up"][e], moe["down"][e]
        return out + weight[..., e, None] * (
            (jax.nn.silu(y @ gate) * (y @ up)) @ down), None

    out, _ = jax.lax.scan(add_expert, jnp.zeros_like(x), jnp.arange(n))
    load = picked.sum(axis=(0, 1, 2))                          # (E,)
    share = jax.lax.stop_gradient(load) / load.sum()
    balance = n * jnp.sum(share * probs.mean(axis=(0, 1)))
    z = jnp.mean(jax.nn.logsumexp(logits, axis=-1) ** 2)
    return x + out, load.astype(jnp.int32), balance, z, chosen


def loss(params, aux, tokens, *, cfg):
    """Training loss of ``tokens`` ``(B, S)``; returns ``(loss, aux)`` like
    the program's loss."""
    del aux
    x = params["wte"]["embedding"][tokens]
    loads, balances, zs, chosen = [], [], [], []
    for i in range(cfg["num_hidden_layers"]):
        p = params[f"block_{i}"]
        x, load, balance, z, picked = _experts(_attention(x, p, cfg), p, cfg)
        loads.append(load)
        balances.append(balance)
        zs.append(z)
        chosen.append(picked)
    x = _rms_norm(x, params["RMSNorm_0"]["scale"], cfg["rms_norm_eps"])
    logp = jax.nn.log_softmax(x @ params["lm_head"]["kernel"], axis=-1)
    targets = jnp.roll(tokens, -1, axis=1)
    nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
    balance, z = jnp.mean(jnp.stack(balances)), jnp.mean(jnp.stack(zs))
    total = (jnp.mean(nll) + cfg["router_aux_loss_coef"] * balance
             + cfg["router_z_loss_coef"] * z)
    return total, {"load": jnp.stack(loads), "balance_loss": balance,
                   "z_loss": z, "experts": jnp.stack(chosen)}
