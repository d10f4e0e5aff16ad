"""Plain float32 reference of the ``internlm2-1.8b`` configuration.

The forward pass and next-token loss of a dense pre-norm decoder in
straightforward ``jax.numpy``: RMSNorm, grouped-query causal attention with
half-split rotary embeddings, SwiGLU, untied output head, no biases.  No
kernels, no remat, no chunking, the full ``(S, S)`` scores and ``(S, vocab)``
logits; the caller runs it under ``jax.default_matmul_precision("highest")``.
It follows the InternLM2 description (``modeling_internlm2.py`` of the source
repository) and reads its sizes from the configuration file's source keys.

Departures from the source, each shared with the program so that the two can
be compared: ``rms_norm_eps`` is the configuration file's (1e-6); q and the
packed k/v matrix are separate leaves (the source packs ``wqkv``); the
target of the last position is the first token (``roll``), as in the
program's loss.  Weights are read from the program's parameter tree by name.
"""

import jax
import jax.numpy as jnp


def _rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * scale


def _rope(x, theta):
    """Rotate pairs ``(i, i + D/2)`` of ``(S, H, D)`` by ``pos * theta^(-2i/D)``."""
    seq, _, dim = x.shape
    half = dim // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angle = jnp.arange(seq, dtype=jnp.float32)[:, None] * freq   # (S, D/2)
    cos, sin = jnp.cos(angle)[:, None, :], jnp.sin(angle)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _block(x, p, cfg):
    seq, hidden = x.shape
    heads, kv_heads = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    dim = hidden // heads
    y = _rms_norm(x, p["RMSNorm_0"]["scale"], cfg["rms_norm_eps"])
    q = (y @ p["q"]["kernel"]).reshape(seq, heads, dim)
    kv = (y @ p["kv"]["kernel"]).reshape(seq, kv_heads, 2, dim)
    k, v = kv[:, :, 0], kv[:, :, 1]
    q, k = _rope(q, cfg["rope_theta"]), _rope(k, cfg["rope_theta"])
    group = heads // kv_heads            # query head j reads kv head j // group
    k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
    scores = jnp.einsum("qhd,khd->hqk", q, k) / jnp.sqrt(jnp.float32(dim))
    causal = jnp.tril(jnp.ones((seq, seq), bool))
    probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    attn = jnp.einsum("hqk,khd->qhd", probs, v).reshape(seq, hidden)
    x = x + attn @ p["proj"]["kernel"]
    y = _rms_norm(x, p["RMSNorm_1"]["scale"], cfg["rms_norm_eps"])
    assert p["gate"]["kernel"].shape == (hidden, cfg["intermediate_size"])
    gated = jax.nn.silu(y @ p["gate"]["kernel"]) * (y @ p["up"]["kernel"])
    return x + gated @ p["down"]["kernel"]


def loss(params, aux, tokens, *, cfg):
    """Mean next-token cross-entropy of ``tokens`` ``(B, S)``; returns
    ``(loss, aux)`` like the program's loss (``aux`` is empty here)."""
    def one(seq_tokens):
        x = params["wte"]["embedding"][seq_tokens]
        for i in range(cfg["num_hidden_layers"]):
            x = _block(x, params[f"block_{i}"], cfg)
        x = _rms_norm(x, params["RMSNorm_0"]["scale"], cfg["rms_norm_eps"])
        logp = jax.nn.log_softmax(x @ params["lm_head"]["kernel"], axis=-1)
        target = jnp.roll(seq_tokens, -1)
        return -jnp.take_along_axis(logp, target[:, None], axis=-1)[:, 0]
    return jnp.mean(jax.vmap(one)(tokens)), aux
