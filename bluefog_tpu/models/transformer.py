"""Decoder-only Transformer LM with pluggable attention.

The reference predates LLM workloads (SURVEY §5.7: no sequence parallelism
anywhere in its tree); this model exists so the framework's long-context
machinery (``bluefog_tpu.parallel.ring_attention`` /
``bluefog_tpu.parallel.ulysses``) has a first-class consumer: the
``attn_impl`` hook receives ``(q, k, v, causal)`` per head-batch and may be a
local attention, a ring attention over a mesh axis, or an all-to-all
(Ulysses) head-parallel attention.

MXU-friendly choices: bfloat16 activations, fused QKV projection, RMSNorm,
static shapes throughout.
"""

from __future__ import annotations

from typing import Any, Callable, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from bluefog_tpu.utils import timeline

__all__ = ["TransformerLM", "TransformerConfig", "local_attention",
           "init_cache", "generate", "DroplessMoe", "moe_stats"]


def local_attention(q, k, v, *, causal: bool = True):
    """Plain single-device attention: ``(B, S, H, D)`` inputs."""
    dt = q.dtype
    scale = 1.0 / np.sqrt(q.shape[-1])
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    if causal:
        s_q, s_k = logits.shape[-2], logits.shape[-1]
        mask = jnp.tril(jnp.ones((s_q, s_k), bool), s_k - s_q)
        logits = jnp.where(mask, logits, jnp.finfo(jnp.float32).min)
    probs = nn.softmax(logits.astype(jnp.float32), axis=-1).astype(dt)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


class TransformerConfig:
    def __init__(self, vocab_size=32000, num_layers=4, num_heads=8,
                 embed_dim=512, mlp_ratio=4, max_seq_len=2048,
                 dtype=jnp.bfloat16, remat=False, remat_policy="full",
                 causal=True, num_experts=0,
                 expert_capacity_factor=2.0, router_group_size=4096,
                 num_kv_heads=None, pos_encoding="learned",
                 rope_theta=10000.0, mlp="gelu", num_experts_per_tok=1,
                 expert_dim=None, norm_topk_prob=False, qk_norm=False,
                 rms_norm_eps=1e-6, router_aux_loss_coef=0.01,
                 router_z_loss_coef=0.001):
        self.vocab_size = vocab_size
        self.num_layers = num_layers
        self.num_heads = num_heads
        # Grouped-query attention (GQA; num_kv_heads=1 is MQA): fewer K/V
        # projection heads, repeated across query groups before attention,
        # so every attn_impl (local / flash / ring / Ulysses) sees uniform
        # (B, S, H, D) heads unchanged.  None = classic MHA.
        if num_kv_heads is not None and num_heads % num_kv_heads:
            raise ValueError(f"num_heads ({num_heads}) must be divisible "
                             f"by num_kv_heads ({num_kv_heads})")
        self.num_kv_heads = num_kv_heads
        # "learned" = absolute wpe table (default); "rope" = rotary applied
        # to q/k inside each block — positions flow in explicitly, so
        # sequence-parallel shards (ring/Ulysses) embed their own offsets
        # and the attention impl itself stays position-agnostic.
        if pos_encoding not in ("learned", "rope"):
            raise ValueError(f"pos_encoding {pos_encoding!r} not in "
                             "('learned', 'rope')")
        if pos_encoding == "rope" and (embed_dim // num_heads) % 2:
            raise ValueError(
                f"rope needs an even head dim; got embed_dim {embed_dim} / "
                f"num_heads {num_heads} = {embed_dim // num_heads}")
        self.pos_encoding = pos_encoding
        self.rope_theta = rope_theta
        if mlp not in ("gelu", "swiglu"):
            raise ValueError(f"mlp {mlp!r} not in ('gelu', 'swiglu')")
        if num_experts_per_tok < 1 or (
                num_experts and num_experts_per_tok > num_experts):
            raise ValueError(
                f"num_experts_per_tok ({num_experts_per_tok}) must lie in "
                f"1..num_experts ({num_experts})")
        if num_experts_per_tok > 1 and not (num_experts
                                            and mlp == "swiglu"):
            raise ValueError(
                "num_experts_per_tok > 1 without mlp='swiglu' and "
                "num_experts > 0 is contradictory: only the dropless "
                "SwiGLU experts route top-k; GELU experts are top-1 Switch")
        self.mlp = mlp
        # With num_experts > 0 the MLP of every block is a mixture of
        # experts, and ``mlp`` says which: "swiglu" = DroplessMoe (top-k of
        # SwiGLU experts, no capacity, nothing dropped: OLMoE, Moonlight),
        # "gelu" = SwitchMlp (top-1, static capacity).  ``expert_dim`` is
        # the width of ONE expert (None = mlp_ratio * embed_dim);
        # ``norm_topk_prob`` renormalises the k chosen probabilities.
        self.num_experts_per_tok = num_experts_per_tok
        self.expert_dim = expert_dim
        self.norm_topk_prob = norm_topk_prob
        # What a training loss adds per DroplessMoe layer (``moe_stats``):
        # coef * load-balancing loss and coef * router z-loss (OLMoE's).
        self.router_aux_loss_coef = router_aux_loss_coef
        self.router_z_loss_coef = router_z_loss_coef
        # RMSNorm over the WHOLE projected q and k (all heads together)
        # before the head split and the rotary embedding (OLMoE, OLMo 2).
        self.qk_norm = qk_norm
        self.rms_norm_eps = rms_norm_eps
        self.embed_dim = embed_dim
        self.mlp_ratio = mlp_ratio
        self.max_seq_len = max_seq_len
        self.dtype = dtype
        # jax.checkpoint per block: recompute activations in the backward
        # instead of keeping every layer's live — trades ~1/3 more FLOPs
        # for O(num_layers) less activation HBM, the standard long-context
        # training knob (pairs with the O(S)-memory flash attention).
        self.remat = remat
        if remat_policy not in ("full", "dots") and not (
                isinstance(remat_policy, str)
                and remat_policy.startswith("dots:")):
            raise ValueError(f"remat_policy {remat_policy!r} not in "
                             "('full', 'dots', 'dots:<K>')")
        if isinstance(remat_policy, str) and remat_policy.startswith("dots:"):
            # Mixed policy: the first K blocks keep their dot_general
            # outputs resident ('dots' — less backward recompute), the
            # remaining blocks use full per-block remat.  The HBM knob for
            # models where all-dots exceeds memory but full remat leaves
            # MFU on the table (the 1.3B headline: dots is +13% where it
            # fits; K dials resident-activation memory continuously).
            try:
                k = int(remat_policy.split(":", 1)[1])
            except ValueError:
                raise ValueError(
                    f"malformed {remat_policy!r}: use 'dots:<int>'"
                ) from None
            if k < 0:
                raise ValueError(f"remat_policy dots:K needs K >= 0, got {k}")
        self.remat_policy = remat_policy
        # causal=False gives BIDIRECTIONAL attention (encoder mode — the
        # ViT uses it); the KV-cache decode path requires causal=True.
        self.causal = causal
        # num_experts > 0 replaces each block's MLP with a mixture of
        # experts (``mlp`` says which, above).  Expert weights are
        # stacked (E, ...) so ``parallel.tp_param_specs``-style expert
        # sharding (P("ep")) runs them expert-parallel under GSPMD.
        self.num_experts = num_experts
        self.expert_capacity_factor = expert_capacity_factor
        self.router_group_size = router_group_size


class SwitchMlp(nn.Module):
    """Top-1 routed mixture-of-experts MLP (Switch Transformer).

    Tokens route within fixed-size groups (``cfg.router_group_size``), so the
    one-hot dispatch tensors are O(T * group_size) — linear in sequence
    length — instead of the O(T^2) a single global group would cost.  Every
    shape is static under jit; expert weights are stacked ``(E, ...)`` so a
    ``P("ep")`` sharding on them runs the einsums expert-parallel with
    GSPMD-placed collectives — same layout-not-algorithm philosophy as
    ``parallel.tensor_parallel``.

    The standard load-balancing auxiliary loss (Switch eq. 4: E * sum_e
    f_e p_e per group) is sown as ``intermediates/moe_aux_loss`` — add
    ``aux_weight * sum(sown)`` to the training loss to keep the router from
    collapsing onto one expert."""
    cfg: Any

    @nn.compact
    def __call__(self, x):
        from bluefog_tpu.parallel.moe import (load_balance_loss,
                                              switch_dispatch)
        cfg = self.cfg
        B, S, d = x.shape
        E = cfg.num_experts
        hidden = cfg.mlp_ratio * d
        T = B * S
        g = min(getattr(cfg, "router_group_size", 4096), T)
        # Pad to a whole number of groups (never silently shrink g — tiny
        # groups disable the capacity guard and gut the balance statistic).
        G = -(-T // g)
        pad = G * g - T
        xt = x.reshape(T, d)
        if pad:
            xt = jnp.concatenate(
                [xt, jnp.zeros((pad, d), xt.dtype)], axis=0)
        xt = xt.reshape(G, g, d)
        capacity = max(1, int(cfg.expert_capacity_factor * g / E))
        logits = nn.Dense(E, use_bias=False, dtype=jnp.float32,
                          name="router")(xt.astype(jnp.float32))
        # Padding tokens route nowhere: without the mask their all-zero
        # logit rows argmax to expert 0, eat its capacity in the last
        # group, and skew the balance statistic toward it.
        valid = (jnp.arange(G * g) < T).astype(jnp.float32).reshape(G, g)
        combine, dispatch = jax.vmap(
            lambda lg, v: switch_dispatch(lg, E, capacity, v))(logits,
                                                               valid)
        # Load balance (Switch eq. 4, per routing group, mean over groups);
        # single-sourced in parallel.moe.load_balance_loss.
        aux = jax.vmap(load_balance_loss)(logits, valid).mean()
        self.sow("intermediates", "moe_aux_loss", aux)
        # batch_axis keeps fan_in per expert (= d / hidden), not E*d.
        init = nn.initializers.lecun_normal(batch_axis=(0,))
        up = self.param("experts_up", init, (E, d, hidden))
        down = self.param("experts_down", init, (E, hidden, d))
        xe = jnp.einsum("gect,gtd->gecd", dispatch.astype(cfg.dtype),
                        xt.astype(cfg.dtype))
        ye = nn.gelu(jnp.einsum("gecd,edh->gech", xe,
                                up.astype(cfg.dtype)))
        ye = jnp.einsum("gech,ehd->gecd", ye, down.astype(cfg.dtype))
        y = jnp.einsum("gtec,gecd->gtd", combine.astype(cfg.dtype), ye)
        return y.reshape(G * g, d)[:T].reshape(B, S, d)


class DroplessMoe(nn.Module):
    """Top-k routed mixture of SwiGLU experts with no capacity: every token
    reaches its ``cfg.num_experts_per_tok`` most probable experts whatever
    the load (``parallel.moe.dropless_moe``: sorted assignments, grouped
    matmuls over ragged groups, cost linear in ``T * k``).

    Parameters: ``router/kernel`` (d, E) and the three stacked leaves
    ``gate``, ``up`` (E, d, f) and ``down`` (E, f, d), ``f =
    cfg.expert_dim``.  Router matmul and softmax run in float32.  Sown into
    ``intermediates`` (read them with ``moe_stats``): ``moe_load`` (E,)
    int32 assignment counts, ``moe_balance_loss`` and ``moe_z_loss``."""
    cfg: Any

    @nn.compact
    def __call__(self, x):
        from bluefog_tpu.parallel.moe import dropless_moe
        cfg = self.cfg
        B, S, d = x.shape
        E = cfg.num_experts
        f = cfg.expert_dim or cfg.mlp_ratio * d
        # batch_axis keeps fan_in per expert (= d / f), not E*d.
        init = nn.initializers.lecun_normal(batch_axis=(0,))
        gate = self.param("gate", init, (E, d, f))
        up = self.param("up", init, (E, d, f))
        down = self.param("down", init, (E, f, d))
        with timeline.device_scope("bf.moe"):
            xt = x.reshape(B * S, d)
            with timeline.device_scope("bf.moe.route"):
                # float32 for real: the default precision of a float32
                # matmul on the TPU is one bfloat16 pass, and a top-k
                # choice flips on the rounding
                logits = nn.Dense(E, use_bias=False, dtype=jnp.float32,
                                  precision=jax.lax.Precision.HIGHEST,
                                  name="router")(xt.astype(jnp.float32))
            y, plan = dropless_moe(
                xt.astype(cfg.dtype), logits, gate, up, down,
                k=cfg.num_experts_per_tok, renormalize=cfg.norm_topk_prob)
        self.sow("intermediates", "moe_load", plan.load)
        self.sow("intermediates", "moe_balance_loss", plan.balance_loss)
        self.sow("intermediates", "moe_z_loss", plan.z_loss)
        return y.reshape(B, S, d)


def moe_stats(intermediates) -> dict:
    """What the ``DroplessMoe`` layers of one forward pass sowed (apply with
    ``mutable=["intermediates"]``): ``load`` (layers, E) int32 assignment
    counts, and ``balance_loss`` and ``z_loss`` as means over the layers.
    A training loss adds ``cfg.router_aux_loss_coef * balance_loss +
    cfg.router_z_loss_coef * z_loss``; ``load`` goes to
    ``parallel.moe.observe_load`` once fetched."""
    found = {"moe_load": [], "moe_balance_loss": [], "moe_z_loss": []}
    for path, leaf in jax.tree_util.tree_leaves_with_path(intermediates):
        for key in found:
            if any(getattr(p, "key", None) == key for p in path):
                found[key].append(leaf)
    if not found["moe_load"]:
        raise ValueError("moe_stats: no DroplessMoe layer sowed anything; "
                         "apply the model with mutable=['intermediates']")
    return {"load": jnp.stack(found["moe_load"]),
            "balance_loss": jnp.mean(jnp.stack(found["moe_balance_loss"])),
            "z_loss": jnp.mean(jnp.stack(found["moe_z_loss"]))}


def apply_rope(x, positions, theta: float = 10000.0):
    """Rotary position embedding on ``(B, S, H, D)`` q or k.

    Pairs dimension ``i`` with ``i + D/2`` (the standard half-split layout)
    and rotates by ``pos * theta^(-2i/D)``; angles computed in f32, result
    cast back to the input dtype."""
    d2 = x.shape[-1] // 2
    freq = theta ** (-jnp.arange(d2, dtype=jnp.float32) / d2)
    ang = positions[..., None].astype(jnp.float32) * freq  # (B, S, d2)
    cos = jnp.cos(ang)[:, :, None, :]
    sin = jnp.sin(ang)[:, :, None, :]
    x1, x2 = x[..., :d2].astype(jnp.float32), x[..., d2:].astype(jnp.float32)
    return jnp.concatenate([x1 * cos - x2 * sin,
                            x1 * sin + x2 * cos], -1).astype(x.dtype)


def block_class(cfg, layer_idx: int = None):
    """The (possibly remat-wrapped) Block class for a config — shared by
    ``TransformerLM`` and ``models.vit.ViT`` so ``remat_policy`` behaves
    identically in both.  ``layer_idx`` selects the per-layer class under
    the mixed ``"dots:<K>"`` policy (None = single-policy configs)."""
    if not cfg.remat:
        return Block
    policy = getattr(cfg, "remat_policy", "full")
    if isinstance(policy, str) and policy.startswith("dots:"):
        k = int(policy.split(":", 1)[1])
        if layer_idx is None:
            raise ValueError(
                "remat_policy='dots:<K>' is per-layer — call "
                "block_class(cfg, layer_idx=i)")
        policy = "dots" if layer_idx < k else "full"
    if policy == "dots":
        # Save every dot_general output, recompute only non-dot ops in
        # the backward: less recompute than full remat at the cost of
        # keeping dot activations resident.  NOTE: with dense
        # local_attention the (B,H,S,S) score/value einsums ARE dots
        # and stay live — at long S use flash attention (a pallas_call,
        # not a dot_general: recomputed, O(S) memory) or "full".
        return nn.remat(Block, policy=jax.checkpoint_policies.checkpoint_dots)
    return nn.remat(Block)


class Block(nn.Module):
    cfg: Any
    attn_impl: Callable

    @nn.compact
    def __call__(self, x, positions=None, cache=None):
        """Training/prefill path when ``cache is None``; with ``cache =
        (k_cache, v_cache)`` (shapes ``(B, L, kv_h, d)``) the input is ONE
        new token per sequence (S == 1) written at position ``positions``
        and attended against the cache — returns ``(x, new_cache)``.  The
        cache stores the kv_h *shared* heads, so GQA shrinks it by
        ``h / kv_h`` (the reason GQA exists)."""
        cfg = self.cfg
        h = cfg.num_heads
        d = cfg.embed_dim // h
        kv_h = cfg.num_kv_heads or h
        rope = getattr(cfg, "pos_encoding", "learned") == "rope"
        if rope and positions is None and cache is None:
            # standalone Block use (e.g. pipeline stages): local positions
            positions = jnp.arange(x.shape[1])[None, :]
        eps = getattr(cfg, "rms_norm_eps", 1e-6)
        y = nn.RMSNorm(epsilon=eps, dtype=cfg.dtype)(x)
        B, S = y.shape[0], y.shape[1]
        if kv_h == h:
            qkv = nn.Dense(3 * cfg.embed_dim, use_bias=False,
                           dtype=cfg.dtype, name="qkv")(y)
            # Head-interleaved fused layout [q_h0 k_h0 v_h0 | q_h1 ...]: a
            # pure relabeling of kernel columns that keeps tensor-parallel
            # shard boundaries (tp_param_specs' column split) aligned to
            # heads, so GSPMD runs attention head-parallel with one psum
            # per block instead of per-activation resharding.
            qkv = qkv.reshape(B, S, h, 3, d)
            q, k1, v1 = qkv[..., 0, :], qkv[..., 1, :], qkv[..., 2, :]
        else:
            # GQA: h query heads, kv_h shared K/V heads (same interleaved
            # column layout per projection; head-aligned TP only up to
            # kv_h ways — beyond that GSPMD re-gathers K/V per block,
            # acceptable since the kv kernel is the small one).
            q = nn.Dense(cfg.embed_dim, use_bias=False, dtype=cfg.dtype,
                         name="q")(y).reshape(B, S, h, d)
            kv = nn.Dense(2 * kv_h * d, use_bias=False, dtype=cfg.dtype,
                          name="kv")(y).reshape(B, S, kv_h, 2, d)
            k1, v1 = kv[..., 0, :], kv[..., 1, :]
        if getattr(cfg, "qk_norm", False):
            # over the whole projection, all heads together, before rope
            q = nn.RMSNorm(epsilon=eps, dtype=cfg.dtype, name="q_norm")(
                q.reshape(B, S, h * d)).reshape(B, S, h, d)
            k1 = nn.RMSNorm(epsilon=eps, dtype=cfg.dtype, name="k_norm")(
                k1.reshape(B, S, kv_h * d)).reshape(B, S, kv_h, d)
        if rope:
            # rotate the kv_h shared heads ONCE, before any fan-out to h
            q = apply_rope(q, positions, cfg.rope_theta)
            k1 = apply_rope(k1, positions, cfg.rope_theta)
        rep = h // kv_h
        if cache is None:
            if (self.is_mutable_collection("kv_cache")
                    and not self.is_initializing()):
                # prefill: expose the per-position shared-head K/V so
                # ``generate`` can fill its decode cache in ONE forward.
                # Gated out of init(), which would otherwise bake a stale
                # entry into the variables users carry around.
                self.sow("kv_cache", "kv_entries", (k1, v1))
            k = jnp.repeat(k1, rep, axis=2) if rep > 1 else k1
            v = jnp.repeat(v1, rep, axis=2) if rep > 1 else v1
            attn = self.attn_impl(
                q, k, v, causal=getattr(self.cfg, "causal", True))
        else:
            ck, cv = cache
            idx = positions[0, 0]  # decode positions are batch-uniform
            ck = jax.lax.dynamic_update_slice(
                ck, k1.astype(ck.dtype), (0, idx, 0, 0))
            cv = jax.lax.dynamic_update_slice(
                cv, v1.astype(cv.dtype), (0, idx, 0, 0))
            cache = (ck, cv)
            # grouped attention of the single query over the cache — never
            # materializes h-head K/V
            L = ck.shape[1]
            qg = q.reshape(B, S, kv_h, rep, d)
            logits = jnp.einsum("bqgrd,blgd->bgrql", qg, ck) / np.sqrt(d)
            mask = (jnp.arange(L) <= idx)[None, None, None, None, :]
            logits = jnp.where(mask, logits.astype(jnp.float32),
                               jnp.finfo(jnp.float32).min)
            probs = nn.softmax(logits, axis=-1).astype(cfg.dtype)
            attn = jnp.einsum("bgrql,blgd->bqgrd", probs, cv)
        attn = attn.reshape(B, S, cfg.embed_dim)
        x = x + nn.Dense(cfg.embed_dim, use_bias=False, dtype=cfg.dtype,
                         name="proj")(attn)
        y = nn.RMSNorm(epsilon=eps, dtype=cfg.dtype)(x)
        if getattr(cfg, "num_experts", 0) > 0:
            moe = (DroplessMoe if getattr(cfg, "mlp", "gelu") == "swiglu"
                   else SwitchMlp)
            x = x + moe(cfg, name="moe")(y)
        elif getattr(cfg, "mlp", "gelu") == "swiglu":
            hidden = cfg.mlp_ratio * cfg.embed_dim
            gate = nn.Dense(hidden, use_bias=False, dtype=cfg.dtype,
                            name="gate")(y)
            up = nn.Dense(hidden, use_bias=False, dtype=cfg.dtype,
                          name="up")(y)
            x = x + nn.Dense(cfg.embed_dim, use_bias=False, dtype=cfg.dtype,
                             name="down")(nn.silu(gate) * up)
        else:
            y = nn.Dense(cfg.mlp_ratio * cfg.embed_dim, use_bias=False,
                         dtype=cfg.dtype, name="up")(y)
            y = nn.gelu(y)
            x = x + nn.Dense(cfg.embed_dim, use_bias=False, dtype=cfg.dtype,
                             name="down")(y)
        return x if cache is None else (x, cache)


class TransformerLM(nn.Module):
    cfg: Any
    attn_impl: Optional[Callable] = None

    @nn.compact
    def __call__(self, tokens, train: bool = True, positions=None,
                 return_hidden: bool = False, cache=None):
        """``positions``: optional (B, S) global position ids — required when
        the sequence axis is sharded (each shard must embed its own offset).
        ``return_hidden``: skip the lm-head and return the final normalized
        activations (B, S, E) — pair with
        ``ops.chunked_loss.chunked_softmax_cross_entropy`` so very long
        sequences never materialize the (S, vocab) logits.
        ``cache``: list of per-block ``(k, v)`` caches (``init_cache``) for
        single-token incremental decoding — tokens must be (B, 1) at
        position ``positions``; returns ``(logits, new_cache)``."""
        cfg = self.cfg
        attn = self.attn_impl or local_attention
        if cache is not None:
            if getattr(cfg, "num_experts", 0) > 0:
                raise NotImplementedError(
                    "KV-cache decoding with MoE blocks is not supported")
            if not getattr(cfg, "causal", True):
                raise ValueError(
                    "KV-cache decoding requires causal=True: the decode "
                    "branch masks by cache index (causal by construction), "
                    "which would diverge from a bidirectional training "
                    "forward")
            if tokens.shape[1] != 1:
                raise ValueError(
                    f"cache decoding takes ONE token per step; got "
                    f"tokens of shape {tokens.shape} (prefill a prompt "
                    f"with a normal forward — see generate())")
            if positions is None:
                raise ValueError(
                    "cache decoding requires explicit positions (the "
                    "cache write index); defaulting to 0 would overwrite "
                    "slot 0 every step")
        x = nn.Embed(cfg.vocab_size, cfg.embed_dim,
                     dtype=cfg.dtype, name="wte")(tokens)
        if positions is None:
            positions = jnp.arange(tokens.shape[1])[None, :]
        rope = getattr(cfg, "pos_encoding", "learned") == "rope"
        if not rope:
            pos = nn.Embed(cfg.max_seq_len, cfg.embed_dim,
                           dtype=cfg.dtype, name="wpe")(positions)
            x = x + pos
        positions = jnp.broadcast_to(positions,
                                     (tokens.shape[0], tokens.shape[1]))
        new_cache = []
        for i in range(cfg.num_layers):
            block_cls = Block if cache is not None else block_class(cfg, i)
            blk = block_cls(cfg, attn, name=f"block_{i}")
            if cache is not None:
                x, blk_cache = blk(x, positions, cache[i])
                new_cache.append(blk_cache)
            elif rope:
                x = blk(x, positions)
            else:
                x = blk(x)
        x = nn.RMSNorm(epsilon=getattr(cfg, "rms_norm_eps", 1e-6),
                       dtype=cfg.dtype)(x)
        head = nn.Dense(cfg.vocab_size, use_bias=False, dtype=jnp.float32,
                        name="lm_head")
        if return_hidden:
            head(x[:, :1])  # materialize the lm_head param without S x V
            return x
        if cache is not None:
            return head(x), new_cache
        return head(x)


def init_cache(cfg, batch: int, max_len: int):
    """Per-block ``(k, v)`` KV caches for incremental decoding: shapes
    ``(batch, max_len, kv_heads, head_dim)`` — kv_heads, not num_heads, so
    GQA/MQA caches are ``num_heads / num_kv_heads`` times smaller."""
    h = cfg.num_heads
    d = cfg.embed_dim // h
    kv_h = cfg.num_kv_heads or h
    z = jnp.zeros((batch, max_len, kv_h, d), cfg.dtype)
    return [(z, z) for _ in range(cfg.num_layers)]


def generate(model, variables, prompt, max_new_tokens: int, *,
             temperature: float = 0.0, rng=None):
    """Autoregressive decoding with the KV cache.

    ``prompt``: (B, P) int tokens.  Returns (B, max_new_tokens).
    ``temperature == 0`` is greedy; otherwise pass ``rng`` for sampling.
    Prefill is ONE batched forward (the per-block shared-head K/V are sown
    into a ``kv_cache`` collection and copied into the decode cache), then
    new tokens stream through a single fused ``lax.scan`` of one-token
    decode steps.  Decode logits match the training forward's to numerical
    tolerance (different contraction order; tested at 1e-4 in f32).
    """
    cfg = model.cfg
    B, P = prompt.shape
    if max_new_tokens <= 0:
        raise ValueError(f"max_new_tokens must be >= 1; got {max_new_tokens}")
    total = P + max_new_tokens
    if getattr(cfg, "pos_encoding", "learned") == "learned" \
            and total > cfg.max_seq_len:
        raise ValueError(f"prompt + max_new_tokens = {total} exceeds "
                         f"max_seq_len {cfg.max_seq_len}")
    if temperature > 0 and rng is None:
        raise ValueError("sampling (temperature > 0) needs rng")
    rng = rng if rng is not None else jax.random.PRNGKey(0)

    def pick(logits, key):
        if temperature > 0:
            key, sub = jax.random.split(key)
            nxt = jax.random.categorical(sub, logits / temperature, axis=-1)
        else:
            nxt = jnp.argmax(logits, axis=-1)
        return nxt.astype(prompt.dtype), key

    # Prefill: one forward over the whole prompt; blocks sow (k1, v1).
    # (drop any stale kv_cache collection an old init may have stored)
    variables = {k: v for k, v in variables.items() if k != "kv_cache"}
    logits, sown = model.apply(
        variables, prompt, positions=jnp.arange(P)[None, :],
        mutable=["kv_cache"])
    cache = []
    for i, (ck, cv) in enumerate(init_cache(cfg, B, total)):
        (k1, v1), = sown["kv_cache"][f"block_{i}"]["kv_entries"]
        cache.append((jax.lax.dynamic_update_slice(
                          ck, k1.astype(ck.dtype), (0, 0, 0, 0)),
                      jax.lax.dynamic_update_slice(
                          cv, v1.astype(cv.dtype), (0, 0, 0, 0))))
    first, rng = pick(logits[:, -1, :], rng)

    def step(carry, t):
        cache, prev, key = carry
        logits, cache = model.apply(
            variables, prev[:, None],
            positions=jnp.broadcast_to(t, (B, 1)), cache=cache)
        nxt, key = pick(logits[:, 0, :], key)
        return (cache, nxt, key), nxt

    if max_new_tokens == 1:
        return first[:, None]
    (_, _, _), outs = jax.lax.scan(
        step, (cache, first, rng), jnp.arange(P, total - 1))
    return jnp.concatenate([first[:, None], outs.swapaxes(0, 1)], axis=1)
