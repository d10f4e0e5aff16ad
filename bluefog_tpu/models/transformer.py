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

import functools
import math
from typing import Any, Callable, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from bluefog_tpu.utils import telemetry, timeline

__all__ = ["TransformerLM", "TransformerConfig", "local_attention",
           "init_cache", "generate", "DroplessMoe", "moe_stats", "ShortConv",
           "Mamba2Mixer", "KimiDeltaMixer", "head_matrix"]


def local_attention(q, k, v, *, causal: bool = True, scale: float = None,
                    window: int = None, segment_ids=None):
    """Plain single-device attention: ``(B, S, H, D)`` inputs (``v`` may
    have a last dim of its own); ``scale=None`` means ``1 / sqrt(D)``.
    ``window=W`` (with ``causal``): a query sees its own key and the ``W -
    1`` before it.  ``segment_ids`` ``(B, S)``: a query sees the keys of its
    own document only (a packed row, ``data.pack_documents``)."""
    dt = q.dtype
    if scale is None:
        scale = 1.0 / np.sqrt(q.shape[-1])
    if window is not None and not causal:
        raise ValueError("local_attention: a window reaches back from the "
                         "diagonal and needs causal=True")
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    if causal:
        s_q, s_k = logits.shape[-2], logits.shape[-1]
        mask = jnp.tril(jnp.ones((s_q, s_k), bool), s_k - s_q)
        if window is not None:
            mask &= ~jnp.tril(jnp.ones((s_q, s_k), bool),
                              s_k - s_q - window)
        logits = jnp.where(mask, logits, jnp.finfo(jnp.float32).min)
    if segment_ids is not None:
        same = segment_ids[:, None, :, None] == segment_ids[:, None, None, :]
        logits = jnp.where(same, logits, jnp.finfo(jnp.float32).min)
    probs = nn.softmax(logits.astype(jnp.float32), axis=-1).astype(dt)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


MIXERS = ("conv", "full_attention", "sliding_attention", "mamba", "kda")
# what an entry of ``layer_types`` may say: a mixer, or "ffn" for a block
# that is its feed-forward part alone
LAYER_KINDS = MIXERS + ("ffn",)


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
                 router_z_loss_coef=0.001, mlp_dim=None, dense_layers=0,
                 num_shared_experts=0, router_scoring="softmax",
                 routed_scaling_factor=1.0, experts_held=None,
                 experts_first=0, kv_lora_rank=None, q_lora_rank=None,
                 qk_nope_head_dim=None, qk_rope_head_dim=None,
                 v_head_dim=None, rope_scaling=None, hyper_streams=1,
                 hyper_sinkhorn_iters=20, hyper_eps=1e-6,
                 hyper_res_clamp=(-30.0, 30.0), layer_types=None,
                 conv_kernel=3, tie_embeddings=False,
                 router_renorm_eps=1e-20, head_dim=None,
                 num_heads_per_layer=None, sliding_window=None,
                 attn_gate=None, rope_parameters=None, block_ffn=True,
                 ssm_heads=None, ssm_head_dim=64, ssm_groups=1,
                 ssm_state=128, ssm_chunk=128,
                 ssm_dt_init=(0.001, 0.1, 1e-4), kda_chunk=64,
                 kda_lower_bound=-5.0, router_groups=1,
                 router_groups_kept=1):
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
        # The size of a head where it is not embed_dim // num_heads (the
        # q projection is then num_heads * head_dim wide and the output
        # projection comes back from it), and the number of query heads
        # layer by layer where the layers differ (None: num_heads in all).
        self.head_dim = head_dim
        if num_heads_per_layer is not None:
            num_heads_per_layer = tuple(num_heads_per_layer)
            if len(num_heads_per_layer) != num_layers or any(
                    h % (num_kv_heads or h) for h in num_heads_per_layer):
                raise ValueError(
                    f"num_heads_per_layer needs num_layers ({num_layers}) "
                    f"entries divisible by num_kv_heads ({num_kv_heads}); "
                    f"got {num_heads_per_layer}")
        self.num_heads_per_layer = num_heads_per_layer
        # "learned" = absolute wpe table (default); "rope" = rotary applied
        # to q/k inside each block — positions flow in explicitly, so
        # sequence-parallel shards (ring/Ulysses) embed their own offsets
        # and the attention impl itself stays position-agnostic; "none" =
        # no positional encoding at all (a hybrid whose recurrent layers
        # carry the order: Nemotron-H's attention layers).
        if pos_encoding not in ("learned", "rope", "none"):
            raise ValueError(f"pos_encoding {pos_encoding!r} not in "
                             "('learned', 'rope', 'none')")
        if pos_encoding == "rope" and (head_dim
                                       or embed_dim // num_heads) % 2:
            raise ValueError(
                f"rope needs an even head dim; got "
                f"{head_dim or embed_dim // num_heads} (head_dim, or "
                f"embed_dim {embed_dim} / num_heads {num_heads})")
        self.pos_encoding = pos_encoding
        self.rope_theta = rope_theta
        if mlp not in ("gelu", "swiglu", "relu2"):
            raise ValueError(
                f"mlp {mlp!r} not in ('gelu', 'swiglu', 'relu2')")
        if num_experts_per_tok < 1 or (
                num_experts and num_experts_per_tok > num_experts):
            raise ValueError(
                f"num_experts_per_tok ({num_experts_per_tok}) must lie in "
                f"1..num_experts ({num_experts})")
        if num_experts_per_tok > 1 and not (
                num_experts and mlp in ("swiglu", "relu2")):
            raise ValueError(
                "num_experts_per_tok > 1 without mlp='swiglu' or 'relu2' "
                "and num_experts > 0 is contradictory: only the dropless "
                "experts route top-k; GELU experts are top-1 Switch")
        self.mlp = mlp
        # With num_experts > 0 the MLP of every block is a mixture of
        # experts, and ``mlp`` says which: "swiglu" = DroplessMoe (top-k of
        # SwiGLU experts, no capacity, nothing dropped: OLMoE, Moonlight),
        # "relu2" = DroplessMoe of un-gated experts, ``down(relu(up x)^2)``
        # (Nemotron-H; the dense MLP and a shared expert take the same
        # form), "gelu" = SwitchMlp (top-1, static capacity).
        # ``expert_dim`` is
        # the width of ONE expert (None = mlp_ratio * embed_dim);
        # ``norm_topk_prob`` renormalises the k chosen probabilities.
        self.num_experts_per_tok = num_experts_per_tok
        self.expert_dim = expert_dim
        self.norm_topk_prob = norm_topk_prob
        # What a training loss adds per DroplessMoe layer (``moe_stats``):
        # coef * load-balancing loss and coef * router z-loss (OLMoE's).
        self.router_aux_loss_coef = router_aux_loss_coef
        self.router_z_loss_coef = router_z_loss_coef
        # RMSNorm of q and k before the rotary embedding, in one of two
        # forms: True = over the WHOLE projection, all heads together, with
        # a scale of its width (OLMoE, OLMo 2); "head" = over each head's
        # own values, with ONE scale of the head dim that the heads share
        # (LFM2).
        if qk_norm not in (False, True, "head"):
            raise ValueError(
                f"qk_norm {qk_norm!r} not in (False, True, 'head'): True "
                "norms the whole projection, 'head' each head's values")
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
        # Width of the dense MLP where it is no multiple of embed_dim
        # (None = mlp_ratio * embed_dim), and the number of leading blocks
        # that keep the dense MLP in a model whose other blocks hold experts
        # (DeepSeek's first_k_dense_replace).
        self.mlp_dim = mlp_dim
        self.dense_layers = dense_layers
        # DroplessMoe beyond OLMoE's: ``num_shared_experts`` SwiGLU experts
        # of width expert_dim that every token passes through, beside the
        # routed ones; ``router_scoring`` "sigmoid" chooses on sigmoid score
        # + bias (the bias is the ``router_state`` collection's, moved by
        # ``parallel.moe.update_router_bias`` and reached by no gradient);
        # under either scoring the (renormalised) weights are multiplied by
        # ``routed_scaling_factor``; ``experts_held`` experts from
        # ``experts_first`` on live on this rank (None = all num_experts):
        # the router keeps its num_experts outputs, the expert leaves hold
        # the held ones only and the layer computes their part of the result.
        if router_scoring not in ("softmax", "sigmoid"):
            raise ValueError(f"router_scoring {router_scoring!r} not in "
                             "('softmax', 'sigmoid')")
        if experts_held is not None and not (
                0 <= experts_first
                and 0 < experts_held <= num_experts - experts_first):
            raise ValueError(
                f"experts_held ({experts_held}) from experts_first "
                f"({experts_first}) must lie within num_experts "
                f"({num_experts})")
        self.num_shared_experts = num_shared_experts
        # ``router_groups`` > 1 (sigmoid scoring only; DeepSeek-V3's
        # ``n_group`` / ``topk_group``): the experts lie in that many
        # consecutive groups, a token's ``router_groups_kept`` best groups
        # (by the sum of a group's two largest score + bias) stay and the
        # top-k is taken among their experts alone.
        if router_groups < 1 or not 1 <= router_groups_kept <= router_groups \
                or (router_groups > 1 and (
                    router_scoring != "sigmoid"
                    or num_experts % router_groups
                    or num_experts // router_groups < 2
                    or router_groups_kept * (num_experts // router_groups)
                    < num_experts_per_tok)):
            raise ValueError(
                f"router_groups ({router_groups}) must divide num_experts "
                f"({num_experts}) into groups of two experts or more under "
                f"router_scoring='sigmoid' (got {router_scoring!r}), and the "
                f"router_groups_kept ({router_groups_kept}) of them hold "
                f"num_experts_per_tok ({num_experts_per_tok}) experts")
        self.router_groups = router_groups
        self.router_groups_kept = router_groups_kept
        self.router_scoring = router_scoring
        self.routed_scaling_factor = routed_scaling_factor
        self.experts_held = experts_held
        self.experts_first = experts_first
        # Latent attention (DeepSeek's MLA) when kv_lora_rank is set: q and
        # k heads of qk_nope_head_dim + qk_rope_head_dim (the rotary part of
        # k is ONE head shared by all), v heads of v_head_dim, q through a
        # rank-q_lora_rank bottleneck (None = one matrix), k and v expanded
        # from a normed rank-kv_lora_rank latent.  ``rope_scaling``: YaRN's
        # dict (factor, original_max_position_embeddings, beta_fast,
        # beta_slow, mscale, mscale_all_dim) for every layer's rotary
        # embedding, latent or plain (``rope_scheme``).
        latent = (kv_lora_rank, qk_nope_head_dim, qk_rope_head_dim,
                  v_head_dim)
        if any(v is not None for v in latent) and None in latent:
            raise ValueError(
                "latent attention needs kv_lora_rank, qk_nope_head_dim, "
                "qk_rope_head_dim and v_head_dim together")
        if kv_lora_rank is not None and (
                pos_encoding != "rope" or qk_rope_head_dim % 2
                or num_kv_heads not in (None, num_heads)):
            raise ValueError(
                "latent attention takes pos_encoding='rope', an even "
                "qk_rope_head_dim and no grouped K/V heads")
        if rope_scaling is not None and rope_scaling.get("type") != "yarn":
            raise ValueError(f"rope_scaling type "
                             f"{rope_scaling.get('type')!r}: only 'yarn'")
        self.kv_lora_rank = kv_lora_rank
        self.q_lora_rank = q_lora_rank
        self.qk_nope_head_dim = qk_nope_head_dim
        self.qk_rope_head_dim = qk_rope_head_dim
        self.v_head_dim = v_head_dim
        self.rope_scaling = rope_scaling
        # hyper_streams n > 1: the residual is n streams (manifold-
        # constrained hyper-connections), carried as (B, S, n * embed_dim);
        # every sub-layer reads a gated sum of them and writes back through
        # a gate and an (n, n) Sinkhorn-normalised mixing matrix
        # (``HyperConnection``).
        if hyper_streams < 1:
            raise ValueError(f"hyper_streams must be >= 1; got "
                             f"{hyper_streams}")
        self.hyper_streams = hyper_streams
        self.hyper_sinkhorn_iters = hyper_sinkhorn_iters
        self.hyper_eps = hyper_eps
        self.hyper_res_clamp = tuple(hyper_res_clamp)
        # The token mixer of each block, one entry a layer: "full_attention"
        # (what the other fields describe), "sliding_attention" (the same
        # attention over the last ``sliding_window`` keys only), "conv", a
        # gated short convolution of ``conv_kernel`` taps (``ShortConv``),
        # "mamba", a Mamba-2 mixer on the chunked scan (``Mamba2Mixer``), or
        # "kda", Kimi Delta Attention on the chunked delta rule
        # (``KimiDeltaMixer``); the entry "ffn" is a block without a mixer,
        # its feed-forward part alone.  None = all full attention.
        # ``block_ffn=False``: no feed-forward part follows a mixer, so that
        # every block is one part alone with one norm, ``x <- x +
        # f(RMSNorm(x))`` (Nemotron-H).
        if layer_types is not None:
            layer_types = tuple(layer_types)
            odd = set(layer_types) - set(LAYER_KINDS)
            if len(layer_types) != num_layers or odd:
                raise ValueError(
                    f"layer_types needs num_layers ({num_layers}) entries "
                    f"of {LAYER_KINDS}; got {len(layer_types)}"
                    + (f" with {sorted(odd)}" if odd else ""))
            if "sliding_attention" in layer_types and not (
                    causal and sliding_window and sliding_window >= 1):
                raise ValueError(
                    "a 'sliding_attention' layer needs causal=True and a "
                    f"sliding_window >= 1; got {sliding_window}")
        self.sliding_window = sliding_window
        # "head": each head's attention output is multiplied by the sigmoid
        # of one value, a linear map of the block's normed input, before
        # the output projection (arXiv:2505.06708's head-wise gate), in
        # plain and in latent attention alike.
        if attn_gate not in (None, "head"):
            raise ValueError(f"attn_gate {attn_gate!r} not in (None, 'head')")
        self.attn_gate = attn_gate
        # The rotary scheme by layer type, as Hugging Face's
        # ``rope_parameters`` gives it: {"full_attention": {"rope_theta",
        # "rope_type" ("default" | "yarn"), "partial_rotary_factor", and
        # under yarn "factor", "original_max_position_embeddings",
        # "beta_fast", "beta_slow", "attention_factor"}, ...}.  A layer type
        # without an entry (and every layer where this is None) takes
        # ``rope_theta`` and ``rope_scaling``.  Keys that name no layer
        # type are not read.
        for kind, scheme in (rope_parameters or {}).items():
            if kind in MIXERS and scheme.get("rope_type", "default") not in (
                    "default", "yarn"):
                raise ValueError(
                    f"rope_parameters[{kind!r}]: rope_type "
                    f"{scheme.get('rope_type')!r} not in ('default', "
                    "'yarn')")
        self.rope_parameters = rope_parameters
        if conv_kernel < 1:
            raise ValueError(f"conv_kernel must be >= 1; got {conv_kernel}")
        self.layer_types = layer_types
        self.conv_kernel = conv_kernel
        self.block_ffn = block_ffn
        # A "mamba" layer: ``ssm_heads`` heads of ``ssm_head_dim`` (the
        # mixer's inner width is their product), ``ssm_groups`` groups of B
        # and C with a state of ``ssm_state`` a head value, the scan in
        # chunks of ``ssm_chunk``, a causal depthwise convolution of
        # ``conv_kernel`` taps before it; the time steps start log-uniform
        # in ``ssm_dt_init`` = (min, max, floor), Mamba-2's.
        if layer_types is not None and "mamba" in layer_types and not (
                ssm_heads and ssm_heads % ssm_groups == 0 and ssm_chunk >= 1):
            raise ValueError(
                f"a 'mamba' layer needs ssm_heads ({ssm_heads}) a multiple "
                f"of ssm_groups ({ssm_groups}) and ssm_chunk >= 1")
        self.ssm_heads = ssm_heads
        self.ssm_head_dim = ssm_head_dim
        self.ssm_groups = ssm_groups
        self.ssm_state = ssm_state
        self.ssm_chunk = ssm_chunk
        self.ssm_dt_init = tuple(ssm_dt_init)
        # A "kda" layer: ``num_heads`` heads of ``head_dim`` (None:
        # ``embed_dim // num_heads``) for keys and values alike, a causal
        # depthwise convolution of ``conv_kernel`` taps on each of q, k and
        # v, log decays a step in ``(kda_lower_bound, 0)`` and the rule in
        # chunks of ``kda_chunk`` (``ops.kda.kda_chunked`` says which chunks
        # and which bounds it takes); ``dt_bias`` starts as a "mamba"
        # layer's, from ``ssm_dt_init``.
        if layer_types is not None and "kda" in layer_types and not (
                kda_chunk >= 1 and -80.0 / 15 <= kda_lower_bound < 0):
            raise ValueError(
                f"a 'kda' layer needs kda_chunk ({kda_chunk}) >= 1 and a "
                f"kda_lower_bound ({kda_lower_bound}) in [-80 / 15, 0): "
                "the chunked rule keeps a sub-block's 15 steps of decay "
                "inside float32")
        self.kda_chunk = kda_chunk
        self.kda_lower_bound = kda_lower_bound
        # The output head is the transposed embedding: no ``lm_head`` leaf.
        self.tie_embeddings = tie_embeddings
        # What a sigmoid router adds to the sum of the chosen scores before
        # it divides by it (``norm_topk_prob``).
        self.router_renorm_eps = router_renorm_eps


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
    cfg.expert_dim``.  Under ``cfg.mlp == "relu2"`` the experts are
    un-gated, ``down(relu(up x)^2)``: no ``gate`` and no ``shared_gate``
    leaf, the same path with the gate left out.  Router matmul and softmax run in float32.  Sown into
    ``intermediates`` (read them with ``moe_stats``): ``moe_load`` (E,)
    int32 assignment counts, ``moe_balance_loss`` and ``moe_z_loss``.

    With ``cfg.experts_held`` the three leaves hold that many experts, from
    ``cfg.experts_first`` on, and the layer computes their part of the
    result; the router and ``moe_load`` keep all ``E``.  Under
    ``cfg.router_scoring == "sigmoid"`` the choice is made on score + the
    variable ``bias`` (E,) of the collection ``router_state`` (zeros at
    init; the training loop moves it with ``parallel.moe.
    update_router_bias`` and no gradient reaches it).
    ``cfg.num_shared_experts`` adds one SwiGLU of that many expert widths
    (``shared_gate``, ``shared_up``, ``shared_down``) that every token
    passes through, under the scope ``bf.moe.shared``."""
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
        held = getattr(cfg, "experts_held", None)
        n = E if held is None else held
        gated = getattr(cfg, "mlp", "swiglu") != "relu2"
        gate = self.param("gate", init, (n, d, f)) if gated else None
        up = self.param("up", init, (n, d, f))
        down = self.param("down", init, (n, f, d))
        routing = {"scale": getattr(cfg, "routed_scaling_factor", 1.0)}
        if held is not None:
            routing["held"] = (cfg.experts_first, held)
        if getattr(cfg, "router_scoring", "softmax") == "sigmoid":
            routing.update(
                scoring="sigmoid",
                renorm_eps=getattr(cfg, "router_renorm_eps", 1e-20),
                bias=self.variable("router_state", "bias", jnp.zeros, (E,),
                                   jnp.float32).value)
        if getattr(cfg, "router_groups", 1) > 1:
            routing.update(n_group=cfg.router_groups,
                           topk_group=cfg.router_groups_kept)
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
                k=cfg.num_experts_per_tok, renormalize=cfg.norm_topk_prob,
                **routing)
            shared = getattr(cfg, "num_shared_experts", 0) * f
            if shared:
                with timeline.device_scope("bf.moe.shared"):
                    dense = functools.partial(nn.Dense, use_bias=False,
                                              dtype=cfg.dtype)
                    xs = xt.astype(cfg.dtype)
                    if gated:
                        hidden = nn.silu(dense(shared, name="shared_gate")(
                            xs)) * dense(shared, name="shared_up")(xs)
                    else:
                        hidden = relu2(dense(shared, name="shared_up")(xs))
                    y = y + dense(d, name="shared_down")(hidden)
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


def yarn_mscale(factor: float, mscale: float = 1.0) -> float:
    """YaRN's attention temperature: ``0.1 * mscale * ln(factor) + 1``
    (1 for a factor of one or less)."""
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_frequencies(dim: int, theta: float, scaling: dict) -> np.ndarray:
    """The ``dim / 2`` rotary frequencies under YaRN (arXiv:2309.00071, as
    DeepSeek-V3's modelling file computes them): pair ``i`` turns at
    ``theta^(-2i/dim)``, divided by ``factor`` where it makes fewer than
    ``beta_slow`` turns over the original context, left alone where it makes
    more than ``beta_fast``, and blended linearly over the pairs between."""
    factor = scaling["factor"]
    original = scaling["original_max_position_embeddings"]
    plain = theta ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)

    def pair_of(turns):
        return dim * math.log(original / (turns * 2 * math.pi)) \
            / (2 * math.log(theta))
    low = max(math.floor(pair_of(scaling.get("beta_fast", 32))), 0)
    high = min(math.ceil(pair_of(scaling.get("beta_slow", 1))), dim - 1)
    ramp = np.clip((np.arange(dim // 2) - low)
                   / ((high - low) or 0.001), 0.0, 1.0)
    return (plain / factor * ramp + plain * (1.0 - ramp)).astype(np.float32)


def _half_swap(dim: int, rot: int, first: int = 0) -> np.ndarray:
    """The constant ``(dim, dim)`` matrix ``R`` of zeros and ones with which
    ``x @ R`` has, of the ``rot`` lanes of a head from ``first`` on, the
    second half where the first was and the first where the second was, and
    zero at every other lane.  Every output is one input or none, so the
    product is exact in any dtype."""
    swap = np.zeros((dim, dim), np.float32)
    i = first + np.arange(rot // 2)
    swap[i + rot // 2, i] = swap[i, i + rot // 2] = 1.0
    return swap


@jax.custom_vjp
def _turn(x, swap, c, s):
    """``x * c + (x @ swap) * s`` in float32 over whole heads, cast back:
    one pass that names no array narrower than a head (``apply_rope`` says
    why the half-swap is a product)."""
    # the array rounds the operands of a default-precision product to
    # bfloat16, which would not be the values of any wider x
    swapped = jnp.einsum(
        "bshd,de->bshe", x, swap.astype(x.dtype),
        preferred_element_type=jnp.float32,
        precision=None if x.dtype == jnp.bfloat16
        else jax.lax.Precision.HIGHEST)
    return (x.astype(jnp.float32) * c + swapped * s).astype(x.dtype)


def _turn_fwd(x, swap, c, s):
    return _turn(x, swap, c, s), (swap, c, s)


def _turn_bwd(residuals, dy):
    # The transpose of a rotation is the rotation back: the same pass with
    # -sin.  Written out because autodiff of the product form would round
    # dy * s to the cotangent's dtype inside the array before the swap,
    # where this swaps the cotangent exactly and multiplies in float32.
    # The angles come from integer positions and constants: no cotangent.
    swap, c, s = residuals
    return _turn(dy, swap, c, -s), None, None, None


_turn.defvjp(_turn_fwd, _turn_bwd)


def apply_rope(x, positions, theta: float = 10000.0, freq=None,
               scale: float = 1.0, rot: int = None, first: int = 0):
    """Rotary position embedding on ``(B, S, H, D)`` q or k.

    Pairs dimension ``i`` with ``i + D/2`` (the standard half-split layout)
    and rotates by ``pos * theta^(-2i/D)`` (or by ``pos * freq[i]`` where
    the ``D / 2`` frequencies are given); cos and sin are multiplied by
    ``scale`` (YaRN's attention factor); angles computed in f32, result
    cast back to the input dtype.  ``rot`` < D: only the ``rot`` dims of a
    head from ``first`` on are rotated (they are the ``D`` above) and the
    rest pass through untouched.

    A head is turned in one pass over its whole width, forward and
    transposed: ``x * [cos, cos, 1] + (x @ R) * [-sin, sin, 0]`` with the
    half-swap ``R`` a constant of zeros and ones (``docs/ops.md``: two
    half-width slices and a concatenate, or slices of the input
    concatenated, cost four times the bytes on a TPU)."""
    dim = x.shape[-1]
    rot = dim - first if rot is None else rot
    if rot % 2 or not 0 <= rot <= dim - first:
        raise ValueError(f"apply_rope: the rotary part of a head of {dim} "
                         f"is {rot} dims from {first} on; it must be even "
                         f"and within the head")
    d2 = rot // 2
    if freq is None:
        freq = theta ** (-jnp.arange(d2, dtype=jnp.float32) / d2)
    ang = positions[..., None].astype(jnp.float32) * freq  # (B, S, d2)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    if scale != 1.0:
        cos, sin = cos * scale, sin * scale
    rest = ((0, 0), (0, 0), (first, dim - first - rot))
    c = jnp.pad(jnp.concatenate([cos, cos], -1), rest, constant_values=1.0)
    s = jnp.pad(jnp.concatenate([-sin, sin], -1), rest)
    return _turn(x, _half_swap(dim, rot, first),
                 c[:, :, None, :], s[:, :, None, :])


def rope_scheme(cfg, kind: str, dim: int) -> tuple:
    """``(rot, theta, freq, factor)`` of the rotary embedding that a plain
    attention layer of type ``kind`` puts on its heads of ``dim``: the first
    ``rot`` dims of a head are rotated (``partial_rotary_factor``; the rest
    pass through untouched), at ``theta``'s frequencies or under YaRN at
    ``freq`` (``yarn_frequencies`` over the ``rot`` dims) with cos and sin
    times ``factor``: the scheme's ``attention_factor``, else the ratio of
    ``yarn_mscale`` at ``mscale`` and ``mscale_all_dim`` where both are
    given, else ``yarn_mscale(factor)``.  The scheme is
    ``cfg.rope_parameters[kind]``; failing that ``cfg.rope_theta`` with
    ``cfg.rope_scaling``."""
    scheme = (getattr(cfg, "rope_parameters", None) or {}).get(kind)
    if scheme is None:
        scaling = getattr(cfg, "rope_scaling", None)
        scheme = dict(scaling or {}, rope_theta=cfg.rope_theta,
                      rope_type="yarn" if scaling else "default")
    theta = scheme.get("rope_theta", cfg.rope_theta)
    rot = int(dim * scheme.get("partial_rotary_factor", 1.0))
    if rot % 2 or not 0 < rot <= dim:
        raise ValueError(f"{kind}: the rotary part of a head of {dim} is "
                         f"{rot} dims; it must be even and within the head")
    if scheme.get("rope_type", "default") != "yarn":
        return rot, theta, None, 1.0
    factor = scheme.get("attention_factor")
    if factor is None:
        mscale, all_dim = scheme.get("mscale"), scheme.get("mscale_all_dim")
        factor = yarn_mscale(scheme["factor"])
        if mscale and all_dim:
            factor = (yarn_mscale(scheme["factor"], mscale)
                      / yarn_mscale(scheme["factor"], all_dim))
    return rot, theta, yarn_frequencies(rot, theta, scheme), float(factor)


class LatentAttention(nn.Module):
    """Multi-head latent attention (DeepSeek-V2/V3's MLA), training and
    prefill form: the keys and values of all heads are expanded from one
    normed latent of ``cfg.kv_lora_rank`` values a token, the queries pass a
    bottleneck of ``cfg.q_lora_rank``, and the rotary part of the key
    (``cfg.qk_rope_head_dim``) is one head that all query heads share.

    ``c_q = RMSNorm(y q_a)``; ``q = c_q q_b`` as ``(H, nope + rope)``;
    ``[c_kv, k_r] = y kv_a``; ``[k_n, v] = RMSNorm(c_kv) kv_b`` as ``(H,
    nope + v)``; rotary (half-split pairs, YaRN frequencies under
    ``cfg.rope_scaling``) on the last ``rope`` dims of ``q`` and on ``k_r``;
    ``k_h = [k_n,h ; k_r]``; softmax of ``q k^T * (nope + rope)^-0.5 *
    m^2``, ``m = yarn_mscale(factor, mscale_all_dim)``; the heads' ``p v``
    go through ``proj`` (``H * v`` to ``embed_dim``).  No bias anywhere.
    ``attn_impl`` gets q and k heads of ``nope + rope`` and v heads of
    ``v_head_dim`` (``ops.flash_attention`` takes both) and the scale.

    ``segment_ids`` (the documents of a packed row) go to ``attn_impl``
    where there are any; ``positions`` then restart at each document.

    Under ``cfg.attn_gate == "head"`` the leaf ``attn_gate`` ``(embed_dim,
    heads)`` gates each head's result before the output projection, ``o_h
    <- sigmoid(y W_g)_h o_h``, as in ``Block``'s plain branch.

    Device scopes: ``bf.mla.q``, ``bf.mla.kv``, ``bf.mla.rope``,
    ``bf.mla.attend`` (the key's assembly and the attention itself),
    ``bf.mla.gate`` (where there is a gate) and ``bf.mla.out``."""
    cfg: Any
    attn_impl: Callable

    @nn.compact
    def __call__(self, y, positions, segment_ids=None):
        cfg = self.cfg
        B, S, _ = y.shape
        h, nope, rope = (cfg.num_heads, cfg.qk_nope_head_dim,
                         cfg.qk_rope_head_dim)
        dv, rank = cfg.v_head_dim, cfg.kv_lora_rank
        dense = functools.partial(nn.Dense, use_bias=False, dtype=cfg.dtype)
        norm = functools.partial(nn.RMSNorm, epsilon=cfg.rms_norm_eps,
                                 dtype=cfg.dtype)
        scaling = cfg.rope_scaling
        with timeline.device_scope("bf.mla.q"):
            if cfg.q_lora_rank is None:
                q = dense(h * (nope + rope), name="q")(y)
            else:
                q = dense(h * (nope + rope), name="q_b")(norm(name="q_a_norm")(
                    dense(cfg.q_lora_rank, name="q_a")(y)))
            q = q.reshape(B, S, h, nope + rope)
        with timeline.device_scope("bf.mla.kv"):
            latent = dense(rank + rope, name="kv_a")(y)
            k_rope = latent[..., rank:].reshape(B, S, 1, rope)
            kv = dense(h * (nope + dv), name="kv_b")(
                norm(name="kv_a_norm")(latent[..., :rank]))
            kv = kv.reshape(B, S, h, nope + dv)
        with timeline.device_scope("bf.mla.rope"):
            freq = None if scaling is None else yarn_frequencies(
                rope, cfg.rope_theta, scaling)
            q = apply_rope(q, positions, cfg.rope_theta, freq, first=nope)
            k_rope = apply_rope(k_rope, positions, cfg.rope_theta, freq)
            if scaling is not None:
                # YaRN's factor on cos and sin (1 where mscale equals
                # mscale_all_dim)
                turn = yarn_mscale(scaling["factor"],
                                   scaling.get("mscale", 1)) / yarn_mscale(
                    scaling["factor"], scaling.get("mscale_all_dim", 0))
                if turn != 1.0:
                    q = q.at[..., nope:].multiply(turn)
                    k_rope = k_rope * turn
        scale = (nope + rope) ** -0.5
        if scaling is not None and scaling.get("mscale_all_dim"):
            scale *= yarn_mscale(scaling["factor"],
                                 scaling["mscale_all_dim"]) ** 2
        with timeline.device_scope("bf.mla.attend"):
            k = jnp.concatenate(
                [kv[..., :nope], jnp.broadcast_to(k_rope, (B, S, h, rope))],
                axis=-1)
            attn = self.attn_impl(q, k, kv[..., nope:], causal=cfg.causal,
                                  scale=scale, **_documents(segment_ids))
        if getattr(cfg, "attn_gate", None) == "head":
            with timeline.device_scope("bf.mla.gate"):
                attn = attn * nn.sigmoid(dense(h, name="attn_gate")(
                    y).astype(jnp.float32)).astype(cfg.dtype)[..., None]
        with timeline.device_scope("bf.mla.out"):
            return dense(cfg.embed_dim, name="proj")(
                attn.reshape(B, S, h * dv))


class HyperConnection(nn.Module):
    """The three maps of manifold-constrained hyper-connections (mHC,
    arXiv:2512.24880) around one sub-layer of a block whose residual is
    ``n = cfg.hyper_streams`` streams, carried as ``x`` ``(B, S, n * d)``.

    ``__call__(x)`` returns ``(u, mix)``: ``u`` ``(B, S, d)`` is what the
    sub-layer reads, and ``mix(f)`` puts its result ``f`` back.  All of the
    map is float32: ``z = RMSNorm_eps(x)`` over the ``n * d`` values of a
    token (``eps = cfg.hyper_eps``, a learned scale); ``[l_pre (n), l_post
    (n), l_res (n * n)] = z phi[:-1] + phi[-1]``; ``H_pre =
    sigmoid(l_pre)``; ``H_post = 2 sigmoid(l_post)``; ``M = exp(clip(l_res,
    *cfg.hyper_res_clamp))`` as ``(n, n)``, then
    ``cfg.hyper_sinkhorn_iters`` times each row divided by its sum ``+
    eps`` and each column by its sum ``+ eps``: ``H_res``, nearly doubly
    stochastic.  ``u = sum_i H_pre[i] x_i`` and ``mix(f)_i = sum_j
    H_res[i, j] x_j + H_post[i] f``.

    Two leaves: ``scale`` ``(n d,)`` and ``phi`` ``(n d + 1, n n + 2 n)``,
    whose last row holds the three biases.  The paper's gains ``alpha`` (a
    logit is ``alpha * (z Phi) + b``, ``alpha`` 0.01 at first) are the
    scale of ``phi``'s columns and no leaf of their own: the rows of
    ``phi`` start at 0.01 times lecun normal, the same functions.  At init
    ``sigmoid(b_pre) = 1 / n``, ``b_post`` is 0 and ``b_res`` 8 times the
    identity, so a fresh block is a pre-norm residual block on the streams'
    mean.  Device scopes: ``bf.mhc.map``, ``bf.mhc.sinkhorn``,
    ``bf.mhc.mix``."""
    cfg: Any

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        n, d, eps = cfg.hyper_streams, cfg.embed_dim, cfg.hyper_eps
        B, S, _ = x.shape
        scale = self.param("scale", nn.initializers.ones, (n * d,))

        def init_phi(key, shape):
            bias = jnp.concatenate([
                jnp.full((n,), -math.log(n - 1.0)), jnp.zeros((n,)),
                8.0 * jnp.eye(n).ravel()])
            rows = nn.initializers.lecun_normal()(key, (n * d, shape[1]))
            return jnp.concatenate([0.01 * rows, bias[None]])

        phi = self.param("phi", init_phi, (n * d + 1, n * n + 2 * n))
        streams = [x[..., i * d:(i + 1) * d].astype(jnp.float32)
                   for i in range(n)]
        with timeline.device_scope("bf.mhc.map"):
            x32 = x.astype(jnp.float32)
            z = x32 * jax.lax.rsqrt(
                jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps) * scale
            # float32 for real, as the router's: the default precision of
            # a float32 matmul on the TPU is one bfloat16 pass.  The tokens
            # go last, so that the small (n, n) work below is over whole
            # rows of tokens and not over 4 x 4 corners of padded tiles.
            logit = jnp.einsum("bsk,km->mbs", z, phi[:-1],
                               precision=jax.lax.Precision.HIGHEST) \
                + phi[-1][:, None, None]
            h_pre = nn.sigmoid(logit[:n])
            h_post = 2.0 * nn.sigmoid(logit[n:2 * n])
        with timeline.device_scope("bf.mhc.sinkhorn"):
            lo, hi = cfg.hyper_res_clamp
            m = jnp.exp(jnp.clip(logit[2 * n:].reshape(n, n, B, S), lo, hi))
            for _ in range(cfg.hyper_sinkhorn_iters):
                m = m / (m.sum(axis=1, keepdims=True) + eps)
                m = m / (m.sum(axis=0, keepdims=True) + eps)
        with timeline.device_scope("bf.mhc.mix"):
            u = sum(h_pre[i][..., None] * streams[i] for i in range(n))

        def mix(f):
            with timeline.device_scope("bf.mhc.mix"):
                f32 = f.astype(jnp.float32)
                return jnp.concatenate([
                    sum(m[i, j][..., None] * streams[j] for j in range(n))
                    + h_post[i][..., None] * f32
                    for i in range(n)], axis=-1).astype(x.dtype)
        return u.astype(x.dtype), mix


def relu2(x):
    """``relu(x)^2``, the un-gated activation of ``mlp="relu2"``."""
    return jnp.square(nn.relu(x))


def causal_taps(u, w):
    """``c_t = sum_j w[:, j] * u_{t - (L - 1) + j}`` over the ``L`` taps of
    ``w`` ``(channels, L)``, each channel of ``u`` ``(B, S, channels)`` on
    its own (depthwise), causal, zeros left of the sequence: ``L`` shifted
    multiply-adds in plain ``jax.numpy``."""
    taps, S = w.shape[1], u.shape[1]
    conv = w[:, taps - 1] * u
    for back in range(1, taps):
        conv = conv + w[:, taps - 1 - back] * jnp.pad(
            u, ((0, 0), (back, 0), (0, 0)))[:, :S]
    return conv


class ShortConv(nn.Module):
    """Gated short convolution (LFM2's ``conv`` layers): the token mixer of
    a block whose ``cfg.layer_types`` entry is ``"conv"``.

    ``[B, C, X] = split_3(y in)``; ``u = B * X``; ``c_t = sum_j w[:, j] *
    u_{t - (L - 1) + j}`` over the ``L = cfg.conv_kernel`` taps, each channel
    on its own (depthwise), causal, zeros left of the sequence; the result
    is ``(C * c) out``.  No bias, no normalisation and no activation inside.
    Leaves: ``in/kernel`` ``(d, 3 d)``, ``w`` ``(d, L)`` and ``out/kernel``
    ``(d, d)``.  The taps are ``L`` shifted multiply-adds in plain
    ``jax.numpy``, in ``cfg.dtype``.  Training and prefill only: the layer
    keeps no state for decoding.

    Device scopes: ``bf.sconv.in``, ``bf.sconv.conv`` (both gates and the
    convolution) and ``bf.sconv.out``."""
    cfg: Any

    @nn.compact
    def __call__(self, y):
        cfg = self.cfg
        d, taps = cfg.embed_dim, cfg.conv_kernel
        dense = functools.partial(nn.Dense, use_bias=False, dtype=cfg.dtype)
        with timeline.device_scope("bf.sconv.in"):
            gates = dense(3 * d, name="in")(y)
        # fan-in of one channel: its ``taps`` values
        w = self.param("w", nn.initializers.lecun_normal(in_axis=1,
                                                         out_axis=0),
                       (d, taps)).astype(cfg.dtype)
        with timeline.device_scope("bf.sconv.conv"):
            b, c, x = jnp.split(gates, 3, axis=-1)
            gated = c * causal_taps(b * x, w)
        with timeline.device_scope("bf.sconv.out"):
            return dense(d, name="out")(gated)


def _dt_bias_init(lo: float, hi: float, floor: float):
    """An initialiser whose ``softplus`` is log-uniform in ``(lo, hi)``, no
    smaller than ``floor`` (Mamba-2's time steps; Kimi Delta Attention's
    ``dt_bias`` starts the same way)."""
    def init(key, shape):
        step = jnp.maximum(jnp.exp(jax.random.uniform(
            key, shape, minval=math.log(lo), maxval=math.log(hi))), floor)
        return step + jnp.log(-jnp.expm1(-step))    # softplus's inverse
    return init


def _a_log_init(key, shape):
    """``log`` of uniform ``[1, 16]``: where ``A_log`` starts."""
    return jnp.log(jax.random.uniform(key, shape, minval=1.0, maxval=16.0))


class Mamba2Mixer(nn.Module):
    """Mamba-2's mixer (arXiv:2405.21060, as Nemotron-H lays it out): the
    token mixer of a block whose ``cfg.layer_types`` entry is ``"mamba"``.

    ``H = cfg.ssm_heads`` heads of ``P = cfg.ssm_head_dim`` (inner width
    ``I = H P``), ``G = cfg.ssm_groups`` groups with a state of ``N =
    cfg.ssm_state``.  ``[z | xBC | dt] = y W_in`` with ``I + (I + 2 G N) +
    H`` columns, no bias; ``xBC <- silu(conv(xBC) + b)``, a causal depthwise
    convolution of ``cfg.conv_kernel`` taps, zeros left of the sequence;
    ``xBC`` splits into ``x`` (H heads of P), ``B`` and ``C`` (G groups of
    N; head ``h`` reads group ``h // (H / G)``); ``dt <- softplus(dt +
    dt_bias)`` and ``A = -exp(A_log)`` a head, both float32; the recurrence
    ``h_t = exp(dt_t A) h_{t-1} + dt_t x_t B_t^T``, ``o_t = h_t C_t + D
    x_t`` runs chunk by chunk (``ops.ssd.ssd_scan`` at ``cfg.ssm_chunk``);
    then ``o <- RMSNorm(o * silu(z))`` over each of the ``G`` groups of ``I /
    G`` values with one scale of ``I``, in float32
    (``ops.gated_norm.gated_rms_norm``), and ``o W_out``.

    Leaves: ``in/kernel`` ``(d, 2 I + 2 G N + H)``, ``conv_w`` ``(I + 2 G
    N, taps)``, ``conv_b``, ``dt_bias``, ``A_log``, ``D`` ``(H,)``,
    ``norm_scale`` ``(I,)`` and ``out/kernel`` ``(I, d)``.  At init ``A`` is
    uniform in ``-[1, 16]``, ``D`` one and ``softplus(dt_bias)`` log-uniform
    in ``cfg.ssm_dt_init``.  Training and prefill only: the layer keeps no
    state for decoding.

    Device scopes: ``bf.ssm.in``, ``bf.ssm.conv`` (taps, bias, SiLU),
    ``bf.ssm.scan`` (time steps, decays, the chunked scan, the skip),
    ``bf.ssm.norm`` (gate and grouped norm: the Pallas kernels
    ``bf_gated_norm_fwd`` and ``bf_gated_norm_bwd``, forward, remat
    recompute and transpose, beside the cut of ``z`` out of the
    in-projection's result and the sum for ``norm_scale``'s gradient) and
    ``bf.ssm.out``.  On a TPU a group ``I / G`` that is no multiple of 128
    raises (``gated_norm.check_tileable``), as the scan's shapes do
    (``ssd.check_tileable``); ``bf_kernel_stagings_total{kernel=
    "bf_gated_norm_fwd" | "bf_gated_norm_bwd"}`` counts the shapes the norm's
    kernels were staged for."""
    cfg: Any

    @nn.compact
    def __call__(self, y):
        from bluefog_tpu.ops.gated_norm import gated_rms_norm
        from bluefog_tpu.ops.ssd import ssd_scan
        cfg = self.cfg
        H, P, G, N = (cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_groups,
                      cfg.ssm_state)
        inner, taps = H * P, cfg.conv_kernel
        B_, S = y.shape[:2]
        dense = functools.partial(nn.Dense, use_bias=False, dtype=cfg.dtype)
        with timeline.device_scope("bf.ssm.in"):
            zxbcdt = dense(2 * inner + 2 * G * N + H, name="in")(y)
        z = zxbcdt[..., :inner]
        xbc = zxbcdt[..., inner:2 * inner + 2 * G * N]
        dt = zxbcdt[..., 2 * inner + 2 * G * N:]
        # fan-in of one channel: its ``taps`` values
        w = self.param("conv_w", nn.initializers.lecun_normal(
            in_axis=1, out_axis=0), (inner + 2 * G * N, taps))
        b = self.param("conv_b", nn.initializers.zeros, (inner + 2 * G * N,))
        dt_bias = self.param("dt_bias", _dt_bias_init(*cfg.ssm_dt_init),
                             (H,))
        a_log = self.param("A_log", _a_log_init, (H,))
        skip = self.param("D", nn.initializers.ones, (H,))
        scale = self.param("norm_scale", nn.initializers.ones, (inner,))
        with timeline.device_scope("bf.ssm.conv"):
            xbc = nn.silu(causal_taps(xbc, w.astype(cfg.dtype))
                          + b.astype(cfg.dtype))
        with timeline.device_scope("bf.ssm.scan"):
            o = ssd_scan(
                xbc[..., :inner].reshape(B_, S, H, P),
                nn.softplus(dt.astype(jnp.float32) + dt_bias),
                -jnp.exp(a_log.astype(jnp.float32)),
                xbc[..., inner:inner + G * N].reshape(B_, S, G, N),
                xbc[..., inner + G * N:].reshape(B_, S, G, N),
                chunk=cfg.ssm_chunk, D=skip)
        with timeline.device_scope("bf.ssm.norm"):
            gated = gated_rms_norm(o.reshape(B_, S, inner), z, scale,
                                   groups=G, eps=cfg.rms_norm_eps)
        with timeline.device_scope("bf.ssm.out"):
            return dense(cfg.embed_dim, name="out")(gated)


class KimiDeltaMixer(nn.Module):
    """Kimi Delta Attention (arXiv:2510.26692, section 3, as Ling-3.0 lays
    it out): the token mixer of a block whose ``cfg.layer_types`` entry is
    ``"kda"``.  A linear-attention layer whose state is corrected by what it
    already predicts for the current key, under a decay per channel.

    ``H = cfg.num_heads`` heads of ``D = cfg.head_dim`` (None: ``embed_dim //
    num_heads``; inner width ``I = H D``), keys and values alike; no bias
    and no positional encoding.  ``[q | k | v] = silu(conv(y W_qkv))``, a
    causal depthwise convolution of ``cfg.conv_kernel`` taps on each of the
    ``3 I`` channels, zeros left of the sequence; ``q`` and ``k`` are
    divided by their length over each head, ``x / sqrt(sum x^2 + 1e-6)``,
    and ``q`` is scaled by ``D^-0.5``.  The log decays a channel of the key,
    float32: ``g = cfg.kda_lower_bound * sigmoid(exp(A_log_h) * (y W_f +
    dt_bias))``, each in ``(kda_lower_bound, 0)`` (``W_f`` one matrix; its
    product leaves the array in float32); ``beta = sigmoid(y W_beta)``, one
    value a head.  The rule, a state ``S`` of ``D x D`` a head in float32,
    zero before the first token: ``S' = Diag(exp(g_t)) S_{t-1}``, ``S_t = S'
    + beta_t k_t (v_t - S'^T k_t)^T``, ``o_t = S_t^T q_t``, run chunk by
    chunk (``ops.kda.kda_chunked`` at ``cfg.kda_chunk``).  Then ``o <-
    RMSNorm(o) * sigmoid(y W_g)`` over each head's ``D`` values in float32,
    one learned scale of ``D`` that the heads share, and ``o W_out``.

    Leaves: ``qkv/kernel`` ``(d, 3 I)``, ``conv_w`` ``(3 I, taps)``,
    ``f/kernel`` ``(d, I)``, ``dt_bias`` ``(I,)``, ``A_log`` ``(H,)``,
    ``beta/kernel`` ``(d, H)``, ``gate/kernel`` ``(d, I)``, ``norm_scale``
    ``(D,)`` and ``out/kernel`` ``(I, d)``.  At init ``exp(A_log)`` is
    uniform in ``[1, 16]`` and ``softplus(dt_bias)`` log-uniform in
    ``cfg.ssm_dt_init``, as flash-linear-attention starts them.  Training
    and prefill only: the rule hands on no state and the convolution keeps
    no last taps.

    Device scopes: ``bf.kda.qkv``, ``bf.kda.conv`` (taps, SiLU, the lengths),
    ``bf.kda.gate`` (``W_f``, the decays, ``beta``), ``bf.kda.chunk`` (the
    chunked rule), ``bf.kda.norm`` (``W_g``, the norm, the gate) and
    ``bf.kda.out``."""
    cfg: Any

    @nn.compact
    def __call__(self, y):
        from bluefog_tpu.ops.kda import kda_chunked
        cfg = self.cfg
        H = cfg.num_heads
        D = getattr(cfg, "head_dim", None) or cfg.embed_dim // H
        inner, taps, f32 = H * D, cfg.conv_kernel, jnp.float32
        B_, S = y.shape[:2]
        dense = functools.partial(nn.Dense, use_bias=False, dtype=cfg.dtype)
        with timeline.device_scope("bf.kda.qkv"):
            qkv = dense(3 * inner, name="qkv")(y)
        # fan-in of one channel: its ``taps`` values
        w = self.param("conv_w", nn.initializers.lecun_normal(
            in_axis=1, out_axis=0), (3 * inner, taps))
        dt_bias = self.param("dt_bias", _dt_bias_init(*cfg.ssm_dt_init),
                             (inner,))
        a_log = self.param("A_log", _a_log_init, (H,))
        scale = self.param("norm_scale", nn.initializers.ones, (D,))
        with timeline.device_scope("bf.kda.conv"):
            qkv = nn.silu(causal_taps(qkv, w.astype(cfg.dtype))).reshape(
                B_, S, 3, H, D)

            def unit(x):
                x = x.astype(f32)
                return x * jax.lax.rsqrt(
                    jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)
            q = (unit(qkv[:, :, 0]) * D ** -0.5).astype(cfg.dtype)
            k = unit(qkv[:, :, 1]).astype(cfg.dtype)
            v = qkv[:, :, 2]
        with timeline.device_scope("bf.kda.gate"):
            # float32 out of the array: the decays' sums run over a chunk
            f = dense(inner, name="f", dot_general=functools.partial(
                jax.lax.dot_general, preferred_element_type=f32))(y)
            g = cfg.kda_lower_bound * nn.sigmoid(
                jnp.exp(a_log.astype(f32))[:, None]
                * (f + dt_bias).reshape(B_, S, H, D))
            beta = nn.sigmoid(dense(H, name="beta")(y).astype(f32))
        with timeline.device_scope("bf.kda.chunk"):
            o = kda_chunked(q, k, v, g, beta, chunk=cfg.kda_chunk)
        with timeline.device_scope("bf.kda.norm"):
            o = o.astype(f32)
            o = o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True)
                                  + cfg.rms_norm_eps) * scale
            gate = dense(inner, name="gate")(y).reshape(B_, S, H, D)
            o = (o * nn.sigmoid(gate.astype(f32))).astype(cfg.dtype)
        with timeline.device_scope("bf.kda.out"):
            return dense(cfg.embed_dim, name="out")(o.reshape(B_, S, inner))


def _documents(segment_ids) -> dict:
    """What an ``attn_impl`` is handed beside q, k and v for a packed row:
    nothing without ids, so that one that knows none serves every model
    that packs nothing."""
    return {} if segment_ids is None else {"segment_ids": segment_ids}


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
    layer_idx: int = 0      # which block of the model: the first
                            # cfg.dense_layers keep the dense MLP

    @nn.compact
    def __call__(self, x, positions=None, cache=None, segment_ids=None):
        """Training/prefill path when ``cache is None``; with ``cache =
        (k_cache, v_cache)`` (shapes ``(B, L, kv_h, d)``) the input is ONE
        new token per sequence (S == 1) written at position ``positions``
        and attended against the cache — returns ``(x, new_cache)``.  The
        cache stores the kv_h *shared* heads, so GQA shrinks it by
        ``h / kv_h`` (the reason GQA exists).

        The mixer is the block's entry of ``cfg.layer_types`` (None: every
        block attends); the entry ``"ffn"`` is a block with no mixer, and
        under ``cfg.block_ffn == False`` no feed-forward part follows a
        mixer: the block is then one part alone on the one residual path,
        ``x <- x + f(RMSNorm(x))``.  Only plain full attention (MHA / GQA)
        takes a cache; latent attention, a sliding window, the gated short
        convolution, the Mamba-2 mixer and Kimi Delta Attention keep no
        decode state and raise on one, as does a block without a mixer.

        ``segment_ids`` ``(B, S)``: the documents of a packed row
        (``data.pack_documents``; ``positions`` restart with them).  Full
        attention, plain or latent, hands them to ``attn_impl``; a block
        that cannot keep the documents apart raises (a convolution's taps
        and a scan's or a delta rule's state would have to be reset at a
        boundary, and a window's kernels know no document mask), as does a
        decode cache.

        Plain attention reads its sizes by layer: ``cfg.head_dim`` (None:
        ``embed_dim // num_heads``), the block's entry of
        ``cfg.num_heads_per_layer``, the rotary scheme of its layer type
        (``rope_scheme``), the window of a ``"sliding_attention"`` layer
        (handed to ``attn_impl`` as ``window=``), and under
        ``cfg.attn_gate == "head"`` the leaf ``attn_gate`` ``(embed_dim,
        heads)``: ``o_h <- sigmoid(y W_g)_h o_h`` before the output
        projection.

        Device scopes of the plain attention branch, a full layer's:
        ``bf.attn.qkv``, ``bf.attn.norm``, ``bf.attn.rope``,
        ``bf.attn.attend`` (the K/V fan-out and the attention itself),
        ``bf.attn.gate`` and ``bf.attn.out``; a sliding layer's are the
        family ``bf.swa.*`` of the same names.  The other mixers carry
        their own: ``bf.mla.*`` (``LatentAttention``), ``bf.sconv.*``
        (``ShortConv``), ``bf.ssm.*`` (``Mamba2Mixer``) and ``bf.kda.qkv``,
        ``bf.kda.conv``, ``bf.kda.gate``, ``bf.kda.chunk``, ``bf.kda.norm``,
        ``bf.kda.out`` (``KimiDeltaMixer``)."""
        cfg = self.cfg
        layer_types = getattr(cfg, "layer_types", None)
        kind = (layer_types[self.layer_idx] if layer_types is not None
                else "full_attention")
        per_layer = getattr(cfg, "num_heads_per_layer", None)
        h = per_layer[self.layer_idx] if per_layer else cfg.num_heads
        d = getattr(cfg, "head_dim", None) or cfg.embed_dim // cfg.num_heads
        kv_h = cfg.num_kv_heads or h
        rope = getattr(cfg, "pos_encoding", "learned") == "rope"
        if rope and positions is None and cache is None:
            # standalone Block use (e.g. pipeline stages): local positions
            positions = jnp.arange(x.shape[1])[None, :]
        eps = getattr(cfg, "rms_norm_eps", 1e-6)
        # the feed-forward part behind the mixer, or nothing
        ffn = self._ffn if getattr(cfg, "block_ffn", True) \
            else lambda x, eps: x
        if kind == "ffn":
            if cache is not None:
                raise NotImplementedError(
                    "a block without a mixer takes no decode cache: "
                    "generate() fills one K/V entry a block")
            return self._ffn(x, eps)
        x, join = self._residual(x, "hc_attn")
        y = nn.RMSNorm(epsilon=eps, dtype=cfg.dtype)(x)
        B, S = y.shape[0], y.shape[1]
        conv, sliding = kind == "conv", kind == "sliding_attention"
        mamba, kda = kind == "mamba", kind == "kda"
        latent = getattr(cfg, "kv_lora_rank", None) is not None
        if cache is not None and (conv or latent or sliding or mamba or kda):
            raise NotImplementedError(
                "only plain full attention takes a decode cache: latent "
                "attention, a sliding window, the gated short convolution, "
                "the Mamba-2 mixer and Kimi Delta Attention (whose rule "
                "hands on no state and whose convolutions keep no last "
                "taps) do not")
        if segment_ids is not None and (conv or mamba or kda or sliding
                                         or cache is not None):
            raise NotImplementedError(
                f"segment_ids reached a {kind!r} block"
                + (" with a decode cache" if cache is not None else "")
                + ": a packed row needs the gated short convolution's taps, "
                "the Mamba-2 scan's state and the delta rule's state and "
                "taps reset at every boundary, a window's kernels masked "
                "by document and a cache written by document; only full "
                "attention (plain or latent) keeps documents apart")
        if conv:
            x = join(ShortConv(cfg, name="conv")(y))
            return ffn(x, eps)
        if mamba:
            x = join(Mamba2Mixer(cfg, name="mamba")(y))
            return ffn(x, eps)
        if kda:
            x = join(KimiDeltaMixer(cfg, name="kda")(y))
            return ffn(x, eps)
        if latent:
            x = join(LatentAttention(cfg, self.attn_impl, name="mla")(
                y, positions, **_documents(segment_ids)))
            return ffn(x, eps)
        scope = "bf.swa" if sliding else "bf.attn"
        with timeline.device_scope(f"{scope}.qkv"):
            if kv_h == h:
                qkv = nn.Dense(3 * h * d, use_bias=False,
                               dtype=cfg.dtype, name="qkv")(y)
                # Head-interleaved fused layout [q_h0 k_h0 v_h0 | q_h1 ...]:
                # a pure relabeling of kernel columns that keeps tensor-
                # parallel shard boundaries (tp_param_specs' column split)
                # aligned to heads, so GSPMD runs attention head-parallel
                # with one psum per block instead of per-activation
                # resharding.
                qkv = qkv.reshape(B, S, h, 3, d)
                q, k1, v1 = qkv[..., 0, :], qkv[..., 1, :], qkv[..., 2, :]
            else:
                # GQA: h query heads, kv_h shared K/V heads (same
                # interleaved column layout per projection; head-aligned TP
                # only up to kv_h ways — beyond that GSPMD re-gathers K/V
                # per block, acceptable since the kv kernel is the small
                # one).
                q = nn.Dense(h * d, use_bias=False, dtype=cfg.dtype,
                             name="q")(y).reshape(B, S, h, d)
                kv = nn.Dense(2 * kv_h * d, use_bias=False, dtype=cfg.dtype,
                              name="kv")(y).reshape(B, S, kv_h, 2, d)
                k1, v1 = kv[..., 0, :], kv[..., 1, :]
        qk_norm = getattr(cfg, "qk_norm", False)
        with timeline.device_scope(f"{scope}.norm"):
            norm = functools.partial(nn.RMSNorm, epsilon=eps,
                                     dtype=cfg.dtype)
            if qk_norm == "head":
                # over each head's own d values, one scale of d for all
                # the heads, before rope
                q = norm(name="q_norm")(q)
                k1 = norm(name="k_norm")(k1)
            elif qk_norm:
                # over the whole projection, all heads together, before
                # rope
                q = norm(name="q_norm")(
                    q.reshape(B, S, h * d)).reshape(B, S, h, d)
                k1 = norm(name="k_norm")(
                    k1.reshape(B, S, kv_h * d)).reshape(B, S, kv_h, d)
        if rope:
            # rotate the kv_h shared heads ONCE, before any fan-out to h
            with timeline.device_scope(f"{scope}.rope"):
                rot, theta, freq, factor = rope_scheme(cfg, kind, d)
                q = apply_rope(q, positions, theta, freq, factor, rot)
                k1 = apply_rope(k1, positions, theta, freq, factor, rot)
        rep = h // kv_h
        if cache is None:
            if (self.is_mutable_collection("kv_cache")
                    and not self.is_initializing()):
                # prefill: expose the per-position shared-head K/V so
                # ``generate`` can fill its decode cache in ONE forward.
                # Gated out of init(), which would otherwise bake a stale
                # entry into the variables users carry around.
                self.sow("kv_cache", "kv_entries", (k1, v1))
            with timeline.device_scope(f"{scope}.attend"):
                k = jnp.repeat(k1, rep, axis=2) if rep > 1 else k1
                v = jnp.repeat(v1, rep, axis=2) if rep > 1 else v1
                # only a sliding layer names a window: an attn_impl that
                # knows none serves every model without one
                reach = {"window": cfg.sliding_window} if sliding else {}
                attn = self.attn_impl(
                    q, k, v, causal=getattr(self.cfg, "causal", True),
                    **reach, **_documents(segment_ids))
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
            with timeline.device_scope(f"{scope}.attend"):
                qg = q.reshape(B, S, kv_h, rep, d)
                logits = jnp.einsum("bqgrd,blgd->bgrql", qg, ck) \
                    / np.sqrt(d)
                mask = (jnp.arange(L) <= idx)[None, None, None, None, :]
                logits = jnp.where(mask, logits.astype(jnp.float32),
                                   jnp.finfo(jnp.float32).min)
                probs = nn.softmax(logits, axis=-1).astype(cfg.dtype)
                attn = jnp.einsum("bgrql,blgd->bqgrd", probs, cv)
        if getattr(cfg, "attn_gate", None) == "head":
            with timeline.device_scope(f"{scope}.gate"):
                gate = nn.Dense(h, use_bias=False, dtype=cfg.dtype,
                                name="attn_gate")(y)
                attn = attn.reshape(B, S, h, d) * nn.sigmoid(
                    gate.astype(jnp.float32)).astype(cfg.dtype)[..., None]
        with timeline.device_scope(f"{scope}.out"):
            attn = attn.reshape(B, S, h * d)
            x = join(nn.Dense(cfg.embed_dim, use_bias=False,
                              dtype=cfg.dtype, name="proj")(attn))
        x = ffn(x, eps)
        return x if cache is None else (x, cache)

    def _residual(self, x, name):
        """What a sub-layer reads of the residual ``x``, and how its result
        ``f`` goes back: ``x`` and ``x + f``, or under ``cfg.hyper_streams``
        a gated sum of the streams and their mixing (``HyperConnection``
        ``name``)."""
        if getattr(self.cfg, "hyper_streams", 1) > 1:
            return HyperConnection(self.cfg, name=name)(x)
        return x, lambda f: x + f

    def _ffn(self, x, eps):
        """The block's second sub-layer on the residual ``x``: the experts
        (past the leading ``cfg.dense_layers`` blocks of a model that has
        any) or the dense MLP."""
        cfg = self.cfg
        x, join = self._residual(x, "hc_ffn")
        y = nn.RMSNorm(epsilon=eps, dtype=cfg.dtype)(x)
        hidden = (getattr(cfg, "mlp_dim", None)
                  or cfg.mlp_ratio * cfg.embed_dim)
        if (getattr(cfg, "num_experts", 0) > 0
                and self.layer_idx >= getattr(cfg, "dense_layers", 0)):
            moe = (DroplessMoe if getattr(cfg, "mlp", "gelu") in (
                "swiglu", "relu2") else SwitchMlp)
            x = join(moe(cfg, name="moe")(y))
        elif getattr(cfg, "mlp", "gelu") == "swiglu":
            gate = nn.Dense(hidden, use_bias=False, dtype=cfg.dtype,
                            name="gate")(y)
            up = nn.Dense(hidden, use_bias=False, dtype=cfg.dtype,
                          name="up")(y)
            x = join(nn.Dense(cfg.embed_dim, use_bias=False, dtype=cfg.dtype,
                              name="down")(nn.silu(gate) * up))
        else:
            y = nn.Dense(hidden, use_bias=False, dtype=cfg.dtype,
                         name="up")(y)
            y = relu2(y) if getattr(cfg, "mlp", "gelu") == "relu2" \
                else nn.gelu(y)
            x = join(nn.Dense(cfg.embed_dim, use_bias=False, dtype=cfg.dtype,
                              name="down")(y))
        return x


class TransformerLM(nn.Module):
    cfg: Any
    attn_impl: Optional[Callable] = None

    @nn.compact
    def __call__(self, tokens, train: bool = True, positions=None,
                 return_hidden: bool = False, cache=None, segment_ids=None):
        """``positions``: optional (B, S) global position ids — required when
        the sequence axis is sharded (each shard must embed its own offset).
        ``segment_ids``: optional (B, S) document ids of packed rows, not
        decreasing along a row (``data.pack_documents`` yields them with
        the ``positions`` that restart at each document): every block's
        attention then keeps to a token's own document, and a block that
        cannot raises.
        ``return_hidden``: skip the lm-head and return the final normalized
        activations (B, S, E) — pair with
        ``ops.chunked_loss.chunked_softmax_cross_entropy`` so very long
        sequences never materialize the (S, vocab) logits.
        ``cache``: list of per-block ``(k, v)`` caches (``init_cache``) for
        single-token incremental decoding — tokens must be (B, 1) at
        position ``positions``; returns ``(logits, new_cache)``."""
        cfg = self.cfg
        attn = self.attn_impl or local_attention
        streams = getattr(cfg, "hyper_streams", 1)
        mixers = getattr(cfg, "layer_types", None) \
            or ("full_attention",) * cfg.num_layers
        for kind in LAYER_KINDS:
            # what was built last, by kind of layer; set, not added to: a
            # model is traced more than once
            telemetry.set_gauge("bf_model_layers_total",
                                mixers.count(kind), mixer=kind)
        if cache is not None:
            if segment_ids is not None:
                raise NotImplementedError(
                    "KV-cache decoding of a packed row is not supported: "
                    "the cache is written by position, not by document")
            if getattr(cfg, "num_experts", 0) > 0:
                raise NotImplementedError(
                    "KV-cache decoding with MoE blocks is not supported")
            if streams > 1 or getattr(cfg, "kv_lora_rank", None) is not None:
                raise NotImplementedError(
                    "KV-cache decoding with latent attention or several "
                    "residual streams is not supported")
            if "conv" in mixers:
                raise NotImplementedError(
                    "KV-cache decoding through a gated short convolution "
                    "is not supported: the layer keeps no decode state")
            if "sliding_attention" in mixers:
                raise NotImplementedError(
                    "KV-cache decoding through a sliding-window layer is "
                    "not supported: its cache of the last sliding_window "
                    "keys is not written")
            if "mamba" in mixers:
                raise NotImplementedError(
                    "KV-cache decoding through a Mamba-2 layer is not "
                    "supported: the scan hands on no state and the "
                    "convolution keeps no last taps")
            if "kda" in mixers:
                raise NotImplementedError(
                    "KV-cache decoding through a Kimi Delta Attention "
                    "layer is not supported: the chunked rule hands on no "
                    "state and the convolutions keep no last taps")
            if "ffn" in mixers:
                raise NotImplementedError(
                    "KV-cache decoding through a block without a mixer is "
                    "not supported: generate() fills one K/V entry a block")
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
        wte = nn.Embed(cfg.vocab_size, cfg.embed_dim, dtype=cfg.dtype,
                       name="wte")
        x = wte(tokens)
        if positions is None:
            positions = jnp.arange(tokens.shape[1])[None, :]
        encoding = getattr(cfg, "pos_encoding", "learned")
        rope = encoding == "rope"
        if encoding == "learned":
            pos = nn.Embed(cfg.max_seq_len, cfg.embed_dim,
                           dtype=cfg.dtype, name="wpe")(positions)
            x = x + pos
        positions = jnp.broadcast_to(positions,
                                     (tokens.shape[0], tokens.shape[1]))
        if streams > 1:
            # every stream starts as the embedding; the blocks carry them
            # side by side, (B, S, n * d)
            x = jnp.tile(x, (1, 1, streams))
        new_cache = []
        for i in range(cfg.num_layers):
            block_cls = Block if cache is not None else block_class(cfg, i)
            blk = block_cls(cfg, attn, i, name=f"block_{i}")
            if cache is not None:
                x, blk_cache = blk(x, positions, cache[i])
                new_cache.append(blk_cache)
            elif segment_ids is not None:
                x = blk(x, positions, None, segment_ids)
            elif rope:
                x = blk(x, positions)
            else:
                x = blk(x)
        if streams > 1:
            # read-out: the streams' sum (the hyper-connections paper's)
            x = x.astype(jnp.float32).reshape(
                x.shape[:2] + (streams, cfg.embed_dim)).sum(axis=2)
        x = nn.RMSNorm(epsilon=getattr(cfg, "rms_norm_eps", 1e-6),
                       dtype=cfg.dtype)(x)
        if getattr(cfg, "tie_embeddings", False):
            # the embedding leaf serves both ends: its gradient is the sum
            # of the lookup's and the head's (``head_matrix``)
            def head(h):
                return jnp.dot(h.astype(jnp.float32),
                               wte.embedding.astype(jnp.float32).T)
            if return_hidden:
                return x
        else:
            head = nn.Dense(cfg.vocab_size, use_bias=False,
                            dtype=jnp.float32, name="lm_head")
            if return_hidden:
                head(x[:, :1])  # materialize the lm_head param without S x V
                return x
        if cache is not None:
            return head(x), new_cache
        return head(x)


def head_matrix(cfg, params):
    """The ``(embed_dim, vocab)`` output matrix of a ``TransformerLM``'s
    parameters, for ``ops.chunked_loss.chunked_softmax_cross_entropy`` beside
    ``return_hidden=True``: the ``lm_head`` kernel, or under
    ``cfg.tie_embeddings`` the transposed embedding (a view of the one leaf:
    no second copy in the tree, no second optimizer state)."""
    if getattr(cfg, "tie_embeddings", False):
        return params["wte"]["embedding"].T
    return params["lm_head"]["kernel"]


def init_cache(cfg, batch: int, max_len: int):
    """Per-block ``(k, v)`` KV caches for incremental decoding: shapes
    ``(batch, max_len, kv_heads, head_dim)`` — kv_heads, not num_heads, so
    GQA/MQA caches are ``num_heads / num_kv_heads`` times smaller."""
    h = cfg.num_heads
    d = getattr(cfg, "head_dim", None) or cfg.embed_dim // h
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
