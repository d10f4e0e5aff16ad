"""Operations and bytes a latent-attention, shared-expert sparse LM trained
on packed documents requires of the chip that holds a share of its experts,
computed from shapes (beside ``benchmark/flops.py``, ``flops_moe.py`` and
``flops_mla.py``, whose conventions hold: a multiply-accumulate is two
operations, recomputation is not counted, the embedding lookup is not a
matmul, of the routed experts only the held ones count at the share an even
router sends them).  Attention is counted at the **visible pairs** of the
packed layout: a query sees the keys at or before it in its own document,
``n (n + 1) / 2`` pairs a document of ``n`` tokens, and a kernel that masked
every tile of the triangle would read as the share of the triangle that is
visible (21.5% at the cell's ten documents), not as 100%.
"""

from __future__ import annotations

from benchmark import flops_mla, flops_moe

# the share of the routed assignments an even router sends to the experts
# held here: ``n_routed_experts / router_width``, the keys xing4.0-29b-a4b's
# file has too
held_share = flops_mla.held_share


def visible_pairs(documents) -> int:
    """Query-key pairs one head visits in a row of these documents."""
    return sum(n * (n + 1) // 2 for n in documents)


def latent_attention_params(config: dict) -> int:
    """Matmul weights of one latent-attention sub-layer: the queries (one
    matrix where ``q_lora_rank`` is null, else the bottleneck's two), the
    latent with the rotary key and its expansion, the output projection."""
    d, heads = config["hidden_size"], config["num_attention_heads"]
    qk = config["qk_nope_head_dim"] + config["qk_rope_head_dim"]
    nope, v = config["qk_nope_head_dim"], config["v_head_dim"]
    rank = config["q_lora_rank"]
    queries = d * heads * qk if rank is None else rank * (d + heads * qk)
    return (queries
            + d * (config["kv_lora_rank"] + config["qk_rope_head_dim"])
            + config["kv_lora_rank"] * heads * (nope + v)
            + heads * v * d)


def expert_layer_params(config: dict) -> float:
    """Matmul weights a token meets in one expert layer: the router over
    all experts, the shared experts' one SwiGLU and the held share of its
    ``num_experts_per_tok`` routed experts."""
    expert = 3 * config["hidden_size"] * config["moe_intermediate_size"]
    return (config["hidden_size"] * config["router_width"]
            + config["n_shared_experts"] * expert
            + config["num_experts_per_tok"] * held_share(config) * expert)


def packed_latent_moe_lm_train(config: dict, *, batch: int,
                               documents) -> dict:
    """Operations of one training step on ``batch`` rows packed from
    ``documents``: per token ``6 *`` the matmul weights it meets (latent
    attention in every block, the dense SwiGLU in the leading blocks, the
    expert layer in the others, the output head over the vocabulary rows
    held) plus attention forward and backward, ``6 * (qk + v)`` operations
    a head and a visible pair."""
    d, layers = config["hidden_size"], config["num_hidden_layers"]
    dense_layers = config["first_k_dense_replace"]
    tokens = batch * sum(documents)
    dense = 3 * d * config["intermediate_size"]
    weights = (layers * latent_attention_params(config)
               + dense_layers * dense
               + (layers - dense_layers) * expert_layer_params(config))
    head = d * config["vocab_size"]
    qk = config["qk_nope_head_dim"] + config["qk_rope_head_dim"]
    attention = (6 * (qk + config["v_head_dim"])
                 * config["num_attention_heads"] * layers * batch
                 * visible_pairs(documents))
    blocks = int(6 * weights * tokens)
    return {"flops": blocks + 6 * head * tokens + attention,
            "blocks": blocks, "head": 6 * head * tokens,
            "attention": attention,
            "latent_attention": 6 * layers * latent_attention_params(config)
            * tokens + attention,
            "experts": int(6 * (layers - dense_layers)
                           * expert_layer_params(config) * tokens),
            "dense_mlp": 6 * dense_layers * dense * tokens,
            "matmul_params": int(weights + head)}


def flash_kernel(kind: str, *, config: dict, batch: int, documents,
                 itemsize: int = 2) -> dict:
    """Operations and HBM bytes one call of a document-masked flash kernel
    (``bf_flash_seg_fwd / dq / dkv``) needs on ``batch`` rows of these
    ``documents`` at this configuration's heads (``qk_nope_head_dim +
    qk_rope_head_dim`` for queries and keys, ``v_head_dim`` for values).
    Per visible pair and head as ``flops_mla.flash_kernel`` counts a pair:
    ``fwd`` the scores and the values; ``dq`` the scores, dP and dQ;
    ``dkv`` the scores, dP, dV and dK.  Bytes: each operand and result
    once, the per-row logsumexp and delta in float32, the ids once a row
    in each of their two layouts."""
    qk_products, v_products, qk_tiles, v_tiles, rows = {
        "fwd": (1, 1, 2, 2, 1),      # q k | v o | lse
        "dq": (2, 1, 3, 2, 2),       # q k dq | v do | lse delta
        "dkv": (2, 2, 3, 3, 2),      # q k dk | v do dv | lse delta
    }[kind]
    qk = config["qk_nope_head_dim"] + config["qk_rope_head_dim"]
    v, seq = config["v_head_dim"], sum(documents)
    bh = batch * config["num_attention_heads"]
    return {
        "flops": 2 * (qk_products * qk + v_products * v)
        * visible_pairs(documents) * bh,
        "bytes": bh * (seq * itemsize * (qk_tiles * qk + v_tiles * v)
                       + rows * seq * 4) + batch * 2 * seq * 4,
    }


def grouped_product(kind: str, *, config: dict, tokens: int, inner: int,
                    outer: int, out_itemsize: int = 2) -> dict:
    """One grouped product of the held experts (``flops_moe.
    grouped_matmul``) at the rows an even router sends them: ``tokens *
    num_experts_per_tok * n_routed_experts / router_width`` (6144 of 49152
    at 8192 tokens: 384 an expert), against the held experts' ``(inner,
    outer)`` matrices; a SwiGLU expert has three such products a pass."""
    rows = round(tokens * config["num_experts_per_tok"] * held_share(config))
    return flops_moe.grouped_matmul(
        kind, rows=rows, inner=inner, outer=outer,
        groups=config["n_routed_experts"], itemsize=2,
        out_itemsize=out_itemsize)
