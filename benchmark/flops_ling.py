"""Operations and bytes a decoder whose blocks mix their tokens by Kimi Delta
Attention (the chunked delta rule) or by gated latent attention, and hold a
share of a group-limited sigmoid-routed mixture of experts with a shared
expert, requires of the chip, computed from shapes (beside
``benchmark/flops.py``, ``flops_moe.py``, ``flops_mla.py`` and
``flops_kanana.py``, whose conventions hold: a multiply-accumulate is two
operations, recomputation is not counted in a step's operations, the
embedding lookup is not a matmul).  Of the routed experts only the held ones
count, at the share of the assignments an even router sends them; the rule
is held to the products of its chunked form over the pairs at or under a
chunk's diagonal and to a substitution for the chunk's system (forming the
inverse is the program's choice and not required); the convolutions' taps,
the gates, the lengths and the norms are elementwise and not counted among
the operations; the grouped choice of experts is comparisons and not
counted.
"""

from __future__ import annotations

from benchmark import flops, flops_moe


def layer_types(config: dict) -> list:
    """The mixer of each layer: layer ``i`` is latent attention where ``(i +
    1) % layer_group_size == 0`` and Kimi Delta Attention otherwise."""
    period = config["layer_group_size"]
    return ["full_attention" if (i + 1) % period == 0 else "kda"
            for i in range(config["num_hidden_layers"])]


def kda_sizes(config: dict) -> tuple:
    """``(heads, head_dim, inner)`` of a KDA mixer: as many key and value
    heads as query heads (``num_kv_heads_for_linear_attn`` 0)."""
    heads, dim = config["num_attention_heads"], config["head_dim"]
    return heads, dim, heads * dim


def kda_params(config: dict) -> int:
    """Matmul weights of one KDA mixer: q, k, v, the decay gate, the output
    gate (one matrix each: ``no_kda_lora``), ``beta`` and the output
    projection."""
    heads, _, inner = kda_sizes(config)
    return config["hidden_size"] * (5 * inner + heads) \
        + inner * config["hidden_size"]


def latent_attention_params(config: dict) -> int:
    """Matmul weights of one gated latent-attention sub-layer: the queries
    (no bottleneck), the latent with the rotary key and its expansion, the
    head-wise gate, the output projection."""
    d, heads = config["hidden_size"], config["num_attention_heads"]
    qk = config["qk_nope_head_dim"] + config["qk_rope_head_dim"]
    assert config["q_lora_rank"] is None
    return (d * heads * qk
            + d * (config["kv_lora_rank"] + config["qk_rope_head_dim"])
            + config["kv_lora_rank"] * heads * (config["qk_nope_head_dim"]
                                                + config["v_head_dim"])
            + d * heads + heads * config["v_head_dim"] * d)


def held_share(config: dict) -> float:
    """The share of the routed assignments an even router sends to the
    experts held here."""
    return config["num_experts"] / config["router_width"]


def expert_layer_params(config: dict) -> float:
    """Matmul weights a token meets in one expert layer: the router over
    all ``router_width`` experts, the shared expert's SwiGLU and the held
    share of its ``num_experts_per_tok`` routed experts."""
    d = config["hidden_size"]
    return (d * config["router_width"]
            + config["num_shared_experts"] * 3 * d
            * config["moe_shared_expert_intermediate_size"]
            + config["num_experts_per_tok"] * held_share(config) * 3 * d
            * config["moe_intermediate_size"])


def kda_chunk(kind: str, *, config: dict, tokens: int, chunk: int,
              itemsize: int = 2) -> dict:
    """Operations and HBM bytes of ONE chunked pass of the delta rule of one
    KDA mixer over ``tokens`` positions (what runs between the lengths and
    the gated norm), in chunks of ``chunk``.

    ``fwd`` (also the remat recompute), a chunk of ``C`` positions with ``T
    = C (C + 1) / 2`` pairs at or under its diagonal and ``T - C`` under it,
    a head of ``D`` for keys and values: the system's matrix ``(2 (T - C)
    D)``, its solution for the values and the keys by substitution (``2 (T -
    C) 2 D``), the queries' matrix (``2 T D``) and its product with the
    corrections (``2 T D``), and three products with a ``D x D`` state: what
    the state predicts, what it gives the queries, and its update (``2 C D
    D`` each).  Bytes: ``q``, ``k`` and ``v`` and the float32 log decays and
    ``beta`` read, ``o`` written, each once; the chunk's matrices and the
    states are the form's own and would stay on the chip in one kernel.
    ``bwd``: every product is differentiated in both operands (twice the
    operations); the five inputs and ``do`` read, five gradients written."""
    heads, dim, inner = kda_sizes(config)
    chunks = -(-tokens // chunk)
    pairs = chunk * (chunk + 1) // 2
    under = pairs - chunk
    forward = chunks * heads * (2 * under * dim + 4 * under * dim
                                + 4 * pairs * dim + 6 * chunk * dim * dim)
    inputs = tokens * (3 * inner * itemsize + inner * 4 + heads * 4)
    result = tokens * inner * itemsize
    if kind == "fwd":
        return {"flops": forward, "bytes": inputs + result}
    if kind == "bwd":
        return {"flops": 2 * forward, "bytes": 2 * inputs + result}
    raise ValueError(f"kda_chunk: unknown kind {kind!r}")


def delta_moe_lm_train(config: dict, *, batch: int, seq: int) -> dict:
    """Operations of one training step on ``batch`` sequences of ``seq``
    tokens, by kind of layer: per token ``6 *`` the matmul weights it meets
    (``kda_params`` or ``latent_attention_params`` in a block's mixer, the
    dense SwiGLU in the leading blocks, ``expert_layer_params`` in the
    others, the output head over the vocabulary rows held), the rule's
    chunked products forward and backward (``kda_chunk``) and causal
    attention over the triangle in the latent layers."""
    d, layers = config["hidden_size"], config["num_hidden_layers"]
    dense_layers, tokens = config["first_k_dense_replace"], batch * seq
    kinds = layer_types(config)
    n_kda, n_mla = kinds.count("kda"), kinds.count("full_attention")
    chunk = config["model"]["args"]["kda_chunk"]
    rule = n_kda * batch * sum(
        kda_chunk(kind, config=config, tokens=seq, chunk=chunk)["flops"]
        for kind in ("fwd", "bwd"))
    qk = config["qk_nope_head_dim"] + config["qk_rope_head_dim"]
    attention = (6 * (qk + config["v_head_dim"])
                 * config["num_attention_heads"] * batch
                 * flops._pairs(seq, True)) * n_mla
    dense = 3 * d * config["intermediate_size"]
    weights = (n_kda * kda_params(config)
               + n_mla * latent_attention_params(config)
               + dense_layers * dense
               + (layers - dense_layers) * expert_layer_params(config))
    head = d * config["vocab_size"]
    blocks = int(6 * weights * tokens) + rule
    return {"flops": blocks + 6 * head * tokens + attention,
            "blocks": blocks, "head": 6 * head * tokens,
            "attention": attention, "rule": rule,
            "kda_mixers": 6 * n_kda * kda_params(config) * tokens + rule,
            "latent_attention": 6 * n_mla * latent_attention_params(config)
            * tokens + attention,
            "experts": int(6 * (layers - dense_layers)
                           * expert_layer_params(config) * tokens),
            "dense_mlp": 6 * dense_layers * dense * tokens,
            "matmul_params": int(weights + head)}


def flash_kernel(kind: str, *, config: dict, batch: int, seq: int) -> dict:
    """One causal flash kernel call of a latent layer at this
    configuration's heads (``flops_mla.flash_kernel``: queries and keys of
    ``qk_nope_head_dim + qk_rope_head_dim``, values of ``v_head_dim``)."""
    from benchmark import flops_mla
    return flops_mla.flash_kernel(
        kind, batch=batch, seq=seq, heads=config["num_attention_heads"],
        qk_dim=config["qk_nope_head_dim"] + config["qk_rope_head_dim"],
        v_dim=config["v_head_dim"], causal=True, itemsize=2)


def grouped_product(kind: str, *, config: dict, tokens: int, inner: int,
                    outer: int, out_itemsize: int = 2) -> dict:
    """One grouped product of the held experts (``flops_moe.
    grouped_matmul``) at the rows an even router sends them: ``tokens *
    num_experts_per_tok * num_experts / router_width`` (512 of 32768 at
    4096 tokens: 64 an expert), against the held experts' ``(inner,
    outer)`` matrices; a SwiGLU expert has three such products a pass."""
    rows = round(tokens * config["num_experts_per_tok"] * held_share(config))
    return flops_moe.grouped_matmul(
        kind, rows=rows, inner=inner, outer=outer,
        groups=config["num_experts"], itemsize=2, out_itemsize=out_itemsize)
