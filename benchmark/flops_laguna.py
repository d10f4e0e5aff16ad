"""Operations and bytes a decoder of window and full attention layers with
head counts of their own, a per-head output gate and a held share of
softmax-routed experts with a shared expert requires of the chip, computed
from shapes (beside ``benchmark/flops.py``, ``flops_moe.py``, ``flops_mla.py``
and ``flops_lfm2.py``, whose conventions hold: a multiply-accumulate is two
operations, recomputation is not counted in a step's operations, the
embedding lookup is not a matmul).  A window layer is held to the pairs its
window shows and never to the whole triangle; of the routed experts only the
held ones count, at the share of the assignments an even router sends them.
"""

from __future__ import annotations

from benchmark import flops, flops_moe


def visible_pairs(seq: int, window: int = None) -> int:
    """Query-key pairs one head visits over ``seq`` positions: query ``i``
    sees the keys ``max(0, i - window + 1) .. i``; no window (or one of the
    whole sequence) is the causal triangle."""
    if window is None or window >= seq:
        return seq * (seq + 1) // 2
    return window * (window + 1) // 2 + (seq - window) * window


def layer_window(config: dict, layer: int):
    """The window of layer ``layer``'s attention, None for a full layer."""
    return (config["sliding_window"]
            if config["layer_types"][layer] == "sliding_attention" else None)


def attention_params(config: dict, layer: int) -> int:
    """Matmul weights of one attention mixer: q at the layer's own head
    count, the packed k and v of the K/V heads, the per-head gate, the
    output projection back from ``heads * head_dim``."""
    d, dim = config["hidden_size"], config["head_dim"]
    heads = config["num_attention_heads_per_layer"][layer]
    gate = d * heads if config["gating"] else 0
    return (d * heads * dim + d * 2 * config["num_key_value_heads"] * dim
            + gate + heads * dim * d)


def attention_pairs_flops(config: dict, layer: int, *, batch: int,
                          seq: int) -> int:
    """Scores and values of one layer, forward and backward: 2 products
    forward and 4 backward a visible pair and head, ``head_dim``
    multiply-accumulates each."""
    heads = config["num_attention_heads_per_layer"][layer]
    return (12 * config["head_dim"] * heads * batch
            * visible_pairs(seq, layer_window(config, layer)))


def held_share(config: dict) -> float:
    """The share of the routed assignments an even router sends to the
    experts held here."""
    return config["num_experts"] / config["router_width"]


def window_moe_lm_train(config: dict, *, batch: int, seq: int) -> dict:
    """Operations of one training step on ``batch`` sequences of ``seq``
    tokens, by kind of layer: per token ``6 *`` the matmul weights it meets
    (each layer's attention mixer at its own head count; the dense SwiGLU
    in the ``dense`` layers of ``mlp_layer_types``, in the ``sparse`` ones
    the router over ``router_width``, the shared expert and the held share
    of its ``num_experts_per_tok`` routed experts; the output head over the
    vocabulary rows held), and attention over the pairs each layer's type
    shows (``visible_pairs``)."""
    d, layers = config["hidden_size"], config["num_hidden_layers"]
    tokens = batch * seq
    kinds = config["layer_types"]
    expert = 3 * d * config["moe_intermediate_size"]
    sparse = (d * config["router_width"]
              + 3 * d * config["shared_expert_intermediate_size"]
              + config["num_experts_per_tok"] * held_share(config) * expert)
    dense = 3 * d * config["intermediate_size"]
    dense_layers = config["mlp_layer_types"].count("dense")
    expert_layers = config["mlp_layer_types"].count("sparse")
    assert dense_layers + expert_layers == layers == len(kinds)
    mixers = {kind: sum(
        6 * attention_params(config, i) * tokens
        + attention_pairs_flops(config, i, batch=batch, seq=seq)
        for i in range(layers) if kinds[i] == kind)
        for kind in ("sliding_attention", "full_attention")}
    attention = sum(attention_pairs_flops(config, i, batch=batch, seq=seq)
                    for i in range(layers))
    weights = (sum(attention_params(config, i) for i in range(layers))
               + dense_layers * dense + expert_layers * sparse)
    head = d * config["vocab_size"]
    blocks = int(6 * weights * tokens)
    return {"flops": blocks + 6 * head * tokens + attention,
            "blocks": blocks, "head": 6 * head * tokens,
            "attention": attention,
            "window_mixers": mixers["sliding_attention"],
            "full_mixers": mixers["full_attention"],
            "experts": int(6 * expert_layers * sparse * tokens),
            "dense_mlp": 6 * dense_layers * dense * tokens,
            "matmul_params": int(weights + head)}


def flash_kernel(kind: str, *, config: dict, layer_type: str, batch: int,
                 seq: int, itemsize: int = 2) -> dict:
    """One flash attention kernel call of a layer of ``layer_type``:
    ``flops.flash_kernel`` (its products a pair and its bytes, each operand
    and result once) with the operations held to the pairs a window shows,
    ``pairs`` of them over all heads.  The K/V heads are repeated to the
    layer type's query heads before the kernel, so it sees that many heads
    of ``head_dim``, bfloat16."""
    layers = [i for i, t in enumerate(config["layer_types"])
              if t == layer_type]
    heads = {config["num_attention_heads_per_layer"][i] for i in layers}
    assert len(heads) == 1, f"one head count a layer type; got {heads}"
    heads = heads.pop()
    cost = flops.flash_kernel(kind, batch=batch, seq=seq, heads=heads,
                              head_dim=config["head_dim"], causal=True,
                              itemsize=itemsize)
    pairs = visible_pairs(seq, layer_window(config, layers[0]))
    return {"flops": cost["flops"] * pairs // visible_pairs(seq),
            "bytes": cost["bytes"], "pairs": pairs * batch * heads}


def grouped_product(kind: str, *, config: dict, tokens: int, inner: int,
                    outer: int, out_itemsize: int = 2) -> dict:
    """One grouped product of the held experts (``flops_moe.
    grouped_matmul``) at the rows an even router sends them: ``tokens *
    num_experts_per_tok * num_experts / router_width``, against the held
    experts' ``(inner, outer)`` matrices."""
    rows = round(tokens * config["num_experts_per_tok"] * held_share(config))
    return flops_moe.grouped_matmul(
        kind, rows=rows, inner=inner, outer=outer,
        groups=config["num_experts"], itemsize=2, out_itemsize=out_itemsize)
