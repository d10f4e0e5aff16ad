"""Operations and bytes a hybrid of gated short convolutions and grouped-query
attention with a held share of sigmoid-routed experts requires of the chip,
computed from shapes (beside ``benchmark/flops.py``, ``flops_moe.py`` and
``flops_mla.py``, whose conventions hold: a multiply-accumulate is two
operations, recomputation is not counted in a step's operations, the
embedding lookup is not a matmul).  Of the routed experts only the held ones
count, at the share of the assignments an even router sends them.
"""

from __future__ import annotations

from benchmark import flops, flops_moe


def conv_mixer_params(config: dict) -> int:
    """Matmul weights of one gated short convolution: the input projection
    to the two gates and the value, and the output projection."""
    d = config["hidden_size"]
    return d * 3 * d + d * d


def attention_params(config: dict) -> int:
    """Matmul weights of one grouped-query attention mixer: q, the packed
    k and v of the K/V heads, the output projection."""
    d, heads = config["hidden_size"], config["num_attention_heads"]
    dim = d // heads
    return d * d + d * 2 * config["num_key_value_heads"] * dim + d * d


def held_share(config: dict) -> float:
    """The share of the routed assignments an even router sends to the
    experts held here."""
    return config["num_experts"] / config["router_width"]


def hybrid_moe_lm_train(config: dict, *, batch: int, seq: int) -> dict:
    """Operations of one training step on ``batch`` sequences of ``seq``
    tokens, by kind of layer: per token ``6 *`` the matmul weights it meets
    (a conv mixer or an attention mixer by ``layer_types``; the dense SwiGLU
    in the leading ``num_dense_layers`` blocks, in the others the router and
    the held share of its ``num_experts_per_tok`` routed experts; the output
    head over the vocabulary rows held, which is the transposed embedding),
    the gates and taps of the convolutions (``sconv_gate``'s operations,
    forward and backward), and causal attention in the attention layers as
    ``flops.dense_lm_train`` counts it."""
    d, kinds = config["hidden_size"], config["layer_types"]
    conv_layers = kinds.count("conv")
    attn_layers = kinds.count("full_attention")
    assert conv_layers + attn_layers == config["num_hidden_layers"]
    dense_layers = config["num_dense_layers"]
    expert_layers = config["num_hidden_layers"] - dense_layers
    tokens = batch * seq
    dense = 3 * d * config["intermediate_size"]
    sparse = (d * config["router_width"]
              + config["num_experts_per_tok"] * held_share(config)
              * 3 * d * config["moe_intermediate_size"])
    weights = (conv_layers * conv_mixer_params(config)
               + attn_layers * attention_params(config)
               + dense_layers * dense + expert_layers * sparse)
    head = d * config["vocab_size"]
    gates = conv_layers * (sconv_gate("fwd", tokens=tokens, config=config)
                           ["flops"]
                           + sconv_gate("bwd", tokens=tokens, config=config)
                           ["flops"])
    attention = int(12 * attn_layers * seq * d * 0.5) * tokens
    blocks = int(6 * weights * tokens) + gates
    return {"flops": blocks + 6 * head * tokens + attention,
            "blocks": blocks, "head": 6 * head * tokens,
            "attention": attention,
            "conv_mixers": 6 * conv_layers * conv_mixer_params(config)
            * tokens + gates,
            "attention_mixers": 6 * attn_layers * attention_params(config)
            * tokens + attention,
            "experts": int(6 * expert_layers * sparse * tokens),
            "dense_mlp": 6 * dense_layers * dense * tokens,
            "matmul_params": int(weights + head)}


def sconv_gate(kind: str, *, tokens: int, config: dict,
               itemsize: int = 2) -> dict:
    """Operations and HBM bytes of the gates and the convolution of ONE
    gated short convolution over ``tokens`` rows of ``hidden_size``
    channels (what runs between the two projections).

    ``fwd`` (also the remat recompute): read the two gates and the value,
    write the gated result: four passes over ``tokens x hidden``; a value
    costs the gate ``B * X``, ``L`` multiply-adds and the gate ``C * c``.
    ``bwd``: read the three and the result's gradient, write the three
    gradients: seven passes; each of the three products is differentiated
    in both operands (the taps' own gradient is ``hidden x L`` values and
    counted in the operations, not the bytes).  Memory-bound by two orders of
    magnitude: about one operation a byte."""
    rows = tokens * config["hidden_size"]
    taps = config["conv_L_cache"]
    forward = 2 * taps + 2
    passes, ops = {"fwd": (4, forward), "bwd": (7, 2 * forward)}[kind]
    return {"flops": ops * rows, "bytes": passes * rows * itemsize}


def flash_kernel(kind: str, *, config: dict, batch: int, seq: int) -> dict:
    """One flash attention kernel call at this configuration's heads: the
    K/V heads are repeated to the query heads before the kernel, so it sees
    ``num_attention_heads`` heads of ``hidden_size / num_attention_heads``
    (64), causal, bfloat16."""
    heads = config["num_attention_heads"]
    return flops.flash_kernel(
        kind, batch=batch, seq=seq, heads=heads,
        head_dim=config["hidden_size"] // heads, causal=True, itemsize=2)


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
