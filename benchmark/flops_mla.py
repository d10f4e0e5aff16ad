"""Operations and bytes a latent-attention, hyper-connected, shared-expert
sparse LM requires of the chip that holds a share of its experts, computed
from shapes (beside ``benchmark/flops.py`` and ``flops_moe.py``, whose
conventions hold: a multiply-accumulate is two operations, recomputation is
not counted, the embedding lookup is not a matmul).  Of the routed experts
only the held ones count, at the share of the assignments an even router
sends them.
"""

from __future__ import annotations

from benchmark import flops


def latent_attention_params(config: dict) -> int:
    """Matmul weights of one latent-attention sub-layer: the two query
    matrices, the latent and its expansion, the output projection."""
    d, heads = config["hidden_size"], config["num_attention_heads"]
    qk = config["qk_nope_head_dim"] + config["qk_rope_head_dim"]
    nope, v = config["qk_nope_head_dim"], config["v_head_dim"]
    return (d * config["q_lora_rank"] + config["q_lora_rank"] * heads * qk
            + d * (config["kv_lora_rank"] + config["qk_rope_head_dim"])
            + config["kv_lora_rank"] * heads * (nope + v)
            + heads * v * d)


def hyper_map_params(config: dict) -> int:
    """Matmul weights of one hyper-connection map: ``(n d) x (n n + 2 n)``."""
    n = config["hc_mult"]
    return n * config["hidden_size"] * (n * n + 2 * n)


def held_share(config: dict) -> float:
    """The share of the routed assignments an even router sends to the
    experts held here."""
    return config["n_routed_experts"] / config["router_width"]


def latent_moe_lm_train(config: dict, *, batch: int, seq: int) -> dict:
    """Operations of one training step on ``batch`` sequences of ``seq``
    tokens: per token ``6 *`` the matmul weights it meets (latent attention
    and two hyper-connection maps in every block, the dense SwiGLU in the
    leading blocks, in the others the router, the shared experts and the
    held share of its ``num_experts_per_tok`` routed experts, the output
    head over the vocabulary rows held) plus causal attention: forward and
    backward ``6 * (qk + v)`` operations a head and a visited key, half the
    square."""
    d, layers = config["hidden_size"], config["num_hidden_layers"]
    dense_layers = config["first_k_dense_replace"]
    tokens = batch * seq
    expert = 3 * d * config["moe_intermediate_size"]
    routed = config["num_experts_per_tok"] * held_share(config) * expert
    per_block = latent_attention_params(config) + 2 * hyper_map_params(config)
    dense = 3 * d * config["intermediate_size"]
    sparse = (d * config["router_width"]
              + config["n_shared_experts"] * expert + routed)
    weights = (layers * per_block + dense_layers * dense
               + (layers - dense_layers) * sparse)
    head = d * config["vocab_size"]
    qk = config["qk_nope_head_dim"] + config["qk_rope_head_dim"]
    attention = int(6 * (qk + config["v_head_dim"])
                    * config["num_attention_heads"] * layers * seq * 0.5
                    ) * tokens
    blocks = int(6 * weights * tokens)
    return {"flops": blocks + 6 * head * tokens + attention,
            "blocks": blocks, "head": 6 * head * tokens,
            "attention": attention,
            "latent_attention": 6 * layers * latent_attention_params(config)
            * tokens + attention,
            "hyper_maps": 6 * layers * 2 * hyper_map_params(config) * tokens,
            "experts": int(6 * (layers - dense_layers) * sparse * tokens),
            "dense_mlp": 6 * dense_layers * dense * tokens,
            "matmul_params": int(weights + head)}


def flash_kernel(kind: str, *, batch: int, seq: int, heads: int,
                 qk_dim: int, v_dim: int, causal: bool = True,
                 itemsize: int = 2) -> dict:
    """Operations and HBM bytes one call of a flash attention kernel needs
    when the values' head dim is its own (``flops.flash_kernel`` at ``qk_dim
    == v_dim``).  Per visited query-key pair and head: ``fwd`` the scores
    (``qk_dim``) and the values (``v_dim``); ``dq`` the scores, dP
    (``v_dim``) and dQ (``qk_dim``); ``dkv`` the scores, dP, dV (``v_dim``)
    and dK (``qk_dim``).  Bytes: each operand and result once, the per-row
    logsumexp and delta in float32."""
    qk_products, v_products, qk_tiles, v_tiles, rows = {
        "fwd": (1, 1, 2, 2, 1),      # q k | v o | lse
        "dq": (2, 1, 3, 2, 2),       # q k dq | v do | lse delta
        "dkv": (2, 2, 3, 3, 2),      # q k dk | v do dv | lse delta
    }[kind]
    bh = batch * heads
    return {
        "flops": 2 * (qk_products * qk_dim + v_products * v_dim)
        * flops._pairs(seq, causal) * bh,
        "bytes": bh * (seq * itemsize * (qk_tiles * qk_dim
                                         + v_tiles * v_dim) + rows * seq * 4),
    }
