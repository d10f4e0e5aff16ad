"""Operations and bytes a decoder whose blocks are ONE part each (a Mamba-2
mixer on a chunked scan, a held share of un-gated ``relu2`` experts with a
shared expert, or grouped-query attention without positions) requires of the
chip, computed from shapes (beside ``benchmark/flops.py``, ``flops_moe.py``,
``flops_mla.py``, ``flops_lfm2.py`` and ``flops_laguna.py``, whose
conventions hold: a multiply-accumulate is two operations, recomputation is
not counted in a step's operations, the embedding lookup is not a matmul).
Of the routed experts only the held ones count, at the share of the
assignments an even router sends them; the scan is held to the products of
its chunked form over the pairs at or under a chunk's diagonal; the
convolution's taps, the gates and the norms are elementwise and not counted
among the operations.
"""

from __future__ import annotations

from benchmark import flops, flops_moe


def blocks(config: dict, kind: str) -> int:
    """How many blocks of the pattern are ``kind`` (``M``, ``E`` or ``*``)."""
    return config["hybrid_override_pattern"].count(kind)


def mamba_sizes(config: dict) -> tuple:
    """``(heads, head_dim, groups, state, inner)`` of a Mamba-2 mixer."""
    heads, dim = config["mamba_num_heads"], config["mamba_head_dim"]
    return heads, dim, config["n_groups"], config["ssm_state_size"], \
        heads * dim


def mamba_params(config: dict) -> int:
    """Matmul weights of one Mamba-2 mixer: the in-projection to ``z``,
    ``xBC`` and ``dt``, and the out-projection."""
    heads, _, groups, state, inner = mamba_sizes(config)
    return config["hidden_size"] * (2 * inner + 2 * groups * state + heads) \
        + inner * config["hidden_size"]


def attention_params(config: dict) -> int:
    """Matmul weights of the attention mixer: q, the packed k and v of the
    K/V heads, the output projection back from ``heads * head_dim``."""
    d, dim = config["hidden_size"], config["head_dim"]
    heads, kv = config["num_attention_heads"], config["num_key_value_heads"]
    return d * heads * dim + d * 2 * kv * dim + heads * dim * d


def held_share(config: dict) -> float:
    """The share of the routed assignments an even router sends to the
    experts held here."""
    return config["n_routed_experts"] / config["router_width"]


def expert_block_params(config: dict) -> float:
    """Matmul weights a token meets in one expert block: the router over
    ``router_width``, the shared expert's two matrices and the held share
    of its ``num_experts_per_tok`` routed experts, two matrices each (the
    experts are un-gated)."""
    d = config["hidden_size"]
    return (d * config["router_width"]
            + config["n_shared_experts"] * 2 * d
            * config["moe_shared_expert_intermediate_size"]
            + config["num_experts_per_tok"] * held_share(config) * 2 * d
            * config["moe_intermediate_size"])


def ssd_scan(kind: str, *, config: dict, tokens: int,
             itemsize: int = 2) -> dict:
    """Operations and HBM bytes of ONE chunked pass of the scan of one
    Mamba-2 mixer over ``tokens`` positions (what runs between the
    convolution and the gated norm), in chunks of ``chunk_size``.

    ``fwd`` (also the remat recompute), a chunk of ``Q`` positions with
    ``T = Q (Q + 1) / 2`` pairs at or under its diagonal: ``C B^T`` a group
    (``2 T N``), its product with the chunk's ``x`` a head (``2 T P``), the
    chunk's state and the entering state's part of the outputs (``2 Q P N``
    each a head).  Bytes: ``x``, ``B``, ``C`` and the float32 time steps
    read, ``y`` written, each once; the ``Q x Q`` matrices and the chunk
    states are the form's own and would stay on the chip in one kernel.
    ``bwd``: every product is differentiated in both operands (twice the
    operations); the four inputs and ``dy`` read, four gradients written."""
    heads, dim, groups, state, inner = mamba_sizes(config)
    q = config["chunk_size"]
    chunks, pairs = -(-tokens // q), q * (q + 1) // 2
    forward = chunks * (groups * 2 * pairs * state
                        + heads * (2 * pairs * dim + 4 * q * dim * state))
    inputs = tokens * ((inner + 2 * groups * state) * itemsize + heads * 4)
    result = tokens * inner * itemsize
    if kind == "fwd":
        return {"flops": forward, "bytes": inputs + result}
    if kind == "bwd":
        return {"flops": 2 * forward, "bytes": 2 * inputs + result}
    raise ValueError(f"ssd_scan: unknown kind {kind!r}")


def ssm_moe_lm_train(config: dict, *, batch: int, seq: int) -> dict:
    """Operations of one training step on ``batch`` sequences of ``seq``
    tokens, by kind of block: per token ``6 *`` the matmul weights it meets
    (``mamba_params`` in an ``M`` block, ``expert_block_params`` in an ``E``
    block, ``attention_params`` in a ``*`` block, the output head over the
    vocabulary rows held), the scan's chunked products forward and backward
    (``ssd_scan``) and causal attention over the triangle."""
    d, tokens = config["hidden_size"], batch * seq
    n_m, n_e, n_a = (blocks(config, c) for c in "ME*")
    assert n_m + n_e + n_a == config["num_hidden_layers"]
    scan = n_m * batch * sum(ssd_scan(kind, config=config,
                                      tokens=seq)["flops"]
                             for kind in ("fwd", "bwd"))
    attention = (12 * config["head_dim"] * config["num_attention_heads"]
                 * batch * flops._pairs(seq, True)) * n_a
    weights = (n_m * mamba_params(config) + n_e * expert_block_params(config)
               + n_a * attention_params(config))
    head = d * config["vocab_size"]
    in_blocks = int(6 * weights * tokens) + scan
    return {"flops": in_blocks + 6 * head * tokens + attention,
            "blocks": in_blocks, "head": 6 * head * tokens,
            "attention": attention, "scan": scan,
            "mamba_mixers": 6 * n_m * mamba_params(config) * tokens + scan,
            "attention_mixers": 6 * n_a * attention_params(config) * tokens
            + attention,
            "experts": int(6 * n_e * expert_block_params(config) * tokens),
            "matmul_params": int(weights + head)}


def flash_kernel(kind: str, *, config: dict, batch: int, seq: int) -> dict:
    """One flash attention kernel call at this configuration's heads: the 2
    K/V heads are repeated to the 32 query heads before the kernel, so it
    sees ``num_attention_heads`` heads of ``head_dim``, causal, bfloat16."""
    return flops.flash_kernel(
        kind, batch=batch, seq=seq, heads=config["num_attention_heads"],
        head_dim=config["head_dim"], causal=True, itemsize=2)


def grouped_product(kind: str, *, config: dict, tokens: int, inner: int,
                    outer: int, out_itemsize: int = 2) -> dict:
    """One grouped product of the held experts (``flops_moe.
    grouped_matmul``) at the rows an even router sends them: ``tokens *
    num_experts_per_tok * n_routed_experts / router_width`` (384 an expert
    at 8192 tokens), against the held experts' ``(inner, outer)`` matrices.
    An un-gated expert has two such products a pass (up, down) where a
    SwiGLU expert has three."""
    rows = round(tokens * config["num_experts_per_tok"] * held_share(config))
    return flops_moe.grouped_matmul(
        kind, rows=rows, inner=inner, outer=outer,
        groups=config["n_routed_experts"], itemsize=2,
        out_itemsize=out_itemsize)
