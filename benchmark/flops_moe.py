"""Operations and bytes a sparse-expert LM requires, computed from shapes
(beside ``benchmark/flops.py``, whose conventions hold: a multiply-accumulate
is two operations, recomputation is not counted, the embedding lookup is not
a matmul).  Only the experts a token is routed to are counted.
"""

from __future__ import annotations


def moe_lm_train(*, hidden: int, experts: int, experts_per_token: int,
                 expert_width: int, vocab: int, layers: int, batch: int,
                 seq: int) -> dict:
    """Operations of one training step on ``batch`` sequences of ``seq``
    tokens of a decoder whose every block is multi-head attention (q, k, v
    and output projections of ``hidden x hidden``) and a top-k mixture of
    SwiGLU experts: per token ``6 *`` the matmul weights it meets (the four
    attention matrices, the router, three matrices of each of its
    ``experts_per_token`` experts, the output head) plus causal attention as
    ``flops.dense_lm_train`` counts it."""
    tokens = batch * seq
    attn_proj = 4 * hidden * hidden
    router = hidden * experts
    routed = experts_per_token * 3 * hidden * expert_width
    blocks = 6 * layers * (attn_proj + router + routed) * tokens
    head = 6 * hidden * vocab * tokens
    attention = int(12 * layers * seq * hidden * 0.5) * tokens
    return {"flops": blocks + head + attention, "blocks": blocks,
            "experts": 6 * layers * routed * tokens, "head": head,
            "attention": attention}


def grouped_matmul(kind: str, *, rows: int, inner: int, outer: int,
                   groups: int, itemsize: int = 2,
                   out_itemsize: int = 2) -> dict:
    """Operations and HBM bytes one grouped product over ragged groups
    needs, from its row count and shapes.

    ``rows`` sorted rows in ``groups`` groups, one ``(inner, outer)`` matrix
    a group.  ``kind``: ``rows`` is ``(rows, inner) x (groups, inner, outer)
    -> (rows, outer)``, each row against its group's matrix (the forward
    product, and the gradient of the rows with the matrix transposed);
    ``weights`` is ``(rows, inner)^T (rows, outer) -> (groups, inner,
    outer)``, each group's rows contracted (the gradient of the matrices).
    Either way every row meets one matrix: ``2 * rows * inner * outer``
    operations whatever the group sizes.  Bytes: each operand and the result
    once (every group's matrix is read or written once, as when no group is
    empty)."""
    if kind not in ("rows", "weights"):
        raise ValueError(f"grouped_matmul: unknown kind {kind!r}")
    matrices = groups * inner * outer
    if kind == "rows":
        moved = (rows * inner + matrices) * itemsize \
            + rows * outer * out_itemsize
    else:
        moved = rows * (inner + outer) * itemsize + matrices * out_itemsize
    return {"flops": 2 * rows * inner * outer, "bytes": moved}
