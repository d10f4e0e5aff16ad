"""Operations and bytes the algorithms require, computed from shapes.

The yardstick for ``mfu_busy``, plain MFU and ``flash_roofline``: what the
forward and backward passes need, not what the program happens to execute.
Recomputation (``remat``) is not counted, the ``wte`` lookup is a gather and
not a matmul, and ResNet-50 is counted from its convolution and dense shapes.
A multiply-accumulate is two operations.
"""

from __future__ import annotations


def matmul(m: int, k: int, n: int) -> int:
    """Operations of an ``(m, k) @ (k, n)`` product."""
    return 2 * m * k * n


# --- dense decoder-only LM ------------------------------------------------

def dense_lm_layer_matmul_params(hidden: int, heads: int, kv_heads: int,
                                 intermediate: int) -> int:
    """Matmul weights of one block: q, packed k/v, output projection and the
    three SwiGLU matrices (no biases, norms are not matmuls)."""
    head_dim = hidden // heads
    attn = hidden * hidden + hidden * 2 * kv_heads * head_dim \
        + hidden * hidden
    return attn + 3 * hidden * intermediate


def dense_lm_train(*, hidden: int, heads: int, kv_heads: int,
                   intermediate: int, vocab: int, layers: int,
                   batch: int, seq: int) -> dict:
    """Operations of one training step on ``batch`` sequences of ``seq``
    tokens: ``6 * N * tokens`` over the matmul weights ``N`` (blocks and the
    output head, not the embedding table) plus causal attention,
    ``12 * L * S * d * 0.5`` per token (scores and values, forward and
    backward, half the square)."""
    tokens = batch * seq
    per_layer = dense_lm_layer_matmul_params(hidden, heads, kv_heads,
                                             intermediate)
    blocks = 6 * layers * per_layer * tokens
    head = 6 * hidden * vocab * tokens
    attention = int(12 * layers * seq * hidden * 0.5) * tokens
    total = blocks + head + attention
    return {"flops": total, "blocks": blocks, "head": head,
            "attention": attention, "matmul_params": layers * per_layer
            + hidden * vocab}


# --- ResNet (bottleneck, v1.5) --------------------------------------------

def resnet_bottleneck_layers(*, stage_sizes, num_filters: int, image: int,
                             num_classes: int) -> list:
    """Every convolution and the dense head of a v1.5 bottleneck ResNet, in
    order: dicts of ``name, out`` (output height == width), ``kernel, cin,
    cout`` and ``dgrad`` (False where the layer's input is the image, whose
    gradient nobody needs)."""
    layers = []
    size = image // 2                       # 7x7 stride 2, padding 3
    layers.append(dict(name="conv_init", out=size, kernel=7, cin=3,
                       cout=num_filters, dgrad=False))
    size //= 2                              # 3x3 max pool, stride 2
    cin = num_filters
    for i, blocks in enumerate(stage_sizes):
        f = num_filters * 2 ** i
        for j in range(blocks):
            stride = 2 if i > 0 and j == 0 else 1
            out = size // stride
            tag = f"stage{i}.block{j}"
            layers.append(dict(name=tag + ".conv1", out=size, kernel=1,
                               cin=cin, cout=f, dgrad=True))
            layers.append(dict(name=tag + ".conv2", out=out, kernel=3,
                               cin=f, cout=f, dgrad=True))
            layers.append(dict(name=tag + ".conv3", out=out, kernel=1,
                               cin=f, cout=4 * f, dgrad=True))
            if cin != 4 * f or stride != 1:
                layers.append(dict(name=tag + ".proj", out=out, kernel=1,
                                   cin=cin, cout=4 * f, dgrad=True))
            cin, size = 4 * f, out
    layers.append(dict(name="dense", out=1, kernel=1, cin=cin,
                       cout=num_classes, dgrad=True))
    return layers


def layer_forward_flops(layer: dict) -> int:
    return matmul(layer["out"] * layer["out"],
                  layer["kernel"] ** 2 * layer["cin"], layer["cout"])


def resnet_train(*, stage_sizes, num_filters: int, image: int,
                 num_classes: int, batch: int) -> dict:
    """Operations of one training step on ``batch`` images: forward, weight
    gradient and (but for the first convolution) input gradient of every
    convolution and of the dense head."""
    layers = resnet_bottleneck_layers(
        stage_sizes=stage_sizes, num_filters=num_filters, image=image,
        num_classes=num_classes)
    forward = sum(layer_forward_flops(l) for l in layers)
    train = sum(layer_forward_flops(l) * (3 if l["dgrad"] else 2)
                for l in layers)
    return {"flops": train * batch, "forward_per_image": forward,
            "train_per_image": train}


# --- flash attention kernels ----------------------------------------------

def _pairs(seq: int, causal: bool) -> int:
    """Query-key pairs attention has to visit."""
    return seq * (seq + 1) // 2 if causal else seq * seq


def flash_kernel(kind: str, *, batch: int, seq: int, heads: int,
                 head_dim: int, causal: bool = True,
                 itemsize: int = 2) -> dict:
    """Operations and HBM bytes one call of a flash attention kernel needs.

    ``kind``: ``fwd`` (scores, values: 2 products per pair), ``dq`` (scores,
    dP, dQ: 3) or ``dkv`` (scores, dP, dV, dK: 4); the backward is two
    kernels here, and each is held to what its own outputs require.  Bytes:
    each operand and result once (``(S, D)`` tiles of ``itemsize``, the
    per-row logsumexp and delta in float32)."""
    products = {"fwd": 2, "dq": 3, "dkv": 4}[kind]
    tiles = {"fwd": 4,        # q k v -> o
             "dq": 5,         # q k v do -> dq
             "dkv": 6}[kind]  # q k v do -> dk dv
    rows = {"fwd": 1, "dq": 2, "dkv": 2}[kind]   # lse (and delta)
    bh = batch * heads
    return {
        "flops": products * 2 * head_dim * _pairs(seq, causal) * bh,
        "bytes": bh * (tiles * seq * head_dim * itemsize + rows * seq * 4),
    }


def roofline_seconds(cost: dict, peaks: dict) -> tuple:
    """Least time the chip could take for ``cost`` and which bound sets it."""
    compute = cost["flops"] / peaks["bf16_flops_per_s"]
    memory = cost["bytes"] / peaks["hbm_bytes_per_s"]
    return (compute, "compute") if compute >= memory else (memory, "memory")
