"""Plain float32 reference of the ``resnet50-imagenet`` configuration.

ResNet-50 v1.5 (He et al., arXiv:1512.03385; stride on the 3x3 as in
torchvision) in training mode: every batch norm normalises with the batch's
own mean and biased variance, so the loss and its gradient need the
parameters only.  Convolutions are ``lax.conv_general_dilated`` in NHWC,
everything in float32; the caller runs it under
``jax.default_matmul_precision("highest")``.

Departure from torchvision, shared with the program so that the two can be
compared: the stride-2 3x3 convolutions pad (0, 1) (flax ``SAME``), where
torchvision pads (1, 1) and drops the last column.  Weights are read from
the program's parameter tree by name.
"""

import jax
import jax.numpy as jnp
from jax import lax


def _conv(x, kernel, stride, padding):
    return lax.conv_general_dilated(
        x, kernel, (stride, stride), padding,
        dimension_numbers=("NHWC", "HWIO", "NHWC"))


def _same(size, kernel, stride):
    """Explicit (before, after) padding of a ``SAME`` convolution."""
    out = -(-size // stride)
    total = max((out - 1) * stride + kernel - size, 0)
    return (total // 2, total - total // 2)


def _conv_same(x, kernel, stride):
    k = kernel.shape[0]
    pad = [_same(x.shape[1], k, stride), _same(x.shape[2], k, stride)]
    return _conv(x, kernel, stride, pad)


def _batch_norm(x, p, eps):
    mean = jnp.mean(x, axis=(0, 1, 2))
    var = jnp.mean(jnp.square(x - mean), axis=(0, 1, 2))
    return (x - mean) * lax.rsqrt(var + eps) * p["scale"] + p["bias"]


def _bottleneck(x, p, stride, eps):
    y = jax.nn.relu(_batch_norm(
        _conv_same(x, p["Conv_0"]["kernel"], 1), p["BatchNorm_0"], eps))
    y = jax.nn.relu(_batch_norm(
        _conv_same(y, p["Conv_1"]["kernel"], stride), p["BatchNorm_1"], eps))
    y = _batch_norm(
        _conv_same(y, p["Conv_2"]["kernel"], 1), p["BatchNorm_2"], eps)
    if "conv_proj" in p:
        x = _batch_norm(_conv_same(x, p["conv_proj"]["kernel"], stride),
                        p["norm_proj"], eps)
    return jax.nn.relu(x + y)


def loss(params, aux, images, labels, *, cfg):
    """Mean softmax cross-entropy of ``images`` ``(B, H, W, 3)`` against
    ``labels``; returns ``(loss, aux)`` like the program's loss (the running
    statistics in ``aux`` are passed through, not updated)."""
    eps = cfg["batch_norm_eps"]
    x = images.astype(jnp.float32)
    x = _conv(x, params["conv_init"]["kernel"], 2, [(3, 3), (3, 3)])
    x = jax.nn.relu(_batch_norm(x, params["bn_init"], eps))
    x = lax.reduce_window(x, -jnp.inf, lax.max, (1, 3, 3, 1), (1, 2, 2, 1),
                          [(0, 0), (1, 1), (1, 1), (0, 0)])
    block = 0
    for stage, blocks in enumerate(cfg["stage_sizes"]):
        for j in range(blocks):
            stride = 2 if stage > 0 and j == 0 else 1
            x = _bottleneck(x, params[f"BottleneckBlock_{block}"], stride,
                            eps)
            block += 1
    x = jnp.mean(x, axis=(1, 2))
    logits = x @ params["Dense_0"]["kernel"] + params["Dense_0"]["bias"]
    logp = jax.nn.log_softmax(logits, axis=-1)
    picked = jnp.take_along_axis(logp, labels[:, None], axis=-1)[:, 0]
    return -jnp.mean(picked), aux
