"""Hand-written SGD with (heavy-ball) momentum, plain ``jax.numpy``:
``trace <- g + momentum * trace``, ``p <- p - lr * trace``.  Works on any
array shape, so a rank-major leaf updates every rank's row at once."""

import jax
import jax.numpy as jnp


def init(params):
    return jax.tree.map(jnp.zeros_like, params)


def update(params, grads, trace, hyper):
    lr, momentum = hyper["learning_rate"], hyper.get("momentum", 0.0)
    trace = jax.tree.map(lambda g, t: g + momentum * t, grads, trace)
    return jax.tree.map(lambda p, t: p - lr * t, params, trace), trace
