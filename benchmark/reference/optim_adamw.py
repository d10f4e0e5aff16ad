"""Hand-written AdamW (Loshchilov and Hutter, decoupled weight decay on
every leaf), plain ``jax.numpy``:

    mu <- b1 mu + (1 - b1) g          nu <- b2 nu + (1 - b2) g^2
    p  <- p - lr * ( mu / (1 - b1^t) / (sqrt(nu / (1 - b2^t)) + eps) + wd p )

Works on any array shape, so a rank-major leaf updates every rank's row at
once; ``t`` counts from 1."""

import jax
import jax.numpy as jnp


def init(params):
    zeros = jax.tree.map(jnp.zeros_like, params)
    return {"mu": zeros, "nu": zeros, "count": 0}


def update(params, grads, state, hyper):
    lr, b1, b2 = hyper["learning_rate"], hyper["b1"], hyper["b2"]
    eps, wd = hyper["eps"], hyper["weight_decay"]
    t = state["count"] + 1
    mu = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g, state["mu"], grads)
    nu = jax.tree.map(lambda v, g: b2 * v + (1 - b2) * g * g,
                      state["nu"], grads)

    def leaf(p, m, v):
        step = (m / (1 - b1 ** t)) / (jnp.sqrt(v / (1 - b2 ** t)) + eps)
        return p - lr * (step + wd * p)
    return (jax.tree.map(leaf, params, mu, nu),
            {"mu": mu, "nu": nu, "count": t})
