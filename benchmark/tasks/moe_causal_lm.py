"""Next-token training of a decoder-only LM whose blocks hold a dropless
top-k mixture of experts (``models.TransformerLM`` with
``models.transformer.DroplessMoe``).

The interface of ``tasks/causal_lm.py``.  The loss adds the two router
losses with the configuration's coefficients, and its ``aux`` carries what a
training loop would fetch now and then: the per-layer expert load (for
``parallel.moe.observe_load``) and the two auxiliary losses.  An item is a
token.
"""

import jax.numpy as jnp

from benchmark import flops_moe, spec

# model, batches and the reference's sample: the dense task's, unchanged
_dense = spec.load_module("tasks/causal_lm.py")
ITEM = _dense.ITEM
make_model = _dense.make_model
items_per_step = _dense.items_per_step
check_batch = _dense.check_batch
make_batch = _dense.make_batch


def init(model, key, config: dict, batch: dict):
    """``(params, aux)``; ``aux`` has the shape the loss returns, so that no
    step after the first retraces."""
    sample = jnp.zeros((1, min(batch["seq_len"], 128)), jnp.int32)
    aux = {"load": jnp.zeros((config["num_hidden_layers"],
                              config["num_experts"]), jnp.int32),
           "balance_loss": jnp.zeros((), jnp.float32),
           "z_loss": jnp.zeros((), jnp.float32)}
    return model.init(key, sample)["params"], aux


def loss_fn(model, config: dict):
    from bluefog_tpu.models.transformer import moe_stats
    from bluefog_tpu.ops.chunked_loss import chunked_softmax_cross_entropy
    cfg = model.cfg

    def loss(params, aux, tokens):
        del aux
        targets = jnp.roll(tokens, -1, axis=1)
        hidden, sown = model.apply({"params": params}, tokens,
                                   return_hidden=True,
                                   mutable=["intermediates"])
        stats = moe_stats(sown["intermediates"])
        ce = chunked_softmax_cross_entropy(
            hidden, params["lm_head"]["kernel"], targets)
        total = (ce + cfg.router_aux_loss_coef * stats["balance_loss"]
                 + cfg.router_z_loss_coef * stats["z_loss"])
        return total, stats
    return loss


def step_flops(config: dict, batch: dict) -> dict:
    return flops_moe.moe_lm_train(
        hidden=config["hidden_size"], experts=config["num_experts"],
        experts_per_token=config["num_experts_per_tok"],
        expert_width=config["intermediate_size"],
        vocab=config["vocab_size"], layers=config["num_hidden_layers"],
        batch=batch["sequences"], seq=batch["seq_len"])
