"""Next-token training of a decoder-only LM with latent attention, several
hyper-connected residual streams and, behind the leading dense blocks, a
held share of a sigmoid-routed mixture of experts with a shared expert
(``models.TransformerLM``: ``LatentAttention``, ``HyperConnection``,
``DroplessMoe`` with ``experts_held``).

The interface of ``tasks/moe_causal_lm.py``.  The loss is the chunked
cross-entropy alone (the configuration has no auxiliary loss).  ``aux``
carries from step to step what is state and no parameter: per expert layer
the router's ``bias`` (the loop hands it to the model as the collection
``router_state`` and takes back ``parallel.moe.update_router_bias`` of it
and the forward's load), and beside it the ``load`` itself, which a training
loop would fetch now and then for ``parallel.moe.observe_load``.  An item is
a token.
"""

import jax.numpy as jnp

from benchmark import flops_mla, spec

_dense = spec.load_module("tasks/causal_lm.py")
ITEM = _dense.ITEM
items_per_step = _dense.items_per_step
make_batch = _dense.make_batch


def make_model(config: dict):
    """The dense task's model, with the one argument that two source keys
    make."""
    m = config["model"]
    clamp = (config["mhc_h_res_clamp_min"], config["mhc_h_res_clamp_max"])
    return _dense.make_model(dict(config, model=dict(
        m, args=dict(m["args"], hyper_res_clamp=clamp))))


def check_batch(batch: dict) -> dict:
    """The sample the float32 reference can hold beside the program's
    weights and two trees of gradients (9.1 GB at the published widths): one
    sequence of at most 1024 tokens, whose full scores are 134 MB a layer and
    whose four float32 streams are 59 MB a copy."""
    return {"sequences": 1, "seq_len": min(batch["seq_len"], 1024)}


def expert_layers(config: dict) -> range:
    return range(config["first_k_dense_replace"],
                 config["num_hidden_layers"])


def init(model, key, config: dict, batch: dict):
    """``(params, aux)``; ``aux`` has the shape the loss returns, so that no
    step after the first retraces.  The biases start at zero."""
    sample = jnp.zeros((1, min(batch["seq_len"], 128)), jnp.int32)
    shape = (len(expert_layers(config)), config["router_width"])
    aux = {"load": jnp.zeros(shape, jnp.int32),
           "bias": jnp.zeros(shape, jnp.float32)}
    return model.init(key, sample)["params"], aux


def loss_fn(model, config: dict):
    from bluefog_tpu.ops.chunked_loss import chunked_softmax_cross_entropy
    from bluefog_tpu.parallel.moe import update_router_bias
    layers, rate = expert_layers(config), config["router_bias_update_rate"]

    def loss(params, aux, tokens):
        targets = jnp.roll(tokens, -1, axis=1)
        state = {f"block_{i}": {"moe": {"bias": aux["bias"][j]}}
                 for j, i in enumerate(layers)}
        hidden, sown = model.apply(
            {"params": params, "router_state": state}, tokens,
            return_hidden=True, mutable=["intermediates"])
        load = jnp.stack([
            sown["intermediates"][f"block_{i}"]["moe"]["moe_load"][0]
            for i in layers])
        ce = chunked_softmax_cross_entropy(
            hidden, params["lm_head"]["kernel"], targets)
        return ce, {"load": load,
                    "bias": update_router_bias(aux["bias"], load, rate)}
    return loss


def step_flops(config: dict, batch: dict) -> dict:
    return flops_mla.latent_moe_lm_train(
        config, batch=batch["sequences"], seq=batch["seq_len"])
