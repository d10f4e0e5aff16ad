"""Next-token training of a decoder-only LM whose blocks take their token
mixer from the layer (a gated short convolution or grouped-query attention
with a per-head QK norm) and hold, behind the leading dense blocks, a held
share of a sigmoid-routed mixture of experts without a shared expert; the
output head is the transposed embedding (``models.TransformerLM``:
``ShortConv``, ``layer_types``, ``DroplessMoe`` with ``experts_held``,
``tie_embeddings``).

The interface of ``tasks/latent_moe_causal_lm.py``.  The loss is the chunked
cross-entropy alone (the configuration has no auxiliary loss), over
``models.transformer.head_matrix``: a view of the one embedding leaf, whose
gradient is the sum of both uses.  ``aux`` carries from step to step what is
state and no parameter: per expert layer the router's ``bias`` (the loop
hands it to the model as the collection ``router_state`` and takes back
``parallel.moe.update_router_bias`` of it and the forward's load), and
beside it the ``load`` itself, which a training loop would fetch now and
then for ``parallel.moe.observe_load``.  An item is a token.
"""

import jax.numpy as jnp

from benchmark import flops_lfm2, spec

_dense = spec.load_module("tasks/causal_lm.py")
ITEM = _dense.ITEM
items_per_step = _dense.items_per_step
make_batch = _dense.make_batch


def make_model(config: dict):
    """The dense task's model, with the one argument that sits in a group of
    the source (``rope_parameters``)."""
    m = config["model"]
    theta = config["rope_parameters"]["rope_theta"]
    return _dense.make_model(dict(config, model=dict(
        m, args=dict(m["args"], rope_theta=theta))))


def check_batch(batch: dict) -> dict:
    """The sample the float32 reference can hold beside the program's
    weights and two trees of gradients (5.6 GB at the published widths): one
    sequence of at most 2048 tokens, whose full scores are 537 MB in the one
    attention layer and whose eight held experts, each applied to every
    token, keep 101 MB of float32 activations an expert layer for the
    backward pass."""
    return {"sequences": 1, "seq_len": min(batch["seq_len"], 2048)}


def expert_layers(config: dict) -> range:
    return range(config["num_dense_layers"], config["num_hidden_layers"])


def init(model, key, config: dict, batch: dict):
    """``(params, aux)``; ``aux`` has the shape the loss returns, so that no
    step after the first retraces.  The biases start at zero."""
    sample = jnp.zeros((1, min(batch["seq_len"], 128)), jnp.int32)
    shape = (len(expert_layers(config)), config["router_width"])
    aux = {"load": jnp.zeros(shape, jnp.int32),
           "bias": jnp.zeros(shape, jnp.float32)}
    return model.init(key, sample)["params"], aux


def loss_fn(model, config: dict):
    from bluefog_tpu.models.transformer import head_matrix
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
            hidden, head_matrix(model.cfg, params), targets)
        return ce, {"load": load,
                    "bias": update_router_bias(aux["bias"], load, rate)}
    return loss


def step_flops(config: dict, batch: dict) -> dict:
    return flops_lfm2.hybrid_moe_lm_train(
        config, batch=batch["sequences"], seq=batch["seq_len"])
