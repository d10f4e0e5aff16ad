"""Next-token training of a decoder-only LM whose blocks are ONE part each by
a pattern (Nemotron-H's ``hybrid_override_pattern``): a Mamba-2 mixer on the
chunked scan (``M``), a held share of a sigmoid-routed mixture of un-gated
``relu2`` experts with a shared expert (``E``), or grouped-query attention
without positions (``*``) (``models.TransformerLM``: ``Mamba2Mixer``,
``layer_types`` with ``"mamba"`` and ``"ffn"``, ``block_ffn=False``,
``DroplessMoe`` with ``experts_held`` and ``mlp="relu2"``,
``pos_encoding="none"``).

The interface of ``tasks/hybrid_moe_causal_lm.py``.  The loss is the chunked
cross-entropy alone (the configuration has no auxiliary loss) over the
untied head.  ``aux`` carries from step to step what is state and no
parameter: per expert block the router's ``bias`` (the loop hands it to the
model as the collection ``router_state`` and takes back
``parallel.moe.update_router_bias`` of it and the forward's load), and
beside it the ``load`` itself, which a training loop would fetch now and
then for ``parallel.moe.observe_load``.  An item is a token.
"""

import jax.numpy as jnp

from benchmark import flops_twotower, spec

_dense = spec.load_module("tasks/causal_lm.py")
ITEM = _dense.ITEM
items_per_step = _dense.items_per_step
make_batch = _dense.make_batch

KINDS = {"M": "mamba", "E": "ffn", "*": "full_attention"}


def make_model(config: dict):
    """The dense task's model, with the three arguments that the source
    states in another form: the pattern's characters as ``layer_types``,
    the shared expert's width in expert widths, and the three keys of the
    time steps' start."""
    m, pattern = config["model"], config["hybrid_override_pattern"]
    odd = set(pattern) - set(KINDS)
    if odd or len(pattern) != config["num_hidden_layers"]:
        raise ValueError(
            f"hybrid_override_pattern {pattern!r}: num_hidden_layers "
            f"characters of {sorted(KINDS)}" + (f"; got {sorted(odd)}"
                                                if odd else ""))
    shared = config["n_shared_experts"] * config[
        "moe_shared_expert_intermediate_size"]
    width = config["moe_intermediate_size"]
    if shared % width:
        raise ValueError(f"a shared expert of {shared} is no whole number "
                         f"of experts of {width}")
    return _dense.make_model(dict(config, model=dict(m, args=dict(
        m["args"], layer_types=[KINDS[c] for c in pattern],
        num_shared_experts=shared // width,
        ssm_dt_init=(config["time_step_min"], config["time_step_max"],
                     config["time_step_floor"])))))


def check_batch(batch: dict) -> dict:
    """The sample the float32 reference can hold beside the program's
    weights and two trees of gradients (8.0 GB at the published widths): one
    sequence of at most 2048 tokens, 16 chunks of the scan, which the
    reference walks step by step (it keeps 32 states of 2.1 MB a Mamba-2
    block and one stretch's 64 for the backward pass) and whose full scores
    are 268 MB a K/V head."""
    return {"sequences": 1, "seq_len": min(batch["seq_len"], 2048)}


def expert_layers(config: dict) -> list:
    return [i for i, c in enumerate(config["hybrid_override_pattern"])
            if c == "E"]


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
    return flops_twotower.ssm_moe_lm_train(
        config, batch=batch["sequences"], seq=batch["seq_len"])
