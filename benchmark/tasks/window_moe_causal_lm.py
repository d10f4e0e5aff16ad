"""Next-token training of a decoder-only LM whose blocks attend over a
sliding window or the whole prefix by layer, with head counts and rotary
schemes of their own and a per-head output gate, and hold, behind the
leading dense blocks, a held share of a softmax-routed mixture of experts
with a shared expert (``models.TransformerLM``: ``layer_types``,
``num_heads_per_layer``, ``head_dim``, ``sliding_window``, ``attn_gate``,
``rope_parameters``, ``DroplessMoe`` with ``experts_held``).

The interface of ``tasks/moe_causal_lm.py``, whose loss this is: the chunked
cross-entropy over the untied head plus the configuration's coefficients
times the balance loss and the router z-loss, means over the expert layers
(``models.transformer.moe_stats``).  ``aux`` carries what a training loop
would fetch now and then: the per-layer expert load (for
``parallel.moe.observe_load``) and the two router terms.  An item is a
token.
"""

import jax.numpy as jnp

from benchmark import flops_laguna, spec

_dense = spec.load_module("tasks/causal_lm.py")
_moe = spec.load_module("tasks/moe_causal_lm.py")
ITEM = _dense.ITEM
items_per_step = _dense.items_per_step
make_batch = _dense.make_batch
loss_fn = _moe.loss_fn


def make_model(config: dict):
    """The dense task's model, with the three arguments that the source
    states in another form: the leading ``dense`` entries of
    ``mlp_layer_types``, the shared expert's width in expert widths, and
    the gate's granularity (``gating`` with the family's ``gating_type``)."""
    m, kinds = config["model"], config["mlp_layer_types"]
    dense = kinds.count("dense")
    if kinds != ["dense"] * dense + ["sparse"] * (len(kinds) - dense):
        raise ValueError(f"mlp_layer_types {kinds}: the dense layers lead")
    shared, width = (config["shared_expert_intermediate_size"],
                     config["moe_intermediate_size"])
    if shared % width:
        raise ValueError(f"a shared expert of {shared} is no whole number "
                         f"of experts of {width}")
    gate = {"per_head": "head"}[config["gating_type"]] \
        if config["gating"] else None
    return _dense.make_model(dict(config, model=dict(m, args=dict(
        m["args"], dense_layers=dense, num_shared_experts=shared // width,
        attn_gate=gate))))


def check_batch(batch: dict) -> dict:
    """The sample the float32 reference can hold beside the program's
    weights and two trees of gradients (8.3 GB at the published widths):
    one sequence of at most 2048 tokens, four windows long, whose full
    scores are 16.8 MB a head and 1.07 GB in a window layer (the reference
    keeps one block's at a time); the comparison compiles to 12.1 GiB of the
    chip's 15.75."""
    return {"sequences": 1, "seq_len": min(batch["seq_len"], 2048)}


def init(model, key, config: dict, batch: dict):
    """``(params, aux)``; ``aux`` has the shape the loss returns, so that no
    step after the first retraces."""
    sample = jnp.zeros((1, min(batch["seq_len"], 128)), jnp.int32)
    aux = {"load": jnp.zeros((config["mlp_layer_types"].count("sparse"),
                              config["router_width"]), jnp.int32),
           "balance_loss": jnp.zeros((), jnp.float32),
           "z_loss": jnp.zeros((), jnp.float32)}
    return model.init(key, sample)["params"], aux


def step_flops(config: dict, batch: dict) -> dict:
    return flops_laguna.window_moe_lm_train(
        config, batch=batch["sequences"], seq=batch["seq_len"])
