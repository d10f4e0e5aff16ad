"""Next-token training of a decoder-only LM whose blocks take their token
mixer by period (``layer_group_size``: the last layer of every group is
gated latent attention, the others Kimi Delta Attention on the chunked delta
rule) and hold, behind the leading dense blocks, a held share of a
sigmoid-routed mixture of experts whose choice is limited to a token's best
groups, with a shared expert (``models.TransformerLM``: ``KimiDeltaMixer``
in the ``kda`` layers of ``layer_types``, ``LatentAttention`` with
``attn_gate="head"``, ``DroplessMoe`` with ``experts_held``,
``router_groups`` and ``router_groups_kept``).

The interface of ``tasks/hybrid_moe_causal_lm.py``.  The loss is the chunked
cross-entropy alone (the configuration has no auxiliary loss) over the
untied head.  ``aux`` carries from step to step what is state and no
parameter, as ``tasks/latent_moe_causal_lm.py`` does: per expert layer the
router's ``bias`` (the loop hands it to the model as the collection
``router_state`` and takes back ``parallel.moe.update_router_bias`` of it
and the forward's load), and beside it the ``load`` itself, which a training
loop would fetch now and then for ``parallel.moe.observe_load``.  An item is
a token.
"""

from benchmark import flops_ling, spec

_dense = spec.load_module("tasks/causal_lm.py")
_latent = spec.load_module("tasks/latent_moe_causal_lm.py")
ITEM = _dense.ITEM
items_per_step = _dense.items_per_step
make_batch = _dense.make_batch
expert_layers = _latent.expert_layers
init = _latent.init
loss_fn = _latent.loss_fn

GATES = {"head_wise": "head"}
CHECK_TOKENS = 1024


def make_model(config: dict):
    """The dense task's model, with the three arguments that the source
    states in another form: the period as ``layer_types``, the granularity
    of the latent layers' output gate, and the shared expert's width in
    expert widths."""
    m = config["model"]
    gate = config["gated_attention_proj_granularity_type"]
    if gate not in GATES:
        raise ValueError(f"gated_attention_proj_granularity_type {gate!r} "
                         f"not in {sorted(GATES)}")
    shared = (config["num_shared_experts"]
              * config["moe_shared_expert_intermediate_size"])
    width = config["moe_intermediate_size"]
    if shared % width:
        raise ValueError(f"a shared expert of {shared} is no whole number "
                         f"of experts of {width}")
    return _dense.make_model(dict(config, model=dict(m, args=dict(
        m["args"], layer_types=flops_ling.layer_types(config),
        attn_gate=GATES[gate], num_shared_experts=shared // width))))


def check_batch(batch: dict) -> dict:
    """The sample the float32 reference can hold beside the program's
    weights and two trees of gradients (9.2 GB at the published widths): one
    sequence of at most 1024 tokens, 16 chunks of the rule, which the
    reference walks token by token (a state of 2.1 MB a layer: the
    reference keeps one every 64 tokens and one stretch's 64 for the
    backward pass) and whose full scores are 134 MB in the latent layer."""
    return {"sequences": 1, "seq_len": min(batch["seq_len"], CHECK_TOKENS)}


def step_flops(config: dict, batch: dict) -> dict:
    return flops_ling.delta_moe_lm_train(
        config, batch=batch["sequences"], seq=batch["seq_len"])
