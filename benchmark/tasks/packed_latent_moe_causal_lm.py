"""Next-token training of a decoder-only LM with latent attention and,
behind the leading dense blocks, a held share of a sigmoid-routed mixture of
experts with shared experts, **on packed documents**
(``models.TransformerLM(tokens, positions=, segment_ids=)``:
``LatentAttention`` under the document mask of ``ops.flash_attention``,
``DroplessMoe`` with ``experts_held``).

The interface of ``tasks/latent_moe_causal_lm.py``, with a batch of three
arrays.  The traffic's ``batch`` names the ``documents`` of a row, the same
in every row; ``make_batch`` draws the tokens from the seed and lays the
``segment_ids`` and the restarting ``positions`` with the library's own
``data.document_layout``, as ``data.pack_documents`` lays a real stream.
The loss is the chunked cross-entropy alone over every position of the row
(no auxiliary loss, no loss mask: the configuration's ``assumed``).  ``aux``
carries the router's ``bias`` and ``load`` as there.  An item is a token.
"""

import jax
import jax.numpy as jnp

from benchmark import flops_kanana, spec

_dense = spec.load_module("tasks/causal_lm.py")
_latent = spec.load_module("tasks/latent_moe_causal_lm.py")
ITEM = _dense.ITEM
items_per_step = _dense.items_per_step
make_model = _dense.make_model
expert_layers = _latent.expert_layers
init = _latent.init

CHECK_TOKENS = 1024


def check_batch(batch: dict) -> dict:
    """The sample the float32 reference can hold beside the program's
    weights and two trees of gradients (8.3 GB at the published widths): the
    row's last 1024 tokens as the packer lays a row that starts there (the
    document the cut falls in opens the row with its rest), whose full
    scores are 134 MB a layer.  At the cell's ten documents that is six
    documents (107, 377, 243, 161, 89, 47) and no boundary on a multiple of
    128; a traffic that left fewer than three documents or only such
    boundaries in its tail would not be this cell's."""
    tokens, tail = min(batch["seq_len"], CHECK_TOKENS), []
    for n in reversed(batch["documents"]):
        tail.insert(0, min(n, tokens - sum(tail)))
        if sum(tail) == tokens:
            break
    bounds = [sum(tail[:i]) for i in range(1, len(tail))]
    assert len(tail) >= 3 and any(b % 128 for b in bounds), tail
    return {"sequences": 1, "seq_len": tokens, "documents": tail}


def make_batch(key, config: dict, batch: dict) -> tuple:
    """``(tokens, segment_ids, positions)``, each ``(sequences, seq_len)``:
    uniform token ids; every row holds the batch's ``documents`` in their
    order; the targets are the next tokens of the same row."""
    from bluefog_tpu.data import document_layout
    shape = (batch["sequences"], batch["seq_len"])
    segment_ids, positions = document_layout(batch["documents"])
    assert segment_ids.shape == shape[1:], (batch["documents"], shape)
    return (jax.random.randint(key, shape, 0, config["vocab_size"],
                               jnp.int32),
            jnp.broadcast_to(segment_ids, shape),
            jnp.broadcast_to(positions, shape))


def loss_fn(model, config: dict):
    from bluefog_tpu.ops.chunked_loss import chunked_softmax_cross_entropy
    from bluefog_tpu.parallel.moe import update_router_bias
    layers, rate = expert_layers(config), config["router_bias_update_rate"]

    def loss(params, aux, tokens, segment_ids, positions):
        targets = jnp.roll(tokens, -1, axis=1)
        state = {f"block_{i}": {"moe": {"bias": aux["bias"][j]}}
                 for j, i in enumerate(layers)}
        hidden, sown = model.apply(
            {"params": params, "router_state": state}, tokens,
            positions=positions, segment_ids=segment_ids,
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
    return flops_kanana.packed_latent_moe_lm_train(
        config, batch=batch["sequences"], documents=batch["documents"])
