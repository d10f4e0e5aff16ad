"""Next-token training of a decoder-only LM (``models.TransformerLM``).

A task turns a configuration file and a traffic file's ``batch`` into the
model, its batches, its loss and the operations a step requires.  An item is
a token.
"""

import jax
import jax.numpy as jnp

from benchmark import flops
from benchmark.build import model_kwargs

ITEM = "token"


def make_model(config: dict):
    from bluefog_tpu import models
    from bluefog_tpu.ops.flash_attention import flash_attention_impl
    m = config["model"]
    attention = {"flash": flash_attention_impl, "local": lambda: None}[
        m.get("attention", "local")]
    return getattr(models, m["class"])(
        models.TransformerConfig(**model_kwargs(config)),
        attn_impl=attention())


def items_per_step(batch: dict) -> int:
    return batch["sequences"] * batch["seq_len"]


def check_batch(batch: dict) -> dict:
    """The small sample the float32 reference can hold: one sequence of at
    most 2048 tokens."""
    return {"sequences": 1, "seq_len": min(batch["seq_len"], 2048)}


def make_batch(key, config: dict, batch: dict) -> tuple:
    """Uniform token ids; the targets are the next tokens of the same row."""
    return (jax.random.randint(
        key, (batch["sequences"], batch["seq_len"]), 0,
        config["vocab_size"], jnp.int32),)


def init(model, key, config: dict, batch: dict):
    """``(params, aux)``; no state beside the parameters.  A short sample is
    enough to shape them (rotary positions: no table tied to the length)."""
    sample = jnp.zeros((1, min(batch["seq_len"], 128)), jnp.int32)
    return model.init(key, sample)["params"], {}


def loss_fn(model, config: dict):
    from bluefog_tpu.ops.chunked_loss import chunked_softmax_cross_entropy
    import optax
    kind = config["model"].get("loss", "softmax_cross_entropy")

    def loss(params, aux, tokens):
        targets = jnp.roll(tokens, -1, axis=1)
        if kind == "chunked_softmax_cross_entropy":
            hidden = model.apply({"params": params}, tokens,
                                 return_hidden=True)
            return chunked_softmax_cross_entropy(
                hidden, params["lm_head"]["kernel"], targets), aux
        logits = model.apply({"params": params}, tokens)
        return optax.softmax_cross_entropy_with_integer_labels(
            logits, targets).mean(), aux
    return loss


def step_flops(config: dict, batch: dict) -> dict:
    return flops.dense_lm_train(
        hidden=config["hidden_size"], heads=config["num_attention_heads"],
        kv_heads=config["num_key_value_heads"],
        intermediate=config["intermediate_size"], vocab=config["vocab_size"],
        layers=config["num_hidden_layers"], batch=batch["sequences"],
        seq=batch["seq_len"])

