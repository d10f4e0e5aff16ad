"""Image classification with batch statistics (``models.ResNet*``).

A task turns a configuration file and a traffic file's ``batch`` into the
model, its batches, its loss and the operations a step requires.  An item is
an image.
"""

import jax
import jax.numpy as jnp

from benchmark import flops
from benchmark.build import model_kwargs

ITEM = "image"


def make_model(config: dict):
    from bluefog_tpu import models
    return getattr(models, config["model"]["class"])(**model_kwargs(config))


def items_per_step(batch: dict) -> int:
    return batch["images"]


def check_batch(batch: dict) -> dict:
    """The small sample the float32 reference can hold: 8 images."""
    return {"images": min(batch["images"], 8)}


def make_batch(key, config: dict, batch: dict) -> tuple:
    """Normal images in the model's compute type, uniform labels."""
    k_x, k_y = jax.random.split(key)
    size, dtype = config["image_size"], model_kwargs(config)["dtype"]
    return (jax.random.normal(
                k_x, (batch["images"], size, size, config["channels"]),
                dtype),
            jax.random.randint(k_y, (batch["images"],), 0,
                               config["num_classes"], jnp.int32))


def init(model, key, config: dict, batch: dict):
    """``(params, aux)`` with the running batch statistics as ``aux``."""
    size = config["image_size"]
    variables = model.init(
        key, jnp.zeros((2, size, size, config["channels"]), model.dtype))
    return variables["params"], variables["batch_stats"]


def loss_fn(model, config: dict):
    import optax

    def loss(params, aux, images, labels):
        logits, new = model.apply(
            {"params": params, "batch_stats": aux}, images, train=True,
            mutable=["batch_stats"])
        return optax.softmax_cross_entropy_with_integer_labels(
            logits, labels).mean(), new["batch_stats"]
    return loss


def step_flops(config: dict, batch: dict) -> dict:
    return flops.resnet_train(
        stage_sizes=config["stage_sizes"], num_filters=config["num_filters"],
        image=config["image_size"], num_classes=config["num_classes"],
        batch=batch["images"])

