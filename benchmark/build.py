"""From a cell's data files to a running job: the model, its trees on the
device, the gradient program and the distributed optimizer, all through the
library's own entry points (``bf.init``, ``bf.rank_map``,
``bf.optim.Distributed*Optimizer``) with library defaults.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def model_kwargs(config: dict) -> dict:
    """Constructor arguments of the model (or of its config class): those
    copied from the configuration's source keys, then the literal ones."""
    m = config["model"]
    kw = {arg: config[key] for arg, key in m.get("from_source", {}).items()}
    kw.update(m.get("args", {}))
    if "dtype" in kw:
        kw["dtype"] = jnp.dtype(kw["dtype"])
    return kw


def rank_keys(seed: int, n: int) -> np.ndarray:
    """Rank-major PRNG keys ``(n, 2, 2)``: row ``i`` holds the key of the
    parameters (the same on every rank, as after
    ``bf.broadcast_parameters``) and the key of rank ``i``'s data."""
    root = jax.random.PRNGKey(seed)
    k_params = jax.random.fold_in(root, 0)
    k_data = jax.random.split(jax.random.fold_in(root, 1), n)
    return np.stack([np.stack([np.asarray(k_params), np.asarray(k)])
                     for k in k_data])


def effective_hyper(optimizer: dict, n: int) -> dict:
    """The base optimizer's hyperparameters as run on ``n`` ranks."""
    hyper = {k: v for k, v in optimizer["base"].items() if k != "name"}
    if optimizer.get("learning_rate_times_size"):
        hyper["learning_rate"] = hyper["learning_rate"] * n
    return hyper


def make_optimizer(optimizer: dict, n: int):
    """``bf.optim.<class>(optax.<base>(**hyper), <communication>, **args)``."""
    import optax
    import bluefog_tpu as bf
    base = getattr(optax, optimizer["base"]["name"])(
        **effective_hyper(optimizer, n))
    cls = getattr(bf.optim, optimizer["class"])
    args = dict(optimizer.get("args", {}))
    if "communication_type" in optimizer:
        return cls(base, bf.optim.CommunicationType[
            optimizer["communication_type"]], **args)
    return cls(base, **args)


class Job:
    """One phase's training job on ``devices``: ``bf.init``, the trees made
    on the device in one jitted call from the seed, the gradient program and
    the optimizer.  ``step()`` is what a user's loop does."""

    def __init__(self, cell, task, devices, seed: int):
        import bluefog_tpu as bf
        traffic = cell.traffic
        init_kw = dict(traffic.get("init", {}))
        topology = traffic.get("topology")
        if topology:
            from bluefog_tpu import topology_util
            init_kw["topology_fn"] = lambda: getattr(
                topology_util, topology["name"])(
                    len(devices), **topology.get("args", {}))
        bf.init(devices=list(devices), **init_kw)
        self.cell, self.task, self.seed = cell, task, seed
        self.n = bf.size()
        self.keys = rank_keys(seed, self.n)
        self.model = task.make_model(cell.config)
        self.vgrad = bf.rank_map(jax.value_and_grad(
            task.loss_fn(self.model, cell.config), has_aux=True))
        self.opt = make_optimizer(traffic["optimizer"], self.n)
        pool = traffic["pool"]

        def make_model_trees(keys):
            return task.init(self.model, keys[0], cell.config,
                             traffic["batch"])

        def make_pool(keys):
            return tuple(
                task.make_batch(k, cell.config, traffic["batch"])
                for k in jax.random.split(keys[1], pool["size"]))

        # Each made on the device in one jitted call from the seed.
        self.fresh = bf.rank_map(make_model_trees)
        self.params, self.aux = self.fresh(self.keys)
        batches = bf.rank_map(make_pool)(self.keys)
        self.state = self.opt.init(self.params)
        self.issued = 0
        self._feed = None
        if pool.get("feed", "device") == "host":
            # The pool lives in host memory and rides the library's input
            # pipeline, two batches ahead.
            from bluefog_tpu.data import prefetch_to_device
            host = jax.device_get(batches)
            del batches
            self.pool = host

            def cycle():
                i = 0
                while True:
                    yield host[i % len(host)]
                    i += 1
            self._feed = prefetch_to_device(cycle(), size=2)
        else:
            self.pool = batches

    def next_batch(self):
        if self._feed is not None:
            return next(self._feed)
        return self.pool[self.issued % len(self.pool)]

    def grad(self, batch):
        (loss, self.aux), grads = self.vgrad(self.params, self.aux, *batch)
        return loss, grads

    def apply(self, grads):
        self.params, self.state = self.opt.step(self.params, grads,
                                                self.state)
        self.issued += 1

    def step(self):
        """One training step; returns the rank-major loss (not fetched)."""
        loss, grads = self.grad(self.next_batch())
        self.apply(grads)
        return loss

    def close(self):
        """Free the device trees and stop the input thread."""
        if self._feed is not None:
            self._feed.close()
        self.params = self.aux = self.state = self.pool = None
