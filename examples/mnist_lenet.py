"""MNIST LeNet with DistributedNeighborAllreduceOptimizer (BASELINE config 2).

Parity: reference ``examples/pytorch_mnist.py``.  The sandbox has no dataset
downloads (zero egress), so a synthetic MNIST stand-in is generated: each
class is a fixed random 28x28 prototype plus noise — linearly separable enough
that accuracy cleanly tracks optimization progress, while every rank trains on
its own disjoint shard (the decentralized-DP setting).
"""

import argparse

import numpy as np


def synthetic_mnist(n_ranks, per_rank, seed=0, proto_seed=42):
    """Class prototypes are fixed by ``proto_seed`` (the task definition);
    ``seed`` only drives the sampled labels/noise so train and held-out sets
    share the same underlying task."""
    prototypes = np.random.RandomState(proto_seed).randn(
        10, 28, 28, 1).astype(np.float32)
    rng = np.random.RandomState(seed)
    ys = rng.randint(0, 10, size=(n_ranks, per_rank))
    xs = prototypes[ys] + 0.8 * rng.randn(
        n_ranks, per_rank, 28, 28, 1).astype(np.float32)
    return xs, ys


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--epochs", type=int, default=5)
    ap.add_argument("--batch-size", type=int, default=64)
    ap.add_argument("--per-rank-samples", type=int, default=512)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--base-optimizer", choices=["adam", "sgd"],
                    default="adam")
    ap.add_argument("--dist-optimizer",
                    choices=["neighbor_allreduce", "allreduce",
                             "gradient_allreduce", "empty"],
                    default="neighbor_allreduce")
    ap.add_argument("--dynamic", action="store_true",
                    help="one-peer dynamic Exp2 topology")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import optax

    import bluefog_tpu as bf
    from bluefog_tpu.models import LeNet5
    from bluefog_tpu.optim import CommunicationType

    bf.init()
    n = bf.size()
    xs, ys = synthetic_mnist(n, args.per_rank_samples)
    xt, yt = synthetic_mnist(n, 256, seed=123)  # held-out

    model = LeNet5()
    params = bf.rank_map(lambda: model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 28, 28, 1))))()

    base = (optax.adam(args.lr) if args.base_optimizer == "adam"
            else optax.sgd(args.lr, momentum=0.9))
    if args.dist_optimizer == "gradient_allreduce":
        opt = bf.optim.DistributedGradientAllreduceOptimizer(base)
    else:
        opt = bf.optim.DistributedAdaptWithCombineOptimizer(
            base,
            CommunicationType(args.dist_optimizer.replace(
                "neighbor_allreduce", "neighbor.allreduce")),
            use_dynamic_topology=args.dynamic)
    state = opt.init(params)

    def loss_fn(p, x, y):
        logits = model.apply(p, x)
        return optax.softmax_cross_entropy_with_integer_labels(
            logits, y).mean()

    grad_all = bf.rank_map(jax.grad(loss_fn))

    @jax.jit
    def accuracy(params, x, y):
        logits = jax.vmap(model.apply)(params, x)
        return (logits.argmax(-1) == y).mean()

    # Framework input pipeline: rank-partitioned sampling + host-async
    # device prefetch (the reference's DistributedSampler+DataLoader role,
    # ``examples/pytorch_mnist.py:100-120``).  static_shards keeps each
    # rank's data fixed across epochs — the heterogeneous decentralized-DP
    # setting this example demonstrates (shuffling happens within shards).
    loader = bf.data.ShardedLoader(
        {"x": xs.reshape(-1, 28, 28, 1), "y": ys.reshape(-1)},
        batch_size=args.batch_size, seed=1, static_shards=True)
    for epoch in range(args.epochs):
        loader.set_epoch(epoch)
        for batch in loader:
            grads = grad_all(params, batch["x"], batch["y"])
            params, state = opt.step(params, grads, state)
        acc = float(accuracy(params, jnp.asarray(xt), jnp.asarray(yt)))
        print(f"epoch {epoch}  held-out accuracy {acc:.4f}")
    assert acc > 0.9, f"training failed: accuracy {acc}"
    print(f"final accuracy {acc:.4f} "
          f"({args.dist_optimizer}, {n} ranks, "
          f"{'dynamic' if args.dynamic else 'static'} topology)")


if __name__ == "__main__":
    main()
