"""Full decentralized image-classification training: the reference's
``examples/pytorch_resnet.py`` protocol, TPU-native.

Covers the same pieces: per-rank data sharding, initial parameter broadcast,
the dist-optimizer grid (neighbor/hierarchical/allreduce/gradient/win_put),
ATC/AWC orders, dynamic one-peer topology, local aggregation
(``--batches-per-communication``), LR warmup + milestone decay
(arxiv 1706.02677 — here an *optax schedule on the update count*, so the
decay position survives checkpoint resume for free, unlike the reference's
manual ``adjust_learning_rate``), per-epoch validation accuracy, and
checkpoint save/resume (``utils/checkpoint.py`` replaces the reference's
``checkpoint-{epoch}.pth``).

Data is synthetic-but-learnable (class-conditional Gaussian images) so the
example runs anywhere the framework does — swap ``make_dataset`` for a real
input pipeline in production.

    python examples/resnet_training.py --model resnet18 --epochs 3
"""

import argparse
import time


def build_parser():
    ap = argparse.ArgumentParser()
    ap.add_argument("--model", default="resnet18",
                    choices=["resnet18", "resnet34", "resnet50", "lenet",
                             "vit"])
    ap.add_argument("--image-size", type=int, default=32)
    ap.add_argument("--num-classes", type=int, default=10)
    ap.add_argument("--samples-per-rank", type=int, default=512)
    ap.add_argument("--val-samples", type=int, default=256)
    ap.add_argument("--batch-size", type=int, default=32,
                    help="per-rank batch size")
    ap.add_argument("--epochs", type=int, default=3)
    ap.add_argument("--base-lr", type=float, default=0.0125)
    ap.add_argument("--warmup-epochs", type=float, default=1)
    ap.add_argument("--momentum", type=float, default=0.9)
    ap.add_argument("--dist-optimizer", default="neighbor_allreduce",
                    choices=["neighbor_allreduce", "allreduce",
                             "hierarchical", "gradient_allreduce", "win_put",
                             "empty"])
    ap.add_argument("--atc-style", action="store_true")
    ap.add_argument("--disable-dynamic-topology", action="store_true")
    ap.add_argument("--batches-per-communication", type=int, default=1,
                    help="local aggregation: communicate every J batches")
    ap.add_argument("--checkpoint-dir", default=None,
                    help="save a checkpoint per epoch; resume if present")
    ap.add_argument("--seed", type=int, default=42)
    return ap


def make_dataset(n_ranks, per_rank, image, classes, seed, *,
                 pattern_seed=0):
    """Class-conditional Gaussians: class c has mean pattern_c; learnable by
    any conv net, rank-sharded like the reference's DistributedSampler.
    ``pattern_seed`` fixes the class means so train/val share a
    distribution while drawing independent samples via ``seed``."""
    import numpy as np
    patterns = np.random.RandomState(pattern_seed).randn(
        classes, image, image, 3).astype(np.float32)
    rng = np.random.RandomState(seed)
    y = rng.randint(0, classes, size=(n_ranks, per_rank))
    x = 0.35 * rng.randn(n_ranks, per_rank, image, image, 3) \
        .astype(np.float32) + patterns[y]
    return x, y


def lr_schedule(args, n, batches_per_epoch):
    """Warmup lr -> lr*size over warmup_epochs, then x0.1 at 2/3 and x0.01
    at 5/6 of training (the reference's 90-epoch milestones, scaled)."""
    import optax
    warm = max(1, int(args.warmup_epochs * batches_per_epoch))
    total = args.epochs * batches_per_epoch
    peak = args.base_lr * n
    # Distinct positive decay boundaries even for very short runs (a dict
    # with colliding keys would silently drop a decay decade).
    b1 = max(1, int(total * 2 / 3) - warm)
    b2 = max(b1 + 1, int(total * 5 / 6) - warm)
    return optax.join_schedules([
        optax.linear_schedule(args.base_lr, peak, warm),
        optax.piecewise_constant_schedule(peak, {b1: 0.1, b2: 0.1}),
    ], [warm])


def main():
    args = build_parser().parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    import bluefog_tpu as bf
    from bluefog_tpu import models
    from bluefog_tpu.optim import CommunicationType
    from bluefog_tpu.utils import checkpoint

    bf.init(local_size=None if args.dist_optimizer != "hierarchical"
            else max(1, len(jax.devices()) // 2))
    n = bf.size()

    if args.model == "lenet":
        model = models.LeNet5(num_classes=args.num_classes)
        has_bn = False
    elif args.model == "vit":
        # Small ViT fit to the example's image size: the patch must DIVIDE
        # the image, so take the largest divisor at most size // 4
        # (worst case 1x1 patches — more tokens, still valid).
        patch = next(p for p in range(max(2, args.image_size // 4), 0, -1)
                     if args.image_size % p == 0)
        model = models.ViT(num_classes=args.num_classes,
                           image_size=args.image_size, patch_size=patch,
                           embed_dim=64, num_layers=4, num_heads=4,
                           dtype=jnp.float32)
        has_bn = False
    else:
        model = getattr(models, args.model.replace("resnet", "ResNet"))(
            num_classes=args.num_classes)
        has_bn = True

    x_train, y_train = make_dataset(n, args.samples_per_rank,
                                    args.image_size, args.num_classes,
                                    args.seed)
    x_val, y_val = make_dataset(n, max(1, args.val_samples // n),
                                args.image_size, args.num_classes,
                                args.seed + 1)
    x_val = x_val.reshape(-1, *x_val.shape[2:])
    y_val = y_val.reshape(-1)

    # Rank-major from birth: every rank initialises the same seed on its
    # own device (the reference broadcasts rank 0's state_dict instead).
    sample = x_train[0][:2]
    variables = bf.rank_map(lambda: model.init(
        jax.random.PRNGKey(args.seed), jnp.asarray(sample)))()
    params = variables["params"]
    bstats = variables["batch_stats"] if has_bn else None

    batches_per_epoch = args.samples_per_rank // args.batch_size
    if batches_per_epoch < 1:
        raise SystemExit(
            f"--batch-size {args.batch_size} exceeds --samples-per-rank "
            f"{args.samples_per_rank}: no full batch per epoch")
    base = optax.sgd(lr_schedule(args, n, batches_per_epoch),
                     momentum=args.momentum)

    comm = {"neighbor_allreduce": CommunicationType.neighbor_allreduce,
            "allreduce": CommunicationType.allreduce,
            "hierarchical": CommunicationType.hierarchical_neighbor_allreduce,
            "empty": CommunicationType.empty}.get(args.dist_optimizer)
    if args.dist_optimizer == "gradient_allreduce":
        opt = bf.optim.DistributedGradientAllreduceOptimizer(
            base, num_steps_per_communication=args.batches_per_communication)
    elif args.dist_optimizer == "win_put":
        opt = bf.optim.DistributedWinPutOptimizer(
            base, num_steps_per_communication=args.batches_per_communication)
    else:
        cls = (bf.optim.DistributedAdaptThenCombineOptimizer if args.atc_style
               else bf.optim.DistributedAdaptWithCombineOptimizer)
        opt = cls(base, comm,
                  use_dynamic_topology=not args.disable_dynamic_topology,
                  num_steps_per_communication=args.batches_per_communication)
    state = opt.init(params)

    if has_bn:
        def loss_fn(p, bs, xb, yb):
            logits, new = model.apply(
                {"params": p, "batch_stats": bs}, xb, train=True,
                mutable=["batch_stats"])
            loss = optax.softmax_cross_entropy_with_integer_labels(
                logits, yb).mean()
            return loss, new["batch_stats"]
        vgrad = bf.rank_map(jax.value_and_grad(loss_fn, has_aux=True))

        @jax.jit
        def infer(p, bs, xb):
            return model.apply({"params": p, "batch_stats": bs}, xb,
                               train=False)
    else:
        def loss_fn(p, xb, yb):
            logits = model.apply({"params": p}, xb)
            return optax.softmax_cross_entropy_with_integer_labels(
                logits, yb).mean(), jnp.zeros(())
        vgrad = bf.rank_map(jax.value_and_grad(loss_fn, has_aux=True))

        @jax.jit
        def infer(p, _, xb):
            return model.apply({"params": p}, xb)

    start_epoch = 0
    if args.checkpoint_dir:
        latest = checkpoint.latest_step(args.checkpoint_dir)
        if latest is not None:
            tmpl = {"params": params, "state": state,
                    **({"bstats": bstats} if has_bn else {}),
                    "epoch": np.zeros((), np.int32)}
            back = checkpoint.restore(args.checkpoint_dir, step=latest,
                                      target=tmpl)
            params = jax.tree.map(jnp.asarray, back["params"])
            state = jax.tree.map(jnp.asarray, back["state"])
            if has_bn:
                bstats = jax.tree.map(jnp.asarray, back["bstats"])
            start_epoch = int(back["epoch"]) + 1
            print(f"resumed from epoch {start_epoch - 1}")

    def validate(params, bstats):
        p0 = jax.tree.map(lambda a: a[0], params)
        bs0 = jax.tree.map(lambda a: a[0], bstats) if has_bn else None
        logits = infer(p0, bs0, jnp.asarray(x_val))
        return float((np.asarray(logits).argmax(-1) == y_val).mean())

    rng = np.random.RandomState(args.seed)
    # A fully-finished checkpoint still reports the restored model's quality.
    acc = validate(params, bstats) if start_epoch >= args.epochs else None
    for epoch in range(start_epoch, args.epochs):
        order = rng.permutation(args.samples_per_rank)
        t0 = time.time()
        running = 0.0
        for b in range(batches_per_epoch):
            idx = order[b * args.batch_size:(b + 1) * args.batch_size]
            # host arrays: each rank's rows go straight to its own device
            xb, yb = x_train[:, idx], y_train[:, idx]
            if has_bn:
                (loss, bstats), grads = vgrad(params, bstats, xb, yb)
            else:
                (loss, _), grads = vgrad(params, xb, yb)
            params, state = opt.step(params, grads, state)
            running += float(loss.mean())
        acc = validate(params, bstats)
        print(f"epoch {epoch}: loss {running / batches_per_epoch:.4f} "
              f"val_acc {acc:.3f} ({time.time() - t0:.1f}s)")
        if args.checkpoint_dir:
            checkpoint.save(
                args.checkpoint_dir,
                {"params": params, "state": state,
                 **({"bstats": bstats} if has_bn else {}),
                 "epoch": np.asarray(epoch, np.int32)}, step=epoch)
    print(f"final val_acc {acc:.3f}")


if __name__ == "__main__":
    main()
