"""Throughput benchmark harness — the reference's headline experiment.

Parity: ``examples/pytorch_benchmark.py`` (model choice, synthetic data,
--dist-optimizer grid, 10-warmup / num-iters x num-batches-per-iter protocol,
mean +- 1.96 sigma reporting).  Runs the FULL decentralized training step over
every visible device.

    python examples/benchmark.py --model resnet50 --batch-size 64 \
        --dist-optimizer neighbor_allreduce

``--efficiency`` reports scaling efficiency — n-device throughput over n x
single-device throughput, the reference's headline scaling metric
(``examples/pytorch_benchmark.py:228-256`` totals img/sec across workers; the
paper reports it relative to one worker).  Single-process only: it compares
the devices this process owns against one of them.  On a multi-host pod,
run the benchmark once per world size instead and divide the totals — the
harness prints the absolute numbers either way.
"""

import argparse
import time

import numpy as np


def build_parser():
    ap = argparse.ArgumentParser()
    ap.add_argument("--model", default="resnet50",
                    choices=["resnet18", "resnet34", "resnet50", "resnet101",
                             "resnet152", "vgg11", "vgg16", "vgg19",
                             "lenet", "vit", "transformer"])
    ap.add_argument("--batch-size", type=int, default=64)
    ap.add_argument("--num-warmup-batches", type=int, default=10)
    ap.add_argument("--num-iters", type=int, default=10)
    ap.add_argument("--num-batches-per-iter", type=int, default=10)
    ap.add_argument("--dist-optimizer", default="neighbor_allreduce",
                    choices=["neighbor_allreduce", "allreduce",
                             "gradient_allreduce", "hierarchical",
                             "win_put", "empty"])
    ap.add_argument("--atc", action="store_true",
                    help="adapt-then-combine order (default AWC)")
    ap.add_argument("--compression", default="none",
                    choices=["none", "bf16"],
                    help="wire compression for the optimizer's collectives")
    ap.add_argument("--dynamic", action="store_true",
                    help="dynamic one-peer Exp2 topology")
    ap.add_argument("--image-size", type=int, default=224)
    ap.add_argument("--seq-len", type=int, default=1024)
    ap.add_argument("--metrics-file", default=None,
                    help="append per-iter throughput as JSONL "
                         "(utils.metrics.MetricsWriter)")
    ap.add_argument("--host-data", action="store_true",
                    help="feed each batch from HOST memory through the "
                         "prefetching input pipeline (data.prefetch_to_"
                         "device) instead of device-resident tensors — "
                         "measures end-to-end throughput incl. host->HBM "
                         "transfer overlap")
    ap.add_argument("--efficiency", action="store_true",
                    help="also measure 1-device throughput and report "
                         "n-device scaling efficiency")
    ap.add_argument("--flash-attention", action="store_true",
                    help="transformer model: use the Pallas flash-attention "
                         "kernel (compiled Mosaic on TPU) instead of dense")
    ap.add_argument("--remat", action="store_true",
                    help="transformer model: jax.checkpoint each block "
                         "(recompute activations in backward; long-context "
                         "memory knob)")
    ap.add_argument("--remat-policy", default="full",
                    help="with --remat: 'full' recomputes everything; "
                         "'dots' saves matmul outputs and recomputes only "
                         "elementwise/attention; 'dots:<K>' applies dots "
                         "to the first K blocks and full to the rest (the "
                         "continuous HBM/MFU dial for models where "
                         "all-dots exceeds memory)")
    ap.add_argument("--chunked-loss", action="store_true",
                    help="transformer model: chunked lm-head cross-entropy "
                         "(never materializes the S x vocab logits)")
    ap.add_argument("--num-experts", type=int, default=0,
                    help="transformer model: switch-MoE blocks with this "
                         "many experts (0 = dense MLP)")
    ap.add_argument("--num-kv-heads", type=int, default=0,
                    help="transformer model: grouped-query attention with "
                         "this many K/V heads (0 = MHA, 1 = MQA)")
    ap.add_argument("--rope", action="store_true",
                    help="transformer model: rotary position embeddings "
                         "instead of a learned table")
    ap.add_argument("--swiglu", action="store_true",
                    help="transformer model: SwiGLU MLP instead of GELU")
    ap.add_argument("--num-layers", type=int, default=4,
                    help="transformer model: number of blocks")
    ap.add_argument("--embed-dim", type=int, default=512,
                    help="transformer model: model width")
    ap.add_argument("--num-heads", type=int, default=8,
                    help="transformer model: attention heads")
    ap.add_argument("--vocab-size", type=int, default=32000)
    ap.add_argument("--momentum", type=float, default=0.9,
                    help="SGD momentum (0 drops the accumulator — one "
                         "params-sized buffer, matters for billion-param "
                         "configs on one chip)")
    ap.add_argument("--mfu", action="store_true",
                    help="transformer model: also report model FLOPs "
                         "utilization from the measured tok/s")
    ap.add_argument("--peak-tflops", type=float, default=None,
                    help="accelerator peak (bf16) TFLOP/s for --mfu "
                         "(default: looked up by device kind)")
    return ap


# Peak dense bf16 TFLOP/s per chip by ``jax.devices()[0].device_kind``
# (Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16).
PEAK_BF16_TFLOPS = {"TPU v5 lite": 197.0}


def peak_tflops(args) -> float:
    """``--peak-tflops``, or the table's row for the device in use; a device
    kind that is not in the table is an error, not a default."""
    if args.peak_tflops is not None:
        return args.peak_tflops
    import jax
    kind = jax.devices()[0].device_kind
    if kind not in PEAK_BF16_TFLOPS:
        raise SystemExit(
            f"--mfu: no peak TFLOP/s on record for device kind {kind!r} "
            f"(known: {sorted(PEAK_BF16_TFLOPS)}); pass --peak-tflops")
    return PEAK_BF16_TFLOPS[kind]


def transformer_train_flops_per_token(args, params_total: int) -> float:
    """Training FLOPs per token: 6*N for the parameter matmuls (fwd 2N +
    bwd 4N) plus the attention scores/values term 12*L*S*d (*0.5 causal),
    the standard PaLM-appendix accounting."""
    attn = 12 * args.num_layers * args.seq_len * args.embed_dim * 0.5
    return 6.0 * params_total + attn


def measure(args, devices=None, quiet=False):
    """Run the benchmark over ``devices`` (default: all) and return
    ``(mean_rate, ci, n_devices)`` where rate is samples/sec across devices."""
    import jax
    import jax.numpy as jnp
    import optax

    import bluefog_tpu as bf
    from bluefog_tpu import models
    from bluefog_tpu.optim import CommunicationType

    local_size = None
    if args.dist_optimizer == "hierarchical":
        ndev = len(devices) if devices is not None else len(jax.devices())
        local_size = max(1, ndev // 2)
    bf.init(devices=devices, local_size=local_size)
    n = bf.size()

    attn = None
    if args.flash_attention:
        from bluefog_tpu.ops.flash_attention import flash_attention_impl
        attn = flash_attention_impl()

    def images(*shape, dtype=jnp.bfloat16):
        """A rank-major synthetic batch, each row made on its rank's device."""
        return bf.rank_map(
            lambda: jnp.zeros((args.batch_size,) + shape, dtype))()

    if args.model.startswith(("resnet", "vgg")):
        name = args.model.replace("resnet", "ResNet").replace("vgg", "VGG")
        model = getattr(models, name)(num_classes=1000, dtype=jnp.bfloat16)
        data = images(args.image_size, args.image_size, 3)
        labels = images(dtype=jnp.int32)
        has_bn = args.model.startswith("resnet")  # classic VGG has no BN
    elif args.model == "lenet":
        model = models.LeNet5()
        data = images(28, 28, 1, dtype=jnp.float32)
        labels = images(dtype=jnp.int32)
        has_bn = False
    elif args.model == "vit":
        model = models.ViT(num_classes=1000, image_size=args.image_size,
                           dtype=jnp.bfloat16, remat=args.remat,
                           remat_policy=args.remat_policy, attn_impl=attn)
        data = images(args.image_size, args.image_size, 3)
        labels = images(dtype=jnp.int32)
        has_bn = False
    else:
        cfg = models.TransformerConfig(
            vocab_size=args.vocab_size, num_layers=args.num_layers,
            num_heads=args.num_heads, embed_dim=args.embed_dim,
            max_seq_len=args.seq_len, remat=args.remat,
            remat_policy=args.remat_policy,
            num_experts=args.num_experts,
            num_kv_heads=args.num_kv_heads or None,
            pos_encoding="rope" if args.rope else "learned",
            mlp="swiglu" if args.swiglu else "gelu")
        model = models.TransformerLM(cfg, attn_impl=attn)
        data = images(args.seq_len, dtype=jnp.int32)
        labels = None
        has_bn = False

    # Rank-major from birth: every rank initialises its own copy on its own
    # device (n copies of a billion-parameter model do not fit device 0).
    sample_shape, sample_dtype = (2,) + data.shape[2:], data.dtype
    variables = bf.rank_map(lambda: model.init(
        jax.random.PRNGKey(0), jnp.zeros(sample_shape, sample_dtype)))()
    # Stashed for --mfu reporting in main() (measure()'s return shape is
    # pinned by callers).
    args._params_total = sum(
        int(np.prod(p.shape[1:])) for p in jax.tree_util.tree_leaves(
            variables["params"] if "params" in variables else variables))

    comm = {"neighbor_allreduce": CommunicationType.neighbor_allreduce,
            "allreduce": CommunicationType.allreduce,
            "hierarchical": CommunicationType.hierarchical_neighbor_allreduce,
            "empty": CommunicationType.empty}.get(args.dist_optimizer)
    base = optax.sgd(0.0125 * n, momentum=args.momentum or None)
    if args.dist_optimizer == "gradient_allreduce":
        opt = bf.optim.DistributedGradientAllreduceOptimizer(
            base, compression=args.compression, donate=True)
    elif args.dist_optimizer == "win_put":
        # Window payloads compress through the transport knob.  Set it
        # unconditionally so "--compression none" overrides a pre-set env
        # var and repeated in-process measure() calls stay self-consistent.
        import os
        from bluefog_tpu.utils import config as _config
        os.environ["BLUEFOG_TPU_WIN_COMPRESSION"] = args.compression
        _config.reload()
        if args.compression != "none" and jax.process_count() == 1:
            print("note: window compression applies to CROSS-PROCESS edges "
                  "only; this single-process run sends nothing over the "
                  "transport, so the flag does not change the measurement")
        opt = bf.optim.DistributedWinPutOptimizer(base)
    else:
        cls = (bf.optim.DistributedAdaptThenCombineOptimizer if args.atc
               else bf.optim.DistributedAdaptWithCombineOptimizer)
        # donate: the loop rebinds params/state every batch, so the step
        # may alias them — one params-sized buffer saved, decisive at
        # billion-parameter scale.
        opt = cls(base, comm, use_dynamic_topology=args.dynamic,
                  compression=args.compression, donate=True)

    if has_bn:
        params, bstats = variables["params"], variables["batch_stats"]

        def loss_fn(p, bs, x, y):
            logits, new = model.apply({"params": p, "batch_stats": bs},
                                      x, train=True, mutable=["batch_stats"])
            return optax.softmax_cross_entropy_with_integer_labels(
                logits, y).mean(), new["batch_stats"]

        vgrad = bf.rank_map(jax.value_and_grad(loss_fn, has_aux=True))

        def one_batch(params, bstats, state, batch):
            x, y = batch
            (_, bstats), grads = vgrad(params, bstats, x, y)
            params, state = opt.step(params, grads, state)
            return params, bstats, state
    else:
        params = variables["params"] if "params" in variables else variables
        if args.model == "transformer" and args.chunked_loss:
            from bluefog_tpu.ops.chunked_loss import \
                chunked_softmax_cross_entropy

            def loss_fn(p, x, _):
                tree = {"params": p} if "params" in variables else p
                h = model.apply(tree, x, return_hidden=True)
                # p is the params mapping in either branch
                kernel = p["lm_head"]["kernel"]
                tgt = jnp.roll(x, -1, axis=1)
                return chunked_softmax_cross_entropy(h, kernel, tgt)
        elif args.model == "transformer":
            def loss_fn(p, x, _):
                logits = model.apply(
                    {"params": p} if "params" in variables else p, x)
                tgt = jnp.roll(x, -1, axis=1)
                return optax.softmax_cross_entropy_with_integer_labels(
                    logits, tgt).mean()
        else:
            def loss_fn(p, x, y):
                logits = model.apply(
                    {"params": p} if "params" in variables else p, x)
                return optax.softmax_cross_entropy_with_integer_labels(
                    logits, y).mean()

        vgrad = bf.rank_map(jax.grad(loss_fn))
        bstats = None

        def one_batch(params, bstats, state, batch):
            x, y = batch
            grads = vgrad(params, x, y)
            params, state = opt.step(params, grads, state)
            return params, bstats, state

    if args.host_data:
        # Realistic feed: batches start in host RAM and ride the input
        # pipeline; prefetch depth 2 overlaps the transfer with compute.
        # device_put always transfers afresh, so one host copy suffices.
        from bluefog_tpu.data import prefetch_to_device
        host_batch = (np.array(data),
                      None if labels is None else np.array(labels))

        def _gen():
            while True:
                yield host_batch

        feed = prefetch_to_device(_gen(), size=2)
        next_batch = lambda: next(feed)  # noqa: E731
    else:
        device_batch = (data, labels)
        next_batch = lambda: device_batch  # noqa: E731

    state = opt.init(params)

    def sync(params):
        leaf = jax.tree_util.tree_leaves(params)[0]
        float(jnp.sum(leaf[..., :1].astype(jnp.float32)))

    for _ in range(args.num_warmup_batches):
        params, bstats, state = one_batch(params, bstats, state,
                                          next_batch())
    sync(params)

    rates = []
    writer = None
    if args.metrics_file and not quiet:
        from bluefog_tpu.utils.metrics import MetricsWriter
        writer = MetricsWriter(args.metrics_file)
    for i in range(args.num_iters):
        t0 = time.perf_counter()
        for _ in range(args.num_batches_per_iter):
            params, bstats, state = one_batch(params, bstats, state,
                                              next_batch())
        sync(params)
        dt = time.perf_counter() - t0
        rate = n * args.batch_size * args.num_batches_per_iter / dt
        rates.append(rate)
        if writer is not None:
            writer.log(step=i, imgs_per_sec=rate, model=args.model,
                       n_devices=n)
        if not quiet:
            print(f"iter {i}: {rate:.1f} img/sec across {n} devices")
    if writer is not None:
        writer.close()

    return float(np.mean(rates)), 1.96 * float(np.std(rates)), n


def main():
    args = build_parser().parse_args()
    import jax

    report_mfu = (args.mfu and args.model == "transformer"
                  and not args.num_experts)
    peak = peak_tflops(args) if report_mfu else None  # fail before measuring
    mean, ci, n = measure(args)
    unit = "tokens" if args.model == "transformer" else "img"
    if args.model == "transformer":
        mean, ci = mean * args.seq_len, ci * args.seq_len
    print(f"total {unit}/sec: {mean:.1f} +- {ci:.1f} "
          f"({mean / n:.1f}/device, model={args.model}, "
          f"optimizer={args.dist_optimizer})")

    if args.mfu and args.model == "transformer":
        if args.num_experts:
            # Switch MoE activates one expert per token; 6*N over ALL
            # expert weights would overstate FLOPs/token several-fold.
            print("note: --mfu accounting covers dense models only "
                  "(top-1 MoE activates 1 of --num-experts expert MLPs "
                  "per token); skipping the MFU report")
        else:
            fpt = transformer_train_flops_per_token(args, args._params_total)
            mfu = mean / n * fpt / (peak * 1e12)
            print(f"params: {args._params_total/1e9:.3f}B  "
                  f"train FLOPs/token: {fpt/1e9:.2f}G  "
                  f"MFU: {100*mfu:.1f}% of {peak:.0f} TFLOP/s/chip")

    if args.efficiency and n > 1:
        mean1, _, _ = measure(args, devices=jax.devices()[:1], quiet=True)
        if args.model == "transformer":
            mean1 = mean1 * args.seq_len
        eff = mean / (n * mean1)
        print(f"single-device {unit}/sec: {mean1:.1f}")
        print(f"scaling efficiency at {n} devices: {100 * eff:.1f}% "
              f"({mean:.1f} vs {n} x {mean1:.1f})")
    elif args.efficiency:
        print("scaling efficiency: only one device visible; nothing to compare")


if __name__ == "__main__":
    main()
