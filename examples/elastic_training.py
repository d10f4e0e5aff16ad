"""Preemption-tolerant decentralized training with ``run_elastic``.

There is no reference counterpart: BlueFog lists fault tolerance as a goal
(``README.rst:19``) but a dead rank simply shuts the job down
(``operations.cc:883-910``).  Here the training loop is restartable — run
this script, kill it (or let the cloud preempt the VM), run it again with
the same ``--ckpt-dir``: it resumes from the newest durable checkpoint and
the final model is bit-identical to an uninterrupted run.

    python examples/elastic_training.py --ckpt-dir /tmp/elastic_demo
    # ... ctrl-C / SIGTERM / VM preemption ...
    python examples/elastic_training.py --ckpt-dir /tmp/elastic_demo

``--preempt-at-step N`` sends the process a SIGTERM from inside (self-test
mode demonstrating the save-on-preemption path).
"""

import argparse
import os
import signal


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--ckpt-dir", required=True)
    ap.add_argument("--steps", type=int, default=60)
    ap.add_argument("--save-every", type=int, default=10)
    ap.add_argument("--batch-size", type=int, default=32)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--preempt-at-step", type=int, default=0)
    ap.add_argument("--optimizer", choices=["neighbor_allreduce",
                                            "push_sum"],
                    default="neighbor_allreduce",
                    help="push_sum: async window gossip — the window "
                         "store (staging mass, associated-P) rides the "
                         "checkpoint via win_state_dict, so resume is "
                         "bit-exact for the one-sided family too")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    import bluefog_tpu as bf
    from bluefog_tpu.models import MLP
    from bluefog_tpu.utils.elastic import Preempted, run_elastic

    bf.init()
    n = bf.size()

    # Deterministic synthetic regression task, sharded statically per rank.
    rng = np.random.RandomState(0)
    xs = rng.randn(n * 512, 16).astype(np.float32)
    w_true = rng.randn(16, 1).astype(np.float32)
    ys = xs @ w_true + 0.01 * rng.randn(n * 512, 1).astype(np.float32)
    loader = bf.data.ShardedLoader({"x": xs, "y": ys},
                                   batch_size=args.batch_size, seed=3,
                                   static_shards=True)

    model = MLP(features=(64,), num_classes=1)  # 1 output: regression head
    params = bf.rank_map(lambda: model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 16))))()
    if args.optimizer == "push_sum":
        # Push-sum needs a topology whose out-degrees drive the
        # column-stochastic split; a directed ring keeps it simple.
        bf.set_topology(bf.topology_util.RingGraph(n, connect_style=2))
        opt = bf.optim.DistributedPushSumOptimizer(optax.sgd(args.lr))
    else:
        opt = bf.optim.DistributedNeighborAllreduceOptimizer(
            optax.adam(args.lr))

    def loss_fn(p, x, y):
        return jnp.mean((model.apply(p, x) - y) ** 2)

    grad_all = bf.rank_map(jax.grad(loss_fn))
    steps_per_epoch = loader.steps_per_epoch

    # Data order is derived from the step, so resume replays the same
    # batches (epoch = step // steps_per_epoch). The example materializes
    # each epoch's batches; a streaming job would re-iterate the loader.
    cache = {"epoch": -1, "batches": None}

    push_sum = args.optimizer == "push_sum"

    def step_fn(state, step):
        epoch = step // steps_per_epoch
        if cache["epoch"] != epoch:
            loader.set_epoch(epoch)
            cache["epoch"], cache["batches"] = epoch, list(loader)
        batch = cache["batches"][step % steps_per_epoch]
        at = opt.debias(state["params"]) if push_sum else state["params"]
        grads = grad_all(at, batch["x"], batch["y"])
        new_p, new_s = opt.step(state["params"], grads, state["opt"])
        out = {"params": new_p, "opt": new_s}
        if push_sum:
            out["win"] = state["win"]  # placeholder; refreshed at save time
        return out

    def on_save(state, step):
        if not push_sum:
            return state
        # The window store (staging mass + associated-P) is side-band state
        # the params pytree cannot carry: snapshot it at SAVE time only (a
        # per-step snapshot would copy every window each step for nothing).
        return {**state, "win": opt.window_state_dict()}

    def on_restore(state, step):
        if push_sum:
            opt.load_window_state_dict(state["win"])

    def report(state, step):
        if args.preempt_at_step and step + 1 == args.preempt_at_step:
            os.kill(os.getpid(), signal.SIGTERM)
        if (step + 1) % args.save_every == 0:
            p = opt.debias(state["params"]) if push_sum else state["params"]
            loss = float(jax.vmap(loss_fn)(
                p, jnp.asarray(xs.reshape(n, -1, 16)),
                jnp.asarray(ys.reshape(n, -1, 1))).mean())
            print(f"step {step + 1}  mean rank loss {loss:.5f}", flush=True)

    state0 = {"params": params, "opt": opt.init(params)}
    if push_sum:
        state0["win"] = opt.window_state_dict()
    try:
        final = run_elastic(step_fn, state0, ckpt_dir=args.ckpt_dir,
                            num_steps=args.steps,
                            save_every=args.save_every, on_step=report,
                            on_restore=on_restore, on_save=on_save)
    except Preempted as e:
        print(f"preempted; checkpoint saved at step {e.step} — rerun with "
              f"the same --ckpt-dir to resume")
        raise SystemExit(75)
    eval_p = opt.debias(final["params"]) if push_sum else final["params"]
    loss = float(jax.vmap(loss_fn)(
        eval_p, jnp.asarray(xs.reshape(n, -1, 16)),
        jnp.asarray(ys.reshape(n, -1, 1))).mean())
    if push_sum:
        opt.free()
        bf.turn_off_win_ops_with_associated_p()
    print(f"done: {args.steps} steps, final mean rank loss {loss:.5f}")


if __name__ == "__main__":
    main()
