"""Benchmark the one-sided (window) gossip family — DP-7/8/9's data plane.

The collective family's numbers live in ``examples/benchmark.py``; this
measures the host-side window store and DCN transport that back
``win_put`` / ``win_accumulate`` / ``win_update`` and the async optimizers
(reference counterpart: chunked RMA, ``mpi_controller.cc:953-1184``).

Reported:
  * per-op wall time and MB/s for a fused ResNet-50-sized buffer
    (``win_put`` all-edges, ``win_accumulate``, ``win_update``,
    ``win_update_then_collect``)
  * dispatch latency of the nonblocking ops (the overlap window: how much
    compute can hide behind an in-flight put)
  * device<->host staging cost (the only part that touches the chip)
  * DP-7 (``DistributedWinPutOptimizer``) step rate vs the synchronous
    DP-3 (``DistributedNeighborAllreduceOptimizer``) on the same model
  * with ``--multiproc``, relaunches itself under ``bfrun -np 2`` and
    measures cross-process puts/s and bytes/s per DCN edge, with and
    without bf16 wire compression

Usage:
  python examples/window_benchmark.py [--elements N] [--rounds R]
  python examples/window_benchmark.py --multiproc
"""

import argparse
import json
import os
import subprocess
import sys
import time

import numpy as np


def _timeit(fn, rounds):
    fn()  # warm caches / first dispatch
    t0 = time.perf_counter()
    for _ in range(rounds):
        fn()
    return (time.perf_counter() - t0) / rounds


def single_process(args):
    import jax
    import jax.numpy as jnp
    import optax

    import bluefog_tpu as bf
    from bluefog_tpu import topology as topo

    bf.init(lambda: topo.ExponentialTwoGraph(max(2, bf_world())))
    n = bf.size()
    P = args.elements
    mb = P * 4 / 1e6
    x = np.random.RandomState(0).randn(n, P).astype(np.float32)
    out = {"n": n, "elements": P, "mb_per_rank": mb}
    print(f"window store: {n} ranks, {mb:.1f} MB/rank fused buffer")

    assert bf.win_create(x, "bench")
    edges = sum(len(bf.out_neighbor_ranks(r)) for r in range(n))

    t = _timeit(lambda: bf.win_put(x, "bench"), args.rounds)
    out["win_put_s"] = t
    print(f"win_put   (all {edges} edges): {t*1e3:8.1f} ms "
          f"({edges * mb / t / 1e3:6.2f} GB/s aggregate)")

    t = _timeit(lambda: bf.win_accumulate(x, "bench"), args.rounds)
    out["win_accumulate_s"] = t
    print(f"win_accumulate               : {t*1e3:8.1f} ms")

    t = _timeit(lambda: bf.win_update("bench"), args.rounds)
    out["win_update_s"] = t
    print(f"win_update (combine)         : {t*1e3:8.1f} ms")

    t = _timeit(lambda: bf.win_update_then_collect("bench"), args.rounds)
    out["win_update_then_collect_s"] = t
    print(f"win_update_then_collect      : {t*1e3:8.1f} ms")

    # Overlap window: nonblocking dispatch returns in microseconds; the put
    # runs on the worker pool while the caller computes.
    t0 = time.perf_counter()
    h = bf.win_put_nonblocking(x, "bench")
    t_dispatch = time.perf_counter() - t0
    bf.win_wait(h)
    out["dispatch_s"] = t_dispatch
    print(f"nonblocking dispatch latency : {t_dispatch*1e6:8.1f} us "
          f"(put completes on the worker pool)")
    bf.win_free("bench")

    # Device<->host staging: the only on-chip cost of the window family.
    xd = jnp.asarray(x[0])
    jax.block_until_ready(xd)
    t = _timeit(lambda: np.asarray(jax.device_get(xd)), args.rounds)
    out["device_to_host_s"] = t
    print(f"device->host ({mb:.0f} MB)      : {t*1e3:8.1f} ms "
          f"({mb / t / 1e3:6.2f} GB/s)")
    t = _timeit(
        lambda: jax.block_until_ready(jax.device_put(x[0])), args.rounds)
    out["host_to_device_s"] = t
    print(f"host->device ({mb:.0f} MB)      : {t*1e3:8.1f} ms "
          f"({mb / t / 1e3:6.2f} GB/s)")

    # DP-7 async optimizer vs DP-3 synchronous on the same tiny model.
    D = args.model_dim
    params = {"w": jnp.asarray(
        np.random.RandomState(1).randn(n, D, 1).astype(np.float32))}
    grads = jax.tree.map(jnp.zeros_like, params)
    for name, opt in [
            ("DP-7 win_put ", bf.optim.DistributedWinPutOptimizer(
                optax.sgd(0.01))),
            ("DP-7 overlap ", bf.optim.DistributedWinPutOptimizer(
                optax.sgd(0.01), window_prefix="winput_ov", overlap=True)),
            ("DP-3 sync nbr", bf.optim.DistributedNeighborAllreduceOptimizer(
                optax.sgd(0.01)))]:
        state = opt.init(params)

        def step(params=params, state=state, opt=opt):
            p, s = opt.step(params, grads, state)
            jax.block_until_ready(jax.tree_util.tree_leaves(p)[0])
            return p, s
        t = _timeit(step, args.rounds)
        out[f"opt_{name.strip().replace(' ', '_')}_s"] = t
        print(f"{name} step ({D}-param model): {t*1e3:8.2f} ms")
        if hasattr(opt, "free"):
            opt.free()
    return out


def bf_world() -> int:
    import jax
    return len(jax.devices())


_MP_CHILD = "_WINBENCH_CHILD"


def multiproc_child(args):
    # bfrun launches us by script path, so sys.path[0] is examples/ — add
    # the repo root for the package import.
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    import jax

    import bluefog_tpu as bf
    from bluefog_tpu import topology as topo

    bf.init_distributed(lambda: topo.RingGraph(bf_world()))
    n = bf.size()
    P = args.elements
    mb = P * 4 / 1e6
    owned = bf.owned_ranks()
    owned_layout = os.environ.get("BFTPU_BENCH_OWNED") == "1"
    if owned_layout:
        # Owned-rows layout: the caller-side array is (owned, P), not
        # (n, P) — at large n the host working set stays O(owned).
        x = np.random.RandomState(0).randn(len(owned), P).astype(np.float32)
        assert bf.win_create(x, "mp", zero_init=True)
    else:
        x = np.random.RandomState(0).randn(n, P).astype(np.float32)
        assert bf.win_create(x, "mp")
    # Cross-process edges: with 2 procs on a ring every rank has one
    # in-neighbor owned by the peer (and one local).
    my = jax.process_index()
    bf.win_fence()
    t0 = time.perf_counter()
    for _ in range(args.rounds):
        bf.win_put(x, "mp")
    bf.win_fence()  # all puts applied at their targets
    dt = (time.perf_counter() - t0) / args.rounds
    # Ring over 2 procs: each process sends its owned ranks' rows along 2
    # edges each; half the edges cross the process boundary.
    edges_out = sum(len(bf.out_neighbor_ranks(r)) for r in owned)
    cross = sum(1 for r in owned for t_ in bf.out_neighbor_ranks(r)
                if t_ not in owned)
    comp = os.environ.get("BLUEFOG_TPU_WIN_COMPRESSION", "none")
    wire_mb = mb * (0.5 if comp == "bf16" else 1.0)
    layout = "owned" if owned_layout else "rank-major"
    host_mb = x.nbytes / 1e6
    print(f"proc{my}: win_put round {dt*1e3:.1f} ms "
          f"({edges_out} edges, {cross} cross-process, "
          f"{cross * wire_mb / dt / 1e3:.2f} GB/s DCN payload, "
          f"compression={comp}, layout={layout}, "
          f"caller array {host_mb:.0f} MB)", flush=True)
    bf.win_free("mp")


def multiproc_parent(args):
    here = os.path.abspath(__file__)
    for comp, owned in (("none", "0"), ("bf16", "0"), ("none", "1")):
        env = dict(os.environ, BLUEFOG_TPU_WIN_COMPRESSION=comp,
                   BFTPU_BENCH_OWNED=owned)
        env[_MP_CHILD] = "1"
        out = subprocess.run(
            [sys.executable, "-m", "bluefog_tpu.run", "-np", "2",
             "--devices-per-proc", "2", sys.executable, here,
             "--elements", str(args.elements), "--rounds", str(args.rounds)],
            env=env, capture_output=True, text=True, timeout=600)
        if out.returncode != 0:
            print(out.stdout)
            print(out.stderr[-2000:], file=sys.stderr)
            raise SystemExit(out.returncode)
        for line in out.stdout.splitlines():
            if line.startswith("proc"):
                print(line)


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--elements", type=int, default=25_557_032,
                    help="elements per rank row (default: ResNet-50 params)")
    ap.add_argument("--rounds", type=int, default=5)
    ap.add_argument("--model-dim", type=int, default=1024)
    ap.add_argument("--multiproc", action="store_true",
                    help="measure cross-process DCN edges under bfrun -np 2")
    ap.add_argument("--json", action="store_true",
                    help="print a JSON summary line at the end")
    args = ap.parse_args()
    if os.environ.get(_MP_CHILD):
        multiproc_child(args)
        return
    if args.multiproc:
        multiproc_parent(args)
        return
    out = single_process(args)
    if args.json:
        print(json.dumps(out))


if __name__ == "__main__":
    main()
