"""Headline benchmark: ResNet-50 decentralized training throughput.

Mirrors the reference's protocol (``examples/pytorch_benchmark.py:38-44,
228-256``): synthetic ImageNet data, N warmup batches, I iterations of B
batches each, report mean images/sec.  The reference's headline number is
4310.6 img/s on 16 V100s == ~269 img/s/GPU at batch 64 (BASELINE.md); here we
measure img/s per TPU chip with the same per-device batch size, running the
FULL decentralized training step (forward, backward, SGD+momentum update, and
the dynamic one-peer Exp-2 neighbor averaging) over all available devices.

Prints ONE JSON line:
  {"metric": ..., "value": ..., "unit": "img/s/chip", "vs_baseline": ...}
``vs_baseline`` is per-chip throughput over the reference's 269 img/s/GPU.

Needs a TPU: without one it exits non-zero and prints no metric line.
"""

import json
import os
import sys
import time
from functools import partial

import numpy as np

BASELINE_PER_GPU = 4310.6 / 16  # img/s per V100, reference docs/performance.rst


def _placement_summary(devs, dyn) -> "dict | None":
    """Modeled placement evidence for BENCH json: identity vs optimized
    max-link-load of the benchmark's own dynamic gossip schedule on the
    interconnect the devices expose (TPU coords / BLUEFOG_TPU_FAKE_TORUS).
    Flat hosts get a synthetic near-square torus sized to the mesh, clearly
    labeled — a cost-model data point proving the optimizer path, never a
    hardware claim."""
    import math

    from bluefog_tpu.ops import placement as PL
    n = len(devs)
    if n < 2 or dyn is None:
        return None
    model = PL.build_model(devs)
    synthetic = model is None
    if model is None:
        r = max(int(math.isqrt(n)), 1)
        while n % r:
            r -= 1
        model = PL.synthetic_torus((r, n // r),
                                   name=f"synthetic-{r}x{n // r}")
    try:
        res = PL.optimize_placement(model, dyn, n, iters=300, seed=0)
    except ValueError:
        return None
    return {
        "model": model.name + (" (synthetic)" if synthetic else ""),
        "max_link_load_naive": res.identity_cost.max_link_load,
        "max_link_load_opt": res.optimized_cost.max_link_load,
        "improvement_ratio": round(res.improvement_ratio, 3),
    }


def _hierarchy_summary(devs, tree_bytes: float) -> "dict | None":
    """Hierarchical-gossip evidence for BENCH json: the two-level policy
    (levels, outer cadence, per-level compression) and the modeled
    per-step wire bytes of each level for THIS run's parameter tree.
    ``enabled`` mirrors ``BLUEFOG_TPU_HIER`` so the schema is stable; on
    hosts whose devices expose no slice structure a synthetic 2-slice
    split is priced and labeled (code-path evidence, never a hardware
    claim — same convention as detail.placement)."""
    from bluefog_tpu import topology
    from bluefog_tpu.utils import config
    cfg = config.get()
    n = len(devs)
    out = {"enabled": bool(cfg.hier)}
    if n < 2:
        return out
    slices = {int(getattr(d, "slice_index", 0) or 0) for d in devs}
    n_slices, synthetic = len(slices), False
    if n_slices < 2 or n % n_slices:
        if n % 2:
            return out
        n_slices, synthetic = 2, True
    try:
        ht = topology.hierarchical_two_level(
            n, n_slices, inner=cfg.hier_inner, outer=cfg.hier_outer,
            outer_every=cfg.hier_outer_every,
            outer_self_weight=cfg.hier_outer_self_weight)
    except ValueError:
        return out
    comp = cfg.hier_outer_compression
    factor = config.compression_byte_factor(comp)
    inner_edges = ht.ici_edges_per_step()
    row_bytes = float(tree_bytes) / n
    out.update({
        "levels": 2,
        "n_slices": n_slices,
        "slice_size": ht.slice_size,
        "synthetic_slices": synthetic,
        "inner": ht.inner_kind,
        "outer": ht.outer_kind,
        "outer_every": ht.outer_every,
        "outer_compression": comp,
        "outer_self_weight": ht.outer_self_weight,
        "ici_bytes_per_step": round(row_bytes * inner_edges, 1),
        "dcn_bytes_per_step": round(
            row_bytes * ht.dcn_edges_per_outer_step() * factor
            / max(ht.outer_every, 1), 1),
    })
    return out


def _sharding_summary(devs) -> "dict | None":
    """Sharded-gossip evidence for BENCH json: the ``ShardPlan`` of a
    labeled synthetic MoE tree (this bench's ResNet tree is fully
    replicated, so a synthetic tree is what exercises the planner —
    code-path evidence, same convention as detail.hierarchy's synthetic
    slices): replicated fraction, planner decisions per leaf, and the
    modeled per-level / per-shard wire bytes on THIS mesh.  ``enabled``
    mirrors ``BLUEFOG_TPU_SHARDED_GOSSIP`` so the schema is stable."""
    import numpy as np
    from bluefog_tpu import topology
    from bluefog_tpu.ops import schedule as S
    from bluefog_tpu.ops import sharded as SH
    from bluefog_tpu.utils import config
    cfg = config.get()
    n = len(devs)
    out = {"enabled": bool(cfg.sharded_gossip)}
    if n < 4 or n % 2:
        return out
    n_shards = 4 if n % 4 == 0 else 2
    tree = {
        "router": np.zeros((n, 256), np.float32),
        "experts": np.zeros((n, n_shards, 512), np.float32),
        # Indivisible model dim: the planner must fall back to
        # replicated and say so in its decision string.
        "head": np.zeros((n, 7, 16), np.float32),
    }
    specs = {"router": None, "experts": ("ep", None),
             "head": ("ep", None)}
    try:
        plan = SH.build_plan(tree, specs, n=n, n_shards=n_shards)
        sched = S.compile_static(topology.ExponentialTwoGraph(n))
        gsched, _per = SH.compile_group_schedules(n, plan.groups)
    except (ValueError, SystemExit):
        return out
    rep_ici, rep_dcn = SH.edge_level_counts(plan.coords, sched)
    g_ici, g_dcn = SH.edge_level_counts(plan.coords, gsched)
    rep_row = plan.rep_bytes / n
    sh_row = (plan.sh_bytes / n / plan.n_shards
              if plan.any_sharded else 0.0)
    out.update(plan.summary())
    out.update({
        "synthetic_tree": True,
        "bytes_per_step": {
            "replicated_ici": round(rep_row * rep_ici, 1),
            "replicated_dcn": round(rep_row * rep_dcn, 1),
            "sharded_ici": round(sh_row * g_ici, 1),
            # Always 0 by construction — in-group schedules cross no
            # replica-group boundary; kept so regressions are visible.
            "sharded_dcn": round(sh_row * g_dcn, 1),
        },
    })
    return out


def _churn_summary() -> "dict | None":
    """Churn-controller evidence for BENCH json: the live membership view
    (epoch, active ranks, change count, last change time) when
    BLUEFOG_TPU_CHURN is on, or the enabled=False stub otherwise — so a
    bench run under churn carries the gang state its numbers were measured
    against.  The single-chip bench never churns; the block exists so the
    JSON schema is stable across workloads (the chaos harness is where the
    membership actually moves)."""
    from bluefog_tpu.ops import membership
    from bluefog_tpu.utils import config
    if not config.get().churn:
        return {"enabled": False}
    m = membership.health_summary()
    if m is None:
        return {"enabled": True, "active": None}
    return {
        "enabled": True,
        "epoch": m["epoch"],
        "active_ranks": m["active_ranks"],
        "changes_total": m["changes_total"],
        "last_change_unix": m["last_change_unix"],
    }


def _links_summary() -> "dict | None":
    """Link-observatory evidence for BENCH json: the observatory gate,
    configured SLO rules, and this rank's live link table (per-edge delay
    EWMA / jitter / divergence, tx goodput) when any traced gossip ran.
    The single-chip bench's fused step never crosses the DCN window
    transport, so the table is typically empty here; the block exists so
    the JSON schema is stable across workloads (multi-proc runs and the
    chaos links harness are where the edges move), mirroring
    detail.churn."""
    from bluefog_tpu.utils import config, linkobs
    if not config.get().link_obs:
        return {"enabled": False}
    rep = linkobs.local_report()
    return {
        "enabled": True,
        "slo_rules": rep["slo"]["rules"],
        "slo_breached": sorted(rep["slo"]["breached"]),
        "edges": rep["edges"],
        "goodput": rep["goodput"],
    }


def _fused_step_summary() -> "dict | None":
    """Whole-step compilation evidence for BENCH json: with
    BLUEFOG_TPU_FUSED_STEP armed, the eager-vs-fused end-to-end step
    time (p50/p99 ms), speedup and one-time compile cost measured on
    bench_comm's loopback transport rig — the put-family twin of the
    allreduce step this bench times (which already runs as one XLA
    program).  Off by default, so the block is ``{"enabled": False}``
    unless the flag is set; capability misses (no native
    bf_xla_win_put_pass handler, non-CPU jax backend) degrade to a
    labeled skip, mirroring detail.links."""
    from bluefog_tpu.utils import config
    if not config.get().fused_step:
        return {"enabled": False}
    import bench_comm
    from bluefog_tpu import native
    from bluefog_tpu.ops import xlaffi
    if not (native.available() and native.has_win_xla()
            and native.has_xla_handler()
            and xlaffi.has_passthrough()):
        return {"enabled": True,
                "skipped": "native bf_xla_win_put_pass unavailable"}
    prev = bench_comm._fused_env_setup()
    try:
        config.reload()
        xlaffi._reset_for_tests()
        if not xlaffi.armed():
            return {"enabled": True,
                    "skipped": xlaffi.disarm_reason() or "disarmed"}
        cell = bench_comm._fused_timing_cell(steps=20, warm=4)
    finally:
        bench_comm._fused_env_restore(prev)
    return {"enabled": True, **cell}


def _synthesis_summary(devs) -> "dict | None":
    """Modeled schedule-synthesis evidence for BENCH json, matching the
    placement pattern: the flagship STATIC Exp2 gossip schedule priced on
    the interconnect the devices expose (synthetic near-square torus on
    flat hosts, labeled), comparing the congestion-packed baseline against
    the sketch-synthesized selection on serial_link_time.  The one-peer
    dynamic schedule the bench actually steps is single-round per phase
    (nothing to synthesize); the static schedule is where the modeled-comm
    win lives and what multi-round deployments dispatch."""
    import math

    from bluefog_tpu import topology
    from bluefog_tpu.ops import placement as PL
    from bluefog_tpu.ops import schedule as S
    from bluefog_tpu.ops import schedule_opt as SO
    from bluefog_tpu.ops import synthesis as SY
    n = len(devs)
    if n < 4:
        return None
    model = PL.build_model(devs)
    synthetic = model is None
    if model is None:
        r = max(int(math.isqrt(n)), 1)
        while n % r:
            r -= 1
        model = PL.synthetic_torus((r, n // r),
                                   name=f"synthetic-{r}x{n // r}")
    try:
        w = topology.weight_matrix(topology.ExponentialTwoGraph(n))
        naive = S._build_schedule(w, optimize=False)
        sched = SO.optimize_schedule(naive)
        packed = SO.congestion_aware_repack(sched, model, None,
                                            budget_factor=2.0,
                                            record=False)
        chosen, ratio = SY.select_schedule(sched, packed, model, None)
    except ValueError:
        return None
    return {
        "model": model.name + (" (synthetic)" if synthetic else ""),
        "sketch": getattr(chosen, "sketch", None),
        "provenance": S.schedule_provenance(chosen),
        "serial_naive": PL.schedule_cost(model, naive).serial_link_time,
        "serial_konig": PL.schedule_cost(model, sched).serial_link_time,
        "serial_packed": PL.schedule_cost(model, packed).serial_link_time,
        "serial_synth": PL.schedule_cost(model, chosen).serial_link_time,
        "improvement_ratio": round(ratio, 3),
    }


def main():
    import jax
    if jax.default_backend() != "tpu":
        print(f"bench: needs a TPU, jax found {jax.default_backend()!r}; "
              "no metric printed", file=sys.stderr)
        raise SystemExit(3)
    import jax.numpy as jnp
    import optax
    from jax import lax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    import bluefog_tpu as bf
    from bluefog_tpu import topology
    from bluefog_tpu.models import ResNet50
    from bluefog_tpu.ops import schedule as S
    from bluefog_tpu.optim import functional as F

    # Places the persistent compile cache; the step below is still built
    # from the functional layer on the bench's own mesh.
    bf.init()
    devs = jax.devices()
    n = len(devs)
    # Reference protocol (batch raised 64 -> 256: the step is
    # HBM-bandwidth-bound, and larger batches amortize the per-step parameter
    # and BN-statistics traffic).
    batch = 256
    image = 224
    warmup, iters, batches_per_iter = 10, 10, 10

    mesh = Mesh(np.asarray(devs), ("dp",))
    model = ResNet50(num_classes=1000, dtype=jnp.bfloat16)

    images = jnp.zeros((n * batch, image, image, 3), jnp.bfloat16)
    labels = jnp.zeros((n * batch,), jnp.int32)
    variables = model.init(jax.random.PRNGKey(0), images[:2])
    params0, batch_stats0 = variables["params"], variables["batch_stats"]
    rank_major = lambda t: jax.tree.map(
        lambda x: jnp.broadcast_to(x[None], (n,) + x.shape), t)
    params, batch_stats = rank_major(params0), rank_major(batch_stats0)

    base = optax.sgd(0.0125 * n, momentum=0.9)
    dyn = S.compile_dynamic(topology.one_peer_exp2_phases(n), n) if n > 1 else None
    combine = F.make_combiner(
        F.CommunicationType.neighbor_allreduce if n > 1
        else F.CommunicationType.empty, axis_name="dp", dyn_sched=dyn)
    # BLUEFOG_TPU_BENCH_COMPRESSION: none (default) | bf16 | sparse:<frac>.
    # sparse composes with the flagship dynamic one-peer Exp2 schedule (the
    # rotating aligned block rides the same lax.switch of phases).
    compression = os.environ.get("BLUEFOG_TPU_BENCH_COMPRESSION", "none")
    combine = F.compress_combiner(combine, compression)

    def local_step(p, bs, st, images, labels, *, reduce_loss):
        def loss_fn(p):
            logits, new_model_state = model.apply(
                {"params": p, "batch_stats": bs}, images, train=True,
                mutable=["batch_stats"])
            logp = jax.nn.log_softmax(logits)
            loss = -jnp.take_along_axis(logp, labels[:, None], -1).mean()
            return loss, new_model_state["batch_stats"]

        (loss, new_bs), grads = jax.value_and_grad(loss_fn, has_aux=True)(p)
        new_p, new_st = F.atc_step(base, combine, p, grads, st)
        return new_p, new_bs, new_st, (lax.pmean(loss, "dp")
                                       if reduce_loss else loss)

    if n == 1:
        # Single chip: no rank-major wrapper, no shard_map (it costs ~20% at
        # n=1 and the combine is identity anyway).
        params, batch_stats = params0, batch_stats0
        state = jax.jit(lambda p: F.dist_init(base, p))(params)
        step = jax.jit(partial(local_step, reduce_loss=False),
                       donate_argnums=(0, 1, 2))
    else:
        def train_step(params, batch_stats, state, images, labels):
            p, bs, st = jax.tree.map(lambda x: x[0],
                                     (params, batch_stats, state))
            new_p, new_bs, new_st, loss = local_step(
                p, bs, st, images, labels, reduce_loss=True)
            return (jax.tree.map(lambda x: x[None], new_p),
                    jax.tree.map(lambda x: x[None], new_bs),
                    jax.tree.map(lambda x: x[None], new_st), loss)

        def init_state(params):
            st = F.dist_init(base, jax.tree.map(lambda x: x[0], params))
            return jax.tree.map(lambda x: x[None], st)

        state = jax.jit(jax.shard_map(
            init_state, mesh=mesh, in_specs=(P("dp"),),
            out_specs=P("dp")))(params)
        step = jax.jit(
            jax.shard_map(
                train_step, mesh=mesh,
                in_specs=(P("dp"), P("dp"), P("dp"), P("dp"), P("dp")),
                out_specs=(P("dp"), P("dp"), P("dp"), P()),
                check_vma=False),
            donate_argnums=(0, 1, 2))

    data_sharding = NamedSharding(mesh, P("dp"))
    images = jax.device_put(images, data_sharding)
    labels = jax.device_put(labels, data_sharding)

    def sync():
        jax.block_until_ready((params, loss))

    for _ in range(warmup):
        params, batch_stats, state, loss = step(
            params, batch_stats, state, images, labels)
    sync()

    # Per-phase latency histograms (utils/telemetry.observe): dispatch
    # wall time per step ("optimizer-update" — the whole fused program's
    # python-side cost) and the per-iteration device sync ("host-sync"),
    # so BENCH json carries p50/p99 TAIL evidence, not just the mean rate.
    # A bench-OWNED series: these definitions differ from the step
    # profiler's canonical bf_step_phase_seconds attribution and must not
    # pollute it.
    from bluefog_tpu.utils import telemetry
    rates = []
    for _ in range(iters):
        t0 = time.perf_counter()
        for _ in range(batches_per_iter):
            t_step = time.perf_counter()
            params, batch_stats, state, loss = step(
                params, batch_stats, state, images, labels)
            telemetry.observe("bf_bench_phase_seconds",
                              time.perf_counter() - t_step,
                              phase="optimizer-update")
        t_sync = time.perf_counter()
        sync()
        telemetry.observe("bf_bench_phase_seconds",
                          time.perf_counter() - t_sync, phase="host-sync")
        dt = time.perf_counter() - t0
        rates.append(n * batch * batches_per_iter / dt)

    total = float(np.mean(rates))
    per_chip = total / n

    # Comm-counter evidence for BENCH_*.json: the training step is ONE
    # fused XLA program, so the host-side dispatch counters never fire
    # inside it — record the schedule-derived traffic through the same
    # telemetry registry instead (calls = executed steps; wire bytes from
    # the per-rank parameter row size and the dynamic schedule's per-call
    # round/edge average) and ship the snapshot in the JSON.
    from bluefog_tpu.ops import collective as C
    steps_run = warmup + iters * batches_per_iter
    tree_bytes = float(sum(x.nbytes for x in jax.tree_util.tree_leaves(
        params)))
    op = "dynamic_neighbor_allreduce" if dyn is not None else "local_sgd"
    telemetry.record_comm_traffic(
        op, tree_bytes, size=n, calls=steps_run,
        sched_stats=None if dyn is None else C.schedule_wire_stats(dyn))
    snap = telemetry.snapshot() if telemetry.enabled() else None

    # Tail-latency trajectory for future rounds: per-phase p50/p99 (ms)
    # from the new step-phase histograms (None when telemetry is off).
    phase_latency = {}
    for ph in ("optimizer-update", "host-sync"):
        pct = telemetry.histogram_percentiles(
            "bf_bench_phase_seconds", (50.0, 99.0), phase=ph)
        if pct:
            phase_latency[ph] = {"p50_ms": round(pct[50.0] * 1e3, 3),
                                 "p99_ms": round(pct[99.0] * 1e3, 3)}

    print(json.dumps({
        "metric": "resnet50_train_imgs_per_sec_per_chip",
        "value": round(per_chip, 1),
        "unit": "img/s/chip",
        "vs_baseline": round(per_chip / BASELINE_PER_GPU, 3),
        "detail": {
            "total_imgs_per_sec": round(total, 1),
            "n_devices": n,
            "per_device_batch": batch,
            "image_size": image,
            "backend": jax.default_backend(),
            "device_kind": devs[0].device_kind,
            "stddev_pct": round(100 * float(np.std(rates)) / max(total, 1e-9), 2),
            "optimizer": "ATC neighbor_allreduce (dynamic one-peer Exp2)"
            if n > 1 else "local SGD (single chip)",
            "compression": compression,
            "phase_latency": phase_latency or None,
            "placement": _placement_summary(devs, dyn),
            "synthesis": _synthesis_summary(devs),
            "hierarchy": _hierarchy_summary(devs, tree_bytes),
            "sharding": _sharding_summary(devs),
            "churn": _churn_summary(),
            "links": _links_summary(),
            "fused_step": _fused_step_summary(),
            "telemetry": snap,
        },
    }))


if __name__ == "__main__":
    main()
