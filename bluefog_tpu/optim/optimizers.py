"""Distributed optimizer classes — the ``bf.Distributed*Optimizer`` surface.

Parity target: the eight factory functions of reference
``torch/optimizers.py:1180-1554``.  Where the reference wraps a
``torch.optim.Optimizer`` instance and splices communication in via autograd
hooks, these wrap an ``optax.GradientTransformation`` and compile the whole
step — communication included — into one jitted ``shard_map`` program over the
rank mesh.

Data model: parameters/gradients are pytrees of *rank-major* arrays (leading
dim == ``bf.size()``), the same single-controller convention as the eager op
API in ``bluefog_tpu.basics``.  ``init`` returns optimizer state whose leaves
are rank-major too (each rank carries its own moments), so the entire training
loop stays device-resident.

Usage::

    opt = bf.optim.DistributedNeighborAllreduceOptimizer(optax.sgd(0.1))
    state = opt.init(params)
    params, state = opt.step(params, grads, state)

Dynamic topology (one-peer Exp2 etc.)::

    opt = bf.optim.DistributedNeighborAllreduceOptimizer(
        optax.sgd(0.1), use_dynamic_topology=True)
    # phase auto-advances with state.step; no recompilation per step.

The class builds one step program per phase of the schedule, each over that
phase's static edges, and ``step()`` launches the one that ``state.step %
period`` names.  No program holds a ``conditional``, which XLA's scheduler
moves nothing across: a leaf's update runs under another leaf's permute.
The host follows the counter and does not fetch it: the state ``step()``
returned last carries the counter remembered with it.  A state the
optimizer did not return itself (a fresh ``init``, a restored checkpoint,
the state of another optimizer object) costs one read of ``state.step`` from
the device, which waits for whatever still computes it, and is followed from
there (``bf_optim_phase_reads_total``).  A static topology, or a dynamic one
of a single phase, has one program and none of this.

Per-step weight mutation (reference README.rst:110-127 mutates
``opt.self_weight``/``opt.neighbor_weights``): pass ``self_weight=...,
src_weights=...`` kwargs to ``step`` — they become *traced* inputs, so
changing them every iteration never recompiles.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import PartitionSpec as P

from bluefog_tpu import basics
from bluefog_tpu import topology as topology_util
from bluefog_tpu.basics import LOCAL_AXIS, MACHINE_AXIS, RANK_AXIS
from bluefog_tpu.ops import collective as C
from bluefog_tpu.ops import schedule as S
from bluefog_tpu.optim import functional as F
from bluefog_tpu.optim.functional import CommunicationType, DistOptState
from bluefog_tpu.utils import telemetry
from bluefog_tpu.utils.timeline import op_span, startup_span, timed_span

__all__ = [
    "CommunicationType",
    "DistributedOptimizer",
    "DistributedGradientAllreduceOptimizer",
    "DistributedAllreduceOptimizer",
    "DistributedNeighborAllreduceOptimizer",
    "DistributedHierarchicalNeighborAllreduceOptimizer",
    "DistributedHierarchicalGossipOptimizer",
    "DistributedAdaptWithCombineOptimizer",
    "DistributedAdaptThenCombineOptimizer",
]


def _read_counter(state: DistOptState) -> int:
    """``state.step`` fetched from the device (one host sync).  It is
    rank-major, one identical counter per rank row: any row is the value,
    so a row this process holds is read (nothing crosses processes)."""
    step = state.step
    if isinstance(step, jax.Array):
        step = step.addressable_shards[0].data
    return int(np.asarray(step).reshape(-1)[0])


class DistributedOptimizer:
    """Generic decentralized optimizer wrapper (see module docstring).

    Parameters
    ----------
    base : optax.GradientTransformation
    communication_type : CommunicationType
    order : "awc" | "atc" | "gradient_allreduce"
    num_steps_per_communication : communicate every J-th step (local
        aggregation, reference ``torch/optimizers.py:348-350``).
    use_dynamic_topology : cycle the one-peer phase table of the active
        topology (or ``phases`` if given) by step index: one step program
        per phase, chosen on the host from ``state.step`` (module
        docstring).
    phases : explicit list of ``topology.DynamicPhase`` for dynamic mode.
    donate : donate the grads and state buffers to the jitted step so XLA
        aliases them into the outputs (grads, same tree shape as params,
        becomes the new params buffer) — peak memory drops by roughly one
        full parameter set (decisive for billion-parameter models on one
        chip).  The caller must NOT reuse the grads or state it passed in
        after ``step`` returns (the usual ``params, state =
        opt.step(params, grads, state)`` rebinding pattern is safe; the
        params argument itself is not donated).
    shard_specs : tree of *model*-dimension ``PartitionSpec``s matching
        the params structure (``parallel.tensor_parallel.tp_param_specs``
        output) that arms sharded-aware gossip (``ops/sharded.py``,
        ``BLUEFOG_TPU_SHARDED_GOSSIP``): leaves whose spec names a mesh
        axis gossip their per-rank shard slice inside the replica group
        holding the same shard coordinate, while replicated leaves ride
        the full topology — per-step DCN bytes drop to the replicated
        fraction of the tree.  Requires ``neighbor_allreduce`` with an
        awc/atc order.  ``None`` (default): today's replicated-only path,
        bit for bit.
    shard_groups : explicit replica groups (iterable of rank iterables
        partitioning ``range(n)``); default: ``num_shards`` contiguous
        blocks.
    num_shards : shard count along each sharded model dim (groups =
        contiguous rank blocks).  Required when ``shard_specs`` marks any
        leaf sharded and ``shard_groups`` is not given.
    profile_every : every N steps, block until the step's device work
        completes, record the TRUE step wall time into the step-profiler
        histograms and gather every rank's duration into a straggler
        report (``bf_straggler_score``, surfaced in ``/healthz`` and
        ``%bfstat``).  The synced sample costs one host sync + one tiny
        allgather per period, so it is opt-in: ``None`` defers to
        ``BLUEFOG_TPU_PROFILE`` / ``BLUEFOG_TPU_PROFILE_EVERY``; 0
        disables outright.  COLLECTIVE in multi-process runs (every
        process steps the same loop, so the periods line up).
    """

    def __init__(self, base: optax.GradientTransformation,
                 communication_type: CommunicationType =
                 CommunicationType.neighbor_allreduce,
                 *, order: str = "awc",
                 num_steps_per_communication: int = 1,
                 use_dynamic_topology: bool = False,
                 phases=None,
                 compression: str = "none", donate: bool = False,
                 profile_every: Optional[int] = None,
                 shard_specs=None, shard_groups=None,
                 num_shards: Optional[int] = None):
        if isinstance(communication_type, str):
            communication_type = CommunicationType(communication_type)
        if compression not in ("none", "bf16") and not (
                isinstance(compression, str)
                and compression.startswith(("sparse", "topk"))):
            raise ValueError(f"unknown compression {compression!r}; "
                             "expected 'none', 'bf16' or 'sparse:<frac>'")
        self.base = base
        self.communication_type = communication_type
        self.order = order
        self.num_steps_per_communication = int(num_steps_per_communication)
        self.use_dynamic_topology = use_dynamic_topology
        self.phases = phases
        # "bf16": halve the wire bytes per round (functional.
        # compress_combiner — the reference family's fp16 compression role).
        self.compression = compression
        self.donate = donate
        if profile_every is not None and int(profile_every) < 0:
            raise ValueError(
                f"profile_every must be >= 0, got {profile_every}")
        self.profile_every = (None if profile_every is None
                              else int(profile_every))
        if shard_specs is not None:
            if communication_type != CommunicationType.neighbor_allreduce:
                raise ValueError(
                    "shard_specs requires CommunicationType."
                    "neighbor_allreduce (sharded leaves gossip per replica "
                    f"group over the compiled schedule), got "
                    f"{communication_type}")
            if order not in ("awc", "atc"):
                raise ValueError(
                    "shard_specs requires a parameter-consensus order "
                    f"(awc/atc), got {order!r}")
        self.shard_specs = shard_specs
        self.shard_groups = shard_groups
        self.num_shards = None if num_shards is None else int(num_shards)
        self._jitted = {}
        # Phase choice under a dynamic topology: the step array of the state
        # returned last and the counter it holds (see _phase).
        self._followed = (None, 0)
        self._steps_seen = 0  # host-side counter for telemetry sampling
        self._ahead = None       # a leaf of the step launched last (step())
        self._hier_meta = None   # set by _hier_gossip_bundle
        self._hier_step0 = None  # state.step of the first hier step seen
        self._shard_plan_cache = {}  # (treedef, shapes) -> ShardPlan
        self._shard_meta_cache = {}  # telemetry edge counts per plan/topo
        self._shard_step0 = None  # state.step of the first sharded step

    # -- schedule resolution ------------------------------------------------
    def _exchange_schedule(self):
        """:meth:`_schedule` for the communication types that exchange over
        one, None for the others."""
        if self.communication_type in (
                CommunicationType.neighbor_allreduce,
                CommunicationType.hierarchical_neighbor_allreduce):
            return self._schedule()
        return None

    def _schedule(self):
        """The compiled schedule of the active (machine) topology: dynamic
        under ``use_dynamic_topology``, else static."""
        ctx = basics._require_init()
        hier = (self.communication_type ==
                CommunicationType.hierarchical_neighbor_allreduce)
        topo = ctx.machine_topology if hier else ctx.topology
        weighted = ctx.is_machine_topo_weighted if hier else ctx.is_topo_weighted
        if topo is None:
            raise RuntimeError("no (machine) topology installed; call bf.init()")
        n = topo.number_of_nodes()
        version = (ctx.machine_topology_version if hier
                   else ctx.topology_version)
        if self.use_dynamic_topology:
            key = ("opt_dyn", version,
                   None if self.phases is None
                   else tuple(tuple(ph.pairs) for ph in self.phases))
            phases = self.phases
            return ctx.static_schedule(key, lambda: S.compile_dynamic(
                phases if phases is not None
                else topology_util.dynamic_phase_table(topo), n))
        key = ("opt_static", version, weighted)
        return ctx.static_schedule(
            key, lambda: S.compile_static(topo, use_topo_weights=weighted))

    # -- sharded-gossip plan resolution ------------------------------------
    def _shard_plan(self, params):
        """Resolve (and cache) the sharded-gossip plan for this tree.

        Returns ``None`` — the verbatim legacy path — unless shard specs
        were supplied AND ``BLUEFOG_TPU_SHARDED_GOSSIP`` is on.  The plan
        is cached by (treedef, shapes, dtypes): the mask depends on leaf
        shapes (indivisible dims fall back to replicated)."""
        from bluefog_tpu.utils import config
        if self.shard_specs is None or not config.get().sharded_gossip:
            return None
        leaves, treedef = jax.tree_util.tree_flatten(params)
        key = (treedef,
               tuple((tuple(l.shape), str(np.dtype(l.dtype)))
                     for l in leaves))
        plan = self._shard_plan_cache.get(key)
        if plan is None:
            from bluefog_tpu.ops import sharded as SH
            plan = SH.build_plan(
                params, self.shard_specs, n=basics.size(),
                n_shards=self.num_shards, groups=self.shard_groups)
            self._shard_plan_cache[key] = plan
        return plan

    def _group_schedule(self, ctx, plan):
        """Merged per-replica-group schedule for ``plan`` (cached on the
        context like every other compiled schedule; the key carries the
        sharding signature so re-sharding re-prices)."""
        from bluefog_tpu.ops import sharded as SH
        return ctx.static_schedule(
            ("opt_sharded", ctx.topology_version, plan.signature),
            lambda: SH.compile_group_schedules(plan.n, plan.groups))

    def _shard_telemetry_meta(self, plan):
        """(replicated-ici, replicated-dcn, in-group) edge counts for the
        per-shard byte accounting, memoized per (topology, plan)."""
        from bluefog_tpu.ops import sharded as SH
        ctx = basics._require_init()
        key = (ctx.topology_version, plan.signature,
               self.use_dynamic_topology)
        meta = self._shard_meta_cache.get(key)
        if meta is None:
            rep_ici, rep_dcn = SH.edge_level_counts(
                plan.coords, self._schedule())
            grp_edges = 0.0
            if plan.any_sharded:
                gsched, _per_group = self._group_schedule(ctx, plan)
                grp_edges = float(
                    sum(len(r.pairs) for r in gsched.rounds))
            meta = (rep_ici, rep_dcn, grp_edges)
            self._shard_meta_cache[key] = meta
        return meta

    def _build_step(self, with_weights: bool, plan=None, phase: int = 0):
        ctx = basics._require_init()
        hier = (self.communication_type in (
                CommunicationType.hierarchical_neighbor_allreduce,
                CommunicationType.hierarchical_gossip))
        sched = self._exchange_schedule()
        if isinstance(sched, S.DynamicSchedule) and sched.period > 1:
            # One program per phase, each over that phase's static schedule:
            # the program holds no conditional, so the scheduler is free to
            # run one leaf's update under another leaf's permute.  step()
            # launches the one the state's counter names (_phase).  (A
            # switch over one phase is no conditional to begin with.)
            sched = sched.phases[phase]
        hier_bundle = None
        if self.communication_type == CommunicationType.hierarchical_gossip:
            hier_bundle = self._hier_gossip_bundle(ctx)
        combine = F.make_combiner(
            self.communication_type,
            axis_name=RANK_AXIS if not hier else MACHINE_AXIS,
            sched=sched,
            local_axis=LOCAL_AXIS if hier else None,
            machine_axis=MACHINE_AXIS if hier else None,
            hier=hier_bundle)
        shard_combine = None
        if plan is not None and plan.any_sharded:
            # The sharded leaves' combiner gossips each rank's own shard
            # slice over the merged per-group schedule; compression
            # composes exactly as on the replicated combiner.
            gsched, _per_group = self._group_schedule(ctx, plan)
            gc = F.make_combiner(
                CommunicationType.neighbor_allreduce,
                axis_name=RANK_AXIS, sched=gsched)
            gc = F.compress_combiner(
                gc, self.compression,
                steps_per_comm=self.num_steps_per_communication)
            shard_combine = F.make_shard_combiner(
                plan, gc, axis_name=RANK_AXIS)
        inner = F.step_fn(
            self.order, self.base, combine,
            axis_name=RANK_AXIS,
            steps_per_comm=self.num_steps_per_communication,
            compression=self.compression,
            shard_plan=plan, shard_combine=shard_combine)
        mesh = ctx.hier_mesh if hier else ctx.mesh
        spec = P((MACHINE_AXIS, LOCAL_AXIS)) if hier else P(RANK_AXIS)

        def run(params, grads, state, *maybe_w):
            local = jax.tree.map(lambda x: x[0], (params, grads, state))
            p, g, s = local
            kw = {"weights": maybe_w[0]} if maybe_w else {}
            new_p, new_s = inner(p, g, s, **kw)
            return jax.tree.map(lambda x: x[None], (new_p, new_s))
        basics._name_program(run, "optim_step")

        # What one call of this program puts on the wire, for step()'s
        # bf_comm_*_total{op="optimizer_step"}: the schedule it was built
        # over, the share of steps that communicate and the payload's size
        # against the tree's.  Computed here, once per built program.
        from bluefog_tpu.utils import config
        traffic = {
            "sched_stats": (None if sched is None
                            else C.schedule_wire_stats(sched)),
            "calls": 1.0 / self.num_steps_per_communication,
            "factor": (0.0 if combine.identity else
                       config.compression_byte_factor(self.compression)),
            "nbytes": None}   # of the combined tree: the first step fills it
        n_w = 1 if with_weights else 0
        # Donate grads + state only: XLA aliases the grads buffer (same
        # tree shape) into new_params, which is the whole params-sized
        # saving; donating params too would just trigger "unusable donated
        # buffer" warnings since no same-shaped output remains to alias.
        return jax.jit(jax.shard_map(
            run, mesh=mesh,
            in_specs=(spec, spec, spec) + (P(),) * n_w,
            out_specs=(spec, spec)),
            donate_argnums=(1, 2) if self.donate else ()), traffic

    def _hier_gossip_bundle(self, ctx) -> dict:
        """Compiled two-level bundle for the ``hierarchical_gossip``
        communication type (BLUEFOG_TPU_HIER) — also stashes the modeled
        per-level wire metadata ``step()`` feeds into
        ``bf_comm_level_bytes_total``."""
        from bluefog_tpu.utils import config
        cfg = config.get()
        if not cfg.hier:
            raise RuntimeError(
                "CommunicationType.hierarchical_gossip requires "
                "BLUEFOG_TPU_HIER=1 (default off — the flat path stays "
                "bit-identical without it)")
        if ctx.local_size >= len(ctx.devices):
            raise RuntimeError(
                "hierarchical_gossip needs a multi-slice mesh: call "
                "bf.init(local_size=<ranks per slice>) so "
                "machine_size() > 1")
        ht = basics._hier_topology(ctx, cfg)
        (inner_sched, outer_scheds, inner_edges), _sig = \
            basics._hier_bundle(ctx, ht, cfg)
        comp = cfg.hier_outer_compression
        frac = (config.parse_sparse_frac(comp)
                if comp.startswith("sparse") else None)
        self._hier_meta = (ht, inner_edges, comp, frac)
        return {"inner_sched": inner_sched, "outer_scheds": outer_scheds,
                "outer_every": ht.outer_every, "outer_compression": comp,
                "outer_frac": frac}

    def _step_program(self, with_weights: bool, plan=None, phase: int = 0):
        """``(jitted step, its traffic)``, built on first use for each
        topology version, weight-override arity, shard plan and phase of a
        dynamic schedule."""
        ctx = basics._require_init()
        key = (ctx.topology_version, ctx.machine_topology_version,
               with_weights,
               None if plan is None else plan.signature, phase)
        if key not in self._jitted:
            with op_span("optim", "build", key=str(key)):
                telemetry.inc("bf_step_program_builds_total",
                              program="optim_step")
                self._jitted[key] = self._build_step(with_weights, plan,
                                                     phase)
        return self._jitted[key]

    def _step_callable(self, with_weights: bool, plan=None):
        """The step program (of phase 0, where there are several)."""
        return self._step_program(with_weights, plan)[0]

    def _phase(self, state: DistOptState):
        """``(phase, counter)`` of the step program ``state`` runs next
        where a dynamic schedule has more than one to choose from, else
        ``(0, None)``.

        The host follows the counter, it does not fetch it: the state
        ``step()`` returned last carries the counter remembered with it,
        and is known by its ``step`` array being that very object.  Any
        other state (a fresh ``init``, a restored checkpoint, another
        optimizer's) is read from the device once, which waits for
        whatever still computes it, and followed from there."""
        sched = (self._exchange_schedule() if self.use_dynamic_topology
                 else None)
        if sched is None or sched.period == 1:
            return 0, None
        step, counter = self._followed
        if state.step is not step:
            telemetry.inc("bf_optim_phase_reads_total")
            counter = _read_counter(state)
        return counter % sched.period, counter

    # -- public surface -----------------------------------------------------
    def init(self, params) -> DistOptState:
        """Build rank-major optimizer state for rank-major ``params``."""
        with startup_span("optim", "init", part="optim_init"):
            return self._init(params)

    def _init(self, params) -> DistOptState:
        ctx = basics._require_init()
        hier = (self.communication_type in (
                CommunicationType.hierarchical_neighbor_allreduce,
                CommunicationType.hierarchical_gossip))
        mesh = ctx.hier_mesh if hier else ctx.mesh
        spec = P((MACHINE_AXIS, LOCAL_AXIS)) if hier else P(RANK_AXIS)

        def run(params):
            local = jax.tree.map(lambda x: x[0], params)
            st = F.dist_init(self.base, local)
            return jax.tree.map(lambda x: x[None], st)
        placed = jax.tree.map(basics._place, params)
        return jax.jit(jax.shard_map(
            basics._name_program(run, "optim_init"), mesh=mesh,
            in_specs=(spec,), out_specs=spec))(placed)

    def _record_exchange_paths(self, params, plan, traffic) -> None:
        """``bf_optim_exchange_leaves/bytes{path}``: how much of one rank's
        exchanged tree the program built last hands to the combiner as it
        is (``direct``) and how much through a fusion buffer (``packed``),
        and which of the direct leaves go on the wire as blocks (``cut``);
        ``bf_optim_exchange_transfers``: the pieces one round of the
        exchange moves, a cut part counting its blocks.  Set on the first
        step of a built program, from the rules ``functional._fused_apply``
        and ``collective._apply_rounds`` traced it with."""
        leaves = [jax.ShapeDtypeStruct(x.shape[1:], x.dtype)
                  for x in jax.tree_util.tree_leaves(params)]
        if plan is not None and plan.any_sharded:
            leaves = [l for l, m in zip(leaves, plan.mask) if not m]
        if not traffic["factor"]:   # the identity combine exchanges nothing
            leaves = []
        # gradient_allreduce leaves a mixed-dtype tree unpacked
        packs = (self.order != "gradient_allreduce"
                 or len({l.dtype for l in leaves}) <= 1)
        direct, packed = (F._split_direct(leaves) if packs
                          else (range(len(leaves)), ()))
        # Only the flat neighbor combiners hand their parts to the pipeline
        # that cuts; under bf16 it cuts what the codec hands on, and over a
        # schedule without rounds (one rank) there is no wire to cut for.
        stats = traffic["sched_stats"]
        wired = stats is None or stats[0] > 0
        cuts = (wired and self.communication_type ==
                CommunicationType.neighbor_allreduce
                and self.compression in ("none", "bf16"))

        def pieces(part) -> int:
            if self.compression == "bf16":
                part = jax.ShapeDtypeStruct(part.shape, jnp.bfloat16)
            return len(C._blocks(part)) if cuts else 1
        parts = [leaves[i] for i in direct]
        if packed:
            parts.append(jax.ShapeDtypeStruct(
                (sum(int(np.prod(leaves[i].shape)) for i in packed),),
                jnp.result_type(*(leaves[i].dtype for i in packed))))
        cut = [i for i in direct if pieces(leaves[i]) > 1]
        for path, idx in (("direct", direct), ("packed", packed),
                          ("cut", cut)):
            telemetry.set_gauge("bf_optim_exchange_leaves", len(idx),
                                path=path)
            telemetry.set_gauge("bf_optim_exchange_bytes", sum(
                F._leaf_bytes(leaves[i]) for i in idx), path=path)
        telemetry.set_gauge("bf_optim_exchange_transfers",
                            sum(pieces(part) for part in parts) * wired)

    def _dispatch(self, params, grads, state, w):
        """Place the trees, launch the step program and book what it puts
        on the wire; returns ``(new_params, new_state)`` without waiting
        for the device."""
        plan = self._shard_plan(params)
        leaves, treedef = jax.tree_util.tree_flatten((params, grads))
        with op_span("optim", "place", leaves=len(leaves)):
            params, grads = jax.tree_util.tree_unflatten(
                treedef, [basics._place(x) for x in leaves])
        phase, counter = self._phase(state)
        fn, traffic = self._step_program(with_weights=w is not None,
                                         plan=plan, phase=phase)
        extra = () if w is None else (jnp.asarray(w, jnp.float32),)
        with op_span("optim", "launch", step=self._steps_seen):
            out = fn(params, grads, state, *extra)
        if counter is not None:
            self._followed = (out[1].step, counter + 1)
        # The in-flight window holds the new parameters: the next step
        # donates the state this one returned (donate=True), never these.
        basics._throttle(out[0])
        if telemetry.enabled():
            # The same counters every eager op feeds at dispatch
            # (bf_comm_*_total), from what the built program was compiled
            # over: silent steps of num_steps_per_communication count as
            # the share of a call they are, the identity combine as zero
            # bytes, sharded leaves in their own per-level series below.
            if traffic["nbytes"] is None:
                traffic["nbytes"] = float(
                    plan.rep_bytes if plan is not None and plan.any_sharded
                    else sum(x.nbytes for x in
                             jax.tree_util.tree_leaves(params)))
                self._record_exchange_paths(params, plan, traffic)
            telemetry.record_comm_traffic(
                "optimizer_step", traffic["nbytes"] * traffic["factor"],
                size=basics.size(), sched_stats=traffic["sched_stats"],
                calls=traffic["calls"])
        if self._hier_meta is not None:
            # Per-level wire accounting of the fused two-level step (the
            # compiled program never crosses Python per level).  The step
            # index must mirror the traced state.step the combiner's
            # cadence cond reads — on a checkpoint resume that does NOT
            # start at zero, so the base is read off the first step's
            # state once (one host sync, first call only) and advanced
            # host-side from there.
            if self._hier_step0 is None:
                self._hier_step0 = _read_counter(state)
            t = self._hier_step0 + self._steps_seen
            if t % self.num_steps_per_communication == 0:
                ht, inner_edges, comp, _frac = self._hier_meta
                tree_bytes = float(sum(
                    x.nbytes for x in jax.tree_util.tree_leaves(params)))
                basics._record_hier_levels(ht, t, tree_bytes,
                                           inner_edges, comp)
        if plan is not None and telemetry.enabled():
            # Per-shard wire accounting, same cadence machinery as the
            # hier path above (the fused program never crosses Python, so
            # the comm-step condition is reconstructed host-side).
            from bluefog_tpu.ops import sharded as SH
            if self._shard_step0 is None:
                self._shard_step0 = _read_counter(state)
            t = self._shard_step0 + self._steps_seen
            if t % self.num_steps_per_communication == 0:
                rep_ici, rep_dcn, grp_edges = \
                    self._shard_telemetry_meta(plan)
                SH.record_level_bytes(
                    plan, rep_ici_edges=rep_ici, rep_dcn_edges=rep_dcn,
                    grp_edges=grp_edges, compression=self.compression)
        return out

    def step(self, params, grads, state: DistOptState, *,
             self_weight: Optional[float] = None,
             src_weights=None, dst_weights=None):
        """One optimizer step; returns ``(new_params, new_state)``.

        Weight kwargs override the schedule's weights for this step only
        (traced — no recompilation when they change every iteration).
        """
        import time as _time

        from bluefog_tpu.utils import profiler
        t0 = telemetry.start_timer()
        # The host's share of the step, on the profiler's clock; its self
        # time (plan lookup, accounting) is its length less place + launch.
        with op_span("optim", "step", step=self._steps_seen):
            out = self._dispatch(params, grads, state,
                                 basics._weight_override_matrix(
                                     self_weight, src_weights, dst_weights))
            self._steps_seen += 1
            # DISPATCH wall time (async — device work keeps running); the
            # synced profile below measures true step latency.
            telemetry.observe_since(t0, "bf_optimizer_step_seconds",
                                    family="collective")
        # The host runs ONE step ahead of the device and no further: when
        # this call returns, the step before the one it launched is over.
        # The device still holds a whole step while the host prepares the
        # next, so it does not idle; a host further ahead asks the
        # allocator for a third tree of gradients while two are alive, and
        # near a chip's capacity the allocator then holds the caller for
        # two steps and lets the next through at once, step after step (one
        # v5e chip, PR 47, 687.5M parameters under AdamW: launches of 620
        # and 22 ms in turn where this gives 313 each; the device's steps
        # are the same).  The smallest leaf is ready when its program is.
        before, self._ahead = self._ahead, min(
            jax.tree_util.tree_leaves(out[0]), key=lambda x: x.size,
            default=None)
        if before is not None:
            with timed_span("optim", "wait", step=self._steps_seen - 2):
                jax.block_until_ready(before)
        pe = profiler.profile_period(self.profile_every)
        if pe and self._steps_seen % pe == 0 and t0 is not None:
            # Synced sample: the step is one fused XLA program, so phase
            # attribution inside it is impossible — what this measures is
            # the whole step's true wall time (dispatch-to-done, including
            # device work queued ahead of it) plus the straggler gather.
            t_sync = _time.perf_counter()
            jax.block_until_ready(out)
            now = _time.perf_counter()
            outer = profiler.active()
            if outer is not None:
                # An enclosing bf.step_profile() owns this step's record:
                # credit the sync wait to it and let ITS exit record the
                # (now truly synced) step and gather stragglers — once,
                # not twice.
                outer.attribute("host-sync", now - t_sync)
                outer.request_straggler()
            else:
                profiler.record_synced_step(now - t0)
        # costs_communication: this sampler adds a combine + host sync,
        # so it only runs when the consensus period was explicitly set.
        k = telemetry.consensus_every(costs_communication=True)
        if k and self._steps_seen % k == 0:
            _sample_consensus_distance(out[0])
        return out


def _sample_consensus_distance(params) -> None:
    """Record the consensus-distance gauge: per rank,
    ``||x_r - (W^T x)_r||_2`` over the flattened parameter tree, where
    ``W^T x`` is the weighted neighborhood mean the ACTIVE topology's
    gossip pulls toward — the per-step disagreement the scaling-efficiency
    claim rests on.  Rides the eager ``neighbor_allreduce`` path (so it is
    exact in multi-process runs) and costs one extra combine of the
    parameters every K steps; mean/max over ranks land in
    ``bf_consensus_distance`` / ``bf_consensus_distance_max``."""
    n = basics.size()
    leaves = [jnp.reshape(jnp.asarray(x), (n, -1)).astype(jnp.float32)
              for x in jax.tree_util.tree_leaves(params)]
    if not leaves:
        return
    flat = jnp.concatenate(leaves, axis=1)
    mean = basics.neighbor_allreduce(flat)
    dist = np.asarray(basics.to_numpy(
        jnp.linalg.norm(flat - mean, axis=1)))
    telemetry.record_consensus_distance(float(dist.mean()),
                                        float(dist.max()))


# ---------------------------------------------------------------------------
# Parity factories (reference torch/optimizers.py:1180-1554)
# ---------------------------------------------------------------------------

def DistributedGradientAllreduceOptimizer(
        base, *, num_steps_per_communication: int = 1,
        **kw) -> DistributedOptimizer:
    """Horovod-equivalent synchronous gradient averaging
    (reference ``:1376``)."""
    return DistributedOptimizer(
        base, CommunicationType.allreduce, order="gradient_allreduce",
        num_steps_per_communication=num_steps_per_communication, **kw)


def DistributedAllreduceOptimizer(
        base, *, num_steps_per_communication: int = 1,
        **kw) -> DistributedOptimizer:
    """Synchronous parameter consensus via global averaging
    (reference ``:1301``)."""
    return DistributedOptimizer(
        base, CommunicationType.allreduce, order="awc",
        num_steps_per_communication=num_steps_per_communication, **kw)


def DistributedNeighborAllreduceOptimizer(
        base, *, num_steps_per_communication: int = 1,
        use_dynamic_topology: bool = False, phases=None,
        **kw) -> DistributedOptimizer:
    """The flagship: AWC neighbor averaging over the active topology
    (reference ``:1326``)."""
    return DistributedOptimizer(
        base, CommunicationType.neighbor_allreduce, order="awc",
        num_steps_per_communication=num_steps_per_communication,
        use_dynamic_topology=use_dynamic_topology, phases=phases, **kw)


def DistributedHierarchicalNeighborAllreduceOptimizer(
        base, *, num_steps_per_communication: int = 1,
        use_dynamic_topology: bool = False, phases=None,
        **kw) -> DistributedOptimizer:
    """Machine-level neighbor averaging: local ICI allreduce fused with
    machine-graph exchange (reference ``:1352``)."""
    return DistributedOptimizer(
        base, CommunicationType.hierarchical_neighbor_allreduce, order="awc",
        num_steps_per_communication=num_steps_per_communication,
        use_dynamic_topology=use_dynamic_topology, phases=phases, **kw)


def DistributedHierarchicalGossipOptimizer(
        base, *, num_steps_per_communication: int = 1,
        order: str = "awc", **kw) -> DistributedOptimizer:
    """Two-level hierarchical gossip (``BLUEFOG_TPU_HIER``): dense
    intra-slice neighbor averaging over ICI every step, sparse one-peer
    inter-slice exchange over DCN on its own cadence with its own
    compression (``BLUEFOG_TPU_HIER_OUTER_*``) — the pod-scale
    composition of ROADMAP item 2 (HiCCL line), fused into the jitted
    step like every collective-family order."""
    return DistributedOptimizer(
        base, CommunicationType.hierarchical_gossip, order=order,
        num_steps_per_communication=num_steps_per_communication, **kw)


def DistributedAdaptWithCombineOptimizer(
        base, communication_type=CommunicationType.neighbor_allreduce,
        *, num_steps_per_communication: int = 1,
        use_dynamic_topology: bool = False, phases=None,
        **kw) -> DistributedOptimizer:
    """AWC with a chosen communication type (reference ``:1497``)."""
    return DistributedOptimizer(
        base, communication_type, order="awc",
        num_steps_per_communication=num_steps_per_communication,
        use_dynamic_topology=use_dynamic_topology, phases=phases, **kw)


def DistributedAdaptThenCombineOptimizer(
        base, communication_type=CommunicationType.neighbor_allreduce,
        *, num_steps_per_communication: int = 1,
        use_dynamic_topology: bool = False, phases=None,
        **kw) -> DistributedOptimizer:
    """ATC with a chosen communication type (reference ``:1426``)."""
    return DistributedOptimizer(
        base, communication_type, order="atc",
        num_steps_per_communication=num_steps_per_communication,
        use_dynamic_topology=use_dynamic_topology, phases=phases, **kw)
