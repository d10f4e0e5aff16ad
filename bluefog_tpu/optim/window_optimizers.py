"""Asynchronous one-sided optimizers: win_put / pull-get / push-sum.

Parity targets (reference ``torch/optimizers.py``):
  * ``_DistributedWinOptimizer`` (:844-1024) -> ``DistributedWinPutOptimizer``
    (push style) and ``DistributedPullGetOptimizer`` (pull style): named
    windows; each step pushes (or pulls) parameters along the topology's
    edges and combines via ``win_update``.
  * ``_DistributedPushSumOptimizer`` (:1026-1178) -> ``DistributedPushSumOptimizer``:
    column-stochastic ``win_accumulate`` of the parameters together with the
    push-sum weight scalar (the "associated-P" window, reference
    ``mpi_context.cc:136-156``), ``win_update_then_collect``, and de-bias
    division — converges to the network average on any strongly-connected
    digraph even though single steps are biased.

These run through the host-side window store (``bluefog_tpu.ops.window``) —
they are the *async gossip* family, deliberately outside jit: communication
overlaps compute via the store's worker pool, mirroring the reference's
nonblocking RMA + finalizer threads.  The local "adapt" math is still jitted
(vmapped over the rank axis).

Fusion: by default (``fuse=True``) the whole parameter pytree travels through
ONE window — each rank's leaves raveled into a single flat row — so a model
with hundreds of parameters issues one transport message per edge per step
instead of one per (leaf, edge).  This mirrors the collective family's
``ravel_pytree`` fusion (``optim/functional.py``) and the reference's fusion
buffer (``tensor_queue.h:70-92``); ``fuse=False`` keeps per-leaf windows (the
reference's per-parameter layout, ``torch/optimizers.py:933-944``).

Async mode (``BLUEFOG_TPU_ASYNC=1``, default off): barrier-free gossip —
the push-sum family drops its per-cadence transport fence entirely, each
rank accumulates at its own pace and every step folds only what has
arrived (associated-P corrects for in-flight mass, so the effective
operator still averages); the window layer's bounded-staleness policy
(``BLUEFOG_TPU_ASYNC_STALENESS_STEPS`` / ``_STALENESS_POLICY``) rejects
or downweights contributions older than the bound, diverting their mass
into a per-edge stale-residual store; and every
``BLUEFOG_TPU_ASYNC_COLLECT_EVERY`` steps one exact collect (fence +
residual fold) backstops the drift.  The put family steps as if
``overlap=True``; the pull family keeps its request/reply shape.  With
``=0`` nothing here changes — the lockstep path is bitwise identical.

Churn: with ``BLUEFOG_TPU_CHURN=1`` and a live gang transport, every
``step()`` drives the churn supervisor (``run/supervisor.maybe_supervisor``)
at the step boundary — failure detection, survivor re-planning and
restart-free window rebuild happen before the step's own window ops; a
committed membership change lands on ``opt.membership_change`` and an
eviction of THIS rank raises so the training loop exits cleanly.  Off
(default): one config check, the legacy path untouched.

Multi-process semantics: each process is authoritative for the ranks of its
local devices only.  ``step`` returns rank-major trees whose NON-owned rows
are frozen at their value from the previous step's input — they are never
silently installed from stale window copies (each process trains its own
ranks, exactly like the reference's one-tensor-per-process model).  Use
:meth:`gather` to materialize every rank's fresh parameters for evaluation.

Owned layout (pod scale): pass parameter trees with leading dim
``len(bf.owned_ranks())`` instead of the world size (row ``i`` = rank
``owned_ranks()[i]``) and the optimizer steps over owned rows ONLY — per-
process state is O(owned + indegree), never O(n), matching the window
layer's owned-slice storage and the reference's one-tensor-per-process
model (``torch/optimizers.py:844-1024``).  Layout is auto-detected from the
leading dim (or forced via ``layout=``); :meth:`gather` materializes the
rank-major view from either layout.
"""

from __future__ import annotations

from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np
import optax

from bluefog_tpu import basics
from bluefog_tpu.ops import window as W
from bluefog_tpu.optim.functional import DistOptState

__all__ = [
    "DistributedWinPutOptimizer",
    "DistributedPullGetOptimizer",
    "DistributedPushSumOptimizer",
]


def _leaf_names(tree, prefix: str):
    paths = jax.tree_util.tree_flatten_with_path(tree)[0]
    return [f"{prefix}.{jax.tree_util.keystr(p)}" for p, _ in paths]


class _WindowOptimizerBase:
    """Shared plumbing: fused (or per-leaf) windows + vmapped local update."""

    def __init__(self, base: optax.GradientTransformation, *,
                 window_prefix: str, num_steps_per_communication: int = 1,
                 fuse: bool = True, layout: str = "auto"):
        if layout not in ("auto", "rank", "owned"):
            raise ValueError(
                f"layout must be 'auto', 'rank' or 'owned', got {layout!r}")
        self.base = base
        self.window_prefix = window_prefix
        self.num_steps_per_communication = int(num_steps_per_communication)
        self.fuse = bool(fuse)
        self.layout = layout
        self._layout = None   # resolved at init(): "rank" or "owned"
        self._names: List[str] = None
        self._update_fn = None
        self._n = 0
        self._rows = 0        # leading dim of caller trees (n or len(owned))
        self._owned: List[int] = []
        self._shapes = None   # per-leaf (rows, *rest) shapes, fused mode
        self._dtypes = None   # per-leaf dtypes (concatenate promotes; cast back)
        self._fused_idx = None  # leaf indices the fused window carries
        self._splits = None   # np.cumsum of those leaves' flat sizes
        # Sharded-aware gossip (ops/sharded.py): subclasses that support
        # it set shard_specs/shard_groups/num_shards; init() resolves the
        # plan.  With an active plan the fused window covers REPLICATED
        # leaves only and one extra "<prefix>.sharded" window carries each
        # rank's own-shard slices, put/updated over in-group edges only.
        self.shard_specs = None
        self.shard_groups = None
        self.num_shards = None
        self._shard_plan = None       # active ops.sharded.ShardPlan
        self._sharded_name = None     # the per-group window's name
        self._shard_edges = None      # {(src, dst): w} in-group put edges
        self._shard_update_kwargs = None  # win_update weight overrides
        self._shard_leaf_idx = None   # flatten indices of sharded leaves
        self._shard_dims = None       # per sharded leaf: model dim
        self._shard_sizes = None      # per sharded leaf: slice row cols

    # -- payload layout ----------------------------------------------------
    def _payloads(self, tree) -> List:
        """Row-major arrays to ship, one per window (1 when fused).

        With the zero-copy XLA put path armed (``BLUEFOG_TPU_WIN_XLA``,
        multi-process, all-f32 trees) the payloads STAY on device: the
        fused concatenate compiles into the step's program instead of a
        host ``np.concatenate``, and each window's put hands its device
        buffer straight to the native transport — the put worker blocks
        on that payload alone, so per-window (per-leaf with
        ``fuse=False``) puts are issued as the step's compiled program
        delivers each output, overlapping the remaining bucket math,
        instead of after a whole-tree host materialization.  Bitwise
        equivalent to the host path (same f32 rows, same wire frames);
        any other configuration takes the legacy numpy path."""
        if self._device_payloads_ok(tree):
            leaves = jax.tree_util.tree_leaves(tree)
            if not self.fuse:
                return list(leaves)
            return [jnp.concatenate(
                [jnp.reshape(x, (self._rows, -1)) for x in leaves], axis=1)]
        leaves = [np.asarray(x) for x in jax.tree_util.tree_leaves(tree)]
        if not self.fuse:
            return leaves
        # Pre-init callers (tests) see every leaf in the one window.
        idxs = (self._fused_idx if self._fused_idx is not None
                else range(len(leaves)))
        out = [np.concatenate(
            [leaves[i].reshape(self._rows, -1) for i in idxs],
            axis=1)] if idxs else []
        if self._shard_plan is not None:
            out.append(self._shard_payload(leaves))
        return out

    def _shard_payload(self, leaves) -> np.ndarray:
        """The sharded window's rows: per rank, its OWN shard slice of
        every sharded leaf, raveled and concatenated (same column order
        as ``_rebuild``'s inverse scatter)."""
        from bluefog_tpu.ops import sharded as SHD
        plan = self._shard_plan
        return np.concatenate(
            [SHD.own_shard_rows(leaves[i], d, plan.coords, plan.n_shards)
             for i, d in zip(self._shard_leaf_idx, self._shard_dims)],
            axis=1)

    def _device_payloads_ok(self, tree) -> bool:
        """Can this tree ship as device payloads through the XLA put
        path?  All-f32 ``jax.Array`` leaves only — the fused device
        concatenate must not change the wire dtype a mixed tree would
        get from numpy's promotion rules."""
        if self._shard_plan is not None:
            # The sharded window's payload is a host-side per-coordinate
            # slice gather; keep every payload on the one (host) path so
            # rep/sharded rows stay a single consistent snapshot.
            return False
        if W._store.distrib is None:
            return False
        from bluefog_tpu.ops import xlaffi
        if not xlaffi.armed():
            return False
        return all(isinstance(x, jax.Array) and x.dtype == jnp.float32
                   for x in jax.tree_util.tree_leaves(tree))

    def _rebuild(self, arrays: List, like):
        """Inverse of :meth:`_payloads` — back to the pytree structure.

        With an active shard plan, ``like`` must be the ADAPTED tree:
        sharded leaves take their combined own-shard slice from the
        sharded window's rows and keep ``like``'s values everywhere else
        (the other coordinates' ghost regions).  Without a plan ``like``
        supplies the tree structure only, as before."""
        treedef = jax.tree_util.tree_structure(like)
        if self.fuse:
            leaves = [None] * len(self._shapes)
            if self._fused_idx:
                parts = np.split(np.asarray(arrays[0]), self._splits[:-1],
                                 axis=1)
                # Cast back to each leaf's own dtype: the fused
                # concatenate promoted mixed-precision trees to a common
                # wire dtype.
                for p, i in zip(parts, self._fused_idx):
                    leaves[i] = p.reshape(self._shapes[i]).astype(
                        self._dtypes[i])
            if self._shard_plan is not None:
                from bluefog_tpu.ops import sharded as SHD
                plan = self._shard_plan
                like_leaves = jax.tree_util.tree_leaves(like)
                rows = np.asarray(arrays[-1])
                off = 0
                for i, d, sz in zip(self._shard_leaf_idx,
                                    self._shard_dims, self._shard_sizes):
                    seg = rows[:, off:off + sz]
                    off += sz
                    leaves[i] = SHD.scatter_shard_rows(
                        np.asarray(like_leaves[i]), seg, d, plan.coords,
                        plan.n_shards).astype(self._dtypes[i])
        else:
            leaves = arrays
        return jax.tree_util.tree_unflatten(
            treedef, [jnp.asarray(x) for x in leaves])

    def _merge_owned(self, prev, new):
        """Freeze non-owned rows (multi-process, rank-major layout): rows of
        ranks owned by other processes keep their previous value instead of
        receiving stale window copies.  Owned layout carries owned rows
        only, so every row is authoritative — identity."""
        if W._store.distrib is None or self._layout == "owned":
            return new
        mask = np.zeros(self._n, bool)
        mask[self._owned] = True

        def one(p, q):
            m = jnp.asarray(mask.reshape((-1,) + (1,) * (jnp.ndim(q) - 1)))
            return jnp.where(m, q, p)
        return jax.tree.map(one, prev, new)

    def gather(self, params):
        """Materialize every rank's authoritative rows in RANK-MAJOR order
        (for evaluation): allgathers owned rows across processes; identity
        single-process rank-major."""
        d = W._store.distrib
        if d is None:
            return params
        from jax.experimental import multihost_utils
        owner = np.array([d.rank_owner[r] for r in range(self._n)])
        if self._layout == "rank":
            rows = np.arange(self._n)

            def one(leaf):
                g = np.asarray(multihost_utils.process_allgather(
                    np.asarray(leaf)))
                return jnp.asarray(g[owner, rows])
            return jax.tree.map(one, params)
        # Owned layout: processes may own different rank counts (non-uniform
        # --hosts placements), and process_allgather needs uniform shapes —
        # pad each process's owned rows to the max count, gather, then take
        # rank r from (owner[r], position of r in owner[r]'s owned list).
        nproc = max(owner) + 1
        owned_of = [[r for r in range(self._n) if owner[r] == p]
                    for p in range(nproc)]
        maxrows = max(len(lst) for lst in owned_of)
        pos = np.zeros(self._n, np.int64)
        for lst in owned_of:
            for i, r in enumerate(lst):
                pos[r] = i

        def one(leaf):
            x = np.asarray(leaf)
            pad = np.zeros((maxrows - x.shape[0],) + x.shape[1:], x.dtype)
            g = np.asarray(multihost_utils.process_allgather(
                np.concatenate([x, pad], axis=0)))
            return jnp.asarray(g[owner, pos])
        return jax.tree.map(one, params)

    # -- lifecycle ---------------------------------------------------------
    def init(self, params) -> DistOptState:
        basics._require_init()
        self._n = basics.size()
        self._owned = W._owned_ranks(self._n)
        # Barrier-free async mode (BLUEFOG_TPU_ASYNC): arm the window
        # layer's bounded-staleness fold and this family's fence-free
        # stepping.  Off (default): one config check, the flag stays
        # False and every path below is bit-identical to the lockstep
        # tree.
        self._async_on = W.configure_async()
        leaves = [np.asarray(x) for x in jax.tree_util.tree_leaves(params)]
        rows = leaves[0].shape[0]
        if any(x.shape[0] != rows for x in leaves):
            raise ValueError(
                "window optimizer trees must share one leading (row) dim; "
                f"got {[x.shape[0] for x in leaves]}")
        if self.layout == "auto":
            if rows == self._n:
                self._layout = "rank"
            elif (W._store.distrib is not None
                  and rows == len(self._owned)):
                self._layout = "owned"
            else:
                raise ValueError(
                    f"{type(self).__name__}.init: leading dim {rows} is "
                    f"neither the world size ({self._n}, rank-major) nor "
                    f"this process's owned-rank count ({len(self._owned)}, "
                    "owned layout)")
        else:
            self._layout = self.layout
            want = self._n if self._layout == "rank" else len(self._owned)
            if rows != want:
                raise ValueError(
                    f"{type(self).__name__}.init: layout={self._layout!r} "
                    f"expects leading dim {want}, got {rows}")
        self._rows = rows
        self._resolve_shard_plan(params, leaves)
        plan = self._shard_plan
        if self.fuse:
            self._shapes = [x.shape for x in leaves]
            self._dtypes = [x.dtype for x in leaves]
            sizes = [int(np.prod(s[1:])) for s in self._shapes]
            self._fused_idx = (list(range(len(leaves))) if plan is None else
                               [i for i, m in enumerate(plan.mask) if not m])
            self._splits = np.cumsum([sizes[i] for i in self._fused_idx])
            self._names = ([f"{self.window_prefix}.fused"]
                           if self._fused_idx else [])
            if plan is not None:
                self._sharded_name = f"{self.window_prefix}.sharded"
                self._names.append(self._sharded_name)
        else:
            self._names = _leaf_names(params, self.window_prefix)
        # Owned-layout creation tensors carry no neighbor rows, so the
        # window layer cannot seed staging from them (it requires
        # zero_init).  Restore the rank layout's seeded-staging semantics
        # with one explicit identity put below instead.
        zero = self._zero_init or self._layout == "owned"
        for name, payload in zip(self._names, self._payloads(params)):
            W.win_create(payload, name, zero_init=zero)
        if self._layout == "owned" and not self._zero_init:
            for name, payload in zip(self._names, self._payloads(params)):
                W.win_put(payload, name)
            # All seeds applied everywhere before the first step's
            # win_update — otherwise it would combine zeros for edges
            # whose seed is still in flight (transient pull toward 0).
            W.win_fence()
        base = self.base

        def init_one(p):
            return base.init(p)
        st = jax.jit(jax.vmap(init_one))(jax.tree.map(jnp.asarray, params))
        self._update_fn = jax.jit(jax.vmap(
            lambda g, s, p: base.update(g, s, p)))
        return DistOptState(st, jnp.asarray(0, jnp.int32))

    def _resolve_shard_plan(self, params, leaves) -> None:
        """Arm sharded-aware gossip when shard specs were supplied, the
        knob is on, and some leaf is actually sharded; otherwise leave
        every structure ``None`` — the verbatim legacy layout."""
        self._shard_plan = None
        self._sharded_name = None
        if self.shard_specs is None:
            return
        from bluefog_tpu.utils import config as _config
        if not _config.get().sharded_gossip:
            return
        from bluefog_tpu.ops import sharded as SHD
        plan = SHD.build_plan(params, self.shard_specs, n=self._n,
                              n_shards=self.num_shards,
                              groups=self.shard_groups)
        if not plan.any_sharded:
            return
        if self._layout != "rank":
            raise ValueError(
                f"{type(self).__name__}: shard_specs requires the "
                "rank-major layout (the sharded window's per-coordinate "
                "rows are rank-indexed); owned layout is not supported")
        if not self.fuse:
            raise ValueError(
                f"{type(self).__name__}: shard_specs requires fuse=True "
                "(the sharded slices ride one dedicated fused window)")
        self._shard_plan = plan
        self._shard_leaf_idx = [i for i, m in enumerate(plan.mask) if m]
        self._shard_dims = [plan.dims[i] for i in self._shard_leaf_idx]
        self._shard_sizes = [
            int(np.prod(leaves[i].shape[1:])) // plan.n_shards
            for i in self._shard_leaf_idx]
        put_edges, self_w, nbr_w = SHD.induced_window_weights(
            plan, basics.load_topology())
        self._shard_edges = put_edges
        self._shard_update_kwargs = {
            "self_weight": self_w, "neighbor_weights": nbr_w}

    def _local_adapt(self, params, grads, state: DistOptState):
        updates, base_state = self._update_fn(grads, state.base, params)
        new_params = jax.tree.map(lambda p, u: p + u, params, updates)
        return new_params, base_state

    # Latest committed membership change observed by _maybe_churn_step
    # (None until the gang churns); `evicted` mirrors the supervisor's
    # verdict for THIS rank.
    membership_change = None
    evicted = False

    def _maybe_churn_step(self, t: int) -> None:
        """Drive the churn supervisor at this step boundary
        (``BLUEFOG_TPU_CHURN=1`` + a live multi-process transport;
        otherwise a no-op after one cheap config check).  The PR 7
        follow-up: training loops no longer have to step the supervisor
        manually — every window-family ``step()`` feeds it, so failure
        detection, survivor re-planning and restart-free window rebuild
        happen before this step's window ops run.  A committed change
        lands in :attr:`membership_change`; if THIS rank was voted out,
        :attr:`evicted` flips and a RuntimeError tells the loop to exit
        (gossiping on as a ghost would wedge the survivors' fences).

        Defers to a MANUALLY-constructed supervisor: when a live
        controller exists that the process-wide singleton does not own
        (chaos harness, custom loops calling ``ChurnSupervisor()``
        directly), its owner is already stepping it — spawning a second
        supervisor here would double-heartbeat and race recoveries."""
        from bluefog_tpu.run import supervisor as sup_mod
        from bluefog_tpu.utils import config as _config
        if not _config.get().churn:
            return
        from bluefog_tpu.ops import membership
        cur = membership.current()
        if cur is not None and (sup_mod._singleton is None
                                or sup_mod._singleton.ctrl is not cur):
            return
        sup = sup_mod.maybe_supervisor()
        if sup is None:
            return
        view = sup.step(t)
        if view is None:
            return
        self.membership_change = view
        if view.evicted:
            self.evicted = True
            raise RuntimeError(
                f"{type(self).__name__}.step: this rank was evicted by "
                f"membership consensus (epoch {view.epoch}); exit the "
                "training loop — the survivors have re-planned without it")

    _async_on = False

    def _async_step_begin(self, t: int) -> None:
        """Async-mode step bookkeeping: publish my step clock (staleness
        ages count against it; both trace-tag encoders stamp it as the
        wire origin step) and the ``bf_async_step_lag{rank}`` gauge — my
        step vs the freshest peer step seen through sampled tags.
        No-op outside async mode."""
        if not self._async_on:
            return
        W.set_async_step(t)
        from bluefog_tpu.utils import telemetry
        telemetry.set_gauge("bf_async_step_lag", float(W.async_step_lag()),
                            rank=str(basics.rank()))

    def _async_collect_due(self, t: int) -> bool:
        """True when this async step is the periodic exact-collect
        backstop (``BLUEFOG_TPU_ASYNC_COLLECT_EVERY``): fence the
        transport, fold the stale residuals back in, collect exactly —
        bounding both the parameter drift and the step lag a straggler
        can accumulate (fast ranks wait here, and only here)."""
        if not self._async_on or W._store.distrib is None:
            return False
        from bluefog_tpu.utils import config as _config
        every = _config.get().async_collect_every
        return every > 0 and (t + 1) % every == 0

    @staticmethod
    def _step_timer():
        from bluefog_tpu.utils import telemetry
        return telemetry.start_timer()

    def _record_step_time(self, t0, t: int) -> None:
        """Step-latency histogram for the async family (the host-side step
        IS the true wall time — window ops complete before return), plus
        the periodic cross-rank straggler gather
        (``BLUEFOG_TPU_PROFILE`` / ``BLUEFOG_TPU_PROFILE_EVERY``).  The
        gather is collective; every process runs the same step loop, so
        the periods line up — same contract as the consensus sampler."""
        from bluefog_tpu.utils import profiler, telemetry
        dt = telemetry.observe_since(t0, "bf_optimizer_step_seconds",
                                     family="window")
        if dt is None:
            return
        pe = profiler.profile_period()
        if pe and (t + 1) % pe == 0:
            outer = profiler.active()
            if outer is not None:
                # An enclosing bf.step_profile() records this step itself;
                # just make sure exactly one straggler gather happens.
                outer.request_straggler()
            else:
                profiler.record_synced_step(dt)

    def _maybe_sample_consensus(self, t: int, payloads, combined) -> None:
        """Consensus-distance gauge for the async family: every K steps
        (``BLUEFOG_TPU_TELEMETRY_CONSENSUS_EVERY``) record, per owned rank,
        the L2 distance between the locally adapted parameters (``payloads``,
        pre-combine) and the ``win_update`` result (``combined``, the
        weighted neighborhood mean) — the same gossip-health signal the
        collective family samples, read off the combine this step already
        performed (zero extra communication)."""
        from bluefog_tpu.utils import telemetry
        k = telemetry.consensus_every()
        if not k or (t + 1) % k:
            return
        sq = None
        for pre, post in zip(payloads, combined):
            diff = (np.asarray(pre, np.float32)
                    - np.asarray(post, np.float32))
            diff = diff.reshape(diff.shape[0], -1)
            s = np.einsum("ij,ij->i", diff, diff)
            sq = s if sq is None else sq + s
        dist = np.sqrt(sq)
        if self._layout == "rank" and W._store.distrib is not None:
            dist = dist[self._owned]  # non-owned rows are zero-filled
        telemetry.record_consensus_distance(float(dist.mean()),
                                            float(dist.max()))

    def free(self):
        # Flush the transport's send queues first: a coalesced edge payload
        # still lingering in a per-peer queue when its window dies here
        # would land at the peer as gossip for a window we no longer track.
        # Best-effort — teardown must complete even when a peer is dead,
        # and promptly even when one is wedged (the legacy free()
        # succeeded locally regardless of peers), hence the short timeout.
        try:
            W.win_flush(timeout=5.0)
        except Exception:  # noqa: BLE001 — never abort cleanup
            from bluefog_tpu.utils.logging import get_logger
            get_logger().warning(
                "window optimizer free(): transport flush failed "
                "(dead peer?); continuing teardown", exc_info=True)
        for name in self._names or []:
            W.win_free(name)
        self._names = None

    def _quiesce(self) -> None:
        """Complete every in-flight window op this optimizer issued (and,
        multi-process, fence the transport) so a snapshot cannot miss
        queued or in-flight gossip mass."""
        if W._store.distrib is not None:
            # Flush-before-fence: queued coalesced sends reach TCP first,
            # so the fence's acks certify THEM applied too (the fence also
            # flushes internally — this surfaces send errors at the
            # snapshot call site instead of inside the fence wait).
            W.win_flush()
            W.win_fence()

    def _require_windows(self, what: str):
        if not self._names:
            raise RuntimeError(
                f"{type(self).__name__}.{what}: no windows exist — call "
                "init() first (and not after free()); a silent empty "
                "snapshot would lose all gossip state")
        return self._names

    def window_state_dict(self):
        """Snapshot every window this optimizer owns (checkpoint-ready
        numpy tree keyed by window name; pair with
        :meth:`load_window_state_dict` after re-``init`` on restart so
        in-staging gossip mass survives elastic restarts).  Quiesces
        in-flight ops first — overlapped puts and transport-in-flight
        mass land before the snapshot.

        Multi-process: COLLECTIVE — the quiesce fences the transport
        (``win_fence`` ends in a barrier), so every process must call
        this (and :meth:`load_window_state_dict`) together, like the
        reference's collective window ops."""
        names = self._require_windows("window_state_dict")
        self._quiesce()
        return {name: W.win_state_dict(name) for name in names}

    def load_window_state_dict(self, state) -> None:
        names = set(self._require_windows("load_window_state_dict"))
        self._quiesce()  # an in-flight put landing after the restore
        #                  would corrupt the just-restored state
        snap = dict(state)
        if set(snap) != names:
            raise ValueError(
                f"{type(self).__name__}.load_window_state_dict: snapshot "
                f"windows {sorted(snap)} do not match this optimizer's "
                f"{sorted(names)} — was the snapshot taken with a "
                "different fuse= setting or window_prefix?")
        for name, s in snap.items():
            W.win_load_state_dict(name, s)

    _zero_init = False


class DistributedWinPutOptimizer(_WindowOptimizerBase):
    """Push-style async optimizer: adapt locally, ``win_put`` the new
    parameters to out-neighbors, combine received neighbor state via
    ``win_update`` (reference factory ``torch/optimizers.py:1271``).

    ``step(..., dst_weights=...)`` takes the same weight forms as
    ``bf.win_put`` and is re-resolvable every call (dynamic topologies).

    ``overlap=True`` makes the put genuinely asynchronous: ``step`` issues
    the nonblocking put and returns WITHOUT waiting — the put executes on
    the worker pool while the caller computes the next forward/backward,
    and the next step's ``win_update`` combines whatever has arrived (one
    extra step of staleness, the reference's actual async operating mode:
    its win optimizers overlapped RMA with compute via hooks,
    ``torch/optimizers.py:889-909``).  The previous put is always waited
    before the next one is issued, so per-window ordering holds even with
    a multi-worker pool.

    Note that in overlap mode the rank's OWN row lags too, not just the
    neighbors': a put self-publishes the adapted parameters into the local
    window, so when step ``t+1``'s ``win_update`` runs before step ``t``'s
    put has landed, the combine is taken over step ``t-1``'s published
    self value — step ``t``'s local adapt result reaches the combined
    state one step late, same as its neighbors see it."""

    def __init__(self, base, *, window_prefix: str = "winput",
                 num_steps_per_communication: int = 1, fuse: bool = True,
                 overlap: bool = False, layout: str = "auto",
                 shard_specs=None, shard_groups=None, num_shards=None):
        super().__init__(base, window_prefix=window_prefix,
                         num_steps_per_communication=num_steps_per_communication,
                         fuse=fuse, layout=layout)
        self.overlap = bool(overlap)
        # Sharded-aware gossip (ops/sharded.py, same contract as the
        # collective family's DistributedOptimizer kwargs): sharded
        # leaves ride a dedicated window whose puts and update weights
        # are restricted to in-replica-group edges.
        self.shard_specs = shard_specs
        self.shard_groups = shard_groups
        self.num_shards = None if num_shards is None else int(num_shards)
        self._pending: List[int] = []

    def step(self, params, grads, state: DistOptState, *,
             dst_weights=None, require_mutex: bool = True):
        t0 = self._step_timer()
        self._maybe_churn_step(int(state.step))
        self._async_step_begin(int(state.step))
        t = int(state.step)
        comm = (t + 1) % self.num_steps_per_communication == 0
        new_params, base_state = self._local_adapt(params, grads, state)
        if comm:
            # Ordering: the previous overlapped put must complete before a
            # new one targets the same window.
            self._drain_pending()
            payloads = self._payloads(new_params)
            handles = [
                W.win_put_nonblocking(
                    payload, name,
                    # The sharded window's puts cross in-group edges
                    # only — its slices must never leave the replica
                    # group that shares their coordinate.
                    dst_weights=(self._shard_edges
                                 if name == self._sharded_name
                                 else dst_weights),
                    require_mutex=require_mutex)
                for name, payload in zip(self._names, payloads)]
            # Async mode implies overlap: the put must not block the
            # step on a slow peer's wire — the next step's win_update
            # combines whatever has arrived (the put family's natural
            # barrier-free operating mode; the staleness policy and the
            # residual store are push-sum/accumulate concepts and do not
            # apply to overwrite puts).
            if self.overlap or self._async_on:
                # Overlapped puts flush themselves when their worker-pool
                # job finishes; kick the transport NOW (non-blocking — the
                # per-peer senders flush on their own threads) so gossip
                # already enqueued rides the wire during the next
                # forward/backward instead of waiting out the linger.
                W.win_flush(wait=False)
                self._pending = handles
            else:
                for h in handles:
                    W.win_wait(h)
            combined = [
                W.win_update(name, require_mutex=require_mutex,
                             # Explicit partial weights: out-of-group
                             # staging (if any ever landed) stays pending
                             # and never leaks into the sharded average.
                             **(self._shard_update_kwargs
                                if name == self._sharded_name else {}))
                for name in self._names]
            self._maybe_sample_consensus(t, payloads, combined)
            new_params = self._rebuild(combined, new_params)
        out = (self._merge_owned(params, new_params),
               DistOptState(base_state, state.step + 1))
        self._record_step_time(t0, t)
        return out

    def _drain_pending(self) -> None:
        for h in self._pending:   # overlapped puts must land first
            W.win_wait(h)
        self._pending = []

    def free(self):
        self._drain_pending()
        super().free()

    def _quiesce(self) -> None:
        self._drain_pending()
        super()._quiesce()


class DistributedPullGetOptimizer(_WindowOptimizerBase):
    """Pull-style async optimizer: adapt locally, publish self memory, then
    ``win_get`` neighbors' parameters and combine (reference factory
    ``torch/optimizers.py:1225``)."""

    def __init__(self, base, *, window_prefix: str = "pullget",
                 num_steps_per_communication: int = 1, fuse: bool = True,
                 layout: str = "auto"):
        super().__init__(base, window_prefix=window_prefix,
                         num_steps_per_communication=num_steps_per_communication,
                         fuse=fuse, layout=layout)

    def step(self, params, grads, state: DistOptState, *,
             src_weights=None, require_mutex: bool = True):
        t0 = self._step_timer()
        self._maybe_churn_step(int(state.step))
        # Pull-style steps stay request/reply (a get cannot fold "whatever
        # arrived" — it asks NOW), but the step clock + lag gauge still
        # publish so a pull gang's telemetry shows who runs ahead.
        self._async_step_begin(int(state.step))
        new_params, base_state = self._local_adapt(params, grads, state)
        t = int(state.step)
        if (t + 1) % self.num_steps_per_communication == 0:
            payloads = self._payloads(new_params)
            # Publish my new parameters as the window's exposed memory (the
            # dst_weights={} put touches no edges — it only refreshes main).
            publish = [W.win_put_nonblocking(payload, name,
                                             self_weight=1.0, dst_weights={})
                       for name, payload in zip(self._names, payloads)]
            for h in publish:
                W.win_wait(h)
            handles = [W.win_get_nonblocking(name, src_weights=src_weights,
                                             require_mutex=require_mutex)
                       for name in self._names]
            for h in handles:
                W.win_wait(h)
            combined = [W.win_update(name, require_mutex=require_mutex)
                        for name in self._names]
            self._maybe_sample_consensus(t, payloads, combined)
            new_params = self._rebuild(combined, params)
        out = (self._merge_owned(params, new_params),
               DistOptState(base_state, state.step + 1))
        self._record_step_time(t0, t)
        return out


class DistributedPushSumOptimizer(_WindowOptimizerBase):
    """Async push-sum gossip SGD (reference factory
    ``torch/optimizers.py:1180``).

    Every step: local adapt, column-stochastic ``win_accumulate`` of the raw
    parameters (each rank splits weight ``1/(outdeg+1)`` over itself and its
    out-neighbors), ``win_update_then_collect``, and the associated-P scalar
    tracks the accumulated weight so ``debias`` recovers unbiased iterates.
    Gradients should be evaluated at ``debias(params)``.
    """

    _zero_init = True

    def __init__(self, base, *, window_prefix: str = "pushsum",
                 num_steps_per_communication: int = 1, fuse: bool = True,
                 layout: str = "auto", auto_collect_rounds: int = 8):
        super().__init__(base, window_prefix=window_prefix,
                         num_steps_per_communication=num_steps_per_communication,
                         fuse=fuse, layout=layout)
        self.auto_collect_rounds = int(auto_collect_rounds)

    def init(self, params) -> DistOptState:
        W.turn_on_win_ops_with_associated_p()
        return super().init(params)

    def _outgoing_weights(self) -> Dict[int, float]:
        topo = basics.load_topology()
        n = basics.size()
        from bluefog_tpu import topology as topology_util
        w = {}
        for r in range(n):
            outs = topology_util.out_neighbor_ranks(topo, r)
            share = 1.0 / (len(outs) + 1.0)
            for o in outs:
                w[(r, o)] = share
        return w

    def _self_share(self) -> np.ndarray:
        topo = basics.load_topology()
        n = basics.size()
        from bluefog_tpu import topology as topology_util
        return np.array([
            1.0 / (len(topology_util.out_neighbor_ranks(topo, r)) + 1.0)
            for r in range(n)])

    def step(self, params, grads, state: DistOptState, *,
             dst_weights=None, require_mutex: bool = True):
        t0 = self._step_timer()
        self._maybe_churn_step(int(state.step))
        self._async_step_begin(int(state.step))
        if dst_weights is None:
            dst_weights = self._outgoing_weights()
        self_share = self._self_share()
        t = int(state.step)
        new_params, base_state = self._local_adapt(params, grads, state)
        # Flow control, lockstep mode: every ``auto_collect_rounds``
        # communication rounds the step fences the transport before
        # folding — no process can run more than that many rounds ahead of
        # a stalled peer (the fence is a barrier), so the fraction of a
        # rank's P mass that can ever be in flight is bounded and de-bias
        # stays well-conditioned WITHOUT caller-side periodic collect().
        # The reference gets the analogous bound for free from MPI's
        # passive-target progress/ordering (``mpi_controller.cc:953-1034``);
        # a TCP transport must make it explicit.  The fence is collective —
        # every process calls step the same number of times (the SPMD
        # training loop), so the fences line up.  auto_collect_rounds=0
        # disables.
        #
        # Async mode (BLUEFOG_TPU_ASYNC=1) replaces this coupling
        # entirely: NO per-cadence fence — ranks accumulate at their own
        # pace, the fold takes whatever has arrived (push-sum associated-P
        # corrects for in-flight mass), the bounded-staleness policy
        # rejects/downweights over-age contributions into the stale-
        # residual store, and the only barrier left is the periodic exact
        # collect (``BLUEFOG_TPU_ASYNC_COLLECT_EVERY``) that folds those
        # residuals back in — a straggler costs its contributions'
        # freshness, not the fleet's throughput.
        fence_now = (not self._async_on
                     and self.auto_collect_rounds > 0
                     and W._store.distrib is not None
                     and (t + 1) % self.auto_collect_rounds == 0)
        backstop_now = self._async_collect_due(t)
        handles = []
        payloads = self._payloads(new_params)
        for name, payload in zip(self._names, payloads):
            # win_accumulate applies self_weight AFTER the edge sends, so the
            # out-edges carry w * p_old and per-source mass
            # (self_share + sum_out w == 1) is conserved — the push-sum
            # column-stochastic invariant.
            handles.append(W.win_accumulate_nonblocking(
                payload, name, self_weight=self_share,
                dst_weights=dst_weights, require_mutex=require_mutex))
        for h in handles:
            W.win_wait(h)
        if fence_now or backstop_now:
            W.win_fence()
            if backstop_now:
                # Post-fence nothing is in flight: folding the stale
                # residuals here and collecting restores EXACT push-sum
                # conservation, including every contribution the
                # staleness policy held back since the last backstop.
                for name in self._names:
                    W.win_fold_stale_residuals(name)
        collected = [W.win_update_then_collect(name,
                                               require_mutex=require_mutex)
                     for name in self._names]
        self._maybe_sample_consensus(t, payloads, collected)
        new_params = self._rebuild(collected, params)
        out = (self._merge_owned(params, new_params),
               DistOptState(base_state, state.step + 1))
        self._record_step_time(t0, t)
        return out

    def collect(self, params, *, require_mutex: bool = True):
        """Fold ALL in-flight gossip into the iterates (evaluation-time
        collect, the reference's end-of-run ``win_update_then_collect``
        usage, ``torch/mpi_ops.py:1206-1260``).

        The async step issues accumulates without a fence — at any instant a
        chunk of the network's value/P mass rides the transport, so an
        instantaneous de-bias snapshot is noisy (a rank whose mass is mostly
        in flight has tiny P and a wild ratio).  ``win_fence`` (which acks
        every peer's applied sends and ends in a barrier) guarantees no
        mass is in flight; the collect then restores exact conservation:
        gathered P sums to ``n`` and the P-weighted average equals the true
        network average."""
        W.win_fence()
        # Async mode: the bounded-staleness policy may be holding
        # rejected/downweighted mass in the stale-residual store — fold
        # it back in post-fence so THIS collect is exact too (no-op with
        # empty stores, i.e. always outside async mode).
        for name in self._names:
            W.win_fold_stale_residuals(name)
        collected = [W.win_update_then_collect(name,
                                               require_mutex=require_mutex)
                     for name in self._names]
        return self._merge_owned(params, self._rebuild(collected, params))

    def associated_p(self) -> np.ndarray:
        """(n,) push-sum weight vector (identical across leaves/windows)."""
        return W.win_associated_p(self._names[0])

    def debias(self, params, *, p_min: float = 1e-3):
        """Divide each rank's slice by its associated-P scalar.

        ``p_min`` floors the divisor: under heavy scheduling skew a rank's
        P mass can be almost entirely in flight (P → 0), and dividing by it
        turns one delayed packet into inf/NaN iterates.  The floor keeps
        the estimate finite (it is inaccurate exactly when most of the
        rank's information is in flight — bound the staleness with a
        periodic :meth:`collect` for an exact de-bias).  Push-sum theory
        assumes bounded delays, under which P stays bounded away from 0
        and the floor never engages; when it DOES engage, a warning is
        logged (the clipped estimate is finite but biased — monitoring
        that watched for inf/NaN would otherwise miss it)."""
        raw = np.asarray(self.associated_p())
        row_rank = np.arange(raw.shape[0])  # row index -> global rank
        if self._layout == "owned":
            # Owned-layout trees carry owned rows only; pick their P slots
            # (associated_p is always global-rank indexed).
            row_rank = np.asarray(self._owned, dtype=np.int64)
            raw = raw[row_rank]
        p = np.maximum(raw, p_min)
        clipped = np.nonzero(raw < p_min)[0]
        if clipped.size:
            from bluefog_tpu.utils.logging import get_logger
            get_logger().warning(
                "push-sum debias: associated-P below p_min=%g for rank(s) "
                "%s — most of their mass is in flight; the de-biased "
                "estimate is clipped (finite but biased). Bound the "
                "staleness with opt.collect().", p_min,
                row_rank[clipped].tolist())

        def div(leaf):
            shape = (-1,) + (1,) * (np.ndim(leaf) - 1)
            return leaf / jnp.asarray(p.reshape(shape), dtype=leaf.dtype)
        return jax.tree.map(div, params)
