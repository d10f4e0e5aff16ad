"""Per-rank distributed-optimizer step functions (the functional core).

Every function here is pure and designed to run *inside* ``jax.shard_map`` /
``pjit`` over the rank mesh axis, so the whole training step — forward,
backward, base-optimizer math and the decentralized communication — is one XLA
program per device.  This replaces the reference's hook machinery
(``torch/optimizers.py``): where BlueFog splices communication into torch
autograd via forward/backward hooks and synchronizes handles in ``step()``,
here the communication is just another op in the traced step.

Execution orders (reference ``torch/optimizers.py:311-320`` theory note):
  AWC (adapt-with-combine, ``_DistributedReduceOptimizer:297-483``):
      ``x_{t+1} = combine(x_t) + base_update(g_t)``
  ATC (adapt-then-combine, ``_DistributedAdaptThenCombineOptimizer:485-842``):
      ``x_{t+1} = combine(x_t + base_update(g_t))``
  gradient allreduce (``_DistributedOptimizer:166-295``):
      ``x_{t+1} = x_t + base_update(allreduce(g_t))``

``combine`` is any of: global allreduce-average (consensus), static/dynamic
neighbor averaging, hierarchical machine-level averaging, or identity
("empty").  Local aggregation — communicate only every J-th step
(``optimizers.py:348-350``) — is a ``lax.cond`` on the traced step counter, so
one compiled program serves both communicating and silent steps.
"""

from __future__ import annotations

import enum
from functools import partial
from typing import Callable, NamedTuple, Optional, Union

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax import lax

from bluefog_tpu.ops import collective as C
from bluefog_tpu.ops.schedule import DynamicSchedule, StaticSchedule
from bluefog_tpu.utils import timeline

__all__ = [
    "CommunicationType",
    "DistOptState",
    "Combiner",
    "make_combiner",
    "make_shard_combiner",
    "compress_combiner",
    "awc_step",
    "atc_step",
    "gradient_allreduce_step",
]


class CommunicationType(enum.Enum):
    """Parity: reference ``torch/optimizers.py:28-34`` (plus the TPU-only
    two-level gossip of ``BLUEFOG_TPU_HIER``)."""
    allreduce = "allreduce"
    neighbor_allreduce = "neighbor.allreduce"
    hierarchical_neighbor_allreduce = "hierarchical.neighbor.allreduce"
    hierarchical_gossip = "hierarchical.gossip"
    empty = "empty"


class DistOptState(NamedTuple):
    base: optax.OptState
    step: jnp.ndarray            # int32 scalar, counts optimizer steps
    acc: Optional[object] = None  # grad accumulator (gradient_allreduce, J>1)


class Combiner(NamedTuple):
    """What :func:`make_combiner` returns, and all a caller may know of it.

    ``combine(parts, step, weights)`` takes the list of arrays one exchange
    moves (:func:`_fused_apply`: the large leaves, then the packed buffer)
    and returns a list of the same shapes and dtypes, in the order given.
    The neighbor combiners hand the list whole to ``ops.collective``, which
    alone decides the order on the wire (smallest first), cuts a part over
    its block size into blocks and chains each piece's add behind its own
    arrival (``collective._apply_rounds``); allreduce and the hierarchical
    types work part by part (:func:`_per_part`).  ``step`` is the
    traced step counter (dynamic schedules choose their phase by it) and
    ``weights`` an optional traced (n, n) matrix overriding the schedule's
    weights (None: the weights baked in).  ``identity``: it exchanges
    nothing.  ``replica_identical``: every rank receives the same result
    (global allreduce), so a codec adds no per-rank residual back.
    ``sched``, ``axis_name``: the compiled edge schedule (static or dynamic)
    of a flat neighbor combiner and its mesh axis, which ``sparse:<frac>``
    rides; None for every other communication type."""
    combine: Callable
    identity: bool = False
    replica_identical: bool = False
    sched: Optional[object] = None
    axis_name: Optional[str] = None


def _per_part(one):
    """``combine`` of a combiner that works array by array."""
    return lambda parts, step, weights: [one(x, step, weights) for x in parts]


def make_combiner(
        comm: CommunicationType,
        *,
        axis_name: str,
        sched: Union[StaticSchedule, DynamicSchedule, None] = None,
        local_axis: Optional[str] = None,
        machine_axis: Optional[str] = None,
        hier: Optional[dict] = None,
) -> Combiner:
    """Build the :class:`Combiner` of a communication type; ``sched`` is
    the compiled schedule of the neighbor types, static or dynamic."""
    def _no_weights(weights, what):
        if weights is not None:
            raise ValueError(
                f"per-step weight overrides are not supported for {what}; "
                "they apply to (dynamic) neighbor_allreduce only")

    if comm == CommunicationType.empty:
        def _empty(parts, step, weights):
            _no_weights(weights, "CommunicationType.empty")
            return parts
        return Combiner(_empty, identity=True)
    if comm == CommunicationType.allreduce:
        def _ar(x, step, weights):
            _no_weights(weights, "CommunicationType.allreduce")
            return C.allreduce(x, axis_name, average=True)
        return Combiner(_per_part(_ar), replica_identical=True)
    if comm == CommunicationType.neighbor_allreduce:
        if isinstance(sched, DynamicSchedule):
            # It chooses its phase once, and one lax.switch serves every
            # part: each branch is one pipeline over the list.
            def _dyn(parts, step, weights):
                if weights is None:
                    return C.dynamic_neighbor_allreduce(
                        parts, step, sched, axis_name)
                # Weight override on a dynamic topology: same phase switching,
                # weights looked up from the traced matrix per active edge.
                branches = [
                    partial(lambda ph, args: C.neighbor_allreduce_matrix(
                        args[0], args[1], ph, axis_name), ph)
                    for ph in sched.phases]
                return lax.switch(step % sched.period, branches,
                                  (parts, weights))
            return Combiner(_dyn, sched=sched, axis_name=axis_name)
        assert sched is not None, "static neighbor_allreduce needs a schedule"

        def _nbr(parts, step, weights):
            if weights is None:
                return C.neighbor_allreduce(parts, sched, axis_name)
            return C.neighbor_allreduce_matrix(parts, weights, sched,
                                               axis_name)
        return Combiner(_nbr, sched=sched, axis_name=axis_name)
    if comm == CommunicationType.hierarchical_gossip:
        assert local_axis and machine_axis, \
            "hierarchical gossip needs local/machine axis names"
        assert hier is not None, \
            "hierarchical gossip needs the compiled level bundle (hier=)"

        def _hgossip(x, step, weights):
            _no_weights(weights, "hierarchical_gossip")
            return C.hierarchical_gossip(
                x, step, hier["inner_sched"], hier["outer_scheds"],
                local_axis=local_axis, machine_axis=machine_axis,
                outer_every=hier.get("outer_every", 1),
                outer_compression=hier.get("outer_compression", "none"),
                outer_frac=hier.get("outer_frac"))
        return Combiner(_per_part(_hgossip))
    if comm == CommunicationType.hierarchical_neighbor_allreduce:
        assert local_axis and machine_axis, \
            "hierarchical combine needs local/machine axis names"
        if isinstance(sched, DynamicSchedule):
            def _hdyn(x, step, weights):
                _no_weights(weights, "hierarchical_neighbor_allreduce")
                return C.dynamic_hierarchical_neighbor_allreduce(
                    x, step, sched, local_axis, machine_axis)
            return Combiner(_per_part(_hdyn))
        assert sched is not None

        def _hier(x, step, weights):
            _no_weights(weights, "hierarchical_neighbor_allreduce")
            return C.hierarchical_neighbor_allreduce(
                x, sched, local_axis, machine_axis)
        return Combiner(_per_part(_hier))
    raise ValueError(f"unknown communication type {comm}")


def make_shard_combiner(plan, group_combine, *, axis_name: str):
    """Per-replica-group combiner for the sharded leaves of a plan.

    ``plan`` is an :class:`ops.sharded.ShardPlan`; ``group_combine`` is a
    :class:`Combiner` (``make_combiner`` output, optionally wrapped by
    ``compress_combiner``) built over the plan's *merged group schedule*
    — its in-group-only edges are what keeps sharded bytes off the DCN.

    The returned callable runs inside ``shard_map`` on the sharded
    sub-list of leaves (flatten order): each rank slices its *own* shard
    chunk along the leaf's sharded model dim, ravels the slices into one
    buffer, gossips it over the group schedule, and writes the combined
    slice back — the other coordinates' ghost values stay untouched, so
    ranks never average slices they don't own."""
    from jax.flatten_util import ravel_pytree
    coords = jnp.asarray(plan.coords, jnp.int32)
    sh_dims = tuple(d for m, d in zip(plan.mask, plan.dims) if m)

    def shard_combine(leaves, step=None):
        # Runs on the per-rank block (rank-major leading dim already
        # stripped by shard_map), so the sharded model dim d IS array
        # axis d here — the host-side +1 offset applies only to the
        # rank-major tree the plan was built from.
        coord = coords[lax.axis_index(axis_name)]
        slices = []
        for leaf, d in zip(leaves, sh_dims):
            chunk = leaf.shape[d] // plan.n_shards
            slices.append(lax.dynamic_slice_in_dim(
                leaf, coord * chunk, chunk, axis=d))
        flat, unravel = ravel_pytree(slices)
        combined = unravel(group_combine.combine([flat], step, None)[0])
        out = []
        for leaf, d, sl in zip(leaves, sh_dims, combined):
            chunk = leaf.shape[d] // plan.n_shards
            out.append(lax.dynamic_update_slice_in_dim(
                leaf, sl.astype(leaf.dtype), coord * chunk, axis=d))
        return out
    return shard_combine


# A leaf of at least this many bytes is exchanged as it is; smaller leaves
# are packed.  Packing a leaf costs two passes over it (into the buffer and
# out again) and makes its exchange wait for the whole buffer; sending it
# alone costs one more collective to issue, a few us.  At the 46.7 GB/s one
# v5e link gave the exchange (PERF.md, PR 23) 1 MiB is 22 us on the wire, so
# from there on the issue cost is under a fifth of the transfer.
_DIRECT_LEAF_BYTES = 1 << 20


_leaf_bytes = C._nbytes


def _split_direct(leaves):
    """Flatten-order indices of the leaves exchanged as they are, and of
    those that are packed (:data:`_DIRECT_LEAF_BYTES`)."""
    big = [_leaf_bytes(l) >= _DIRECT_LEAF_BYTES for l in leaves]
    return ([i for i, b in enumerate(big) if b],
            [i for i, b in enumerate(big) if not b])


def _fused_apply(fn, tree):
    """Apply ``fn`` (list of arrays -> list of arrays, elementwise in shape
    and dtype) to a pytree's leaves: the large ones as they are, the small
    ones through one fusion buffer.

    A leaf of :data:`_DIRECT_LEAF_BYTES` or more reaches ``fn`` in its own
    shape and dtype: no copy into a buffer and none out of it, and its
    exchange depends on nothing but the leaf, so the scheduler can run one
    leaf's scale and add under another's permute.  (On the v5e the one flat
    buffer of a 2 GB tree cost three passes over it, 34 ms of a 244 ms
    step, and 8 GB of scratch: PERF.md, PR 23.)  The remaining leaves ravel
    into one flat buffer, so a model with hundreds of small parameters
    issues one collective set instead of one per parameter (the TPU-native
    replacement for the reference's FusionBufferManager + fused-response
    machinery, ``tensor_queue.h:70-92``, ``operations.cc:918-1001``).  The
    leaf's size is all that decides: there is no option over it.  All
    parts, the large leaves and then the buffer, reach ``fn`` in ONE call
    and in flatten order.  This function neither orders, cuts nor chains
    them: a neighbor combiner hands the list to
    ``ops.collective._apply_rounds``, which sends the parts smallest first,
    the ones over its block size as blocks, and adds each piece behind its
    own arrival; the dynamic combiner chooses its phase once for all."""
    from jax.flatten_util import ravel_pytree
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    if not leaves:
        return tree
    direct, packed = _split_direct(leaves)
    parts = [leaves[i] for i in direct]
    # Three device scopes (docs/timeline.md): a trace books every operation
    # of the step program to the one its metadata names.
    if packed:
        with timeline.device_scope("bf.optim.fuse"):
            flat, unravel = ravel_pytree([leaves[i] for i in packed])
        parts.append(flat)
    with timeline.device_scope("bf.optim.combine"):
        parts = fn(parts)
    out = list(leaves)
    for i, part in zip(direct, parts):
        out[i] = part
    if packed:
        with timeline.device_scope("bf.optim.unfuse"):
            for i, leaf in zip(packed, unravel(parts[-1])):
                out[i] = leaf
    return jax.tree_util.tree_unflatten(treedef, out)


def _tree_combine(params, combine: Combiner, step, weights,
                  steps_per_comm: int, shard_plan=None, shard_combine=None):
    """Apply ``combine`` to a pytree, skipping steps where
    ``step % steps_per_comm != 0`` (local aggregation).

    With an active ``shard_plan`` (a plan whose mask marks some leaves
    sharded) the tree is split by the mask: replicated leaves ride
    :func:`_fused_apply` over the full topology, sharded leaves go through
    ``shard_combine`` (:func:`make_shard_combiner`) — per-replica-group
    gossip of each rank's own shard slice.  Without an active plan every
    leaf is replicated, which is what keeps fully replicated trees
    bit-identical under the knob.
    """
    sharded_on = shard_combine is not None and shard_plan.any_sharded
    if combine.identity and not sharded_on:
        return params  # empty communication: no fusion copies, no cond

    def comm_all(p):
        leaves, treedef = jax.tree_util.tree_flatten(p)
        mask = shard_plan.mask if sharded_on else (False,) * len(leaves)
        rep_idx = [i for i, m in enumerate(mask) if not m]
        sh_idx = [i for i, m in enumerate(mask) if m]
        out = list(leaves)
        rep_out = _fused_apply(
            lambda parts: combine.combine(parts, step, weights),
            [leaves[i] for i in rep_idx])
        for i, leaf in zip(rep_idx, rep_out):
            out[i] = leaf
        if sh_idx:
            sh_out = shard_combine([leaves[i] for i in sh_idx], step=step)
            for i, leaf in zip(sh_idx, sh_out):
                out[i] = leaf
        return jax.tree_util.tree_unflatten(treedef, out)
    if steps_per_comm == 1:
        return comm_all(params)
    # lax.cond keeps one compiled program; both branches are cheap to trace.
    return lax.cond(step % steps_per_comm == 0, comm_all, lambda p: p, params)


def awc_step(base: optax.GradientTransformation, combine: Combiner,
             params, grads, state: DistOptState, *,
             weights=None, steps_per_comm: int = 1,
             shard_plan=None, shard_combine=None):
    """Adapt-with-combine: communicate params, then apply the base update.

    Matches ``_DistributedReduceOptimizer`` (reference
    ``torch/optimizers.py:297-483``): the forward hook launches communication
    of ``x_t`` while backward computes ``g_t``; ``step()`` waits and applies
    the local update to the *combined* parameters.  Each part's update
    depends only on its own combine (:func:`_fused_apply`).
    """
    combined = _tree_combine(params, combine, state.step, weights,
                             steps_per_comm, shard_plan, shard_combine)
    with timeline.device_scope("bf.optim.update"):
        updates, base_state = base.update(grads, state.base, combined)
        new_params = optax.apply_updates(combined, updates)
    return new_params, DistOptState(base_state, state.step + 1)


def atc_step(base: optax.GradientTransformation, combine: Combiner,
             params, grads, state: DistOptState, *,
             weights=None, steps_per_comm: int = 1,
             shard_plan=None, shard_combine=None):
    """Adapt-then-combine: local base update first, then communicate.

    Matches ``_DistributedAdaptThenCombineOptimizer`` (reference
    ``torch/optimizers.py:485-842``) — which re-implements sgd/adam/rmsprop/
    adagrad/adadelta by hand to fuse the update into the backward hook; here
    any optax transformation slots in unchanged.  A part's combine depends
    only on its own leaves' updates (:func:`_fused_apply`).
    """
    with timeline.device_scope("bf.optim.update"):
        updates, base_state = base.update(grads, state.base, params)
        half = optax.apply_updates(params, updates)
    new_params = _tree_combine(half, combine, state.step, weights,
                               steps_per_comm, shard_plan, shard_combine)
    return new_params, DistOptState(base_state, state.step + 1)


def compress_combiner(combine: Combiner, compression: str,
                      *, steps_per_comm: int = 1) -> Combiner:
    """Wrap a combiner so its payload crosses the wire compressed.

    Both codecs work part by part: on each large leaf and on the packed
    buffer, each handed to the wrapped combiner as a list of one.

    ``"bf16"`` casts to bfloat16 before the collective and back after —
    half the ICI/DCN bytes per round, the role of the reference family's
    fp16 compression (Horovod-style; BlueFog inherits the float16 wire
    type, ``common/half.h``).  ``"none"`` returns the combiner unchanged.

    ``"sparse:<frac>"`` ships a step-rotating block of ``ceil(frac *
    size)`` entries of each part; every part is swept in full in
    ``ceil(1/frac)`` communication rounds (comment below).

    A decentralized combiner gets the local quantization residual
    ``x - q(x)`` added back after combining — difference compression: the
    error becomes ``(W - I)(q(x) - x)`` instead of ``W (q(x) - x)``, so a
    rank's own f32 master weights are never truncated by its own round
    trips (with ``combine = identity`` the wrapper is exact).  A
    ``replica_identical`` combiner (global allreduce: parameter consensus
    or synchronous gradient averaging) does not: every rank must apply the
    bit-identical result, which is worth more there than the residual's
    accuracy (with it the drift is bf16-scale and re-averaged each round).
    """
    if compression in (None, "none"):
        return combine
    if isinstance(compression, str) and (compression.startswith("sparse")
                                         or compression.startswith("topk")):
        if compression.startswith("topk"):
            raise ValueError(
                "magnitude-only top-k gossip does not converge under the "
                "stateless per-round residual (never-picked coordinates "
                "stay unmixed forever); use compression='sparse:<frac>' — "
                "a step-rotating aligned block that sweeps every "
                "coordinate and reaches EXACT consensus")
        # "sparse:<frac>": ship only ceil(frac*size) entries per round of
        # each part it is handed (a large leaf or a packed buffer:
        # _fused_apply; the block rotates within the part, not within the
        # whole tree) —
        # (k,) values + (k,) int32 indices per edge instead of the dense
        # payload (C.sparse_neighbor_allreduce).  The index block ROTATES
        # with the step and is IDENTICAL on every rank, so each round is
        # exact dense gossip restricted to the block and a full sweep
        # covers every coordinate in ceil(1/frac) rounds — block-
        # coordinate gossip.  The per-round residual x - q keeps the
        # unsent coordinates locally intact; mass conservation is exact
        # and consensus reaches machine precision (measured; magnitude-
        # only top-k selection instead STALLS, because per-rank picks
        # disagree and never-picked coordinates never mix).
        if ":" not in compression:
            raise ValueError(
                f"malformed {compression!r}: use 'sparse:<frac>' "
                "(e.g. 'sparse:0.25')")
        try:
            frac = float(compression.split(":", 1)[1])
        except ValueError:
            raise ValueError(
                f"malformed {compression!r}: the fraction must be a "
                "float in (0, 1], e.g. 'sparse:0.25'") from None
        if not 0.0 < frac <= 1.0:
            raise ValueError(
                f"sparse fraction must be in (0, 1], got {frac}")
        if combine.identity:
            return combine  # empty communication: string validated above
        sched, axis_name = combine.sched, combine.axis_name
        if sched is None:
            raise ValueError(
                "compression='sparse:<frac>' needs a (static or dynamic) "
                "neighbor_allreduce combiner (the sparse exchange rides "
                "the compiled edge schedule); use 'bf16' for the other "
                "communication types")

        def wrapped_sparse(x, step, weights):
            if weights is not None:
                raise ValueError(
                    "per-step weight overrides are not supported under "
                    "sparse compression (weights are baked into the "
                    "sparse schedule)")
            kk = max(1, int(np.ceil(frac * x.size)))
            s = jnp.asarray(0 if step is None else step, jnp.int32)
            # Rotate by the COMMUNICATION-round index: with local
            # aggregation (steps_per_comm J > 1) the combiner only runs
            # when step % J == 0, and rotating by the raw step would
            # alias to multiples of gcd(J*kk, size) — entire coordinate
            # blocks would never cross the wire.
            rnd_idx = s // max(1, int(steps_per_comm))
            rot = ((jnp.arange(kk, dtype=jnp.int32) + rnd_idx * kk)
                   % x.size)
            if isinstance(sched, DynamicSchedule):
                out, q = C.dynamic_sparse_neighbor_allreduce(
                    x, s, sched, axis_name, indices=rot,
                    return_sent=True)
            else:
                out, q = C.sparse_neighbor_allreduce(
                    x, sched, axis_name, indices=rot, aligned=True,
                    return_sent=True)
            return out + (x - q)
        return combine._replace(combine=_per_part(wrapped_sparse))
    if compression != "bf16":
        raise ValueError(f"unknown compression {compression!r}; "
                         "expected 'none', 'bf16' or 'sparse:<frac>'")
    if combine.identity:
        return combine  # keep _tree_combine's identity fast path

    def wrapped(x, step, weights):
        q = x.astype(jnp.bfloat16)
        out = combine.combine([q], step, weights)[0].astype(x.dtype)
        if not combine.replica_identical:
            out = out + (x - q.astype(x.dtype))
        return out
    return combine._replace(combine=_per_part(wrapped))


def gradient_allreduce_step(base: optax.GradientTransformation,
                            params, grads, state: DistOptState, *,
                            axis_name: str, steps_per_comm: int = 1,
                            compression: str = "none"):
    """Horovod-style synchronous gradient averaging
    (reference ``_DistributedOptimizer``, ``torch/optimizers.py:166-295``).

    With ``steps_per_comm > 1`` gradients accumulate locally on silent steps
    and the J-step aggregate is averaged and applied on communicating steps
    only — every rank always applies the identical update, preserving the
    replica-identical invariant (the reference's delayed-allreduce counters,
    ``torch/optimizers.py:348-383``).

    A uniform-dtype gradient tree rides :func:`_fused_apply` like the
    parameter-consensus orders; the packed averaging is bit-identical to
    per-leaf (psum and the bf16 casts are elementwise), it just issues one
    allreduce for the small leaves instead of one each.  Mixed-dtype trees
    stay on the per-leaf path: the ravel would promote every leaf to a
    common dtype, changing the psum rounding — this order's
    replica-identical numerics must not shift underneath existing runs.
    """
    # The allreduce combiner is replica_identical: the codec adds no
    # residual, so every rank applies the bit-identical averaged gradient.
    average = compress_combiner(
        make_combiner(CommunicationType.allreduce, axis_name=axis_name),
        compression).combine
    uniform_dtype = len(
        {l.dtype for l in jax.tree_util.tree_leaves(grads)}) <= 1

    def comm(g):
        if uniform_dtype:
            return _fused_apply(lambda parts: average(parts, None, None), g)
        return jax.tree.map(lambda x: average([x], None, None)[0], g)

    def update(avg):
        with timeline.device_scope("bf.optim.update"):
            updates, base_state = base.update(avg, state.base, params)
            return optax.apply_updates(params, updates), base_state
    if steps_per_comm == 1:
        new_params, base_state = update(comm(grads))
        return new_params, DistOptState(base_state, state.step + 1)

    acc = state.acc if state.acc is not None else \
        jax.tree.map(jnp.zeros_like, grads)
    acc = jax.tree.map(lambda a, g: a + g, acc, grads)

    def communicate(_):
        return update(comm(acc)) + (jax.tree.map(jnp.zeros_like, acc),)

    def silent(_):
        return params, state.base, acc

    new_params, base_state, new_acc = lax.cond(
        (state.step + 1) % steps_per_comm == 0, communicate, silent, None)
    return new_params, DistOptState(base_state, state.step + 1, new_acc)


def dist_init(base: optax.GradientTransformation, params) -> DistOptState:
    return DistOptState(base.init(params), jnp.asarray(0, jnp.int32))


def step_fn(order: str, base: optax.GradientTransformation,
            combine: Combiner, *, axis_name: str,
            steps_per_comm: int = 1, compression: str = "none",
            shard_plan=None, shard_combine=None) -> Callable:
    """Bind an execution order to a ``(params, grads, state[, weights])`` fn
    (``compression``: :func:`compress_combiner`)."""
    if order in ("awc", "atc"):
        combine = compress_combiner(combine, compression,
                                    steps_per_comm=steps_per_comm)
        return partial(awc_step if order == "awc" else atc_step, base,
                       combine, steps_per_comm=steps_per_comm,
                       shard_plan=shard_plan, shard_combine=shard_combine)
    if order == "gradient_allreduce":
        if shard_plan is not None and shard_plan.any_sharded:
            raise ValueError(
                "sharded gossip applies to the parameter-consensus orders "
                "(awc/atc); gradient allreduce averages gradients globally "
                "and cannot restrict sharded leaves to replica groups")
        return partial(gradient_allreduce_step, base, axis_name=axis_name,
                       steps_per_comm=steps_per_comm,
                       compression=compression)
    raise ValueError(f"unknown execution order {order!r}")
