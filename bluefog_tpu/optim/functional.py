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
from typing import Callable, NamedTuple, Optional

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


Combiner = Callable[..., jnp.ndarray]  # (x, *, step, weights) -> x


def make_combiner(
        comm: CommunicationType,
        *,
        axis_name: str,
        sched: Optional[StaticSchedule] = None,
        dyn_sched: Optional[DynamicSchedule] = None,
        local_axis: Optional[str] = None,
        machine_axis: Optional[str] = None,
        hier: Optional[dict] = None,
) -> Combiner:
    """Build the per-leaf ``combine`` function for a communication type.

    The returned callable has signature ``combine(x, step, weights)`` where
    ``step`` is the traced step counter (used by dynamic schedules) and
    ``weights`` is an optional traced (n, n) matrix overriding the static
    schedule's weights (None => baked-in weights).  A callable marked
    ``takes_parts`` (dynamic neighbor averaging) also takes a list of
    arrays for ``x`` and chooses its phase once for all of them.
    """
    def _no_weights(weights, what):
        if weights is not None:
            raise ValueError(
                f"per-step weight overrides are not supported for {what}; "
                "they apply to (dynamic) neighbor_allreduce only")

    if comm == CommunicationType.empty:
        def _empty(x, step=None, weights=None):
            _no_weights(weights, "CommunicationType.empty")
            return x
        _empty.is_identity = True  # lets _tree_combine skip fusion copies
        return _empty
    if comm == CommunicationType.allreduce:
        def _ar(x, step=None, weights=None):
            _no_weights(weights, "CommunicationType.allreduce")
            return C.allreduce(x, axis_name, average=True)
        _ar.is_allreduce = True  # replica-identical: compress without residual
        return _ar
    if comm == CommunicationType.neighbor_allreduce:
        if dyn_sched is not None:
            def _dyn(x, step, weights=None):
                if weights is None:
                    return C.dynamic_neighbor_allreduce(
                        x, step, dyn_sched, axis_name)
                # Weight override on a dynamic topology: same phase switching,
                # weights looked up from the traced matrix per active edge.
                branches = [
                    partial(lambda ph, args: jax.tree.map(
                        lambda p: C.neighbor_allreduce_matrix(
                            p, args[1], ph, axis_name), args[0]), ph)
                    for ph in dyn_sched.phases]
                return lax.switch(step % dyn_sched.period, branches,
                                  (x, weights))
            # x may be the list of all parts of a step's exchange
            # (_fused_apply): one lax.switch then serves them all.
            _dyn.takes_parts = True
            # Lets compress_combiner run the aligned rotating-block sparse
            # exchange under the same lax.switch of phases
            # (compression="sparse:<frac>" on dynamic topologies).
            _dyn._sparse_dyn_args = (dyn_sched, axis_name)
            return _dyn
        assert sched is not None, "static neighbor_allreduce needs a schedule"

        def _nbr(x, step=None, weights=None):
            if weights is None:
                return C.neighbor_allreduce(x, sched, axis_name)
            return C.neighbor_allreduce_matrix(x, weights, sched, axis_name)
        # Lets compress_combiner build the top-k SPARSE exchange over the
        # same compiled edge schedule (compression="sparse:<frac>").
        _nbr._sparse_args = (sched, axis_name)
        return _nbr
    if comm == CommunicationType.hierarchical_gossip:
        assert local_axis and machine_axis, \
            "hierarchical gossip needs local/machine axis names"
        assert hier is not None, \
            "hierarchical gossip needs the compiled level bundle (hier=)"

        def _hgossip(x, step, weights=None):
            _no_weights(weights, "hierarchical_gossip")
            return C.hierarchical_gossip(
                x, step, hier["inner_sched"], hier["outer_scheds"],
                local_axis=local_axis, machine_axis=machine_axis,
                outer_every=hier.get("outer_every", 1),
                outer_compression=hier.get("outer_compression", "none"),
                outer_frac=hier.get("outer_frac"))
        return _hgossip
    if comm == CommunicationType.hierarchical_neighbor_allreduce:
        assert local_axis and machine_axis, \
            "hierarchical combine needs local/machine axis names"
        if dyn_sched is not None:
            def _hdyn(x, step, weights=None):
                _no_weights(weights, "hierarchical_neighbor_allreduce")
                return C.dynamic_hierarchical_neighbor_allreduce(
                    x, step, dyn_sched, local_axis, machine_axis)
            return _hdyn
        assert sched is not None

        def _hier(x, step=None, weights=None):
            _no_weights(weights, "hierarchical_neighbor_allreduce")
            return C.hierarchical_neighbor_allreduce(
                x, sched, local_axis, machine_axis)
        return _hier
    raise ValueError(f"unknown communication type {comm}")


def make_shard_combiner(plan, group_combine, *, axis_name: str):
    """Per-replica-group combiner for the sharded leaves of a plan.

    ``plan`` is an :class:`ops.sharded.ShardPlan`; ``group_combine`` is a
    regular combiner (``make_combiner`` output, optionally wrapped by
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
        if not leaves:
            return leaves
        coord = coords[lax.axis_index(axis_name)]
        slices = []
        for leaf, d in zip(leaves, sh_dims):
            chunk = leaf.shape[d] // plan.n_shards
            slices.append(lax.dynamic_slice_in_dim(
                leaf, coord * chunk, chunk, axis=d))
        flat, unravel = ravel_pytree(slices)
        combined = unravel(group_combine(flat, step=step, weights=None))
        out = []
        for leaf, d, sl in zip(leaves, sh_dims, combined):
            chunk = leaf.shape[d] // plan.n_shards
            out.append(lax.dynamic_update_slice_in_dim(
                leaf, sl.astype(leaf.dtype), coord * chunk, axis=d))
        return out
    return shard_combine


def _bucket_groups(leaves, fusion_buckets: Optional[int]):
    """Partition flatten-order leaf indices into contiguous fusion buckets.

    ``fusion_buckets`` (explicit count) wins over the
    ``BLUEFOG_TPU_FUSION_BUCKET_MB`` size cap; with neither, one bucket —
    today's whole-tree ravel.  Buckets are contiguous in tree-flatten
    order, byte-balanced (count mode) or size-capped (MB mode), and
    deterministic: every SPMD rank must build identical buffers.
    """
    from bluefog_tpu.utils import config
    nbytes = [int(np.prod(l.shape)) * l.dtype.itemsize for l in leaves]
    total = sum(nbytes)
    if fusion_buckets is not None:
        k = max(1, min(int(fusion_buckets), len(leaves)))
        if k == 1:
            return [list(range(len(leaves)))]
        # Close bucket b once the running total crosses b/k of the bytes:
        # balanced without look-ahead, never more than k buckets.
        groups, cur, cum, b = [], [], 0, 1
        for i, nb in enumerate(nbytes):
            cur.append(i)
            cum += nb
            if cum * k >= b * total and b < k:
                groups.append(cur)
                cur, b = [], b + 1
        if cur:
            groups.append(cur)
        return groups
    cap = config.get().fusion_bucket_mb * (1 << 20)
    if cap <= 0:
        return [list(range(len(leaves)))]
    groups, cur, cur_bytes = [], [], 0
    for i, nb in enumerate(nbytes):
        if cur and cur_bytes + nb > cap:
            groups.append(cur)
            cur, cur_bytes = [], 0
        cur.append(i)
        cur_bytes += nb
    if cur:
        groups.append(cur)
    return groups


# A leaf of at least this many bytes is exchanged as it is; smaller leaves
# are packed.  Packing a leaf costs two passes over it (into the buffer and
# out again) and makes its exchange wait for the whole buffer; sending it
# alone costs one more collective to issue, a few us.  At the 46.7 GB/s one
# v5e link gave the exchange (PERF.md, PR 23) 1 MiB is 22 us on the wire, so
# from there on the issue cost is under a fifth of the transfer.
_DIRECT_LEAF_BYTES = 1 << 20


def _leaf_bytes(leaf) -> int:
    return int(np.prod(leaf.shape)) * leaf.dtype.itemsize


def _split_direct(leaves):
    """Flatten-order indices of the leaves exchanged as they are, and of
    those that are packed (:data:`_DIRECT_LEAF_BYTES`)."""
    big = [_leaf_bytes(l) >= _DIRECT_LEAF_BYTES for l in leaves]
    return ([i for i, b in enumerate(big) if b],
            [i for i, b in enumerate(big) if not b])


def _fused_apply(fn, tree, fusion_buckets: Optional[int]):
    """Apply ``fn`` (list of arrays -> list of arrays, elementwise in shape
    and dtype) to a pytree's leaves: the large ones as they are, the small
    ones through fusion buckets.

    A leaf of :data:`_DIRECT_LEAF_BYTES` or more reaches ``fn`` in its own
    shape and dtype: no copy into a buffer and none out of it, and its
    exchange depends on nothing but the leaf, so the scheduler can run one
    leaf's scale and add under another's permute.  (On the v5e the one flat
    buffer of a 2 GB tree cost three passes over it, 34 ms of a 244 ms
    step, and 8 GB of scratch: PERF.md, PR 23.)  The remaining leaves ravel
    into one flat buffer per bucket (``_bucket_groups``), so a model with
    hundreds of small parameters issues one collective set per bucket
    instead of one per parameter; a tree with no large leaf lowers to the
    program it lowered to before there was a direct path.  All parts, the
    large leaves and then the buffers, reach ``fn`` in ONE call: a combiner
    that takes a list (``takes_parts``) chooses its phase once for all."""
    from jax.flatten_util import ravel_pytree
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    if not leaves:
        return tree
    direct, packed = _split_direct(leaves)
    groups = [[packed[j] for j in grp] for grp in _bucket_groups(
        [leaves[i] for i in packed], fusion_buckets)] if packed else []
    # Three device scopes (docs/timeline.md): a trace books every operation
    # of the step program to the one its metadata names.
    with timeline.device_scope("bf.optim.fuse"):
        raveled = [ravel_pytree([leaves[i] for i in grp]) for grp in groups]
    with timeline.device_scope("bf.optim.combine"):
        parts = fn([leaves[i] for i in direct] + [flat for flat, _ in raveled])
    out = list(leaves)
    for i, part in zip(direct, parts):
        out[i] = part
    with timeline.device_scope("bf.optim.unfuse"):
        for grp, (_, unravel), flat in zip(groups, raveled,
                                           parts[len(direct):]):
            for i, leaf in zip(grp, unravel(flat)):
                out[i] = leaf
    return jax.tree_util.tree_unflatten(treedef, out)


def _combine_parts(combine, step, weights):
    """``fn`` of :func:`_fused_apply` for a combiner: a combiner marked
    ``takes_parts`` gets the list whole (one phase switch a step), every
    other one is applied part by part, which without a switch is the same
    program."""
    if getattr(combine, "takes_parts", False):
        return lambda parts: combine(parts, step=step, weights=weights)
    return lambda parts: [combine(x, step=step, weights=weights)
                          for x in parts]


def _tree_combine(params, combine, step, weights, steps_per_comm: int,
                  fuse: bool = True, fusion_buckets: Optional[int] = None,
                  shard_plan=None, shard_combine=None):
    """Apply ``combine`` to a pytree, skipping steps where
    ``step % steps_per_comm != 0`` (local aggregation).

    ``fuse=True`` hands the leaves of 1 MiB and more to ``combine`` as they
    are and ravels the smaller ones into fusion-bucket buffers (default:
    one), so a model with hundreds of small parameters issues one ppermute
    set per round per bucket instead of one per parameter — the TPU-native
    replacement for the reference's FusionBufferManager + fused-response
    machinery (``tensor_queue.h:70-92``, ``operations.cc:918-1001``).  The
    copies into and out of a buffer are real passes over it on the device
    (19 ms a step for a 2 GB buffer on the v5e: PERF.md, PR 23), which is
    why only leaves too small to pay for a collective of their own are
    packed.  ``fusion_buckets > 1`` (or the ``BLUEFOG_TPU_FUSION_BUCKET_MB``
    cap) splits what is packed — see :func:`_fused_apply`.  ``fuse=False``
    combines every leaf alone.

    With an active ``shard_plan`` (a plan whose mask marks some leaves
    sharded) the tree is split by the mask: replicated leaves ride the
    legacy fused path over the full topology, sharded leaves go through
    ``shard_combine`` (:func:`make_shard_combiner`) — per-replica-group
    gossip of each rank's own shard slice.  Without an active plan this
    function is byte-for-byte the legacy replicated-only path, which is
    what keeps fully replicated trees bit-identical under the knob.
    """
    sharded_on = (shard_plan is not None and shard_combine is not None
                  and shard_plan.any_sharded)
    if not sharded_on:
        if getattr(combine, "is_identity", False):
            return params  # empty communication: no fusion copies, no cond

        def comm_all(p):
            if fuse:
                return _fused_apply(_combine_parts(combine, step, weights),
                                    p, fusion_buckets)
            with timeline.device_scope("bf.optim.combine"):
                return jax.tree.map(
                    lambda x: combine(x, step=step, weights=weights), p)
        if steps_per_comm == 1:
            return comm_all(params)
        # lax.cond keeps one compiled program; both branches cheap to trace.
        return lax.cond(step % steps_per_comm == 0, comm_all,
                        lambda p: p, params)

    rep_idx = [i for i, m in enumerate(shard_plan.mask) if not m]
    sh_idx = [i for i, m in enumerate(shard_plan.mask) if m]

    def comm_all(p):
        leaves, treedef = jax.tree_util.tree_flatten(p)
        out = list(leaves)
        if rep_idx and not getattr(combine, "is_identity", False):
            rep = [leaves[i] for i in rep_idx]
            if fuse:
                rep_out = _fused_apply(
                    _combine_parts(combine, step, weights),
                    rep, fusion_buckets)
            else:
                rep_out = [combine(x, step=step, weights=weights)
                           for x in rep]
            for i, leaf in zip(rep_idx, rep_out):
                out[i] = leaf
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
             weights=None, steps_per_comm: int = 1, fuse: bool = True,
             fusion_buckets: Optional[int] = None,
             shard_plan=None, shard_combine=None):
    """Adapt-with-combine: communicate params, then apply the base update.

    Matches ``_DistributedReduceOptimizer`` (reference
    ``torch/optimizers.py:297-483``): the forward hook launches communication
    of ``x_t`` while backward computes ``g_t``; ``step()`` waits and applies
    the local update to the *combined* parameters.  Each part's update
    depends only on its own combine (:func:`_fused_apply`).
    """
    combined = _tree_combine(params, combine, state.step, weights,
                             steps_per_comm, fuse, fusion_buckets,
                             shard_plan, shard_combine)
    with timeline.device_scope("bf.optim.update"):
        updates, base_state = base.update(grads, state.base, combined)
        new_params = optax.apply_updates(combined, updates)
    return new_params, DistOptState(base_state, state.step + 1)


def atc_step(base: optax.GradientTransformation, combine: Combiner,
             params, grads, state: DistOptState, *,
             weights=None, steps_per_comm: int = 1, fuse: bool = True,
             fusion_buckets: Optional[int] = None,
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
                               steps_per_comm, fuse, fusion_buckets,
                               shard_plan, shard_combine)
    return new_params, DistOptState(base_state, state.step + 1)


def compress_combiner(combine: Combiner, compression: str,
                      *, residual: bool = True,
                      steps_per_comm: int = 1) -> Combiner:
    """Wrap a combiner so its payload crosses the wire compressed.

    The wrapped combiner takes one array, so ``_fused_apply`` applies it
    part by part: to each large leaf and to each packed buffer.

    ``"bf16"`` casts to bfloat16 before the collective and back after —
    half the ICI/DCN bytes per round, the role of the reference family's
    fp16 compression (Horovod-style; BlueFog inherits the float16 wire
    type, ``common/half.h``).  ``"none"`` returns the combiner unchanged.

    ``"sparse:<frac>"`` ships a step-rotating block of ``ceil(frac *
    size)`` entries of each part; every part is swept in full in
    ``ceil(1/frac)`` communication rounds (comment below).

    ``residual=True`` (parameter-consensus orders) adds back the local
    quantization residual ``x - q(x)`` after combining — difference
    compression: the error becomes ``(W - I)(q(x) - x)`` instead of
    ``W (q(x) - x)``, so a rank's own f32 master weights are never
    truncated by its own round trips (with ``combine = identity`` the
    wrapper is exact).  Set ``residual=False`` where every rank must apply
    the bit-identical result (synchronous gradient averaging).
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
        if getattr(combine, "is_identity", False):
            return combine  # empty communication: string validated above
        args = getattr(combine, "_sparse_args", None)
        dyn_args = getattr(combine, "_sparse_dyn_args", None)
        if args is None and dyn_args is None:
            raise ValueError(
                "compression='sparse:<frac>' needs a (static or dynamic) "
                "neighbor_allreduce combiner (the sparse exchange rides "
                "the compiled edge schedule); use 'bf16' for the other "
                "communication types")
        if not residual:
            raise ValueError(
                "sparse compression requires residual error feedback "
                "(decentralized orders); it cannot keep an allreduce "
                "replica-identical")

        def wrapped_sparse(x, step=None, weights=None):
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
            if args is not None:
                sched, axis_name = args
                out, q = C.sparse_neighbor_allreduce(
                    x, sched, axis_name, indices=rot, aligned=True,
                    return_sent=True)
            else:
                dyn_sched, axis_name = dyn_args
                out, q = C.dynamic_sparse_neighbor_allreduce(
                    x, s, dyn_sched, axis_name, indices=rot,
                    return_sent=True)
            return out + (x - q)
        return wrapped_sparse
    if compression != "bf16":
        raise ValueError(f"unknown compression {compression!r}; "
                         "expected 'none', 'bf16' or 'sparse:<frac>'")
    if getattr(combine, "is_identity", False):
        return combine  # keep _tree_combine's identity fast path

    def wrapped(x, **kw):
        q = x.astype(jnp.bfloat16)
        out = combine(q, **kw).astype(x.dtype)
        if residual:
            out = out + (x - q.astype(x.dtype))
        return out
    return wrapped


def gradient_allreduce_step(base: optax.GradientTransformation,
                            params, grads, state: DistOptState, *,
                            axis_name: str, steps_per_comm: int = 1,
                            compression: str = "none", fuse: bool = True,
                            fusion_buckets: Optional[int] = None):
    """Horovod-style synchronous gradient averaging
    (reference ``_DistributedOptimizer``, ``torch/optimizers.py:166-295``).

    With ``steps_per_comm > 1`` gradients accumulate locally on silent steps
    and the J-step aggregate is averaged and applied on communicating steps
    only — every rank always applies the identical update, preserving the
    replica-identical invariant (the reference's delayed-allreduce counters,
    ``torch/optimizers.py:348-383``).

    ``fuse``/``fusion_buckets`` ride the same bucket machinery as the
    parameter-consensus orders; for a uniform-dtype gradient tree the fused
    averaging is bit-identical to per-leaf (psum and the bf16 casts are
    elementwise), it just issues one allreduce per bucket instead of one
    per gradient leaf.  Mixed-dtype trees stay on the per-leaf path: the
    ravel would promote every leaf to a common dtype, changing the psum
    rounding — this order's replica-identical numerics must not shift
    underneath existing runs.
    """
    # residual=False: every rank must apply the bit-identical averaged
    # gradient (the replica-identical invariant below).
    one = compress_combiner(
        lambda x, **kw: C.allreduce(x, axis_name, average=True),
        compression, residual=False)
    uniform_dtype = len(
        {l.dtype for l in jax.tree_util.tree_leaves(grads)}) <= 1

    def comm(g):
        if fuse and uniform_dtype:
            return _fused_apply(lambda parts: [one(x) for x in parts], g,
                                fusion_buckets)
        return jax.tree.map(one, g)
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
            steps_per_comm: int = 1, fuse: bool = True,
            fusion_buckets: Optional[int] = None,
            compression: str = "none",
            residual: Optional[bool] = None,
            shard_plan=None, shard_combine=None) -> Callable:
    """Bind an execution order to a ``(params, grads, state[, weights])`` fn.

    ``fusion_buckets`` splits the buffer of the packed leaves (those under
    1 MiB: :func:`_fused_apply`) into that many byte-balanced buckets
    (None: one bucket, or the ``BLUEFOG_TPU_FUSION_BUCKET_MB`` size cap
    when set).

    ``residual`` controls difference compression under ``compression='bf16'``.
    A global-consensus allreduce must keep replicas bit-identical, so the
    per-rank quantization residual is NOT re-added after combining (with
    residual the drift is bf16-scale and re-averaged each round, but the
    replica-identical invariant is worth more than the residual's accuracy
    for that order); decentralized combiners keep difference compression.
    Callers that know the communication type should pass this explicitly
    (``optim.optimizers`` does); with ``None`` it falls back to the
    ``is_allreduce`` tag ``make_combiner`` sets."""
    if residual is None:
        residual = not getattr(combine, "is_allreduce", False)
    combine = compress_combiner(combine, compression, residual=residual,
                                steps_per_comm=steps_per_comm)
    if order == "awc":
        return partial(awc_step, base, combine,
                       steps_per_comm=steps_per_comm, fuse=fuse,
                       fusion_buckets=fusion_buckets,
                       shard_plan=shard_plan, shard_combine=shard_combine)
    if order == "atc":
        return partial(atc_step, base, combine,
                       steps_per_comm=steps_per_comm, fuse=fuse,
                       fusion_buckets=fusion_buckets,
                       shard_plan=shard_plan, shard_combine=shard_combine)
    if order == "gradient_allreduce":
        if shard_plan is not None and shard_plan.any_sharded:
            raise ValueError(
                "sharded gossip applies to the parameter-consensus orders "
                "(awc/atc); gradient allreduce averages gradients globally "
                "and cannot restrict sharded leaves to replica groups")
        return partial(gradient_allreduce_step, base, axis_name=axis_name,
                       steps_per_comm=steps_per_comm,
                       compression=compression, fuse=fuse,
                       fusion_buckets=fusion_buckets)
    raise ValueError(f"unknown execution order {order!r}")
