"""Collective ops over TPU mesh axes.

Functional core of the framework: every op is a pure function designed to run
inside ``jax.shard_map`` / ``pjit`` over a named mesh axis, so XLA schedules
the communication on ICI/DCN and fuses the weighted combines into it.  This
layer replaces the reference's controller layer (``mpi_controller.cc``,
``nccl_controller.cc``): where BlueFog dispatches MPI_Neighbor_allgather /
ncclSend/Recv from a background thread and does the weighted combine in Torch
callback code (``torch/mpi_ops.cc:357-445``), here the whole thing — permutes
plus combine — is one XLA program.

Op inventory and semantics parity (reference ``bluefog/torch/mpi_ops.py``):
  allreduce(:106), broadcast(:212), allgather(:285), neighbor_allgather(:364),
  neighbor_allreduce(:433-595), hierarchical_neighbor_allreduce(:596),
  pair_gossip(:787-848); hierarchical local allreduce (``mpi_ops.py:92-104``).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from bluefog_tpu.ops.schedule import (
    DynamicSchedule,
    PairGossipSchedule,
    StaticSchedule,
)

__all__ = [
    "allreduce",
    "local_allreduce",
    "broadcast",
    "allgather",
    "neighbor_allgather",
    "neighbor_allreduce",
    "neighbor_allreduce_matrix",
    "sparse_neighbor_allreduce",
    "dynamic_sparse_neighbor_allreduce",
    "dynamic_neighbor_allreduce",
    "pair_gossip",
    "hierarchical_neighbor_allreduce",
    "dynamic_hierarchical_neighbor_allreduce",
    "hierarchical_gossip",
    "schedule_wire_stats",
]


def schedule_wire_stats(sched) -> tuple:
    """``(rounds, edges, hops, provenance)`` of a compiled schedule — the
    per-call wire-cost metadata telemetry records at dispatch time (the op
    bodies here are traced into one XLA program, so Python-side counters
    cannot live in them; the schedule is the ground truth for what the
    program moves).

    ``StaticSchedule``/``PairGossipSchedule``: rounds is the ppermute count
    per call, edges the total (src, dst) pairs across them.  A
    ``DynamicSchedule`` executes ONE phase per call (``lax.switch``), so
    all three are averaged over the period — the exact per-call value
    for uniform phases (one-peer walks), the expectation otherwise.

    ``hops`` is the modeled physical cost: the weighted link-crossing
    count of one call under the active interconnect model and placement
    (``ops/placement``), at unit payload per edge — the dispatch layer
    scales it by the per-rank row bytes into
    ``bf_schedule_hop_bytes_total``.  None when no physical model is
    active (the historical two-element view, extended).

    Counts reflect the schedule AS COMPILED: with the min-round repack on
    (``BLUEFOG_TPU_SCHEDULE_OPT``, default) the rounds gauge is the
    optimized ``max(max_outdeg, max_indeg)`` count, not the shift-distance
    decomposition's; edges are invariant under repacking.

    ``provenance`` is the :class:`~bluefog_tpu.ops.schedule.CompiledSchedule`
    artifact's pipeline tag (``naive`` / ``konig`` / ``congestion`` /
    ``synthesized:<sketch>``; a ``DynamicSchedule`` reports its phases'
    consensus, ``mixed`` when they disagree) — what
    ``bf_comm_schedule_provenance_total`` labels per-op calls with."""
    from bluefog_tpu.ops import placement as PL
    from bluefog_tpu.ops.schedule import schedule_provenance
    phases = getattr(sched, "phases", None)
    prov = schedule_provenance(sched)
    if phases is not None:  # DynamicSchedule
        per = [_logical_rounds_edges(ph) for ph in phases]
        k = max(len(per), 1)
        # Hops delegate to the one implementation of the per-call phase
        # average (it caches the dynamic-level value, so per-phase hops
        # are not recomputed here just to be discarded).
        return (sum(r for r, _ in per) / k,
                sum(e for _, e in per) / k,
                PL.modeled_schedule_hops(sched), prov)
    return _logical_rounds_edges(sched) + (
        PL.modeled_schedule_hops(sched), prov)


def _logical_rounds_edges(sched) -> tuple:
    rnd = getattr(sched, "round", None)
    rounds = sched.rounds if rnd is None else [rnd]
    return (len(rounds), sum(len(r.pairs) for r in rounds))


def _axis_index(axis_name):
    return lax.axis_index(axis_name)


def _const(arr: np.ndarray, dtype) -> jnp.ndarray:
    return jnp.asarray(arr, dtype=dtype)


# ---------------------------------------------------------------------------
# Dense collectives
# ---------------------------------------------------------------------------

def allreduce(x: jnp.ndarray, axis_name: str, *, average: bool = True) -> jnp.ndarray:
    """Global sum (or average) over a mesh axis."""
    s = lax.psum(x, axis_name)
    if average:
        s = s / lax.axis_size(axis_name)
    return s


def local_allreduce(x: jnp.ndarray, local_axis: str, *, average: bool = True) -> jnp.ndarray:
    """Allreduce restricted to the machine-local mesh axis — the reference's
    ``allreduce(..., is_hierarchical_local=True)`` over the LOCAL communicator."""
    return allreduce(x, local_axis, average=average)


def broadcast(x: jnp.ndarray, root_rank: int, axis_name: str) -> jnp.ndarray:
    """Every rank gets ``root_rank``'s value."""
    idx = _axis_index(axis_name)
    contrib = jnp.where(idx == root_rank, x, jnp.zeros_like(x))
    return lax.psum(contrib, axis_name)


def allgather(x: jnp.ndarray, axis_name: str) -> jnp.ndarray:
    """Concatenate every rank's tensor along the leading axis (rank order)."""
    return lax.all_gather(x, axis_name, tiled=True)


# ---------------------------------------------------------------------------
# Neighbor family
# ---------------------------------------------------------------------------

def _tree_sum(terms: list) -> jnp.ndarray:
    """Balanced pairwise sum: depth ``ceil(log2(k))`` instead of a serial
    add chain, so no permuted term's consumption is serialized behind every
    earlier round — XLA is free to add round r's arrival while round r+1 is
    still on the wire (and fp error grows O(log k), not O(k))."""
    while len(terms) > 1:
        nxt = [terms[i] + terms[i + 1] for i in range(0, len(terms) - 1, 2)]
        if len(terms) % 2:
            nxt.append(terms[-1])
        terms = nxt
    return terms[0]


# A part of more bytes than this goes on the wire as blocks of at most this
# many, cut along its leading axis; each block is a transfer of its own.
# What follows the last byte of an exchange is then one block's add (0.3 ms
# for 64 MiB on a v5e) and not the largest part's (3.3 ms for the 758 MB
# leaves of internlm2-1.8b), and a block is added while the next is on the
# wire.  Smaller blocks buy nothing more at the end and cost a transfer to
# issue each; larger ones leave more behind the last byte.  Chosen from one
# sweep on the chip (PERF.md, PR 32); there is no option over it.
_BLOCK_BYTES = 64 << 20

# How many pieces ahead of the one being added a piece is made ready to
# send.  The TPU compiler keeps at most five collective-permutes in flight
# and a v5e link serves them in the order they start, so with five on the
# wire and the next one ready the link never waits for the compute units.
_DEPTH = 6


def _nbytes(x) -> int:
    return int(np.prod(x.shape)) * x.dtype.itemsize


def _blocks(x) -> list:
    """Row ranges ``[(lo, hi), ...]`` of the blocks ``x`` goes on the wire
    as; one range over all of it for a part of at most :data:`_BLOCK_BYTES`
    or without an axis to cut.  Blocks are runs of whole rows of the leading
    axis, a multiple of 8 where a block holds that many (the TPU tiles the
    last two axes 8 x 128, so such a block is cut out and put back without
    a change of layout), the last one shorter; no view is reshaped, so a
    part with few, long rows goes as blocks of one row each, whatever their
    size."""
    nbytes = _nbytes(x)
    if x.ndim == 0 or nbytes <= _BLOCK_BYTES:
        return [(0, x.shape[0] if x.ndim else 1)]
    rows = x.shape[0]
    per = max(1, _BLOCK_BYTES // (nbytes // rows))
    if per >= 8:
        per -= per % 8
    return [(lo, min(lo + per, rows)) for lo in range(0, rows, per)]


def _apply_rounds(x, sched: StaticSchedule, axis_name: str, idx, w=None):
    """``self_scale[i] * x + sum_r ppermute(x * send_scale_r)`` for every
    part of ``x``, an array or a pytree of arrays — the weighted neighbor
    combine, with weights applied source-side (see schedule.py; ``w``: a
    traced (n, n) matrix to take them from instead) — written as ONE
    pipeline whose order is the wire's.

    *Who orders.*  This function: the parts go on the wire in ascending
    order of bytes (ties in the order given), so the first byte leaves
    behind the smallest part's scale and every later part is made ready
    under the transfers queued before it.  *Who cuts.*  This function: a
    part over :data:`_BLOCK_BYTES` goes as the blocks of :func:`_blocks`,
    adjacent in the order, each a ``ppermute`` of its own, and their sums
    land one by one in the part's result (``dynamic_update_slice`` into a
    buffer that starts as zeros, which XLA does in place).  *Who chains.*
    This function: a piece is a part or a block; piece ``m`` is added when
    it has arrived and piece ``m - 1`` has been added, and that moment also
    starts piece ``m + _DEPTH`` and scales piece ``m + _DEPTH + 1``
    (``lax.optimization_barrier`` over the four: a data dependency is all
    the scheduler respects, and without it the compiler awaits the pieces
    in an order of its own, or fuses one piece's add with the next one's
    scale and so sends nothing while it waits).  A piece's terms are summed
    by :func:`_tree_sum` over the rounds as before.

    Every element sees the multiply, permute and add it saw when parts were
    exchanged one by one: the result is theirs bit for bit.  A schedule
    without rounds has no wire and nothing to order: each part is scaled
    where it stands.  A list of one part under the block size lowers to one
    scale per round, one permute per round and one sum, with no barrier."""
    rounds = sched.rounds
    parts, treedef = jax.tree.flatten(x)

    def scales(dt):
        if w is None:
            return (_const(sched.self_scale, dt)[idx],
                    [_const(rnd.send_scale, dt)[idx] for rnd in rounds])
        # Static per-round dst of each src (-1 = silent, precomputed on the
        # round); silent ranks get a zero scale so the value they permute
        # is masked out.
        send = []
        for rnd in rounds:
            dst = _const(rnd.dst_of, jnp.int32)[idx]
            send.append(jnp.where(
                dst >= 0, w[idx, jnp.maximum(dst, 0)], 0.0).astype(dt))
        return w[idx, idx].astype(dt), send

    if not rounds:
        return jax.tree.unflatten(
            treedef, [part * scales(part.dtype)[0] for part in parts])
    # Where every rank keeps the share it sends (one round, self weight ==
    # edge weight: the one-peer walks), the kept term IS the sent array and
    # is not made twice.
    kept_is_sent = (w is None and len(rounds) == 1 and np.array_equal(
        sched.self_scale, rounds[0].send_scale))
    # The pieces in wire order: (part, first row, rows or None for all of
    # it), and what each waits for: its row offset and, on a part's first
    # piece, the part's scales.
    pieces, held = [], []
    for i in sorted(range(len(parts)), key=lambda i: _nbytes(parts[i])):
        blocks = _blocks(parts[i])
        for lo, hi in blocks:
            pieces.append((i, lo, hi - lo if len(blocks) > 1 else None))
            held.append((jnp.int32(lo),
                         None if lo else scales(parts[i].dtype)))
    whole, ready, kept_of, flying = {}, {}, {}, {}

    def prepare(p, got):
        """Scale piece ``p``: its part whole on the part's first piece
        (one fusion with whatever made the part), then its rows of that."""
        (i, _, rows), (at, scale) = pieces[p], got
        if scale is not None:
            sent = [parts[i] * s for s in scale[1]]
            whole[i] = sent, (sent[0] if kept_is_sent
                              else parts[i] * scale[0])
        sent, kept = whole[i]
        if rows is not None:
            sent = [lax.dynamic_slice_in_dim(s, at, rows) for s in sent]
            kept = lax.dynamic_slice_in_dim(kept, at, rows)
        ready[p], kept_of[p] = sent, (kept, at)

    def start(p, sent):
        flying[p] = [lax.ppermute(s, axis_name, rnd.pairs)
                     for s, rnd in zip(sent, rounds)]

    for p in range(min(_DEPTH + 1, len(pieces))):
        prepare(p, held[p])
    for p in range(min(_DEPTH, len(pieces))):
        start(p, ready.pop(p))
    out = [None] * len(parts)
    last = None     # the part of the piece added last
    for m, (i, lo, rows) in enumerate(pieces):
        arrivals = flying.pop(m)
        a, b = m + _DEPTH, m + _DEPTH + 1
        if last is not None or a < len(pieces):
            done, arrivals, sent, waited = lax.optimization_barrier((
                None if last is None else out[last], arrivals,
                ready.pop(a, None), held[b] if b < len(pieces) else None))
            if last is not None:
                out[last] = done
            if sent is not None:
                start(a, sent)
            if waited is not None:
                prepare(b, waited)
        kept, at = kept_of.pop(m)
        total = _tree_sum([kept] + arrivals)
        if rows is not None:
            total = lax.dynamic_update_slice_in_dim(
                out[i] if lo else jnp.zeros_like(parts[i]), total, at, 0)
        out[i], last = total, i
    return jax.tree.unflatten(treedef, out)


def neighbor_allreduce(x, sched: StaticSchedule, axis_name: str):
    """Weighted neighbor averaging over a static topology.

    ``out_i = W[i,i] * x_i + sum_{j -> i} W[j,i] * x_j`` with ``W`` baked into
    ``sched``.  One ``lax.ppermute`` per shift-distance class of the topology
    (Exp2 over n ranks: log2(n) permutes, all riding ICI concurrently).
    ``x`` is an array or a pytree of arrays, the parts of one exchange,
    which go through one pipeline (:func:`_apply_rounds`: smallest first,
    a part over :data:`_BLOCK_BYTES` as blocks).
    """
    return _apply_rounds(x, sched, axis_name, _axis_index(axis_name))


def sparse_neighbor_allreduce(x: jnp.ndarray, sched: StaticSchedule,
                              axis_name: str, *, k: int = None,
                              indices: jnp.ndarray = None,
                              valid: jnp.ndarray = None,
                              aligned: bool = False,
                              return_sent: bool = False):
    """Top-k SPARSIFIED weighted neighbor averaging (beyond the reference).

    Each rank ships only its ``k`` largest-magnitude entries — a
    ``(k,)`` values array plus ``(k,)`` int32 indices per edge round —
    so the per-edge wire bytes are ``k * 8`` instead of ``4 * x.size``
    (a 50× cut at 1% density).  The combine runs entirely on the
    compressed representation ``q_i = scatter(vals_i, idx_i)``::

        out_i = W[i,i] * q_i  +  sum_{j -> i} W[j,i] * q_j

    — the self term uses ``q_i`` too, so the difference-compression
    wrapper ``out + (x - q)`` is EXACT at consensus (every row of W sums
    to 1 on q, and the dropped mass re-enters locally).  The optimizer
    family exposes this as ``compression="sparse:<frac>"`` with a
    step-ROTATING aligned index block: per-rank magnitude picks disagree
    across ranks and never-picked coordinates would never mix (measured:
    the spread stalls), while the aligned rotating block is exact dense
    gossip per block and sweeps every coordinate each ceil(1/frac)
    rounds — consensus to machine precision.

    ``return_sent=True`` also returns the dense representation ``q`` of
    this rank's own outgoing payload (zeros except the top-k entries) —
    what the residual ``x - q`` must be computed against.

    ``indices`` overrides the magnitude selection with a caller-chosen
    (k,) int32 index set (may be traced — e.g. a step-rotating block);
    ``valid`` is an optional (k,) bool mask zeroing individual slots
    (dropping duplicate picks without a dynamic shape).  ``aligned=True``
    asserts every rank passes the SAME index set (the rotating-block
    case): the per-round index permute is skipped — receivers scatter at
    their own ``indices`` — halving the wire bytes to ``k * 4`` per edge.

    Static-shape by construction (``k`` is a Python int), so the whole
    exchange jits into the same ppermute-per-round schedule as the dense
    op; ranks without an edge in a round receive ppermute's zero fill
    (a scatter-add of 0.0 at index 0 — harmless)."""
    idx = _axis_index(axis_name)
    dt = x.dtype
    flat = x.reshape(-1)
    if indices is None:
        if k is None:
            raise ValueError("pass k= (top-k selection) or indices=")
        _, pos = lax.top_k(jnp.abs(flat), k)
    else:
        pos = indices
    vals = flat[pos]
    if valid is not None:
        vals = vals * valid.astype(dt)
    # scatter-ADD, exactly like the receivers: with duplicate indices a
    # .set would drop one contribution from q while the wire still carried
    # it — the residual x - q would then re-add sent mass (divergence).
    q_flat = jnp.zeros_like(flat).at[pos].add(vals)
    out = q_flat * _const(sched.self_scale, dt)[idx]
    if aligned and indices is None:
        raise ValueError("aligned=True requires caller-provided indices "
                         "(identical on every rank)")
    for rnd in sched.rounds:
        sv = vals * _const(rnd.send_scale, dt)[idx]
        rv = lax.ppermute(sv, axis_name, rnd.pairs)
        # Aligned indices are identical everywhere: scatter at our own pos
        # instead of shipping k int32s per edge that equal it anyway.
        rp = pos if aligned else lax.ppermute(pos, axis_name, rnd.pairs)
        out = out.at[rp].add(rv)
    out = out.reshape(x.shape)
    if return_sent:
        return out, q_flat.reshape(x.shape)
    return out


def dynamic_sparse_neighbor_allreduce(
        x: jnp.ndarray, step: jnp.ndarray, sched: DynamicSchedule,
        axis_name: str, *, indices: jnp.ndarray,
        valid: jnp.ndarray = None, return_sent: bool = False):
    """Sparse (aligned rotating-block) gossip over a PER-STEP topology.

    The dynamic counterpart of :func:`sparse_neighbor_allreduce`: the
    phase — which edges are live this round — is chosen by ``lax.switch``
    on the traced ``step`` exactly as in
    :func:`dynamic_neighbor_allreduce`, and within the chosen phase the
    payload is the caller's ``(k,)`` aligned index block (identical on
    every rank, typically step-rotating).  A one-peer dynamic phase has a
    single edge, so the wire bytes per round drop from ``4 * x.size`` to
    ``k * 4`` — the compression the flagship dynamic-Exp2 configuration
    runs under ``compression='sparse:<frac>'``.

    Only the aligned-indices mode exists here: per-rank magnitude picks
    are provably non-convergent under the stateless per-round residual
    (see the static op's docstring), and aligned blocks are the only mode
    the optimizer family emits.  ``return_sent=True`` additionally
    returns the dense representation ``q`` of the outgoing payload for
    the residual ``x - q``; ``q`` is phase-independent (it depends only
    on ``indices``) but is computed inside each branch so the whole
    exchange stays one ``lax.switch``.
    """
    def make_branch(ph: StaticSchedule):
        def branch(ops):
            xx, pos = ops
            return sparse_neighbor_allreduce(
                xx, ph, axis_name, indices=pos, valid=valid,
                aligned=True, return_sent=True)
        return branch
    out, q = lax.switch(step % sched.period,
                        [make_branch(ph) for ph in sched.phases],
                        (x, indices))
    if return_sent:
        return out, q
    return out


def neighbor_allreduce_matrix(x, w: jnp.ndarray, sched: StaticSchedule,
                              axis_name: str):
    """Neighbor averaging with a *traced* (n, n) weight matrix ``w``, of an
    array or of a pytree of arrays (one pipeline: :func:`_apply_rounds`).

    The permutation structure (which edges exist) is static and comes from
    ``sched``; the weights are a runtime argument, so per-step weight mutation
    — the reference's ``opt.self_weight / opt.neighbor_weights`` dynamic knobs
    (README.rst:110-127) — changes no compiled code.  ``w[s, d]`` scales the
    ``s -> d`` edge; ``w[i, i]`` is the self weight.
    """
    return _apply_rounds(x, sched, axis_name, _axis_index(axis_name), w)


def dynamic_neighbor_allreduce(x, step: jnp.ndarray, sched: DynamicSchedule,
                               axis_name: str):
    """Neighbor averaging whose topology changes every step.

    ``step`` is a traced scalar; the phase is chosen by ``lax.switch`` over the
    schedule's period, so the op compiles once and never renegotiates — this
    replaces the reference's per-step send/recv-list plumbing
    (``mpi_controller.cc:418-454``) and its stop-the-world topology handshake.

    ``x`` is an array or a pytree of arrays (the parts of one exchange:
    large leaves and packed buffers).  The phase is chosen ONCE for the whole
    tree and each branch is the pipeline of :func:`_apply_rounds` over that
    phase's edges, which orders the parts, cuts the large ones and chains
    the adds: the scheduler moves no operation across a ``conditional``, so
    only inside one branch can one part's scale and add run under another's
    permute, and nothing in front of the switch runs under any.  This is
    the form for a caller who has only a
    traced counter: the eager op, and ``functional.step_fn`` under a ``jit``
    of the caller's own.  The optimizer classes know the counter on the host
    and do without the switch: one program per phase over that phase's
    :class:`StaticSchedule` (``optim/optimizers.py``).
    """
    idx = _axis_index(axis_name)
    branches = [partial(_apply_rounds, sched=ph, axis_name=axis_name, idx=idx)
                for ph in sched.phases]
    return lax.switch(step % sched.period, branches, x)


def _slot_tables(sched: StaticSchedule) -> list:
    """Per-round output slot tables for ordered concat — now cached on the
    schedule itself (``StaticSchedule.slot_tables``), so repeated retraces
    of ``neighbor_allgather`` against one schedule don't rebuild
    O(rounds·n) Python tables each time.  Kept as a thin delegate for
    callers/tests addressing the historical name."""
    return list(sched.slot_tables)


def neighbor_allgather(x: jnp.ndarray, sched: StaticSchedule,
                       axis_name: str) -> jnp.ndarray:
    """Gather in-neighbor tensors, stacked along a new leading axis.

    Output shape is ``(max_indegree, *x.shape)`` with neighbors in ascending
    src-rank order; ranks with smaller indegree see zero padding in the tail
    slots (SPMD needs uniform shapes — the reference's ragged
    ``indegree * dim0`` output shape only works because each MPI rank owns its
    own allocation).  Unweighted: raw neighbor tensors, matching
    ``bf.neighbor_allgather`` (``torch/mpi_ops.py:364``).
    """
    idx = _axis_index(axis_name)
    k = max(sched.max_indegree, 1)
    out = jnp.zeros((k,) + x.shape, dtype=x.dtype)
    for rnd, slots in zip(sched.rounds, sched.slot_tables):
        recv = lax.ppermute(x, axis_name, rnd.pairs)  # zeros when silent
        slot = jnp.maximum(_const(slots, jnp.int32)[idx], 0)
        out = lax.dynamic_update_index_in_dim(
            out, lax.dynamic_index_in_dim(out, slot, 0, keepdims=False) + recv,
            slot, 0)
    return out


def pair_gossip(x: jnp.ndarray, sched: PairGossipSchedule,
                axis_name: str) -> jnp.ndarray:
    """Two-rank exchange-and-average (reference ``MPI_Sendrecv`` gossip,
    ``mpi_controller.cc:748-774``).  Ranks without a partner pass through."""
    dt = x.dtype
    idx = _axis_index(axis_name)
    rnd = sched.round
    out = x * _const(sched.self_scale, dt)[idx]
    return out + lax.ppermute(x * _const(rnd.send_scale, dt)[idx],
                              axis_name, rnd.pairs)


# ---------------------------------------------------------------------------
# Hierarchical family (2-axis mesh: machine x local)
# ---------------------------------------------------------------------------

def _shard_pad(x: jnp.ndarray, parts: int):
    flat = x.reshape(-1)
    pad = (-flat.size) % parts
    if pad:
        flat = jnp.concatenate([flat, jnp.zeros((pad,), flat.dtype)])
    return flat, pad


def _machine_combine(s: jnp.ndarray, sched: StaticSchedule, machine_axis: str):
    return _apply_rounds(s, sched, machine_axis, _axis_index(machine_axis))


def _hierarchical(x: jnp.ndarray, combine, local_axis: str) -> jnp.ndarray:
    """Bandwidth-optimal hierarchical averaging skeleton.

    reduce_scatter over the local (ICI) axis so each local rank owns a
    ``1/local_size`` shard of the machine sum, run the machine-level neighbor
    combine on shards only (DCN traffic = tensor size, not
    ``local_size x`` tensor size), then all_gather the combined shards back.
    Equivalent to the reference's local-allreduce -> local-rank-0 exchange ->
    local-bcast pipeline (``mpi_controller.cc:455-515``) including its
    divide-by-local_size-after-combine averaging order
    (``torch/mpi_ops.cc:416-419``).
    """
    local_size = lax.axis_size(local_axis)
    flat, _pad = _shard_pad(x, local_size)
    shard = lax.psum_scatter(flat, local_axis, tiled=True)
    combined = combine(shard)
    full = lax.all_gather(combined, local_axis, tiled=True)
    full = full[: x.size].reshape(x.shape)
    return full / local_size


def hierarchical_neighbor_allreduce(x: jnp.ndarray, sched: StaticSchedule,
                                    local_axis: str,
                                    machine_axis: str) -> jnp.ndarray:
    """Machine-level neighbor averaging: machines are super-nodes, weights in
    ``sched`` index machines (compile with the machine topology)."""
    return _hierarchical(
        x, lambda s: _machine_combine(s, sched, machine_axis), local_axis)


def dynamic_hierarchical_neighbor_allreduce(
        x: jnp.ndarray, step: jnp.ndarray, sched: DynamicSchedule,
        local_axis: str, machine_axis: str) -> jnp.ndarray:
    """Hierarchical averaging with a per-step machine topology (e.g.
    ``GetExp2DynamicSendRecvMachineRanks`` phases)."""
    def combine(s):
        idx = _axis_index(machine_axis)
        branches = [partial(_apply_rounds, sched=ph, axis_name=machine_axis,
                            idx=idx) for ph in sched.phases]
        return lax.switch(step % sched.period, branches, s)
    return _hierarchical(x, combine, local_axis)


# ---------------------------------------------------------------------------
# Two-level hierarchical gossip (dense ICI inner x sparse DCN outer)
# ---------------------------------------------------------------------------

def hierarchical_gossip(x: jnp.ndarray, step: jnp.ndarray,
                        inner_sched: StaticSchedule,
                        outer_scheds, *, local_axis: str,
                        machine_axis: str, outer_every: int = 1,
                        outer_compression: str = "none",
                        outer_frac: float = None) -> jnp.ndarray:
    """Two-level gossip step (``topology.HierarchicalTopology`` executor).

    Every step runs the DENSE intra-slice neighbor combine over the local
    (ICI) mesh axis; every ``outer_every``-th step additionally runs the
    SPARSE one-peer exchange over the machine (DCN) axis — phase selected
    by ``lax.switch``, so the whole period compiles into one program.

    Per-level compression applies to the OUTER level only (the inner level
    always ships dense over ICI):

      ``bf16``          — the exchanged payload crosses DCN as bfloat16;
          the local quantization residual ``y - q(y)`` is re-added after
          the mix (difference compression — a rank's own f32 values are
          never truncated by its own round trip).
      ``sparse:<frac>`` — only a step-ROTATING aligned index block of
          ``ceil(frac * size)`` coordinates crosses DCN; within the block
          the exchange is exact dense gossip, off-block coordinates keep
          their local values untouched, and the rotation sweeps every
          coordinate each ``ceil(1/frac)`` outer steps (the block-
          coordinate-gossip scheme of ``sparse_neighbor_allreduce`` —
          aligned blocks, not per-rank magnitude picks, because the
          latter provably stall).  The outer PHASE is held for a full
          block sweep so every coordinate sees every shift distance
          (``HierarchicalTopology.outer_phase_index``).

    Cadence is a ``lax.cond`` on the traced step — one compiled program
    serves outer and inner-only steps alike.
    """
    idx_l = _axis_index(local_axis)
    y = _apply_rounds(x, inner_sched, local_axis, idx_l)
    if not outer_scheds:
        return y
    step = jnp.asarray(step, jnp.int32)
    dt = x.dtype
    idx_m = _axis_index(machine_axis)
    k = max(1, int(outer_every))
    outer_step = step // k
    nphases = len(outer_scheds)
    sparse = isinstance(outer_compression, str) and \
        outer_compression.startswith("sparse")

    if sparse:
        if outer_frac is None:
            raise ValueError("sparse outer compression needs outer_frac")
        size = int(np.prod(x.shape))
        kk = max(1, int(np.ceil(outer_frac * size)))
        nblocks = max(1, -(-size // kk))  # ceil(size / kk)
        rot = (jnp.arange(kk, dtype=jnp.int32)
               + (outer_step % nblocks) * kk) % size
        phase_idx = (outer_step // nblocks) % nphases

        def make_branch(ph: StaticSchedule):
            if len(ph.rounds) != 1:
                raise ValueError(
                    "sparse outer compression expects one-round outer "
                    f"phases (a pure slice shift), got {len(ph.rounds)}")
            rnd = ph.rounds[0]

            def br(y):
                flat = y.reshape(-1)
                vals = flat[rot]
                sv = vals * _const(rnd.send_scale, dt)[idx_m]
                rv = lax.ppermute(sv, machine_axis, rnd.pairs)
                self_sc = _const(ph.self_scale, dt)[idx_m]
                # On the block: theta*vals + recv; off-block: untouched.
                return flat.at[rot].add(
                    (self_sc - 1.0) * vals + rv).reshape(y.shape)
            return br
    else:
        phase_idx = outer_step % nphases

        def make_branch(ph: StaticSchedule):
            def br(y):
                if outer_compression == "bf16":
                    q = y.astype(jnp.bfloat16)
                    mixed = _apply_rounds(q, ph, machine_axis,
                                          idx_m).astype(dt)
                    return mixed + (y - q.astype(dt))
                return _apply_rounds(y, ph, machine_axis, idx_m)
            return br

    branches = [make_branch(ph) for ph in outer_scheds]

    def with_outer(y):
        return lax.switch(phase_idx, branches, y)
    if k == 1:
        return with_outer(y)
    return lax.cond(step % k == 0, with_outer, lambda y: y, y)
