"""One-sided window ops: the async gossip family.

TPU has no remote-memory-access over ICI, so the reference's MPI RMA windows
(``mpi_context.h:41-115``, ``mpi_controller.cc:796-1184``) and NCCL passive-
recv service (``nccl_controller.cc:1113-1238``) are re-designed as a host-side
window store: per-rank main buffers plus one staging buffer per in-neighbor
edge, with per-rank mutexes, version counters and the associated-P scalar
vector (push-sum weights, ``mpi_context.cc:136-156``).  Puts/gets/accumulates
run asynchronously on a worker pool (the honest analogue of the reference's
nonblocking RMA + finalizer threads); ``win_update`` synchronizes and performs
the weighted in-place combine exactly like ``DoWinSync`` + ``AvgWithNeighbor``
(``torch/mpi_win_ops.cc:345-428``).

Semantics preserved from the reference (test oracle:
``test/torch_win_ops_test.py``):
  * ``win_put(t, name, dst_weights)`` overwrites dst's buffer-for-me with
    ``w * t``; ``win_accumulate`` adds instead; ``win_get(name, src_weights)``
    pulls ``w * main[src]`` into my buffer-for-src.
  * ``win_update`` combines self memory with in-neighbor buffers (topology
    weights if weighted, else uniform ``1/(indeg+1)``) and writes the result
    back to self memory.  ``win_update_then_collect`` sums with weight 1 and
    zeroes the staging buffers (push-sum collect).
  * mutexes serialize concurrent writers per rank; version counters expose
    per-edge staleness; associated-P mirrors every put/accumulate/update on a
    scalar so push-sum can de-bias.

Single-process runs use the process-global store directly (the eager API is
single-controller: all ranks live in this process).  Multi-process runs keep
the same API but split authority by *rank ownership*: each process is
authoritative for the ranks of its local devices; one-sided edges whose
target rank lives in another process travel over the DCN TCP transport
(``ops/transport.py`` + ``native/src/winsvc.cc``) and are applied by the
owner's drain thread with identical observable semantics — versions, mutex,
associated-P (the structural analogue of the reference's passive-recv
service, ``nccl_controller.cc:1113-1238``).  ``win_fence`` provides the
epoch synchronization (parity: ``torch/mpi_win_ops.cc:608-646``).
"""

from __future__ import annotations

import os
import threading
from concurrent.futures import Future, ThreadPoolExecutor
from contextlib import contextmanager
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from bluefog_tpu.utils import config

__all__ = [
    "win_create", "win_free", "win_put", "win_put_nonblocking",
    "win_get", "win_get_nonblocking", "win_accumulate",
    "win_accumulate_nonblocking", "win_update", "win_update_then_collect",
    "win_wait", "win_poll", "win_mutex", "win_fence", "win_flush",
    "get_win_version",
    "win_state_dict", "win_load_state_dict",
    "get_current_created_window_names", "win_associated_p",
    "turn_on_win_ops_with_associated_p", "turn_off_win_ops_with_associated_p",
    "configure_async", "async_armed", "set_async_step", "async_step_lag",
    "async_info", "win_fold_stale_residuals", "clear_async_staleness",
]

# Wire op codes live in ops.transport (single source of truth).  Field use:
#   GET_REQ    src=window src rank (owned by receiver), dst=requesting rank
#   GET_REPLY  src/dst as the originating GET_REQ; payload = main[src]
#   FENCE_REQ  src=requesting rank; FENCE_ACK echoes it back
#   MUTEX_ACQ  src=requesting rank, dst=rank whose mutex; GRANT echoes;
#   MUTEX_REL  src=requesting rank, dst=rank whose mutex
from bluefog_tpu.ops.transport import (  # noqa: E402
    OP_PUT, OP_ACCUMULATE, OP_GET_REQ, OP_GET_REPLY, OP_FENCE_REQ,
    OP_FENCE_ACK, OP_MUTEX_ACQ, OP_MUTEX_GRANT, OP_MUTEX_REL, OP_MEMBER,
    OP_GANG, OP_BF16_FLAG, OP_SPARSE_FLAG, OP_TRACE_FLAG, OP_FLAG_MASK,
    make_trace_tag, trace_strip, sparse_encode, sparse_decode)
from bluefog_tpu.utils import flightrec, linkobs  # noqa: E402
# Zero-copy XLA put path (BLUEFOG_TPU_WIN_XLA): plan-compiled dispatch of
# remote put edges straight from the device buffer into the native
# per-peer arenas, plus the host-staging-copy accounting helpers.
from bluefog_tpu.ops import xlaffi  # noqa: E402

# Hard cap on waiting for a peer's reply.  Env-overridable so fault-injection
# tests (and impatient deployments) can bound partition detection; the
# reference's equivalent knob is the MPI-level timeout its users set out of
# band.
_MSG_TIMEOUT_SEC = float(os.environ.get("BLUEFOG_TPU_WIN_TIMEOUT", "300"))


class _Window:
    """State of one named window — OWNED-SLICE layout.

    Every buffer is allocated only for the ranks this process owns and
    their in-edges: ``main``/``p_main``/``main_versions``/``mutexes`` are
    dicts keyed by owned rank, ``staging``/``p_staging``/``versions`` by
    ``(dst, src)`` edges with owned ``dst``.  Single-process runs own every
    rank, so the layout degenerates to the full rank-major state; in
    multi-process runs per-window RSS is O(owned + indegree) instead of the
    O(n) rank-major arrays plus O(n²) version matrix a pod-scale world
    cannot afford (round-3 VERDICT Weak #4).

    ``layout`` records the CALLER-side array convention: ``"rank"`` windows
    take and return rank-major ``(n, ...)`` arrays (non-owned rows ignored
    on input, zero-filled on output); ``"owned"`` windows (multi-process
    only) take and return ``(len(owned), ...)`` arrays — row ``i`` is rank
    ``owned[i]`` — so no O(n) array ever materializes."""

    def __init__(self, name: str, tensor: np.ndarray, in_nbrs: List[List[int]],
                 out_nbrs: List[List[int]], zero_init: bool,
                 owned: List[int], layout: str):
        n = len(in_nbrs)
        self.name = name
        self.n = n
        self.shape = tensor.shape[1:]
        self.dtype = tensor.dtype
        self.in_nbrs = in_nbrs
        self.out_nbrs = out_nbrs
        self.owned = list(owned)
        self.layout = layout
        # rank -> row index in caller-side arrays (identity for rank-major)
        self.row_of = ({r: r for r in range(n)} if layout == "rank"
                       else {r: i for i, r in enumerate(self.owned)})
        # main[r]: rank r's exposed memory (win_get source, win_update self
        # term) — owned ranks only.
        self.main: Dict[int, np.ndarray] = {
            r: tensor[self.row_of[r]].copy() for r in self.owned}
        # staging[(dst, src)]: data src pushed toward dst (or dst pulled
        # from src) — edges into owned ranks only; a non-owned dst's
        # staging lives at its owner.
        self.staging: Dict[tuple, np.ndarray] = {}
        for dst in self.owned:
            for src in in_nbrs[dst]:
                if zero_init:
                    init = np.zeros(self.shape, self.dtype)
                elif layout == "rank":
                    # Neighbor's initial value, from the (process-identical)
                    # rank-major creation tensor.
                    init = tensor[src].copy()
                else:  # owned layout has no non-owned rows to seed from
                    raise ValueError(
                        "owned-layout windows require zero_init=True (the "
                        "creation tensor carries no neighbor rows to seed "
                        "staging with)")
                self.staging[(dst, src)] = init
        # versions[(dst, src)]: puts into the slot since the last update.
        self.versions: Dict[tuple, int] = {k: 0 for k in self.staging}
        # Counts self-publishes to main[r] (win_put's self_weight scaling):
        # a publish landing mid-combine serializes AFTER the update — the
        # swap must not clobber it with the pre-publish combine result.
        self.main_versions: Dict[int, int] = {r: 0 for r in self.owned}
        self.mutexes: Dict[int, threading.RLock] = {
            r: threading.RLock() for r in self.owned}
        self.lock = threading.RLock()           # store-structure lock
        # Serializes whole win_update calls against each other (snapshot →
        # combine → swap must not interleave between two updates, or one
        # update's swap would mis-read the other's version resets).  The
        # drain thread never takes this lock — puts stay concurrent with
        # the combine, which is the point of the lock split.
        self.update_lock = threading.Lock()
        # associated-P scalars (push-sum weights); self starts at 1.0
        self.p_main: Dict[int, float] = {r: 1.0 for r in self.owned}
        self.p_staging: Dict[tuple, float] = {k: 0.0 for k in self.staging}
        # Receiver-side stale-contribution store (BLUEFOG_TPU_ASYNC
        # bounded staleness): value/P mass the staleness policy diverted
        # away from staging instead of dropping, keyed by the same
        # (dst, src) edges.  Folded back into staging at the periodic
        # exact collect (win_fold_stale_residuals) so push-sum mass
        # conservation holds: staging + stale residual + wire-in-flight
        # always equals the mass senders put on the wire.  Empty (and
        # never touched) outside async mode.
        self.stale_residual: Dict[tuple, np.ndarray] = {}
        self.p_stale_residual: Dict[tuple, float] = {}


class _Distrib:
    """Multi-process window state: DCN transport + rank-ownership directory.

    ``rank_owner[r]`` is the process index authoritative for rank ``r``;
    ``proc_addr[p]`` is process ``p``'s (host, port) transport endpoint."""

    def __init__(self, transport, rank_owner: Dict[int, int],
                 proc_addr: Dict[int, tuple], my_proc: int):
        self.transport = transport
        self.rank_owner = rank_owner
        self.proc_addr = proc_addr
        self.my_proc = my_proc
        self.my_rank = min(r for r, p in rank_owner.items() if p == my_proc)
        self.cv = threading.Condition()
        self.pending_gets: Dict[tuple, int] = {}   # (name, dst, src) -> n
        self.fence_acks = 0
        # Striped-transport fan-out counting (guarded by cv): FENCE_REQ
        # and MUTEX_REL ride EVERY stripe of a peer (each stripe is an
        # independent FIFO, so only the full set certifies that all data
        # sent before them has drained); the copies carry their fan-out
        # width in the wire `weight` field plus a sender-side SERIAL in
        # `p_weight`, and the receiver acts on the LAST copy of the
        # NEWEST serial.  The serial makes a partially-delivered fan-out
        # (one stripe's copy lost to a send failure) harmless: its stale
        # leftover count can never complete a LATER fan-out early —
        # copies of an older serial are discarded, a newer serial resets
        # the count.  Keys: requesting rank (fence) / (name, rank,
        # requester) (mutex release); values: (serial, copies seen).
        self.fence_req_seen: Dict[int, tuple] = {}
        self.rel_seen: Dict[tuple, tuple] = {}
        self.fanout_serial = 0  # monotonic per process, guarded by cv
        # remote-mutex bookkeeping.  grant_events is safe keyed on
        # (name, rank) because mutex_serial allows one outstanding ACQ per
        # (name, rank) per process; different processes land in distinct
        # remote_holds entries (keyed by requester rank).
        self.grant_events: Dict[tuple, threading.Event] = {}  # (name, rank)
        self.remote_holds: Dict[tuple, threading.Event] = {}  # (name, rank, req)
        self.mutex_serial: Dict[tuple, threading.Lock] = {}   # (name, rank)
        # inbound messages for windows not yet created locally (SPMD skew)
        self.parked: Dict[str, list] = {}


class _WindowStore:
    def __init__(self):
        self.windows: Dict[str, _Window] = {}
        self.lock = threading.RLock()
        self.pool = ThreadPoolExecutor(max_workers=8,
                                       thread_name_prefix="bf-win")
        # Inbound service work (GET replies, fence acks) runs on its own
        # executor: user ops on `pool` BLOCK waiting for peers' replies, so
        # servicing replies from the same pool could deadlock both sides
        # until timeout when the pool is saturated with blocked user ops.
        self.svc_pool = ThreadPoolExecutor(max_workers=4,
                                           thread_name_prefix="bf-win-svc")
        self.handles: Dict[int, Future] = {}
        self.next_handle = 0
        self.associated_p_enabled = False
        self.distrib: Optional[_Distrib] = None
        # Messages that arrived between the listener going live and the
        # directory being installed (peers can finish init_transport's
        # allgather earlier than us and start sending immediately).
        self.preinit_msgs: list = []

    def get(self, name: str) -> _Window:
        with self.lock:
            if name not in self.windows:
                raise KeyError(f"window {name!r} does not exist")
            return self.windows[name]

    def submit(self, fn) -> int:
        from bluefog_tpu import basics
        from bluefog_tpu.utils import telemetry
        basics._require_active()  # suspended sessions reject new async work
        with self.lock:
            h = self.next_handle
            self.next_handle += 1
            self.handles[h] = self.pool.submit(fn)
            telemetry.set_gauge("bf_win_inflight_handles", len(self.handles))
            return h


_store = _WindowStore()


def _any_window_exists() -> bool:
    return bool(_store.windows)


def _drain_handles(timeout: float = 60.0) -> bool:
    """Wait for every outstanding nonblocking window op (``bf.suspend``
    quiesce step).  Returns False if any op is still in flight at timeout —
    op *errors* are left for the owning ``win_wait`` to surface."""
    import time as _time
    from concurrent.futures import TimeoutError as _FutTimeout
    with _store.lock:
        futures = list(_store.handles.values())
    deadline = _time.monotonic() + timeout  # one budget for ALL handles
    drained = True
    for f in futures:
        try:
            f.result(timeout=max(0.0, deadline - _time.monotonic()))
        except _FutTimeout:
            drained = False
        except Exception:
            pass  # the owning win_wait will surface the error
    return drained


def _free_all_windows() -> None:
    d = _store.distrib
    unreg = getattr(d.transport, "unregister_window", None) \
        if d is not None else None
    with _store.lock:
        for f in _store.handles.values():
            f.cancel()
        _store.handles.clear()
        if unreg is not None:
            for n in _store.windows:
                unreg(n)
        _store.windows.clear()
    _drop_ef_residuals()


def _shutdown_transport() -> None:
    d = _store.distrib
    _store.distrib = None
    if d is not None:
        from bluefog_tpu.utils import stall
        stall.set_peer_probe(None)
        # Cached XLA put plans route onto this transport's native sender;
        # they must die before it does (a later re-init builds fresh ones
        # keyed on the new directory).
        xlaffi.invalidate()
        d.transport.stop()
        # No transport, no edges: per-edge staleness gauges describing a
        # dead wire must not linger as live series (churn hygiene class),
        # and the async per-peer step/age estimates describe peers that
        # no longer exist.  The gang join/directory service rode this
        # transport too — uninstall it so a later re-init starts clean.
        from bluefog_tpu.ops import gang as _gang
        _gang.install(None)
        clear_contribution_age()
        clear_async_staleness()
        linkobs.clear_all()


def _to_numpy(x) -> np.ndarray:
    from bluefog_tpu.utils import telemetry
    try:
        out = np.asarray(jax.device_get(x))
    except RuntimeError:
        # Multi-host sharded array: assemble the addressable rows; rows of
        # ranks owned elsewhere are zero-filled and never read (only owned
        # rows feed edge sends and self-scaling).
        x = jnp.asarray(x)
        out = np.zeros(x.shape, dtype=np.dtype(x.dtype.name))
        for shard in x.addressable_shards:
            out[shard.index] = np.asarray(shard.data)
        xlaffi.count_host_copy(out.nbytes, "device_get")
        return out
    # Host-staging accounting (verified by pointer identity: CPU-backend
    # jax aliases the buffer and counts nothing): the device_get copy is
    # the first of the staging copies the XLA put path eliminates.
    if telemetry.enabled() and xlaffi._materialize_copied(x, out):
        xlaffi.count_host_copy(out.nbytes, "device_get")
    return out


# ---------------------------------------------------------------------------
# Multi-process plumbing (rank ownership + DCN transport)
# ---------------------------------------------------------------------------

def _owns(rank: int) -> bool:
    d = _store.distrib
    return d is None or d.rank_owner[rank] == d.my_proc


def _owned_ranks(n: int) -> List[int]:
    d = _store.distrib
    if d is None:
        return list(range(n))
    return [r for r in range(n) if d.rank_owner[r] == d.my_proc]


def _local_host_addr() -> str:
    """This process's DCN-reachable address for the window transport."""
    import socket
    override = os.environ.get("BFTPU_WIN_HOST")
    if override:
        return override
    coord = os.environ.get("BFTPU_COORDINATOR")
    if coord and ":" in coord:
        # Learn the interface that routes to the coordinator (UDP trick:
        # no packet is sent, the kernel just picks the route).
        try:
            host, port = coord.rsplit(":", 1)
            with socket.socket(socket.AF_INET, socket.SOCK_DGRAM) as s:
                s.connect((host, int(port)))
                return s.getsockname()[0]
        except OSError:
            pass
    try:
        return socket.gethostbyname(socket.gethostname())
    except OSError:
        return "127.0.0.1"


# Monotonic namespace for the coordinator-KV endpoint exchange: KV keys
# are write-once, and an SPMD re-init must not collide with the previous
# incarnation's entries.  Every process calls init_transport the same
# number of times (it is an SPMD call), so the counters agree.
_kv_exchange_generation = 0


def _exchange_endpoints(me: str, n_procs: int, my_proc: int) -> list:
    """All processes' transport endpoints (``host:port`` strings, index =
    process id).

    Prefers the jax distributed coordinator's key-value store — pure gRPC,
    so it works even where the backend cannot run multi-process XLA
    computations (CPU gangs), and exactly when a churn/chaos gang must
    bootstrap without a collective.  Falls back to the legacy
    ``process_allgather`` path when no coordinator client is up or the KV
    store misbehaves."""
    global _kv_exchange_generation
    client = None
    try:
        from jax._src import distributed as _dist
        client = getattr(_dist.global_state, "client", None)
    except Exception:  # noqa: BLE001 — private API; absence = fallback
        client = None
    if client is not None:
        gen = _kv_exchange_generation
        _kv_exchange_generation += 1
        try:
            client.key_value_set(f"bf/win_addr/{gen}/{my_proc}", me)
            return [client.blocking_key_value_get(
                f"bf/win_addr/{gen}/{p}", 120_000)
                for p in range(n_procs)]
        except Exception as e:  # noqa: BLE001 — degrade to the collective
            from bluefog_tpu.utils.logging import get_logger
            get_logger().warning(
                "window transport: coordinator-KV endpoint exchange failed "
                "(%s); falling back to the collective allgather", e)
    raw = me.encode()
    if len(raw) > 64:
        raise ValueError(f"transport address too long: {raw!r}")
    buf = np.zeros(64, np.uint8)
    buf[:len(raw)] = np.frombuffer(raw, np.uint8)
    from jax.experimental import multihost_utils
    gathered = np.asarray(multihost_utils.process_allgather(buf))
    return [bytes(gathered[p]).rstrip(b"\0").decode()
            for p in range(gathered.shape[0])]


def make_transport(port: int = 0):
    """One window transport wired to this store's apply callbacks but with
    no rank directory yet — the raw listener a coordinator-free bootstrap
    (``ops/gang.py``) builds before it knows who its peers are.  Inbound
    data messages buffer in ``preinit_msgs`` until ``install_distrib``;
    OP_GANG control frames are consumed immediately (a joining process
    receives its grant here)."""
    from bluefog_tpu.ops.transport import WindowTransport
    return WindowTransport(_apply_inbound,
                           apply_batch=_apply_inbound_batch,
                           apply_items=_apply_inbound_items, port=port)


def install_distrib(transport, rank_owner: Dict[int, int],
                    proc_addr: Dict[int, tuple], my_proc: int) -> None:
    """Install the multi-process rank directory over a live transport and
    replay any messages that raced ahead of it — the shared tail of every
    bootstrap path (coordinator KV, allgather, or the gang directory)."""
    with _store.lock:
        # Install the directory and replay messages that raced ahead of it
        # under one lock hold, so the drain thread (blocked on this lock in
        # its preinit check) cannot interleave a newer message first.
        _store.distrib = _Distrib(transport, dict(rank_owner),
                                  dict(proc_addr), my_proc)
        pending, _store.preinit_msgs = _store.preinit_msgs, []
        for msg in pending:
            _apply_inbound(*msg)
    # Stall warnings can now name unreachable peers (reference
    # ``operations.cc:417-429`` lists missing ranks per stalled tensor).
    from bluefog_tpu.utils import stall
    stall.set_peer_probe(_probe_missing_ranks)
    # Barrier-free async mode (BLUEFOG_TPU_ASYNC): arm the bounded-
    # staleness fold with the transport — with the knob off this is one
    # config check and the flag stays False (bitwise legacy paths).
    configure_async()


def init_transport() -> bool:
    """Start the DCN window transport and exchange the rank directory.

    Called by ``basics.init_distributed()`` when the world spans processes
    (and directly by chaos-gang workers that skip the collective init).
    The per-process (host, port) endpoint rides the coordinator's KV store
    when available, else a ``process_allgather`` — replacing the
    reference's MPI control plane for window bootstrap
    (``nccl_controller.cc:1240-1286``)."""
    from bluefog_tpu import basics
    if _store.distrib is not None:
        return True
    if jax.process_count() == 1:
        return False
    transport = make_transport()
    me = f"{_local_host_addr()}:{transport.port}"
    addrs = _exchange_endpoints(me, jax.process_count(),
                                jax.process_index())
    proc_addr = {}
    for p, addr in enumerate(addrs):
        host, _, port = addr.rpartition(":")
        proc_addr[p] = (host, int(port))
    rank_owner = {i: d.process_index
                  for i, d in enumerate(basics._ctx.devices)}
    install_distrib(transport, rank_owner, proc_addr, jax.process_index())
    return True


def _probe_missing_ranks(timeout: float = 1.0) -> List[int]:
    """Ranks whose owning process's transport endpoint does not accept a TCP
    connection — the liveness source for stall warnings.  Peers are probed
    concurrently so a sweep costs max(timeout), not sum over dead hosts."""
    import socket
    d = _store.distrib
    if d is None:
        return []

    def reachable(addr) -> bool:
        try:
            socket.create_connection(addr, timeout=timeout).close()
            return True
        except OSError:
            return False

    peers = [(p, addr) for p, addr in sorted(d.proc_addr.items())
             if p != d.my_proc]
    if not peers:
        return []
    with ThreadPoolExecutor(max_workers=min(16, len(peers)),
                            thread_name_prefix="bf-stall-probe") as pool:
        alive = list(pool.map(lambda pa: reachable(pa[1]), peers))
    missing: List[int] = []
    for (p, _), ok in zip(peers, alive):
        if not ok:
            missing.extend(r for r, owner in d.rank_owner.items()
                           if owner == p)
    from bluefog_tpu.utils import telemetry
    telemetry.inc("bf_win_peer_probes_total")
    telemetry.set_gauge("bf_win_unreachable_peers", len(missing))
    return sorted(missing)


_BF16 = np.dtype(jnp.bfloat16)

# Sender-side error-feedback residuals of the sparse:<frac> codec, keyed by
# (window name, src, dst) edge: the un-sent complement of every
# sparsified row accumulates here and is folded into the NEXT send on the
# same edge, so the time-summed wire traffic carries the full mass and
# sparsification bias can never break consensus.  Guarded by its own lock
# (window ops run on a worker pool).
_ef_residuals: Dict[tuple, np.ndarray] = {}
_ef_lock = threading.Lock()


# Per-edge contribution-age extrema (seconds), keyed by src rank: the
# freshest/stalest gauges summarize what the per-src age histogram
# records sample by sample — the sensors a bounded-staleness async mode
# (ROADMAP item 4) will read to reject/downweight old contributions.
_age_lock = threading.Lock()
_age_minmax: Dict[int, list] = {}


def _note_trace_commit(name: str, src: int, tag, dst: int = -1) -> None:
    """One tagged contribution reached its staging slot: record its age
    (receiver wall clock minus the tag's origin wall clock — NTP-grade
    across hosts, exact on one host) into the per-src histogram + the
    freshest/stalest gauges, feed the link observatory's per-edge delay
    estimator (``dst`` = the receiving rank, when the caller knows it),
    and give the flight recorder its COMMIT event so the tag's chain
    ends where the state changed."""
    import time as _time
    from bluefog_tpu.utils import telemetry
    if _async.armed and len(tag) > 4 and tag[4] >= 0:
        # Every traced data commit feeds the freshest-peer-step estimate
        # (state, not telemetry): the put and pull families never route
        # through the accumulate-only staleness policy, but their
        # bf_async_step_lag must still see who runs ahead.
        with _async.lock:
            if tag[4] > _async.peer_step.get(src, -(1 << 62)):
                _async.peer_step[src] = int(tag[4])
    if flightrec.enabled():
        flightrec.note(flightrec.COMMIT, src=tag[0], dst=src, seq=tag[1],
                       name=name)
    linkobs.note_commit(src, dst, tag)
    if not telemetry.enabled():
        return
    age = max(0.0, (_time.time_ns() // 1000 - tag[3]) / 1e6)
    telemetry.observe("bf_win_contribution_age_seconds", age,
                      src=str(src))
    with _age_lock:
        mm = _age_minmax.get(src)
        if mm is None:
            mm = _age_minmax[src] = [age, age]
        else:
            mm[0] = min(mm[0], age)
            mm[1] = max(mm[1], age)
        lo, hi = mm
    telemetry.set_gauge("bf_win_contribution_freshest_age_seconds", lo,
                        src=str(src))
    telemetry.set_gauge("bf_win_contribution_stalest_age_seconds", hi,
                        src=str(src))


def clear_contribution_age(ranks=None) -> None:
    """Drop the per-edge age gauges for ``ranks`` (None = every edge) —
    churn hygiene: a dead peer's last-known ages must not linger as live
    series (the same orphan-gauge class ``drop_peer`` already clears for
    ``bf_win_tx_queue_depth``).  Histograms stay — they are monotonic
    counters, not state claims about a live edge."""
    from bluefog_tpu.utils import telemetry
    with _age_lock:
        targets = list(_age_minmax) if ranks is None else \
            [r for r in ranks if r in _age_minmax]
        for r in targets:
            _age_minmax.pop(r, None)
    for r in targets:
        telemetry.clear_gauge("bf_win_contribution_freshest_age_seconds",
                              src=str(r))
        telemetry.clear_gauge("bf_win_contribution_stalest_age_seconds",
                              src=str(r))


# ---------------------------------------------------------------------------
# Barrier-free async gossip: step clock + bounded-staleness policy
# (BLUEFOG_TPU_ASYNC / _STALENESS_STEPS / _STALENESS_POLICY)
# ---------------------------------------------------------------------------

class _AsyncGossip:
    """Process-wide state of the async window-gossip mode.

    ``armed`` is the single hot-path check every commit performs: with
    ``BLUEFOG_TPU_ASYNC=0`` (the default) it stays False and every data
    path is bit-identical to the lockstep tree.  The step clock
    (``step`` + the EWMA ``step_period``) is published by the window
    optimizer family each step; ``peer_step`` tracks the freshest origin
    step seen per in-neighbor (from sampled wire trace tags) and
    ``edge_age`` the last estimated age per edge — the estimate
    unsampled messages on the same edge inherit (staleness is a sender
    property: a straggler is persistently behind, so a 1/N sample tracks
    it)."""

    def __init__(self):
        self.lock = threading.Lock()
        self.armed = False
        self.staleness_steps = 0
        self.policy = ("reject", 0.0)
        self.step = 0
        self.step_period = 0.0          # EWMA seconds per local step
        self._last_step_mono = None
        self.peer_step: Dict[int, int] = {}
        self.edge_age: Dict[tuple, float] = {}


_async = _AsyncGossip()


def configure_async(enabled: Optional[bool] = None) -> bool:
    """(Re-)arm the async gossip mode from config (``enabled`` overrides
    ``BLUEFOG_TPU_ASYNC``); returns the armed state.  Disarming clears
    every estimate so a later re-arm starts fresh."""
    cfg = config.get()
    on = cfg.async_mode if enabled is None else bool(enabled)
    with _async.lock:
        _async.staleness_steps = int(cfg.async_staleness_steps)
        _async.policy = config.parse_staleness_policy(
            cfg.async_staleness_policy)
        _async.armed = on
        if not on:
            _async.peer_step.clear()
            _async.edge_age.clear()
            _async._last_step_mono = None
            _async.step_period = 0.0
    # Native drain-fold parity: the C decoder must stop folding
    # accumulates across PUT-headed entries exactly when the Python
    # decoder does (see _apply_data_run), or the policy would see
    # different granularity per hot path.
    from bluefog_tpu import native
    handle = native.lib()
    if handle is not None and hasattr(handle,
                                      "bf_winsvc_set_fold_across_put"):
        handle.bf_winsvc_set_fold_across_put(0 if on else 1)
    return on


def async_armed() -> bool:
    return _async.armed


def set_async_step(step: int) -> None:
    """Publish this process's training-step clock: staleness ages count
    against it, and both trace-tag encoders (the Python sender and the
    native XLA-plan path) stamp it into the wire trailer as the origin
    step, so receivers measure age in steps exactly."""
    import time as _time
    now = _time.monotonic()
    with _async.lock:
        prev, _async._last_step_mono = _async._last_step_mono, now
        _async.step = int(step)
        if prev is not None and now > prev:
            dt = now - prev
            _async.step_period = dt if _async.step_period == 0.0 \
                else 0.9 * _async.step_period + 0.1 * dt
    from bluefog_tpu.ops import transport as _transport
    _transport.set_trace_origin_step(step)
    # Step boundary: the link observatory refreshes divergence/rates and
    # evaluates SLO rules here (sync loops get the same tick from the
    # churn supervisor; calling both is harmless — breaches are latched).
    linkobs.on_step(step)


def async_step_lag() -> int:
    """My step vs the freshest-seen peer step (positive = I am behind the
    freshest peer; 0 when no peer origin step has been observed)."""
    with _async.lock:
        if not _async.peer_step:
            return 0
        return max(_async.peer_step.values()) - _async.step


def async_info() -> Optional[dict]:
    """The /healthz "async" block source: None unless the mode is armed."""
    with _async.lock:
        if not _async.armed:
            return None
        cfg = config.get()
        freshest = max(_async.peer_step.values(), default=None)
        return {
            "step": _async.step,
            "staleness_steps": _async.staleness_steps,
            "policy": cfg.async_staleness_policy,
            "collect_every": cfg.async_collect_every,
            "step_lag": (freshest - _async.step)
            if freshest is not None else 0,
            "step_period_sec": round(_async.step_period, 6),
            "peer_steps": dict(_async.peer_step),
        }


def _staleness_factor(name: str, key: tuple, tag) -> tuple:
    """Bounded-staleness decision for ONE arriving ACCUMULATE
    contribution (call with ``win.lock`` held): returns ``(keep, action)``
    where ``keep`` is the fraction entering staging and ``action`` is
    None (fresh — the caller must take the exact legacy arithmetic
    path), ``"reject"`` (keep == 0.0) or ``"downweight"``.

    Age in origin steps: exact when the message carried a trace tag with
    an origin step (my step clock minus the tag's step); a tag without a
    step clock falls back to wall-clock age converted through my own
    step period; an UNSAMPLED message inherits its edge's last sampled
    estimate (fresh until the first sample — the optimistic default, the
    periodic collect backstop covers what it misses)."""
    if not _async.armed:
        return 1.0, None
    src = key[1]
    with _async.lock:
        bound = _async.staleness_steps
        kind, alpha = _async.policy
        if tag is not None:
            o_step = tag[4] if len(tag) > 4 else -1
            if o_step >= 0:
                age = float(max(0, _async.step - o_step))
                if o_step > _async.peer_step.get(src, -(1 << 62)):
                    _async.peer_step[src] = int(o_step)
            else:
                import time as _time
                age_sec = max(0.0, (_time.time_ns() // 1000 - tag[3]) / 1e6)
                period = _async.step_period
                age = age_sec / period if period > 0 else 0.0
            _async.edge_age[(name,) + key] = age
        else:
            age = _async.edge_age.get((name,) + key, 0.0)
    if bound <= 0 or age <= bound:
        return 1.0, None
    if kind == "downweight":
        return alpha, "downweight"
    return 0.0, "reject"


def _divert_stale(win: _Window, key: tuple, contrib: np.ndarray,
                  p_mass: float, keep: float) -> None:
    """Move the non-admitted fraction of one stale contribution into the
    window's stale-residual store (call with ``win.lock`` held).
    ``contrib`` may be a zero-copy view into a transport buffer — the
    store always owns its arrays."""
    frac = 1.0 - keep
    add = contrib if keep == 0.0 else contrib * win.dtype.type(frac)
    res = win.stale_residual.get(key)
    if res is None:
        win.stale_residual[key] = np.array(add, dtype=win.dtype)
    else:
        res += add
    if _store.associated_p_enabled:
        win.p_stale_residual[key] = \
            win.p_stale_residual.get(key, 0.0) + frac * p_mass


def _note_stale(name: str, actions) -> None:
    """Telemetry for applied staleness decisions (outside ``win.lock`` —
    counters are not state)."""
    from bluefog_tpu.utils import telemetry
    if not telemetry.enabled():
        return
    for src, action in actions:
        telemetry.inc("bf_win_stale_rejected_total" if action == "reject"
                      else "bf_win_stale_downweighted_total",
                      src=str(src))


def win_fold_stale_residuals(name: Optional[str] = None) -> int:
    """Fold every stale-diverted contribution back into its staging slot
    (one window, or all).  Returns the number of edges folded.

    The async optimizer calls this right after its periodic
    ``win_fence`` (the ``BLUEFOG_TPU_ASYNC_COLLECT_EVERY`` backstop) and
    before the exact collect: post-fence nothing is in flight, so
    staging + these residuals is exactly the mass senders shipped — the
    collect that follows restores exact push-sum conservation including
    everything the staleness policy held back.  Residuals of edges that
    no longer exist (survivor re-plan dropped the edge) die with their
    window, same as staging from a dead peer."""
    with _store.lock:
        names = [name] if name is not None else list(_store.windows)
    folded = 0
    for nm in names:
        try:
            win = _store.get(nm)
        except KeyError:
            continue
        with win.lock:
            for key, res in list(win.stale_residual.items()):
                if key in win.staging:
                    win.staging[key] += res
                    win.versions[key] += 1
                    if _store.associated_p_enabled:
                        win.p_staging[key] += \
                            win.p_stale_residual.get(key, 0.0)
                    folded += 1
            win.stale_residual.clear()
            win.p_stale_residual.clear()
    return folded


def clear_async_staleness(ranks=None) -> None:
    """Drop the per-peer async staleness state for ``ranks`` (None = all)
    — churn hygiene, the same orphan-series class as
    :func:`clear_contribution_age`: a dead peer's last-known origin step
    must not keep inflating ``bf_async_step_lag``, and its per-src stale
    counters must not linger as live series."""
    from bluefog_tpu.utils import telemetry
    with _async.lock:
        if ranks is None:
            # Union of BOTH estimate stores: a src aged only through the
            # wall-clock fallback (no origin step) lives in edge_age but
            # never in peer_step — its counters must clear too.
            targets = sorted(set(_async.peer_step)
                             | {k[2] for k in _async.edge_age})
        else:
            targets = [int(r) for r in ranks]
        for r in targets:
            _async.peer_step.pop(r, None)
        for k in [k for k in _async.edge_age if k[2] in targets]:
            _async.edge_age.pop(k, None)
    for r in targets:
        telemetry.clear_counter("bf_win_stale_rejected_total", src=str(r))
        telemetry.clear_counter("bf_win_stale_downweighted_total",
                                src=str(r))


def _drop_ef_residuals(name: Optional[str] = None) -> None:
    """Forget sender residuals (all windows, or one freed window's) —
    Python dict AND the native XLA-put twin (plus that path's cached
    plans, whose edge routing dies with the window)."""
    with _ef_lock:
        if name is None:
            _ef_residuals.clear()
        else:
            for k in [k for k in _ef_residuals if k[0] == name]:
                _ef_residuals.pop(k, None)
    xlaffi.invalidate(name)


def _sparse_payload(name: str, src: int, dst: int,
                    payload: np.ndarray, frac: float) -> np.ndarray:
    """Top-|magnitude| sparsification with error feedback for one edge.

    The residual from the previous send on this (name, src, dst) edge is
    added before selection, the top ``ceil(frac * size)`` entries of the
    corrected row ship (bit-exact f32 values), and the complement becomes
    the new residual — classic EF-SGD compression applied at the wire."""
    flat = payload.reshape(-1)
    key = (name, src, dst)
    # A put stream that switched from the XLA plan path to this host
    # path would otherwise strand mass in the NATIVE residual store:
    # take it (copy-and-erase) and fold it in — residuals are additive,
    # so the merge is exact.  None on pure-host runs (no native store
    # entry) and pure-FFI runs (this encoder never runs).
    nat = xlaffi.take_native_residual(name, src, dst, flat.size)
    with _ef_lock:
        res = _ef_residuals.get(key)
        v = flat + res if res is not None and res.shape == flat.shape \
            else flat.copy()
        if nat is not None:
            v += nat
        k = max(1, int(np.ceil(frac * v.size)))
        if k >= v.size:
            idx = np.arange(v.size, dtype=np.int64)
        else:
            idx = np.argpartition(np.abs(v), v.size - k)[-k:]
            idx.sort()
        vals = v[idx]
        residual = v
        residual[idx] = 0.0  # in place: v is our copy
        _ef_residuals[key] = residual
    return sparse_encode(vals, idx)


def _send_to_proc(proc: int, op: int, name: str, src: int, dst: int,
                  weight: float, p_weight: float = 0.0,
                  payload: Optional[np.ndarray] = None,
                  stripe: Optional[int] = None) -> None:
    d = _store.distrib
    host, port = d.proc_addr[proc]
    comp = config.get().win_compression
    if payload is None:
        payload = np.empty(0, np.uint8)
    elif (payload.size and payload.dtype == np.float32
          and comp.startswith("sparse")
          and (op & ~OP_FLAG_MASK) == OP_ACCUMULATE):
        # Ship only the top-|magnitude| fraction of the row; the un-sent
        # complement stays in the sender's error-feedback residual and
        # rides the next send on this edge.  ACCUMULATE edges only (the
        # push-sum family): the receiver folds sparse contributions with
        # ``+=``, so the time-summed staging mass equals the exact input
        # mass.  PUT overwrites its staging slot — a scattered-into-zeros
        # row would zero every unsent coordinate at the receiver and the
        # residual would re-ship stale sums as a "current value", so puts
        # (like GET replies and control ops) keep exact payloads.
        # The fraction consults the tuner's override table: empty (the
        # BLUEFOG_TPU_TUNE=0 default) passes the configured value through
        # bitwise; an armed tuner may halve it on a measured-hot edge.
        from bluefog_tpu.utils import tuner
        payload = _sparse_payload(
            name, src, dst, payload,
            tuner.override_float("sparse_frac",
                                 config.parse_sparse_frac(comp)))
        op |= OP_SPARSE_FLAG
    elif (payload.size and payload.dtype == np.float32
          and comp == "bf16"):
        # Halve the DCN bytes per gossip edge; the op byte carries an
        # explicit flag so the receiver never has to infer compression
        # from the payload size.
        payload = payload.astype(_BF16)
        op |= OP_BF16_FLAG
    if payload.size and (op & ~OP_FLAG_MASK) in (OP_PUT, OP_ACCUMULATE):
        # Wire trace tag (BLUEFOG_TPU_TRACE_SAMPLE): the sampled 1-in-N
        # data message carries its identity + origin timestamps as a
        # trailer INSIDE the payload — appended after any codec, so it
        # survives OP_BATCH framing, bf16/sparse and striping without
        # further protocol.  Default off: make_trace_tag returns None
        # from one config check and nothing here mutates.
        tag = make_trace_tag(src)
        if tag is not None:
            payload = np.frombuffer(payload.tobytes() + tag, np.uint8)
            op |= OP_TRACE_FLAG
    from bluefog_tpu.utils import telemetry
    if telemetry.enabled():
        telemetry.inc("bf_win_proc_tx_bytes_total", float(payload.nbytes),
                      proc=proc)
        # Cross-process window traffic IS the DCN level of the two-level
        # wire accounting (intra-process gossip never leaves the host).
        telemetry.inc("bf_comm_level_bytes_total", float(payload.nbytes),
                      level="dcn")
    d.transport.send(host, port, op, name, src, dst, weight, payload,
                     p_weight, stripe=stripe)


def _send_to_rank_owner(rank: int, op: int, name: str, src: int, dst: int,
                        weight: float, p_weight: float = 0.0,
                        payload: Optional[np.ndarray] = None,
                        stripe: Optional[int] = None) -> None:
    _send_to_proc(_store.distrib.rank_owner[rank], op, name, src, dst,
                  weight, p_weight, payload, stripe=stripe)


def _transport_stripes(d) -> int:
    """The live transport's stripe width (1 when unknown: fakes/tests)."""
    return int(getattr(d.transport, "n_stripes", 1) or 1)


def _fanout_weight(n_stripes: int) -> float:
    """Wire ``weight`` of a FENCE_REQ / MUTEX_REL fan-out copy: the copy
    count, carried on the wire so the receiver — whatever its OWN stripe
    setting — acts on the last copy.  Exactly 0.0 single-stream, keeping
    the ``BLUEFOG_TPU_WIN_STRIPES=1`` wire bitwise-identical to the
    pre-stripe transport (receivers treat weight < 2 as one copy)."""
    return float(n_stripes) if n_stripes > 1 else 0.0


def _fanout_serial(d, n_stripes: int) -> float:
    """Wire ``p_weight`` of a fan-out's copies: a per-process monotonic
    serial shared by every copy of ONE fan-out, so the receiver's count
    can never be completed by stale copies of an earlier, partially
    delivered fan-out.  Exactly 0.0 single-stream (one copy, no counting
    — the pre-stripe wire, bit for bit)."""
    if n_stripes <= 1:
        return 0.0
    with d.cv:
        d.fanout_serial += 1
        return float(d.fanout_serial)


def _fanout_count(seen: dict, key, serial: float):
    """Advance one fan-out counter for an arriving copy (call under
    ``d.cv``).  Returns the copies seen for ``serial``, or None when the
    copy belongs to an OLDER fan-out than the one being counted (stale —
    discard).  The counter entry is ``(serial, count)``; a newer serial
    resets the count, so a lost copy only strands ITS OWN fan-out (whose
    sender already surfaced the send failure) and never a later one."""
    cur = seen.get(key)
    if cur is not None and cur[0] > serial:
        return None  # stale copy of an earlier fan-out
    count = cur[1] + 1 if cur is not None and cur[0] == serial else 1
    seen[key] = (serial, count)
    return count


def _flush_transport(procs=None, since=None, timeout=None) -> None:
    """Drain the transport's send queues (coalesced path) so the enclosing
    op's completion keeps its legacy meaning: every edge payload handed to
    TCP, every asynchronous send error surfaced HERE (on the worker that
    owns the op) rather than lost on a sender thread.

    ``procs`` restricts the drain to the peer processes the op actually
    addressed — one dead or slow neighbor must only stall ops targeting
    it, as with the legacy blocking send.  ``since`` is the transport's
    :meth:`error_token` snapshot from before the op's sends (batch
    failures between then and now raise even if another op's flush
    consumed the stored error first).  No-op single-process, with legacy
    per-message sends, or on empty queues."""
    d = _store.distrib
    if d is None:
        return
    addrs = None if procs is None else {d.proc_addr[p] for p in procs}
    if addrs is not None and not addrs:
        return
    d.transport.flush(timeout=_MSG_TIMEOUT_SEC if timeout is None
                      else timeout, addrs=addrs, since=since)


def win_flush(wait: bool = True, timeout: Optional[float] = None) -> None:
    """Flush the DCN window transport's per-peer send queues.

    With coalescing on (``BLUEFOG_TPU_WIN_COALESCE``, default), one-sided
    ops enqueue their edge payloads onto per-peer sender queues; the window
    ops already flush at their own boundaries, so ``win_wait``/``win_fence``
    semantics are unchanged — this entry point exists for callers pacing
    raw ``*_nonblocking`` streams who want queued gossip on the wire NOW
    instead of after the linger.  ``wait=False`` only kicks the sender
    workers (no blocking, no error surfacing — pacing, not a barrier);
    ``timeout`` overrides the per-peer drain wait (default
    ``BLUEFOG_TPU_WIN_TIMEOUT``).  No-op in single-process runs."""
    if wait:
        _flush_transport(timeout=timeout)
    else:
        d = _store.distrib
        if d is not None:
            d.transport.kick()


def _payload_row(win: _Window, payload, compressed: bool = False,
                 copy: bool = True, sparse: bool = False) -> np.ndarray:
    """Decode one wire payload (bytes or a zero-copy memoryview into the
    transport's recv buffer) to a window-shaped row.  ``copy=False`` skips
    the defensive copy — for callers that immediately fold the row into a
    fresh array (scale/accumulate) and never retain the view past the
    apply call."""
    expected = int(np.prod(win.shape)) * win.dtype.itemsize
    if sparse:
        # sparse:<frac> edge (OP_SPARSE_FLAG): scatter the shipped
        # (index, value) pairs into a zero row — always a fresh array,
        # never a view into the recv buffer.
        idx, vals = sparse_decode(payload)
        row = np.zeros(int(np.prod(win.shape)), dtype=win.dtype)
        if idx.size:
            if int(idx.max(initial=0)) >= row.size or \
                    int(idx.min(initial=0)) < 0:
                raise ValueError(
                    f"window {win.name!r}: sparse payload indexes outside "
                    f"the {row.size}-element row")
            row[idx] = vals.astype(win.dtype)
        return row.reshape(win.shape)
    if compressed:
        # bf16-compressed edge (sender had BLUEFOG_TPU_WIN_COMPRESSION=bf16),
        # declared by the OP_BF16_FLAG wire bit.
        if len(payload) * 2 != expected:
            raise ValueError(
                f"window {win.name!r}: bf16-flagged payload of {len(payload)} "
                f"bytes does not match half a {expected}-byte row")
        return np.frombuffer(payload, dtype=_BF16).astype(
            win.dtype).reshape(win.shape)
    if len(payload) != expected:
        raise ValueError(
            f"window {win.name!r}: payload of {len(payload)} bytes does not "
            f"match the {expected}-byte row (shape {win.shape}, "
            f"dtype {win.dtype})")
    row = np.frombuffer(payload, dtype=win.dtype).reshape(win.shape)
    return row.copy() if copy else row


def _reply_get(name: str, src: int, dst: int, weight: float) -> None:
    """Answer a GET_REQ: ship ``main[src]`` (owned here) back to ``dst``'s
    owner, which scales by ``weight`` on receipt.  ``win.lock`` gives the
    row snapshot atomicity; callers wanting writer exclusion take the
    distributed mutex explicitly (``win_mutex``)."""
    try:
        win = _store.get(name)
    except KeyError:
        return  # freed concurrently; requester's timeout reports it
    with win.lock:
        row = win.main[src].copy()
        p_w = weight * float(win.p_main[src])
    _send_to_rank_owner(dst, OP_GET_REPLY, name, src, dst, weight, p_w, row)


@contextmanager
def _remote_mutex(name: str, rank: int, my_rank: int):
    """Writer-side distributed mutex on a remotely-owned rank: ACQ → wait
    GRANT → (critical section) → REL.  The REL travels the same FIFO stream
    as any puts sent inside, so the owner applies them before releasing —
    the TCP analogue of lock/put/unlock (``mpi_controller.cc:953-1034``)."""
    d = _store.distrib
    with d.cv:
        serial = d.mutex_serial.setdefault((name, rank), threading.Lock())
    with serial:  # one outstanding ACQ per (name, rank) per process
        granted = threading.Event()
        with d.cv:
            d.grant_events[(name, rank)] = granted
        try:
            import time as _time
            from bluefog_tpu.utils import telemetry
            t0 = _time.monotonic()
            proc = d.rank_owner[rank]
            tok = d.transport.error_token({d.proc_addr[proc]})
            _send_to_rank_owner(rank, OP_MUTEX_ACQ, name, my_rank, rank, 0.0)
            # Surface a coalesced send failure NOW (the legacy blocking
            # send raised here synchronously) instead of burning the full
            # grant timeout on a peer that never saw the ACQ.
            _flush_transport({proc}, since=tok)
            if not granted.wait(timeout=_MSG_TIMEOUT_SEC):
                raise ConnectionError(
                    f"win_mutex({name!r}): rank {rank}'s owner did not grant "
                    f"within {_MSG_TIMEOUT_SEC:.0f}s")
            telemetry.inc("bf_win_mutex_acquisitions_total", kind="remote")
            telemetry.inc("bf_win_mutex_wait_seconds_total",
                          _time.monotonic() - t0, kind="remote")
            yield
        finally:
            try:
                proc = d.rank_owner[rank]
                tok = d.transport.error_token({d.proc_addr[proc]})
                # Striped transport: the REL fans out across EVERY stripe
                # of the owner (copy count in the wire weight field), so
                # the owner releases only when each stripe — any of which
                # may carry this critical section's puts — has drained
                # past the release.  Single-stream sends exactly one copy
                # with weight 0.0: the pre-stripe wire, bit for bit.
                n_str = _transport_stripes(d)
                w = _fanout_weight(n_str)
                serial = _fanout_serial(d, n_str)
                for k in range(n_str):
                    _send_to_rank_owner(rank, OP_MUTEX_REL, name, my_rank,
                                        rank, w, p_weight=serial, stripe=k)
                # As with the legacy blocking send, a REL that cannot
                # reach the owner raises here (the owner would otherwise
                # hold the mutex until its own timeout).
                _flush_transport({proc}, since=tok)
            finally:
                with d.cv:
                    d.grant_events.pop((name, rank), None)


def _hold_mutex_for_remote(name: str, rank: int, requester: int) -> None:
    """Acquire rank's (locally-owned) mutex on behalf of a remote requester;
    hold it until the matching MUTEX_REL arrives.  Runs on its own daemon
    thread (holds are long-lived; they must not occupy service workers)."""
    d = _store.distrib
    try:
        win = _store.get(name)
    except KeyError:
        return
    release = threading.Event()
    key = (name, rank, requester)
    try:
        with win.mutexes[rank]:
            # Register only AFTER the mutex is ours: with the striped REL
            # fan-out, a PREDECESSOR hold's late release copies may still
            # be arriving while this thread blocks on the acquire —
            # registering early would let that release's completion set
            # OUR event (a premature release breaking mutual exclusion).
            # The requester sends its REL only after our GRANT, which
            # follows this registration, so no release aimed at us can
            # race it.
            with d.cv:
                d.remote_holds[key] = release
            proc = d.rank_owner[requester]
            tok = d.transport.error_token({d.proc_addr[proc]})
            _send_to_rank_owner(requester, OP_MUTEX_GRANT, name, requester,
                                rank, 0.0)
            # A GRANT that cannot reach the requester raises here (as the
            # legacy blocking send did), releasing the mutex immediately
            # instead of holding it for the requester's full timeout.
            _flush_transport({proc}, since=tok)
            release.wait(timeout=_MSG_TIMEOUT_SEC)
    finally:
        with d.cv:
            # Only remove our own registration: a back-to-back ACQ from the
            # same requester may already have installed its successor event.
            if d.remote_holds.get(key) is release:
                d.remote_holds.pop(key, None)


def _apply_inbound(op: int, name: str, src: int, dst: int, weight: float,
                   p_weight: float, payload) -> None:
    """Drain-thread entry: apply one inbound transport message to the local
    (owned) window state.  Must never block on peers — replies and mutex
    holds are pushed onto the worker pool.

    ``payload`` may be a zero-copy memoryview into the transport's recv
    buffer (valid only for this call): every retaining path (parking)
    snapshots it to bytes; every applying path folds it into a fresh
    array before returning."""
    if (op & ~OP_FLAG_MASK) == OP_MEMBER:
        # Churn-controller control plane (ops/membership.py): decoded and
        # consumed immediately, never parked — a pre-init or post-shutdown
        # heartbeat is simply dropped (the sender re-heartbeats on its own
        # cadence, so nothing is lost).
        from bluefog_tpu.ops import membership
        membership.handle_wire(payload)
        return
    if (op & ~OP_FLAG_MASK) == OP_GANG:
        # Gang join/bootstrap control plane (ops/gang.py): same contract
        # as OP_MEMBER — consumed immediately, dropped when the subsystem
        # is not installed (BLUEFOG_TPU_ELASTIC_JOIN off).  Routed BEFORE
        # the directory check: a joining process receives its grant on a
        # transport that has no rank directory yet.
        from bluefog_tpu.ops import gang
        gang.handle_wire(payload)
        return
    orig_op = op  # parked/replayed messages must keep the wire flag bits
    compressed = bool(op & OP_BF16_FLAG)
    sparse = bool(op & OP_SPARSE_FLAG)
    traced = bool(op & OP_TRACE_FLAG)
    op &= ~OP_FLAG_MASK
    d = _store.distrib
    if d is None:
        with _store.lock:
            if _store.distrib is None:
                # Directory not installed yet (peer finished init first):
                # buffer — init_transport replays in arrival order.  The
                # recv buffer is reused after this call: own the bytes.
                _store.preinit_msgs.append(
                    (orig_op, name, src, dst, weight, p_weight,
                     bytes(payload)))
                return
            d = _store.distrib
    if op == OP_FENCE_REQ:
        # Striped fan-out: the requester sent one copy down EVERY stripe
        # (count in `weight`, serial in `p_weight`; weight < 2 = the
        # single-stream wire).  Only the LAST copy of the NEWEST serial
        # is answered — each stripe is FIFO, so the full set arriving
        # certifies every put sent before the fence has been applied,
        # whichever stripe it sharded onto.
        total = int(weight) if weight >= 2.0 else 1
        if total > 1:
            with d.cv:
                seen = _fanout_count(d.fence_req_seen, src, p_weight)
                if seen is None or seen < total:
                    return
                d.fence_req_seen.pop(src, None)
        _store.svc_pool.submit(_send_to_rank_owner, src, OP_FENCE_ACK, "",
                               src, dst, 0.0)
        return
    if op == OP_FENCE_ACK:
        with d.cv:
            d.fence_acks += 1
            d.cv.notify_all()
        return
    if op == OP_MUTEX_GRANT:
        with d.cv:
            ev = d.grant_events.get((name, dst))
        if ev is not None:
            ev.set()
        return
    if op == OP_MUTEX_REL:
        # Same fan-out counting as FENCE_REQ: the REL travels every
        # stripe, and the mutex is released only when ALL copies of the
        # newest serial arrived — i.e. when every stripe that might
        # carry the critical section's puts has drained past the
        # release point.  A stale count left by a PARTIALLY delivered
        # earlier release (one copy lost to a send failure the requester
        # already saw) can never complete a later one early.
        total = int(weight) if weight >= 2.0 else 1
        with d.cv:
            if total > 1:
                key = (name, dst, src)
                seen = _fanout_count(d.rel_seen, key, p_weight)
                if seen is None or seen < total:
                    return
                d.rel_seen.pop(key, None)
            ev = d.remote_holds.get((name, dst, src))
        if ev is not None:
            ev.set()
        return
    with _store.lock:
        win = _store.windows.get(name)
        if win is None:
            # SPMD skew: the peer created + wrote this window before our
            # win_create ran.  Park; win_create replays in arrival order
            # (payload snapshotted — the recv buffer is reused).
            d.parked.setdefault(name, []).append(
                (orig_op, name, src, dst, weight, p_weight, bytes(payload)))
            return
    if op in (OP_PUT, OP_ACCUMULATE, OP_GET_REPLY):
        # Applied (not parked) data payload: inbound bytes per peer process
        # (counted here, after the park checks, so a parked message's
        # replay is not double-counted).
        from bluefog_tpu.utils import telemetry
        if telemetry.enabled():
            telemetry.inc("bf_win_proc_rx_bytes_total", float(len(payload)),
                          proc=d.rank_owner.get(src, -1))
    if op in (OP_PUT, OP_ACCUMULATE):
        # Deliberately mutex-free: the drain thread must never block on a
        # rank mutex (a remote holder's REL would be queued behind us —
        # deadlock).  Slot atomicity comes from win.lock; writer exclusion
        # is the sender's job via the distributed mutex (_remote_mutex).
        from bluefog_tpu.utils.timeline import op_span
        with op_span(f"win_apply.{name}.{src}->{dst}", "COMMUNICATE"):
            tag = None
            if traced:
                # Strip the trace trailer before the codec-length
                # validation; the tag's age is recorded only once the
                # contribution actually lands in its staging slot.
                payload, tag = trace_strip(payload)
            # copy=False: the scale below materializes a fresh array; the
            # transient view is never retained.
            row = _payload_row(win, payload, compressed, copy=False,
                               sparse=sparse)
            stale_action = None
            with win.lock:
                if (dst, src) not in win.staging:
                    return
                if op == OP_ACCUMULATE:
                    keep, stale_action = _staleness_factor(
                        name, (dst, src), tag)
                    if stale_action is None:
                        win.staging[(dst, src)] += \
                            row * win.dtype.type(weight)
                    else:
                        # Bounded staleness (async mode): the admitted
                        # fraction enters staging, the complement is
                        # HELD in the stale-residual store — never
                        # dropped, so mass conservation survives.
                        contrib = row * win.dtype.type(weight)
                        if keep:
                            win.staging[(dst, src)] += \
                                contrib * win.dtype.type(keep)
                        _divert_stale(win, (dst, src), contrib,
                                      p_weight, keep)
                else:
                    win.staging[(dst, src)] = row * win.dtype.type(weight)
                if stale_action != "reject":
                    win.versions[dst, src] += 1
                if _store.associated_p_enabled:
                    if op == OP_ACCUMULATE:
                        if stale_action is None:
                            win.p_staging[(dst, src)] += p_weight
                        elif keep:
                            win.p_staging[(dst, src)] += keep * p_weight
                    else:
                        win.p_staging[(dst, src)] = p_weight
            if stale_action is not None:
                _note_stale(name, [(src, stale_action)])
            if tag is not None:
                _note_trace_commit(name, src, tag, dst)
    elif op == OP_GET_REQ:
        _store.svc_pool.submit(_reply_get, name, src, dst, weight)
    elif op == OP_GET_REPLY:
        from bluefog_tpu.utils.timeline import op_span
        with op_span(f"win_apply.{name}.{src}->{dst}", "COMMUNICATE"):
            if traced:  # senders never tag replies; strip defensively
                payload, _ = trace_strip(payload)
            # copy=False: the scale below materializes a fresh array; the
            # transient view is never retained.
            row = _payload_row(win, payload, compressed, copy=False,
                               sparse=sparse)
            with win.lock:
                if (dst, src) in win.staging:
                    win.staging[(dst, src)] = row * win.dtype.type(weight)
                    win.versions[dst, src] += 1
                    if _store.associated_p_enabled:
                        win.p_staging[(dst, src)] = p_weight
        with d.cv:
            key = (name, dst, src)
            d.pending_gets[key] = d.pending_gets.get(key, 0) - 1
            d.cv.notify_all()
    elif op == OP_MUTEX_ACQ:
        threading.Thread(target=_hold_mutex_for_remote,
                         args=(name, dst, src), daemon=True,
                         name=f"bf-win-hold-{dst}").start()


def _apply_inbound_batch(msgs) -> None:
    """Drain-thread entry for one decoded OP_BATCH frame.

    Sub-messages apply in arrival order (the FIFO contract fence and mutex
    REL rely on), but runs of consecutive puts/accumulates into the SAME
    window take the vectorized path: rows are decoded and scaled outside
    the lock, consecutive contributions to one staging slot are pre-folded,
    and the whole run commits under ONE ``win.lock`` hold — per-message
    mutex traffic was the receive side's dominant cost for small gossip
    rows.  Control messages (fence, mutex, get) and anything that must
    park fall through to the per-message path, which owns its copies.

    Exception isolation matches the legacy drain loop: one malformed
    sub-message (payload validation, SPMD shape skew) loses only itself,
    never the rest of the frame — a fence request riding behind a bad put
    must still be answered, or the sender's win_fence would time out on a
    healthy peer."""
    import logging
    i, n = 0, len(msgs)
    while i < n:
        base_op = msgs[i][0] & ~OP_FLAG_MASK
        if base_op not in (OP_PUT, OP_ACCUMULATE):
            try:
                _apply_inbound(*msgs[i])
            except Exception:  # noqa: BLE001 — isolate per message
                logging.getLogger("bluefog_tpu").exception(
                    "window transport apply failed (batched control msg)")
            i += 1
            continue
        name = msgs[i][1]
        j = i + 1
        while (j < n and msgs[j][1] == name
               and (msgs[j][0] & ~OP_FLAG_MASK) in (OP_PUT, OP_ACCUMULATE)):
            j += 1
        try:
            _apply_data_run(name, msgs[i:j])
        except Exception:  # noqa: BLE001 — isolate per run
            logging.getLogger("bluefog_tpu").exception(
                "window transport apply failed (batched data run)")
        i = j


def _apply_inbound_items(items) -> None:
    """Drain-thread entry for the NATIVE transport path: an ordered list of
    ``(0, msg)`` raw messages and ``(1, commit)`` folded commit entries
    (``ops/transport.WindowTransport`` docs).  Decode, codec work and
    same-slot folding already happened in C++; what remains per run is one
    ``win.lock`` hold committing the folded slots — the Python structural
    twin of :func:`_apply_inbound_batch`, with the per-message work gone.

    Exception isolation matches the batched path: one bad run or control
    message loses only itself, never the rest of the drain result."""
    import logging
    i, n = 0, len(items)
    while i < n:
        kind, payload = items[i]
        if kind == 0:
            try:
                _apply_inbound(*payload)
            except Exception:  # noqa: BLE001 — isolate per message
                logging.getLogger("bluefog_tpu").exception(
                    "window transport apply failed (native raw msg)")
            i += 1
            continue
        name = payload[0]
        j = i + 1
        while j < n and items[j][0] == 1 and items[j][1][0] == name:
            j += 1
        try:
            _commit_native_run(name, [it[1] for it in items[i:j]])
        except Exception:  # noqa: BLE001 — isolate per run
            logging.getLogger("bluefog_tpu").exception(
                "window transport apply failed (native commit run)")
        i = j


def _commit_native_run(name: str, entries) -> None:
    """Commit one window's run of natively-folded entries under ONE
    ``win.lock`` hold.  Each entry is ``(name, replace, src, dst, p_mass,
    puts, accs, values, wire_bytes, trace)`` with ``values`` a zero-copy
    f32 view into the transport's drain buffer (valid only for this
    call): replace entries copy it into a fresh staging array, accumulate
    entries fold it in with ``+=`` — numerically IDENTICAL to what the
    Python batched apply computes for the same frames, since the C++ fold
    replicates its decode/scale/fold order bit-for-bit.  ``trace`` (the
    last folded wire trace tag, or None) feeds the per-edge
    contribution-age telemetry once the entry lands."""
    d = _store.distrib
    with _store.lock:
        win = _store.windows.get(name) if d is not None else None
    if win is None or d is None:
        # Pre-init or SPMD-skew parking: re-materialize each folded entry
        # as ONE equivalent message (the fold already collapsed the run:
        # a put with the folded row at weight 1 carries the same state)
        # and let the per-message path own the parking bookkeeping.  The
        # folded version ticks collapse to one per entry in this narrow
        # race — the replayed STATE is exact.
        for (nm, replace, src, dst, p_mass, _puts, _accs, vals, _wb,
             _tr) in entries:
            _apply_inbound(OP_PUT if replace else OP_ACCUMULATE, nm, src,
                           dst, 1.0, p_mass, np.asarray(vals).tobytes())
        return
    from bluefog_tpu.utils import telemetry
    if telemetry.enabled():
        for (_nm, _r, src, _d2, _pm, _p, _a, _v, wire_bytes,
             _tr) in entries:
            telemetry.inc("bf_win_proc_rx_bytes_total", float(wire_bytes),
                          proc=d.rank_owner.get(src, -1))
    expected = int(np.prod(win.shape, dtype=np.int64))
    from bluefog_tpu.utils.timeline import op_span
    noted = []
    stale_noted = []
    with op_span(f"win_apply_batch.{name}", "COMMUNICATE"):
        with win.lock:
            for (_nm, replace, src, dst, p_mass, puts, accs, vals, _wb,
                 trace) in entries:
                key = (dst, src)
                if key not in win.staging:
                    continue
                if vals.size != expected:
                    # A window freed+recreated with a different shape while
                    # this entry was in flight: drop it, as the Python
                    # path's _payload_row validation would.
                    import logging
                    logging.getLogger("bluefog_tpu").warning(
                        "window %r: folded entry of %d elements does not "
                        "match the %d-element row — dropped", name,
                        vals.size, expected)
                    continue
                row = vals.reshape(win.shape)
                if replace:
                    win.staging[key] = row.copy()  # own it: buffer is reused
                    win.versions[key] += puts + accs
                    if _store.associated_p_enabled:
                        win.p_staging[key] = p_mass
                else:
                    keep, action = _staleness_factor(name, key, trace)
                    if action is None:
                        win.staging[key] += row
                        win.versions[key] += puts + accs
                        if _store.associated_p_enabled:
                            win.p_staging[key] += p_mass
                    else:
                        # Bounded staleness (async mode): admitted
                        # fraction in, the complement held in the
                        # stale-residual store (which always copies —
                        # `row` is a view into the reused drain buffer).
                        if keep:
                            win.staging[key] += row * win.dtype.type(keep)
                            win.versions[key] += puts + accs
                            if _store.associated_p_enabled:
                                win.p_staging[key] += keep * p_mass
                        _divert_stale(win, key, row, p_mass, keep)
                        stale_noted.append((src, action))
                if trace is not None:
                    noted.append((src, dst, trace))
    _note_stale(name, stale_noted)
    for src, dst_r, tag in noted:  # outside win.lock: not state
        _note_trace_commit(name, src, tag, dst_r)


def _apply_data_run(name: str, group) -> None:
    """Apply a run of put/accumulate messages for one window, vectorized:
    decode + scale outside the lock, fold consecutive same-slot
    contributions (put-then-accumulate folds into the put: ``A`` then
    ``+= B`` is ``A + B`` with both version ticks kept), commit the whole
    run under one lock hold."""
    d = _store.distrib
    with _store.lock:
        win = _store.windows.get(name) if _store.distrib is not None else None
    if d is None or win is None:
        # Preinit or SPMD-skew parking: the per-message path owns the
        # bookkeeping (and snapshots each payload to bytes).
        for m in group:
            _apply_inbound(*m)
        return
    from bluefog_tpu.utils import telemetry
    if telemetry.enabled():
        for (_op, _n, src, _dst, _w, _pw, payload) in group:
            telemetry.inc("bf_win_proc_rx_bytes_total", float(len(payload)),
                          proc=d.rank_owner.get(src, -1))
    # -- decode + fold outside the lock ------------------------------------
    # entries: [replace, (dst, src), scaled_row, p_mass, version_ticks,
    #           trace_tag_or_None]
    entries = []
    for (op, _n, src, dst, weight, p_weight, payload) in group:
        compressed = bool(op & OP_BF16_FLAG)
        sparse = bool(op & OP_SPARSE_FLAG)
        accumulate = (op & ~OP_FLAG_MASK) == OP_ACCUMULATE
        try:
            tag = None
            if op & OP_TRACE_FLAG:
                payload, tag = trace_strip(payload)
            row = _payload_row(win, payload, compressed, copy=False,
                               sparse=sparse)
        except ValueError:
            # One malformed payload (shape/flag skew) loses only itself —
            # per-message isolation, as on the legacy drain path.
            import logging
            logging.getLogger("bluefog_tpu").exception(
                "window transport apply failed (batched row decode)")
            continue
        scaled = row * win.dtype.type(weight)  # fresh array: view not kept
        key = (dst, src)
        if accumulate and entries and entries[-1][1] == key \
                and (not _async.armed or not entries[-1][0]):
            # Fold into the previous same-slot entry (put or accumulate):
            # the slot would have received both anyway, in this order.
            # Async mode refuses to fold an accumulate into a PUT-headed
            # entry: puts bypass the staleness policy (overwrite
            # semantics), so the fold would smuggle the accumulate's
            # mass past it — each accumulate gets its own decision
            # instead.  Accumulate-into-accumulate folds stay (one wire
            # frame = one arrival burst; the last tag governs the run).
            entries[-1][2] += scaled
            entries[-1][3] += p_weight
            entries[-1][4] += 1
            if tag is not None:  # latest tag wins, as in the native fold
                entries[-1][5] = tag
        else:
            entries.append([not accumulate, key, scaled, p_weight, 1, tag])
    # -- commit under one lock hold ----------------------------------------
    from bluefog_tpu.utils.timeline import op_span
    noted = []
    stale_noted = []
    with op_span(f"win_apply_batch.{name}", "COMMUNICATE"):
        with win.lock:
            for replace, key, scaled, p_mass, ticks, tag in entries:
                if key not in win.staging:
                    continue
                if replace:
                    win.staging[key] = scaled
                    win.versions[key] += ticks
                    if _store.associated_p_enabled:
                        win.p_staging[key] = p_mass
                else:
                    keep, action = _staleness_factor(name, key, tag)
                    if action is None:
                        win.staging[key] += scaled
                        win.versions[key] += ticks
                        if _store.associated_p_enabled:
                            win.p_staging[key] += p_mass
                    else:
                        # Bounded staleness (async mode): admitted
                        # fraction in, the complement held in the
                        # stale-residual store (mass conserved).
                        if keep:
                            win.staging[key] += \
                                scaled * win.dtype.type(keep)
                            win.versions[key] += ticks
                            if _store.associated_p_enabled:
                                win.p_staging[key] += keep * p_mass
                        _divert_stale(win, key, scaled, p_mass, keep)
                        stale_noted.append((key[1], action))
                if tag is not None:
                    noted.append((key[1], key[0], tag))
    _note_stale(name, stale_noted)
    for src, dst_r, tag in noted:  # outside win.lock: not state
        _note_trace_commit(name, src, tag, dst_r)


def _neighbors_from_topology():
    from bluefog_tpu import basics
    topo = basics.load_topology()
    n = basics.size()
    from bluefog_tpu import topology as topology_util
    in_nbrs = [topology_util.in_neighbor_ranks(topo, r) for r in range(n)]
    out_nbrs = [topology_util.out_neighbor_ranks(topo, r) for r in range(n)]
    return n, in_nbrs, out_nbrs


def _resolve_edge_weights(weights, nbrs_of, default: float, *,
                          peer_is_src: bool = False,
                          ranks=None) -> Dict[tuple, float]:
    """Normalize dst/src weight arguments to ``{(rank, peer): w}``.

    ``weights`` may be None (every edge gets ``default``), a full (n, n)
    matrix in the module-wide ``W[src, dst]`` convention, or a dict
    ``{peer: w}`` applied uniformly (the single-controller reading of the
    reference's per-process dicts).  ``peer_is_src`` marks in-neighbor
    callers (win_get / win_update), where ``r`` is the destination, so the
    matrix lookup is ``W[peer, r]`` instead of ``W[r, peer]``.

    ``ranks`` restricts the ``r`` enumeration (callers pass the window's
    owned ranks: non-owned edges would be filtered later anyway, and at pod
    scale an O(n·deg) python dict per op call is real latency)."""
    out: Dict[tuple, float] = {}
    n = len(nbrs_of)
    rs = range(n) if ranks is None else ranks
    if weights is None:
        for r in rs:
            for peer in nbrs_of[r]:
                out[(r, peer)] = default
    elif isinstance(weights, dict):
        if weights and isinstance(next(iter(weights)), tuple):
            return {k: float(v) for k, v in weights.items()}
        for r in rs:
            for peer in nbrs_of[r]:
                if peer in weights:
                    out[(r, peer)] = float(weights[peer])
    else:
        w = np.asarray(weights, dtype=float)
        assert w.shape == (n, n), "weight matrix must be (size, size)"
        for r in rs:
            for peer in nbrs_of[r]:
                out[(r, peer)] = float(w[peer, r] if peer_is_src else w[r, peer])
    return out


# ---------------------------------------------------------------------------
# Lifecycle
# ---------------------------------------------------------------------------

def win_create(tensor, name: str, zero_init: bool = False) -> bool:
    """Create a named window from a rank-major ``(size, ...)`` tensor — or,
    in multi-process runs, an owned-rows ``(len(owned_ranks), ...)`` tensor
    (row ``i`` = this process's ``owned_ranks()[i]``), in which case every
    window op on it takes and returns owned-rows arrays and no O(n) buffer
    is ever allocated.

    Allocates one staging buffer per in-neighbor edge of the *current*
    topology (which is frozen while windows exist, as in the reference) —
    owned ranks' in-edges only.  In multi-process runs this is an SPMD call
    (every process creates the window); inbound gossip that raced ahead of
    local creation is replayed in arrival order."""
    if jax.process_count() > 1 and _store.distrib is None:
        raise RuntimeError(
            "window ops across processes need the DCN transport: call "
            "bf.init_distributed() (and build the native core with "
            "`make -C bluefog_tpu/native`) before win_create — without it "
            "each process would silently gossip with its own private copy")
    n, in_nbrs, out_nbrs = _neighbors_from_topology()
    t = _to_numpy(tensor)
    owned = _owned_ranks(n)
    if t.shape[0] == n:
        layout = "rank"
    elif _store.distrib is not None and t.shape[0] == len(owned):
        layout = "owned"
    else:
        raise ValueError(
            f"win_create({name!r}): leading dim {t.shape[0]} is neither the "
            f"world size ({n}, rank-major) nor this process's owned-rank "
            f"count ({len(owned)}, owned layout)")
    d = _store.distrib
    with _store.lock:
        if name in _store.windows:
            return False
        win = _store.windows[name] = _Window(name, t, in_nbrs, out_nbrs,
                                             zero_init, owned, layout)
        if d is not None:
            for msg in d.parked.pop(name, []):
                _apply_inbound(*msg)
    if d is not None and win.dtype == np.float32:
        # Opt the window into the native drain fold path (f32 rows only —
        # the C++ fold is f32 arithmetic; other dtypes keep the raw
        # per-message path).  After creation: a commit can never precede
        # the window it targets.
        reg = getattr(d.transport, "register_window", None)
        if reg is not None:
            reg(name, int(np.prod(win.shape, dtype=np.int64)))
    return True


def win_free(name: Optional[str] = None) -> bool:
    # Unregister from the native drain BEFORE removing the window, so the
    # freed-window race window (in-flight folded commits for a window that
    # no longer exists) is as narrow as the frame already being decoded.
    d = _store.distrib
    unreg = getattr(d.transport, "unregister_window", None) \
        if d is not None else None
    try:
        with _store.lock:
            if name is None:
                if unreg is not None:
                    for n in _store.windows:
                        unreg(n)
                _store.windows.clear()
            elif name in _store.windows:
                if unreg is not None:
                    unreg(name)
                del _store.windows[name]
            else:
                return False
        return True
    finally:
        # A freed window's sender residuals must not leak into a later
        # window recreated under the same name (possibly with a different
        # shape) — purged even on the not-found path, so a residual can
        # never outlive its name.
        _drop_ef_residuals(name)


def get_current_created_window_names() -> List[str]:
    with _store.lock:
        return sorted(_store.windows)


# ---------------------------------------------------------------------------
# One-sided ops
# ---------------------------------------------------------------------------

def _count_win_op(op: str, nbytes: float, edges) -> None:
    """Dispatch-time counters for one one-sided op: calls, topology edges
    it touches, and the element bytes it moves (puts/accumulates: the
    caller payload; gets: one window row per pulled edge; updates: the
    combined owned rows)."""
    from bluefog_tpu.utils import telemetry
    if not telemetry.enabled():
        return
    telemetry.inc("bf_win_ops_total", op=op)
    telemetry.inc("bf_win_edges_total", float(len(edges)), op=op)
    telemetry.inc("bf_win_bytes_total", float(nbytes), op=op)


def _row_nbytes(win: _Window) -> int:
    return int(np.prod(win.shape, dtype=np.int64)) * win.dtype.itemsize


def _validate_edges(edges: Dict[tuple, float], nbrs_of: List[List[int]],
                    *, peer_is_src: bool, op: str) -> None:
    """Reject edges absent from the window's topology — a put/get naming a
    non-neighbor is a caller bug (the reference's MPI graph communicator
    errors likewise), not something to drop silently."""
    for (r, peer) in edges:
        if peer not in nbrs_of[r]:
            kind = "in-neighbor" if peer_is_src else "out-neighbor"
            raise ValueError(
                f"{op}: rank {peer} is not an {kind} of rank {r} in the "
                "window's topology")


def _expected_rows(win: _Window) -> int:
    return win.n if win.layout == "rank" else len(win.owned)


def _validate_payload(win: _Window, t: np.ndarray, op: str) -> None:
    want = _expected_rows(win)
    if t.shape[0] != want:
        kind = ("rank-major (world size)" if win.layout == "rank"
                else "owned-rows (this process's owned-rank count)")
        raise ValueError(
            f"{op}({win.name!r}): leading dim {t.shape[0]} != {want} — "
            f"this window uses the {kind} layout")


def _do_put(name: str, tensor, edges: Dict[tuple, float],
            require_mutex: bool, accumulate: bool, self_weight=None) -> None:
    from bluefog_tpu.utils.timeline import op_span
    try:
        win = _store.get(name)
    except KeyError:
        return  # window freed after dispatch; put becomes a no-op
    op = OP_ACCUMULATE if accumulate else OP_PUT
    kind = "win_accumulate" if accumulate else "win_put"
    d = _store.distrib
    remote_procs = ({d.rank_owner[dst] for (src, dst) in edges
                     if _owns(src) and not _owns(dst)}
                    if d is not None else set())
    # Error token scoped to the peers THIS op will address (taken before
    # any enqueue): failures on other peers' senders never fail this op.
    tok = (d.transport.error_token({d.proc_addr[p] for p in remote_procs})
           if remote_procs else None)
    # Zero-copy XLA put path (BLUEFOG_TPU_WIN_XLA): when the payload is a
    # committed device array, the remote edges dispatch as ONE native
    # plan run straight off the XLA buffer — no device_get, no per-edge
    # temp, no tobytes.  Plan build failure (and =0) falls back to the
    # host-staged per-edge loop below, which stays byte-identical on the
    # wire (the oracle contract).
    plan = None
    if remote_procs and xlaffi.keep_device_ok(tensor, win):
        remote_edges = tuple(
            ((src, dst), w) for (src, dst), w in edges.items()
            if _owns(src) and not _owns(dst))
        plan = xlaffi.prepare_put(d, win, name, op, remote_edges,
                                  per_edge=require_mutex)
    if plan is not None:
        _ffi_put(win, name, tensor, edges, plan, op, accumulate,
                 require_mutex, kind)
    else:
        if not isinstance(tensor, np.ndarray):
            # FFI-armed dispatch fell through: materialize once and take
            # the host-staged path for this put.
            tensor = _to_numpy(tensor)
        for (src, dst), w in edges.items():
            if not _owns(src):
                continue  # src's owner performs this edge
            row = win.row_of[src]  # caller-side row index of the src rank
            # Per-edge span: the host-side path can show what one fused
            # XLA program cannot — each (src, dst) transfer individually
            # (the reference's per-phase timeline granularity, per edge).
            with op_span(f"{kind}.{name}.{src}->{dst}", "COMMUNICATE"):
                _do_put_edge(win, name, tensor, row, src, dst, w, op,
                             accumulate, require_mutex)
    # Op boundary: every remote edge enqueued above must be handed to TCP
    # (and any sender-worker error surfaced on THIS op's future) before the
    # op reports complete — win_wait keeps its local-completion meaning.
    # Scoped to the peers this op addressed: an unrelated slow neighbor
    # does not stall it.
    if remote_procs:
        _flush_transport(remote_procs, since=tok)
    if self_weight is not None:
        host_t = tensor if isinstance(tensor, np.ndarray) \
            else xlaffi.host_view(tensor)
        _publish_self(win, host_t, self_weight)


def _ffi_put(win, name, tensor, edges, plan, op, accumulate,
             require_mutex, kind) -> None:
    """Dispatch one put through the compiled XLA plan: local edges keep
    the legacy in-store write (through a zero-copy host view), remote
    edges hand the device buffer pointer to the native plan executor —
    under each edge's distributed mutex when the caller asked for writer
    exclusion (per-edge plans preserve the one-hold-at-a-time rule)."""
    from bluefog_tpu.utils.timeline import op_span
    d = _store.distrib
    local = [((src, dst), w) for (src, dst), w in edges.items()
             if _owns(src) and _owns(dst)]
    if local:
        host_t = xlaffi.host_view(tensor)
        for (src, dst), w in local:
            with op_span(f"{kind}.{name}.{src}->{dst}", "COMMUNICATE"):
                _do_put_edge(win, name, host_t, win.row_of[src], src, dst,
                             w, op, accumulate, require_mutex)
    tx = getattr(d.transport, "_tx", None)
    if not tx:
        raise ConnectionError(
            f"{kind}({name!r}): window transport is stopping")
    if plan.codec == 2:
        # Sparse error feedback: residuals a previous HOST-path send left
        # in the Python dict must ride this native dispatch — push them
        # into the native store (additive merge, exact) so a mixed-path
        # stream never strands mass on either side.
        with _ef_lock:
            taken = []
            for _pid, grp in plan.groups:
                for (src, dst), _w in grp:
                    r = _ef_residuals.pop((name, src, dst), None)
                    if r is not None:
                        taken.append((src, dst, r))
        for src, dst, r in taken:
            xlaffi.push_native_residual(name, src, dst, r)
    # dispatch_lock serializes the P refresh + run per cached plan:
    # concurrent puts sharing the plan must each ship their OWN mass.
    with plan.dispatch_lock, op_span(f"{kind}.{name}.xla", "COMMUNICATE"):
        if _store.associated_p_enabled:
            # One snapshot of the P masses for every remote edge — the
            # same values the per-edge loop reads under win.lock (self-
            # publish only happens after the sends, so nothing can
            # interleave).
            with win.lock:
                for pid, grp in plan.groups:
                    xlaffi.set_group_p(
                        pid, [w * float(win.p_main[src])
                              for (src, _dst), w in grp])
            plan.p_set = True
        elif plan.p_set:
            # Associated-P was turned OFF since this plan last shipped:
            # re-zero the cached masses or the wire would carry stale P
            # (the host-path oracle sends 0.0).
            for pid, grp in plan.groups:
                xlaffi.set_group_p(pid, [0.0] * len(grp))
            plan.p_set = False
        for pid, grp in plan.groups:  # one group (one mutex hold) per
            if require_mutex:         # edge in the require_mutex form
                (src, dst), _w = grp[0]
                with _remote_mutex(name, dst, src):
                    _ffi_run_group(win, name, plan, pid, grp, tx, tensor,
                                   require_mutex)
            else:
                _ffi_run_group(win, name, plan, pid, grp, tx, tensor,
                               require_mutex)
    xlaffi.record_dispatch(plan)


def _ffi_run_group(win, name, plan, pid, grp, tx, tensor,
                   require_mutex) -> None:
    """Run one plan group, rebuilding once if the native plan was evicted
    or invalidated between the cache fetch and this dispatch (nothing was
    sent in that case — the executor validates the plan id first)."""
    d = _store.distrib
    try:
        xlaffi.run_group(pid, tx, tensor)
    except xlaffi.PlanVanished:
        fresh = xlaffi.prepare_put(d, win, name, plan.op, tuple(grp),
                                   per_edge=False)
        if fresh is None:
            raise
        if _store.associated_p_enabled:
            with win.lock:
                xlaffi.set_group_p(
                    fresh.groups[0][0],
                    [w * float(win.p_main[src]) for (src, _dst), w in grp])
        xlaffi.run_group(fresh.groups[0][0], tx, tensor)


def _do_put_edge(win, name, tensor, row, src, dst, w, op, accumulate,
                 require_mutex) -> None:
    """One (src, dst) edge of a put/accumulate (src owned here)."""
    if not _owns(dst):
        # Remote edge: ship the raw row + weight; the owner's drain
        # thread scales and applies (one-sided put completion = local
        # send completion; remote visibility is ordered by win_fence /
        # win_update, as with MPI_Put).  require_mutex maps to the
        # writer-side distributed mutex, as in the reference.
        with win.lock:
            p_w = w * float(win.p_main[src]) \
                if _store.associated_p_enabled else 0.0
        # Cast to the window dtype: the receiver reconstructs the row
        # with frombuffer(win.dtype), so a mismatched payload would be
        # dropped on exactly the cross-process edges.
        payload = np.ascontiguousarray(tensor[row], dtype=win.dtype)
        if payload.base is None and payload is not tensor:
            # ascontiguousarray materialized (dtype cast or a strided
            # input): a real host staging copy, not a view.
            xlaffi.count_host_copy(payload.nbytes, "edge_temp")
        if require_mutex:
            with _remote_mutex(name, dst, src):
                _send_to_rank_owner(dst, op, name, src, dst, w, p_w,
                                    payload)
        else:
            _send_to_rank_owner(dst, op, name, src, dst, w, p_w, payload)
        return
    # Cast once: a float64 input on a float32 window must not widen the
    # staging slot (same invariant as _publish_self and the remote path).
    payload = np.asarray(tensor[row] * w, dtype=win.dtype)
    xlaffi.count_host_copy(payload.nbytes, "edge_temp")  # scaled temp
    mutex = win.mutexes[dst] if require_mutex else None
    if mutex:
        mutex.acquire()
    try:
        with win.lock:
            if (dst, src) not in win.staging:
                return  # window freed concurrently
            if accumulate:
                win.staging[(dst, src)] += payload
            else:
                # payload is freshly allocated above — no aliasing, no copy
                win.staging[(dst, src)] = payload
            win.versions[dst, src] += 1
            if _store.associated_p_enabled:
                if accumulate:
                    win.p_staging[(dst, src)] += w * win.p_main[src]
                else:
                    win.p_staging[(dst, src)] = w * win.p_main[src]
    finally:
        if mutex:
            mutex.release()


def _validate_self_weight(win: _Window, self_weight) -> None:
    """Dispatch-time check (BEFORE the async submit): a bad vector must
    fail loudly at the call site, not inside a worker after remote edge
    sends already landed at peers."""
    if self_weight is None:
        return
    sw = np.asarray(self_weight, dtype=float)
    if sw.ndim and sw.shape != (win.n,):
        # The vector form is GLOBAL-rank indexed (n,), even for owned-
        # layout windows — an owned-length vector would silently mis-scale
        # on process 0 and index out of bounds everywhere else.
        raise ValueError(
            f"self_weight vector must have shape ({win.n},) — one entry "
            f"per global rank — got {sw.shape}")


def _publish_self(win, tensor, self_weight) -> None:
    # Self-scaling happens AFTER the edge sends so outgoing payloads carry
    # the PRE-scaled associated-P mass (column-stochastic conservation:
    # self_weight + sum of dst weights == 1 must hold on p_old).  Only
    # owned rows are authoritative here.
    sw = np.asarray(self_weight, dtype=float)
    with win.lock:
        sw_vec = sw if sw.ndim else np.full(win.n, float(sw))
        for r in win.owned:
            # Explicit cast: a float64 payload on a float32 window must
            # not leak wider rows into main (cross-process GET replies
            # and state-dict round trips size rows by win.dtype).
            win.main[r] = np.asarray(
                tensor[win.row_of[r]] * sw_vec[r], dtype=win.dtype)
            win.main_versions[r] += 1
            if _store.associated_p_enabled:
                win.p_main[r] *= sw_vec[r]


def win_put_nonblocking(tensor, name: str, *, self_weight=None,
                        dst_weights=None, require_mutex: bool = False) -> int:
    """Scaled overwrite of each destination's buffer-for-me (async).

    ``self_weight`` — scalar or per-rank (n,) vector — rescales my exposed
    memory to ``self_weight * tensor`` (applied after the sends dispatch).
    With associated-P enabled, push-sum column-stochastic scaling applies: the
    caller should pass ``dst_weights``/``self_weight`` summing to 1 per source
    (reference ``_DistributedPushSumOptimizer``,
    ``torch/optimizers.py:1026-1178``)."""
    win = _store.get(name)  # raise early on unknown window
    # Zero-copy XLA put path: a committed device array stays on device —
    # the worker hands its buffer pointer to the native plan executor
    # (remote edges) and takes a zero-copy host view only if local edges
    # or a self-publish need it.  Everything else converts here, exactly
    # as before.
    t = tensor if xlaffi.keep_device_ok(tensor, win) else _to_numpy(tensor)
    _validate_payload(win, t, "win_put")
    _validate_self_weight(win, self_weight)
    edges = _resolve_edge_weights(dst_weights, win.out_nbrs, 1.0,
                                  ranks=win.owned)
    _validate_edges(edges, win.out_nbrs, peer_is_src=False, op="win_put")
    _count_win_op("put", t.nbytes, edges)
    from bluefog_tpu.utils.timeline import op_span

    def _work():
        with op_span(f"win_put.{name}", "COMMUNICATE"):
            _do_put(name, t, edges, require_mutex,
                    accumulate=False, self_weight=self_weight)
    return _store.submit(_work)


def win_put(tensor, name: str, *, self_weight: float = None, dst_weights=None,
            require_mutex: bool = False) -> bool:
    win_wait(win_put_nonblocking(tensor, name, self_weight=self_weight,
                                 dst_weights=dst_weights,
                                 require_mutex=require_mutex))
    return True


def win_accumulate_nonblocking(tensor, name: str, *, self_weight=None,
                               dst_weights=None,
                               require_mutex: bool = False) -> int:
    """Scaled add into each destination's buffer-for-me (async).

    ``self_weight`` semantics as in ``win_put_nonblocking`` (scalar or (n,)
    vector, applied after the sends so P mass is conserved)."""
    win = _store.get(name)  # raise early on unknown window
    t = tensor if xlaffi.keep_device_ok(tensor, win) else _to_numpy(tensor)
    _validate_payload(win, t, "win_accumulate")
    _validate_self_weight(win, self_weight)
    edges = _resolve_edge_weights(dst_weights, win.out_nbrs, 1.0,
                                  ranks=win.owned)
    _validate_edges(edges, win.out_nbrs, peer_is_src=False,
                    op="win_accumulate")
    _count_win_op("accumulate", t.nbytes, edges)
    from bluefog_tpu.utils.timeline import op_span

    def _work():
        with op_span(f"win_accumulate.{name}", "COMMUNICATE"):
            _do_put(name, t, edges, require_mutex,
                    accumulate=True, self_weight=self_weight)
    return _store.submit(_work)


def win_accumulate(tensor, name: str, *, self_weight=None,
                   dst_weights=None, require_mutex: bool = False) -> bool:
    win_wait(win_accumulate_nonblocking(
        tensor, name, self_weight=self_weight, dst_weights=dst_weights,
        require_mutex=require_mutex))
    return True


def _do_get(name: str, edges: Dict[tuple, float], require_mutex: bool) -> None:
    from bluefog_tpu.utils.timeline import op_span
    try:
        win = _store.get(name)
    except KeyError:
        return  # window freed after dispatch; get becomes a no-op
    d = _store.distrib
    remote = []
    for (dst, src), w in edges.items():
        if not _owns(dst):
            continue  # dst's owner performs this edge
        if not _owns(src):
            remote.append((dst, src, w))
            continue
        with op_span(f"win_get.{name}.{src}->{dst}", "COMMUNICATE"):
            mutex = win.mutexes[src] if require_mutex else None
            if mutex:
                mutex.acquire()
            try:
                with win.lock:
                    if (dst, src) not in win.staging:
                        continue
                    win.staging[(dst, src)] = (win.main[src]
                                               * win.dtype.type(w))
                    win.versions[dst, src] += 1
                    if _store.associated_p_enabled:
                        win.p_staging[(dst, src)] = w * win.p_main[src]
            finally:
                if mutex:
                    mutex.release()
    if remote:
        # One-sided pull: request each remote row, then wait for the replies
        # (the blocking analogue of chunked MPI_Get, mpi_controller.cc:1123).
        req_procs = {d.rank_owner[src] for (_, src, _) in remote}
        tok = d.transport.error_token(
            {d.proc_addr[p] for p in req_procs})
        with d.cv:
            for (dst, src, w) in remote:
                key = (name, dst, src)
                d.pending_gets[key] = d.pending_gets.get(key, 0) + 1
        for (dst, src, w) in remote:
            with op_span(f"win_get_req.{name}.{src}->{dst}", "COMMUNICATE"):
                _send_to_rank_owner(src, OP_GET_REQ, name, src, dst, w)
        # GET_REQs are urgent (the senders flush them on sight); the
        # explicit flush — scoped to the owners actually asked — surfaces
        # any send error here instead of a timeout below misread as a
        # dead peer.
        _flush_transport(req_procs, since=tok)
        deadline_keys = [(name, dst, src) for (dst, src, _) in remote]
        with d.cv:
            ok = d.cv.wait_for(
                lambda: all(d.pending_gets.get(k, 0) <= 0
                            for k in deadline_keys),
                timeout=_MSG_TIMEOUT_SEC)
            for k in deadline_keys:
                d.pending_gets.pop(k, None)
        if not ok:
            raise ConnectionError(
                f"win_get({name!r}): no reply from remote rank(s) "
                f"{sorted({s for (_, s, _) in remote})} within "
                f"{_MSG_TIMEOUT_SEC:.0f}s")


def win_get_nonblocking(name: str, *, src_weights=None,
                        require_mutex: bool = False) -> int:
    """Pull ``w * main[src]`` from each in-neighbor into my staging (async)."""
    win = _store.get(name)
    edges = _resolve_edge_weights(src_weights, win.in_nbrs, 1.0,
                                  peer_is_src=True, ranks=win.owned)
    _validate_edges(edges, win.in_nbrs, peer_is_src=True, op="win_get")
    _count_win_op("get", len(edges) * _row_nbytes(win), edges)
    from bluefog_tpu.utils.timeline import op_span

    def _work():
        with op_span(f"win_get.{name}", "COMMUNICATE"):
            _do_get(name, edges, require_mutex)
    return _store.submit(_work)


def win_get(name: str, *, src_weights=None, require_mutex: bool = False) -> bool:
    win_wait(win_get_nonblocking(name, src_weights=src_weights,
                                 require_mutex=require_mutex))
    return True


# ---------------------------------------------------------------------------
# Update (sync + weighted combine)
# ---------------------------------------------------------------------------

def _default_update_weights(win: _Window):
    """Topology-default combine weights — OWNED edges only (non-owned dst
    rows are combined by their owners; enumerating them here would cost
    O(n·indeg) python work per update at pod scale)."""
    from bluefog_tpu import basics
    from bluefog_tpu import topology as topology_util
    if basics.is_topo_weighted():
        wmat = topology_util.weight_matrix(basics.load_topology())
        self_w = np.diag(wmat)
        nbr_w = {(dst, src): wmat[src, dst]
                 for dst in win.owned for src in win.in_nbrs[dst]}
    else:
        self_w = np.array([1.0 / (len(win.in_nbrs[r]) + 1)
                           for r in range(win.n)])
        nbr_w = {(dst, src): 1.0 / (len(win.in_nbrs[dst]) + 1)
                 for dst in win.owned for src in win.in_nbrs[dst]}
    return self_w, nbr_w


def win_update(name: str, *, self_weight=None, neighbor_weights=None,
               reset_weights: bool = False, require_mutex: bool = False):
    """Combine self memory with in-neighbor staging buffers, in place.

    ``out_i = sw_i * main_i + sum_src w[dst=i,src] * staging[i,src]``; writes
    back to self memory and returns the result as a jax array — rank-major
    ``(n, ...)`` for rank-layout windows, ``(len(owned), ...)`` for
    owned-layout ones.  ``reset_weights`` zeroes the staging buffers
    afterwards.

    Multi-process: only rows of ranks owned by this process are combined
    and returned fresh (every process runs the same update for its own
    ranks); the owned-slice store keeps NO copies of other ranks' rows, so
    a rank-major return zero-fills them — consume owned rows only (the
    optimizers' ``_merge_owned`` masking, or the owned layout, which never
    materializes the O(n) array at all).

    Locking: ``win.lock`` is held to SNAPSHOT the inputs, to SWAP the
    results back, and (keep-staging mode) for at most ONE edge's multiply
    at a time during the combine — the transport drain thread is never
    serialized behind the whole O(n·indeg·size) combine, only behind a
    single O(size) scale of the slot it is racing with (reference analogue:
    ``MPI_Win_sync`` is a memory barrier, not a critical section over the
    combine, ``mpi_controller.cc:890-915``).  With ``reset_weights`` the
    staging buffers are MOVED out at snapshot time (fresh zero buffers swap
    in, no copy): a put or accumulate landing mid-combine writes into the
    fresh buffer and is pending for the next update — exactly the serialize-
    after ordering, with no double-counted mass.  Without ``reset_weights``
    the slots stay live and each is read once under its brief per-edge
    lock (no point-in-time cross-edge snapshot is implied: an edge read
    later in the combine may include a put that landed after an earlier
    edge's read — any such put serializes before this update for its edge
    and the pending counters account for it exactly)."""
    from bluefog_tpu.utils.timeline import op_span
    win = _store.get(name)
    _count_win_op("update", len(win.owned) * _row_nbytes(win), {})
    owned = win.owned
    acquired = []
    if require_mutex:
        for r in owned:  # only owned mutexes matter — remote writers to my
            win.mutexes[r].acquire()   # staging serialize on my owner locks
            acquired.append(win.mutexes[r])
    win.update_lock.acquire()  # one update at a time per window: a
    acquired.append(win.update_lock)   # concurrent update's swap must not
    try:                               # mis-read this one's version resets
        with op_span(f"win_update.{name}", "UPDATE"):
            if (self_weight is None) != (neighbor_weights is None):
                raise ValueError(
                    "self_weight and neighbor_weights have to be presented at "
                    "the same time (matches reference torch/mpi_ops.py:1050)")
            if self_weight is None and neighbor_weights is None:
                self_w, nbr_w = _default_update_weights(win)
            else:
                n = win.n
                self_w = np.full(n, 1.0 if self_weight is None else self_weight)
                nbr_w = _resolve_edge_weights(
                    neighbor_weights, win.in_nbrs, 1.0, peer_is_src=True,
                    ranks=win.owned)
            self_w_vec = self_w if isinstance(self_w, np.ndarray) \
                else np.full(win.n, float(self_w))
            # -- snapshot (under lock; moves for reset, copies otherwise) ---
            stag: Dict[tuple, np.ndarray] = {}
            p_stag: Dict[tuple, float] = {}
            with win.lock:
                out = {r: win.main[r].copy() for r in owned}
                p_out = {r: win.p_main[r] for r in owned}
                p_snap = dict(p_out)        # pre-combine P, for publish
                for dst in owned:           # reconciliation in the swap
                    for src in win.in_nbrs[dst]:
                        k = (dst, src)
                        if k not in win.staging:
                            continue
                        if nbr_w.get(k) is None:
                            # Edge excluded from an explicit partial
                            # neighbor_weights: its gossip mass is NOT
                            # consumed by this update — leave staging,
                            # P and version counters pending (reference
                            # resets only buffers included in
                            # neighbor_weights, torch/mpi_ops.py:1068).
                            continue
                        if reset_weights:
                            # Move: consume the slot now.  Zero-fill is
                            # lazy-paged — far cheaper than a copy.
                            stag[k] = win.staging[k]
                            win.staging[k] = np.zeros(win.shape, win.dtype)
                            p_stag[k] = win.p_staging[k]
                            win.p_staging[k] = 0.0
                            win.versions[dst, src] = 0
                        # else: keep-staging path snapshots NOTHING here —
                        # the combine reads each live slot (data + P) under
                        # a brief per-edge lock hold instead, saving a full
                        # read+write pass over every staging buffer.
                ver = dict(win.versions)
                mver = dict(win.main_versions)
            # -- combine (locks held per edge at most; one scratch buffer) --
            tmp = np.empty(win.shape, win.dtype)
            for dst in owned:
                acc = out[dst]
                np.multiply(acc, win.dtype.type(self_w_vec[dst]), out=acc)
                p_acc = p_out[dst] * self_w_vec[dst]
                for src in win.in_nbrs[dst]:
                    k = (dst, src)
                    w = nbr_w.get(k)
                    if w is None:
                        continue
                    if reset_weights:
                        if k not in stag:
                            continue
                        np.multiply(stag[k], win.dtype.type(w), out=tmp)
                    else:
                        # Slot still live: scale it under win.lock so a
                        # concurrent drain-thread write cannot tear the
                        # read (held for ONE edge's multiply, not the
                        # whole combine).
                        with win.lock:
                            if k not in win.staging:
                                continue
                            np.multiply(win.staging[k],
                                        win.dtype.type(w), out=tmp)
                            p_stag[k] = win.p_staging[k]
                            # This update consumed everything in the slot
                            # as of NOW — make the swap's pending-count
                            # delta exact for puts that landed between
                            # the snapshot and this read.
                            ver[dst, src] = win.versions[dst, src]
                    np.add(acc, tmp, out=acc)
                    p_acc += w * p_stag.get(k, 0.0)
                p_out[dst] = p_acc
            # -- swap (under lock) ------------------------------------------
            # Scoped to owned ranks: rows owned by other processes stay
            # untouched (their owners run the same update), and version
            # counters reset per consumed edge only — one rank's update never
            # wipes another's staleness counters (reference per-target
            # semantics, mpi_context.cc:91-113).
            with win.lock:
                for dst in owned:
                    if win.main_versions[dst] == mver[dst]:
                        win.main[dst] = out[dst]
                        if _store.associated_p_enabled:
                            win.p_main[dst] = p_out[dst]
                    elif _store.associated_p_enabled:
                        # A self-publish landed mid-combine; it serializes
                        # after this update.  For main that means the
                        # publish value stands (a publish REPLACES main, so
                        # the combine result is superseded either way).  P
                        # is MULTIPLICATIVE (publish does p_main *= sw), so
                        # serialize-after means p = p_combined * sw: apply
                        # the publishes' accumulated factor on top of the
                        # combined mass, or the consumed staging P would
                        # vanish and push-sum conservation break.
                        factor = (win.p_main[dst] / p_snap[dst]
                                  if p_snap[dst] != 0.0 else 1.0)
                        win.p_main[dst] = p_out[dst] * factor
                    # The returned array still reports this update's result
                    # (pre-publish), as a serialized update-then-publish
                    # would.
                    if not reset_weights:
                        # Consume-in-place semantics: counters drop to the
                        # number of updates that landed mid-combine (those
                        # serialize after this update).
                        for src in win.in_nbrs[dst]:
                            if (dst, src) not in win.staging:
                                continue
                            if nbr_w.get((dst, src)) is None:
                                # Unconsumed edge (excluded by a partial
                                # neighbor_weights): its pending count is
                                # untouched — rebaselining it would
                                # under-report staleness.
                                continue
                            delta = win.versions[dst, src] - ver[dst, src]
                            win.versions[dst, src] = max(0, delta)
            if win.layout == "owned":
                ret = np.stack([out[r] for r in owned])
            else:
                # Rank-major return: owned rows carry the combine result,
                # non-owned rows are zero (their owners run the same
                # update; no stale copies are kept in the owned layout).
                ret = np.zeros((win.n,) + win.shape, win.dtype)
                for r in owned:
                    ret[r] = out[r]
            # Commit re-entry: ``ret`` is fresh and uniquely owned, so it
            # re-enters jax as a zero-copy view where the runtime allows
            # (CPU backend aliases; else dlpack) instead of a host→device
            # re-upload — a verified copy counts into
            # bf_win_host_copy_bytes_total{path="commit"}.
            return xlaffi.commit_to_jax(ret)
    finally:
        for m in acquired:
            m.release()


def win_update_then_collect(name: str, *, require_mutex: bool = True):
    """Sum self memory with all received contributions and zero the staging
    buffers — the push-sum collect step (``torch/mpi_ops.py:1206-1260``)."""
    win = _store.get(name)
    _count_win_op("update_then_collect",  # + the inner "update"
                  len(win.owned) * _row_nbytes(win), {})
    # Owned edges only: collects of non-owned ranks run at their owners.
    all_edges = {(dst, src): 1.0
                 for dst in win.owned for src in win.in_nbrs[dst]}
    return win_update(name, self_weight=1.0, neighbor_weights=all_edges,
                      reset_weights=True, require_mutex=require_mutex)


# ---------------------------------------------------------------------------
# Handles / mutex / versions / associated-P
# ---------------------------------------------------------------------------

def win_wait(handle: int) -> bool:
    from bluefog_tpu.utils import telemetry
    with _store.lock:
        fut = _store.handles.pop(handle, None)
        telemetry.set_gauge("bf_win_inflight_handles", len(_store.handles))
    if fut is None:
        return True
    from bluefog_tpu.utils import stall
    t0 = telemetry.start_timer()
    try:
        with stall.watch(f"win_wait(handle={handle})"):
            fut.result()
    except KeyError:
        return False  # window freed while the op was in flight
    finally:
        # Host-side latency of one nonblocking window op: queue wait on
        # the worker pool + the op's own edge sends/replies.
        telemetry.observe_since(t0, "bf_win_wait_seconds")
    return True


def win_poll(handle: int) -> bool:
    with _store.lock:
        fut = _store.handles.get(handle)
    return fut is None or fut.done()


@contextmanager
def win_mutex(name: str, *, for_self: bool = False,
              ranks: Optional[List[int]] = None):
    """Acquire the distributed mutex of the given ranks (default: my
    out-neighbors; ``for_self`` adds my own rank) — reference
    ``mpi_controller.cc:1532-1602`` exposed via ``bf.win_mutex``.

    Ranks owned by other processes are locked through the transport
    (ACQ→GRANT, released by REL): the owner's worker holds the rank's local
    lock until our release message lands.  Acquisition is in ascending rank
    order everywhere, so cross-process lock cycles cannot form."""
    from bluefog_tpu import basics
    basics._require_active()
    win = _store.get(name)
    d = _store.distrib
    if ranks is None:
        ranks = sorted(set(basics.out_neighbor_ranks(basics.rank())))
        if for_self:
            ranks = sorted(set(ranks + [basics.rank()]))
    my_rank = basics.rank()
    import time as _time
    from contextlib import ExitStack
    from bluefog_tpu.utils import telemetry
    with ExitStack() as stack:
        for r in sorted(set(ranks)):  # ascending everywhere: no lock cycles
            if _owns(r):
                t0 = _time.monotonic()
                win.mutexes[r].acquire()
                telemetry.inc("bf_win_mutex_acquisitions_total", kind="local")
                telemetry.inc("bf_win_mutex_wait_seconds_total",
                              _time.monotonic() - t0, kind="local")
                stack.callback(win.mutexes[r].release)
            else:
                stack.enter_context(_remote_mutex(name, r, my_rank))
        yield


def win_fence(name: Optional[str] = None) -> None:
    """Collective epoch fence over the one-sided family (parity:
    ``bf.win_fence``, reference ``torch/mpi_win_ops.cc:608-646``).

    On return: every window op this process dispatched has executed, every
    transport message any process sent before its fence has been applied at
    its target, and all processes have reached the fence.  Per-connection
    TCP FIFO makes the ack exact: our FENCE_REQ trails our puts on the same
    stream, so the peer's ack certifies those puts were applied.  On the
    striped transport the REQ fans out across every stripe of each peer
    and the ack answers the LAST copy — the same certificate, per
    stripe."""
    from bluefog_tpu import basics
    basics._require_active()
    with _store.lock:
        outstanding = list(_store.handles.items())
    errors = []
    for _, fut in outstanding:
        try:
            fut.result(timeout=_MSG_TIMEOUT_SEC)
        except KeyError:
            pass  # window freed while the op was in flight (win_wait parity)
        except Exception as e:  # noqa: BLE001 — re-raised below
            errors.append(e)
    with _store.lock:
        # Fence completes the handles it waited on — a fence-only flow
        # (nonblocking ops, no win_wait) must not leak futures forever.
        for h, _ in outstanding:
            _store.handles.pop(h, None)
    if errors:
        raise errors[0]
    d = _store.distrib
    if d is not None:
        peers = [p for p in d.proc_addr if p != d.my_proc]
        with d.cv:
            d.fence_acks = 0
        tok = d.transport.error_token()
        # Striped transport: one FENCE_REQ copy rides EVERY stripe of
        # each peer (the copy count travels in the wire weight field),
        # and the peer acks only the last copy — so the ack certifies
        # that every stripe, any of which may carry this process's puts,
        # has drained past the fence.  Single-stream sends exactly one
        # copy with weight 0.0 (the pre-stripe wire, bit for bit).
        n_str = _transport_stripes(d)
        w = _fanout_weight(n_str)
        serial = _fanout_serial(d, n_str)
        for p in peers:
            for k in range(n_str):
                _send_to_proc(p, OP_FENCE_REQ, name or "", d.my_rank, -1,
                              w, p_weight=serial, stripe=k)
        # Fence requests always flush the peer's queue first: FENCE_REQ is
        # an urgent op (enqueued BEHIND any still-queued puts, flushed on
        # sight), and this explicit drain surfaces send errors before the
        # ack wait — so the ack still certifies every prior put applied.
        _flush_transport(since=tok)
        with d.cv:
            ok = d.cv.wait_for(lambda: d.fence_acks >= len(peers),
                               timeout=_MSG_TIMEOUT_SEC)
        if not ok:
            raise ConnectionError(
                f"win_fence: missing acks ({d.fence_acks}/{len(peers)}) "
                f"after {_MSG_TIMEOUT_SEC:.0f}s")
    basics.barrier()


def win_state_dict(name: str) -> Dict[str, object]:
    """Snapshot a window's complete state for checkpointing: main memory,
    per-edge staging, version counters and associated-P.  Pairs with
    :func:`win_load_state_dict` so elastic restarts (``utils.elastic``)
    can resume async-gossip training without losing in-staging mass —
    push-sum's conservation invariant survives a crash/restore cycle.
    The returned tree is plain numpy (orbax/`utils.checkpoint`-ready);
    staging keys are ``"dst:src"`` strings.

    Serializes against in-flight ``win_update`` calls via ``update_lock``:
    the update's snapshot/combine/swap window holds mass in a local that
    no lock-free snapshot could see — without this, a snapshot landing
    mid-update would silently drop it."""
    win = _store.get(name)
    with win.update_lock, win.lock:
        return {
            "main": {str(r): win.main[r].copy() for r in win.owned},
            "staging": {f"{d}:{s}": a.copy()
                        for (d, s), a in win.staging.items()},
            "versions": {f"{d}:{s}": np.int64(v)
                         for (d, s), v in win.versions.items()},
            "main_versions": {str(r): np.int64(win.main_versions[r])
                              for r in win.owned},
            "p_main": {str(r): np.float64(win.p_main[r])
                       for r in win.owned},
            "p_staging": {f"{d}:{s}": np.float64(v)
                          for (d, s), v in win.p_staging.items()},
            # Async-mode stale-residual store: mass the bounded-staleness
            # policy held back and has not yet folded — without it a
            # checkpoint taken mid-async-epoch would silently lose
            # conserved push-sum mass.  Empty outside async mode.
            "stale_residual": {f"{d}:{s}": a.copy()
                               for (d, s), a in win.stale_residual.items()},
            "p_stale_residual": {
                f"{d}:{s}": np.float64(v)
                for (d, s), v in win.p_stale_residual.items()},
        }


def win_load_state_dict(name: str, state: Dict[str, object]) -> None:
    """Restore a window from :func:`win_state_dict` output.  The window
    must already exist (``win_create`` with the same topology) — this
    overwrites its buffers in place (serialized against in-flight updates,
    as in :func:`win_state_dict`)."""
    win = _store.get(name)
    if isinstance(state.get("main"), np.ndarray) or (
            hasattr(state.get("main"), "ndim")
            and getattr(state["main"], "ndim", 0) >= 1):
        raise ValueError(
            f"win_load_state_dict({name!r}): snapshot uses the pre-owned-"
            "slice array format (rank-major 'main'); re-snapshot with this "
            "version's win_state_dict — formats are not cross-version "
            "compatible")
    main = {int(r): np.asarray(v) for r, v in dict(state["main"]).items()}
    if set(main) != set(win.owned):
        raise ValueError(
            f"win_load_state_dict({name!r}): snapshot rows "
            f"{sorted(main)} do not match this process's owned ranks "
            f"{win.owned}")
    for r, v in main.items():
        if v.shape != win.shape or v.dtype != win.dtype:
            raise ValueError(
                f"win_load_state_dict({name!r}): snapshot row {r} "
                f"{v.shape}/{v.dtype} does not match the window "
                f"{win.shape}/{win.dtype}")
    staging = {tuple(int(x) for x in k.split(":")): np.asarray(v)
               for k, v in dict(state["staging"]).items()}
    if set(staging) != set(win.staging):
        raise ValueError(
            f"win_load_state_dict({name!r}): snapshot edges do not match "
            "the window's topology (recreate the window under the "
            "topology it was saved with)")
    with win.update_lock, win.lock:
        for r, v in main.items():
            win.main[r] = v.copy()
        for k, v in staging.items():
            win.staging[k][:] = v
        for k, v in dict(state["versions"]).items():
            win.versions[tuple(int(x) for x in k.split(":"))] = int(v)
        for r, v in dict(state["main_versions"]).items():
            win.main_versions[int(r)] = int(v)
        for r, v in dict(state["p_main"]).items():
            win.p_main[int(r)] = float(v)
        for k, v in dict(state["p_staging"]).items():
            win.p_staging[tuple(int(x) for x in k.split(":"))] = float(v)
        # Optional (snapshots predating async mode lack them): restore
        # the stale-residual store for edges the window still has.
        win.stale_residual.clear()
        win.p_stale_residual.clear()
        for k, v in dict(state.get("stale_residual", {})).items():
            key = tuple(int(x) for x in k.split(":"))
            if key in win.staging:
                win.stale_residual[key] = np.asarray(v).copy()
        for k, v in dict(state.get("p_stale_residual", {})).items():
            key = tuple(int(x) for x in k.split(":"))
            if key in win.staging:
                win.p_stale_residual[key] = float(v)


def get_win_version(name: str, rank: Optional[int] = None) -> Dict[int, int]:
    """Per-in-neighbor update counts since the last ``win_update``.

    Only OWNED ranks carry version state (their owners track the rest) —
    asking for a non-owned rank raises rather than inventing zeros."""
    from bluefog_tpu import basics
    win = _store.get(name)
    r = basics.rank() if rank is None else rank
    if r not in win.main_versions:
        raise ValueError(
            f"get_win_version({name!r}): rank {r} is owned by another "
            "process — query its owner")
    with win.lock:
        return {src: int(win.versions[r, src]) for src in win.in_nbrs[r]}


def win_associated_p(name: str, rank: Optional[int] = None) -> float:
    """The push-sum de-bias scalar of a rank (all ranks if rank is None).

    Non-owned entries of the full VECTOR report 1.0 (the initial value, a
    placeholder for rows the caller masks anyway); an EXPLICIT non-owned
    rank query raises instead of fabricating a value — its authoritative P
    lives at its owner (same rule as :func:`get_win_version`)."""
    win = _store.get(name)
    with win.lock:
        if rank is None:
            p = np.ones(win.n)
            for r in win.owned:
                p[r] = win.p_main[r]
            return p
        if rank not in win.p_main:
            raise ValueError(
                f"win_associated_p({name!r}): rank {rank} is owned by "
                "another process — query its owner")
        return float(win.p_main[rank])


def turn_on_win_ops_with_associated_p() -> None:
    _store.associated_p_enabled = True


def turn_off_win_ops_with_associated_p() -> None:
    _store.associated_p_enabled = False
