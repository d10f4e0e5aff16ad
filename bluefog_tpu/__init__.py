"""bluefog_tpu: a TPU-native decentralized training framework.

A from-scratch JAX/XLA re-design of BlueFog's capability set (neighbor
averaging and gossip over static/dynamic virtual topologies, hierarchical
machine-level graphs, one-sided async windows, push-sum) with topologies
compiled to ``lax.ppermute`` / ``psum`` schedules over TPU mesh axes instead of
an MPI/NCCL background thread with rank-0 negotiation.

Public surface mirrors ``import bluefog.torch as bf`` (reference
``bluefog/torch/__init__.py:39-77``):

>>> import bluefog_tpu as bf
>>> bf.init()
>>> y = bf.neighbor_allreduce(x)
"""

import time as _time
_import_t0 = _time.perf_counter()

from bluefog_tpu import topology  # noqa: F401
from bluefog_tpu import topology as topology_util  # parity alias  # noqa: F401

from bluefog_tpu.version import __version__  # noqa: F401

# Module-level context API (init/rank/size/ops) — imported lazily to keep
# `import bluefog_tpu` cheap and jax-initialization-free until first use.
from bluefog_tpu.basics import (  # noqa: F401
    init,
    init_distributed,
    shutdown,
    initialized,
    suspend,
    resume,
    suspended,
    size,
    rank,
    local_size,
    local_rank,
    machine_size,
    machine_rank,
    is_homogeneous,
    owned_ranks,
    mesh,
    hierarchical_mesh,
    rank_map,
    set_topology,
    set_machine_topology,
    placement_info,
    synthesis_info,
    membership_info,
    gang_info,
    load_topology,
    load_machine_topology,
    in_neighbor_ranks,
    out_neighbor_ranks,
    in_neighbor_machine_ranks,
    out_neighbor_machine_ranks,
    allreduce,
    allreduce_,
    allreduce_nonblocking,
    allreduce_nonblocking_,
    allgather,
    allgather_nonblocking,
    allgather_v,
    broadcast,
    broadcast_,
    broadcast_nonblocking,
    broadcast_nonblocking_,
    broadcast_optimizer_state,
    set_skip_negotiate_stage,
    get_skip_negotiate_stage,
    mpi_threads_supported,
    nccl_built,
    unified_mpi_window_model_supported,
    neighbor_allgather,
    neighbor_allgather_nonblocking,
    neighbor_allgather_v,
    neighbor_allreduce,
    neighbor_allreduce_nonblocking,
    dynamic_neighbor_allreduce,
    dynamic_neighbor_allreduce_nonblocking,
    hierarchical_neighbor_allreduce,
    hierarchical_neighbor_allreduce_nonblocking,
    dynamic_hierarchical_neighbor_allreduce,
    dynamic_hierarchical_neighbor_allreduce_nonblocking,
    hierarchical_gossip,
    hierarchical_gossip_nonblocking,
    hierarchical_gossip_info,
    local_allreduce,
    local_allreduce_nonblocking,
    pair_gossip,
    pair_gossip_nonblocking,
    poll,
    wait,
    synchronize,
    barrier,
    to_numpy,
    broadcast_parameters,
    allreduce_parameters,
)

from bluefog_tpu.ops.window import (  # noqa: F401
    win_create,
    win_free,
    win_put,
    win_put_nonblocking,
    win_get,
    win_get_nonblocking,
    win_accumulate,
    win_accumulate_nonblocking,
    win_update,
    win_update_then_collect,
    win_wait,
    win_poll,
    win_mutex,
    win_fence,
    win_flush,
    win_state_dict,
    win_load_state_dict,
    get_win_version,
    get_current_created_window_names,
    win_associated_p,
    turn_on_win_ops_with_associated_p,
    turn_off_win_ops_with_associated_p,
    # Barrier-free async gossip (BLUEFOG_TPU_ASYNC): fold held-back
    # stale mass / read the async block programmatically.
    win_fold_stale_residuals,
    async_info,
)

# Zero-copy XLA window put path (BLUEFOG_TPU_WIN_XLA) diagnostics:
# armed/disarm-reason/handler capability, for operators and the bench.
from bluefog_tpu.ops.xlaffi import info as win_xla_info  # noqa: F401

from bluefog_tpu import data  # noqa: F401  (DistributedSampler, ShardedLoader)
from bluefog_tpu import optim  # noqa: F401  (Distributed*Optimizer family)

from bluefog_tpu.utils.timeline import (  # noqa: F401
    timeline_start_activity,
    timeline_end_activity,
    timeline_context,
    start_timeline,
    stop_timeline,
)

from bluefog_tpu.utils import telemetry  # noqa: F401
from bluefog_tpu.utils.telemetry import telemetry_snapshot  # noqa: F401

# Transport flight recorder (BLUEFOG_TPU_FLIGHT_RECORDER): dump the
# in-memory event ring to flightrec.<rank>.bin — the gossip black box
# `python -m bluefog_tpu.tools trace-gossip` merges across ranks.
from bluefog_tpu.utils.flightrec import dump as flight_recorder_dump  # noqa: F401,E501
# Link observatory (BLUEFOG_TPU_LINK_OBS): the cluster-wide measured
# link matrix — per-edge delay/jitter/divergence plus the hot edge —
# assembled over the aggregate-snapshot collective (call on all ranks).
from bluefog_tpu.utils.linkobs import link_report  # noqa: F401
# Elastic scale-up / coordinator-free bootstrap (BLUEFOG_TPU_ELASTIC_JOIN):
# bf.gang.init_elastic() / bf.gang.join_gang() — see docs/operations.md
# "Growing the gang".
from bluefog_tpu.ops import gang  # noqa: F401

from bluefog_tpu.utils import profiler  # noqa: F401
from bluefog_tpu.utils.profiler import step_profile  # noqa: F401

# What the import itself took of the way to the first step (the other parts
# come from bf.init() and opt.init(): docs/observability.md).
telemetry.set_gauge("bf_startup_seconds", _time.perf_counter() - _import_t0,
                    part="import")
