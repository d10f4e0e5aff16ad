"""Environment-variable config system.

Parity: the reference configures everything through ``BLUEFOG_*`` env vars
(``docs/env_variable.rst``); this module is the single authoritative inventory
for the TPU rebuild.  Values are read lazily on first access and cached; call
``reload()`` after mutating ``os.environ`` in tests.

| Variable | Default | Meaning |
|---|---|---|
| BLUEFOG_TIMELINE              | unset | timeline file prefix (one file/rank) |
| BLUEFOG_TPU_LOG_LEVEL         | warn  | trace/debug/info/warn/error/fatal |
| BLUEFOG_TPU_LOG_HIDE_TIME     | 0     | drop timestamps from log lines |
| BLUEFOG_TPU_NO_NATIVE         | 0     | never build/load the C++ core |
| BLUEFOG_TPU_PYTHON_TIMELINE   | 0     | force the Python timeline writer |
| BLUEFOG_TPU_STALL_WARNING_SEC | 60    | stall-detector threshold (0=off) |
| BLUEFOG_TPU_WIN_PORT          | 0     | DCN window-service port (0=ephemeral) |
| BLUEFOG_TPU_WIN_MAX_PENDING   | 4096  | inbound window-message queue bound |
| BLUEFOG_TPU_WIN_COMPRESSION   | none  | bf16 (halve cross-host window payloads) or sparse:<frac> (top-|magnitude| + sender error feedback) |
| BLUEFOG_TPU_WIN_COALESCE      | 1     | 0: legacy per-message transport sends |
| BLUEFOG_TPU_WIN_NATIVE        | 1     | 0: keep the transport hot loop (batch/drain/fold) in Python; 1 auto-falls back when the native core is missing/stale |
| BLUEFOG_TPU_WIN_XLA           | 1     | 0: pin the host-staged put path (the bitwise oracle); 1 auto-disarms (one warning) without jax.ffi, the bf_xla native symbols, or host-addressable device buffers |
| BLUEFOG_TPU_SHARDED_GOSSIP    | 1     | sharding-aware gossip (ops/sharded.py): with explicit shard specs, replicated leaves gossip over the full topology while sharded leaves gossip per replica group only — DCN bytes scale with the replicated fraction; 0 forces replicated-only gossip; fully replicated trees are bitwise identical either way |
| BLUEFOG_TPU_WIN_COALESCE_LINGER_MS | 1.0 | sender-worker linger before flushing a partial batch |
| BLUEFOG_TPU_WIN_COALESCE_BYTES | 1 MiB | queued bytes that force an immediate batch flush |
| BLUEFOG_TPU_WIN_TX_QUEUE      | 1024  | per-peer outbound queue bound (messages); full blocks the producer |
| BLUEFOG_TPU_WIN_STRIPES       | auto  | sockets/sender-workers/send-arenas per DCN peer; frames shard by (window, row); auto = placement model's dcn_link_cost (no model: 1) |
| BLUEFOG_TPU_WIN_DECODE_THREADS | auto | drain-side decode pool size (native path); 0 = inline single-thread decode; auto sizes from the host core count |
| BLUEFOG_TPU_WIN_RETRIES       | 1     | transient-send retries before ConnectionError (0=none) |
| BLUEFOG_TPU_WIN_RETRY_BACKOFF_MS | 50 | base of the jittered exponential retry backoff |
| BLUEFOG_TPU_TRACE_SAMPLE      | 0     | wire trace-tag sampling: "1/N" (or plain "N") tags every Nth put/accumulate with a (src, seq, origin-time, origin-step) trailer; 0/unset = off, wire bitwise identical |
| BLUEFOG_TPU_ASYNC             | 0     | 1: barrier-free async window-optimizer mode — no per-step transport fence, fold whatever has arrived, bounded-staleness policy; 0 = bitwise legacy lockstep |
| BLUEFOG_TPU_ASYNC_STALENESS_STEPS | 0 | staleness bound k (origin steps): contributions older than k steps at commit hit the staleness policy; 0 = unbounded (accept everything) |
| BLUEFOG_TPU_ASYNC_STALENESS_POLICY | reject | what happens to an over-bound contribution: reject (full mass to the stale-residual store) or downweight:<alpha> (alpha enters staging, 1-alpha to the store) |
| BLUEFOG_TPU_ASYNC_COLLECT_EVERY | 64  | drift backstop: every N async steps the optimizer fences the transport, folds the stale residuals back in and performs an exact collect; 0 = never |
| BLUEFOG_TPU_FLIGHT_RECORDER   | 0     | 1: record transport events (enqueue/flush/sendmsg/drain/decode/fold/commit) into the native in-memory ring, dumped to flightrec.<rank>.bin on fatal transport error / eviction / bf.flight_recorder_dump() |
| BLUEFOG_TPU_FLIGHT_RECORDER_EVENTS | 65536 | flight-recorder ring capacity (events; oldest overwritten) |
| BLUEFOG_TPU_FLIGHT_RECORDER_PATH | flightrec | dump path prefix (files are <prefix>.<rank>.bin) |
| BLUEFOG_TPU_LINK_OBS          | 1     | 0: disable the link observatory (utils/linkobs.py) — no per-edge delay/jitter/goodput/divergence estimation, no SLO evaluation, bitwise inert |
| BLUEFOG_TPU_SLO               | unset | declarative SLO rules, `<metric><op><value>` joined by `;` (e.g. `link_delay_us>50000;step_lag>128`); evaluated at step boundaries, breaches degrade /healthz + bump bf_slo_breaches_total + dump the flight recorder |
| BLUEFOG_TPU_TUNE              | 0     | 1: arm the self-tuning comm control plane (utils/tuner.py) — measured link costs re-price placement/synthesis (MeasuredModel) and adapt transport knobs online; 0 pins every knob and every modeled cost bitwise |
| BLUEFOG_TPU_TUNE_DIVERGENCE   | 3.0   | measured-vs-modeled divergence ratio that triggers a tuner adaptation epoch (same line as bf_link_divergence_ratio's x3 alert) |
| BLUEFOG_TPU_TUNE_DWELL_STEPS  | 20    | hysteresis: minimum steps between tuner epochs, and the revert-on-regression probation window length |
| BLUEFOG_TPU_CHURN             | 0     | 1: enable the elastic-gossip churn controller |
| BLUEFOG_TPU_CHURN_HEARTBEAT_MS | 250  | membership heartbeat period |
| BLUEFOG_TPU_CHURN_SUSPECT_MS  | 1500  | heartbeat silence before a peer is suspected |
| BLUEFOG_TPU_CHURN_STRAGGLER_STEPS | 0 | step lag that marks a live peer a straggler suspect (0=off) |
| BLUEFOG_TPU_ELASTIC_JOIN      | 0     | 1: enable the gossip-native join/bootstrap subsystem (ops/gang.py) — wired joins, the replicated endpoint directory, coordinator-free gang bootstrap; 0 = every legacy path bit-identical |
| BLUEFOG_TPU_GANG_DIR_PATH     | unset | endpoint-directory persistence prefix (files are <prefix>.<proc>.json, beside owned_ranks.json when pointed at the checkpoint dir); unset = in-memory only |
| BLUEFOG_TPU_JOIN_TIMEOUT_MS   | 30000 | how long a joining process waits for a join grant per contacted endpoint |
| BLUEFOG_TPU_CHAOS             | unset | fault-injection spec (set by bfrun --chaos) |
| BLUEFOG_TPU_TELEMETRY         | 1     | 0: disable the metric registry entirely |
| BLUEFOG_TPU_TELEMETRY_PORT    | unset | serve /metrics + /healthz (0=ephemeral) |
| BLUEFOG_TPU_TELEMETRY_CONSENSUS_EVERY | 10 | consensus-distance sample period (0=off) |
| BLUEFOG_TPU_PROFILE           | 0     | 1: enable the step profiler's periodic sampling |
| BLUEFOG_TPU_PROFILE_EVERY     | 50    | straggler-gather / synced-sample period (steps) |
| BLUEFOG_TPU_SCHEDULE_OPT      | 1     | 0: skip the min-round schedule repack |
| BLUEFOG_TPU_SCHEDULE_SYNTH    | 1     | 0: skip sketch-guided schedule synthesis (PR 5 congestion-repack path exactly) |
| BLUEFOG_TPU_SCHEDULE_SYNTH_SKETCH | auto | synthesis sketch: auto / ring-within-slice / hierarchical / chunked-pipelined |
| BLUEFOG_TPU_PLACEMENT         | 1     | 0: keep raw device-enumeration rank order |
| BLUEFOG_TPU_PLACEMENT_ITERS   | 1000  | simulated-annealing refinement iterations |
| BLUEFOG_TPU_PLACEMENT_ROUND_BUDGET | 2.0 | congestion-repack round budget (x König; 0=off) |
| BLUEFOG_TPU_FAKE_TORUS        | unset | synthetic torus spec (e.g. 4x8) for CPU testing |
| BLUEFOG_TPU_TORUS_WRAP        | auto  | real-coords wrap policy: auto / 1 (torus) / 0 (mesh) |
| BLUEFOG_TPU_HIER              | 0     | 1: enable two-level hierarchical gossip (dense ICI inner x sparse DCN outer) |
| BLUEFOG_TPU_HIER_OUTER_EVERY  | 1     | outer (inter-slice) cadence: communicate over DCN every k steps |
| BLUEFOG_TPU_HIER_INNER        | exp2  | intra-slice dense topology: exp2 / ring |
| BLUEFOG_TPU_HIER_OUTER        | exp2  | inter-slice one-peer walk: exp2 / ring |
| BLUEFOG_TPU_HIER_OUTER_COMPRESSION | none | outer-level codec: none / bf16 / sparse:<frac> (inner stays dense) |
| BLUEFOG_TPU_HIER_OUTER_SELF_WEIGHT | 0.5 | cadence-1 outer self weight (cadence-corrected to theta**k) |
| BFTPU_COORDINATOR             | unset | set by bfrun: coordinator host:port |
| BFTPU_NUM_PROCESSES           | unset | set by bfrun |
| BFTPU_PROCESS_ID              | unset | set by bfrun |
| BFTPU_LOCAL_ID                | 0     | set by bfrun: slot index on the host |
| BFTPU_LOCAL_SIZE              | 1     | set by bfrun: slots on this host |

(The ``BFTPU_*`` rendezvous variables are consumed directly by
``basics.init_distributed`` at process startup, not through ``Config`` —
they describe the launch, not tunable behavior.)

(The reference's fusion/cycle-time/vendor-override knobs have no TPU
equivalent: XLA owns fusion and scheduling, and there is exactly one vendor.)
"""

from __future__ import annotations

import os
from dataclasses import dataclass
from typing import Optional

__all__ = ["Config", "get", "reload", "COMPRESSION_VOCAB",
           "parse_sparse_frac", "compression_byte_factor",
           "parse_staleness_policy"]


# The one wire-compression vocabulary (window transport + hierarchical
# outer level): error messages enumerate it dynamically so growing the
# codec set can never leave a stale hardcoded list behind.
COMPRESSION_VOCAB = ("none", "bf16", "sparse:<frac>")


def parse_sparse_frac(value: str) -> float:
    """Fraction of a ``sparse:<frac>`` codec spec, validated in (0, 1]."""
    if ":" not in value:
        raise ValueError(
            f"malformed {value!r}: use 'sparse:<frac>' (e.g. 'sparse:0.25')")
    try:
        frac = float(value.split(":", 1)[1])
    except ValueError:
        raise ValueError(
            f"malformed {value!r}: the fraction must be a float in (0, 1], "
            "e.g. 'sparse:0.25'") from None
    if not 0.0 < frac <= 1.0:
        raise ValueError(f"sparse fraction must be in (0, 1], got {frac}")
    return frac


def compression_byte_factor(value: str) -> float:
    """Wire-bytes multiplier of a compression spec (the ONE accounting
    rule telemetry, BENCH json and the schedule-dump table share):
    ``none`` 1.0, ``bf16`` 0.5, ``sparse:<frac>`` the fraction."""
    if value in (None, "none"):
        return 1.0
    if value == "bf16":
        return 0.5
    if isinstance(value, str) and value.startswith("sparse"):
        return parse_sparse_frac(value)
    raise ValueError(
        f"unknown compression {value!r}; expected one of "
        f"{', '.join(COMPRESSION_VOCAB)}")


def _validated_compression(value: str, var: str =
                           "BLUEFOG_TPU_WIN_COMPRESSION") -> str:
    if value in ("none", "bf16"):
        return value
    if value.startswith("sparse"):
        parse_sparse_frac(value)  # raises on a malformed fraction
        return value
    raise ValueError(
        f"{var}={value!r} is not supported; expected one of "
        f"{', '.join(COMPRESSION_VOCAB)} (a typo here would otherwise "
        "silently disable compression)")


def parse_staleness_policy(value: str):
    """Parse ``BLUEFOG_TPU_ASYNC_STALENESS_POLICY`` into ``(kind, alpha)``:
    ``("reject", 0.0)`` or ``("downweight", alpha)`` with alpha in (0, 1).
    A typo fails loudly — a silently-misread policy would either drop
    fresh gossip or admit arbitrarily stale mass."""
    if value == "reject":
        return ("reject", 0.0)
    if value.startswith("downweight"):
        if ":" not in value:
            raise ValueError(
                f"malformed {value!r}: use 'downweight:<alpha>' "
                "(e.g. 'downweight:0.25')")
        try:
            alpha = float(value.split(":", 1)[1])
        except ValueError:
            raise ValueError(
                f"malformed {value!r}: the alpha must be a float in "
                "(0, 1), e.g. 'downweight:0.25'") from None
        if not 0.0 < alpha < 1.0:
            raise ValueError(
                f"downweight alpha must be in (0, 1), got {alpha} "
                "(1.0 would be a no-op — raise "
                "BLUEFOG_TPU_ASYNC_STALENESS_STEPS instead; 0.0 is "
                "'reject')")
        return ("downweight", alpha)
    raise ValueError(
        f"BLUEFOG_TPU_ASYNC_STALENESS_POLICY={value!r} is not supported; "
        "expected 'reject' or 'downweight:<alpha>'")


def _validated_staleness_policy(value: str) -> str:
    parse_staleness_policy(value)  # raises on malformed input
    return value


def _validated_sketch(value: str) -> str:
    # Lazy import: synthesis owns the sketch vocabulary (a module-level
    # import would cycle through bluefog_tpu/__init__ -> basics -> config).
    from bluefog_tpu.ops.synthesis import SKETCHES
    allowed = ("auto",) + SKETCHES
    if value not in allowed:
        raise ValueError(
            f"BLUEFOG_TPU_SCHEDULE_SYNTH_SKETCH={value!r} is not a known "
            f"sketch; expected one of {', '.join(allowed)} (a typo here "
            "would otherwise silently fall back to some default sketch)")
    return value


def _validated_slo(value: Optional[str]) -> Optional[str]:
    if value is None or not value.strip():
        return None
    # Lazy import: linkobs owns the SLO grammar (module-level would
    # cycle: linkobs imports config for its own gate).
    from bluefog_tpu.utils.linkobs import parse_slo_rules
    parse_slo_rules(value)  # raises on malformed input — fail at init,
    return value            # not silently-never-alert during an incident


def _parse_trace_sample(raw: Optional[str]) -> int:
    """``BLUEFOG_TPU_TRACE_SAMPLE`` parser: ``"1/N"`` (the documented
    spelling) or a plain integer period ``N`` both mean "tag every Nth
    data message"; ``0``/unset/empty disable tagging entirely (the wire
    stays bitwise identical).  A typo fails loudly — silently-off tracing
    during an incident would be worse than a crash at init."""
    if raw is None:
        return 0
    raw = raw.strip()
    if raw in ("", "0", "off"):
        return 0
    if raw.startswith("1/"):
        raw = raw[2:]
    try:
        period = int(raw)
    except ValueError:
        raise ValueError(
            f"BLUEFOG_TPU_TRACE_SAMPLE={raw!r} is not '1/N', an integer "
            "period N, or 0/off") from None
    if period < 0:
        raise ValueError(
            f"BLUEFOG_TPU_TRACE_SAMPLE period must be >= 0, got {period}")
    return period


def _flag(name: str, default: bool = False) -> bool:
    return os.environ.get(name, "1" if default else "0") in ("1", "true",
                                                             "True", "yes")


def _int_or_auto(name: str, floor: int = 0) -> int:
    """Integer env knob with an ``auto`` sentinel: unset or ``auto``
    returns -1 (the consumer derives the value), anything else must be an
    integer >= ``floor`` — a typo fails loudly, never silently pins some
    default."""
    raw = os.environ.get(name, "auto").strip().lower()
    if raw in ("", "auto"):
        return -1
    try:
        v = int(raw)
    except ValueError:
        raise ValueError(
            f"{name}={raw!r} is not an integer or 'auto'") from None
    if v < floor:
        raise ValueError(f"{name}={v} must be >= {floor} (or 'auto')")
    return v


@dataclass(frozen=True)
class Config:
    timeline_prefix: Optional[str]
    log_level: str
    log_hide_time: bool
    no_native: bool
    python_timeline: bool
    stall_warning_sec: float
    win_port: int
    win_max_pending: int
    win_compression: str
    # DCN transport coalescing (ops/transport.py): on by default — sends
    # enqueue onto per-peer queues flushed as OP_BATCH frames; off is the
    # escape hatch restoring one blocking native RPC per message.
    win_coalesce: bool
    win_coalesce_linger_ms: float
    win_coalesce_bytes: int
    win_tx_queue: int
    # Multi-stream striped DCN transport (ops/transport.py +
    # native/src/winsvc.cc): how many sockets + sender workers + send
    # arenas drive EACH peer endpoint.  Frames shard deterministically by
    # (window, row) so every stripe is an independent FIFO; fences and
    # mutex releases fan out across all stripes and complete only when
    # every stripe has drained.  -1 (the "auto" default) tunes the count
    # from the placement model's dcn_link_cost — flat hosts / no model
    # stay at 1, which reproduces the single-stream wire behavior
    # bitwise.  An explicit integer >= 1 pins it.
    win_stripes: int
    # Drain-side decode pool (native path only): how many C++ workers
    # decode/scale/fold inbound frames in parallel ahead of the ordered
    # drain emit.  0 pins the inline single-thread decode (bit-identical
    # — the pool changes scheduling, never bytes); -1 (the "auto"
    # default) sizes from the host core count.
    win_decode_threads: int
    # Native window-transport hot path (native/src/winsvc.cc bf_wintx_* +
    # bf_winsvc_drain): per-peer coalescing send queues, OP_BATCH frame
    # encode/decode and same-slot drain folding run in C++ instead of
    # Python threads under the GIL.  On by default but AUTO-falls back to
    # the (bit-identical) Python hot loop whenever the native core is
    # missing, stale, or predates these symbols; 0 pins the Python path
    # (the equivalence oracle) unconditionally.
    win_native: bool
    # Zero-copy XLA window put path (ops/xlaffi.py + native/src/xlacall.cc):
    # puts whose payload is a committed f32 jax.Array hand the XLA buffer
    # pointer straight to the native per-peer arenas — no device_get, no
    # per-edge temp, no tobytes.  On by default but AUTO-disarms (one
    # logged warning) when jax has no FFI module, the native core lacks
    # the bf_xla symbols, or device buffers are not host-addressable
    # (non-CPU backends, pending the TPU lowering); 0 pins the host-staged
    # PR-9 path unconditionally — the bitwise equivalence oracle.
    win_xla: bool
    # Sharded-aware gossip (ops/sharded.py): optimizers given per-leaf
    # PartitionSpecs neighbor-average only the replicated (data-parallel)
    # leaves over the full topology, while sharded (expert/stage/tensor)
    # leaves gossip their per-rank own-shard slice inside the replica
    # group that holds the same shard coordinate — per-step DCN bytes
    # drop to the replicated fraction of the tree.  ON by default, but a
    # plan only activates when explicit shard specs are passed AND some
    # leaf is actually sharded; every existing call site (no specs, or a
    # fully replicated tree) stays bitwise identical.  0 forces today's
    # replicated-only behavior even when specs are supplied.
    sharded_gossip: bool
    # Transient-send retry policy of the DCN transport (ops/transport.py):
    # how many times a failed native send is retried with jittered
    # exponential backoff (base win_retry_backoff_ms, doubling per
    # attempt) before raising ConnectionError.  Each attempt is counted in
    # bf_win_tx_retries_total.  0 disables retries (fail fast — what the
    # churn controller's failure detector wants).
    win_retries: int
    win_retry_backoff_ms: float
    # Message-level wire trace tags (ops/transport.py OP_TRACE_FLAG):
    # every Nth put/accumulate carries a compact (src, seq, origin-time)
    # trailer the drain side turns into per-edge contribution-age
    # telemetry and the trace-gossip tool turns into cross-rank flow
    # arrows.  0 (the default) = off: no flag, no trailer, no counter
    # mutation — the wire is bitwise identical to the pre-trace
    # transport.
    trace_sample: int
    # Barrier-free asynchronous window gossip (optim/window_optimizers.py
    # + ops/window.py): ranks issue win_accumulate puts at their own
    # cadence with NO per-step transport fence; each step folds only what
    # has arrived, push-sum associated-P weights correct for in-flight
    # mass, and contributions older than async_staleness_steps (origin
    # steps, from the wire trace tags; wall-clock fallback when a message
    # is unsampled) are rejected or downweighted per
    # async_staleness_policy with the diverted mass held in a per-edge
    # stale-residual store (folded back in at the periodic exact
    # collect, so push-sum mass conservation holds).  OFF by default:
    # with async_mode=0 nothing anywhere changes — the lockstep path is
    # bitwise identical to the pre-async tree.
    async_mode: bool
    async_staleness_steps: int
    async_staleness_policy: str
    # Every N async steps the optimizer fences the transport, folds the
    # stale residuals back into staging and performs an exact collect —
    # the drift backstop bounding both parameter drift and the step lag
    # a straggler can accumulate (the membership controller widens its
    # straggler threshold by exactly this much).  0 = no backstop (lag
    # is unbounded by design; step-lag eviction disables itself).
    async_collect_every: int
    # Native transport flight recorder (winsvc.cc bf_rec_*): a fixed-size
    # in-memory ring of enqueue/flush/sendmsg/drain/decode/fold/commit
    # events keyed (window, peer, stripe, seq), ~tens of ns per event,
    # dumped to <flight_recorder_path>.<rank>.bin on fatal transport
    # error, churn eviction/membership change, or an explicit
    # bf.flight_recorder_dump().  Off by default: the ring is never
    # allocated and every record site is a single pointer-null check.
    flight_recorder: bool
    flight_recorder_events: int
    flight_recorder_path: str
    # Link observatory (utils/linkobs.py): online per-edge delay/jitter/
    # goodput/divergence estimation off the trace-tag commit path and the
    # tx stats pump, plus the declarative SLO engine.  ON by default —
    # when the trace sampler is off it merely never receives a sample;
    # =0 is bitwise inert (no flag, no registry mutation anywhere).
    link_obs: bool
    # SLO rule spec ("<metric><op><value>;..."), validated at init by
    # linkobs.parse_slo_rules; None = no rules, the engine never runs.
    slo: Optional[str]
    # Self-tuning comm control plane (utils/tuner.py): the link
    # observatory's measured per-edge delay/goodput EWMAs re-price the
    # placement/synthesis cost model (ops/placement.MeasuredModel) and
    # drive bounded, hysteresis-guarded runtime adaptation of the
    # transport knobs (stripes, coalesce linger, outer cadence, sparse
    # fraction, staleness bound).  OFF by default — with tune=0 the tuner
    # is never constructed, no override is ever installed and every knob
    # and every modeled cost stays bitwise as configured.
    tune: bool
    # Divergence ratio (measured vs modeled, min-normalized — the same
    # statistic as bf_link_divergence_ratio) at which the tuner opens an
    # adaptation epoch.  Defaults to the observatory's x3 alert line.
    tune_divergence: float
    # Hysteresis: minimum steps the tuner dwells between epochs; also the
    # probation window after each epoch before the change is committed or
    # reverted on regression (bf_optimizer_step_seconds medians).
    tune_dwell_steps: int
    # Elastic-gossip churn controller (ops/membership.py +
    # run/supervisor.py); OFF by default — with churn=0 no membership
    # state exists, no heartbeat is ever sent and every code path is
    # bit-identical to the pre-churn tree.
    churn: bool
    churn_heartbeat_ms: float
    churn_suspect_ms: float
    # Step lag (in heartbeat-reported steps) beyond which a LIVE peer is
    # proposed for eviction as a persistent straggler.  0 (default)
    # disables straggler eviction — dead/unreachable peers only.
    churn_straggler_steps: int
    # Gossip-native join/bootstrap subsystem (ops/gang.py): wired joins
    # (`bfrun --join` processes admitted into a live gang over the window
    # transport, placement-aware rank assignment, one committed grow
    # epoch) and the gossip-replicated endpoint directory that replaces
    # the jax-coordinator KV store for bootstrap (`bfrun --elastic`).
    # OFF by default: with elastic_join=0 no directory exists, OP_GANG
    # frames are dropped on receipt, and every wire byte and committed
    # state is bit-identical to the pre-join tree.
    elastic_join: bool
    # Directory persistence prefix; each process writes
    # <prefix>.<proc>.json atomically on every directory change, so a
    # fresh process can bootstrap from disk with no live coordinator.
    gang_dir_path: Optional[str]
    # Per-endpoint grant wait for a joining process.
    join_timeout_ms: float
    # Fault-injection spec (utils/chaos.py grammar), normally set for a
    # gang by `bfrun --chaos`; unset = no injection.
    chaos: Optional[str]
    telemetry: bool
    telemetry_port: Optional[int]
    telemetry_consensus_every: int
    # Min-round repack of compiled ppermute schedules (ops/schedule_opt.py);
    # on by default — off is the escape hatch for debugging a schedule by
    # its raw shift-distance decomposition.
    schedule_opt: bool
    # Sketch-guided schedule synthesis (ops/synthesis.py); on by default
    # but structurally inert without an interconnect model.  0 restores
    # the PR 5 congestion-repack dispatch path exactly (the synthesized
    # candidate is never computed, never compared, never cached under a
    # live key).
    schedule_synth: bool
    # Which communication sketch the synthesis grows schedules from:
    # "auto" tries every sketch and keeps the best modeled
    # serial_link_time; a specific name pins it (debugging/benchmarks).
    schedule_synth_sketch: str
    # Physical-topology-aware rank placement (ops/placement.py); on by
    # default but structurally inert without an interconnect model (real
    # TPU coords or BLUEFOG_TPU_FAKE_TORUS).  0 restores raw device-
    # enumeration order exactly.
    placement: bool
    # Simulated-annealing refinement budget for the placement search.
    placement_iters: int
    # Congestion-aware round repack budget as a multiple of the König
    # round bound (ops/schedule_opt.congestion_aware_repack); 0 disables
    # the repack (placement permutation still applies).
    placement_round_budget: float
    # Synthetic torus spec ("RxC" / "XxYxZ") standing in for device
    # coords — makes the whole placement layer testable on the CPU mesh.
    fake_torus: Optional[str]
    # Wraparound policy for real-coords interconnect models: "auto"
    # (default — wrap 3-D dims that are multiples of 4 per the v4/v5p
    # slice rule, model 2-D sub-pod slices as meshes), "1" force torus,
    # "0" force mesh.  Modeling a wrap link that does not exist would let
    # the optimizer install a placement that is wrong on hardware.
    torus_wrap: str
    # Two-level hierarchical gossip (topology.HierarchicalTopology +
    # basics.hierarchical_gossip); OFF by default — with hier=0 no
    # hierarchical state exists anywhere and every flat path is
    # bit-identical to the pre-hier tree.
    hier: bool
    # Outer (inter-slice DCN) cadence: communicate between slices every k
    # steps; intermediate steps run the dense intra-slice level alone.
    hier_outer_every: int
    # Per-level topology kinds ("exp2" or "ring").
    hier_inner: str
    hier_outer: str
    # Outer-level wire codec (none / bf16 / sparse:<frac>); the inner ICI
    # level always stays dense.
    hier_outer_compression: str
    # Cadence-1 outer self weight theta; the builder cadence-corrects it
    # to theta**k (see topology.hierarchical_two_level).
    hier_outer_self_weight: float
    # Whether the consensus period was explicitly configured: samplers
    # that COST communication (the collective optimizer family) stay off
    # unless the operator asked; free samplers use the default period.
    telemetry_consensus_set: bool
    # Step profiler (utils/profiler.py): profile=1 turns on periodic
    # synced-step sampling + cross-rank straggler gathers at period
    # profile_every; an explicit profile_every= argument on the optimizer
    # overrides both.  bf.step_profile() works regardless of this flag.
    profile: bool
    profile_every: int

    @staticmethod
    def from_env() -> "Config":
        return Config(
            timeline_prefix=os.environ.get("BLUEFOG_TIMELINE"),
            log_level=os.environ.get("BLUEFOG_TPU_LOG_LEVEL", "warn").lower(),
            log_hide_time=_flag("BLUEFOG_TPU_LOG_HIDE_TIME"),
            no_native=_flag("BLUEFOG_TPU_NO_NATIVE"),
            python_timeline=_flag("BLUEFOG_TPU_PYTHON_TIMELINE"),
            stall_warning_sec=float(
                os.environ.get("BLUEFOG_TPU_STALL_WARNING_SEC", "60")),
            win_port=int(os.environ.get("BLUEFOG_TPU_WIN_PORT", "0")),
            win_max_pending=int(
                os.environ.get("BLUEFOG_TPU_WIN_MAX_PENDING", "4096")),
            win_compression=_validated_compression(os.environ.get(
                "BLUEFOG_TPU_WIN_COMPRESSION", "none").lower()),
            win_coalesce=_flag("BLUEFOG_TPU_WIN_COALESCE", default=True),
            win_coalesce_linger_ms=float(os.environ.get(
                "BLUEFOG_TPU_WIN_COALESCE_LINGER_MS", "1.0")),
            win_coalesce_bytes=int(os.environ.get(
                "BLUEFOG_TPU_WIN_COALESCE_BYTES", str(1 << 20))),
            win_tx_queue=int(os.environ.get(
                "BLUEFOG_TPU_WIN_TX_QUEUE", "1024")),
            win_stripes=_int_or_auto("BLUEFOG_TPU_WIN_STRIPES", floor=1),
            win_decode_threads=_int_or_auto(
                "BLUEFOG_TPU_WIN_DECODE_THREADS", floor=0),
            win_native=_flag("BLUEFOG_TPU_WIN_NATIVE", default=True),
            win_xla=_flag("BLUEFOG_TPU_WIN_XLA", default=True),
            sharded_gossip=_flag("BLUEFOG_TPU_SHARDED_GOSSIP",
                                 default=True),
            win_retries=int(os.environ.get(
                "BLUEFOG_TPU_WIN_RETRIES", "1")),
            win_retry_backoff_ms=float(os.environ.get(
                "BLUEFOG_TPU_WIN_RETRY_BACKOFF_MS", "50")),
            trace_sample=_parse_trace_sample(
                os.environ.get("BLUEFOG_TPU_TRACE_SAMPLE")),
            async_mode=_flag("BLUEFOG_TPU_ASYNC"),
            async_staleness_steps=int(os.environ.get(
                "BLUEFOG_TPU_ASYNC_STALENESS_STEPS", "0")),
            async_staleness_policy=_validated_staleness_policy(
                os.environ.get("BLUEFOG_TPU_ASYNC_STALENESS_POLICY",
                               "reject").lower()),
            async_collect_every=int(os.environ.get(
                "BLUEFOG_TPU_ASYNC_COLLECT_EVERY", "64")),
            flight_recorder=_flag("BLUEFOG_TPU_FLIGHT_RECORDER"),
            flight_recorder_events=int(os.environ.get(
                "BLUEFOG_TPU_FLIGHT_RECORDER_EVENTS", "65536")),
            flight_recorder_path=os.environ.get(
                "BLUEFOG_TPU_FLIGHT_RECORDER_PATH", "flightrec"),
            link_obs=_flag("BLUEFOG_TPU_LINK_OBS", default=True),
            slo=_validated_slo(os.environ.get("BLUEFOG_TPU_SLO")),
            tune=_flag("BLUEFOG_TPU_TUNE"),
            tune_divergence=float(os.environ.get(
                "BLUEFOG_TPU_TUNE_DIVERGENCE", "3.0")),
            tune_dwell_steps=int(os.environ.get(
                "BLUEFOG_TPU_TUNE_DWELL_STEPS", "20")),
            churn=_flag("BLUEFOG_TPU_CHURN"),
            churn_heartbeat_ms=float(os.environ.get(
                "BLUEFOG_TPU_CHURN_HEARTBEAT_MS", "250")),
            churn_suspect_ms=float(os.environ.get(
                "BLUEFOG_TPU_CHURN_SUSPECT_MS", "1500")),
            churn_straggler_steps=int(os.environ.get(
                "BLUEFOG_TPU_CHURN_STRAGGLER_STEPS", "0")),
            elastic_join=_flag("BLUEFOG_TPU_ELASTIC_JOIN"),
            gang_dir_path=os.environ.get("BLUEFOG_TPU_GANG_DIR_PATH"),
            join_timeout_ms=float(os.environ.get(
                "BLUEFOG_TPU_JOIN_TIMEOUT_MS", "30000")),
            chaos=os.environ.get("BLUEFOG_TPU_CHAOS"),
            telemetry=_flag("BLUEFOG_TPU_TELEMETRY", default=True),
            telemetry_port=(
                None if os.environ.get("BLUEFOG_TPU_TELEMETRY_PORT") is None
                else int(os.environ["BLUEFOG_TPU_TELEMETRY_PORT"])),
            telemetry_consensus_every=int(os.environ.get(
                "BLUEFOG_TPU_TELEMETRY_CONSENSUS_EVERY", "10")),
            telemetry_consensus_set=(
                "BLUEFOG_TPU_TELEMETRY_CONSENSUS_EVERY" in os.environ),
            schedule_opt=_flag("BLUEFOG_TPU_SCHEDULE_OPT", default=True),
            schedule_synth=_flag("BLUEFOG_TPU_SCHEDULE_SYNTH", default=True),
            schedule_synth_sketch=_validated_sketch(os.environ.get(
                "BLUEFOG_TPU_SCHEDULE_SYNTH_SKETCH", "auto").lower()),
            placement=_flag("BLUEFOG_TPU_PLACEMENT", default=True),
            placement_iters=int(
                os.environ.get("BLUEFOG_TPU_PLACEMENT_ITERS", "1000")),
            placement_round_budget=float(os.environ.get(
                "BLUEFOG_TPU_PLACEMENT_ROUND_BUDGET", "2.0")),
            fake_torus=os.environ.get("BLUEFOG_TPU_FAKE_TORUS"),
            torus_wrap=os.environ.get("BLUEFOG_TPU_TORUS_WRAP", "auto"),
            hier=_flag("BLUEFOG_TPU_HIER"),
            hier_outer_every=int(os.environ.get(
                "BLUEFOG_TPU_HIER_OUTER_EVERY", "1")),
            hier_inner=os.environ.get(
                "BLUEFOG_TPU_HIER_INNER", "exp2").lower(),
            hier_outer=os.environ.get(
                "BLUEFOG_TPU_HIER_OUTER", "exp2").lower(),
            hier_outer_compression=_validated_compression(
                os.environ.get("BLUEFOG_TPU_HIER_OUTER_COMPRESSION",
                               "none").lower(),
                var="BLUEFOG_TPU_HIER_OUTER_COMPRESSION"),
            hier_outer_self_weight=float(os.environ.get(
                "BLUEFOG_TPU_HIER_OUTER_SELF_WEIGHT", "0.5")),
            profile=_flag("BLUEFOG_TPU_PROFILE"),
            profile_every=int(
                os.environ.get("BLUEFOG_TPU_PROFILE_EVERY", "50")),
        )


_cfg: Optional[Config] = None


def get() -> Config:
    global _cfg
    if _cfg is None:
        _cfg = Config.from_env()
    return _cfg


def reload() -> Config:
    global _cfg
    _cfg = None
    return get()
