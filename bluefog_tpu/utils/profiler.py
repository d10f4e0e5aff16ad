"""Distributed step profiler: phase attribution + cross-rank stragglers.

The telemetry registry (``utils/telemetry.py``) says how MUCH the gossip
paths communicate; this module says where each training step's WALL TIME
goes — per phase, per rank, as latency distributions rather than means.
Asynchronous gossip systems live or die by tail behavior (SGP / AD-PSGD
motivate decentralization precisely by straggler-resilience), so the
scaling-efficiency claim needs p50/p99-level evidence:

  * ``bf.step_profile()`` wraps one training step and attributes its wall
    time into named phases — ``grad-compute`` / ``gossip-communicate`` /
    ``optimizer-update`` / ``host-sync`` — via the existing
    ``timeline.op_span`` machinery: while a profiler is active every
    framework op span (ENQUEUE/COMMUNICATE/UPDATE) reports its duration
    here, explicit sub-phases are marked with ``prof.phase(name)``, and
    whatever remains unattributed is the step's own compute.  Phases land
    in the ``bf_step_phase_seconds`` histogram (plus ``bf_step_seconds``
    for the whole step).
  * Every N profiled steps (``BLUEFOG_TPU_PROFILE_EVERY``, or the
    ``profile_every=`` argument on ``DistributedOptimizer``) the profiler
    rides the collective path — the same ``bf.allgather`` pattern as the
    consensus-distance gauge and ``aggregate_snapshot`` — to gather every
    rank's step duration and emit a STRAGGLER REPORT: per-rank z-scores,
    the slowest rank's identity, and a ``bf_straggler_score`` gauge,
    surfaced in ``/healthz`` and ``%bfstat``.

The straggler gather is COLLECTIVE in multi-process runs: every process
must profile the same steps (the SPMD training loop does this naturally —
same loop, same step indices).  Everything here is inert when
``BLUEFOG_TPU_TELEMETRY=0``: no registry mutation, no span hook, no
communication.

Merged-trace tooling (``python -m bluefog_tpu.tools trace-merge``) is the
offline half of this subsystem — see ``bluefog_tpu/tools``.
"""

from __future__ import annotations

import threading
import time
from contextlib import contextmanager
from typing import Dict, Optional

import numpy as np

from bluefog_tpu.utils import config, telemetry

__all__ = [
    "PHASES",
    "StepProfiler",
    "step_profile",
    "active",
    "profile_period",
    "record_synced_step",
    "straggler_report",
    "last_straggler_report",
]

# The canonical phase set.  Every op span maps into one of the last three;
# the unattributed remainder of a profiled step is grad-compute (the
# step's own forward/backward math — the only part the framework cannot
# see from inside its comm entry points).
PHASES = ("grad-compute", "gossip-communicate", "optimizer-update",
          "host-sync")


def _classify_span(op_name: str, span_phase: str) -> str:
    """Map a ``timeline.op_span`` (op, phase) pair to a profiler phase.

    UPDATE spans and ``opt.step()``'s own host phases (``bf.optim.*``)
    are the optimizer; the ``synchronize`` COMMUNICATE span, a throttle
    wait and a wait for the next batch are host-side blocks (host-sync);
    launching the gradient program (``bf.rank_map.*``) is the step's own
    compute; every other ENQUEUE/COMMUNICATE span is communication work
    (dispatching a collective, a window edge transfer, a transport
    apply)."""
    if span_phase == "UPDATE" or op_name == "optim":
        return "optimizer-update"
    if op_name in ("synchronize", "throttle", "data"):
        return "host-sync"
    if op_name == "rank_map":
        return "grad-compute"
    return "gossip-communicate"


# ---------------------------------------------------------------------------
# Module state (the active profiler + last straggler report)
# ---------------------------------------------------------------------------

_active: Optional["StepProfiler"] = None
_state_lock = threading.Lock()
_step_count = 0          # profiled steps seen (straggler-gather period base)
_last_report: Optional[dict] = None


def active() -> Optional["StepProfiler"]:
    """The StepProfiler currently wrapping a step, or None."""
    return _active


def last_straggler_report() -> Optional[dict]:
    """The most recent cross-rank straggler report (``/healthz`` and
    ``%bfstat`` read this), or None before the first gather."""
    rep = _last_report
    return None if rep is None else dict(rep)


def _reset_for_tests() -> None:
    global _active, _step_count, _last_report
    _active = None
    _step_count = 0
    _last_report = None
    _uninstall_hook()


def profile_period(explicit: Optional[int] = None) -> int:
    """Straggler-gather / profile-sampling period in steps (0 = off).

    An explicit argument (``DistributedOptimizer(profile_every=N)``) wins;
    otherwise ``BLUEFOG_TPU_PROFILE=1`` enables the env-configured
    ``BLUEFOG_TPU_PROFILE_EVERY``.  Always 0 when telemetry is disabled —
    profiling must never mutate a disabled registry or add collectives."""
    cfg = config.get()
    if not cfg.telemetry:
        return 0
    if explicit is not None:
        return max(int(explicit), 0)
    return cfg.profile_every if cfg.profile else 0


# ---------------------------------------------------------------------------
# op_span hook plumbing (installed only while a profiler is active)
# ---------------------------------------------------------------------------

def _on_op_span(op_name: str, span_phase: str, seconds: float) -> None:
    p = _active
    if p is None:
        return
    if op_name.startswith("win_apply.") or \
            (op_name, span_phase) == ("data", "place"):
        # Drain-thread spans are PEER-driven (inbound gossip landing while
        # we happen to be profiling) — not this step's own work; billing
        # them to the active step would misattribute a neighbor's traffic.
        # The prefetch thread's placement runs beside the step, not in it.
        return
    p.attribute(_classify_span(op_name, span_phase), seconds)


def _install_hook() -> None:
    from bluefog_tpu.utils import timeline
    timeline.set_op_span_hook(_on_op_span)


def _uninstall_hook() -> None:
    from bluefog_tpu.utils import timeline
    timeline.set_op_span_hook(None)


# ---------------------------------------------------------------------------
# StepProfiler
# ---------------------------------------------------------------------------

class StepProfiler:
    """Context wrapping ONE training step; see :func:`step_profile`.

    ``straggler``: None (default) gathers cross-rank step times every
    :func:`profile_period` profiled steps; True forces a gather on this
    step; False never gathers.  ``clock`` is injectable for tests.

    Attribution scope: only TOP-LEVEL op spans report (nested per-edge
    window spans are folded into their op-level parent), and peer-driven
    drain-thread work (``win_apply``) is excluded.  Spans from the window
    worker pool DO attribute — they are this step's own puts/gets — so in
    overlap modes a previous step's still-draining put can bill the
    current step; that spillover is the async design's real behavior, and
    the ``grad-compute`` remainder is floored at 0 when concurrent comm
    threads make attributed time exceed the step's wall time."""

    def __init__(self, *, straggler: Optional[bool] = None,
                 clock=time.perf_counter):
        self._clock = clock
        self._straggler = straggler
        self._phases: Dict[str, float] = {}
        self._lock = threading.Lock()  # window workers attribute concurrently
        self._t0: Optional[float] = None
        self._enabled = False
        self._prev: Optional[StepProfiler] = None

    def attribute(self, phase: str, seconds: float) -> None:
        """Add ``seconds`` of this step's wall time to ``phase``."""
        with self._lock:
            self._phases[phase] = self._phases.get(phase, 0.0) + seconds

    @contextmanager
    def phase(self, name: str):
        """Explicitly mark a sub-phase (``with prof.phase("grad-compute")``)
        — time inside is attributed to ``name`` instead of the remainder."""
        t0 = self._clock()
        try:
            yield
        finally:
            self.attribute(name, self._clock() - t0)

    def phases(self) -> Dict[str, float]:
        with self._lock:
            return dict(self._phases)

    def request_straggler(self) -> None:
        """Ask for the cross-rank gather at this step's exit (the
        optimizer families call this when their own ``profile_every``
        sample lands inside an enclosing ``bf.step_profile()`` — ONE
        gather, owned by the outer context, instead of two).  An explicit
        ``straggler=False`` on the context wins: the caller opted out of
        collectives (e.g. a non-lockstep async-family loop where an
        unmatched allgather would hang), and a sampler must not override
        that."""
        if self._straggler is None:
            self._straggler = True

    def __enter__(self) -> "StepProfiler":
        global _active
        self._enabled = telemetry.enabled()
        if not self._enabled:
            return self
        with _state_lock:
            self._prev = _active
            _active = self
            _install_hook()
        self._t0 = self._clock()
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        global _active, _step_count
        if not self._enabled:
            return False
        total = self._clock() - self._t0
        with _state_lock:
            _active = self._prev
            if _active is None:
                _uninstall_hook()
        attributed = sum(self.phases().values())
        if total > attributed:
            # The step's own compute: everything no framework span claimed.
            self.attribute("grad-compute", total - attributed)
        for ph, dt in sorted(self.phases().items()):
            telemetry.observe("bf_step_phase_seconds", dt, phase=ph)
        telemetry.observe("bf_step_seconds", total)
        if exc_type is None:
            with _state_lock:
                _step_count += 1
                count = _step_count
            want = self._straggler
            if want is None:
                p = profile_period()
                want = bool(p) and count % p == 0
            if want:
                times = _gather_step_seconds(total)
                if times is not None:
                    _record_straggler(times)
        return False


def step_profile(*, straggler: Optional[bool] = None,
                 clock=time.perf_counter) -> StepProfiler:
    """``with bf.step_profile(): ...`` — profile one training step.

    While active, every framework op span feeds the phase accumulators
    (see module docstring); on exit the per-phase durations land in the
    ``bf_step_phase_seconds`` histogram and — on straggler steps — all
    ranks' step durations are gathered into a straggler report.  Inert
    when telemetry is disabled."""
    return StepProfiler(straggler=straggler, clock=clock)


# ---------------------------------------------------------------------------
# Straggler attribution (rides the collective path)
# ---------------------------------------------------------------------------

def straggler_report(step_seconds) -> dict:
    """Pure straggler math over per-rank step durations: z-scores, the
    slowest rank, and the straggler score (max z-score — how many standard
    deviations the worst rank sits above the fleet).  A uniform fleet
    scores 0.

    The max z-score is capped at ``sqrt(n-1)`` by construction (one slow
    rank among n), so on small gangs it identifies the straggler but not
    its SEVERITY — ``slowest_over_mean`` (slowest rank's time over the
    fleet mean, also the ``bf_straggler_ratio`` gauge) carries the
    magnitude: 1.0 = uniform, 2.0 = the slowest rank takes twice the mean
    step time."""
    t = np.asarray(step_seconds, dtype=np.float64).reshape(-1)
    mean = float(t.mean())
    std = float(t.std())
    z = (t - mean) / std if std > 0 else np.zeros_like(t)
    slowest = int(np.argmax(t))
    return {
        "step_seconds": [round(float(v), 6) for v in t],
        "mean_sec": round(mean, 6),
        "std_sec": round(std, 6),
        "z_scores": [round(float(v), 3) for v in z],
        "slowest_rank": slowest,
        "straggler_score": round(float(z.max()) if t.size > 1 else 0.0, 3),
        "slowest_over_mean": round(float(t[slowest]) / mean
                                   if mean > 0 else 1.0, 3),
    }


def _gather_step_seconds(my_seconds: float) -> Optional[np.ndarray]:
    """Gather every rank's step duration over the collective path (one
    (n, 1) float32 allgather — the consensus-gauge pattern).  COLLECTIVE
    in multi-process runs; None when the context is not initialized."""
    from bluefog_tpu import basics
    if not basics.initialized():
        return None
    n = basics.size()
    rows = np.zeros((n, 1), np.float32)
    for r in basics.owned_ranks():
        rows[r, 0] = my_seconds
    gathered = np.asarray(basics.to_numpy(basics.allgather(rows)))
    return gathered[0].reshape(n)


def _record_straggler(times: np.ndarray) -> None:
    global _last_report
    rep = straggler_report(times)
    telemetry.set_gauge("bf_straggler_score", rep["straggler_score"])
    telemetry.set_gauge("bf_straggler_ratio", rep["slowest_over_mean"])
    telemetry.set_gauge("bf_straggler_rank", rep["slowest_rank"])
    telemetry.inc("bf_straggler_reports_total")
    _last_report = rep


def record_synced_step(total_seconds: float,
                       *, straggler: bool = True) -> None:
    """Record one fully-synced step measured by a caller (the optimizer
    families' ``profile_every`` hook): the step histogram and — by
    default — a straggler gather.  No phases: the step is one compiled
    program, and what the host can time around it is dispatch, which the
    ``bf.optim.*`` spans carry.  The caller must have block_until_ready'd
    the step so ``total_seconds`` is true wall time, and in multi-process
    runs must call this on every process together (collective gather)."""
    if not telemetry.enabled():
        return
    telemetry.observe("bf_step_seconds", total_seconds)
    if straggler:
        times = _gather_step_seconds(total_seconds)
        if times is not None:
            _record_straggler(times)


# ---------------------------------------------------------------------------
# Smoke entry point (`make prof-smoke`)
# ---------------------------------------------------------------------------

def _smoke() -> int:
    """Tiny CPU-backed profiled loop: assert the phase histogram appears in
    a /metrics scrape, the straggler gauge in /healthz, and that
    trace-merge produces valid JSON with one process lane per rank.

    All stateful calls go through the canonically-imported modules (under
    ``python -m`` THIS file is the separate ``__main__`` module)."""
    import json
    import os
    import tempfile
    import urllib.request
    os.environ.setdefault("BLUEFOG_TPU_TELEMETRY", "1")
    os.environ["BLUEFOG_TPU_PYTHON_TIMELINE"] = "1"
    tmpdir = tempfile.mkdtemp(prefix="bf-prof-smoke-")
    prefix = os.path.join(tmpdir, "tl_")
    import jax
    jax.config.update("jax_platforms", "cpu")
    import optax

    import bluefog_tpu as bf
    from bluefog_tpu import tools
    from bluefog_tpu.utils import config as _config
    from bluefog_tpu.utils import telemetry as T
    from bluefog_tpu.utils import timeline
    _config.reload()
    bf.init()
    n = bf.size()
    timeline.start_timeline(f"{prefix}0.json")
    params = {"w": np.ones((n, 8), np.float32)}
    grads = {"w": np.full((n, 8), 0.01, np.float32)}
    opt = bf.optim.DistributedNeighborAllreduceOptimizer(
        optax.sgd(0.01), profile_every=2)
    state = opt.init(params)
    for _ in range(4):
        with bf.step_profile():
            params, state = opt.step(params, grads, state)
    timeline.stop_timeline()
    port = T.start_http_server(0)
    with urllib.request.urlopen(
            f"http://127.0.0.1:{port}/metrics", timeout=10) as r:
        text = r.read().decode()
    with urllib.request.urlopen(
            f"http://127.0.0.1:{port}/healthz", timeout=10) as r:
        hz = json.loads(r.read().decode())
    T.stop_http_server()
    assert "bf_step_phase_seconds_bucket" in text, \
        "missing step-phase histogram in /metrics"
    assert 'phase="grad-compute"' in text and 'phase="host-sync"' in text, \
        "missing phase labels"
    assert "bf_step_seconds_count" in text, "missing step histogram"
    assert "bf_optimizer_step_seconds_bucket" in text, \
        "missing optimizer step histogram"
    assert "straggler" in hz, f"no straggler report in /healthz: {hz}"
    assert "straggler_score" in hz["straggler"]
    merged = tools.trace_merge(prefix)
    events = json.load(open(merged))  # must be VALID json
    lanes = {e["pid"] for e in events if e.get("ph") != "M"}
    assert lanes == {0}, f"expected one process lane per rank, got {lanes}"
    summary = tools.trace_summary(merged)
    print("profiler smoke OK:", len(text.splitlines()), "metric lines;",
          "straggler score", hz["straggler"]["straggler_score"],
          "| merged trace", merged, f"({len(events)} events)")
    print(summary)
    return 0


if __name__ == "__main__":
    import sys
    sys.exit(_smoke())
