"""Timeline: named-activity tracing to chrome://tracing JSON + jax.profiler.

Replaces the reference's C++ Timeline (``common/timeline.{h,cc}``: dedicated
writer thread fed by a lock-free queue, one JSON file per rank, enabled by
``BLUEFOG_TIMELINE=<prefix>``).  Here user-level named activities are recorded
through the same env-var contract and additionally forwarded to
``jax.profiler.TraceAnnotation`` so they show up inside TPU profiler traces
alongside XLA ops — something the reference cannot do.

One span mechanism, two users: the *host-side* named-activity API
(``bf.timeline_start_activity/timeline_end_activity/timeline_context``,
reference ``basics.py:415-495``) and the framework's own spans
(:func:`op_span`: ``bf.optim.step``, ``bf.rank_map.launch``, ...; the table
is in ``docs/timeline.md``).  Both open a span through :func:`_begin`, which
writes it on the profiler's clock, so inside ``jax.profiler.trace()`` the
host spans line up with the device's program and op events, whose names
(:func:`device_scope`, the ``jit_bf_*`` programs) are stable too.
"""

from __future__ import annotations

import atexit
import json
import os
import queue
import threading
import time
from contextlib import contextmanager
from typing import Dict

import jax.monitoring
import jax.profiler
from jax._src.core import trace_state_clean as _trace_state_clean

from bluefog_tpu.utils import telemetry

__all__ = [
    "timeline_enabled",
    "timeline_start_activity",
    "timeline_end_activity",
    "timeline_context",
    "start_timeline",
    "stop_timeline",
    "flush",
    "counter_event",
    "counter_events_supported",
    "probe_span",
    "thread_name",
    "set_op_span_hook",
    "listening",
    "op_span",
    "timed_span",
    "startup_span",
    "watch_builds",
    "unwatch_builds",
    "device_scope",
    "CLOCK_ANCHOR_NAME",
]

_TRACE_EVENT_SENTINEL = None


class _TimelineWriter:
    """Background JSON writer: events go through a queue so the training
    thread never blocks on file IO (same design as timeline.h:46-76)."""

    def __init__(self, path: str):
        self.path = path
        self.q: "queue.Queue" = queue.Queue(maxsize=1 << 16)
        self._thread = threading.Thread(target=self._run, daemon=True,
                                        name="bf-timeline")
        self._thread.start()

    def _run(self):
        with open(self.path, "w") as f:
            f.write("[\n")
            first = True
            while True:
                ev = self.q.get()
                if ev is _TRACE_EVENT_SENTINEL:
                    break
                if not first:
                    f.write(",\n")
                f.write(json.dumps(ev))
                first = False
                f.flush()
            f.write("\n]\n")

    def emit(self, ev: dict):
        try:
            self.q.put_nowait(ev)
        except queue.Full:
            pass  # drop rather than stall training

    def close(self):
        self.q.put(_TRACE_EVENT_SENTINEL)
        self._thread.join(timeout=5)


class _NativeTimelineWriter:
    """Native-core writer (``native/src/timeline.cc``): SPSC ring + writer
    thread in C++, zero Python-side allocation per event."""

    def __init__(self, path: str):
        from bluefog_tpu import native
        self.path = path
        self._lib = native.lib()
        assert self._lib is not None
        self._h = self._lib.bf_timeline_open(path.encode(), os.getpid())
        if not self._h:
            raise OSError(f"cannot open timeline file {path!r}")

    def emit(self, ev: dict):
        self._lib.bf_timeline_event(
            self._h, ev["name"].encode(), ev["cat"].encode(),
            ev["ph"].encode(), ev["ts"], ev.get("dur", 0), ev["tid"])

    def close(self):
        if self._h:
            self._lib.bf_timeline_close(self._h)
            self._h = None


def _make_writer(path: str):
    from bluefog_tpu import native
    if native.available() and \
            os.environ.get("BLUEFOG_TPU_PYTHON_TIMELINE") != "1":
        return _NativeTimelineWriter(path)
    return _TimelineWriter(path)


_writer = None
_active: Dict[str, object] = {}
# The synthetic lane of the ``bf.build.<stage>`` spans.
_BUILD_LANE = 997
_lock = threading.Lock()


def _process_index() -> int:
    """This process's rank for timeline file naming (never 0-hardcoded:
    under ``bfrun`` fan-out every process would clobber the same file)."""
    env = os.environ.get("BFTPU_PROCESS_ID")
    if env is not None:
        return int(env)
    try:
        import jax
        return jax.process_index()
    except Exception:
        return 0


def _maybe_autostart():
    global _writer
    if _writer is None:
        prefix = os.environ.get("BLUEFOG_TIMELINE")
        if prefix:
            # One file per rank, <prefix><rank>.json — matches reference
            # operations.cc:450-459.
            start_timeline(f"{prefix}{_process_index()}.json")


def timeline_enabled() -> bool:
    _maybe_autostart()
    return _writer is not None


# Clock-anchor metadata event name: emitted once at timeline start, pairs
# this process's monotonic event clock with wall time so the trace-merge
# tool (``python -m bluefog_tpu.tools trace-merge``) can align per-rank
# traces onto one timeline.
CLOCK_ANCHOR_NAME = "bf_clock_anchor"

_atexit_installed = False


def _emit_clock_anchor() -> None:
    w = _writer
    if w is None:
        return
    mono_us = time.monotonic_ns() // 1000
    args = {"monotonic_us": mono_us, "unix_us": time.time_ns() // 1000,
            "rank": _process_index()}
    if hasattr(w, "q"):
        w.emit({"name": CLOCK_ANCHOR_NAME, "ph": "M", "ts": mono_us,
                "pid": os.getpid(), "tid": 0, "args": args})
        return
    # Native writer: its wire format carries no args payload, so the
    # anchor rides a SIDECAR file trace-merge also reads — wall alignment
    # must not silently degrade on the default (native) writer.
    try:
        with open(w.path + ".anchor.json", "w") as f:
            json.dump(args, f)
    except OSError:
        pass  # tracing must never take the job down; merge will warn


def start_timeline(path: str) -> bool:
    """Begin writing a chrome-tracing file (parity: ``bf.timeline_start``)."""
    global _writer, _atexit_installed
    with _lock:
        if _writer is not None:
            return False
        _writer = _make_writer(path)
        if not _atexit_installed:
            # A process that never calls stop_timeline() must still close
            # the JSON array on normal interpreter exit — a truncated file
            # fails strict parsers (the trace-merge tool repairs them, but
            # nothing else does).
            atexit.register(stop_timeline)
            _atexit_installed = True
    _emit_clock_anchor()
    thread_name(_BUILD_LANE, "bf.build")
    return True


def stop_timeline() -> bool:
    global _writer
    with _lock:
        if _writer is None:
            return False
        _writer.close()
        _writer = None
    return True


def flush() -> None:
    """Best-effort drain of queued events to disk (used by ``bf.suspend`` so
    a paused notebook can open the trace).  The Python writer flushes per
    event once the queue drains; the native writer flushes on its own tick —
    here we just give both a moment to catch up without tearing down."""
    w = _writer
    if w is None:
        return
    q = getattr(w, "q", None)
    if q is not None:
        deadline = time.monotonic() + 2.0
        while not q.empty() and time.monotonic() < deadline:
            time.sleep(0.01)


def _begin(annotation: str, name: str, cat: str, args: dict):
    """Open one host span wherever someone listens and return what
    :func:`_end` closes: on the profiler's clock (a ``TraceAnnotation``
    costs well under a microsecond while no ``jax.profiler`` trace runs),
    and in the chrome-JSON file if a timeline is open.  ``args`` ride the
    annotation (``step=3`` joins a launch to its device execution) and
    the Python writer's event."""
    ann = jax.profiler.TraceAnnotation(annotation, **args)
    ann.__enter__()
    if _writer is not None:
        _emit_edge("B", name, cat, args)
    return ann


def _end(ann, name: str, cat: str, late: dict = None) -> None:
    """Close a span; ``late`` are the arguments known only now (whether a
    launch was held): the annotation takes them as it takes the others, the
    file's closing edge carries them (a viewer merges both edges' args)."""
    if late:
        ann.set_metadata(**late)
    if _writer is not None:
        _emit_edge("E", name, cat, late)
    ann.__exit__(None, None, None)


def _emit_edge(ph: str, name: str, cat: str, args) -> None:
    w = _writer
    if w is None:   # closed by another thread since the caller looked
        return
    ev = {"name": name, "cat": cat, "ph": ph,
          "ts": time.monotonic_ns() // 1000, "pid": os.getpid(),
          "tid": threading.get_ident()}
    if args:
        ev["args"] = args
    w.emit(ev)


def device_scope(name: str):
    """Name the device operations traced inside the ``with``: the name
    lands in their ``op_name`` metadata (``jit(bf_optim_step)/.../
    bf.optim.update/mul``) and nowhere else, so the compiled program is
    the same with and without it.  A trace reader books a device event to
    the scope its instruction's metadata names."""
    return jax.named_scope(name)


def timeline_start_activity(tensor_name: str, activity_name: str = "USER") -> bool:
    """Open a named activity span (parity: ``basics.py:415-451``)."""
    _maybe_autostart()
    if _writer is None:
        return False
    key = f"{tensor_name}:{activity_name}"
    ann = _begin(key, activity_name, tensor_name, {})
    with _lock:
        prior = _active.pop(key, None)
        _active[key] = ann
    if prior is not None:
        # A same-key span was still open (retry loop / double start): close it
        # so the profiler's thread-local annotation stack stays balanced.
        prior.__exit__(None, None, None)
    return True


def timeline_end_activity(tensor_name: str, activity_name: str = "USER") -> bool:
    if _writer is None:
        return False
    key = f"{tensor_name}:{activity_name}"
    with _lock:
        ann = _active.pop(key, None)
    if ann is not None:
        _end(ann, activity_name, tensor_name)
    else:
        _emit_edge("E", activity_name, tensor_name, None)
    return True


@contextmanager
def timeline_context(tensor_name: str, activity_name: str = "USER"):
    """``with bf.timeline_context("grad_sync"):`` span recorder."""
    timeline_start_activity(tensor_name, activity_name)
    try:
        yield
    finally:
        timeline_end_activity(tensor_name, activity_name)


def probe_span(name: str, ts_us: int, dur_us: int, tid: int,
               cat: str) -> None:
    """Emit one complete ("X") span on a synthetic lane — the
    ``bf.build.<stage>`` spans are these.  ``ts_us`` is on the same
    monotonic microsecond clock as every other event here, so
    trace-merge's clock anchors align the lanes cross-rank for free.
    Works on both writers (the native wire format carries ``dur``)."""
    w = _writer
    if w is None:
        return
    w.emit({"name": name, "cat": cat, "ph": "X", "ts": int(ts_us),
            "dur": max(0, int(dur_us)), "pid": os.getpid(), "tid": int(tid)})


def thread_name(tid: int, name: str) -> None:
    """Label a synthetic lane with a chrome-tracing thread_name metadata
    event (Python writer only — the native format has no args payload)."""
    w = _writer
    if w is None or not hasattr(w, "q"):
        return
    w.emit({"name": "thread_name", "ph": "M", "ts": 0, "pid": os.getpid(),
            "tid": int(tid), "args": {"name": name}})


def counter_events_supported() -> bool:
    """True when a timeline writer that can carry counter events is live.
    The native SPSC writer's wire format has no ``args`` payload, so
    counter events ride the Python writer only — no autostart probe here
    (telemetry polls this on every snapshot; it must stay one check)."""
    return _writer is not None and hasattr(_writer, "q")


def counter_event(name: str, value: float, cat: str = "telemetry") -> None:
    """Emit one chrome-tracing COUNTER event (``"ph": "C"``): the series
    renders as a stacked counter track alongside the op spans.  Telemetry
    (``utils/telemetry.py``) emits every registry series through this on
    snapshot/scrape."""
    w = _writer
    if w is None or not hasattr(w, "q"):
        return
    w.emit({"name": name, "cat": cat, "ph": "C",
            "ts": time.monotonic_ns() // 1000, "pid": os.getpid(),
            "tid": 0, "args": {"value": float(value)}})


# Installed by utils.profiler while a StepProfiler is active: called as
# ``hook(op_name, phase, seconds)`` for every completed TOP-LEVEL op span
# so the profiler can attribute step time to phases even with no timeline
# file.  Only outermost spans report (per-thread depth gate below): the
# window family nests per-edge COMMUNICATE spans inside the op-level span,
# and reporting both would double-count the same wall time.
_span_hook = None
_span_depth = threading.local()


def set_op_span_hook(hook) -> None:
    """Register (or clear, with ``None``) the op-span duration observer."""
    global _span_hook
    _span_hook = hook


def listening() -> bool:
    """Is anyone there to take a span: a running ``jax.profiler`` trace, an
    open timeline file or the ``StepProfiler`` hook.  A span costs a few
    microseconds either way; what costs more to record (the allocator's
    state at a launch: ``basics.rank_map``) is sampled only then."""
    return (_writer is not None or _span_hook is not None
            or jax.profiler.TraceAnnotation.is_enabled())


class op_span:
    """Framework-internal span ``bf.<op_name>.<phase>``: the eager ops'
    ENQUEUE/COMMUNICATE/UPDATE phases (the automatic analogue of the
    reference's per-phase ActivityStart/End hooks,
    ``mpi_controller.cc:540-561``) and the training step's own host phases
    (``bf.optim.step`` > ``bf.optim.place`` / ``bf.optim.launch``,
    ``bf.rank_map.launch``, ``bf.data.wait``, ...).  One ``with`` feeds all
    three listeners: a running ``jax.profiler`` trace, the chrome-JSON
    timeline, and the ``StepProfiler`` hook.  ``args`` (``step=``,
    ``batch=``) are the identifiers that join a span to its counterpart on
    another thread or on the device; :meth:`set` adds those that only the
    span's end knows.  A class and not a generator: with nobody listening a
    span costs one idle annotation and three module-global checks (no
    autostart probe, no registry mutation)."""

    __slots__ = ("_op", "_phase", "_args", "_late", "_ann", "_t0")

    def __init__(self, op_name: str, phase: str, **args):
        self._op, self._phase, self._args = op_name, phase, args
        self._late = None

    def set(self, **args) -> None:
        """Arguments of the open span that were not known when it began
        (``held=1``); they are written when it closes."""
        self._late = args if self._late is None else {**self._late, **args}

    def __enter__(self):
        if _writer is None and os.environ.get("BLUEFOG_TIMELINE"):
            _maybe_autostart()
        self._t0 = None
        if _span_hook is not None:
            _span_depth.d = getattr(_span_depth, "d", 0) + 1
            self._t0 = time.perf_counter()
        self._ann = _begin(f"bf.{self._op}.{self._phase}", self._phase,
                           self._op, self._args)
        return self

    def __exit__(self, *exc):
        _end(self._ann, self._phase, self._op, self._late)
        if self._t0 is not None:
            _span_depth.d -= 1
            if _span_depth.d == 0 and _span_hook is not None:
                _span_hook(self._op, self._phase,
                           time.perf_counter() - self._t0)
        return False


# The spans in which the host can stand still, and the histogram each also
# feeds (``tools/metrics_lint`` reads the table).
_STANDSTILL_METRICS = {
    "bf.rank_map.launch": "bf_rank_map_launch_seconds",
    "bf.rank_map.wait": "bf_rank_map_wait_seconds",
    "bf.optim.wait": "bf_optim_wait_seconds",
}


class timed_span(op_span):
    """An :class:`op_span` of ``_STANDSTILL_METRICS`` whose seconds also land
    in its histogram of the registry: where the host stood still is read off
    an untraced run too.  One ``with`` feeds both; with telemetry off the
    histogram is not touched."""

    __slots__ = ("_series", "_s0")

    def __init__(self, op_name: str, phase: str, **args):
        super().__init__(op_name, phase, **args)
        self._series = _STANDSTILL_METRICS[f"bf.{op_name}.{phase}"]

    def __enter__(self):
        self._s0 = telemetry.start_timer()
        return super().__enter__()

    def __exit__(self, *exc):
        super().__exit__(*exc)
        telemetry.observe_since(self._s0, self._series)
        return False


@contextmanager
def startup_span(op_name: str, phase: str, part: str):
    """A span of the way to the first step (``bf.init.devices``,
    ``bf.init.topology``, ``bf.optim.init``) whose seconds also land in
    the gauge ``bf_startup_seconds{part}``: a start-up is over before
    anyone opens a trace, so the figure lives in the registry as well."""
    t0 = time.perf_counter()
    with op_span(op_name, phase):
        yield
    telemetry.set_gauge("bf_startup_seconds", time.perf_counter() - t0,
                        part=part)


# ---------------------------------------------------------------------------
# What stands between process start and the first step: jax's own events
# ---------------------------------------------------------------------------

_BUILD_STAGES = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "compile",
}
_CACHE_RESULTS = {
    "/jax/compilation_cache/cache_hits": "hit",
    "/jax/compilation_cache/cache_misses": "miss",
}
_watching = False


def _on_build_stage(event: str, start: float, end: float, *,
                    fun_name: str = "", **_) -> None:
    """jax reports every trace, lowering and backend compile as a time
    span with the function's name (``jax/_src/dispatch.py``).  Each goes
    into ``bf_program_build_seconds{program, stage}`` and, with a timeline
    open, onto the lane ``bf.build`` as ``bf.build.<stage>`` with ``cat``
    the program.  The library's own programs (``bf_*``) keep their names
    and every other function is ``other``.  A trace that runs inside
    another trace (a jitted helper, every ``jnp`` function) is part of that
    one's seconds: a ``bf_*`` one is booked as ``trace_nested``, another is
    not booked at all."""
    stage = _BUILD_STAGES.get(event)
    if stage is None:
        return
    program = fun_name[4:-1] if fun_name.startswith("jit(") else fun_name
    if not program.startswith("bf_"):
        program = "other"
    if stage == "trace" and not _trace_state_clean():
        if program == "other":
            return
        stage = "trace_nested"
    telemetry.observe("bf_program_build_seconds", end - start,
                      program=program, stage=stage)
    if _writer is None and os.environ.get("BLUEFOG_TIMELINE"):
        _maybe_autostart()
    if _writer is not None:
        # jax stamps wall time; the timeline runs on the monotonic clock
        offset = time.monotonic() - time.time()
        probe_span(f"bf.build.{stage}", int((start + offset) * 1e6),
                   int((end - start) * 1e6), _BUILD_LANE, cat=program)


def _on_cache_event(event: str, **_) -> None:
    result = _CACHE_RESULTS.get(event)
    if result is not None:
        telemetry.inc("bf_compile_cache_total", result=result)


def watch_builds() -> None:
    """Listen to jax's compile-stage and compile-cache events
    (``bf.init()`` calls this; a second call changes nothing)."""
    global _watching
    with _lock:
        if _watching:
            return
        _watching = True
    jax.monitoring.register_event_time_span_listener(_on_build_stage)
    jax.monitoring.register_event_listener(_on_cache_event)


def unwatch_builds() -> None:
    """Stop listening (``bf.shutdown()``)."""
    global _watching
    with _lock:
        if not _watching:
            return
        _watching = False
    jax.monitoring.unregister_event_time_span_listener(_on_build_stage)
    jax.monitoring.unregister_event_listener(_on_cache_event)
