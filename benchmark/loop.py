"""The measured loops: warm-up, the timed window, the traced window.

Closed loop, one process, one thread: the next step is issued when the
previous call returns.  Nothing waits for the device inside a group of steps,
so dispatch runs ahead as in a user's job.  A group ends when the parameter
tree its last step produced is ready, and the host waits for that only after
it has issued the next group: the device never idles for the measurement's
sake.  (Waiting before issuing cost ResNet-50 15 to 20 ms of idle device per
group, and the host's jitter in that gap moved the median between two modes
0.6% apart: my chip runs, PR 22.)
"""

from __future__ import annotations

import os
import shutil
import time
from dataclasses import dataclass, field

import jax
import numpy as np

GROUP = 5          # steps between two host syncs


@dataclass
class Timed:
    """One timed phase."""
    name: str
    start: float
    end: float
    groups: list = field(default_factory=list)      # seconds per group
    losses: np.ndarray | None = None                # (steps, n), float64
    attempted: int = 0
    error: str | None = None

    @property
    def seconds(self) -> float:
        return self.end - self.start

    def quartiles(self) -> tuple:
        q1, q2, q3 = np.percentile(self.groups, [25, 50, 75])
        return float(q1), float(q2), float(q3)


def warm_up(job, steps: int) -> None:
    """Compile (or load) both programs and run them ``steps`` times."""
    for _ in range(steps):
        job.step()
    jax.block_until_ready((job.params, job.state, job.aux))


def group_end(job):
    """What to wait on for the end of the group just issued: the smallest
    leaf of the new parameter tree.  Every leaf is an output of the same
    optimizer program, so one is ready when all are, and holding one small
    leaf keeps no second tree alive while the next group runs."""
    return min(jax.tree.leaves(job.params), key=lambda x: x.size)


def timed(job, name: str, seconds: float) -> Timed:
    """Groups of ``GROUP`` steps until ``seconds`` are over; the groups that
    end inside the window count.  A step that raises ends the phase."""
    losses = []
    out = Timed(name=name, start=time.perf_counter(), end=0.0)
    deadline, last, pending = out.start + seconds, out.start, None

    def finish(mark):
        """Wait for a group's end; count it if it ended inside the window."""
        nonlocal last
        jax.block_until_ready(mark)
        now = time.perf_counter()
        if now <= deadline:
            out.groups.append(now - last)
        last = now

    try:
        while time.perf_counter() < deadline:
            for _ in range(GROUP):
                out.attempted += 1
                losses.append(job.step())
            if pending is not None:
                finish(pending)
            pending = group_end(job)
        finish(pending)
        jax.block_until_ready(job.params)
    except Exception as e:  # noqa: BLE001 - recorded; the run reports failed
        import traceback
        traceback.print_exc()
        out.error = f"{type(e).__name__}: {e}"
    out.end = time.perf_counter()
    out.losses = np.asarray([np.asarray(l, np.float64) for l in losses]
                            ).reshape(len(losses), -1)
    return out


def traced(job, trace_dir: str, *, blocked_steps: int, free_groups: int
           ) -> str:
    """Record one profiler trace of two short steady stretches and return
    the path of its ``.xplane.pb``.

    ``bench.blocked``: every step waits for the device after each of its two
    calls, inside spans of the benchmark's own, so that device events can be
    assigned to the gradient or the optimizer program by the span that
    contains them (both programs are called ``jit_run`` in the trace).
    ``bench.free``: groups as in the timed window, each group's end waited
    for after the next group is issued; the idle share, the gaps and the
    kernel sums are read there.
    """
    from jax.profiler import TraceAnnotation as Span
    shutil.rmtree(trace_dir, ignore_errors=True)
    os.makedirs(trace_dir)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0      # host spans, not every Python call
    jax.profiler.start_trace(trace_dir, profiler_options=options)
    try:
        with Span("bench.blocked"):
            for _ in range(blocked_steps):
                with Span("bench.next_batch"):
                    batch = job.next_batch()
                with Span("bench.grad"):
                    _, grads = jax.block_until_ready(job.grad(batch))
                with Span("bench.optim_dispatch"):
                    job.apply(grads)
                with Span("bench.optim_wait"):
                    jax.block_until_ready((job.params, job.state))
        with Span("bench.free"):
            pending = None
            for _ in range(free_groups):
                for _ in range(GROUP):
                    with Span("bench.next_batch"):
                        batch = job.next_batch()
                    with Span("bench.grad"):
                        _, grads = job.grad(batch)
                    with Span("bench.optim_dispatch"):
                        job.apply(grads)
                with Span("bench.group_sync"):
                    jax.block_until_ready(pending)
                pending = group_end(job)
            with Span("bench.group_sync"):
                jax.block_until_ready(job.params)
    finally:
        jax.profiler.stop_trace()
    for root, _, files in os.walk(trace_dir):
        for f in files:
            if f.endswith(".xplane.pb"):
                return os.path.join(root, f)
    raise RuntimeError(f"the profiler left no .xplane.pb under {trace_dir}")
