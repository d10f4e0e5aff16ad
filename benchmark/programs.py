"""What the compiled programs of a step say about themselves: compile
seconds and cache traffic, cross-device collectives in the HLO, bytes on one
device.  ``CompileClock`` and ``collective_counts`` are copied from
``chip_smoke.py`` (PERF.md, Open questions, lists the originals).
"""

from __future__ import annotations

import re

import jax

_COLLECTIVE = re.compile(
    r"\s(all-gather|all-reduce|all-to-all|collective-permute)(?:-start)?\(")


def collective_counts(compiled) -> dict:
    """Cross-device collective ops in a compiled program's HLO, by kind."""
    counts = dict.fromkeys(
        ("all-gather", "all-reduce", "all-to-all", "collective-permute"), 0)
    for m in _COLLECTIVE.finditer(compiled.as_text()):
        counts[m.group(1)] += 1
    return counts


class CompileClock:
    """Seconds spent in XLA compilation (persistent-cache retrieval
    included), the number of compile requests and the cache's hits and
    misses, from jax's own monitoring events."""

    def __init__(self):
        import jax.monitoring
        self.seconds = 0.0
        self.compiles = 0
        self.hits = 0
        self.misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, seconds, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += seconds
            self.compiles += 1

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def line(self) -> str:
        return (f"compile: {self.seconds:.1f}s in {self.compiles} programs, "
                f"persistent cache {self.hits} hits {self.misses} misses")


def program_bytes(compiled) -> int:
    """Bytes one device holds while the program runs: arguments, outputs and
    scratch, less the outputs that alias an argument."""
    m = compiled.memory_analysis()
    return (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes - m.alias_size_in_bytes)


def tree_bytes_per_device(tree, n: int) -> int:
    """Bytes of a rank-major tree on each of its ``n`` devices."""
    return sum(x.nbytes for x in jax.tree_util.tree_leaves(tree)) // n


def step_programs(job) -> dict:
    """The two programs of a step as compiled for the job's own arguments.
    After the warm-up both come from jax's in-process cache: lowering does
    not consume donated buffers and nothing compiles again."""
    batch = job.pool[0]
    grad = job.vgrad.lower(job.params, job.aux, *batch).compile()
    step = job.opt._step_callable(with_weights=False).lower(
        job.params, job.params, job.state).compile()
    return {"grad": grad, "step": step}


def peak_bytes(job, programs: dict) -> dict:
    """Peak bytes on one device over the step's programs: each program's own
    bytes plus the job's live trees that are not among its arguments (the
    optimizer state during the gradient program, the rest of the data pool,
    the batch statistics during the optimizer step)."""
    per = lambda tree: tree_bytes_per_device(tree, job.n)  # noqa: E731
    one = per(job.pool[0])
    # Batches resident beside the one in use: the rest of a device pool, or
    # the two the input pipeline keeps ahead of a host pool.
    others = per(job.pool) - one if job._feed is None else 2 * one
    grad = program_bytes(programs["grad"]) + per(job.state) + others
    step = program_bytes(programs["step"]) + others + one + per(job.aux)
    return {"grad": grad, "step": step, "peak": max(grad, step)}
