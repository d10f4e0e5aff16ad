"""What the readers of the way to the first step share (PR 36).

``setup_s`` is process start to the first timed step.  The program books
that way itself (``docs/observability.md``, Start-up): jax's compile events
as the histogram ``bf_program_build_seconds{program, stage}`` (``stage``:
``trace``, ``lower``, ``compile``; ``program``: the library's own ``bf_*``
names, ``other`` for the rest), the persistent cache's answers as
``bf_compile_cache_total{result}``, the Pallas wrappers' runs as
``bf_kernel_stagings_total{kernel}`` and ``bf.init()`` / ``opt.init()`` as
the gauge ``bf_startup_seconds{part}``.  All of it is over before the
profiler trace starts, so these readers take the registry's snapshot that
``program_common.program`` keeps (the whole process up to the end of the
traced steps: the warm-up, and the harness's own ``lower().compile()`` of
both programs for their text and bytes) and no span.  A program without the
series (the parent of PR 36) gives None from all four.
"""

from __future__ import annotations

import re

from benchmark import spec

STAGES = ("trace", "lower", "compile")
_SERIES = re.compile(r'^(\w+)\{(\w+)="([^"]*)"(?:,(\w+)="([^"]*)")?\}$')


def counters(ctx) -> dict:
    return spec.load_module(
        "layer_metrics/program_common.py").program(ctx).counters


def by_label(ctx, name: str) -> dict:
    """``{label value (a tuple where the series has two): value}`` of the
    series ``name{...}``, labels in the registry's (sorted) order."""
    out = {}
    for key, value in counters(ctx).items():
        m = _SERIES.match(key)
        if m and m.group(1) == name:
            out[(m.group(3), m.group(5)) if m.group(4) else m.group(3)] = value
    return out


def builds(ctx) -> dict:
    """``{program: {stage: (seconds, times)}}``."""
    seconds = by_label(ctx, "bf_program_build_seconds_sum")
    times = by_label(ctx, "bf_program_build_seconds_count")
    out = {}
    for (program, stage), value in seconds.items():
        out.setdefault(program, {})[stage] = (
            value, int(times.get((program, stage), 0)))
    return out


def seconds(stages: dict) -> float:
    """A program's own seconds: a ``trace_nested`` lies inside another
    program's ``trace`` and is left out."""
    return sum(stages.get(s, (0.0, 0))[0] for s in STAGES)


def stage_line(stages: dict) -> str:
    parts = [f"{s} {stages[s][0]:.3f}s x{stages[s][1]}"
             for s in STAGES + ("trace_nested",) if s in stages]
    return ", ".join(parts) or "nothing"


def cache_line(ctx) -> str:
    cache = by_label(ctx, "bf_compile_cache_total")
    return (f"persistent cache {int(cache.get('hit', 0))} hits "
            f"{int(cache.get('miss', 0))} misses")
