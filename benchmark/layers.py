"""The traced run: record the two stretches, reduce the trace once, and hand
the reduction to the cell's per-layer metric readers
(``benchmark/layer_metrics/<name>.py``, found by the names in
``BENCHMARK.json``).  A reader that finds nothing to read returns None and
its metric is left out of the line.
"""

from __future__ import annotations

import re
from dataclasses import dataclass

from benchmark import loop, spec
from benchmark import trace_reduce as tr


@dataclass
class Context:
    """What a reader gets: the reduced trace, the stretches, the cell's own
    files and the peaks of the device."""
    trace: tr.Trace
    cell: spec.Cell
    peaks: dict
    step_flops: dict          # operations one step requires on one chip
    chip: int                 # the chip single-chip readers look at
    blocked: tr.Event | None  # the stretch with a wait after every call
    free: tr.Event | None     # the stretch with waits at group ends only
    free_steps: int
    busy_s: float             # device-busy seconds of the free stretch,
    window_s: float           # averaged over the chips, and its length
    mosaic_calls: dict        # Mosaic kernel instructions of the programs

    def free_ops(self) -> list:
        """The first chip's device events of the free stretch."""
        if self.free is None or self.chip not in self.trace.ops:
            return []
        return tr.within(self.trace.ops[self.chip], self.free.start,
                         self.free.end)


_MOSAIC_CALL = re.compile(
    r'^\s*(?:ROOT )?%([\w.\-]+) = (.*?) custom-call\((.*?)\), '
    r'custom_call_target="tpu_custom_call".*?op_name="([^"]*)"', re.M)


def mosaic_calls(compiled: dict) -> dict:
    """Every Mosaic kernel instruction of the compiled programs by its HLO
    name (the name its device events carry): operand and result counts and
    the scope it came from."""
    calls = {}
    for program in compiled.values():
        for name, result, operands, op_name in _MOSAIC_CALL.findall(
                program.as_text()):
            calls[name] = {"operands": operands.count("%"),
                           "results": len(re.findall(r"\w+\[", result)),
                           "op_name": op_name}
    return calls


def context(trace: tr.Trace, cell, task, peaks, compiled) -> Context:
    free = trace.stretch("free")
    chips = trace.chips()
    busy = window = 0.0
    if free is not None and chips:
        window = free.duration * 1e-9
        busy = sum(tr.busy(trace.ops[c], free.start, free.end)
                   for c in chips) / len(chips) * 1e-9
    return Context(
        trace=trace, cell=cell, peaks=peaks,
        step_flops=task.step_flops(cell.config, cell.traffic["batch"]),
        chip=chips[0] if chips else 0, blocked=trace.stretch("blocked"),
        free=free, busy_s=busy, window_s=window,
        mosaic_calls=mosaic_calls(compiled),
        free_steps=len(trace.spans_named("bench.optim_dispatch",
                                         inside=free)) if free else 0)


def breakdown(ctx: Context) -> dict:
    """The device operations that took most time and the longest idle gaps,
    named by the benchmark span the host was in, over the free stretch on
    the first chip."""
    ops = ctx.free_ops()
    if not ops:
        return {"device_ops": [], "idle_gaps": []}
    inner = [s for s in ctx.trace.spans
             if s.name not in ("bench.free", "bench.blocked")]
    gaps = tr.idle_gaps(ops, ctx.free.start, ctx.free.end)
    return {"device_ops": tr.top_by_name(ops, 10),
            "idle_gaps": tr.label_gaps(gaps, inner or ctx.trace.spans, 10)}


def run(job, cell, task, peaks, compiled, trace_dir: str, *,
        blocked_steps: int, free_groups: int) -> dict:
    path = loop.traced(job, trace_dir, blocked_steps=blocked_steps,
                       free_groups=free_groups)
    ctx = context(tr.Trace.from_xplane(path), cell, task, peaks, compiled)
    print(f"trace: {path}; chips {ctx.trace.chips()}, "
          f"{sum(map(len, ctx.trace.ops.values()))} device events, "
          f"{len(ctx.trace.spans)} benchmark spans; free stretch "
          f"{ctx.window_s:.4f}s with {ctx.free_steps} steps, device busy "
          f"{ctx.busy_s:.4f}s", flush=True)
    metrics = {m["name"]: spec.layer_metric_reader(m["name"])(ctx)
               for m in cell.per_layer}
    return {"metrics": metrics, "busy_s": ctx.busy_s,
            "window_s": ctx.window_s, "breakdown": breakdown(ctx),
            "steps": blocked_steps + free_groups * loop.GROUP}
