"""What the readers of the program's own names, spans and counters share.

PR 22's readers time the step from outside: spans of the benchmark's loop
around the two calls, a stretch that waits after each.  These read what the
program says about itself (``docs/timeline.md``): the device's one event per
program execution (``jit_bf_rank_map_<fn>``, ``jit_bf_optim_step``: line
``XLA Modules`` of a device plane), the host spans ``bf.*`` that
``utils/timeline.op_span`` writes on the profiler's clock with their
arguments (``step=``, ``batch=``), the ``jax.named_scope`` names in the
compiled programs' metadata (``bf.optim.update``, ...), and the telemetry
registry.  Everything is read in the free stretch on the first chip.

The harness hands a reader the reduced trace and the cell, and neither the
raw trace nor the compiled programs, and this PR may not edit the harness.
So the reduction made here finds its sources itself: the ``.xplane.pb``
under the cell's trace directory, the live executables of the process
(``client.live_executables()``: the same HLO text ``compiled.as_text()``
gives) and ``telemetry.snapshot()``.  It is made once per traced run, kept on
the context, and written beside the trace as ``program.json`` (what
``selftest/trace_v5e_program.json`` was trimmed from).  A program without
these names, spans or counters (the parent of PR 23) yields empty lists, and
every reader returns None.

A device event belongs to the program whose execution encloses it, and to a
scope through its instruction: the scope the instruction's own ``op_name``
names; failing that the one most of its fused instructions name; failing
that the scope of the first instruction that uses its result, then of the
first whose result it uses.  (The TPU compiler gives the copies, slices and
dynamic-update-slices it makes around the flat buffer no metadata; they are
the fuse's and the unfuse's work, and their neighbours say which.)  A fusion
across a scope boundary is booked whole to the scope its own metadata names.
"""

from __future__ import annotations

import collections
import glob
import json
import os
import re
import statistics
from dataclasses import dataclass, field

from benchmark import spec
from benchmark import trace_reduce as tr

SPAN_PREFIX = "bf."
GRAD_PROGRAM = "jit_bf_rank_map_"
STEP_PROGRAM = "jit_bf_optim_step"
_MODULES_LINE = "XLA Modules"
_SCOPE = re.compile(r"bf\.[a-z_]+\.[a-z_]+")


@dataclass(frozen=True)
class Span:
    """One host span ``bf.*`` with the thread it ran on and its arguments."""
    name: str
    start: float    # ns on the profiler's clock
    end: float
    thread: str
    args: dict = field(default_factory=dict)

    @property
    def duration(self) -> float:
        return self.end - self.start


@dataclass
class Program:
    spans: list       # Span, every thread, sorted by start
    modules: dict     # chip -> [tr.Event]: name the XLA module, what run_id
    scopes: dict      # module name -> {instruction name: scope}
    counters: dict    # the registry's series at the end of the traced run

    def to_json(self, path: str) -> None:
        with open(path, "w") as f:
            json.dump({
                "program_spans": [[s.name, s.start, s.end, s.thread, s.args]
                                  for s in self.spans],
                "modules": {str(c): [[e.name, e.start, e.end, e.what]
                                     for e in v]
                            for c, v in self.modules.items()},
                "scopes": self.scopes, "counters": self.counters}, f)

    @classmethod
    def from_json(cls, path: str) -> "Program":
        """Every key is optional: a file of the old reduction
        (``Trace.to_json``) reads as a program that says nothing."""
        with open(path) as f:
            raw = json.load(f)
        return cls(
            spans=[Span(*row) for row in raw.get("program_spans", [])],
            modules={int(c): [tr.Event(*row) for row in rows]
                     for c, rows in raw.get("modules", {}).items()},
            scopes=raw.get("scopes", {}), counters=raw.get("counters", {}))


# --- the sources -------------------------------------------------------------

def read_xplane(path: str) -> tuple:
    """``(spans, modules)`` of a raw trace.  On a CPU mesh no plane is a
    device, and XLA's CPU client stands in so that the readers run in the
    rehearsal: the k-th run it starts (``ExecuteHelper``, one per virtual
    device) is the k-th ``PjitFunction(<fn>)`` the host called, and lasts
    until the next one starts (a step's programs wait for each other)."""
    from jax.profiler import ProfileData
    spans, modules, calls, runs, horizon = [], {}, [], {}, 0.0
    for plane in ProfileData.from_file(path).planes:
        chip = tr._DEVICE_PLANE.match(plane.name)
        for i, line in enumerate(plane.lines):
            if chip and line.name == _MODULES_LINE:
                modules[int(chip.group(1))] = [
                    tr.Event(e.name.split("(")[0], e.start_ns,
                             e.start_ns + e.duration_ns,
                             str(dict(e.stats).get("run_id", "")))
                    for e in line.events]
            if plane.name != "/host:CPU":
                continue
            for e in line.events:
                end = e.start_ns + e.duration_ns
                horizon = max(horizon, end)
                if e.name.startswith(SPAN_PREFIX):
                    spans.append(Span(e.name, e.start_ns, end,
                                      f"{line.name}#{i}",
                                      {k: str(v) for k, v in e.stats}))
                elif e.name.startswith("PjitFunction("):
                    calls.append((e.start_ns, end, "jit_" + e.name[13:-1]))
                elif e.name == "PjRtCpuExecutable::ExecuteHelper":
                    run = dict(e.stats).get("run_id")
                    runs[run] = min(runs.get(run, e.start_ns), e.start_ns)
    if not modules and runs:
        outer = []      # a call is two nested events: keep the outer one
        for call in sorted(calls):
            if not outer or call[0] >= outer[-1][1]:
                outer.append(call)
        starts = sorted(runs.values()) + [horizon]
        if len(outer) == len(runs):
            modules[0] = [tr.Event(name, starts[k], starts[k + 1], str(k))
                          for k, (_, _, name) in enumerate(outer)]
    return sorted(spans, key=lambda s: s.start), modules


_COMPUTATION = re.compile(r"^(?:ENTRY )?%([\w.\-]+) \(.*\{\s*$")
_INSTRUCTION = re.compile(r"^\s*(?:ROOT )?%([\w.\-]+) = (.*)$")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLS = re.compile(r"calls=%([\w.\-]+)")
_REF = re.compile(r"%([\w.\-]+)")


def scope_of(op_name: str | None) -> str | None:
    """The innermost ``bf.<layer>.<name>`` scope an ``op_name`` holds:
    ``transpose(jvp(bf.loss.chunked))/while/body/dot_general`` ->
    ``bf.loss.chunked``."""
    found = _SCOPE.findall(op_name or "")
    return found[-1] if found else None


def instruction_scopes(hlo_text: str) -> dict:
    """``{instruction name: scope}`` for every instruction of a compiled
    module's text that a scope can be found for, by the module docstring's
    four rules.  Only an instruction without any metadata looks to its
    neighbours, and the first neighbour with metadata answers, scope or
    not: a bare copy inside the model's blocks is not the loss's."""
    # label: a scope, "" for metadata that names none, None for no metadata
    label, body_of, operands, users = {}, {}, {}, collections.defaultdict(list)
    in_computation = collections.defaultdict(list)
    computation = None
    for line in hlo_text.splitlines():
        m = _COMPUTATION.match(line)
        if m:
            computation = m.group(1)
            continue
        m = _INSTRUCTION.match(line)
        if not m or computation is None:
            continue
        name, rest = m.groups()
        in_computation[computation].append(name)
        op = _OP_NAME.search(rest)
        label[name] = (scope_of(op.group(1)) or "") if op else None
        calls = _CALLS.search(rest)
        if calls:
            body_of[name] = calls.group(1)
        operands[name] = _REF.findall(rest.split(", metadata=")[0])
    for name, refs in operands.items():
        operands[name] = refs = [r for r in refs if r in label and r != name]
        for r in refs:
            users[r].append(name)

    def named(name):
        """Rules one and two."""
        if label[name] is None and name in body_of:
            inside = [label[i] for i in in_computation[body_of[name]]
                      if label[i] is not None]
            scoped = collections.Counter(filter(None, inside))
            if inside:
                label[name] = (scoped.most_common(1)[0][0] if scoped
                               else "")
        return label[name]

    def nearest(name, edges, memo):
        """The label of the nearest instruction with metadata along
        ``edges``."""
        if name not in memo:
            memo[name] = None
            for nxt in edges[name]:
                found = named(nxt)
                memo[name] = (found if found is not None
                              else nearest(nxt, edges, memo))
                if memo[name] is not None:
                    break
        return memo[name]

    out, ahead, behind = {}, {}, {}
    fused = set(body_of.values())       # their instructions are no events
    for computation, names in in_computation.items():
        if computation in fused:
            continue
        for name in names:
            found = named(name)
            if found is None:
                found = nearest(name, users, ahead)
            if found is None:
                found = nearest(name, operands, behind)
            if found:
                out[name] = found
    return out


def live_scopes() -> dict:
    """``{module name: {instruction: scope}}`` of the ``jit_bf_*`` programs
    this process has compiled or loaded and still holds."""
    import jax
    out = {}
    for executable in jax.devices()[0].client.live_executables():
        for module in executable.hlo_modules():
            if module.name.startswith("jit_bf_") and module.name not in out:
                out[module.name] = instruction_scopes(module.to_string())
    return out


def trace_path(cell_name: str) -> str | None:
    found = glob.glob(os.path.join(spec.ROOT, ".bench_trace", cell_name,
                                   "**", "*.xplane.pb"), recursive=True)
    return found[0] if found else None


def program(ctx) -> Program:
    """The reduction for this traced run: made on first use, kept on the
    context (a test hands one over the same way)."""
    if getattr(ctx, "program", None) is None:
        path = trace_path(ctx.cell.name)
        spans, modules = read_xplane(path) if path else ([], {})
        counters = {}
        if spans:      # the program has its side of this: ask it the rest
            from bluefog_tpu.utils import telemetry
            counters = telemetry.snapshot()
        ctx.program = Program(spans, modules, live_scopes() if spans else {},
                              counters)
        if path:
            ctx.program.to_json(os.path.join(os.path.dirname(path),
                                             "program.json"))
    return ctx.program


# --- what the readers compute ------------------------------------------------

def spans_in_free(ctx, name: str) -> list:
    if ctx.free is None:
        return []
    return [s for s in program(ctx).spans if s.name == name
            and ctx.free.start <= s.start and s.end <= ctx.free.end]


def span_median_ms(ctx, name: str):
    """Median length of the host span ``name`` in the free stretch."""
    spans = spans_in_free(ctx, name)
    if not spans:
        return None
    return statistics.median(s.duration for s in spans) * 1e-6


def executions(ctx, prefix: str, free_only: bool = True) -> list:
    """The first chip's executions of the program whose module name starts
    with ``prefix``.  The device's clock sits within a millisecond of the
    host's (0.9 ms apart in the first v5e trace looked at), so an execution
    counts as in the free stretch if its middle is."""
    found = [m for m in program(ctx).modules.get(ctx.chip, [])
             if m.name.startswith(prefix)]
    if free_only:
        found = [m for m in found if ctx.free is not None and
                 ctx.free.start <= (m.start + m.end) / 2 < ctx.free.end]
    return found


def program_device_ms(ctx, prefix: str):
    """Device-busy time inside the executions of one program, per
    execution (one execution is one step)."""
    runs = executions(ctx, prefix)
    if not runs or ctx.chip not in ctx.trace.ops:
        return None
    ops = ctx.trace.ops[ctx.chip]
    return sum(tr.busy(ops, m.start, m.end) for m in runs) / len(runs) * 1e-6


def scope_ms(ctx, prefix: str) -> dict | None:
    """Self time of the device operations inside the executions of one
    program, per execution, by scope (``None``: no rule found one)."""
    runs = executions(ctx, prefix)
    if not runs or ctx.chip not in ctx.trace.ops:
        return None
    scopes = program(ctx).scopes
    total = collections.defaultdict(float)
    for m in runs:
        inside = tr.within(ctx.trace.ops[ctx.chip], m.start, m.end)
        for e, t in tr.self_times(inside):
            total[scopes.get(m.name, {}).get(e.name)] += t
    return {k: v / len(runs) * 1e-6 for k, v in total.items()}


def scope_device_ms(ctx, prefix: str, *wanted: str):
    by_scope = scope_ms(ctx, prefix)
    if by_scope is None or not any(w in by_scope for w in wanted):
        return None
    return sum(by_scope.get(w, 0.0) for w in wanted)


def counter(ctx, name: str, **labels):
    key = name + ("{" + ",".join(f'{k}="{v}"' for k, v in labels.items())
                  + "}" if labels else "")
    return program(ctx).counters.get(key)
