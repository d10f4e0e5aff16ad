"""What the five readers of a run's regime share: who held the host in the
free stretch and with how much memory to spare, and which path the held
experts took.

**The host.**  ``bf.rank_map`` writes on every ``bf.rank_map.launch`` the
allocator's state as the call begins (span arguments ``in_use``,
``reserved``, ``largest_free``, ``limit``, bytes of the process's first
device; absent where ``memory_stats()`` is None) and whether the launch
outlasted its arguments (``held=0|1``: one was still being computed when the
call began and all were ready when it returned: the runtime held the call
for memory until the step in flight was over).  The host can stand still in
three places of the library, each a span: ``bf.optim.wait`` (``step()``
returns when the step before the one it launched is over),
``bf.rank_map.wait`` (after three held launches the calls wait for their
arguments first) and a held ``bf.rank_map.launch``.  ``held`` is what tells
a program of this kind from its parent: without it on any launch every
reader here returns None, and with it a reader that finds none of its own
spans returns 0.0.

**The device.**  ``parallel/moe.py::_branch`` names the two branches of the
held share's one ``lax.cond``: every operation of the overflow branch
carries ``bf_moe_held_overflow`` in its ``op_name`` and every operation of
the window branch ``bf_moe_held_window``, forward, remat recompute and
transpose.  The names are not of the shape ``bf.<layer>.<name>``, so
``program_common.py`` books the operations as before; here the live
gradient programs' text is read again with each ``op_name`` cut down to its
marker, by the same four rules (``instruction_scopes``), as
``moe_common._grad_scopes`` reads it for the bare ``bf.moe``.  On the device
a conditional is one event that encloses the operations of the branch it
took (``conditional.7`` or ``cond.7`` around the window's gather, products
and sum; it is known by its opcode in the program's text), so
one execution of a held share's conditional is one such event, and the
branch is read off the markers of the events inside it.
"""

from __future__ import annotations

import collections
import copy
import dataclasses
import re
import statistics

from benchmark import spec
from benchmark import trace_reduce as tr

LAUNCH, GRAD_WAIT, OPTIM_WAIT = ("bf.rank_map.launch", "bf.rank_map.wait",
                                 "bf.optim.wait")
MEMORY = ("in_use", "reserved", "largest_free", "limit")
WINDOW, OVERFLOW = "bf_moe_held_window", "bf_moe_held_overflow"
# the markers as scopes of the shape program_common.py knows
_AS_SCOPE = {WINDOW: "bf.held.window", OVERFLOW: "bf.held.overflow"}
_CALLED = re.compile(
    r"(?:calls|body|condition|to_apply|true_computation|false_computation)"
    r"=%([\w.\-]+)|branch_computations=\{([^}]*)\}")
GIB = 2.0 ** 30


def _common():
    return spec.load_module("layer_metrics/program_common.py")


# --- the host ----------------------------------------------------------------

def instrumented(ctx) -> bool:
    """Does the program say which launches were held."""
    return any(s.name == LAUNCH and "held" in s.args
               for s in _common().program(ctx).spans)


def quartiles_ms(spans) -> str:
    """``q1 / median / q3`` of the spans' lengths, ms."""
    ms = sorted(s.duration * 1e-6 for s in spans)
    if len(ms) < 2:
        return " / ".join(f"{x:.3f}" for x in ms) or "none"
    q1, q2, q3 = statistics.quantiles(ms, n=4)
    return f"{q1:.3f} / {q2:.3f} / {q3:.3f}"


def launches(ctx) -> tuple:
    """``(held, free)``: the launches of the free stretch that outlasted
    their arguments and those that did not."""
    found = _common().spans_in_free(ctx, LAUNCH)
    held = [s for s in found if s.args.get("held") == "1"]
    return held, [s for s in found if s.args.get("held") != "1"]


def per_step_ms(ctx, spans) -> float:
    """The spans' summed length over the steps of the free stretch."""
    return sum(s.duration for s in spans) * 1e-6 / max(ctx.free_steps, 1)


def host_account(ctx) -> None:
    """Print where the host's step went, as means a step of the free
    stretch, beside the device's step: the three places it can stand still,
    its own work by span, the benchmark loop's two spans around them, and
    what no span covers."""
    common = _common()
    held, free = launches(ctx)
    parts = {
        "bf.optim.wait": common.spans_in_free(ctx, OPTIM_WAIT),
        "bf.rank_map.wait": common.spans_in_free(ctx, GRAD_WAIT),
        "bf.rank_map.launch held": held,
        "bf.rank_map.launch free": free,
        "bf.optim.place": common.spans_in_free(ctx, "bf.optim.place"),
        "bf.optim.launch": common.spans_in_free(ctx, "bf.optim.launch"),
        "bench.next_batch": ctx.trace.spans_named("bench.next_batch",
                                                  inside=ctx.free),
        "bench.group_sync": ctx.trace.spans_named("bench.group_sync",
                                                  inside=ctx.free)}
    ms = {name: per_step_ms(ctx, spans) for name, spans in parts.items()}
    steps = common.spans_in_free(ctx, "bf.optim.step")
    ms["bf.optim.step self"] = (per_step_ms(ctx, steps)
                                - ms["bf.optim.place"]
                                - ms["bf.optim.launch"])
    wall = ctx.window_s * 1e3 / max(ctx.free_steps, 1)
    device = sum(common.program_device_ms(ctx, p) or 0.0
                 for p in (common.GRAD_PROGRAM, common.STEP_PROGRAM))
    print(f"  host's step, mean ms of {ctx.free_steps} steps: " + ", ".join(
        f"{name} {v:.3f}" for name, v in ms.items())
        + f"; sum {sum(ms.values()):.3f} of a wall step of {wall:.3f} "
        f"(no span: {wall - sum(ms.values()):.3f}); the device's step "
        f"(gradient + optimizer program, busy) {device:.3f}")


def headrooms(ctx) -> list:
    """``limit - in_use - reserved`` of every launch of the free stretch
    that carries the allocator's state, bytes."""
    return [int(s.args["limit"]) - int(s.args["in_use"])
            - int(s.args["reserved"])
            for s in _common().spans_in_free(ctx, LAUNCH)
            if all(k in s.args for k in MEMORY)]


# --- the device --------------------------------------------------------------

def as_markers(text: str) -> str:
    """A module's text with every ``op_name`` cut down to the marker it
    holds, as a scope ``instruction_scopes`` can find, or to nothing."""
    def cut(m):
        for marker, scope in _AS_SCOPE.items():
            if marker in m.group(1):
                return f'op_name="{scope}"'
        return 'op_name=""'
    return _common()._OP_NAME.sub(cut, text)


def pass_of(op_name: str) -> str:
    """The pass an ``op_name`` belongs to: the remat recompute, the
    transpose or the forward."""
    return ("recompute" if "rematted_computation" in op_name
            else "transpose" if "transpose(" in op_name else "forward")


def held_conditionals(text: str) -> dict:
    """``{conditional instruction: (pass, markers)}`` of a module's text
    for the conditionals of a held share: those outside both branches whose
    own branches, followed through every computation they call, hold a
    marked instruction.  ``pass`` by the conditional's own ``op_name``;
    ``markers`` are the branches that hold any operation at all (the
    recompute's overflow branch keeps nothing and the compiler may leave it
    empty)."""
    common = _common()
    holds = collections.defaultdict(set)     # computation -> its markers
    calls = collections.defaultdict(set)     # computation -> computations
    found, computation = {}, None
    for line in text.splitlines():
        m = common._COMPUTATION.match(line)
        if m:
            computation = m.group(1)
            continue
        m = common._INSTRUCTION.match(line)
        if not m or computation is None:
            continue
        name, rest = m.groups()
        op = common._OP_NAME.search(rest)
        op_name = op.group(1) if op else ""
        mine = {scope for marker, scope in _AS_SCOPE.items()
                if marker in op_name}
        holds[computation] |= mine
        called = {c for one, many in _CALLED.findall(rest)
                  for c in [one] + common._REF.findall(many) if c}
        calls[computation] |= called
        if " conditional(" in rest and not mine:
            found[name] = (op_name, called)

    def reach(start):
        seen, stack = set(), list(start)
        while stack:
            c = stack.pop()
            if c not in seen:
                seen.add(c)
                stack.extend(calls[c])
        return set().union(*(holds[c] for c in seen))
    out = {}
    for name, (op_name, called) in found.items():
        markers = reach(called)
        if markers:
            out[name] = (pass_of(op_name), frozenset(markers))
    return out


def branch_maps(ctx) -> tuple:
    """``({module: {instruction: marker scope}}, {module:
    held_conditionals})`` of the live gradient programs; made once a traced
    run."""
    if getattr(ctx, "regime_maps", None) is None:
        import jax
        common, markers, by_pass = _common(), {}, {}
        if common.program(ctx).spans:
            for executable in jax.devices()[0].client.live_executables():
                for module in executable.hlo_modules():
                    if module.name.startswith(common.GRAD_PROGRAM) \
                            and module.name not in markers:
                        text = module.to_string()
                        markers[module.name] = common.instruction_scopes(
                            as_markers(text))
                        by_pass[module.name] = held_conditionals(text)
        ctx.regime_maps = markers, by_pass
    return ctx.regime_maps


def conditionals(ctx) -> list:
    """``(conditional, pass, branch)`` of every execution of a held
    share's conditional in the free stretch on the first chip: the branch
    whose marker an event inside it carries, and where none does, the one
    branch of the two that holds no operation."""
    common = _common()
    markers, held = branch_maps(ctx)
    names = {_AS_SCOPE[OVERFLOW]: "overflow", _AS_SCOPE[WINDOW]: "window"}
    out = []
    if ctx.chip not in ctx.trace.ops:
        return out
    for run in common.executions(ctx, common.GRAD_PROGRAM):
        marker, of_module = markers.get(run.name, {}), held.get(run.name, {})
        inside = tr.within(ctx.trace.ops[ctx.chip], run.start, run.end)
        for cond in inside:
            if cond.name not in of_module:
                continue
            which, static = of_module[cond.name]
            seen = {marker.get(e.name) for e in tr.within(
                inside, cond.start, cond.end) if e.end <= cond.end}
            took = [b for b in names if b in seen] or [
                b for b in names if b not in static]
            if len(took) == 1:
                out.append((cond.name, which, names[took[0]]))
    return out


def by_conditional(found) -> str:
    """``pass conditional: overflow of all`` for the printed line."""
    count = collections.Counter((p, c) for c, p, _ in found)
    over = collections.Counter((p, c) for c, p, b in found
                               if b == "overflow")
    return ", ".join(f"{p} {c} {over[p, c]}/{n}"
                     for (p, c), n in sorted(count.items()))


def branch_ms(ctx) -> dict | None:
    """Self time a step of the gradient program's device operations by
    branch marker (``program_common.scope_ms`` over the markers' map); None
    where no live gradient program carries a marker."""
    common = _common()
    markers, _ = branch_maps(ctx)
    if not any(markers.values()):
        return None
    view = copy.copy(ctx)
    view.program = dataclasses.replace(common.program(ctx), scopes=markers)
    by_scope = common.scope_ms(view, common.GRAD_PROGRAM) or {}
    return {"overflow": by_scope.get(_AS_SCOPE[OVERFLOW], 0.0),
            "window": by_scope.get(_AS_SCOPE[WINDOW], 0.0)}
