"""``gossip_gb_per_step`` (layer ``ops.collective``): gigabytes one chip puts
on the wire per step, from the program's own counter
``bf_comm_wire_bytes_total{op="optimizer_step"}`` over the ``opt.step()``
calls it counted (``bf_optimizer_step_seconds_count``) and the chips.  Every
step of the process runs the same program, so the ratio of the totals is the
traced run's.  The line it prints holds it against the bytes of the
``collective-permute`` results in the same trace."""

import re

from benchmark import spec

_RESULT = re.compile(r"^(\w+?)(\d+)\[([\d,]*)\]")


def permute_bytes(ctx) -> float:
    """Bytes of the ``collective-permute-done`` results of the free
    stretch on the first chip, per step."""
    total = 0
    for e in ctx.free_ops():
        m = _RESULT.match(e.what)
        if e.name.startswith("collective-permute-done") and m:
            size = int(m.group(2)) // 8
            for d in filter(None, m.group(3).split(",")):
                size *= int(d)
            total += size
    return total / ctx.free_steps if ctx.free_steps else 0.0


def read(ctx):
    common = spec.load_module("layer_metrics/program_common.py")
    wire = common.counter(ctx, "bf_comm_wire_bytes_total",
                          op="optimizer_step")
    steps = common.counter(ctx, "bf_optimizer_step_seconds_count",
                           family="collective")
    if wire is None or not steps:
        return None
    value = wire / steps / ctx.cell.chips / 1e9
    print(f"  gossip_gb_per_step: counter {wire:.0f} B over {steps:.0f} "
          f"steps and {ctx.cell.chips} chip(s); collective-permute results "
          f"in the trace {permute_bytes(ctx) / 1e9:.9f} GB a step")
    return value
