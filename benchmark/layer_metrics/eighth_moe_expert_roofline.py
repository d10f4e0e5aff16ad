"""``eighth_moe_expert_roofline`` (layer ``parallel.moe``): the least time
the chip's peaks allow for the grouped products over the held experts' rows
at 2048 x 768 (16 experts, 384 rows each from an even router: 6144 of the
49152 assignments; three products a pass), over the time the kernels took,
in percent.  Each call is held to the operations and bytes of its shape at
the rows an even router sends to the held experts
(``kanana_common.product_cost``, the same cost as
``flops_kanana.grouped_product``), a recomputed call counts as a call, and
the calls are counted from the trace.  The reader prints which bound sets
each kind."""

import collections

from benchmark import flops, spec


def read(ctx):
    common = spec.load_module("layer_metrics/kanana_common.py")
    least, taken, calls, bounds = 0.0, 0.0, collections.Counter(), {}
    for event in common.product_events(ctx):
        kind, cost = common.product_cost(ctx, event)
        if cost is None:
            print(f"  eighth_moe_expert_roofline: {event.name}: "
                  f"{event.what!r} is no grouped product of this cell's "
                  "sizes")
            return None
        seconds, bound = flops.roofline_seconds(cost, ctx.peaks)
        least += seconds
        taken += event.duration * 1e-9
        calls[kind] += 1
        bounds[kind] = f"{seconds * 1e3:.3f} ms each at least ({bound}-bound)"
    if not taken:
        return None
    print("  eighth_moe_expert_roofline: " + "; ".join(
        f"{kind} {n} calls, {bounds[kind]}" for kind, n in sorted(
            calls.items())) + f"; {taken * 1e3:.3f} ms taken in the free "
          f"stretch of {ctx.free_steps} steps")
    return 100.0 * least / taken
