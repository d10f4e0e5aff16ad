"""``optim_launch_ms`` (layer ``optim``, host): median length of the span
``bf.optim.launch`` in the free stretch: the call of the jitted step program,
from Python's argument handling to the runtime's enqueue.  The line it
prints splits ``bf.optim.step`` into place, launch and self."""

import statistics

from benchmark import spec


def read(ctx):
    common = spec.load_module("layer_metrics/program_common.py")
    launch = common.span_median_ms(ctx, "bf.optim.launch")
    steps = common.spans_in_free(ctx, "bf.optim.step")
    if launch is None or not steps:
        return launch
    inside = [s for name in ("bf.optim.place", "bf.optim.launch",
                             "bf.optim.build")
              for s in common.spans_in_free(ctx, name)]
    own = statistics.median(
        step.duration - sum(s.duration for s in inside
                            if step.start <= s.start and s.end <= step.end)
        for step in steps) * 1e-6
    print(f"  optim_launch_ms: bf.optim.step median "
          f"{common.span_median_ms(ctx, 'bf.optim.step'):.3f} ms = place "
          f"{common.span_median_ms(ctx, 'bf.optim.place'):.3f} + launch "
          f"{launch:.3f} + self {own:.3f} (medians, {len(steps)} steps)")
    return launch
