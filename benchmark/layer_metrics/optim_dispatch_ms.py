"""``optim_dispatch_ms`` (layer ``optim``): host time from calling
``opt.step()`` to its return (plan lookup, placing every leaf, telemetry,
dispatch), the median ``bench.optim_dispatch`` span of the free stretch."""

import statistics


def read(ctx):
    spans = ctx.trace.spans_named("bench.optim_dispatch", inside=ctx.free)
    if not spans:
        return None
    return statistics.median(s.duration for s in spans) * 1e-6
