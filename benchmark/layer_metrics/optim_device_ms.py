"""``optim_device_ms`` (layer ``optim``): device-busy time of the
optimizer-step program (update, fuse, combine) per step: the device events
between the start of ``bench.optim_dispatch`` and the end of the
``bench.optim_wait`` that follows it, in the blocked stretch, first chip."""

from benchmark.trace_reduce import Event


def read(ctx):
    starts = ctx.trace.spans_named("bench.optim_dispatch", inside=ctx.blocked)
    waits = ctx.trace.spans_named("bench.optim_wait", inside=ctx.blocked)
    if not starts or len(starts) != len(waits):
        return None
    whole = [Event("optim", d.start, w.end) for d, w in zip(starts, waits)]
    return ctx.trace.device_ns_in(ctx.chip, whole) / len(whole) * 1e-6
