"""``grad_device_ms`` (layer ``models``): device-busy time of the gradient
program per step, the device events inside the ``bench.grad`` spans of the
blocked stretch on the first chip."""


def read(ctx):
    spans = ctx.trace.spans_named("bench.grad", inside=ctx.blocked)
    if not spans:
        return None
    return ctx.trace.device_ns_in(ctx.chip, spans) / len(spans) * 1e-6
