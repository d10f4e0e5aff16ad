"""``gossip_device_ms`` (layer ``ops.collective``): time of the
``collective-permute`` operations per step on the first chip, each from the
begin of its start to the end of its done, in the free stretch."""

from benchmark import trace_reduce as tr


def read(ctx):
    spans = tr.union(tr.async_intervals(ctx.free_ops(), "collective-permute"))
    if not spans or not ctx.free_steps:
        return None
    return tr.length(spans) / ctx.free_steps * 1e-6
