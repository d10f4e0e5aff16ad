"""``gossip_exposed_ms`` (layer ``ops.collective``): the part of
``gossip_device_ms`` during which no other operation runs on that chip."""

from benchmark import trace_reduce as tr


def read(ctx):
    ops = ctx.free_ops()
    if not tr.async_intervals(ops, "collective-permute") \
            or not ctx.free_steps:
        return None
    return tr.exposed(ops, "collective-permute") / ctx.free_steps * 1e-6
