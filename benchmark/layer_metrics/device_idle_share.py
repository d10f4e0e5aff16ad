"""``device_idle_share`` (layer ``device``): 1 minus the union of the device
operations' intervals over the free stretch, in percent, averaged over the
chips used."""


def read(ctx):
    if ctx.free is None or not ctx.window_s:
        return None
    return 100.0 * (1.0 - ctx.busy_s / ctx.window_s)
