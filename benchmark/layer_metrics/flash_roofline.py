"""``flash_roofline`` (layer ``ops.flash_attention``): the least time the
chip's peaks allow for the kernel calls made, over the time they took, in
percent.  Each call is held to the operations and bytes its own outputs
require (``benchmark/flops.py``); at these lengths every kernel is bound by
compute (bfloat16 peak), which the reader prints."""

from benchmark import flops, spec


def read(ctx):
    common = spec.load_module("layer_metrics/flash_common.py")
    events = common.kernel_events(ctx)
    if not events:
        return None
    least = {kind: flops.roofline_seconds(common.call_cost(ctx, kind),
                                          ctx.peaks)
             for kind in {k for _, k in events}}
    print("  flash_roofline: " + "; ".join(
        f"{kind} {sum(1 for _, k in events if k == kind)} calls, least "
        f"{seconds * 1e3:.3f} ms each ({bound}-bound)"
        for kind, (seconds, bound) in sorted(least.items())))
    taken = sum(e.duration for e, _ in events) * 1e-9
    return 100.0 * sum(least[k][0] for _, k in events) / taken
