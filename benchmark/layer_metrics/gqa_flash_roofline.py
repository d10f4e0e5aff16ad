"""``gqa_flash_roofline`` (layer ``ops.flash_attention``): the least time
the chip's peaks allow for the flash kernel calls made at heads of 64 (32 of
them: the K/V heads are repeated before the kernel), over the time they
took, in percent.  Each call is held to the operations and bytes its own
outputs require (``benchmark/flops_lfm2.py``); a recomputed forward counts
as a call.  The reader prints which bound sets each kind."""

from benchmark import flops, spec


def read(ctx):
    common = spec.load_module("layer_metrics/lfm2_common.py")
    events = common.flash_events(ctx)
    if not events:
        return None
    least = {kind: flops.roofline_seconds(common.flash_cost(ctx, kind),
                                          ctx.peaks)
             for kind in {k for _, k in events}}
    taken = {kind: sum(e.duration for e, k in events if k == kind) * 1e-9
             for kind in least}
    print("  gqa_flash_roofline: " + "; ".join(
        f"{kind} {sum(1 for _, k in events if k == kind)} calls, least "
        f"{seconds * 1e3:.3f} ms each ({bound}-bound), "
        f"{taken[kind] * 1e3:.3f} ms taken"
        for kind, (seconds, bound) in sorted(least.items())))
    return 100.0 * sum(least[k][0] for _, k in events) / sum(taken.values())
