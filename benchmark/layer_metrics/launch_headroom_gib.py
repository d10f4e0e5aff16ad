"""``launch_headroom_gib`` (layer ``models``, host): the least room the
allocator had as a gradient launch of the free stretch began: the smallest
``limit - in_use - reserved`` over the ``bf.rank_map.launch`` spans that
carry the allocator's state (the first chip's ``memory_stats()``), GiB.
Under one tree of gradients plus the program's temporaries the runtime
holds the launch until the step in flight gives its memory back.  The line
it prints gives the median, the smallest and the median
``largest_free`` (and, a line before, every launch's ``held``, length and
``largest_free``: whether the allocator's largest hole tells the held
launches from the free ones), the gauge ``bf_launch_headroom_min_bytes`` (every launch
since ``bf.init()``) and the chip's own ``peak_bytes_in_use`` (the run's
last lines put it beside the computed peak).  0.0 where no launch carries
the state (a platform whose ``memory_stats()`` is None)."""

import statistics

from benchmark import spec


def read(ctx):
    regime = spec.load_module("layer_metrics/regime_common.py")
    if not regime.instrumented(ctx):
        return None
    common = spec.load_module("layer_metrics/program_common.py")
    rooms = regime.headrooms(ctx)
    if not rooms:
        print("  launch_headroom_gib: no launch carries the allocator's "
              "state (memory_stats() is None here)")
        return 0.0
    import jax
    free = [int(s.args["largest_free"])
            for s in common.spans_in_free(ctx, regime.LAUNCH)
            if "largest_free" in s.args]
    stats = jax.devices()[0].memory_stats() or {}
    since_init = common.counter(ctx, "bf_launch_headroom_min_bytes")
    print("  launch_headroom_gib: by launch, held / ms / largest_free GiB: "
          + ", ".join(f"{s.args.get('held')} {s.duration * 1e-6:.1f} "
                      f"{int(s.args['largest_free']) / regime.GIB:.3f}"
                      for s in common.spans_in_free(ctx, regime.LAUNCH)
                      if "largest_free" in s.args))
    print(f"  launch_headroom_gib: {len(rooms)} launches, least "
          f"{min(rooms) / regime.GIB:.4f} median "
          f"{statistics.median(rooms) / regime.GIB:.4f} GiB; largest_free "
          f"least {min(free) / regime.GIB:.4f} median "
          f"{statistics.median(free) / regime.GIB:.4f} GiB; "
          f"bf_launch_headroom_min_bytes {since_init}; memory_stats "
          f"peak_bytes_in_use {stats.get('peak_bytes_in_use')} of "
          f"bytes_limit {stats.get('bytes_limit')}")
    return min(rooms) / regime.GIB
