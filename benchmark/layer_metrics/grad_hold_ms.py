"""``grad_hold_ms`` (layer ``models``, host): how long a step of the free
stretch the gradient launch stood still, as a mean: the lengths of the
``bf.rank_map.wait`` spans plus those of the ``bf.rank_map.launch`` spans
with ``held=1`` (the launch outlasted its arguments: the runtime held it
for memory until the step in flight was over), over the steps.  0 where the
host runs ahead freely.  The line it prints gives the held launches of
all, the waits beside ``bf_rank_map_waits_total`` and
``bf_rank_map_held_launches_total`` (every launch since ``bf.init()``),
and the quartiles of the held and of the free launches."""

from benchmark import spec


def read(ctx):
    regime = spec.load_module("layer_metrics/regime_common.py")
    if not regime.instrumented(ctx):
        return None
    common = spec.load_module("layer_metrics/program_common.py")
    held, free = regime.launches(ctx)
    waits = common.spans_in_free(ctx, regime.GRAD_WAIT)
    print(f"  grad_hold_ms: {len(held)} of {len(held) + len(free)} launches "
          f"held, q1 / median / q3 ms {regime.quartiles_ms(held)}; the "
          f"others {regime.quartiles_ms(free)}; {len(waits)} waits "
          f"{regime.quartiles_ms(waits)}; since bf.init(): "
          f"bf_rank_map_waits_total "
          f"{common.counter(ctx, 'bf_rank_map_waits_total') or 0:.0f}, "
          f"bf_rank_map_held_launches_total "
          f"{common.counter(ctx, 'bf_rank_map_held_launches_total') or 0:.0f}"
          f" of bf_rank_map_launches_total "
          f"{common.counter(ctx, 'bf_rank_map_launches_total') or 0:.0f}")
    return regime.per_step_ms(ctx, held + waits)
