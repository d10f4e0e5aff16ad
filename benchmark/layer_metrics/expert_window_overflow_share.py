"""``expert_window_overflow_share`` (layer ``parallel.moe``): of the held
share's conditionals executed in the free stretch on the first chip (one an
expert layer and pass: forward, remat recompute, transpose), the percentage
that took the overflow branch: the held run was longer than the window and
was covered window after window.  A conditional is one device event around
the operations of the branch it took, and the branch is told by the marker
(``bf_moe_held_overflow`` / ``bf_moe_held_window``) that the live gradient
program's text gives those operations (``regime_common.py``).  The line it
prints gives the count by pass and conditional.  0.0 where the program
marks its branches and none overflowed, or holds no windowed share."""

from benchmark import spec


def read(ctx):
    regime = spec.load_module("layer_metrics/regime_common.py")
    if not regime.instrumented(ctx):
        return None
    found = regime.conditionals(ctx)
    over = sum(branch == "overflow" for *_, branch in found)
    print(f"  expert_window_overflow_share: {over} of {len(found)} "
          f"conditionals of a held share took the overflow branch in "
          f"{ctx.free_steps} steps; overflow / all by pass and "
          f"conditional: {regime.by_conditional(found) or 'none executed'}")
    return 100.0 * over / len(found) if found else 0.0
