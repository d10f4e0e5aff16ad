"""``expert_window_overflow_device_ms`` (layer ``parallel.moe``): self time
a step of the gradient program's device operations that carry the overflow
branch's marker (``bf_moe_held_overflow``: the windows' gathers, products
and sums of a held run longer than its window, forward, remat recompute and
transpose), free stretch, first chip.  The line it prints gives the window
branch's beside it.  0.0 where the program marks its branches and none
overflowed, or holds no windowed share."""

from benchmark import spec


def read(ctx):
    regime = spec.load_module("layer_metrics/regime_common.py")
    if not regime.instrumented(ctx):
        return None
    ms = regime.branch_ms(ctx) or {"overflow": 0.0, "window": 0.0}
    print(f"  expert_window_overflow_device_ms: ms a step under the "
          f"overflow branch {ms['overflow']:.3f}, under the window branch "
          f"{ms['window']:.3f}")
    return ms["overflow"]
