"""``moe_device_ms`` (layer ``parallel.moe``): self time per step of the
gradient program's device operations under ``bf.moe`` (forward, remat
recompute and transpose of the dropless expert layer), free stretch, first
chip.  The line it prints splits it by inner scope; ``unattributed`` is what
runs under ``bf.moe`` and under none of the four."""

from benchmark import spec


def read(ctx):
    common = spec.load_module("layer_metrics/moe_common.py")
    by_scope = common.scope_ms(ctx)
    if by_scope is None:
        return None
    print("  moe_device_ms: the expert layer by scope, ms a step: "
          + ", ".join(
              f"{'unattributed' if s == common.UNATTRIBUTED else s} {ms:.3f}"
              for s, ms in sorted(by_scope.items(), key=lambda kv: -kv[1]))
          + f"; sum {sum(by_scope.values()):.3f}")
    return sum(by_scope.values())
