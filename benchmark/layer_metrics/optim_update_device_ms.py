"""``optim_update_device_ms`` (layer ``optim``): self time per step of the
optimizer program's device operations under the scope ``bf.optim.update``
(``base.update`` + ``optax.apply_updates``), free stretch, first chip.  The
line it prints splits the whole program by scope; ``unattributed`` is what no
rule of ``program_common.py`` found a scope for."""

from benchmark import spec


def read(ctx):
    common = spec.load_module("layer_metrics/program_common.py")
    by_scope = common.scope_ms(ctx, common.STEP_PROGRAM)
    if not by_scope or "bf.optim.update" not in by_scope:
        return None
    print("  optim_update_device_ms: the optimizer program by scope, ms a "
          "step: " + ", ".join(
              f"{scope or 'unattributed'} {ms:.3f}" for scope, ms in sorted(
                  by_scope.items(), key=lambda kv: -kv[1]))
          + f"; sum {sum(by_scope.values()):.3f}")
    return by_scope["bf.optim.update"]
