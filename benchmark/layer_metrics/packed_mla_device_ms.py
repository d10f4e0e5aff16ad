"""``packed_mla_device_ms`` (layer ``models``): self time per step of the
gradient program's device operations under ``bf.mla.*`` where latent
attention has no query bottleneck and runs on a packed row (forward, remat
recompute and transpose of the one query matrix to 32 heads of 192, the
latent of 512 with its rotary key and its expansion, the rotary part over
positions that restart at each document, the key's assembly with the
document-masked flash kernels, the output projection from 4096), free
stretch, first chip.  The line it prints gives the five parts, and a second
one the masked kernels' own time inside ``bf.mla.attend``.  None where the
program has no ``bf.mla.*`` scope."""

from benchmark import spec


def read(ctx):
    common = spec.load_module("layer_metrics/kanana_common.py")
    total = common.parts_ms(ctx, "packed_mla_device_ms", common.MLA)
    events = common.flash_events(ctx)
    if total is not None and events:
        taken = {kind: sum(e.duration for e, k in events if k == kind) * 1e-6
                 / max(ctx.free_steps, 1) for kind in ("fwd", "dq", "dkv")}
        print("  packed_mla_device_ms: the masked kernels inside "
              "bf.mla.attend, ms a step: " + ", ".join(
                  f"bf_flash_seg_{kind} {ms:.3f}"
                  for kind, ms in taken.items())
              + f"; sum {sum(taken.values()):.3f}")
    return total
