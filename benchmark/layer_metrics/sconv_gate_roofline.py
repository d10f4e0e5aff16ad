"""``sconv_gate_roofline`` (layer ``models``): the least time the chip's
peaks allow for the gates and the convolutions of a step (every conv layer's
forward, remat recompute and transpose at the bytes ``flops_lfm2.sconv_gate``
says they must move: memory-bound), over the self time of the gradient
program's device operations under ``bf.sconv.conv``, in percent; free
stretch, first chip."""

from benchmark import spec


def read(ctx):
    common = spec.load_module("layer_metrics/lfm2_common.py")
    taken_ms = common.grad_scope_ms(ctx).get("bf.sconv.conv")
    if not taken_ms:
        return None
    least_ms = common.sconv_gate_least_s(ctx) * 1e3
    print(f"  sconv_gate_roofline: least {least_ms:.3f} ms a step "
          f"(memory-bound), {taken_ms:.3f} ms taken under bf.sconv.conv")
    return 100.0 * least_ms / taken_ms
