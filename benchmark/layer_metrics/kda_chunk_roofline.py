"""``kda_chunk_roofline`` (layer ``ops.kda``): the least time the chip's
peaks allow for a step's chunked delta rules (every KDA mixer's forward,
remat recompute and transpose, each one chunked pass at the operations and
bytes ``flops_ling.kda_chunk`` says it needs), over the self time of the
gradient program's device operations under ``bf.kda.chunk``, in percent;
free stretch, first chip.  The rule is no kernel, so the scope is what is
held; the reader prints which bound sets a pass."""

from benchmark import spec


def read(ctx):
    common = spec.load_module("layer_metrics/ling_common.py")
    taken_ms = common.grad_scope_ms(ctx).get("bf.kda.chunk")
    if not taken_ms:
        return None
    seconds, bound = common.chunk_least_s(ctx)
    print(f"  kda_chunk_roofline: least {seconds * 1e3:.3f} ms a step "
          f"({bound}-bound), {taken_ms:.3f} ms taken under bf.kda.chunk")
    return 100.0 * seconds * 1e3 / taken_ms
