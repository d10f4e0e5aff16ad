"""``ssm_scan_roofline`` (layer ``ops.ssd``): the least time the chip's
peaks allow for a step's chunked scans (every Mamba-2 block's forward, remat
recompute and transpose, each one chunked pass at the operations and bytes
``flops_twotower.ssd_scan`` says it needs), over the self time of the
gradient program's device operations under ``bf.ssm.scan``, in percent;
free stretch, first chip.  The scan is no kernel, so the scope is what is
held; the reader prints which bound sets a pass."""

from benchmark import spec


def read(ctx):
    common = spec.load_module("layer_metrics/twotower_common.py")
    taken_ms = common.grad_scope_ms(ctx).get("bf.ssm.scan")
    if not taken_ms:
        return None
    seconds, bound = common.scan_least_s(ctx)
    print(f"  ssm_scan_roofline: least {seconds * 1e3:.3f} ms a step "
          f"({bound}-bound), {taken_ms:.3f} ms taken under bf.ssm.scan")
    return 100.0 * seconds * 1e3 / taken_ms
