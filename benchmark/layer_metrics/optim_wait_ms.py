"""``optim_wait_ms`` (layer ``optim``, host): median length of the span
``bf.optim.wait`` in the free stretch: how long ``opt.step()`` stood still
until the step before the one it launched was over.  About a device step
less the host's own work where the device sets the pace and the host is
held here; near 0 where the runtime holds the gradient launch instead
(``grad_hold_ms`` has the step then).  The lines it prints give the
quartiles and the count, the histogram ``bf_optim_wait_seconds`` of the
registry (every step since ``bf.init()``), and the host's whole step as
means by span beside the device's (``regime_common.host_account``)."""

import statistics

from benchmark import spec


def read(ctx):
    regime = spec.load_module("layer_metrics/regime_common.py")
    if not regime.instrumented(ctx):
        return None
    common = spec.load_module("layer_metrics/program_common.py")
    waits = common.spans_in_free(ctx, regime.OPTIM_WAIT)
    print(f"  optim_wait_ms: {len(waits)} waits in {ctx.free_steps} steps, "
          f"q1 / median / q3 ms {regime.quartiles_ms(waits)}, mean a step "
          f"{regime.per_step_ms(ctx, waits):.3f}; bf_optim_wait_seconds "
          f"count {common.counter(ctx, 'bf_optim_wait_seconds_count')} sum "
          f"{common.counter(ctx, 'bf_optim_wait_seconds_sum')}")
    regime.host_account(ctx)
    return statistics.median(s.duration for s in waits) * 1e-6 if waits \
        else 0.0
