"""``host_lead_ms`` (layer ``device``): how long a launched optimizer program
waited in the device's queue: the median over the steps of the free stretch
of (start of the k-th ``jit_bf_optim_step`` execution on the first chip -
end of the k-th ``bf.optim.launch`` on the host).  The k-th launch of the
trace is the k-th execution (the warm-up ends with a wait, and ``step=`` on
the span counts the launches).  Near 0 the host sets the pace; the device's
clock is only aligned to the host's within about a millisecond."""

import statistics

from benchmark import spec


def read(ctx):
    common = spec.load_module("layer_metrics/program_common.py")
    launches = [s for s in common.program(ctx).spans
                if s.name == "bf.optim.launch"]
    runs = common.executions(ctx, common.STEP_PROGRAM, free_only=False)
    if not launches or len(launches) != len(runs) or ctx.free is None:
        return None
    leads = [run.start - launch.end for launch, run in zip(launches, runs)
             if ctx.free.start <= launch.start and launch.end <= ctx.free.end]
    return statistics.median(leads) * 1e-6 if leads else None
