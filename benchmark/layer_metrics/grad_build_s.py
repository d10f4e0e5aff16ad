"""``grad_build_s`` (layer ``models``, host, set-up): seconds the gradient
program took to trace, lower and compile, every time it did: the sum over
``stage`` of ``bf_program_build_seconds_sum{program=P}``, ``P`` the program
whose executions (``jit_bf_rank_map_<fn>``) fill the free stretch.  The
lines it prints give each stage with its count (a count above the calls
that should compile is a recompile), the cache's hits and misses, and the
same for every other program of the process: the account of ``setup_s``."""

import re

from benchmark import spec


def read(ctx):
    common = spec.load_module("layer_metrics/program_common.py")
    setup = spec.load_module("layer_metrics/setup_common.py")
    builds = setup.builds(ctx)
    runs = common.executions(ctx, common.GRAD_PROGRAM)
    if not builds or not runs:
        return None
    # a module's name is its function's with every other character as "_"
    named = {"jit_" + re.sub(r"\W", "_", p): p for p in builds}
    program = named.get(runs[0].name)
    if program is None:
        return None
    value = setup.seconds(builds[program])
    print(f"  grad_build_s: {program} {setup.stage_line(builds[program])}; "
          f"{setup.cache_line(ctx)}")
    for other in sorted(set(builds) - {program}):
        print(f"    {other}: {setup.seconds(builds[other]):.3f}s = "
              f"{setup.stage_line(builds[other])}")
    print(f"    all programs: "
          f"{sum(setup.seconds(b) for b in builds.values()):.3f}s")
    return value
