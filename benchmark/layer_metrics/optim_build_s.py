"""``optim_build_s`` (layer ``optim``, host, set-up): seconds the optimizer's
programs took to trace, lower and compile: ``bf_optim_step`` (every phase's
program is called that) and ``bf_optim_init``, summed over ``stage`` of
``bf_program_build_seconds_sum``.  Prints the stages with their counts, and
the ``jax.jit`` objects ``bf_step_program_builds_total{program="optim_step"}``
counted beside the compiles."""

from benchmark import spec


def read(ctx):
    setup = spec.load_module("layer_metrics/setup_common.py")
    builds = setup.builds(ctx)
    mine = {p: builds[p] for p in ("bf_optim_step", "bf_optim_init")
            if p in builds}
    if not mine:
        return None
    objects = setup.by_label(ctx, "bf_step_program_builds_total").get(
        "optim_step", 0)
    compiles = builds.get("bf_optim_step", {}).get("compile", (0.0, 0))[1]
    for program, stages in mine.items():
        print(f"  optim_build_s: {program} {setup.stage_line(stages)}")
    print(f"    {int(objects)} step program object(s) built "
          f"(bf_step_program_builds_total) beside {compiles} compile(s) of "
          f"bf_optim_step")
    return sum(setup.seconds(stages) for stages in mine.values())
