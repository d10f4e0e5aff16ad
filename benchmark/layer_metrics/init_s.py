"""``init_s`` (layer ``basics``, host, set-up): the sum of the gauge
``bf_startup_seconds{part}``: ``import bluefog_tpu``, ``bf.init()`` (devices
and meshes; topology and schedules) and ``opt.init()`` (its program's build
included).  Prints each part, and the build seconds inside ``optim_init``
that ``optim_build_s`` counts as well."""

from benchmark import spec


def read(ctx):
    setup = spec.load_module("layer_metrics/setup_common.py")
    parts = setup.by_label(ctx, "bf_startup_seconds")
    if not parts:
        return None
    inside = setup.seconds(setup.builds(ctx).get("bf_optim_init", {}))
    print("  init_s: " + ", ".join(
        f"{part} {value:.4f}s" for part, value in sorted(parts.items()))
        + f"; optim_init holds the build of bf_optim_init, {inside:.4f}s, "
        f"which optim_build_s counts too")
    return sum(parts.values())
