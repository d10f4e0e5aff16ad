"""``kernel_stagings`` (layer ``ops.flash_attention``, host, set-up): times
the Python wrapper of a Pallas kernel ran in this process, the sum over
``kernel`` of ``bf_kernel_stagings_total``: each is one Mosaic lowering in
the ``lower`` stage of the program around it.  A bare kernel is staged at
every call site and again in every retrace, one behind ``jax.jit`` once a
shape.  Prints the count by kernel beside the Mosaic instructions the two
compiled programs hold (``ctx.mosaic_calls``, by the kernel's name)."""

import collections

from benchmark import spec


def read(ctx):
    setup = spec.load_module("layer_metrics/setup_common.py")
    staged = setup.by_label(ctx, "bf_kernel_stagings_total")
    if not staged:
        return None
    compiled = collections.Counter(
        name.split(".")[0] for name in ctx.mosaic_calls)
    print("  kernel_stagings: " + ", ".join(
        f"{kernel} {int(n)} (compiled {compiled.get(kernel, 0)})"
        for kernel, n in sorted(staged.items()))
        + f"; {sum(compiled.values())} Mosaic instructions in the two "
        f"compiled programs")
    return sum(staged.values())
