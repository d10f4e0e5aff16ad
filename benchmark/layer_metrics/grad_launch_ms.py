"""``grad_launch_ms`` (layer ``models``, host): median length of the span
``bf.rank_map.launch`` in the free stretch: the call of the jitted gradient
program."""

from benchmark import spec


def read(ctx):
    return spec.load_module("layer_metrics/program_common.py").span_median_ms(
        ctx, "bf.rank_map.launch")
