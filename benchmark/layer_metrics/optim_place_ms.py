"""``optim_place_ms`` (layer ``optim``, host): median length of the span
``bf.optim.place`` in the free stretch: ``opt.step()`` handing every leaf of
the parameter and gradient trees to ``jax.device_put``."""

from benchmark import spec


def read(ctx):
    return spec.load_module("layer_metrics/program_common.py").span_median_ms(
        ctx, "bf.optim.place")
