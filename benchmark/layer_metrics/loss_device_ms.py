"""``loss_device_ms`` (layer ``models``, ``ops/chunked_loss.py``): self time
per step of the gradient program's device operations under
``bf.loss.chunked``: forward, remat recompute and transpose carry the scope
alike; free stretch, first chip."""

from benchmark import spec


def read(ctx):
    common = spec.load_module("layer_metrics/program_common.py")
    return common.scope_device_ms(ctx, common.GRAD_PROGRAM,
                                  "bf.loss.chunked")
