"""``swa_flash_roofline`` (layer ``ops.flash_attention``): the least time
the chip's peaks allow for the windowed flash kernel calls
(``bf_flash_win_fwd / dq / dkv``) over the time they took, in percent.  Each
call is held to the operations of the pairs its window shows (``S x W``,
never the triangle: ``benchmark/flops_laguna.py``) and to the bytes of its
own operands and results; a recomputed forward counts as a call.  The reader
prints which bound sets each kind, and the tiles the grids compute against
those a perfect skip would."""

from benchmark import spec


def read(ctx):
    common = spec.load_module("layer_metrics/laguna_common.py")
    share = common.flash_share(ctx, "sliding_attention", "swa_flash_roofline")
    if share is None:
        return None
    tiles = common.window_tiles(ctx)
    if tiles:
        print(f"  swa_flash_roofline: {tiles}")
    return share[0]
