"""``packed_flash_roofline`` (layer ``ops.flash_attention``): the least time
the chip's peaks allow for the document-masked flash kernel calls
(``bf_flash_seg_fwd / dq / dkv``) at query-key heads of 192 and value heads
of 128, over the time they took, in percent.  Each call is held to the
operations of the **visible pairs** of the traffic's documents and to the
bytes of its operands and results (``flops_kanana.flash_kernel``); a
recomputed forward counts as a call.  A kernel that masked every tile of
the triangle would read the visible share of the triangle (21.5% at the
cell's documents) of what one that skips reads.  The reader prints which
bound sets each kind, the tiles whose liveness the device decides
(``bf_flash_tiles_total{kind="by_data"}``), the layout's dead, crossed and
inside tiles by the library's host function, and the ceiling that whole
256 x 256 chunks leave."""

from benchmark import flops, spec


def read(ctx):
    common = spec.load_module("layer_metrics/kanana_common.py")
    program = spec.load_module("layer_metrics/program_common.py")
    events = common.flash_events(ctx)
    if not events:
        return None
    documents = ctx.cell.traffic["batch"]["documents"]
    least = {kind: flops.roofline_seconds(common.flash_cost(ctx, kind),
                                          ctx.peaks)
             for kind in {k for _, k in events}}
    taken = {kind: sum(e.duration for e, k in events if k == kind) * 1e-9
             for kind in least}
    print("  packed_flash_roofline: " + "; ".join(
        f"{kind} {sum(1 for _, k in events if k == kind)} calls, least "
        f"{seconds * 1e3:.3f} ms each ({bound}-bound), "
        f"{taken[kind] * 1e3:.3f} ms taken"
        for kind, (seconds, bound) in sorted(least.items())))
    by_data = {kind: program.counter(
        ctx, "bf_flash_tiles_total", kernel=f"bf_flash_seg_{kind}",
        kind="by_data") for kind in ("fwd", "dq", "dkv")}
    tiles = {blocks: common.layout_tiles(documents, *blocks)
             for blocks in ((1024, 1024), (1024, 512))}
    print(f"  packed_flash_roofline: bf_flash_tiles_total by_data {by_data} "
          "(every staging's tiles at or under the diagonal); a head's "
          f"tiles of this layout at 1024 x 1024 {tiles[1024, 1024]}, at "
          f"1024 x 512 {tiles[1024, 512]}; whole chunks of {common.CHUNK} x "
          f"{common.CHUNK} that hold a visible pair leave at most "
          f"{100 * common.chunk_ceiling(documents):.1f}% of the roofline")
    return 100.0 * sum(least[k][0] for _, k in events) / sum(taken.values())
