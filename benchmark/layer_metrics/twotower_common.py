"""What the five readers of the ``twotower-s8192-1chip`` cell share: the
gradient program's device time under the scopes of a Mamba-2 mixer
(``bf.ssm.*``), of plain attention (``bf.attn.*``) and of a held share of
un-gated experts with a shared expert (``bf.moe*``), and the cost of the
scan and of the kernel calls at this configuration's shapes
(``benchmark/flops_twotower.py``).

The scopes are those of ``models/transformer.py`` (``Mamba2Mixer``:
``bf.ssm.in``, ``bf.ssm.conv``, ``bf.ssm.scan``, ``bf.ssm.norm``,
``bf.ssm.out``; ``Block``'s plain attention branch: ``bf.attn.qkv``,
``bf.attn.attend``, ``bf.attn.out``, and ``norm`` / ``rope`` where a model
has them: this one has neither) and of ``parallel/moe.py`` (the four of
``moe_common.py`` and ``bf.moe.shared``); forward, remat recompute and
transpose carry the names alike.  ``program_common.py`` assigns each device
operation of the gradient program to a scope, ``moe_common.py`` tells the
bare ``bf.moe`` apart and ``xing_common.py`` keeps the reduction on the
context; none is edited.  A program without these scopes (the parent of
PR 42) yields None everywhere.

The scan is no kernel: its time is the self time under ``bf.ssm.scan``, held
to ``flops_twotower.ssd_scan`` (a Mamba-2 block's forward, its remat
recompute and its transpose, each one chunked pass).  The causal flash
kernels are told apart by the names the library gives them
(``bf_flash_fwd / dq / dkv.<n>``) and held to ``flops_twotower.flash_kernel``
at 32 heads of 128 (the 2 K/V heads are repeated before the kernel).  The
grouped products (``bf_moe_gmm_*``) are held, at the rows an even router
sends to the experts held here (384 an expert at 8192 tokens), two products
a pass, to what ``flops_twotower.grouped_product`` gives: the configuration
names its sizes with ``xing4.0-29b-a4b``'s keys (``n_routed_experts`` held of
``router_width``), so ``xing_common.product_cost`` reads an event's kind and
shape and arrives at the same cost (the selftest holds the two equal).  Off
the TPU (the rehearsal) the kernels run in the Pallas interpreter and no
event is a kernel call.
"""

from __future__ import annotations

from benchmark import flops, flops_twotower, spec

SSM = ("bf.ssm.in", "bf.ssm.conv", "bf.ssm.scan", "bf.ssm.norm",
       "bf.ssm.out")
ATTN = ("bf.attn.qkv", "bf.attn.norm", "bf.attn.rope", "bf.attn.attend",
        "bf.attn.out")

_xing = spec.load_module("layer_metrics/xing_common.py")
_moe = spec.load_module("layer_metrics/moe_common.py")
# the reduction by scope, kept on the context; the expert layer's parts with
# the shared expert; the kernels' events
grad_scope_ms, parts_ms = _xing.grad_scope_ms, _xing.parts_ms
moe_parts_ms, flash_events = _xing.moe_parts_ms, _xing.flash_events
product_events, product_cost = _moe.product_events, _xing.product_cost


def scan_least_s(ctx) -> tuple:
    """``(seconds, bound)``: the least a step's scans can take, every
    Mamba-2 block's forward, its remat recompute where the model recomputes
    its blocks, and its transpose, each a chunked pass at its operations and
    bytes; ``bound`` names what sets the forward pass."""
    config, batch = ctx.cell.config, ctx.cell.traffic["batch"]
    passes = ["fwd", "bwd"] + (
        ["fwd"] if config["model"]["args"].get("remat") else [])
    least = [flops.roofline_seconds(flops_twotower.ssd_scan(
        kind, config=config, tokens=batch["seq_len"]), ctx.peaks)
        for kind in passes]
    return (flops_twotower.blocks(config, "M") * batch["sequences"]
            * sum(seconds for seconds, _ in least), least[0][1])


def flash_share(ctx, label: str):
    """``(percent, taken_ms a step)`` of the causal flash kernel calls: the
    least time the chip's peaks allow for them over the time they took, and
    a printed line by kind; None without such calls."""
    events = flash_events(ctx)
    if not events:
        return None
    batch = ctx.cell.traffic["batch"]
    least = {kind: flops.roofline_seconds(flops_twotower.flash_kernel(
        kind, config=ctx.cell.config, batch=batch["sequences"],
        seq=batch["seq_len"]), ctx.peaks) for kind in {k for _, k in events}}
    taken = {kind: sum(e.duration for e, k in events if k == kind) * 1e-9
             for kind in least}
    print(f"  {label}: " + "; ".join(
        f"{kind} {sum(1 for _, k in events if k == kind)} calls, least "
        f"{seconds * 1e3:.3f} ms each ({bound}-bound), "
        f"{taken[kind] * 1e3:.3f} ms taken"
        for kind, (seconds, bound) in sorted(least.items())))
    return (100.0 * sum(least[k][0] for _, k in events)
            / sum(taken.values()),
            sum(taken.values()) * 1e3 / max(ctx.free_steps, 1))
