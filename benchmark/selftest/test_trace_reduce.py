"""``benchmark/trace_reduce.py`` on hand-made events and on a trimmed trace
recorded on the chip (``trace_v5e.json``: PR 22, one v5e host, the
``lm-s4096-gossip-4chip`` cell, chips 0 and 1, one blocked step and two free
steps).  Runs on the CPU: the reduction is arithmetic on intervals.

    python3 -m pytest benchmark/selftest/test_trace_reduce.py -q
"""

import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import trace_reduce as tr  # noqa: E402
from benchmark.trace_reduce import Event as E  # noqa: E402

FIXTURE = os.path.join(ROOT, "benchmark", "selftest", "trace_v5e.json")

# One chip: a while loop (0-100) around a fusion, an asynchronous
# collective-permute whose transfer a second fusion hides in part, a third
# fusion; then a gap and a lone copy.
OPS = [E("while.1", 0, 100), E("fusion.1", 10, 30),
       E("collective-permute-start.1", 30, 32), E("fusion.2", 32, 50),
       E("collective-permute-done.1", 50, 70), E("fusion.3", 70, 100),
       E("copy.1", 120, 130)]


def test_intervals():
    assert tr.union([(5, 7), (0, 2), (1, 3), (7, 9), (4, 4)]) == [
        (0, 3), (5, 9)]
    assert tr.length([(0, 3), (5, 9)]) == 7
    assert tr.clip([(0, 3), (5, 9)], 2, 6) == [(2, 3), (5, 6)]
    assert tr.subtract([(0, 10), (20, 30)], [(2, 4), (8, 22), (29, 40)]) == [
        (0, 2), (4, 8), (22, 29)]


def test_busy_is_a_union_and_sums_use_self_time():
    assert tr.busy(OPS, 0, 200) == 110            # 0-100 and 120-130
    assert tr.busy(OPS, 90, 125) == 15
    assert [e.name for e in tr.leaves(OPS)] == [
        "fusion.1", "collective-permute-start.1", "fusion.2",
        "collective-permute-done.1", "fusion.3", "copy.1"]
    selfs = {e.name: t for e, t in tr.self_times(OPS)}
    assert selfs["while.1"] == 10                 # 100 less its 90 of body
    assert sum(selfs.values()) == tr.busy(OPS, 0, 200)
    top = tr.top_by_name(OPS, 2)
    assert [name for name, _ in top] == ["fusion.3", "fusion.1"]
    assert [t for _, t in top] == pytest.approx([30e-9, 20e-9])


def test_collective_time_and_the_exposed_part():
    assert tr.async_intervals(OPS, "collective-permute") == [(30, 70)]
    # of 30-70, fusion.2 covers 32-50: start (2) and the wait in done (20)
    assert tr.exposed(OPS, "collective-permute") == 22
    sync = [E("collective-permute.4", 0, 10), E("fusion.9", 10, 20)]
    assert tr.async_intervals(sync, "collective-permute") == [(0, 10)]
    assert tr.exposed(sync, "collective-permute") == 10


def test_gaps_are_named_by_the_span_the_host_was_in():
    gaps = tr.idle_gaps(OPS, 0, 200)
    assert gaps == [(100, 120), (130, 200)]
    spans = [E("bench.grad", 95, 125), E("bench.group_sync", 126, 190)]
    assert tr.label_gaps(gaps, spans) == [
        ["bench.group_sync", 70e-9], ["bench.grad", 20e-9]]
    assert tr.label_gaps([(300, 310)], spans) == [["outside", 10e-9]]


def test_device_time_inside_spans():
    trace = tr.Trace({0: OPS}, [E("bench.blocked", 0, 140),
                                E("bench.grad", 0, 60),
                                E("bench.grad", 60, 125)])
    blocked = trace.stretch("blocked")
    grads = trace.spans_named("bench.grad", inside=blocked)
    assert len(grads) == 2
    assert trace.device_ns_in(0, grads) == 60 + 40 + 5
    assert trace.stretch("free") is None


def test_device_event_names():
    e = tr._device_event(
        "%fusion.3 = bf16[8,128]{1,0:T(8,128)(2,1)} fusion(bf16[8,128]{1,0} "
        "%p), kind=kLoop", 5.0, 2.0)
    assert (e.name, e.start, e.end) == ("fusion.3", 5.0, 7.0)
    assert e.what == "bf16[8,128] fusion(bf16[8,128] %p), kind=kLoop"
    assert tr._device_event("ThunkExecutor::Execute", 0, 1).name == \
        "ThunkExecutor::Execute"


# --- the trace recorded on the chip -------------------------------------------

# The gradient program's Mosaic kernel instructions as its HLO names them
# (forward twice a layer under remat, then the two backward kernels).
HLO = "\n".join(
    f'  %{name} = {result} custom-call({", ".join(f"%p{i}" for i in range(n))}'
    f'), custom_call_target="tpu_custom_call", operand_layout_constraints={{}}, '
    f'metadata={{op_name="jit(run)/shard_map/jvp(TransformerLM)/{name[:7]}/'
    f'pallas_call" stack_frame_id=4}}'
    for name, n, result in [
        ("block_0.4", 3, "(bf16[32,4096,128]{2,1,0}, f32[32,4096,1]{2,1,0})"),
        ("block_0.5", 3, "(bf16[32,4096,128]{2,1,0}, f32[32,4096,1]{2,1,0})"),
        ("block_0.6", 6, "(bf16[32,4096,128]{2,1,0}, bf16[32,4096,128]{2,1,0})"),
        ("block_0.7", 6, "bf16[32,4096,128]{2,1,0}"),
        ("block_1.4", 3, "(bf16[32,4096,128]{2,1,0}, f32[32,4096,1]{2,1,0})"),
        ("block_1.5", 3, "(bf16[32,4096,128]{2,1,0}, f32[32,4096,1]{2,1,0})"),
        ("block_1.6", 6, "(bf16[32,4096,128]{2,1,0}, bf16[32,4096,128]{2,1,0})"),
        ("block_1.7", 6, "bf16[32,4096,128]{2,1,0}")])


class Program:
    def as_text(self):
        return HLO


@pytest.fixture(scope="module")
def ctx():
    from benchmark import layers, spec
    cell = spec.load_cell("lm-s4096-gossip-4chip")
    return layers.context(
        tr.Trace.from_json(FIXTURE), cell, spec.task_module(cell),
        spec.peak_row("TPU v5 lite"), {"grad": Program()})


def read(name, ctx):
    from benchmark import spec
    return spec.layer_metric_reader(name)(ctx)


def test_recorded_trace_has_the_benchmarks_structure(ctx):
    trace = ctx.trace
    assert trace.chips() == [0, 1]
    assert ctx.blocked is not None and ctx.free is not None
    assert ctx.free_steps == 5
    assert len(trace.spans_named("bench.grad", inside=ctx.blocked)) == 1
    assert len(trace.spans_named("bench.group_sync", inside=ctx.free)) == 1
    # every device event of the blocked step lies inside the gradient's span
    # or between the optimizer's dispatch and the end of its wait: the host
    # and the device are on one clock
    grad, = trace.spans_named("bench.grad", inside=ctx.blocked)
    dispatch, = trace.spans_named("bench.optim_dispatch", inside=ctx.blocked)
    wait, = trace.spans_named("bench.optim_wait", inside=ctx.blocked)
    inside = tr.within(trace.ops[0], ctx.blocked.start, ctx.blocked.end)
    assert inside and all(
        (grad.start <= e.start and e.end <= grad.end)
        or (dispatch.start <= e.start and e.end <= wait.end) for e in inside)
    assert set(ctx.mosaic_calls) == {f"block_{i}.{j}" for i in (0, 1)
                                     for j in (4, 5, 6, 7)}
    assert ctx.mosaic_calls["block_0.6"] == {
        "operands": 6, "results": 2,
        "op_name": "jit(run)/shard_map/jvp(TransformerLM)/block_0/"
                   "pallas_call"}


def test_per_layer_metrics_of_the_recorded_trace(ctx):
    """Pinned to what the reduction gave when the trace was trimmed (PR 22);
    the relations between them are what the definitions require."""
    got = {name: read(name, ctx) for name in (
        "grad_device_ms", "optim_device_ms", "optim_dispatch_ms",
        "gossip_device_ms", "gossip_exposed_ms", "flash_kernel_ms",
        "flash_roofline", "device_idle_share", "mfu_busy")}
    assert got == pytest.approx({
        "grad_device_ms": 147.485629, "optim_device_ms": 96.331134,
        "optim_dispatch_ms": 5.10055, "gossip_device_ms": 43.2833848,
        "gossip_exposed_ms": 43.2833848, "flash_kernel_ms": 12.9437472,
        "flash_roofline": 59.30376856, "device_idle_share": 0.393071556,
        "mfu_busy": 33.98129945}, rel=1e-8)
    # nothing hides the exchange: the done waits with no other op running
    assert got["gossip_exposed_ms"] <= got["gossip_device_ms"]
    assert got["gossip_device_ms"] < got["optim_device_ms"]
    # the kernels' time is the sum of the named events, five steps' worth
    free = tr.within(ctx.trace.ops[0], ctx.free.start, ctx.free.end)
    named = sum(e.duration for e in free if e.name in ctx.mosaic_calls)
    assert got["flash_kernel_ms"] == pytest.approx(named / 5 * 1e-6)
    # busy and idle make up the window, on both chips
    for chip in (0, 1):
        ops = ctx.trace.ops[chip]
        gaps = tr.idle_gaps(ops, ctx.free.start, ctx.free.end)
        assert tr.busy(ops, ctx.free.start, ctx.free.end) + tr.length(gaps) \
            == pytest.approx(ctx.free.duration)
    assert ctx.busy_s < ctx.window_s


def test_breakdown_of_the_recorded_trace(ctx):
    from benchmark import layers
    out = layers.breakdown(ctx)
    assert len(out["device_ops"]) == 10 and len(out["idle_gaps"]) == 10
    assert out["device_ops"][0][0] == "collective-permute-done.1"
    assert out["idle_gaps"][0][0] == "bench.group_sync"
    times = [t for _, t in out["device_ops"]]
    assert times == sorted(times, reverse=True)
