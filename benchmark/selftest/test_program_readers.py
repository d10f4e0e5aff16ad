"""The readers of the program's own names, spans and counters
(``layer_metrics/program_common.py`` and the twelve metrics of PR 23) on
hand-made events, on a trimmed trace recorded on the chip
(``trace_v5e_program.json``: PR 23, one v5e host, the
``lm-s4096-gossip-4chip`` cell, chip 0, the last blocked step and the first
two free steps, with the device's module line, the ``bf.*`` host spans, the
scopes of the two programs' instructions and the counters) and on the traced
twins.  The expected values of the fixture were summed by hand from its rows
(a few lines of plain Python over the JSON, not the code under test).

    python3 -m pytest benchmark/selftest/test_program_readers.py -q
"""

import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import layers, spec  # noqa: E402
from benchmark import trace_reduce as tr  # noqa: E402
from benchmark.trace_reduce import Event as E  # noqa: E402

common = spec.load_module("layer_metrics/program_common.py")
Span = common.Span
FIXTURE = os.path.join(ROOT, "benchmark", "selftest",
                       "trace_v5e_program.json")
NEW = ["grad_program_device_ms", "optim_program_device_ms",
       "optim_update_device_ms", "optim_fuse_device_ms",
       "optim_combine_device_ms", "loss_device_ms", "optim_place_ms",
       "optim_launch_ms", "grad_launch_ms", "host_lead_ms", "input_wait_ms",
       "gossip_gb_per_step"]


def read(name, ctx):
    return spec.layer_metric_reader(name)(ctx)


def context(trace, program, steps, cell="lm-s4096-gossip-4chip"):
    ctx = layers.Context(
        trace=trace, cell=spec.load_cell(cell), peaks={}, step_flops={},
        chip=0, blocked=trace.stretch("blocked"), free=trace.stretch("free"),
        free_steps=steps, busy_s=0.0, window_s=0.0, mosaic_calls={})
    ctx.program = program
    return ctx


# --- hand-made -----------------------------------------------------------------

HLO = '''HloModule jit_bf_optim_step, is_scheduled=true

%fused_computation.1 (p: f32[8]) -> f32[8] {
  %p = f32[8]{0} parameter(0)
  %c = f32[] constant(2), metadata={op_name="jit(bf_optim_step)/shard_map"}
  ROOT %mul.1 = f32[8]{0} multiply(%p, %p), metadata={op_name="jit(bf_optim_step)/shard_map/bf.optim.update/mul"}
}

%fused_computation.2 (p: f32[8]) -> f32[8] {
  %p.2 = f32[8]{0} parameter(0)
  ROOT %dus = f32[8]{0} dynamic-update-slice(%p.2, %p.2)
}

%branch (q: f32[8]) -> f32[8] {
  %q = f32[8]{0} parameter(0)
  %collective-permute-start = (f32[8]{0}, f32[8]{0}) collective-permute-start(%q), metadata={op_name="jit(bf_optim_step)/shard_map/bf.optim.combine/cond/branch_0_fun/ppermute"}
  %collective-permute-done = f32[8]{0} collective-permute-done(%collective-permute-start), metadata={op_name="jit(bf_optim_step)/shard_map/bf.optim.combine/cond/branch_0_fun/ppermute"}
  %fusion.9 = f32[8]{0} fusion(%collective-permute-done), kind=kLoop, calls=%fused_computation.3, metadata={op_name="jit(bf_optim_step)/shard_map/bf.optim.unfuse/split"}
  ROOT %copy.7 = f32[8]{0} copy(%fusion.9)
}

ENTRY %main (a: f32[8]) -> f32[8] {
  %a = f32[8]{0} parameter(0), metadata={op_name="params['w']"}
  %update_fusion = f32[8]{0} fusion(%a), kind=kLoop, calls=%fused_computation.1
  %copy.1 = f32[8]{0} copy(%update_fusion)
  %dus_fusion.1 = f32[8]{0} fusion(%copy.1), kind=kLoop, calls=%fused_computation.2
  %dus_fusion = f32[8]{0} fusion(%dus_fusion.1), kind=kLoop, calls=%fused_computation.2, metadata={op_name="jit(bf_optim_step)/shard_map/bf.optim.fuse/concatenate"}
  %conditional.1 = f32[8]{0} conditional(%dus_fusion), branch_computations={%branch}, metadata={op_name="jit(bf_optim_step)/shard_map/bf.optim.combine/cond"}
  %lone = f32[8]{0} copy(%a)
  ROOT %out = f32[8]{0} add(%conditional.1, %lone), metadata={op_name="jit(bf_optim_step)/shard_map/add"}
}
'''


def test_scope_of_an_op_name():
    assert common.scope_of(
        "jit(f)/transpose(jvp(bf.loss.chunked))/while/body/dot_general"
    ) == "bf.loss.chunked"
    assert common.scope_of("jit(f)/bf.optim.combine/cond/bf.optim.fuse/x"
                           ) == "bf.optim.fuse"         # the innermost
    assert common.scope_of("jit(f)/shard_map/add") is None
    assert common.scope_of(None) is None


def test_an_instruction_finds_its_scope_by_the_four_rules():
    scopes = common.instruction_scopes(HLO)
    # its own metadata
    assert scopes["conditional.1"] == "bf.optim.combine"
    assert scopes["collective-permute-done"] == "bf.optim.combine"
    assert scopes["fusion.9"] == "bf.optim.unfuse"
    assert scopes["dus_fusion"] == "bf.optim.fuse"
    # none of its own: what its fused instructions name
    assert scopes["update_fusion"] == "bf.optim.update"
    # nothing inside either: the first instruction that uses its result ...
    assert scopes["dus_fusion.1"] == "bf.optim.fuse"
    assert scopes["copy.1"] == "bf.optim.fuse"          # through dus_fusion.1
    # ... and at the end of a computation, the one whose result it uses
    assert scopes["copy.7"] == "bf.optim.unfuse"
    # a neighbour with metadata that names no scope answers "none"
    assert "lone" not in scopes and "out" not in scopes
    # instructions inside a fusion are no device events
    assert "mul.1" not in scopes and "dus" not in scopes


# One chip, two steps, times in milliseconds: a gradient program (0-100,
# 200-300) and an optimizer program (100-160, 300-360) a step.  The optimizer
# program: an update fusion, a copy the compiler made for the fuse, the
# exchange inside a conditional.
def ev(name, start, end, what=""):
    return E(name, start * 1e6, end * 1e6, what)


def sp(name, start, end, thread, **args):
    return Span(name, start * 1e6, end * 1e6, thread, args)


def ops_of_a_step(t):
    return [ev("loss_fusion", t + 10, t + 40),
            ev("block_fusion", t + 40, t + 95),
            ev("update_fusion", t + 100, t + 110),
            ev("copy.1", t + 110, t + 115),
            ev("conditional.1", t + 115, t + 158),
            ev("collective-permute-start", t + 116, t + 118,
               "(f32[8], f32[8]) collective-permute-start(f32[8] %q)"),
            ev("collective-permute-done", t + 118, t + 150,
               "f32[8] collective-permute-done((f32[8], f32[8]) %s)"),
            ev("fusion.9", t + 150, t + 156), ev("copy.7", t + 156, t + 157)]


def hand_made():
    trace = tr.Trace({0: ops_of_a_step(0) + ops_of_a_step(200)},
                     [ev("bench.free", 0, 400)])
    scopes = {"jit_bf_optim_step": common.instruction_scopes(HLO),
              "jit_bf_rank_map_loss": {"loss_fusion": "bf.loss.chunked"}}
    spans, modules = [], []
    for k, t in enumerate((0, 200)):
        modules += [ev("jit_bf_rank_map_loss", t, t + 100, str(2 * k)),
                    ev("jit_bf_optim_step", t + 100, t + 160, str(2 * k + 1))]
        # the host runs ahead: both steps are launched before the first ends
        h = 20 * k
        spans += [sp("bf.rank_map.launch", h + 1, h + 3, "main"),
                  sp("bf.optim.step", h + 4, h + 18, "main", step=k),
                  sp("bf.optim.place", h + 5, h + 12, "main", leaves=2),
                  sp("bf.optim.launch", h + 13, h + 16 + k, "main", step=k),
                  sp("bf.data.wait", h + 0, h + 1, "main", batch=k),
                  sp("bf.data.place", h + 0, h + 9, "feeder", batch=k + 2)]
    counters = {'bf_comm_wire_bytes_total{op="optimizer_step"}': 4 * 32.0 * 5,
                'bf_optimizer_step_seconds_count{family="collective"}': 5.0}
    return context(trace, common.Program(spans, {0: modules}, scopes,
                                         counters), steps=2)


def test_readers_on_hand_made_events(capsys):
    ctx = hand_made()
    assert read("grad_program_device_ms", ctx) == pytest.approx(85)
    assert read("optim_program_device_ms", ctx) == pytest.approx(58)
    assert read("optim_update_device_ms", ctx) == pytest.approx(10)
    # copy.1 (5) for the fuse, fusion.9 (6) and copy.7 (1) for the unfuse
    assert read("optim_fuse_device_ms", ctx) == pytest.approx(12)
    # start 2 + done 32 + the conditional's own 43 - 41
    assert read("optim_combine_device_ms", ctx) == pytest.approx(36)
    assert "unattributed" not in capsys.readouterr().out
    assert read("loss_device_ms", ctx) == pytest.approx(30)
    assert read("optim_place_ms", ctx) == pytest.approx(7)
    assert read("optim_launch_ms", ctx) == pytest.approx(3.5)
    # a step's own time is its 14 less its children: 4 and 3
    assert ("bf.optim.step median 14.000 ms = place 7.000 + launch 3.500 "
            "+ self 3.500") in capsys.readouterr().out
    assert read("grad_launch_ms", ctx) == pytest.approx(2)
    # launches end at 16 and 37; their executions start at 100 and 300
    assert read("host_lead_ms", ctx) == pytest.approx((84 + 263) / 2)
    assert read("input_wait_ms", ctx) == pytest.approx(1)
    # 4 edges x 32 B a step over 4 chips; the permute's result is f32[8]
    assert read("gossip_gb_per_step", ctx) == pytest.approx(32e-9)
    assert "0.000000032 GB" in capsys.readouterr().out


def test_a_program_without_names_spans_or_counters_reads_as_nothing():
    """The parent of PR 23: both programs are ``jit_run``, no ``bf.*`` span,
    no scope, no ``optimizer_step`` counter.  No reader raises."""
    trace = tr.Trace({0: ops_of_a_step(0)}, [ev("bench.free", 0, 200)])
    modules = {0: [ev("jit_run", 0, 100, "0"), ev("jit_run", 100, 160, "1")]}
    for program in (common.Program([], modules, {}, {}),
                    common.Program([], {}, {}, {})):
        ctx = context(trace, program, steps=1)
        assert [read(name, ctx) for name in NEW] == [None] * len(NEW)
    # ... and a context the harness made finds no trace file to reduce
    ctx = context(trace, None, steps=1, cell="tiny-resnet-1dev")
    ctx.cell.name = "no-such-cell"
    assert read("optim_place_ms", ctx) is None
    assert ctx.program.spans == [] and ctx.program.modules == {}


def test_the_reduction_survives_json(tmp_path):
    program = hand_made().program
    path = str(tmp_path / "program.json")
    program.to_json(path)
    again = common.Program.from_json(path)
    assert again.spans == [Span(s.name, s.start, s.end, s.thread, s.args)
                           for s in program.spans]
    assert again.modules == program.modules
    assert again.scopes == program.scopes
    assert again.counters == program.counters
    # the old reduction's file is a subset: one fixture serves both readers
    with open(path) as f:
        assert set(json.load(f)) == {"program_spans", "modules", "scopes",
                                     "counters"}


def test_only_executions_in_the_free_stretch_count():
    ctx = hand_made()
    ctx.free = ev("bench.free", 190, 400)      # the second step only
    assert len(common.executions(ctx, common.STEP_PROGRAM)) == 1
    assert len(common.executions(ctx, common.STEP_PROGRAM,
                                 free_only=False)) == 2
    assert read("optim_program_device_ms", ctx) == pytest.approx(58)
    assert read("optim_place_ms", ctx) is None     # its spans came earlier


# --- the trace recorded on the chip ------------------------------------------

def recorded():
    return context(tr.Trace.from_json(FIXTURE),
                   common.Program.from_json(FIXTURE), steps=2)


def test_the_old_reduction_reads_the_new_fixture_as_before():
    trace = tr.Trace.from_json(FIXTURE)
    assert trace.chips() == [0] and len(trace.ops[0]) == 2703
    free = trace.stretch("free")
    assert len(trace.spans_named("bench.optim_dispatch", inside=free)) == 2
    # the exchange, as PR 22's gossip readers see it: 43.28 ms a step, exposed
    ops = tr.within(trace.ops[0], free.start, free.end)
    spans = tr.union(tr.async_intervals(ops, "collective-permute"))
    assert tr.length(spans) / 2 * 1e-6 == pytest.approx(43.284, abs=2e-3)
    assert tr.exposed(ops, "collective-permute") == pytest.approx(
        tr.length(spans), rel=1e-4)


def test_the_fixture_holds_what_the_program_says():
    program = common.Program.from_json(FIXTURE)
    assert [m.name for m in program.modules[0]] == [
        "jit_bf_rank_map_loss", "jit_bf_optim_step"] * 3
    assert {s.name for s in program.spans} == {
        "bf.rank_map.launch", "bf.optim.step", "bf.optim.place",
        "bf.optim.launch"}
    assert len({s.thread for s in program.spans}) == 1
    launches = [s for s in program.spans if s.name == "bf.optim.launch"]
    assert [s.args["step"] for s in launches] == ["6", "7", "8"]
    assert set(program.scopes["jit_bf_rank_map_loss"].values()) == {
        "bf.loss.chunked"}
    assert set(program.scopes["jit_bf_optim_step"].values()) == {
        "bf.optim.update", "bf.optim.fuse", "bf.optim.combine",
        "bf.optim.unfuse"}


@pytest.mark.parametrize("metric, expected", [
    # device-busy time inside the two executions of each program, halved
    ("grad_program_device_ms", 147.5053475),
    ("optim_program_device_ms", 96.318869),
    # innermost covering event of every elementary interval, by its scope
    ("loss_device_ms", 79.0313135),
    ("optim_update_device_ms", 18.7995935),
    ("optim_fuse_device_ms", 7.0185305 + 12.245155),
    ("optim_combine_device_ms", 58.2555885),
    # medians of two spans
    ("optim_place_ms", (2.67562 + 2.56814) / 2),
    ("optim_launch_ms", (2.213389 + 1.938699) / 2),
    ("grad_launch_ms", (2.53632 + 2.43581) / 2),
    # launches 7 and 8 waited 140.870737 and 377.261367 ms for the device
    ("host_lead_ms", (140.870737 + 377.261367) / 2),
    ("input_wait_ms", None),
    # 137,332,686,848 B over 17 steps and 4 chips: 504,899,584 float32
    ("gossip_gb_per_step", 504899584 * 4 / 1e9),
])
def test_readers_on_the_recorded_trace(metric, expected, capsys):
    value = read(metric, recorded())
    if expected is None:
        assert value is None
    else:
        assert value == pytest.approx(expected, rel=1e-9)
    out = capsys.readouterr().out
    if metric == "optim_update_device_ms":
        # the scopes cover the program: the parts sum to the whole
        assert "unattributed 0.000" in out and "sum 96.319" in out
    if metric == "optim_launch_ms":
        assert "bf.optim.step median 4.846 ms" in out
    if metric == "gossip_gb_per_step":
        assert "in the trace 2.019598336 GB a step" in out


def test_inside_agrees_with_outside_on_the_recorded_trace():
    """The program's own names give what PR 22's spans give: the optimizer
    program's device time in the free stretch against the device time
    between dispatch and wait in the (one) blocked step."""
    ctx = recorded()
    inside = read("optim_program_device_ms", ctx)
    assert read("optim_device_ms", ctx) == pytest.approx(inside, rel=3e-2)
    assert read("grad_device_ms", ctx) == pytest.approx(
        read("grad_program_device_ms", ctx), rel=3e-2)
    assert read("optim_dispatch_ms", ctx) >= (
        read("optim_place_ms", ctx) + read("optim_launch_ms", ctx))
    assert read("gossip_device_ms", ctx) < read("optim_combine_device_ms",
                                                ctx)


# --- the traced twins ----------------------------------------------------------

def traced_twin(name):
    """``benchmark/run.py --trace 1`` on a twin, as ``test_cells_cpu.py``
    runs it; its numbers are not device numbers."""
    import subprocess
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    done = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", name, "--seed",
         "7", "--seconds", "4", "--trace", "1"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=900)
    assert done.returncode == 0, done.stderr[-2000:]
    line = json.loads(done.stdout.strip().splitlines()[-1])
    assert line["correct"] is True, done.stdout[-3000:]
    return line["metrics"], done


def test_the_hostfed_twin_reads_its_input_wait():
    """No cell of ``BENCHMARK.json`` feeds from the host yet, so
    ``input_wait_ms`` has its reader and no entry there (a declared metric
    has to be in every line of its cells): the reader is run here on the
    trace the twin's own run left."""
    name = "tiny-resnet-hostfed-1dev"
    metrics, _ = traced_twin(name)
    # a one-chip cell: the four-chip cell's metrics stay out of its line
    assert not {"optim_fuse_device_ms", "optim_combine_device_ms",
                "gossip_gb_per_step", "loss_device_ms"} & set(metrics)
    path = common.trace_path(name)
    trace = tr.Trace.from_xplane(path)
    steps = len(trace.spans_named("bench.optim_dispatch",
                                  inside=trace.stretch("free")))
    spans, modules = common.read_xplane(path)
    ctx = context(trace, common.Program(spans, modules, {}, {}), steps,
                  cell=name)
    waits = common.spans_in_free(ctx, "bf.data.wait")
    assert steps > 0 and len(waits) == steps
    assert read("input_wait_ms", ctx) == pytest.approx(
        sum(s.duration for s in waits) / steps * 1e-6)
    # the k-th wait is for the batch the feeder placed as its k-th
    placed = {s.args["batch"] for s in spans if s.name == "bf.data.place"}
    assert {s.args["batch"] for s in waits} <= placed


def test_the_gossip_twin_reads_every_other_new_metric():
    metrics, done = traced_twin("tiny-lm-gossip-4dev")
    assert set(NEW) - {"input_wait_ms"} <= set(metrics), done.stdout[-3000:]
    assert "input_wait_ms" not in metrics         # its pool is on the device
    # counts, which a CPU run can give: 4 edges a step, a row each
    out = done.stdout
    assert "the optimizer program by scope" in out
    assert "bf.optim.step median" in out
    assert "no donated" not in done.stderr and "in-flight window" \
        not in done.stderr                        # the throttle's old warning
