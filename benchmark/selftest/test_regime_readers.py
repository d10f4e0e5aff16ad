"""The five readers of a run's regime (``layer_metrics/regime_common.py``:
``optim_wait_ms``, ``grad_hold_ms``, ``launch_headroom_gib``,
``expert_window_overflow_share``, ``expert_window_overflow_device_ms``) on
hand-made spans and events, on a program that says nothing of the kind (the
parent's), and on the traced twin of a cell that holds a windowed share.

    python3 -m pytest benchmark/selftest/test_regime_readers.py -q   (two minutes)
"""

import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import spec  # noqa: E402
from benchmark import trace_reduce as tr  # noqa: E402
from benchmark.selftest import test_program_readers as readers  # noqa: E402

common = readers.common
regime = spec.load_module("layer_metrics/regime_common.py")
ev, sp, read = readers.ev, readers.sp, readers.read
NEW = ["optim_wait_ms", "grad_hold_ms", "launch_headroom_gib",
       "expert_window_overflow_share", "expert_window_overflow_device_ms"]
HELD_CELLS = ["xing4-s4096-1chip", "lfm2-s8192-1chip", "laguna-s8192-1chip",
              "twotower-s8192-1chip", "kanana2-packed-s8192-1chip",
              "ling3-kda-s4096-1chip"]
GB = 10 ** 9

# A gradient program with one expert layer's conditional, forward, remat
# recompute and transpose: the window branch a gather and a kernel, the
# overflow branch a loop over them.  The window's gather has no metadata of
# its own and is told by what it fuses.  The recompute's overflow branch
# keeps nothing and holds no operation: a run of it is known by that.
HLO = '''HloModule jit_bf_rank_map_loss, is_scheduled=true

%fused.w (p: f32[8]) -> f32[8] {
  %p = f32[8]{0} parameter(0)
  ROOT %g = f32[8]{0} negate(%p), metadata={op_name="jit(loss)/jvp(M)/bf.moe/bf.moe.dispatch/cond/branch_1_fun/bf_moe_held_window/bf.moe.dispatch/gather"}
}

%window (q: f32[8]) -> f32[8] {
  %q = f32[8]{0} parameter(0)
  %gather_fusion = f32[8]{0} fusion(%q), kind=kLoop, calls=%fused.w
  ROOT %bf_moe_gmm_fwd.1 = f32[8]{0} custom-call(%gather_fusion), custom_call_target="tpu_custom_call", metadata={op_name="jit(loss)/jvp(M)/bf.moe/bf.moe.dispatch/cond/branch_1_fun/bf_moe_held_window/bf.moe.experts/jit(_grouped_fwd)/bf_moe_gmm_fwd"}
}

%body (r: f32[8]) -> f32[8] {
  %r = f32[8]{0} parameter(0)
  ROOT %bf_moe_gmm_fwd.2 = f32[8]{0} custom-call(%r), custom_call_target="tpu_custom_call", metadata={op_name="jit(loss)/jvp(M)/bf.moe/bf.moe.dispatch/cond/branch_0_fun/bf_moe_held_overflow/while/body/bf.moe.experts/jit(_grouped_fwd)/bf_moe_gmm_fwd"}
}

%overflow (s: f32[8]) -> f32[8] {
  %s = f32[8]{0} parameter(0)
  ROOT %while.1 = f32[8]{0} while(%s), condition=%never, body=%body, metadata={op_name="jit(loss)/jvp(M)/bf.moe/bf.moe.dispatch/cond/branch_0_fun/bf_moe_held_overflow/while"}
}

%window_t (t: f32[8]) -> f32[8] {
  %t = f32[8]{0} parameter(0)
  ROOT %bf_moe_gmm_dlhs.1 = f32[8]{0} custom-call(%t), custom_call_target="tpu_custom_call", metadata={op_name="jit(loss)/transpose(jvp(M))/checkpoint/bf.moe/bf.moe.dispatch/cond/branch_1_fun/bf_moe_held_window/bf.moe.experts/bf_moe_gmm_dlhs"}
}

%overflow_t (u: f32[8]) -> f32[8] {
  %u = f32[8]{0} parameter(0)
  ROOT %bf_moe_gmm_dlhs.2 = f32[8]{0} custom-call(%u), custom_call_target="tpu_custom_call", metadata={op_name="jit(loss)/transpose(jvp(M))/checkpoint/bf.moe/bf.moe.dispatch/cond/branch_0_fun/bf_moe_held_overflow/while/body/bf.moe.experts/bf_moe_gmm_dlhs"}
}

%window_r (v: f32[8]) -> f32[8] {
  %v = f32[8]{0} parameter(0)
  ROOT %bf_moe_gmm_fwd.3 = f32[8]{0} custom-call(%v), custom_call_target="tpu_custom_call", metadata={op_name="jit(loss)/transpose(jvp(M))/checkpoint/rematted_computation/bf.moe/bf.moe.dispatch/cond/branch_1_fun/bf_moe_held_window/bf.moe.experts/jit(_grouped_fwd)/bf_moe_gmm_fwd"}
}

%kept_nothing (w: f32[8]) -> f32[8] {
  ROOT %w = f32[8]{0} parameter(0)
}

ENTRY %main (a: f32[8]) -> f32[8] {
  %a = f32[8]{0} parameter(0)
  %route = f32[8]{0} negate(%a), metadata={op_name="jit(loss)/jvp(M)/bf.moe/bf.moe.route/top_k"}
  %cond.4 = f32[8]{0} conditional(%route, %a, %a), branch_computations={%overflow, %window}, metadata={op_name="jit(loss)/jvp(M)/bf.moe/bf.moe.dispatch/cond"}
  %other.1 = f32[8]{0} conditional(%route, %a, %a), branch_computations={%elsewhere, %elsewhere}, metadata={op_name="jit(loss)/jvp(M)/bf.loss.chunked/cond"}
  %cond.6 = f32[8]{0} conditional(%route, %a, %a), branch_computations={%kept_nothing, %window_r}, metadata={op_name="jit(loss)/transpose(jvp(M))/checkpoint/rematted_computation/bf.moe/bf.moe.dispatch/cond"}
  ROOT %conditional.9 = f32[8]{0} conditional(%route, %cond.4, %cond.4), branch_computations={%overflow_t, %window_t}, metadata={op_name="jit(loss)/transpose(jvp(M))/checkpoint/bf.moe/bf.moe.dispatch/cond"}
}
'''
GRAD = "jit_bf_rank_map_loss"


def test_the_markers_are_read_by_the_four_rules_and_no_scope_moves():
    markers = common.instruction_scopes(regime.as_markers(HLO))
    for name, marker in {
            "gather_fusion": "bf.held.window", "bf_moe_gmm_fwd.1":
            "bf.held.window", "bf_moe_gmm_dlhs.1": "bf.held.window",
            "bf_moe_gmm_fwd.2": "bf.held.overflow", "while.1":
            "bf.held.overflow", "bf_moe_gmm_dlhs.2": "bf.held.overflow"
            }.items():
        assert markers[name] == marker, name
    # the conditionals themselves and what is around them carry none
    assert not {"a", "route", "cond.4", "other.1", "conditional.9"} \
        & set(markers)
    both = frozenset({"bf.held.window", "bf.held.overflow"})
    assert regime.held_conditionals(HLO) == {
        "cond.4": ("forward", both), "conditional.9": ("transpose", both),
        "cond.6": ("recompute", frozenset({"bf.held.window"}))}
    # what the other readers see is what they see without the markers
    stripped = HLO.replace("/bf_moe_held_window", "").replace(
        "/bf_moe_held_overflow", "")
    assert "bf_moe_held" not in stripped
    assert common.instruction_scopes(HLO) == common.instruction_scopes(
        stripped)
    assert common.instruction_scopes(HLO)["bf_moe_gmm_fwd.2"] \
        == "bf.moe.experts"


def step_ops(t, overflow):
    """A step's device events, ms: the forward conditional through one
    branch, a conditional of another layer, the recompute and the transpose
    through the same branch."""
    if overflow:
        fwd = [ev("while.1", t + 11, t + 29), ev("bf_moe_gmm_fwd.2", t + 12,
                                                 t + 18),
               ev("bf_moe_gmm_fwd.2", t + 20, t + 28)]
        bwd = [ev("bf_moe_gmm_dlhs.2", t + 61, t + 79)]
    else:
        fwd = [ev("gather_fusion", t + 11, t + 14),
               ev("bf_moe_gmm_fwd.1", t + 14, t + 20)]
        bwd = [ev("bf_moe_gmm_dlhs.1", t + 61, t + 70)]
    again = [] if overflow else [ev("bf_moe_gmm_fwd.3", t + 52, t + 56)]
    return ([ev("route", t + 1, t + 9), ev("cond.4", t + 10, t + 30)] + fwd
            + [ev("other.1", t + 40, t + 50), ev("inner", t + 41, t + 49),
               ev("cond.6", t + 51, t + 57)] + again
            + [ev("conditional.9", t + 60, t + 80)] + bwd)


def hand_made(memory=True, waits=True, marked=True):
    """Four steps of 100 ms on one chip.  The host: steps 0 and 1 run
    ahead (their launches return at once, ``opt.step()`` waits 80 ms); the
    launch of step 2 is held 90 ms with 1 GB to spare; step 3 waits 70 ms
    for its arguments first and then launches in 2."""
    ops, modules, spans = [], [], []
    for k in range(4):
        t = 100 * k
        ops += step_ops(t, overflow=k == 2)
        modules.append(ev(GRAD, t, t + 90, str(k)))
        modules.append(ev("jit_bf_optim_step", t + 90, t + 100, str(k)))
    room = {0: 5, 1: 4, 2: 1, 3: 3}

    def launch(k, start, end, held):
        args = {"held": held}
        if memory:
            args.update(limit=16 * GB, in_use=(13 - room[k]) * GB,
                        reserved=3 * GB, largest_free=room[k] * GB // 2)
        return sp("bf.rank_map.launch", start, end, "main",
                  **{a: str(v) for a, v in args.items()})
    spans += [launch(0, 1, 3, 0), sp("bf.optim.wait", 10, 90, "main"),
              launch(1, 101, 103, 0), sp("bf.optim.wait", 110, 190, "main"),
              launch(2, 201, 291, 1), launch(3, 372, 374, 0)]
    if waits:
        spans += [sp("bf.rank_map.wait", 302, 372, "main"),
                  sp("bf.optim.wait", 292, 293, "main"),
                  sp("bf.optim.wait", 380, 381, "main")]
    else:
        spans = [s for s in spans if s.name != "bf.optim.wait"]
    for k in range(4):
        spans += [sp("bf.optim.step", 100 * k + 94, 100 * k + 99, "main"),
                  sp("bf.optim.place", 100 * k + 95, 100 * k + 97, "main"),
                  sp("bf.optim.launch", 100 * k + 97, 100 * k + 98, "main")]
    trace = tr.Trace({0: ops}, [ev("bench.free", 0, 400)]
                     + [ev("bench.group_sync", 399, 400)])
    counters = {"bf_rank_map_waits_total": 1.0,
                "bf_rank_map_held_launches_total": 4.0,
                "bf_rank_map_launches_total": 11.0,
                "bf_optim_wait_seconds_count": 9.0,
                "bf_optim_wait_seconds_sum": 0.5,
                "bf_launch_headroom_min_bytes": 1.0 * GB}
    ctx = readers.context(trace, common.Program(
        sorted(spans, key=lambda s: s.start), {0: modules}, {}, counters),
        steps=4, cell="laguna-s8192-1chip")
    ctx.window_s = 0.4
    text = HLO if marked else HLO.replace("/bf_moe_held_window", "").replace(
        "/bf_moe_held_overflow", "")
    ctx.regime_maps = ({GRAD: common.instruction_scopes(
        regime.as_markers(text))}, {GRAD: regime.held_conditionals(text)})
    return ctx


def test_readers_on_hand_made_spans_and_events(capsys):
    ctx = hand_made()
    # 80, 80, 1, 1
    assert read("optim_wait_ms", ctx) == pytest.approx(40.5)
    out = capsys.readouterr().out
    assert "4 waits in 4 steps" in out and "mean a step 40.500" in out
    assert "bf_optim_wait_seconds count 9.0 sum 0.5" in out
    # the host's step: every span by name, and what is left of the wall
    assert ("bf.optim.wait 40.500, bf.rank_map.wait 17.500, "
            "bf.rank_map.launch held 22.500, bf.rank_map.launch free 1.500, "
            "bf.optim.place 2.000, bf.optim.launch 1.000, bench.next_batch "
            "0.000, bench.group_sync 0.250, bf.optim.step self 2.000; sum "
            "87.250 of a wall step of 100.000 (no span: 12.750)") in out
    # the held launch's 90 and the wait's 70, over four steps
    assert read("grad_hold_ms", ctx) == pytest.approx(40.0)
    out = capsys.readouterr().out
    assert "1 of 4 launches held, q1 / median / q3 ms 90.000" in out
    assert "1 waits 70.000" in out
    assert ("bf_rank_map_waits_total 1, bf_rank_map_held_launches_total 4 "
            "of bf_rank_map_launches_total 11") in out
    assert read("launch_headroom_gib", ctx) == pytest.approx(GB / 2 ** 30)
    out = capsys.readouterr().out
    assert "4 launches, least 0.9313 median 3.2596 GiB" in out
    assert "bf_launch_headroom_min_bytes 1000000000.0" in out
    # three conditionals a step, all through the overflow branch in step 2
    # (the recompute's runs nothing there); the conditional of another
    # layer counts for nothing
    assert read("expert_window_overflow_share", ctx) == pytest.approx(25.0)
    out = capsys.readouterr().out
    assert "3 of 12 conditionals" in out
    assert ("forward cond.4 1/4, recompute cond.6 1/4, transpose "
            "conditional.9 1/4") in out
    # overflow: the loop's own 4 + 6 + 8 + 18, in one step of four; window:
    # (3 + 6 + 4 + 9) in three
    assert read("expert_window_overflow_device_ms", ctx) \
        == pytest.approx(36 / 4)
    assert "under the window branch 16.500" in capsys.readouterr().out


def test_a_reader_that_finds_none_of_its_kind_reads_zero_and_not_none(
        capsys):
    """The program says which launches were held (``held`` is on its
    spans), and the run had no wait, no allocator's state (a CPU mesh) and
    no marked branch: 0.0, so that the line has every declared metric."""
    ctx = hand_made(memory=False, waits=False, marked=False)
    ctx.program.spans[:] = [s for s in ctx.program.spans
                            if s.args.get("held") != "1"]
    assert [read(name, ctx) for name in NEW] == [0.0] * 5
    out = capsys.readouterr().out
    assert "no launch carries the allocator's state" in out
    assert "none executed" in out


@pytest.mark.parametrize("program", ["its parent's", "none"])
def test_the_parent_reads_as_nothing_and_nothing_raises(program):
    """The parent writes ``bf.rank_map.launch`` and ``bf.optim.wait`` too,
    without ``held``: a regime is read whole or not at all."""
    ctx = hand_made()
    if program == "none":
        ctx.program = common.Program([], {}, {}, {})
    else:
        ctx.program.spans[:] = [
            common.Span(s.name, s.start, s.end, s.thread, {})
            for s in ctx.program.spans]
    ctx.regime_maps = None if program == "none" else ({GRAD: {}},
                                                      {GRAD: {}})
    assert [read(name, ctx) for name in NEW] == [None] * 5
    # ... and the readers PR 23 made of the same spans read on
    older = readers.hand_made()
    assert [read(name, older) for name in NEW] == [None] * 5
    assert read("grad_launch_ms", older) == pytest.approx(2)


def test_the_five_entries_are_the_last_and_say_what_the_readers_are():
    bench = spec.read_json(os.path.join(ROOT, "BENCHMARK.json"))
    entries = bench["per_layer"][-5:]
    assert [m["name"] for m in entries] == NEW
    assert [(m["unit"], m["better"], m["source"], m["layer"], m["moves"])
            for m in entries] == [
        ("ms", "higher", "program_span", "optim", "throughput_per_chip"),
        ("ms", "lower", "program_span", "models", "throughput_per_chip"),
        ("GiB", "higher", "program_span", "models", "peak_hbm_gib"),
        ("%", "lower", "device_trace", "parallel.moe",
         "throughput_per_chip"),
        ("ms", "lower", "device_trace", "parallel.moe",
         "throughput_per_chip")]
    assert [m.get("workloads") for m in entries] == [None] * 3 \
        + [HELD_CELLS] * 2
    for w in bench["workloads"]:
        names = [m["name"] for m in spec.load_cell(w["name"]).per_layer]
        want = NEW if w["name"] in HELD_CELLS else NEW[:3]
        assert names[-len(want):] == want, w["name"]
        assert set(names) & set(NEW) == set(want), w["name"]
    for name in NEW:
        assert callable(spec.layer_metric_reader(name))


# --- a traced twin, end to end -----------------------------------------------

@pytest.fixture(scope="module")
def windowed_twin():
    """``laguna-s8192-1chip``'s twin: 4 of 16 experts held, so its share
    has a window, which ``tiny-xing``'s half has not."""
    from benchmark.selftest import test_laguna_cell_cpu as laguna
    done = laguna.run(trace=1)
    assert done.returncode == 0, done.stderr[-2000:]
    line = json.loads(done.stdout.strip().splitlines()[-1])
    assert line["correct"] is True, done.stdout[-3000:]
    return line["metrics"], done.stdout


def test_the_windowed_twin_reads_its_regime(windowed_twin):
    """Every reader runs on a real trace and the line has all five.  What
    a CPU mesh can say: ``step()`` waits every step; no allocator keeps an
    account; the branches' operations are in the program and in the trace.
    (Counts are not held here: on a CPU mesh an execution's window is the
    host's call and not the device's run, so of the 120 conditionals of
    four layers, three passes and ten steps few start inside one, and a
    launch may find its arguments ready a moment later than it began.)"""
    metrics, out = windowed_twin
    assert set(NEW) <= set(metrics), out[-3000:]
    assert metrics["optim_wait_ms"]["value"] > 0
    assert "10 waits in 10 steps" in out
    assert "host's step, mean ms of 10 steps: bf.optim.wait " in out
    assert metrics["grad_hold_ms"]["value"] >= 0
    assert " of 10 launches held" in out
    assert metrics["launch_headroom_gib"]["value"] == 0     # no account
    assert "no launch carries the allocator's state" in out
    assert 0 <= metrics["expert_window_overflow_share"]["value"] <= 100
    assert " conditionals of a held share took the overflow branch" in out
    assert metrics["expert_window_overflow_device_ms"]["value"] >= 0
    assert "under the window branch " in out
