"""The four readers of the way to the first step (PR 36: ``grad_build_s``,
``optim_build_s``, ``kernel_stagings``, ``init_s``;
``layer_metrics/setup_common.py``) on a hand-made snapshot of the registry,
on an empty one (the parent of PR 36: None from all four) and on a traced
twin end to end.

    python3 -m pytest benchmark/selftest/test_setup_readers.py -q
"""

import importlib
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import spec  # noqa: E402
from benchmark.selftest import test_program_readers as readers  # noqa: E402

NEW = ["grad_build_s", "optim_build_s", "kernel_stagings", "init_s"]


def build(program, stage, seconds, times):
    labels = f'{{program="{program}",stage="{stage}"}}'
    return {"bf_program_build_seconds_sum" + labels: seconds,
            "bf_program_build_seconds_count" + labels: float(times),
            # a histogram's buckets ride along and are nobody's series
            f'bf_program_build_seconds_bucket{{le="+Inf",program="{program}"'
            f',stage="{stage}"}}': float(times)}


def snapshot():
    counters = {
        'bf_compile_cache_total{result="hit"}': 7.0,
        'bf_compile_cache_total{result="miss"}': 2.0,
        'bf_kernel_stagings_total{kernel="bf_flash_fwd"}': 4.0,
        'bf_kernel_stagings_total{kernel="bf_flash_dq"}': 2.0,
        'bf_kernel_stagings_total{kernel="bf_moe_gmm_fwd"}': 3.0,
        'bf_startup_seconds{part="import"}': 0.5,
        'bf_startup_seconds{part="init_devices"}': 0.25,
        'bf_startup_seconds{part="init_topology"}': 0.125,
        'bf_startup_seconds{part="optim_init"}': 1.0,
        'bf_step_program_builds_total{program="optim_step"}': 2.0,
        'bf_step_program_builds_total{program="rank_map"}': 3.0,
        'bf_optimizer_step_seconds_count{family="collective"}': 5.0}
    for row in (("bf_rank_map_loss", "trace", 4.0, 3),
                ("bf_rank_map_loss", "lower", 8.0, 1),
                ("bf_rank_map_loss", "compile", 0.5, 2),
                ("bf_rank_map_make_pool", "compile", 16.0, 1),
                ("bf_optim_step", "trace", 0.25, 2),
                ("bf_optim_step", "compile", 2.0, 3),
                ("bf_optim_init", "lower", 0.125, 1),
                ("bf_flash", "trace_nested", 32.0, 4),
                ("other", "trace", 1.0, 9)):
        counters.update(build(*row))
    return counters


def context(counters, mosaic=()):
    ctx = readers.hand_made()       # two steps of jit_bf_rank_map_loss
    ctx.program.counters = counters
    ctx.mosaic_calls = {name: {} for name in mosaic}
    return ctx


@pytest.mark.parametrize("metric, expected, printed", [
    ("grad_build_s", 12.5,
     ["bf_rank_map_loss trace 4.000s x3, lower 8.000s x1, compile 0.500s "
      "x2; persistent cache 7 hits 2 misses",
      "bf_rank_map_make_pool: 16.000s = compile 16.000s x1",
      # a nested trace is inside another program's: not its own seconds
      "bf_flash: 0.000s = trace_nested 32.000s x4",
      "other: 1.000s = trace 1.000s x9",
      "all programs: 31.875s"]),
    ("optim_build_s", 2.375,
     ["bf_optim_step trace 0.250s x2, compile 2.000s x3",
      "bf_optim_init lower 0.125s x1",
      "2 step program object(s) built (bf_step_program_builds_total) "
      "beside 3 compile(s) of bf_optim_step"]),
    ("kernel_stagings", 9.0,
     ["bf_flash_dq 2 (compiled 1), bf_flash_fwd 4 (compiled 2), "
      "bf_moe_gmm_fwd 3 (compiled 0); 3 Mosaic instructions"]),
    ("init_s", 1.875,
     ["import 0.5000s, init_devices 0.2500s, init_topology 0.1250s, "
      "optim_init 1.0000s", "bf_optim_init, 0.1250s"]),
])
def test_readers_on_a_hand_made_snapshot(metric, expected, printed, capsys):
    ctx = context(snapshot(), mosaic=("bf_flash_fwd.1", "bf_flash_fwd.2",
                                      "bf_flash_dq.7"))
    assert readers.read(metric, ctx) == pytest.approx(expected)
    out = capsys.readouterr().out
    for line in printed:
        assert line in out, out


@pytest.mark.parametrize("metric", NEW)
def test_a_program_without_the_series_reads_as_nothing(metric, capsys):
    """The parent of PR 36 has the step's counters and none of these."""
    old = {k: v for k, v in snapshot().items()
           if k.startswith(("bf_step_program", "bf_optimizer_step"))}
    for counters in ({}, old):
        assert readers.read(metric, context(counters)) is None
    assert capsys.readouterr().out == ""


def test_the_gradient_program_is_the_one_that_runs_in_the_free_stretch():
    ctx = context(snapshot())
    ctx.program.modules = {0: []}       # nothing ran: no name to look up
    assert readers.read("grad_build_s", ctx) is None
    assert readers.read("optim_build_s", ctx) == pytest.approx(2.375)
    # a module's name holds "_" where the function's has another character
    ctx = context({**snapshot(), **build("bf_rank_map_<lambda>", "lower",
                                         3.0, 1)})
    for m in ctx.program.modules[0]:
        if m.name == "jit_bf_rank_map_loss":
            ctx.program.modules[0][ctx.program.modules[0].index(m)] = \
                readers.ev("jit_bf_rank_map__lambda_", m.start * 1e-6,
                           m.end * 1e-6, m.what)
    assert readers.read("grad_build_s", ctx) == pytest.approx(3.0)


def test_the_entries_say_what_the_readers_are():
    bench = spec.read_json(os.path.join(ROOT, "BENCHMARK.json"))
    entries = {m["name"]: m for m in bench["per_layer"]}
    assert list(entries)[-4:] == NEW            # appended, in this order
    lm_cells = [w["name"] for w in bench["workloads"]
                if w["name"] != "resnet50-b256-1chip"]
    for name in NEW:
        m = entries[name]
        assert (m["source"], m["moves"], m["better"]) == (
            "program_counter", "setup_s", "lower")
        assert m.get("workloads") == (lm_cells if name == "kernel_stagings"
                                      else None)
    assert [entries[n]["layer"] for n in NEW] == [
        "models", "optim", "ops.flash_attention", "basics"]


@pytest.mark.parametrize("module, test", [
    ("xing_cell_cpu", "test_the_cell_is_declared_with_its_five_metrics"),
    ("lfm2_cell_cpu", "test_the_cell_is_declared_with_its_six_metrics")])
def test_a_held_share_cell_is_declared_as_it_was_and_with_the_four(
        module, test, monkeypatch):
    """Those two tests count their cell's metrics with a literal (12 + 5,
    12 + 6) in files this PR may not edit, and fail on the four appended
    entries.  They run here unchanged on ``BENCHMARK.json`` less the four,
    so everything else they hold still holds, and the four are looked for
    beside them (``PERF.md``, Open questions, asks for the two literals)."""
    cell_tests = importlib.import_module(f"benchmark.selftest.test_{module}")
    names = [m["name"] for m in
             spec.load_cell(cell_tests.STANDS_FOR).per_layer]
    assert names[-4:] == NEW
    read_json = spec.read_json

    def without_the_four(path):
        data = read_json(path)
        if os.path.basename(path) == "BENCHMARK.json":
            data["per_layer"] = [m for m in data["per_layer"]
                                 if m["name"] not in NEW]
        return data
    monkeypatch.setattr(spec, "read_json", without_the_four)
    getattr(cell_tests, test)()


# --- a traced twin, end to end -----------------------------------------------

@pytest.fixture(scope="module")
def twin():
    return readers.traced_twin("tiny-lm-long-1dev")


@pytest.mark.parametrize("metric", NEW)
def test_the_long_twin_reads_its_way_to_the_first_step(twin, metric):
    metrics, done = twin
    assert metrics[metric]["value"] > 0, done.stdout[-3000:]
    out = done.stdout
    if metric == "grad_build_s":
        assert "grad_build_s: bf_rank_map_loss trace" in out
        for program in ("bf_optim_init", "bf_optim_step",
                        "bf_rank_map_make_model_trees", "other"):
            assert f"    {program}: " in out
        assert "persistent cache 0 hits 0 misses" in out    # a CPU mesh
    if metric == "optim_build_s":
        assert "1 step program object(s) built" in out
    if metric == "kernel_stagings":
        # two layers, interpreter: the kernels are staged, none compiled
        assert metrics[metric]["value"] == 10
        assert "bf_flash_fwd 6 (compiled 0)" in out
    if metric == "init_s":
        for part in ("import", "init_devices", "init_topology",
                     "optim_init"):
            assert f"{part} " in out.split("init_s: ")[1].splitlines()[0]
