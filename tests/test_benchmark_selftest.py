"""The benchmark's CPU self-tests as cases of tier-1 (ROADMAP I6).

``benchmark/selftest/test_trace_reduce.py``, ``test_program_readers.py``,
``test_setup_readers.py``, ``test_regime_readers.py`` and ``test_dropin.py``
hold the per-layer readers to a trace recorded on the chip, to hand-made counters and to the tiny twins of
the cells, traced here on the CPU in child processes.  The readers find the step program's operations by its scopes
(``bf.optim.fuse`` / ``combine`` / ``unfuse`` / ``update``, ``bf.loss.chunked``,
``bf.moe*``), its spans and its counters, so these cases fail when a library
change moves one of them: the ledger's per-layer metrics would read ``null``.
Their tests are collected here under their own names behind the file's;
``test_cells_cpu.py``, ``test_moe_cell_cpu.py`` (three minutes) and the
``test_twin_*`` cases of ``test_xing_cell_cpu.py``, ``test_lfm2_cell_cpu.py``,
``test_laguna_cell_cpu.py``, ``test_twotower_cell_cpu.py``,
``test_kanana_cell_cpu.py`` and ``test_ling_cell_cpu.py`` (whose other cases,
the cell's declaration, its published widths, its cost functions and its
roofline readers, run here; of ``test_laguna_cell_cpu.py``,
``test_twotower_cell_cpu.py``, ``test_kanana_cell_cpu.py`` and
``test_ling_cell_cpu.py`` the traced twin too, a minute and a half and about
a minute three times: the cell's checks and every new reader on a CPU trace)
stay by hand.

Two cases are collected through ``test_setup_readers.py`` and not directly:
``test_the_cell_is_declared_with_its_five_metrics`` / ``..._six_metrics``
count their cell's per-layer metrics with a literal (``12 + 5``, ``12 + 6``)
in files of the benchmark that PR 36 may not edit, and PR 36 appends four
metrics to every cell.  ``test_setup_readers.py`` runs both unchanged on
``BENCHMARK.json`` less those four entries and then looks for the four.
The same file's ``test_the_entries_say_what_the_readers_are`` holds those four
to be the last of ``per_layer`` and ``kernel_stagings`` to list every cell but
ResNet's; a ``model_config`` PR appends a cell and its metrics behind them (the
contract: new entries go last) and may not edit that file either, so the case
runs here unchanged on ``BENCHMARK.json`` less everything appended after the
four, cut by position and not by a list of names: the metrics behind the four,
the cells behind the last one ``kernel_stagings`` lists, the configurations
behind the last one such a cell names.  PR 40's cell and PR 42's are behind
that line, and PR 47's cell and PR 50's, and the next appends without
touching this file but for its tuple of files.  One more case runs on a cut file:
``test_twotower_cell_cpu.py`` holds PR 42's entries to be the last of their
lists, so it runs here on ``BENCHMARK.json`` as that PR left it
(``_WHEN_LAST``); PR 47's own declaration case counts by position instead.

PR 52 appends five metrics that every cell (three of them) or every
held-share cell (two) reports, behind everything that was there, so they are
behind every one of those lines and list cells before them.  The cuts below
therefore take them off first, by position (``_regime_cut``: the last five,
held to be ``test_regime_readers.NEW``), and the cases that look at a
cell's list from its end (PR 36's four as the last of ``xing4`` and
``lfm2``, ``12 + 5`` and ``12 + 6`` of them) run through
``test_setup_readers_a_held_share_cell_is_declared...`` here on
``BENCHMARK.json`` less the five;
``test_regime_readers_the_five_entries_are_the_last...`` looks for the five.
"""

import importlib
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

# PR 36's own declaration test, run below on the file less what came later
_BEHIND_THE_FOUR = "test_the_entries_say_what_the_readers_are"
# PR 42's declaration test holds its cell, configuration and five metrics to
# be the LAST of their lists (a file of the benchmark that a later PR may not
# edit): run below on the file cut behind them
_WHEN_LAST = ("twotower_cell_cpu",
              "test_declared_with_its_five_metrics_and_no_other_cells")

# PR 36's case that runs the two literal counts (12 + 5, 12 + 6) on the file
# less its four: run below on the file less PR 52's five as well
_FROM_THE_END = "test_a_held_share_cell_is_declared_as_it_was_and_with_the_four"

for _file in ("trace_reduce", "program_readers", "setup_readers",
              "regime_readers", "dropin",
              "xing_cell_cpu", "lfm2_cell_cpu", "laguna_cell_cpu",
              "twotower_cell_cpu", "kanana_cell_cpu", "ling_cell_cpu"):
    _module = importlib.import_module(f"benchmark.selftest.test_{_file}")
    for _name, _obj in vars(_module).items():
        if _name.startswith(("test_twin_", "test_the_cell_is_declared_")) \
                or _name in (_BEHIND_THE_FOUR, _FROM_THE_END) \
                or (_file, _name) == _WHEN_LAST:
            continue    # two minutes, by hand; through test_setup_readers
        if _name.startswith("test_"):
            globals()[f"test_{_file}_{_name[len('test_'):]}"] = _obj
        elif type(_obj).__module__ == "_pytest.fixtures":
            globals()[_name] = _obj     # a fixture its tests ask for by name


def _regime_cut(spec):
    """``spec.read_json`` that gives ``BENCHMARK.json`` less PR 52's five
    entries, cut by position: they are the last five."""
    regime = importlib.import_module("benchmark.selftest.test_regime_readers")
    read_json = spec.read_json
    whole = read_json(os.path.join(ROOT, "BENCHMARK.json"))
    five = len(regime.NEW)
    assert [m["name"] for m in whole["per_layer"][-five:]] == regime.NEW

    def less_the_five(path):
        data = read_json(path)
        if os.path.basename(path) == "BENCHMARK.json":
            data["per_layer"] = data["per_layer"][:-five]
        return data
    return less_the_five


@pytest.mark.parametrize("module, test", [
    ("xing_cell_cpu", "test_the_cell_is_declared_with_its_five_metrics"),
    ("lfm2_cell_cpu", "test_the_cell_is_declared_with_its_six_metrics")])
def test_setup_readers_a_held_share_cell_is_declared_as_it_was_and_with_the_four(
        module, test, monkeypatch):
    """PR 36's case unchanged (it takes its own four off and runs the
    cell's literal count), on ``BENCHMARK.json`` less PR 52's five."""
    from benchmark import spec
    setup = importlib.import_module("benchmark.selftest.test_setup_readers")
    monkeypatch.setattr(spec, "read_json", _regime_cut(spec))
    getattr(setup, _FROM_THE_END)(module, test, monkeypatch)


def test_setup_readers_the_entries_say_what_the_readers_are(monkeypatch):
    from benchmark import spec
    setup = importlib.import_module("benchmark.selftest.test_setup_readers")
    monkeypatch.setattr(spec, "read_json", _regime_cut(spec))
    read_json = spec.read_json
    whole = read_json(os.path.join(ROOT, "BENCHMARK.json"))
    names = [m["name"] for m in whole["per_layer"]]
    four = names.index(setup.NEW[0]) + len(setup.NEW)
    assert names[four - len(setup.NEW):four] == setup.NEW   # in this order
    listed = whole["per_layer"][names.index("kernel_stagings")]["workloads"]
    cells = [w["name"] for w in whole["workloads"]]
    last_cell = max(cells.index(name) for name in listed) + 1
    configs = [c["name"] for c in whole["configs"]]
    last_config = max(configs.index(w["config"])
                      for w in whole["workloads"][:last_cell]) + 1
    # what came later is behind the line and touches nothing before it
    assert listed == [n for n in cells[:last_cell]
                      if n != "resnet50-b256-1chip"]
    for m in whole["per_layer"][four:]:
        assert set(m["workloads"]) <= set(cells[last_cell:]), m["name"]
    for w in whole["workloads"][last_cell:]:
        assert configs.index(w["config"]) >= last_config, w["name"]

    def up_to_the_four(path):
        data = read_json(path)
        if os.path.basename(path) == "BENCHMARK.json":
            data["per_layer"] = data["per_layer"][:four]
            data["workloads"] = data["workloads"][:last_cell]
            data["configs"] = data["configs"][:last_config]
        return data
    monkeypatch.setattr(spec, "read_json", up_to_the_four)
    getattr(setup, _BEHIND_THE_FOUR)()


def test_twotower_cell_cpu_declared_with_its_five_metrics_and_no_other_cells(
        monkeypatch):
    """PR 42's case unchanged, on ``BENCHMARK.json`` cut behind that PR's
    entries by position: behind its cell, its configuration and the last
    metric that lists its cell alone."""
    from benchmark import spec
    module = importlib.import_module(
        f"benchmark.selftest.test_{_WHEN_LAST[0]}")
    monkeypatch.setattr(spec, "read_json", _regime_cut(spec))
    read_json = spec.read_json
    whole = read_json(os.path.join(ROOT, "BENCHMARK.json"))
    cell = [w["name"] for w in whole["workloads"]].index(
        module.STANDS_FOR) + 1
    config = [c["name"] for c in whole["configs"]].index(
        whole["workloads"][cell - 1]["config"]) + 1
    metric = max(i for i, m in enumerate(whole["per_layer"])
                 if m.get("workloads") == [module.STANDS_FOR]) + 1
    # what came later is behind the line and lists none of what is before
    for m in whole["per_layer"][metric:]:
        assert module.STANDS_FOR not in m["workloads"], m["name"]

    def as_pr_42_left_it(path):
        data = read_json(path)
        if os.path.basename(path) == "BENCHMARK.json":
            data["per_layer"] = data["per_layer"][:metric]
            data["workloads"] = data["workloads"][:cell]
            data["configs"] = data["configs"][:config]
        return data
    monkeypatch.setattr(spec, "read_json", as_pr_42_left_it)
    getattr(module, _WHEN_LAST[1])()

