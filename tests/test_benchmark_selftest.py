"""The benchmark's CPU self-tests as cases of tier-1 (ROADMAP I6).

``benchmark/selftest/test_trace_reduce.py``, ``test_program_readers.py``,
``test_setup_readers.py`` and ``test_dropin.py`` hold the per-layer readers to
a trace recorded on the chip, to hand-made counters and to the tiny twins of
the cells, traced here on the CPU in child processes.  The readers find the step program's operations by its scopes
(``bf.optim.fuse`` / ``combine`` / ``unfuse`` / ``update``, ``bf.loss.chunked``,
``bf.moe*``), its spans and its counters, so these cases fail when a library
change moves one of them: the ledger's per-layer metrics would read ``null``.
Their tests are collected here under their own names behind the file's;
``test_cells_cpu.py``, ``test_moe_cell_cpu.py`` (three minutes) and the
``test_twin_*`` cases of ``test_xing_cell_cpu.py``, ``test_lfm2_cell_cpu.py``
and ``test_laguna_cell_cpu.py`` (whose other cases, the cell's declaration,
its published widths, its cost functions and its roofline readers, run here;
of ``test_laguna_cell_cpu.py`` the traced twin too, a minute and a half: the
cell's checks and every new reader on a CPU trace) stay by hand.

Two cases are collected through ``test_setup_readers.py`` and not directly:
``test_the_cell_is_declared_with_its_five_metrics`` / ``..._six_metrics``
count their cell's per-layer metrics with a literal (``12 + 5``, ``12 + 6``)
in files of the benchmark that PR 36 may not edit, and PR 36 appends four
metrics to every cell.  ``test_setup_readers.py`` runs both unchanged on
``BENCHMARK.json`` less those four entries and then looks for the four.
The same file's ``test_the_entries_say_what_the_readers_are`` holds those four
to be the last of ``per_layer`` and ``kernel_stagings`` to list every cell but
ResNet's; PR 40 appends a cell and five metrics behind them (the contract: new
entries go last) and may not edit that file either, so the case runs here
unchanged on ``BENCHMARK.json`` less PR 40's entries, and the five are looked
for behind the four.
"""

import importlib
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

# PR 36's own declaration test, run below on the file less PR 40's entries
_BEHIND_THE_FOUR = "test_the_entries_say_what_the_readers_are"

for _file in ("trace_reduce", "program_readers", "setup_readers", "dropin",
              "xing_cell_cpu", "lfm2_cell_cpu", "laguna_cell_cpu"):
    _module = importlib.import_module(f"benchmark.selftest.test_{_file}")
    for _name, _obj in vars(_module).items():
        if _name.startswith(("test_twin_", "test_the_cell_is_declared_")) \
                or _name == _BEHIND_THE_FOUR:
            continue    # two minutes, by hand; through test_setup_readers
        if _name.startswith("test_"):
            globals()[f"test_{_file}_{_name[len('test_'):]}"] = _obj
        elif type(_obj).__module__ == "_pytest.fixtures":
            globals()[_name] = _obj     # a fixture its tests ask for by name


PR40_METRICS = ["swa_attn_device_ms", "swa_flash_roofline",
                "gated_attn_device_ms", "small_moe_device_ms",
                "small_moe_expert_roofline"]


def test_setup_readers_the_entries_say_what_the_readers_are(monkeypatch):
    from benchmark import spec
    setup = importlib.import_module("benchmark.selftest.test_setup_readers")
    read_json = spec.read_json
    whole = read_json(os.path.join(ROOT, "BENCHMARK.json"))
    names = [m["name"] for m in whole["per_layer"]]
    assert names[-9:] == setup.NEW + PR40_METRICS    # appended, in this order
    assert whole["workloads"][-1]["name"] == "laguna-s8192-1chip"
    assert whole["configs"][-1]["name"] == "laguna-xs.2"

    def before_pr40(path):
        data = read_json(path)
        if os.path.basename(path) == "BENCHMARK.json":
            data["per_layer"] = [m for m in data["per_layer"]
                                 if m["name"] not in PR40_METRICS]
            data["workloads"] = data["workloads"][:-1]
            data["configs"] = data["configs"][:-1]
        return data
    monkeypatch.setattr(spec, "read_json", before_pr40)
    getattr(setup, _BEHIND_THE_FOUR)()
