"""The benchmark's CPU self-tests as cases of tier-1 (ROADMAP I6).

``benchmark/selftest/test_trace_reduce.py``, ``test_program_readers.py``,
``test_setup_readers.py`` and ``test_dropin.py`` hold the per-layer readers to
a trace recorded on the chip, to hand-made counters and to the tiny twins of
the cells, traced here on the CPU in child processes.  The readers find the step program's operations by its scopes
(``bf.optim.fuse`` / ``combine`` / ``unfuse`` / ``update``, ``bf.loss.chunked``,
``bf.moe*``), its spans and its counters, so these cases fail when a library
change moves one of them: the ledger's per-layer metrics would read ``null``.
Their tests are collected here under their own names behind the file's;
``test_cells_cpu.py``, ``test_moe_cell_cpu.py`` (three minutes) and the two
``test_twin_*`` cases each of ``test_xing_cell_cpu.py`` and
``test_lfm2_cell_cpu.py`` (whose other cases, the cell's declaration, its
published widths and its roofline readers, run here) stay by hand.

Two cases are collected through ``test_setup_readers.py`` and not directly:
``test_the_cell_is_declared_with_its_five_metrics`` / ``..._six_metrics``
count their cell's per-layer metrics with a literal (``12 + 5``, ``12 + 6``)
in files of the benchmark that PR 36 may not edit, and PR 36 appends four
metrics to every cell.  ``test_setup_readers.py`` runs both unchanged on
``BENCHMARK.json`` less those four entries and then looks for the four.
"""

import importlib
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

for _file in ("trace_reduce", "program_readers", "setup_readers", "dropin",
              "xing_cell_cpu", "lfm2_cell_cpu"):
    _module = importlib.import_module(f"benchmark.selftest.test_{_file}")
    for _name, _obj in vars(_module).items():
        if _name.startswith(("test_twin_", "test_the_cell_is_declared_")):
            continue    # two minutes, by hand; through test_setup_readers
        if _name.startswith("test_"):
            globals()[f"test_{_file}_{_name[len('test_'):]}"] = _obj
        elif type(_obj).__module__ == "_pytest.fixtures":
            globals()[_name] = _obj     # a fixture its tests ask for by name
