"""A configuration, a traffic mix, a cell and a per-layer metric dropped in
as new files are found by name, with no edit to a file that is there.

    python3 -m pytest benchmark/selftest/test_dropin.py -q

The test works on a copy of ``BENCHMARK.json`` and ``benchmark/``, adds four
files and three entries to the copy, and drives the copy's own code.
"""

import importlib.util
import json
import os
import shutil
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

READER = '''"""A metric a later PR might add: device events per step."""


def read(ctx):
    if not ctx.free_steps:
        return None
    return len(ctx.free_ops()) / ctx.free_steps
'''


def test_new_files_are_found_by_name(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "benchmark"), tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = {p: os.path.getmtime(p) for p in map(str, tmp_path.rglob("*"))
              if os.path.isfile(p) and not p.endswith("BENCHMARK.json")}

    def read(path):
        with open(tmp_path / path) as f:
            return json.load(f)

    def write(path, obj):
        with open(tmp_path / path, "w") as f:
            json.dump(obj, f)

    # a configuration (with its reference beside it), a traffic mix, a reader
    config = read("benchmark/configs/internlm2-1.8b.json")
    config.update(name="dropped-lm", num_hidden_layers=1)
    write("benchmark/configs/dropped-lm.json", config)
    shutil.copy(tmp_path / "benchmark/reference/internlm2-1.8b.py",
                tmp_path / "benchmark/reference/dropped-lm.py")
    traffic = read("benchmark/traffic/tokens-1x16384.json")
    traffic.update(name="dropped-tokens",
                   batch={"sequences": 4, "seq_len": 2048})
    write("benchmark/traffic/dropped-tokens.json", traffic)
    (tmp_path / "benchmark/layer_metrics/device_events_per_step.py"
     ).write_text(READER)
    # and their entries
    bench = read("BENCHMARK.json")
    bench["configs"].append({
        "name": "dropped-lm", "source": config["source"],
        "file": "benchmark/configs/dropped-lm.json",
        "reduced": ["num_hidden_layers"], "why": "drop-in test"})
    bench["workloads"].append({
        "name": "dropped-cell", "config": "dropped-lm",
        "traffic": "dropped-tokens", "chips": 1, "why": "drop-in test"})
    bench["per_layer"].append({
        "name": "device_events_per_step", "unit": "events",
        "better": "lower", "source": "device_trace", "layer": "device",
        "moves": "throughput_per_chip", "workloads": ["dropped-cell"]})
    write("BENCHMARK.json", bench)

    spec_file = importlib.util.spec_from_file_location(
        "dropin_spec", tmp_path / "benchmark" / "spec.py")
    spec = importlib.util.module_from_spec(spec_file)
    sys.modules["dropin_spec"] = spec           # dataclasses look it up
    spec_file.loader.exec_module(spec)
    cell = spec.load_cell("dropped-cell")
    assert cell.config["num_hidden_layers"] == 1
    assert cell.traffic["batch"] == {"sequences": 4, "seq_len": 2048}
    assert "device_events_per_step" in [m["name"] for m in cell.per_layer]
    assert "gossip_device_ms" not in [m["name"] for m in cell.per_layer]
    assert spec.reference_module(cell).loss is not None
    assert spec.task_module(cell).items_per_step(cell.traffic["batch"]) \
        == 8192
    # the new reader runs on the trace recorded on the chip
    from benchmark import layers, trace_reduce
    trace = trace_reduce.Trace.from_json(os.path.join(
        ROOT, "benchmark", "selftest", "trace_v5e.json"))
    ctx = layers.context(trace, cell, spec.task_module(cell),
                         spec.peak_row("TPU v5 lite"), {})
    per_step = spec.layer_metric_reader("device_events_per_step")(ctx)
    assert per_step == len(trace_reduce.within(
        trace.ops[0], ctx.free.start, ctx.free.end)) / 5
    # the command finds the cell by name too, and holds it to the chip
    done = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", "dropped-cell"],
        cwd=tmp_path, capture_output=True, text=True, timeout=300,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert done.returncode != 0 and done.stdout.strip() == ""
    assert "'dropped-cell' needs platform 'tpu'" in done.stderr
    # nothing that was there has been touched
    after = {p: os.path.getmtime(p) for p in before}
    assert after == before
