"""The ``olmoe-s4096-1chip`` cell's tiny twin end to end on the CPU, and its
five ``moe_*`` readers.

    python3 -m pytest benchmark/selftest/test_moe_cell_cpu.py -q    (a minute)

``selftest/workloads.json`` is not this PR's to edit, so the twin is built
here: a ``spec.Cell`` of ``selftest/configs/tiny-olmoe.json`` and
``selftest/traffic/tiny-tokens-adamw.json`` with the metric lists of
``olmoe-s4096-1chip``, handed to ``benchmark/run.py`` in a process of its own
(``JAX_PLATFORMS=cpu``, Pallas in interpret mode: the grouped-matmul and
flash kernels choose it themselves off the chip).  Interpreted kernels are
ordinary instructions and no event is a grouped product, so the traced twin
reads the four scope metrics and leaves ``moe_expert_roofline`` out; that
reader runs here on hand-made events of the names and shapes the program
compiled for the v5e has.
Its numbers are not device numbers.
"""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import layers, spec  # noqa: E402
from benchmark import trace_reduce as tr  # noqa: E402

TWIN = "tiny-olmoe-1dev"
STANDS_FOR = "olmoe-s4096-1chip"
SCOPE_METRICS = {"moe_device_ms", "moe_route_device_ms",
                 "moe_permute_device_ms", "moe_expert_device_ms"}

DRIVER = f'''
import os, sys
sys.path.insert(0, {ROOT!r})
from benchmark import spec
from benchmark.selftest.test_moe_cell_cpu import twin_cell
find = spec.load_cell
spec.load_cell = lambda name: twin_cell() if name == {TWIN!r} else find(name)
from benchmark import run
sys.exit(run.main(sys.argv[1:]))
'''


def twin_cell() -> spec.Cell:
    real = spec.load_cell(STANDS_FOR)
    here = os.path.join(spec.HERE, "selftest")
    return spec.Cell(
        name=TWIN, chips=1, config_name="tiny-olmoe",
        traffic_name="tiny-tokens-adamw",
        config=spec.read_json(os.path.join(here, "configs",
                                           "tiny-olmoe.json")),
        traffic=spec.read_json(os.path.join(here, "traffic",
                                            "tiny-tokens-adamw.json")),
        end_to_end=real.end_to_end, per_layer=real.per_layer,
        platform="cpu", peaks_of="TPU v5 lite")


def run(trace):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    return subprocess.run(
        [sys.executable, "-c", DRIVER, "--workload", TWIN, "--seed",
         "2147483659", "--seconds", "4", "--trace", str(trace)],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=900)


def test_the_cell_is_declared_with_its_five_metrics():
    cell = spec.load_cell(STANDS_FOR)
    assert (cell.chips, cell.config_name, cell.traffic_name) == (
        1, "olmoe-1b-7b", "tokens-2x4096-adamw")
    names = [m["name"] for m in cell.per_layer]
    assert SCOPE_METRICS | {"moe_expert_roofline"} <= set(names)
    assert {"gossip_device_ms", "flash_roofline", "loss_device_ms"
            }.isdisjoint(names)
    assert [m["name"] for m in cell.end_to_end] == [
        "throughput_per_chip", "peak_hbm_gib", "setup_s"]
    assert cell.config["num_hidden_layers"] == 1
    assert cell.traffic["batch"] == {"sequences": 2, "seq_len": 4096}
    for name in names:
        assert callable(spec.layer_metric_reader(name))


def test_twin_untraced():
    done = run(0)
    assert done.returncode == 0, done.stderr[-2000:]
    line = json.loads(done.stdout.strip().splitlines()[-1])
    assert line["correct"] is True, done.stdout[-3000:]
    assert line["failed"] == 0 and line["attempted"] > 0
    assert line["device"]["platform"] == "cpu"
    assert set(line["metrics"]) == {"throughput_per_chip", "peak_hbm_gib",
                                    "setup_s"}
    assert "compilation(s) inside the measured window" not in done.stdout
    assert "check model: ok" in done.stdout


def test_twin_traced_reads_the_scopes():
    done = run(1)
    assert done.returncode == 0, done.stderr[-2000:]
    line = json.loads(done.stdout.strip().splitlines()[-1])
    assert line["correct"] is True, done.stdout[-3000:]
    got = {k: v["value"] for k, v in line["metrics"].items()}
    assert SCOPE_METRICS <= set(got), done.stdout[-3000:]
    parts = (got["moe_route_device_ms"] + got["moe_permute_device_ms"]
             + got["moe_expert_device_ms"])
    assert 0 < parts <= got["moe_device_ms"] * (1 + 1e-9)
    assert got["moe_device_ms"] < got["grad_program_device_ms"]
    # what every cell reads: the general metrics apply by themselves
    assert {"grad_device_ms", "optim_device_ms", "device_idle_share",
            "mfu_busy", "optim_update_device_ms", "grad_program_device_ms",
            "optim_program_device_ms"} <= set(got)
    assert "moe_device_ms: the expert layer by scope" in done.stdout


# --- the roofline reader on hand-made events ----------------------------------

def _context(events, steps=2):
    trace = tr.Trace(ops={0: events}, spans=[
        tr.Event("bench.free", 0.0, 1e9)])
    return layers.Context(
        trace=trace, cell=spec.load_cell(STANDS_FOR),
        peaks=spec.peak_row("TPU v5 lite"), step_flops={}, chip=0,
        blocked=None, free=trace.stretch("free"), free_steps=steps,
        busy_s=0.0, window_s=0.0, mosaic_calls={})


def test_roofline_counts_the_calls_of_the_trace():
    ms = 1e6
    events = [
        tr.Event("bf_moe_gmm_fwd.10", 0 * ms, 4 * ms,
                 "bf16[65536,1024] custom-call(...)"),
        tr.Event("bf_moe_gmm_dlhs.4", 4 * ms, 6 * ms,
                 "bf16[65536,2048] custom-call(...)"),
        tr.Event("bf_moe_gmm_drhs.3", 6 * ms, 10 * ms,
                 "f32[64,1024,2048] custom-call(...)"),
        tr.Event("bf_flash_fwd.2", 10 * ms, 10.1 * ms,
                 "(bf16[32,4096,128], f32[32,4096]) custom-call(...)"),
        tr.Event("fusion.7", 11 * ms, 12 * ms, "bf16[8192,2048] fusion(...)"),
    ]
    peaks = spec.peak_row("TPU v5 lite")
    least = 2 * 65536 * 2048 * 1024 / peaks["bf16_flops_per_s"]   # each
    got = spec.layer_metric_reader("moe_expert_roofline")(_context(events))
    assert got == pytest.approx(100 * 3 * least / 10e-3)
    assert 0 < got < 100
    common = spec.load_module("layer_metrics/moe_common.py")
    ctx = _context(events)
    kinds = [common.product_cost(ctx, e)[0] for e in
             common.product_events(ctx)]
    assert kinds == ["rows", "rows", "weights"]
    assert common.product_cost(ctx, events[0])[1]["bytes"] \
        == 2 * (65536 * 2048 + 64 * 2048 * 1024 + 65536 * 1024)
    assert common.product_cost(ctx, events[2])[1]["bytes"] \
        == 2 * 65536 * (1024 + 2048) + 4 * 64 * 1024 * 2048
    # a program without the layer, or a result that is no product: nothing
    assert spec.layer_metric_reader("moe_expert_roofline")(
        _context(events[-1:])) is None
    odd = [tr.Event("bf_moe_gmm_fwd.1", 0, ms, "f32[7,9] custom-call(")]
    assert spec.layer_metric_reader("moe_expert_roofline")(
        _context(odd)) is None
