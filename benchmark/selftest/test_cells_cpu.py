"""The cells' tiny twins end to end on a CPU mesh, and the gate that keeps
the real cells off it.

    python3 -m pytest benchmark/selftest/test_cells_cpu.py -q     (minutes)

Every twin runs ``benchmark/run.py`` as the driver would, in a process of its
own with ``JAX_PLATFORMS=cpu``, four virtual devices and Pallas in interpret
mode (the kernels choose it themselves off the chip).  A twin's numbers are
not device numbers: its line says ``"platform": "cpu"``.
"""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CONTRACT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}


def run(workload, *, trace=0, seconds=4):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    return subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", workload,
         "--seed", "7", "--seconds", str(seconds), "--trace", str(trace)],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=900)


def bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


@pytest.mark.parametrize("cell", [w["name"] for w in bench()["workloads"]])
def test_a_cell_needs_a_tpu(cell):
    done = run(cell)
    assert done.returncode != 0
    assert done.stdout.strip() == ""          # no metric, no result line
    assert "needs platform 'tpu'" in done.stderr


def twins():
    with open(os.path.join(ROOT, "benchmark", "selftest",
                           "workloads.json")) as f:
        return json.load(f)["workloads"]


@pytest.mark.parametrize("twin", twins(), ids=lambda t: t["name"])
def test_twin_untraced(twin):
    done = run(twin["name"])
    assert done.returncode == 0, done.stderr[-2000:]
    line = json.loads(done.stdout.strip().splitlines()[-1])
    assert set(line) == CONTRACT_KEYS
    assert line["correct"] is True, done.stdout[-3000:]
    assert line["failed"] == 0 and line["attempted"] > 0
    assert line["device"]["platform"] == "cpu"
    assert line["device"]["memory_peak_bytes"] > 0
    wanted = {m["name"] for m in bench()["end_to_end"]
              if twin["stands_for"] in m.get("workloads",
                                             [twin["stands_for"]])}
    assert set(line["metrics"]) == wanted
    for m in line["metrics"].values():
        assert m["value"] > 0 and isinstance(m["unit"], str)
    assert "compilation(s) inside the measured window" not in done.stdout


@pytest.mark.parametrize("name", ["tiny-lm-gossip-4dev", "tiny-resnet-1dev"])
def test_twin_traced(name):
    done = run(name, trace=1)
    assert done.returncode == 0, done.stderr[-2000:]
    line = json.loads(done.stdout.strip().splitlines()[-1])
    assert set(line) == CONTRACT_KEYS | {"breakdown"}
    assert line["correct"] is True, done.stdout[-3000:]
    assert 0 < line["device"]["busy_s"] <= line["device"]["window_s"]
    per_layer = {m["name"] for m in bench()["per_layer"]}
    assert set(line["metrics"]) <= per_layer
    # what every cell reads, whatever the platform
    assert {"grad_device_ms", "optim_device_ms", "optim_dispatch_ms",
            "device_idle_share", "mfu_busy"} <= set(line["metrics"])
    for key in ("device_ops", "idle_gaps"):
        assert 0 < len(line["breakdown"][key]) <= 10
