"""The ``xing4-s4096-1chip`` cell's tiny twin end to end on the CPU, and its
five readers.

    python3 -m pytest benchmark/selftest/test_xing_cell_cpu.py -q   (two minutes)

``selftest/workloads.json`` is not this PR's to edit, so the twin is built
here as ``test_moe_cell_cpu.py`` builds its own: a ``spec.Cell`` of
``selftest/configs/tiny-xing.json`` and
``selftest/traffic/tiny-tokens-1row-adamw.json`` with the metric lists of
``xing4-s4096-1chip``, handed to ``benchmark/run.py`` in a process of its own
(``JAX_PLATFORMS=cpu``; the flash and grouped-matmul kernels choose the
Pallas interpreter themselves off the chip).  Interpreted kernels are
ordinary instructions and no event is a kernel call, so the traced twin reads
the three scope metrics and leaves the two rooflines out; those readers run
here on hand-made events of the names and shapes the program compiled for
the v5e has.  Its numbers are not device numbers.
"""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import flops_mla, layers, spec  # noqa: E402
from benchmark import trace_reduce as tr  # noqa: E402

TWIN = "tiny-xing-1dev"
STANDS_FOR = "xing4-s4096-1chip"
SCOPE_METRICS = {"mla_device_ms", "mhc_device_ms", "moe_share_device_ms"}
ROOFLINES = {"mla_flash_roofline", "moe_share_expert_roofline"}

DRIVER = f'''
import os, sys
sys.path.insert(0, {ROOT!r})
from benchmark import spec
from benchmark.selftest.test_xing_cell_cpu import twin_cell
find = spec.load_cell
spec.load_cell = lambda name: twin_cell() if name == {TWIN!r} else find(name)
from benchmark import run
sys.exit(run.main(sys.argv[1:]))
'''


def twin_cell() -> spec.Cell:
    real = spec.load_cell(STANDS_FOR)
    here = os.path.join(spec.HERE, "selftest")
    return spec.Cell(
        name=TWIN, chips=1, config_name="tiny-xing",
        traffic_name="tiny-tokens-1row-adamw",
        config=spec.read_json(os.path.join(here, "configs",
                                           "tiny-xing.json")),
        traffic=spec.read_json(os.path.join(
            here, "traffic", "tiny-tokens-1row-adamw.json")),
        end_to_end=real.end_to_end, per_layer=real.per_layer,
        platform="cpu", peaks_of="TPU v5 lite")


def run(trace):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    return subprocess.run(
        [sys.executable, "-c", DRIVER, "--workload", TWIN, "--seed",
         "2147483693", "--seconds", "4", "--trace", str(trace)],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=1200)


def test_the_cell_is_declared_with_its_five_metrics():
    cell = spec.load_cell(STANDS_FOR)
    assert (cell.chips, cell.config_name, cell.traffic_name) == (
        1, "xing4.0-29b-a4b", "tokens-1x4096-adamw")
    names = [m["name"] for m in cell.per_layer]
    assert SCOPE_METRICS | ROOFLINES <= set(names)
    assert {"gossip_device_ms", "flash_roofline", "loss_device_ms",
            "moe_expert_roofline"}.isdisjoint(names)
    assert len(names) == 12 + 5
    assert [m["name"] for m in cell.end_to_end] == [
        "throughput_per_chip", "peak_hbm_gib", "setup_s"]
    assert cell.traffic["batch"] == {"sequences": 1, "seq_len": 4096}
    for name in names:
        assert callable(spec.layer_metric_reader(name))
    # no older cell reads the new metrics
    for other in ("olmoe-s4096-1chip", "lm-s16384-1chip"):
        assert (SCOPE_METRICS | ROOFLINES).isdisjoint(
            m["name"] for m in spec.load_cell(other).per_layer)


def test_the_configuration_keeps_the_published_widths():
    config = spec.load_cell(STANDS_FOR).config
    published = {
        "hidden_size": 3584, "intermediate_size": 9216,
        "moe_intermediate_size": 1024, "num_attention_heads": 32,
        "q_lora_rank": 768, "kv_lora_rank": 512, "qk_nope_head_dim": 128,
        "qk_rope_head_dim": 64, "v_head_dim": 128, "num_experts_per_tok": 4,
        "n_shared_experts": 1, "hc_mult": 4, "hc_sinkhorn_iters": 20,
        "routed_scaling_factor": 2, "router_width": 64}
    assert {k: config[k] for k in published} == published
    assert config["source_values"] == {
        "num_hidden_layers": 40, "first_k_dense_replace": 2,
        "n_routed_experts": 64, "vocab_size": 131072,
        "num_nextn_predict_layers": 1}
    assert sorted(config["reduced"]) == sorted(config["source_values"])
    bench = spec.read_json(os.path.join(spec.ROOT, "BENCHMARK.json"))
    entry = next(c for c in bench["configs"]
                 if c["name"] == "xing4.0-29b-a4b")
    assert sorted(entry["reduced"]) == sorted(config["reduced"])
    step = flops_mla.latent_moe_lm_train(config, batch=1, seq=4096)
    assert step["matmul_params"] == pytest.approx(370.2e6, rel=1e-3)
    assert step["flops"] / 4096 == pytest.approx(2.851e9, rel=1e-3)
    assert (step["blocks"] + step["head"] + step["attention"]
            == step["flops"])


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
    assert ROOFLINES.isdisjoint(got)        # interpreted kernels: no events
    for name in SCOPE_METRICS:
        assert 0 < got[name] < got["grad_program_device_ms"]
    assert {"grad_device_ms", "optim_device_ms", "device_idle_share",
            "mfu_busy", "optim_update_device_ms", "grad_program_device_ms",
            "optim_program_device_ms"} <= set(got)
    for scope in ("bf.mla.attend", "bf.mhc.sinkhorn", "shared"):
        assert scope in done.stdout


# --- the roofline readers on hand-made events ---------------------------------

def _context(events, steps=2):
    trace = tr.Trace(ops={0: events}, spans=[
        tr.Event("bench.free", 0.0, 1e9)])
    return layers.Context(
        trace=trace, cell=spec.load_cell(STANDS_FOR),
        peaks=spec.peak_row("TPU v5 lite"), step_flops={}, chip=0,
        blocked=None, free=trace.stretch("free"), free_steps=steps,
        busy_s=0.0, window_s=0.0, mosaic_calls={})


MS = 1e6
EVENTS = [
    tr.Event("bf_flash_fwd.10", 0 * MS, 2 * MS,
             "(bf16[32,4096,128], f32[32,4096,1]) custom-call(...)"),
    tr.Event("bf_flash_dq.4", 2 * MS, 5 * MS,
             "bf16[32,4096,192] custom-call(...)"),
    tr.Event("bf_flash_dkv.3", 5 * MS, 9 * MS,
             "(bf16[32,4096,192], bf16[32,4096,128]) custom-call(...)"),
    tr.Event("bf_moe_gmm_fwd.2", 10 * MS, 10.5 * MS,
             "bf16[16384,1024] custom-call(...)"),
    tr.Event("bf_moe_gmm_dlhs.2", 10.5 * MS, 11 * MS,
             "bf16[16384,3584] custom-call(...)"),
    tr.Event("bf_moe_gmm_drhs.1", 11 * MS, 12 * MS,
             "f32[8,3584,1024] custom-call(...)"),
    tr.Event("fusion.7", 12 * MS, 13 * MS, "bf16[4096,3584] fusion(...)"),
]


def test_flash_roofline_holds_each_kind_to_its_own_dims():
    peaks = spec.peak_row("TPU v5 lite")
    pairs = 4096 * 4097 // 2 * 32
    least = {"fwd": 2 * (192 + 128) * pairs,
             "dq": 2 * (2 * 192 + 128) * pairs,
             "dkv": 2 * (2 * 192 + 2 * 128) * pairs}
    got = spec.layer_metric_reader("mla_flash_roofline")(_context(EVENTS))
    assert got == pytest.approx(
        100 * sum(least.values()) / peaks["bf16_flops_per_s"] / 9e-3)
    assert 0 < got < 100
    assert spec.layer_metric_reader("mla_flash_roofline")(
        _context(EVENTS[-1:])) is None


def test_expert_roofline_counts_the_held_rows():
    peaks = spec.peak_row("TPU v5 lite")
    common = spec.load_module("layer_metrics/xing_common.py")
    ctx = _context(EVENTS)
    kinds = [common.product_cost(ctx, e) for e in common.product_events(ctx)]
    assert [k for k, _ in kinds] == ["rows", "rows", "weights"]
    rows = 4096 * 4 * 8 // 64       # an even router's share
    assert all(c["flops"] == 2 * rows * 3584 * 1024 for _, c in kinds)
    assert kinds[0][1]["bytes"] == 2 * (rows * 3584 + 8 * 3584 * 1024
                                        + rows * 1024)
    assert kinds[2][1]["bytes"] == 2 * rows * (3584 + 1024) \
        + 4 * 8 * 3584 * 1024
    got = spec.layer_metric_reader("moe_share_expert_roofline")(ctx)
    least = sum(max(c["flops"] / peaks["bf16_flops_per_s"],
                    c["bytes"] / peaks["hbm_bytes_per_s"]) for _, c in kinds)
    assert got == pytest.approx(100 * least / 2e-3)
    assert 0 < got < 100
    odd = [tr.Event("bf_moe_gmm_fwd.1", 0, MS, "f32[7,9] custom-call(")]
    assert spec.layer_metric_reader("moe_share_expert_roofline")(
        _context(odd)) is None
    assert spec.layer_metric_reader("moe_share_expert_roofline")(
        _context(EVENTS[-1:])) is None
