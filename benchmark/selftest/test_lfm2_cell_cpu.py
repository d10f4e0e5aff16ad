"""The ``lfm2-s8192-1chip`` cell's tiny twin end to end on the CPU, and its
six readers.

    python3 -m pytest benchmark/selftest/test_lfm2_cell_cpu.py -q   (two minutes)

``selftest/workloads.json`` is not this PR's to edit, so the twin is built
here as ``test_xing_cell_cpu.py`` builds its own: a ``spec.Cell`` of
``selftest/configs/tiny-lfm2.json`` and
``selftest/traffic/tiny-tokens-2row-adamw.json`` with the metric lists of
``lfm2-s8192-1chip``, handed to ``benchmark/run.py`` in a process of its own
(``JAX_PLATFORMS=cpu``; the flash and grouped-matmul kernels choose the
Pallas interpreter themselves off the chip).  Interpreted kernels are
ordinary instructions and no event is a kernel call, so the traced twin reads
the three scope metrics and the gates' roofline (a scope's time against
bytes) and leaves the two kernel rooflines out; those readers run here on
hand-made events of the names and shapes the program compiled for the v5e
has.  Its numbers are not device numbers.
"""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import flops_lfm2, layers, spec  # noqa: E402
from benchmark import trace_reduce as tr  # noqa: E402

TWIN = "tiny-lfm2-1dev"
STANDS_FOR = "lfm2-s8192-1chip"
SCOPE_METRICS = {"sconv_device_ms", "gqa_attn_device_ms",
                 "hybrid_moe_device_ms", "sconv_gate_roofline"}
KERNEL_ROOFLINES = {"gqa_flash_roofline", "hybrid_moe_expert_roofline"}

DRIVER = f'''
import os, sys
sys.path.insert(0, {ROOT!r})
from benchmark import spec
from benchmark.selftest.test_lfm2_cell_cpu import twin_cell
find = spec.load_cell
spec.load_cell = lambda name: twin_cell() if name == {TWIN!r} else find(name)
from benchmark import run
sys.exit(run.main(sys.argv[1:]))
'''


def twin_cell() -> spec.Cell:
    real = spec.load_cell(STANDS_FOR)
    here = os.path.join(spec.HERE, "selftest")
    return spec.Cell(
        name=TWIN, chips=1, config_name="tiny-lfm2",
        traffic_name="tiny-tokens-2row-adamw",
        config=spec.read_json(os.path.join(here, "configs",
                                           "tiny-lfm2.json")),
        traffic=spec.read_json(os.path.join(
            here, "traffic", "tiny-tokens-2row-adamw.json")),
        end_to_end=real.end_to_end, per_layer=real.per_layer,
        platform="cpu", peaks_of="TPU v5 lite")


def run(trace):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    return subprocess.run(
        [sys.executable, "-c", DRIVER, "--workload", TWIN, "--seed",
         "2147483711", "--seconds", "4", "--trace", str(trace)],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=1200)


def test_the_cell_is_declared_with_its_six_metrics():
    cell = spec.load_cell(STANDS_FOR)
    assert (cell.chips, cell.config_name, cell.traffic_name) == (
        1, "lfm2-24b-a2b", "tokens-2x8192-adamw")
    names = [m["name"] for m in cell.per_layer]
    assert SCOPE_METRICS | KERNEL_ROOFLINES <= set(names)
    assert {"gossip_device_ms", "flash_roofline", "loss_device_ms",
            "moe_expert_roofline", "mla_device_ms",
            "moe_share_device_ms"}.isdisjoint(names)
    assert len(names) == 12 + 6
    assert [m["name"] for m in cell.end_to_end] == [
        "throughput_per_chip", "peak_hbm_gib", "setup_s"]
    assert cell.traffic["batch"] == {"sequences": 2, "seq_len": 8192}
    adamw = spec.load_cell("xing4-s4096-1chip").traffic
    for key in ("optimizer", "programs", "pool", "order", "mixing"):
        assert cell.traffic[key] == adamw[key]
    for name in names:
        assert callable(spec.layer_metric_reader(name))
    # no older cell reads the new metrics
    for other in ("olmoe-s4096-1chip", "lm-s16384-1chip",
                  "xing4-s4096-1chip"):
        assert (SCOPE_METRICS | KERNEL_ROOFLINES).isdisjoint(
            m["name"] for m in spec.load_cell(other).per_layer)


def test_the_configuration_keeps_the_published_widths():
    config = spec.load_cell(STANDS_FOR).config
    published = {
        "conv_L_cache": 3, "conv_bias": False, "hidden_size": 2048,
        "intermediate_size": 11776, "max_position_embeddings": 128000,
        "model_type": "lfm2_moe", "moe_intermediate_size": 1536,
        "norm_eps": 1e-05, "norm_topk_prob": True,
        "num_attention_heads": 32, "num_experts_per_tok": 4,
        "num_key_value_heads": 8, "routed_scaling_factor": 1,
        "use_expert_bias": True, "router_width": 64,
        "rope_parameters": {"rope_theta": 1000000, "rope_type": "default"}}
    assert {k: config[k] for k in published} == published
    assert config["layer_types"] == ["conv", "full_attention", "conv",
                                     "conv", "conv"]
    values = config["source_values"]
    assert {k: values[k] for k in ("num_hidden_layers", "num_dense_layers",
                                   "num_experts", "vocab_size")} == {
        "num_hidden_layers": 40, "num_dense_layers": 2, "num_experts": 64,
        "vocab_size": 65536}
    assert sorted(config["reduced"]) == sorted(values)
    bench = spec.read_json(os.path.join(spec.ROOT, "BENCHMARK.json"))
    entry = next(c for c in bench["configs"] if c["name"] == "lfm2-24b-a2b")
    assert sorted(entry["reduced"]) == sorted(config["reduced"])
    assert entry["source"] == config["source"]
    for key in ("loss_rtol", "grad_rtol", "why"):
        assert config["model_check"][key]
    step = flops_lfm2.hybrid_moe_lm_train(config, batch=2, seq=8192)
    # 4 conv mixers, 1 attention mixer, the dense MLP, 4 routers with the
    # held share of top-4, the tied head over the slice
    assert step["matmul_params"] == pytest.approx(
        4 * 16.777e6 + 10.486e6 + 72.352e6 + 4 * (0.131e6 + 4.719e6)
        + 16.777e6, rel=1e-3)
    per_token = step["flops"] / 16384
    assert per_token == pytest.approx(3 * 406.6e6, rel=5e-3)
    assert (step["blocks"] + step["head"] + step["attention"]
            == step["flops"])
    assert (step["conv_mixers"] + step["attention_mixers"] + step["experts"]
            + step["dense_mlp"] + step["head"] == step["flops"])


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
    assert KERNEL_ROOFLINES.isdisjoint(got)   # interpreted kernels: no events
    for name in SCOPE_METRICS - {"sconv_gate_roofline"}:
        assert 0 < got[name] < got["grad_program_device_ms"]
    assert {"grad_device_ms", "optim_device_ms", "device_idle_share",
            "mfu_busy", "optim_update_device_ms", "grad_program_device_ms",
            "optim_program_device_ms"} <= set(got)
    for scope in ("bf.sconv.conv", "bf.attn.norm", "unattributed"):
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
    tr.Event("bf_flash_fwd.10", 0 * MS, 3 * MS,
             "(bf16[64,8192,64], f32[64,8192,1]) custom-call(...)"),
    tr.Event("bf_flash_dq.4", 3 * MS, 8 * MS,
             "bf16[64,8192,64] custom-call(...)"),
    tr.Event("bf_flash_dkv.3", 8 * MS, 14 * MS,
             "(bf16[64,8192,64], bf16[64,8192,64]) custom-call(...)"),
    tr.Event("bf_moe_gmm_fwd.2", 20 * MS, 21 * MS,
             "bf16[65536,1536] custom-call(...)"),
    tr.Event("bf_moe_gmm_dlhs.2", 21 * MS, 22 * MS,
             "bf16[65536,2048] custom-call(...)"),
    tr.Event("bf_moe_gmm_drhs.1", 22 * MS, 24 * MS,
             "f32[8,2048,1536] custom-call(...)"),
    tr.Event("fusion.7", 24 * MS, 25 * MS, "bf16[16384,2048] fusion(...)"),
]


def test_flash_roofline_holds_each_kind_to_heads_of_64():
    peaks = spec.peak_row("TPU v5 lite")
    pairs = 8192 * 8193 // 2 * 2 * 32        # two rows, 32 query heads
    least = {"fwd": 2 * 2 * 64 * pairs, "dq": 3 * 2 * 64 * pairs,
             "dkv": 4 * 2 * 64 * pairs}
    got = spec.layer_metric_reader("gqa_flash_roofline")(_context(EVENTS))
    assert got == pytest.approx(
        100 * sum(least.values()) / peaks["bf16_flops_per_s"] / 14e-3)
    assert 0 < got < 100
    assert spec.layer_metric_reader("gqa_flash_roofline")(
        _context(EVENTS[-1:])) is None


def test_expert_roofline_counts_the_held_rows_at_1536():
    peaks = spec.peak_row("TPU v5 lite")
    common = spec.load_module("layer_metrics/lfm2_common.py")
    ctx = _context(EVENTS)
    kinds = [common.product_cost(ctx, e) for e in common.product_events(ctx)]
    assert [k for k, _ in kinds] == ["rows", "rows", "weights"]
    rows = 16384 * 4 * 8 // 64       # an even router's share: 8192
    assert rows == 8192
    assert all(c["flops"] == 2 * rows * 2048 * 1536 for _, c in kinds)
    assert kinds[0][1]["bytes"] == 2 * (rows * 2048 + 8 * 2048 * 1536
                                        + rows * 1536)
    assert kinds[2][1]["bytes"] == 2 * rows * (2048 + 1536) \
        + 4 * 8 * 2048 * 1536
    got = spec.layer_metric_reader("hybrid_moe_expert_roofline")(ctx)
    least = sum(max(c["flops"] / peaks["bf16_flops_per_s"],
                    c["bytes"] / peaks["hbm_bytes_per_s"]) for _, c in kinds)
    assert got == pytest.approx(100 * least / 4e-3)
    assert 0 < got < 100
    odd = [tr.Event("bf_moe_gmm_fwd.1", 0, MS, "f32[7,9] custom-call(")]
    assert spec.layer_metric_reader("hybrid_moe_expert_roofline")(
        _context(odd)) is None
    assert spec.layer_metric_reader("hybrid_moe_expert_roofline")(
        _context(EVENTS[-1:])) is None


def test_gate_roofline_counts_forward_recompute_and_transpose():
    """Four conv layers, each 4 + 4 + 7 passes over 16384 x 2048 bfloat16
    values; the reader divides by the time under ``bf.sconv.conv``."""
    peaks = spec.peak_row("TPU v5 lite")
    common = spec.load_module("layer_metrics/lfm2_common.py")
    ctx = _context(EVENTS)
    least = 4 * 15 * 16384 * 2048 * 2 / peaks["hbm_bytes_per_s"]
    assert common.sconv_gate_least_s(ctx) == pytest.approx(least)
    cost = flops_lfm2.sconv_gate("fwd", tokens=16384, config=ctx.cell.config)
    assert cost["flops"] / cost["bytes"] == 1.0     # far under the ridge
    ctx.xing_scope_ms = {"bf.sconv.conv": 10.0, "bf.sconv.in": 3.0}
    assert spec.layer_metric_reader("sconv_gate_roofline")(ctx) \
        == pytest.approx(100 * least * 1e3 / 10.0)
    assert spec.layer_metric_reader("sconv_device_ms")(ctx) == 13.0
    ctx.xing_scope_ms = {"bf.moe.route": 1.0}
    assert spec.layer_metric_reader("sconv_gate_roofline")(ctx) is None
    assert spec.layer_metric_reader("sconv_device_ms")(ctx) is None
    assert spec.layer_metric_reader("gqa_attn_device_ms")(ctx) is None
