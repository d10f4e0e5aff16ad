"""The ``twotower-s8192-1chip`` cell's tiny twin end to end on the CPU, its
five readers, and ``benchmark/flops_twotower.py`` against hand counts.

    python3 -m pytest benchmark/selftest/test_twotower_cell_cpu.py -q   (three minutes)

``selftest/workloads.json`` is not this PR's to edit, so the twin is built
here as ``test_laguna_cell_cpu.py`` builds its own: a ``spec.Cell`` of
``selftest/configs/tiny-twotower.json`` and
``selftest/traffic/tiny-tokens-1row-adamw.json`` with the metric lists of
``twotower-s8192-1chip``, handed to ``benchmark/run.py`` in a process of its
own (``JAX_PLATFORMS=cpu``; the flash and grouped-matmul kernels choose the
Pallas interpreter themselves off the chip; the scan is plain ``jax.numpy``
on every platform).  Interpreted kernels are ordinary instructions and no
event is a kernel call, so the traced twin reads the three scope metrics and
``ssm_scan_roofline`` (a scope's time against the scan's cost) and leaves
the grouped products' roofline out; that reader runs here on hand-made
events of the names and shapes the program compiled for the v5e has.  Its
numbers are not device numbers.  Tier-1 runs everything here but
``test_twin_untraced`` (``tests/test_benchmark_selftest.py``): the traced
twin runs the same checks.
"""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import flops, flops_twotower, layers, spec  # noqa: E402
from benchmark import trace_reduce as tr  # noqa: E402

TWIN = "tiny-twotower-1dev"
STANDS_FOR = "twotower-s8192-1chip"
SCOPE_METRICS = {"ssm_device_ms", "relu2_moe_device_ms",
                 "kv2_attn_device_ms", "ssm_scan_roofline"}
KERNEL_ROOFLINES = {"relu2_moe_expert_roofline"}
# six seeds of the twin read 0.25 to 0.90 on their worst gradient leaf (the
# toy's model_check.why); this one reads 0.25
SEED = 2147483743

DRIVER = f'''
import os, sys
sys.path.insert(0, {ROOT!r})
from benchmark import spec
from benchmark.selftest.test_twotower_cell_cpu import twin_cell
find = spec.load_cell
spec.load_cell = lambda name: twin_cell() if name == {TWIN!r} else find(name)
from benchmark import run
sys.exit(run.main(sys.argv[1:]))
'''


def twin_cell() -> spec.Cell:
    real = spec.load_cell(STANDS_FOR)
    here = os.path.join(spec.HERE, "selftest")
    return spec.Cell(
        name=TWIN, chips=1, config_name="tiny-twotower",
        traffic_name="tiny-tokens-1row-adamw",
        config=spec.read_json(os.path.join(here, "configs",
                                           "tiny-twotower.json")),
        traffic=spec.read_json(os.path.join(
            here, "traffic", "tiny-tokens-1row-adamw.json")),
        end_to_end=real.end_to_end, per_layer=real.per_layer,
        platform="cpu", peaks_of="TPU v5 lite")


def run(trace):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    return subprocess.run(
        [sys.executable, "-c", DRIVER, "--workload", TWIN, "--seed",
         str(SEED), "--seconds", "4", "--trace", str(trace)],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=1200)


def test_declared_with_its_five_metrics_and_no_other_cells():
    cell = spec.load_cell(STANDS_FOR)
    assert (cell.chips, cell.config_name, cell.traffic_name) == (
        1, "nemotron-twotower-30b-a3b", "tokens-1x8192-adamw")
    names = [m["name"] for m in cell.per_layer]
    assert SCOPE_METRICS | KERNEL_ROOFLINES <= set(names)
    assert {"gossip_device_ms", "flash_roofline", "loss_device_ms",
            "kernel_stagings", "moe_expert_roofline", "mla_device_ms",
            "gqa_flash_roofline", "hybrid_moe_device_ms",
            "gated_attn_device_ms", "small_moe_device_ms"}.isdisjoint(names)
    # every metric without a list of cells is this cell's too
    bench = spec.read_json(os.path.join(spec.ROOT, "BENCHMARK.json"))
    assert {m["name"] for m in bench["per_layer"]
            if "workloads" not in m} <= set(names)
    assert [m["name"] for m in cell.end_to_end] == [
        "throughput_per_chip", "peak_hbm_gib", "setup_s"]
    # the traffic is laguna-s8192-1chip's file, unchanged
    assert cell.traffic == spec.load_cell("laguna-s8192-1chip").traffic
    assert cell.traffic["batch"] == {"sequences": 1, "seq_len": 8192}
    for name in names:
        assert callable(spec.layer_metric_reader(name))
    for m in bench["per_layer"]:
        if m["name"] in SCOPE_METRICS | KERNEL_ROOFLINES:
            assert m["workloads"] == [STANDS_FOR]
            assert m["moves"] == "throughput_per_chip"
            assert m["source"] == "device_trace"
    entry = next(w for w in bench["workloads"] if w["name"] == STANDS_FOR)
    assert "45%" in entry["why"] and "384 rows" in entry["why"]
    assert len(entry["why"]) <= 200
    # appended: the last cell, the last configuration, the last five metrics
    assert bench["workloads"][-1] is entry
    assert bench["configs"][-1]["name"] == cell.config_name
    assert [m["name"] for m in bench["per_layer"][-5:]] == [
        "ssm_device_ms", "ssm_scan_roofline", "relu2_moe_device_ms",
        "relu2_moe_expert_roofline", "kv2_attn_device_ms"]


# the catalog row's ``config`` (model-configs guide, architectures.jsonl):
# every number under its own key
PUBLISHED = {
    "attention_bias": False, "chunk_size": 128, "conv_kernel": 4,
    "expand": 2, "head_dim": 128, "hidden_size": 2688,
    "intermediate_size": 1856, "layer_norm_epsilon": 1e-05,
    "mamba_head_dim": 64, "mamba_hidden_act": "silu", "mamba_num_heads": 64,
    "mamba_proj_bias": False, "max_position_embeddings": 262144,
    "mlp_bias": False, "mlp_hidden_act": "relu2",
    "model_type": "nemotron_h", "moe_intermediate_size": 1856,
    "moe_shared_expert_intermediate_size": 3712, "n_group": 1,
    "n_groups": 8, "n_shared_experts": 1, "norm_eps": 1e-05,
    "norm_topk_prob": True, "num_attention_heads": 32,
    "num_experts_per_tok": 6, "num_key_value_heads": 2,
    "num_logits_to_keep": 1, "partial_rotary_factor": 1,
    "rescale_prenorm_residual": True, "residual_in_fp32": False,
    "rope_theta": 10000, "routed_scaling_factor": 2.5,
    "sliding_window": None, "ssm_state_size": 128,
    "tie_word_embeddings": False, "time_step_floor": 0.0001,
    "time_step_limit": [0, None], "time_step_max": 0.1,
    "time_step_min": 0.001, "topk_group": 1, "use_bias": False,
    "use_conv_bias": True, "use_mamba_kernels": True}
PATTERN = "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"


def test_the_configuration_keeps_the_published_widths():
    config = spec.load_cell(STANDS_FOR).config
    assert {k: config[k] for k in PUBLISHED} == PUBLISHED
    # the cut: the first nine blocks, a sixteenth of the experts, an eighth
    # of the vocabulary
    assert len(PATTERN) == 52 and (PATTERN.count("M"), PATTERN.count("E"),
                                   PATTERN.count("*")) == (23, 23, 6)
    assert config["hybrid_override_pattern"] == PATTERN[:9] == "MEMEM*EME"
    assert (config["num_hidden_layers"], config["n_routed_experts"],
            config["vocab_size"], config["router_width"]) == (9, 8, 16384,
                                                              128)
    values = config["source_values"]
    assert {k: values[k] for k in ("num_hidden_layers", "n_routed_experts",
                                   "vocab_size")} == {
        "num_hidden_layers": 52, "n_routed_experts": 128,
        "vocab_size": 131072}
    assert values["hybrid_override_pattern"].startswith(PATTERN + " ")
    assert config["n_routed_experts"] * 16 == values["n_routed_experts"]
    assert config["vocab_size"] * 8 == values["vocab_size"]
    assert sorted(config["reduced"]) == sorted(values)
    bench = spec.read_json(os.path.join(spec.ROOT, "BENCHMARK.json"))
    entry = next(c for c in bench["configs"]
                 if c["name"] == "nemotron-twotower-30b-a3b")
    assert sorted(entry["reduced"]) == sorted(config["reduced"])
    assert entry["source"] == config["source"]
    assert entry["file"] == "benchmark/configs/nemotron-twotower-30b-a3b.json"
    assert len(entry["why"]) <= 200
    # no width among the reduced keys
    assert not any(k.endswith(("_dim", "_rank", "_size")) and k != "vocab_size"
                   for k in config["reduced"])
    for key in ("loss_rtol", "grad_rtol", "why"):
        assert config["model_check"][key]
    for key in ("what", "why", "stands_for", "bias"):
        assert config["cut"][key]
    assert "sixteen chips" in config["cut"]["stands_for"]
    # what the file does not say, each with its reason; no positional
    # encoding is the first
    for key, mark in (("pos_encoding", "(a)"), ("router_scoring", "(b)"),
                      ("router_bias_update_rate", "(c)"),
                      ("shared_expert", "(d)"), ("gated_norm", "(e)"),
                      ("initializer", "(f)")):
        assert config["assumed"][key].startswith(mark)
    assert config["pos_encoding"] == "none"
    # the denoiser tower and the diffusion objective are named as left out
    for text in (config["cut"]["what"], config["departures"][0]):
        assert "denoiser" in text and "diffusion" in text


# --- benchmark/flops_twotower.py against hand counts ----------------------------

def test_flops_step_by_hand():
    config = spec.load_cell(STANDS_FOR).config
    step = flops_twotower.ssm_moe_lm_train(config, batch=1, seq=8192)
    mamba = 2688 * 10304 + 4096 * 2688
    attn = 2688 * 4096 * 2 + 2688 * 512
    assert (mamba, attn) == (38_707_200, 23_396_352)
    assert flops_twotower.mamba_params(config) == mamba
    assert flops_twotower.attention_params(config) == attn
    # router, the shared expert's two matrices, the held sixteenth of top-6
    sparse = 2688 * 128 + 2 * 2688 * 3712 + 6 * (8 / 128) * 2 * 2688 * 1856
    assert flops_twotower.expert_block_params(config) == sparse
    assert step["matmul_params"] == 4 * mamba + 4 * sparse + attn \
        + 2688 * 16384
    tokens = 8192
    # a chunk of 128: 8256 pairs at or under its diagonal
    pairs = 128 * 129 // 2
    chunk = 8 * 2 * pairs * 128 + 64 * (2 * pairs * 64 + 4 * 128 * 64 * 128)
    fwd = flops_twotower.ssd_scan("fwd", config=config, tokens=tokens)
    assert fwd["flops"] == 64 * chunk
    assert flops_twotower.ssd_scan("bwd", config=config,
                                   tokens=tokens)["flops"] == 2 * 64 * chunk
    assert step["scan"] == 4 * 3 * 64 * chunk
    causal = 8192 * 8193 // 2
    assert step["attention"] == 12 * 128 * 32 * causal
    assert step["mamba_mixers"] == 6 * 4 * mamba * tokens + step["scan"]
    assert step["experts"] == 6 * 4 * sparse * tokens
    assert step["head"] == 6 * 2688 * 16384 * tokens
    assert (step["blocks"] + step["head"] + step["attention"]
            == step["flops"])
    assert (step["mamba_mixers"] + step["attention_mixers"] + step["experts"]
            + step["head"] == step["flops"])
    # the Mamba-2 blocks are 45% of the arithmetic, attention 16%
    assert 0.44 < step["mamba_mixers"] / step["flops"] < 0.46
    assert 0.15 < step["attention_mixers"] / step["flops"] < 0.17
    # 2.15 GFLOP a token: three times the forward's 717 MFLOP
    assert step["flops"] / tokens / 3 == pytest.approx(716e6, rel=0.01)


def test_flops_of_the_scan_and_the_kernel_calls_by_hand():
    config = spec.load_cell(STANDS_FOR).config
    peaks = spec.peak_row("TPU v5 lite")
    fwd = flops_twotower.ssd_scan("fwd", config=config, tokens=8192)
    # x, B, C bfloat16 and dt float32 read, y written
    assert fwd["bytes"] == 8192 * ((4096 + 2048) * 2 + 64 * 4) + 8192 * 4096 * 2
    bwd = flops_twotower.ssd_scan("bwd", config=config, tokens=8192)
    assert bwd["bytes"] == 2 * 8192 * ((4096 + 2048) * 2 + 64 * 4) \
        + 8192 * 4096 * 2
    seconds, bound = flops.roofline_seconds(fwd, peaks)
    assert bound == "memory" and seconds == pytest.approx(0.207e-3, rel=0.01)
    with pytest.raises(ValueError, match="kind"):
        flops_twotower.ssd_scan("dq", config=config, tokens=8192)
    # a tail is a chunk
    assert flops_twotower.ssd_scan("fwd", config=config, tokens=8193)[
        "flops"] == fwd["flops"] * 65 // 64
    for kind in ("fwd", "dq", "dkv"):
        want = flops.flash_kernel(kind, batch=1, seq=8192, heads=32,
                                  head_dim=128)
        assert flops_twotower.flash_kernel(kind, config=config, batch=1,
                                           seq=8192) == want
    rows = flops_twotower.grouped_product("rows", config=config, tokens=8192,
                                          inner=2688, outer=1856)
    assert rows["flops"] == 2 * 3072 * 2688 * 1856      # 384 rows an expert
    assert rows["bytes"] == 2 * (3072 * 2688 + 8 * 2688 * 1856
                                 + 3072 * 1856)
    # 384 rows an expert are just over the 241 at which the matrices'
    # bytes stop setting the bound: 0.155 ms of products, 0.131 of bytes
    seconds, bound = flops.roofline_seconds(rows, peaks)
    assert bound == "compute" and seconds == pytest.approx(0.1556e-3,
                                                           rel=0.01)


# --- the twin ---------------------------------------------------------------------------

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


def test_traced_twin_runs_the_checks_and_reads_the_scopes():
    done = run(1)
    assert done.returncode == 0, done.stderr[-2000:]
    line = json.loads(done.stdout.strip().splitlines()[-1])
    assert line["correct"] is True, done.stdout[-3000:]
    got = {k: v["value"] for k, v in line["metrics"].items()}
    assert SCOPE_METRICS <= set(got), done.stdout[-3000:]
    assert KERNEL_ROOFLINES.isdisjoint(got)   # interpreted kernels: no events
    for name in SCOPE_METRICS - {"ssm_scan_roofline"}:
        assert 0 < got[name] < got["grad_program_device_ms"]
    assert got["ssm_scan_roofline"] > 0
    bench = spec.read_json(os.path.join(spec.ROOT, "BENCHMARK.json"))
    assert {m["name"] for m in bench["per_layer"]
            if "workloads" not in m} <= set(got)
    for said in ("bf.ssm.in", "bf.ssm.conv", "bf.ssm.scan", "bf.ssm.norm",
                 "bf.ssm.out", "bf.attn.attend", "shared", "unattributed",
                 "bf_ssm_chunks_total"):
        assert said in done.stdout


# --- the readers on hand-made events and scopes ---------------------------------------

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
    tr.Event("bf_flash_fwd.2", 0 * MS, 6 * MS,
             "(bf16[32,8192,128], f32[32,8192,1]) custom-call(...)"),
    tr.Event("bf_flash_dq.1", 6 * MS, 13 * MS,
             "bf16[32,8192,128] custom-call(...)"),
    tr.Event("bf_moe_gmm_fwd.2", 20 * MS, 20.5 * MS,
             "bf16[6144,1856] custom-call(...)"),
    tr.Event("bf_moe_gmm_dlhs.2", 21 * MS, 21.5 * MS,
             "bf16[6144,2688] custom-call(...)"),
    tr.Event("bf_moe_gmm_drhs.1", 22 * MS, 23 * MS,
             "f32[8,2688,1856] custom-call(...)"),
    tr.Event("fusion.7", 24 * MS, 25 * MS, "bf16[8192,2688] fusion(...)"),
]


def test_expert_roofline_counts_the_held_rows_at_1856():
    peaks = spec.peak_row("TPU v5 lite")
    common = spec.load_module("layer_metrics/twotower_common.py")
    ctx = _context(EVENTS)
    kinds = [common.product_cost(ctx, e) for e in common.product_events(ctx)]
    assert [k for k, _ in kinds] == ["rows", "rows", "weights"]
    rows = 8192 * 6 * 8 // 128      # an even router's share: 384 an expert
    assert rows == 3072 == 8 * 384
    assert all(c["flops"] == 2 * rows * 2688 * 1856 for _, c in kinds)
    # the reader's cost is the configuration's own cost function's
    config = ctx.cell.config
    assert kinds[0][1] == flops_twotower.grouped_product(
        "rows", config=config, tokens=8192, inner=2688, outer=1856)
    assert kinds[2][1] == flops_twotower.grouped_product(
        "weights", config=config, tokens=8192, inner=2688, outer=1856,
        out_itemsize=4)
    assert kinds[0][1]["bytes"] == 2 * (rows * 2688 + 8 * 2688 * 1856
                                        + rows * 1856)
    assert kinds[2][1]["bytes"] == 2 * rows * (2688 + 1856) \
        + 4 * 8 * 2688 * 1856
    got = spec.layer_metric_reader("relu2_moe_expert_roofline")(ctx)
    least = sum(max(c["flops"] / peaks["bf16_flops_per_s"],
                    c["bytes"] / peaks["hbm_bytes_per_s"]) for _, c in kinds)
    assert got == pytest.approx(100 * least / 2e-3)
    assert 0 < got < 100
    odd = [tr.Event("bf_moe_gmm_fwd.1", 0, MS, "f32[7,9] custom-call(")]
    assert spec.layer_metric_reader("relu2_moe_expert_roofline")(
        _context(odd)) is None
    assert spec.layer_metric_reader("relu2_moe_expert_roofline")(
        _context(EVENTS[-1:])) is None


def test_scan_roofline_holds_the_scope_to_three_passes_a_block():
    peaks = spec.peak_row("TPU v5 lite")
    config = spec.load_cell(STANDS_FOR).config
    ctx = _context(EVENTS)
    ctx.xing_scope_ms = {"bf.ssm.scan": 40.0, "bf.ssm.in": 50.0}
    cost = {k: flops_twotower.ssd_scan(k, config=config, tokens=8192)
            for k in ("fwd", "bwd")}
    least = 4 * (2 * cost["fwd"]["bytes"] + cost["bwd"]["bytes"]) \
        / peaks["hbm_bytes_per_s"]
    got = spec.layer_metric_reader("ssm_scan_roofline")(ctx)
    assert got == pytest.approx(100 * least * 1e3 / 40.0)
    assert 0 < got < 100
    ctx.xing_scope_ms = {"bf.attn.attend": 20.0}
    assert spec.layer_metric_reader("ssm_scan_roofline")(ctx) is None


def test_scope_readers_sum_their_families_and_fall_silent_without_them():
    program = spec.load_module("layer_metrics/program_common.py")
    ctx = _context(EVENTS)
    ctx.program = program.Program([], {}, {}, {"bf_ssm_chunks_total": 512.0})
    ctx.xing_scope_ms = {"bf.ssm.in": 40.0, "bf.ssm.conv": 6.0,
                         "bf.ssm.scan": 30.0, "bf.ssm.norm": 4.0,
                         "bf.ssm.out": 16.0, "bf.attn.attend": 20.0,
                         "bf.attn.qkv": 5.0, "bf.attn.out": 4.0}
    assert spec.layer_metric_reader("ssm_device_ms")(ctx) == 96.0
    assert spec.layer_metric_reader("kv2_attn_device_ms")(ctx) == 29.0
    # a program without these scopes (the parent's): both say nothing
    ctx.xing_scope_ms = {"bf.moe.route": 1.0}
    assert spec.layer_metric_reader("ssm_device_ms")(ctx) is None
    assert spec.layer_metric_reader("kv2_attn_device_ms")(ctx) is None
    ctx.moe_scope_ms = None
    assert spec.layer_metric_reader("relu2_moe_device_ms")(ctx) is None
    ctx.moe_scope_ms = {"bf.moe.route": 1.0, "bf.moe.experts": 4.0,
                        "bf.moe.shared": 2.0, "bf.moe.layer": 0.5}
    assert spec.layer_metric_reader("relu2_moe_device_ms")(ctx) == 7.5


def test_the_causal_kernels_share_is_read_at_32_heads():
    peaks = spec.peak_row("TPU v5 lite")
    common = spec.load_module("layer_metrics/twotower_common.py")
    share, taken_ms = common.flash_share(_context(EVENTS), "test")
    pairs = 8192 * 8193 // 2
    least = (2 + 3) * 2 * 128 * pairs * 32 / peaks["bf16_flops_per_s"]
    assert share == pytest.approx(100 * least / 13e-3)
    assert taken_ms == pytest.approx(13.0 / 2)
    assert common.flash_share(_context(EVENTS[2:]), "test") is None
