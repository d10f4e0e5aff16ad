"""The ``laguna-s8192-1chip`` cell's tiny twin end to end on the CPU, its five
readers, and ``benchmark/flops_laguna.py`` against hand counts.

    python3 -m pytest benchmark/selftest/test_laguna_cell_cpu.py -q   (three minutes)

``selftest/workloads.json`` is not this PR's to edit, so the twin is built
here as ``test_lfm2_cell_cpu.py`` builds its own: a ``spec.Cell`` of
``selftest/configs/tiny-laguna.json`` and
``selftest/traffic/tiny-tokens-1row-adamw.json`` with the metric lists of
``laguna-s8192-1chip``, handed to ``benchmark/run.py`` in a process of its own
(``JAX_PLATFORMS=cpu``; the flash and grouped-matmul kernels choose the
Pallas interpreter themselves off the chip).  Interpreted kernels are
ordinary instructions and no event is a kernel call, so the traced twin reads
the three scope metrics and leaves the two kernel rooflines out; those
readers run here on hand-made events of the names and shapes the program
compiled for the v5e has.  Its numbers are not device numbers.  Tier-1 runs
everything here but ``test_twin_untraced`` (``tests/
test_benchmark_selftest.py``): the traced twin runs the same checks.
"""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import flops, flops_laguna, layers, spec  # noqa: E402
from benchmark import trace_reduce as tr  # noqa: E402

TWIN = "tiny-laguna-1dev"
STANDS_FOR = "laguna-s8192-1chip"
SCOPE_METRICS = {"swa_attn_device_ms", "gated_attn_device_ms",
                 "small_moe_device_ms"}
KERNEL_ROOFLINES = {"swa_flash_roofline", "small_moe_expert_roofline"}

DRIVER = f'''
import os, sys
sys.path.insert(0, {ROOT!r})
from benchmark import spec
from benchmark.selftest.test_laguna_cell_cpu import twin_cell
find = spec.load_cell
spec.load_cell = lambda name: twin_cell() if name == {TWIN!r} else find(name)
from benchmark import run
sys.exit(run.main(sys.argv[1:]))
'''


def twin_cell() -> spec.Cell:
    real = spec.load_cell(STANDS_FOR)
    here = os.path.join(spec.HERE, "selftest")
    return spec.Cell(
        name=TWIN, chips=1, config_name="tiny-laguna",
        traffic_name="tiny-tokens-1row-adamw",
        config=spec.read_json(os.path.join(here, "configs",
                                           "tiny-laguna.json")),
        traffic=spec.read_json(os.path.join(
            here, "traffic", "tiny-tokens-1row-adamw.json")),
        end_to_end=real.end_to_end, per_layer=real.per_layer,
        platform="cpu", peaks_of="TPU v5 lite")


def run(trace):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    return subprocess.run(
        [sys.executable, "-c", DRIVER, "--workload", TWIN, "--seed",
         "2147483740", "--seconds", "4", "--trace", str(trace)],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=1200)


def test_declared_with_its_five_metrics_and_no_other_cells():
    cell = spec.load_cell(STANDS_FOR)
    assert (cell.chips, cell.config_name, cell.traffic_name) == (
        1, "laguna-xs.2", "tokens-1x8192-adamw")
    names = [m["name"] for m in cell.per_layer]
    assert SCOPE_METRICS | KERNEL_ROOFLINES <= set(names)
    assert {"gossip_device_ms", "flash_roofline", "loss_device_ms",
            "kernel_stagings", "moe_expert_roofline", "mla_device_ms",
            "gqa_flash_roofline", "hybrid_moe_device_ms"}.isdisjoint(names)
    # every metric without a list of cells is this cell's too
    bench = spec.read_json(os.path.join(spec.ROOT, "BENCHMARK.json"))
    assert {m["name"] for m in bench["per_layer"]
            if "workloads" not in m} <= set(names)
    assert [m["name"] for m in cell.end_to_end] == [
        "throughput_per_chip", "peak_hbm_gib", "setup_s"]
    assert cell.traffic["batch"] == {"sequences": 1, "seq_len": 8192}
    adamw = spec.load_cell("lfm2-s8192-1chip").traffic
    for key in ("optimizer", "programs", "pool", "order", "mixing"):
        assert cell.traffic[key] == adamw[key]
    for name in names:
        assert callable(spec.layer_metric_reader(name))
    for m in bench["per_layer"]:
        if m["name"] in SCOPE_METRICS | KERNEL_ROOFLINES:
            assert m["workloads"] == [STANDS_FOR]
            assert m["moves"] == "throughput_per_chip"
    entry = next(w for w in bench["workloads"] if w["name"] == STANDS_FOR)
    assert "75%" in entry["why"] and "256 rows" in entry["why"]
    assert len(entry["why"]) <= 200


def test_the_configuration_keeps_the_published_widths():
    config = spec.load_cell(STANDS_FOR).config
    published = {
        "model_type": "laguna", "hidden_size": 2048,
        "intermediate_size": 8192, "num_attention_heads": 48,
        "num_key_value_heads": 8, "head_dim": 128,
        "max_position_embeddings": 262144, "attention_bias": False,
        "rms_norm_eps": 1e-06, "num_experts_per_tok": 8,
        "moe_intermediate_size": 512, "shared_expert_intermediate_size": 512,
        "tie_word_embeddings": False, "gating": True, "sliding_window": 512,
        "moe_apply_router_weight_on_input": False,
        "partial_rotary_factor": 0.5, "moe_routed_scaling_factor": 2.5,
        "router_width": 256,
        "rope_parameters": {
            "full_attention": {
                "rope_theta": 500000, "rope_type": "yarn", "factor": 64,
                "original_max_position_embeddings": 4096, "beta_slow": 1,
                "beta_fast": 64, "attention_factor": 1.4158883083359672,
                "partial_rotary_factor": 0.5},
            "sliding_attention": {"rope_type": "default",
                                  "rope_theta": 10000,
                                  "partial_rotary_factor": 1},
            "original_max_position_embeddings": 4096}}
    assert {k: config[k] for k in published} == published
    # the cut: layer 0 and one whole period behind it, an eighth of the
    # experts and of the vocabulary
    assert config["layer_types"] == [
        "full_attention", "sliding_attention", "sliding_attention",
        "sliding_attention", "full_attention"]
    assert config["mlp_layer_types"] == ["dense"] + ["sparse"] * 4
    assert config["num_attention_heads_per_layer"] == [48, 64, 64, 64, 48]
    assert (config["num_hidden_layers"], config["num_experts"],
            config["vocab_size"]) == (5, 32, 12544)
    values = config["source_values"]
    assert {k: values[k] for k in ("num_hidden_layers", "num_experts",
                                   "vocab_size")} == {
        "num_hidden_layers": 40, "num_experts": 256, "vocab_size": 100352}
    assert config["num_experts"] * 8 == values["num_experts"]
    assert config["vocab_size"] * 8 == values["vocab_size"]
    assert sorted(config["reduced"]) == sorted(values)
    bench = spec.read_json(os.path.join(spec.ROOT, "BENCHMARK.json"))
    entry = next(c for c in bench["configs"] if c["name"] == "laguna-xs.2")
    assert sorted(entry["reduced"]) == sorted(config["reduced"])
    assert entry["source"] == config["source"]
    assert len(entry["why"]) <= 200
    for key in ("loss_rtol", "grad_rtol", "why"):
        assert config["model_check"][key]
    for key in ("what", "why", "stands_for", "bias"):
        assert config["cut"][key]
    # the five things the file does not say, each with its reason
    for key, mark in (("gating_type", "(a)"), ("router_scoring", "(b)"),
                      ("qk_norm_and_shared_gate", "(c)"),
                      ("auxiliary_loss", "(d)"), ("initializer", "(e)")):
        assert config["assumed"][key].startswith(mark)


# --- benchmark/flops_laguna.py against hand counts --------------------------------

def test_flops_visible_pairs_of_a_window():
    # the first 512 queries see a triangle, the other 7680 see 512 keys each
    assert flops_laguna.visible_pairs(8192, 512) \
        == 512 * 513 // 2 + 7680 * 512 == 4_063_488
    causal = 8192 * 8193 // 2
    assert causal == 33_558_528 == flops._pairs(8192, True)
    assert flops_laguna.visible_pairs(8192) == causal
    for window in (8192, 8193, 10 ** 6):     # W >= S is the causal count
        assert flops_laguna.visible_pairs(8192, window) == causal
    assert flops_laguna.visible_pairs(8192, 1) == 8192
    assert 0.121 < 4_063_488 / causal < 0.1211
    # by brute force at a small size
    for seq, window in ((9, 4), (16, 16), (5, 7), (12, 1)):
        assert flops_laguna.visible_pairs(seq, window) == sum(
            1 for i in range(seq) for j in range(seq) if 0 <= i - j < window)


def test_flops_step_by_hand():
    config = spec.load_cell(STANDS_FOR).config
    step = flops_laguna.window_moe_lm_train(config, batch=1, seq=8192)
    full = 2048 * 6144 * 2 + 2048 * 2048 + 2048 * 48
    window = 2048 * 8192 * 2 + 2048 * 2048 + 2048 * 64
    assert (full, window) == (29_458_432, 37_879_808)
    assert flops_laguna.attention_params(config, 0) == full
    assert flops_laguna.attention_params(config, 2) == window
    dense = 3 * 2048 * 8192
    # router, shared expert, the held eighth of top-8: one expert's worth
    sparse = 2048 * 256 + 3 * 2048 * 512 + 8 * (32 / 256) * 3 * 2048 * 512
    assert step["matmul_params"] == 2 * full + 3 * window + dense \
        + 4 * sparse + 2048 * 12544
    tokens = 8192
    pairs_w, pairs_f = 4_063_488, 33_558_528
    attention = 12 * 128 * (3 * 64 * pairs_w + 2 * 48 * pairs_f)
    assert step["attention"] == attention
    assert step["window_mixers"] == 3 * (6 * window * tokens
                                         + 12 * 128 * 64 * pairs_w)
    assert step["full_mixers"] == 2 * (6 * full * tokens
                                       + 12 * 128 * 48 * pairs_f)
    assert step["dense_mlp"] == 6 * dense * tokens
    assert step["experts"] == 6 * 4 * sparse * tokens
    assert step["head"] == 6 * 2048 * 12544 * tokens
    assert (step["blocks"] + step["head"] + step["attention"]
            == step["flops"])
    assert (step["window_mixers"] + step["full_mixers"] + step["experts"]
            + step["dense_mlp"] + step["head"] == step["flops"])
    # attention of both kinds with its projections: about three quarters
    share = (step["window_mixers"] + step["full_mixers"]) / step["flops"]
    assert 0.73 < share < 0.76
    # masked and not skipped, the window layers' pairs alone would be 8.3
    # times their required count
    assert pairs_f / pairs_w == pytest.approx(8.26, abs=0.01)


def test_flops_of_the_kernel_calls_by_hand():
    config = spec.load_cell(STANDS_FOR).config
    call = lambda kind, layer_type: flops_laguna.flash_kernel(  # noqa: E731
        kind, config=config, layer_type=layer_type, batch=1, seq=8192)
    fwd = call("fwd", "sliding_attention")
    assert fwd["flops"] == 4 * 128 * 4_063_488 * 64
    assert fwd["pairs"] == 4_063_488 * 64
    assert fwd["bytes"] == 64 * (4 * 8192 * 128 * 2 + 8192 * 4)
    assert call("dq", "sliding_attention")["flops"] == 6 * 128 * 4_063_488 * 64
    assert call("dkv", "sliding_attention")["flops"] \
        == 8 * 128 * 4_063_488 * 64
    # a full layer's call is flops.flash_kernel at its 48 heads
    for kind in ("fwd", "dq", "dkv"):
        want = flops.flash_kernel(kind, batch=1, seq=8192, heads=48,
                                  head_dim=128)
        got = call(kind, "full_attention")
        assert (got["flops"], got["bytes"]) == (want["flops"], want["bytes"])
    # the window's forward is compute-bound still: 0.68 ms against 0.33
    peaks = spec.peak_row("TPU v5 lite")
    seconds, bound = flops.roofline_seconds(fwd, peaks)
    assert bound == "compute" and seconds == pytest.approx(0.676e-3,
                                                           rel=0.01)
    rows = flops_laguna.grouped_product("rows", config=config, tokens=8192,
                                        inner=2048, outer=512)
    assert rows["flops"] == 2 * 8192 * 2048 * 512      # 256 rows an expert
    assert rows["bytes"] == 2 * (8192 * 2048 + 32 * 2048 * 512
                                 + 8192 * 512)
    assert flops.roofline_seconds(rows, peaks)[1] == "memory"


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
    for name in SCOPE_METRICS:
        assert 0 < got[name] < got["grad_program_device_ms"]
    bench = spec.read_json(os.path.join(spec.ROOT, "BENCHMARK.json"))
    assert {m["name"] for m in bench["per_layer"]
            if "workloads" not in m} <= set(got)
    for scope in ("bf.swa.attend", "bf.swa.gate", "bf.attn.gate", "shared",
                  "unattributed"):
        assert scope in done.stdout


# --- the roofline readers on hand-made events -------------------------------------

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
    tr.Event("bf_flash_win_fwd.3", 0 * MS, 2 * MS,
             "(bf16[64,8192,128], f32[64,8192,1]) custom-call(...)"),
    tr.Event("bf_flash_win_dq.1", 2 * MS, 5 * MS,
             "bf16[64,8192,128] custom-call(...)"),
    tr.Event("bf_flash_win_dkv.1", 5 * MS, 9 * MS,
             "(bf16[64,8192,128], bf16[64,8192,128]) custom-call(...)"),
    tr.Event("bf_flash_fwd.2", 10 * MS, 18 * MS,
             "(bf16[48,8192,128], f32[48,8192,1]) custom-call(...)"),
    tr.Event("bf_moe_gmm_fwd.2", 20 * MS, 20.5 * MS,
             "bf16[16384,512] custom-call(...)"),
    tr.Event("bf_moe_gmm_dlhs.2", 21 * MS, 21.5 * MS,
             "bf16[16384,2048] custom-call(...)"),
    tr.Event("bf_moe_gmm_drhs.1", 22 * MS, 23 * MS,
             "f32[32,2048,512] custom-call(...)"),
    tr.Event("fusion.7", 24 * MS, 25 * MS, "bf16[8192,2048] fusion(...)"),
]


def test_window_roofline_holds_each_kind_to_the_visible_pairs():
    peaks = spec.peak_row("TPU v5 lite")
    pairs = 4_063_488 * 64
    least = sum(n * 2 * 128 * pairs for n in (2, 3, 4))
    ctx = _context(EVENTS)
    got = spec.layer_metric_reader("swa_flash_roofline")(ctx)
    assert got == pytest.approx(
        100 * least / peaks["bf16_flops_per_s"] / 9e-3)
    assert 0 < got < 100
    # held to the triangle the same times would read 8.26 times as much
    assert got * 33_558_528 / 4_063_488 > 100
    common = spec.load_module("layer_metrics/laguna_common.py")
    # the full layers' calls are not the window's, nor the other way round
    assert [e.name for e, _ in common.flash_events(
        ctx, "sliding_attention")] == [e.name for e in EVENTS[:3]]
    assert [e.name for e, _ in common.flash_events(
        ctx, "full_attention")] == ["bf_flash_fwd.2"]
    assert spec.layer_metric_reader("swa_flash_roofline")(
        _context(EVENTS[3:])) is None


def test_window_tiles_are_read_from_the_counters():
    common = spec.load_module("layer_metrics/laguna_common.py")
    program = spec.load_module("layer_metrics/program_common.py")
    ctx = _context(EVENTS)
    ctx.program = program.Program([], {}, {}, {})
    assert common.window_tiles(ctx) == ""       # the parent: no counter
    name = 'kernel="bf_flash_win_fwd"'
    ctx.program = program.Program([], {}, {}, {
        f"bf_kernel_stagings_total{{{name}}}": 3.0,
        f'bf_flash_tiles_total{{{name},kind="crossed"}}': 3.0 * 64 * 23,
        f'bf_flash_tiles_total{{{name},kind="interior"}}': 0.0,
        f'bf_flash_tiles_total{{{name},kind="skipped"}}': 3.0 * 64})
    said = common.window_tiles(ctx)
    assert ("fwd 1472 crossed and 0 interior tiles computed a call (64 dead "
            "steps) for 248 Mi visible pairs") in said
    assert 4_063_488 * 64 / 2 ** 20 == 248.015625


def test_expert_roofline_counts_the_held_rows_at_512():
    peaks = spec.peak_row("TPU v5 lite")
    common = spec.load_module("layer_metrics/laguna_common.py")
    ctx = _context(EVENTS)
    kinds = [common.product_cost(ctx, e) for e in common.product_events(ctx)]
    assert [k for k, _ in kinds] == ["rows", "rows", "weights"]
    rows = 8192 * 8 * 32 // 256      # an even router's share: 256 an expert
    assert rows == 8192 == 32 * 256
    assert all(c["flops"] == 2 * rows * 2048 * 512 for _, c in kinds)
    assert kinds[0][1]["bytes"] == 2 * (rows * 2048 + 32 * 2048 * 512
                                        + rows * 512)
    assert kinds[2][1]["bytes"] == 2 * rows * (2048 + 512) \
        + 4 * 32 * 2048 * 512
    got = spec.layer_metric_reader("small_moe_expert_roofline")(ctx)
    least = sum(max(c["flops"] / peaks["bf16_flops_per_s"],
                    c["bytes"] / peaks["hbm_bytes_per_s"]) for _, c in kinds)
    assert got == pytest.approx(100 * least / 2e-3)
    assert 0 < got < 100
    odd = [tr.Event("bf_moe_gmm_fwd.1", 0, MS, "f32[7,9] custom-call(")]
    assert spec.layer_metric_reader("small_moe_expert_roofline")(
        _context(odd)) is None
    assert spec.layer_metric_reader("small_moe_expert_roofline")(
        _context(EVENTS[-1:])) is None


def test_scope_readers_sum_their_families_and_fall_silent_without_them():
    ctx = _context(EVENTS)
    ctx.xing_scope_ms = {"bf.swa.attend": 10.0, "bf.swa.qkv": 3.0,
                         "bf.swa.gate": 0.5, "bf.attn.attend": 20.0,
                         "bf.attn.gate": 0.25, "bf.attn.out": 2.0}
    assert spec.layer_metric_reader("swa_attn_device_ms")(ctx) == 13.5
    assert spec.layer_metric_reader("gated_attn_device_ms")(ctx) == 22.25
    # a program whose attention has no gate (every older cell's, and the
    # parent's): the gated reader says nothing
    ctx.xing_scope_ms = {"bf.attn.attend": 20.0, "bf.moe.route": 1.0}
    assert spec.layer_metric_reader("gated_attn_device_ms")(ctx) is None
    assert spec.layer_metric_reader("swa_attn_device_ms")(ctx) is None
    ctx.moe_scope_ms = None
    assert spec.layer_metric_reader("small_moe_device_ms")(ctx) is None
    ctx.moe_scope_ms = {"bf.moe.route": 1.0, "bf.moe.experts": 4.0,
                        "bf.moe.shared": 2.0, "bf.moe.layer": 0.5}
    assert spec.layer_metric_reader("small_moe_device_ms")(ctx) == 7.5
