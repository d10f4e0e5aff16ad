"""The ``kanana2-packed-s8192-1chip`` cell's tiny twin end to end on the
CPU, its four readers, and ``benchmark/flops_kanana.py`` against hand counts.

    python3 -m pytest benchmark/selftest/test_kanana_cell_cpu.py -q   (two minutes)

``selftest/workloads.json`` is not this PR's to edit, so the twin
(``tiny-kanana2-packed-1dev``) is built here as ``test_twotower_cell_cpu.py``
builds its own: a ``spec.Cell`` of ``selftest/configs/tiny-kanana2.json`` and
``selftest/traffic/tiny-tokens-packed-adamw.json`` (one row of 256 tokens
packed from six documents) with the metric lists of
``kanana2-packed-s8192-1chip``, handed to ``benchmark/run.py`` in a process of
its own (``JAX_PLATFORMS=cpu``; the flash and grouped-matmul kernels choose
the Pallas interpreter themselves off the chip).  Interpreted kernels are
ordinary instructions and no event is a kernel call, so the traced twin reads
the two scope metrics and leaves the two rooflines out; those readers run
here on hand-made events of the names and shapes the program compiled for the
v5e has.  Its numbers are not device numbers.  Tier-1 runs everything here
but ``test_twin_untraced`` (``tests/test_benchmark_selftest.py``): the traced
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

from benchmark import flops, flops_kanana, layers, spec  # noqa: E402
from benchmark import trace_reduce as tr  # noqa: E402

TWIN = "tiny-kanana2-packed-1dev"
STANDS_FOR = "kanana2-packed-s8192-1chip"
SCOPE_METRICS = {"packed_mla_device_ms", "eighth_moe_device_ms"}
KERNEL_ROOFLINES = {"packed_flash_roofline", "eighth_moe_expert_roofline"}
DOCUMENTS = [2961, 1734, 1207, 811, 562, 377, 243, 161, 89, 47]
# six seeds of the twin read 0.06 to 0.65 on their worst gradient leaf (the
# toy's model_check.why: with 2 of 16 experts held few tokens carry a held
# expert's gradient, and a flipped choice moves a large share of it); this
# one reads 0.06
SEED = 2147483693

DRIVER = f'''
import os, sys
sys.path.insert(0, {ROOT!r})
from benchmark import spec
from benchmark.selftest.test_kanana_cell_cpu import twin_cell
find = spec.load_cell
spec.load_cell = lambda name: twin_cell() if name == {TWIN!r} else find(name)
from benchmark import run
sys.exit(run.main(sys.argv[1:]))
'''


def twin_cell() -> spec.Cell:
    real = spec.load_cell(STANDS_FOR)
    here = os.path.join(spec.HERE, "selftest")
    return spec.Cell(
        name=TWIN, chips=1, config_name="tiny-kanana2",
        traffic_name="tiny-tokens-packed-adamw",
        config=spec.read_json(os.path.join(here, "configs",
                                           "tiny-kanana2.json")),
        traffic=spec.read_json(os.path.join(
            here, "traffic", "tiny-tokens-packed-adamw.json")),
        end_to_end=real.end_to_end, per_layer=real.per_layer,
        platform="cpu", peaks_of="TPU v5 lite")


def run(trace):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    return subprocess.run(
        [sys.executable, "-c", DRIVER, "--workload", TWIN, "--seed",
         str(SEED), "--seconds", "4", "--trace", str(trace)],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=1200)


def test_declared_with_its_four_metrics_and_no_other_cells():
    cell = spec.load_cell(STANDS_FOR)
    assert (cell.chips, cell.config_name, cell.traffic_name) == (
        1, "kanana-2-30b-a3b", "tokens-1x8192-packed-adamw")
    names = [m["name"] for m in cell.per_layer]
    assert SCOPE_METRICS | KERNEL_ROOFLINES <= set(names)
    assert {"gossip_device_ms", "flash_roofline", "loss_device_ms",
            "kernel_stagings", "moe_expert_roofline", "mla_device_ms",
            "mla_flash_roofline", "moe_share_device_ms",
            "gqa_flash_roofline", "hybrid_moe_device_ms",
            "gated_attn_device_ms", "small_moe_device_ms",
            "ssm_device_ms", "kv2_attn_device_ms"}.isdisjoint(names)
    # every metric without a list of cells is this cell's too
    bench = spec.read_json(os.path.join(spec.ROOT, "BENCHMARK.json"))
    assert {m["name"] for m in bench["per_layer"]
            if "workloads" not in m} <= set(names)
    assert [m["name"] for m in cell.end_to_end] == [
        "throughput_per_chip", "peak_hbm_gib", "setup_s"]
    assert cell.traffic["batch"] == {"sequences": 1, "seq_len": 8192,
                                     "documents": DOCUMENTS}
    # but for the documents the traffic is laguna-s8192-1chip's
    other = spec.load_cell("laguna-s8192-1chip").traffic
    for key in ("pool", "optimizer", "order", "mixing", "programs"):
        assert cell.traffic[key] == other[key], key
    for name in names:
        assert callable(spec.layer_metric_reader(name))
    for m in bench["per_layer"]:
        if m["name"] in SCOPE_METRICS | KERNEL_ROOFLINES:
            assert m["workloads"] == [STANDS_FOR]
            assert m["moves"] == "throughput_per_chip"
            assert m["source"] == "device_trace"
    # no older cell reads the new metrics
    for other in ("xing4-s4096-1chip", "twotower-s8192-1chip"):
        assert (SCOPE_METRICS | KERNEL_ROOFLINES).isdisjoint(
            m["name"] for m in spec.load_cell(other).per_layer)
    entry = next(w for w in bench["workloads"] if w["name"] == STANDS_FOR)
    assert "21.5%" in entry["why"] and "384 rows" in entry["why"]
    assert len(entry["why"]) <= 200
    # appended behind what PR 42 left: the ninth cell, the eighth
    # configuration, four metrics from the fifty-first on (by position, so
    # that the next PR's appended entries fail nothing here)
    assert bench["workloads"][8] is entry
    assert bench["configs"][7]["name"] == cell.config_name
    assert [m["name"] for m in bench["per_layer"][50:54]] == [
        "packed_mla_device_ms", "packed_flash_roofline",
        "eighth_moe_device_ms", "eighth_moe_expert_roofline"]


# the catalog row's ``config`` (model-configs guide, architectures.jsonl):
# every number under its own key
PUBLISHED = {
    "attention_bias": False, "first_k_dense_replace": 1, "head_dim": 64,
    "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 6144,
    "kv_lora_rank": 512, "max_position_embeddings": 32768,
    "model_type": "deepseek_v3", "moe_intermediate_size": 768,
    "moe_layer_freq": 1, "n_group": 1, "n_shared_experts": 2,
    "norm_topk_prob": True, "num_attention_heads": 32,
    "num_experts_per_tok": 6, "num_key_value_heads": 32,
    "q_lora_rank": None, "qk_head_dim": 192, "qk_nope_head_dim": 128,
    "qk_rope_head_dim": 64, "rms_norm_eps": 1e-06, "rope_interleave": True,
    "rope_scaling": None, "rope_theta": 1000000,
    "routed_scaling_factor": 2.448, "scoring_func": "sigmoid",
    "tie_word_embeddings": False, "topk_group": 1,
    "topk_method": "noaux_tc", "v_head_dim": 128}


def test_the_configuration_keeps_the_published_widths():
    config = spec.load_cell(STANDS_FOR).config
    assert {k: config[k] for k in PUBLISHED} == PUBLISHED
    # the cut: six layers, an eighth of the experts and of the vocabulary
    assert (config["num_hidden_layers"], config["n_routed_experts"],
            config["vocab_size"], config["router_width"],
            config["experts_first"]) == (6, 16, 16032, 128, 0)
    assert config["source_values"] == {
        "num_hidden_layers": 48, "n_routed_experts": 128,
        "vocab_size": 128256}
    assert config["n_routed_experts"] * 8 == 128
    assert config["vocab_size"] * 8 == 128256
    assert sorted(config["reduced"]) == sorted(config["source_values"])
    bench = spec.read_json(os.path.join(spec.ROOT, "BENCHMARK.json"))
    entry = next(c for c in bench["configs"]
                 if c["name"] == "kanana-2-30b-a3b")
    assert sorted(entry["reduced"]) == sorted(config["reduced"])
    assert entry["source"] == config["source"]
    assert entry["file"] == "benchmark/configs/kanana-2-30b-a3b.json"
    assert len(entry["why"]) <= 200
    # no width among the reduced keys
    assert not any(k.endswith(("_dim", "_rank", "_size")) and k != "vocab_size"
                   for k in config["reduced"])
    for key in ("loss_rtol", "grad_rtol", "why"):
        assert config["model_check"][key]
    for key in ("what", "why", "stands_for", "bias"):
        assert config["cut"][key]
    assert "eight chips" in config["cut"]["stands_for"]
    for key in ("router_width", "router_bias_update_rate", "auxiliary_loss",
                "loss_mask", "head_dim", "shared_experts", "initializer",
                "parameter_dtype"):
        assert config["assumed"][key]
    assert any("rope_interleave" in d for d in config["departures"])
    # the model takes its sizes from the source's keys, none by a literal
    m = config["model"]
    assert set(m["args"]) == {"pos_encoding", "mlp", "remat", "dtype"}
    assert m["from_source"]["q_lora_rank"] == "q_lora_rank"
    assert "hyper_streams" not in m["from_source"]


# --- benchmark/flops_kanana.py against hand counts --------------------------------

def test_flops_step_by_hand():
    config = spec.load_cell(STANDS_FOR).config
    step = flops_kanana.packed_latent_moe_lm_train(config, batch=1,
                                                   documents=DOCUMENTS)
    attn = 2048 * 6144 + 2048 * 576 + 512 * 8192 + 4096 * 2048
    assert attn == 26_345_472 == flops_kanana.latent_attention_params(config)
    expert = 3 * 2048 * 768
    sparse = 2048 * 128 + 2 * expert + 6 * (16 / 128) * expert
    assert flops_kanana.expert_layer_params(config) == sparse
    dense = 3 * 2048 * 6144
    assert step["matmul_params"] == 6 * attn + dense + 5 * sparse \
        + 2048 * 16032
    pairs = sum(n * (n + 1) // 2 for n in DOCUMENTS)
    assert pairs == 7_225_056 == flops_kanana.visible_pairs(DOCUMENTS)
    assert pairs / (8192 * 8193 // 2) == pytest.approx(0.215, abs=5e-4)
    assert step["attention"] == 6 * (192 + 128) * 32 * 6 * pairs
    assert step["head"] == 6 * 2048 * 16032 * 8192
    assert step["dense_mlp"] == 6 * dense * 8192
    assert step["experts"] == 6 * 5 * sparse * 8192
    assert (step["blocks"] + step["head"] + step["attention"]
            == step["flops"])
    assert (step["latent_attention"] + step["experts"] + step["dense_mlp"]
            + step["head"] == step["flops"])
    # 17.2 TFLOP a step; latent attention 61%, the experts 19%, the dense
    # layer 11%, the head 9%
    assert step["flops"] == pytest.approx(17.16e12, rel=1e-3)
    for part, share in (("latent_attention", 0.61), ("experts", 0.19),
                        ("dense_mlp", 0.11), ("head", 0.09)):
        assert step[part] / step["flops"] == pytest.approx(share, abs=0.005)
    # a query bottleneck is counted where a configuration has one
    narrow = dict(config, q_lora_rank=768)
    assert flops_kanana.latent_attention_params(narrow) == attn \
        - 2048 * 6144 + 768 * (2048 + 6144)


def test_flops_of_the_kernel_calls_by_hand():
    config = spec.load_cell(STANDS_FOR).config
    peaks = spec.peak_row("TPU v5 lite")
    pairs = flops_kanana.visible_pairs(DOCUMENTS)
    want = {"fwd": 2 * (192 + 128), "dq": 2 * (2 * 192 + 128),
            "dkv": 2 * (2 * 192 + 2 * 128)}
    for kind, per_pair in want.items():
        cost = flops_kanana.flash_kernel(kind, config=config, batch=1,
                                         documents=DOCUMENTS)
        assert cost["flops"] == per_pair * pairs * 32
        # the visible pairs' operations are the bound, not the bytes
        assert flops.roofline_seconds(cost, peaks)[1] == "compute"
    fwd = flops_kanana.flash_kernel("fwd", config=config, batch=1,
                                    documents=DOCUMENTS)
    assert fwd["bytes"] == 32 * (8192 * 2 * (2 * 192 + 2 * 128)
                                 + 8192 * 4) + 2 * 8192 * 4
    # one document of 8192 is the causal kernel's count
    from benchmark import flops_mla
    whole = flops_kanana.flash_kernel("dkv", config=config, batch=1,
                                      documents=[8192])
    assert whole["flops"] == flops_mla.flash_kernel(
        "dkv", batch=1, seq=8192, heads=32, qk_dim=192, v_dim=128)["flops"]
    rows = flops_kanana.grouped_product("rows", config=config, tokens=8192,
                                        inner=2048, outer=768)
    assert rows["flops"] == 2 * 6144 * 2048 * 768       # 384 rows an expert
    assert rows["bytes"] == 2 * (6144 * 2048 + 16 * 2048 * 768
                                 + 6144 * 768)
    # 384 rows an expert at 2048 x 768: the matrices' bytes set the bound,
    # 0.104 ms against 0.098 of products
    seconds, bound = flops.roofline_seconds(rows, peaks)
    assert bound == "memory" and seconds == pytest.approx(
        rows["bytes"] / peaks["hbm_bytes_per_s"])
    assert seconds > rows["flops"] / peaks["bf16_flops_per_s"] \
        == pytest.approx(0.0981e-3, rel=0.01)


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
    # (self times on a CPU's threads overlap: no upper bound holds here)
    for name in SCOPE_METRICS:
        assert got[name] > 0
    bench = spec.read_json(os.path.join(spec.ROOT, "BENCHMARK.json"))
    assert {m["name"] for m in bench["per_layer"]
            if "workloads" not in m} <= set(got)
    for said in ("bf.mla.q", "bf.mla.attend", "shared", "unattributed",
                 "check model: ok"):
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
    tr.Event("bf_flash_seg_fwd.10", 0 * MS, 3 * MS,
             "(bf16[32,8192,128], f32[32,8192,1]) custom-call(...)"),
    tr.Event("bf_flash_seg_dq.4", 3 * MS, 7 * MS,
             "bf16[32,8192,192] custom-call(...)"),
    tr.Event("bf_flash_seg_dkv.3", 7 * MS, 12 * MS,
             "(bf16[32,8192,192], bf16[32,8192,128]) custom-call(...)"),
    # a causal kernel's event is none of the masked ones
    tr.Event("bf_flash_fwd.2", 12 * MS, 14 * MS,
             "(bf16[32,8192,128], f32[32,8192,1]) custom-call(...)"),
    tr.Event("bf_moe_gmm_fwd.2", 20 * MS, 20.5 * MS,
             "bf16[49152,768] custom-call(...)"),
    tr.Event("bf_moe_gmm_dlhs.2", 21 * MS, 21.5 * MS,
             "bf16[49152,2048] custom-call(...)"),
    tr.Event("bf_moe_gmm_drhs.1", 22 * MS, 23 * MS,
             "f32[16,2048,768] custom-call(...)"),
    tr.Event("fusion.7", 24 * MS, 25 * MS, "bf16[8192,2048] fusion(...)"),
]


def test_flash_roofline_holds_the_masked_kernels_to_the_visible_pairs():
    peaks = spec.peak_row("TPU v5 lite")
    ctx = _context(EVENTS)
    program = spec.load_module("layer_metrics/program_common.py")
    ctx.program = program.Program([], {}, {}, {
        'bf_flash_tiles_total{kernel="bf_flash_seg_fwd",kind="by_data"}':
        1152.0})
    pairs = 7_225_056 * 32
    least = {"fwd": 2 * (192 + 128) * pairs,
             "dq": 2 * (2 * 192 + 128) * pairs,
             "dkv": 2 * (2 * 192 + 2 * 128) * pairs}
    got = spec.layer_metric_reader("packed_flash_roofline")(ctx)
    assert got == pytest.approx(
        100 * sum(least.values()) / peaks["bf16_flops_per_s"] / 12e-3)
    assert 0 < got < 100
    # the causal kernels' events alone: nothing of this metric's to read
    assert spec.layer_metric_reader("packed_flash_roofline")(
        _context(EVENTS[3:])) is None
    # and xing's reader does not take the masked kernels for its own
    xing = spec.load_module("layer_metrics/xing_common.py")
    assert [e.name for e, _ in xing.flash_events(ctx)] == ["bf_flash_fwd.2"]


def test_the_layouts_ceiling_and_tiles_are_host_arithmetic():
    common = spec.load_module("layer_metrics/kanana_common.py")
    # 153 squares of 256 x 256 at or under the diagonal hold a visible pair
    assert common.chunk_ceiling(DOCUMENTS, 256) == pytest.approx(
        7_225_056 / (153 * 256 * 256))
    assert common.chunk_ceiling([8192], 1024) == pytest.approx(
        (8192 * 8193 // 2) / (36 * 1024 * 1024))
    assert common.layout_tiles(DOCUMENTS, 1024, 1024) == {
        "dead": 19, "crossed": 13, "inside": 4}


def test_expert_roofline_counts_the_held_rows_at_768():
    peaks = spec.peak_row("TPU v5 lite")
    common = spec.load_module("layer_metrics/kanana_common.py")
    ctx = _context(EVENTS)
    kinds = [common.product_cost(ctx, e) for e in common.product_events(ctx)]
    assert [k for k, _ in kinds] == ["rows", "rows", "weights"]
    rows = 8192 * 6 * 16 // 128     # an even router's share: 384 an expert
    assert rows == 6144 == 16 * 384
    assert all(c["flops"] == 2 * rows * 2048 * 768 for _, c in kinds)
    # the reader's cost is the configuration's own cost function's
    config = ctx.cell.config
    assert kinds[0][1] == flops_kanana.grouped_product(
        "rows", config=config, tokens=8192, inner=2048, outer=768)
    assert kinds[2][1] == flops_kanana.grouped_product(
        "weights", config=config, tokens=8192, inner=2048, outer=768,
        out_itemsize=4)
    got = spec.layer_metric_reader("eighth_moe_expert_roofline")(ctx)
    least = sum(max(c["flops"] / peaks["bf16_flops_per_s"],
                    c["bytes"] / peaks["hbm_bytes_per_s"]) for _, c in kinds)
    assert got == pytest.approx(100 * least / 2e-3)
    assert 0 < got < 100
    odd = [tr.Event("bf_moe_gmm_fwd.1", 0, MS, "f32[7,9] custom-call(")]
    assert spec.layer_metric_reader("eighth_moe_expert_roofline")(
        _context(odd)) is None
    assert spec.layer_metric_reader("eighth_moe_expert_roofline")(
        _context(EVENTS[-1:])) is None


def test_scope_readers_sum_their_families_and_fall_silent_without_them():
    ctx = _context(EVENTS)
    ctx.xing_scope_ms = {"bf.mla.q": 10.0, "bf.mla.kv": 6.0,
                         "bf.mla.rope": 3.0, "bf.mla.attend": 60.0,
                         "bf.mla.out": 8.0, "bf.moe.route": 1.0}
    assert spec.layer_metric_reader("packed_mla_device_ms")(ctx) == 87.0
    # a program without these scopes (the parent's): both say nothing
    ctx.xing_scope_ms = {"bf.attn.attend": 20.0}
    assert spec.layer_metric_reader("packed_mla_device_ms")(ctx) is None
    ctx.moe_scope_ms = None
    assert spec.layer_metric_reader("eighth_moe_device_ms")(ctx) is None
    ctx.moe_scope_ms = {"bf.moe.route": 1.0, "bf.moe.experts": 4.0,
                        "bf.moe.shared": 2.0, "bf.moe.layer": 0.5}
    assert spec.layer_metric_reader("eighth_moe_device_ms")(ctx) == 7.5
