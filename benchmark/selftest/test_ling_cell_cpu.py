"""The ``ling3-kda-s4096-1chip`` cell's tiny twin end to end on the CPU, its
four readers, and ``benchmark/flops_ling.py`` against hand counts.

    python3 -m pytest benchmark/selftest/test_ling_cell_cpu.py -q   (three minutes)

``selftest/workloads.json`` is not this PR's to edit, so the twin
(``tiny-ling3-1dev``) is built here as ``test_kanana_cell_cpu.py`` builds its
own: a ``spec.Cell`` of ``selftest/configs/tiny-ling3.json`` and
``selftest/traffic/tiny-tokens-1row-adamw.json`` (one row of 256 tokens)
with the metric lists of ``ling3-kda-s4096-1chip``, handed to
``benchmark/run.py`` in a process of its own (``JAX_PLATFORMS=cpu``; the
flash and grouped-matmul kernels choose the Pallas interpreter themselves
off the chip; the delta rule is plain ``jax.numpy`` on every platform).
The cell's four metrics read scopes and counters, none a kernel's events, so
the traced twin reads all of them: the three scope metrics and
``kda_chunk_roofline`` (a scope's time against the rule's cost).  Its
numbers are not device numbers.  Tier-1 runs everything here but ``test_twin_untraced``
(``tests/test_benchmark_selftest.py``): the traced twin runs the same
checks.
"""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import flops, flops_ling, layers, spec  # noqa: E402
from benchmark import trace_reduce as tr  # noqa: E402

TWIN = "tiny-ling3-1dev"
STANDS_FOR = "ling3-kda-s4096-1chip"
SCOPE_METRICS = {"kda_device_ms", "kda_chunk_roofline",
                 "gated_mla_device_ms", "group_moe_device_ms"}
# the toy's model_check.why: in bfloat16 the toy is chaotic and its bounds
# refuse only what is no gradient at all; any seed passes them
SEED = 2147483757

DRIVER = f'''
import os, sys
sys.path.insert(0, {ROOT!r})
from benchmark import spec
from benchmark.selftest.test_ling_cell_cpu import twin_cell
find = spec.load_cell
spec.load_cell = lambda name: twin_cell() if name == {TWIN!r} else find(name)
from benchmark import run
sys.exit(run.main(sys.argv[1:]))
'''


def twin_cell() -> spec.Cell:
    real = spec.load_cell(STANDS_FOR)
    here = os.path.join(spec.HERE, "selftest")
    return spec.Cell(
        name=TWIN, chips=1, config_name="tiny-ling3",
        traffic_name="tiny-tokens-1row-adamw",
        config=spec.read_json(os.path.join(here, "configs",
                                           "tiny-ling3.json")),
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


def test_declared_with_its_four_metrics_and_no_other_cells():
    cell = spec.load_cell(STANDS_FOR)
    assert (cell.chips, cell.config_name, cell.traffic_name) == (
        1, "ling-3.0-flash", "tokens-1x4096-adamw")
    names = [m["name"] for m in cell.per_layer]
    assert SCOPE_METRICS <= set(names)
    assert {"gossip_device_ms", "flash_roofline", "loss_device_ms",
            "kernel_stagings", "moe_expert_roofline", "mla_device_ms",
            "mla_flash_roofline", "moe_share_device_ms",
            "gqa_flash_roofline", "hybrid_moe_device_ms",
            "gated_attn_device_ms", "small_moe_device_ms", "ssm_device_ms",
            "kv2_attn_device_ms", "packed_mla_device_ms",
            "eighth_moe_device_ms"}.isdisjoint(names)
    # every metric without a list of cells is this cell's too
    bench = spec.read_json(os.path.join(spec.ROOT, "BENCHMARK.json"))
    assert {m["name"] for m in bench["per_layer"]
            if "workloads" not in m} <= set(names)
    assert [m["name"] for m in cell.end_to_end] == [
        "throughput_per_chip", "peak_hbm_gib", "setup_s"]
    # the traffic is xing4-s4096-1chip's file, as it is
    assert cell.traffic == spec.load_cell("xing4-s4096-1chip").traffic
    assert cell.traffic["batch"] == {"sequences": 1, "seq_len": 4096}
    for name in names:
        assert callable(spec.layer_metric_reader(name))
    for m in bench["per_layer"]:
        if m["name"] in SCOPE_METRICS:
            assert m["workloads"] == [STANDS_FOR]
            assert m["moves"] == "throughput_per_chip"
            assert m["source"] == "device_trace"
    # no older cell reads the new metrics
    for other in ("xing4-s4096-1chip", "twotower-s8192-1chip",
                  "kanana2-packed-s8192-1chip"):
        assert (SCOPE_METRICS).isdisjoint(
            m["name"] for m in spec.load_cell(other).per_layer)
    entry = next(w for w in bench["workloads"] if w["name"] == STANDS_FOR)
    assert "5 KDA mixers" in entry["why"] and "64 rows" in entry["why"]
    assert len(entry["why"]) <= 200
    # appended behind what PR 47 left: the tenth cell, the ninth
    # configuration, four metrics from the fifty-fifth on (by position, so
    # that the next PR's appended entries fail nothing here)
    assert bench["workloads"][9] is entry
    assert bench["configs"][8]["name"] == cell.config_name
    assert [m["name"] for m in bench["per_layer"][54:58]] == [
        "kda_device_ms", "kda_chunk_roofline", "gated_mla_device_ms",
        "group_moe_device_ms"]
    # the held experts' grouped products get no roofline of their own here:
    # a collapsed router sends some of the 8 held experts no row, the
    # kernels skip their matrices, and a share of the bytes an even router
    # needs read 102% on the chip (PERF.md, PR 50)
    assert not any("expert_roofline" in n for n in names)
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 1


# the catalog row's ``config`` (model-configs guide, architectures.jsonl):
# every number under its own key, but for the five that are cut
PUBLISHED = {
    "first_k_dense_replace": 1, "head_dim": 128, "hidden_act": "silu",
    "gated_attention_proj_granularity_type": "head_wise",
    "group_norm_size": 1, "hidden_size": 2560, "intermediate_size": 6144,
    "kda_lower_bound": -5, "kda_safe_gate": True, "kv_lora_rank": 512,
    "layer_group_size": 6, "linear_silu": True,
    "max_position_embeddings": 262144, "max_window_layers": 20,
    "moe_intermediate_size": 768, "moe_router_enable_expert_bias": True,
    "moe_shared_expert_intermediate_size": 768,
    "mtp_loss_scaling_factor": 0, "mtp_use_kda": False, "n_group": 8,
    "no_kda_lora": True, "norm_topk_prob": True, "num_attention_heads": 32,
    "num_experts_per_tok": 8, "num_key_value_heads": 32,
    "num_kv_heads_for_linear_attn": 0, "num_shared_experts": 1,
    "partial_rotary_factor": 0.5, "q_lora_rank": None, "qk_head_dim": 192,
    "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "rms_norm_eps": 1e-06,
    "rope_interleave": True, "rope_scaling": None, "rope_theta": 6000000,
    "rotary_dim": 64, "routed_scaling_factor": 2.5,
    "scale_router_input": False, "score_function": "sigmoid",
    "scoring_func": "sigmoid", "seq_aux": True, "short_conv_kernel_size": 4,
    "tie_word_embeddings": False, "topk_group": 4,
    "topk_method": "noaux_tc", "up_proj_norm": False, "use_bias": False,
    "use_kda_lora": False, "use_mla_nope": False, "use_nGPT": False,
    "use_qk_norm": True, "use_qkv_bias": False, "v_head_dim": 128,
    "value_norm": False, "model_type": "bailing_hybrid"}


def test_the_configuration_keeps_the_published_widths():
    config = spec.load_cell(STANDS_FOR).config
    assert {k: config[k] for k in PUBLISHED} == PUBLISHED
    # the swiglu limits are copied whole: 42 entries, none in the cut's six
    assert len(config["expert_swiglu_limit_list"]) == 42
    assert len(config["share_expert_swiglu_limit_list"]) == 42
    assert not any(config["expert_swiglu_limit_list"][:6])
    assert not any(config["share_expert_swiglu_limit_list"][:6])
    # the cut: one period, an eighth of a group's experts, of the vocabulary
    assert (config["num_hidden_layers"], config["num_experts"],
            config["vocab_size"], config["router_width"],
            config["experts_first"], config["num_nextn_predict_layers"]) \
        == (6, 8, 19648, 512, 0, 0)
    assert config["source_values"] == {
        "num_hidden_layers": 42, "first_k_dense_replace": 2,
        "num_experts": 512, "vocab_size": 157184,
        "num_nextn_predict_layers": 1}
    assert config["num_experts"] * 64 == 512
    assert config["vocab_size"] * 8 == 157184
    assert sorted(config["reduced"]) == sorted(config["source_values"])
    assert flops_ling.layer_types(config) == ["kda"] * 5 + ["full_attention"]
    assert flops_ling.layer_types(dict(config, num_hidden_layers=42)).count(
        "kda") == 35
    bench = spec.read_json(os.path.join(spec.ROOT, "BENCHMARK.json"))
    entry = next(c for c in bench["configs"] if c["name"] == "ling-3.0-flash")
    assert sorted(entry["reduced"]) == sorted(config["reduced"])
    assert entry["source"] == config["source"]
    assert entry["file"] == "benchmark/configs/ling-3.0-flash.json"
    assert len(entry["why"]) <= 200
    # no width among the reduced keys
    assert not any(k.endswith(("_dim", "_rank", "_size")) and k != "vocab_size"
                   for k in config["reduced"])
    for key in ("loss_rtol", "grad_rtol", "why"):
        assert config["model_check"][key]
    for key in ("what", "why", "stands_for", "bias"):
        assert config["cut"][key]
    assert "64 chips" in config["cut"]["stands_for"]
    assert "767,006,496" in config["cut"]["why"]
    for key in ("kda", "latent_attention", "experts", "model"):
        assert config["equations"][key]
    for key in ("router_width", "router_bias_update_rate", "auxiliary_loss",
                "use_qk_norm", "gated_attention_proj_granularity_type",
                "no_kda_lora", "kda_safe_gate", "chunk", "initializer",
                "parameter_dtype"):
        assert config["assumed"][key]
    assert any("multi-token-prediction" in d for d in config["departures"])
    assert any("rope_interleave" in d for d in config["departures"])
    # the model takes its sizes from the source's keys, one by a literal:
    # the chunk, which the source has no key for
    m = config["model"]
    assert set(m["args"]) == {"pos_encoding", "mlp", "remat", "dtype",
                              "kda_chunk"}
    assert m["from_source"]["router_groups"] == "n_group"
    assert m["from_source"]["router_groups_kept"] == "topk_group"
    assert m["from_source"]["kda_lower_bound"] == "kda_lower_bound"
    assert m["from_source"]["conv_kernel"] == "short_conv_kernel_size"


# --- benchmark/flops_ling.py against hand counts ---------------------------------

def test_flops_step_by_hand():
    config = spec.load_cell(STANDS_FOR).config
    step = flops_ling.delta_moe_lm_train(config, batch=1, seq=4096)
    kda = 5 * 2560 * 4096 + 2560 * 32 + 4096 * 2560
    assert kda == 62_996_480 == flops_ling.kda_params(config)
    mla = 2560 * 6144 + 2560 * 576 + 512 * 8192 + 2560 * 32 + 4096 * 2560
    assert mla == 31_965_184 == flops_ling.latent_attention_params(config)
    expert = 3 * 2560 * 768
    sparse = 2560 * 512 + expert + 8 * (8 / 512) * expert
    assert flops_ling.expert_layer_params(config) == sparse
    dense = 3 * 2560 * 6144
    assert step["matmul_params"] == 5 * kda + mla + dense + 5 * sparse \
        + 2560 * 19648
    # the rule, a chunk of 64 and a head of 128: 2016 pairs under the
    # diagonal, 2080 at or under it
    chunk = 2 * 2016 * 128 + 4 * 2016 * 128 + 4 * 2080 * 128 \
        + 6 * 64 * 128 * 128
    assert chunk == 8_904_704
    fwd = flops_ling.kda_chunk("fwd", config=config, tokens=4096, chunk=64)
    assert fwd["flops"] == 64 * 32 * chunk
    assert step["rule"] == 5 * 3 * fwd["flops"]
    assert step["attention"] == 6 * (192 + 128) * 32 * (4096 * 4097 // 2)
    assert step["head"] == 6 * 2560 * 19648 * 4096
    assert step["dense_mlp"] == 6 * dense * 4096
    assert step["experts"] == 6 * 5 * sparse * 4096
    assert (step["blocks"] + step["head"] + step["attention"]
            == step["flops"])
    assert (step["kda_mixers"] + step["latent_attention"] + step["experts"]
            + step["dense_mlp"] + step["head"] == step["flops"])
    # 12.7 TFLOP a step, 1033 MFLOP a token forward; the KDA mixers 63%,
    # the head 10%, latent attention 10%, the dense layer 9%, experts 8%
    assert step["flops"] == pytest.approx(12.69e12, rel=1e-3)
    assert step["flops"] / 3 / 4096 == pytest.approx(1032.5e6, rel=1e-3)
    for part, share in (("kda_mixers", 0.63), ("latent_attention", 0.10),
                        ("head", 0.10), ("dense_mlp", 0.09),
                        ("experts", 0.08)):
        assert step[part] / step["flops"] == pytest.approx(share, abs=0.006)


def test_flops_of_the_rule_and_the_kernel_calls_by_hand():
    config = spec.load_cell(STANDS_FOR).config
    peaks = spec.peak_row("TPU v5 lite")
    fwd = flops_ling.kda_chunk("fwd", config=config, tokens=4096, chunk=64)
    bwd = flops_ling.kda_chunk("bwd", config=config, tokens=4096, chunk=64)
    # q, k, v in bfloat16, the log decays and beta in float32, o written
    assert fwd["bytes"] == 4096 * (3 * 4096 * 2 + 4096 * 4 + 32 * 4) \
        + 4096 * 4096 * 2
    assert bwd["flops"] == 2 * fwd["flops"]
    assert bwd["bytes"] == 2 * (fwd["bytes"] - 4096 * 4096 * 2) \
        + 4096 * 4096 * 2
    # 18.2 GFLOP over 202 MB: the bytes set the bound (0.246 ms against
    # 0.093 of products), as for Mamba-2's scan
    assert fwd["flops"] == pytest.approx(18.24e9, rel=1e-3)
    seconds, bound = flops.roofline_seconds(fwd, peaks)
    assert bound == "memory" and seconds == pytest.approx(0.246e-3, rel=0.01)
    # a tail is a whole chunk
    assert flops_ling.kda_chunk("fwd", config=config, tokens=4097,
                                chunk=64)["flops"] == 65 * fwd["flops"] // 64
    with pytest.raises(ValueError, match="unknown kind"):
        flops_ling.kda_chunk("both", config=config, tokens=64, chunk=64)
    from benchmark import flops_mla
    assert flops_ling.flash_kernel("dkv", config=config, batch=1, seq=4096) \
        == flops_mla.flash_kernel("dkv", batch=1, seq=4096, heads=32,
                                  qk_dim=192, v_dim=128)
    rows = flops_ling.grouped_product("rows", config=config, tokens=4096,
                                      inner=2560, outer=768)
    assert rows["flops"] == 2 * 512 * 2560 * 768        # 64 rows an expert
    assert rows["bytes"] == 2 * (512 * 2560 + 8 * 2560 * 768 + 512 * 768)
    # 64 rows an expert at 2560 x 768: the matrices' bytes set the bound
    seconds, bound = flops.roofline_seconds(rows, peaks)
    assert bound == "memory"


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
    # (self times on a CPU's threads overlap: no upper bound holds here)
    for name in SCOPE_METRICS:
        assert got[name] > 0
    bench = spec.read_json(os.path.join(spec.ROOT, "BENCHMARK.json"))
    assert {m["name"] for m in bench["per_layer"]
            if "workloads" not in m} <= set(got)
    for said in ("bf.kda.qkv", "bf.kda.chunk", "bf.kda.norm", "bf.mla.gate",
                 "bf_kda_chunks_total", "bf_moe_route_groups_total{kept=2}",
                 "shared", "unattributed", "check model: ok"):
        assert said in done.stdout, said


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
    tr.Event("bf_moe_gmm_fwd.2", 20 * MS, 20.5 * MS,
             "bf16[1024,768] custom-call(...)"),
    tr.Event("bf_moe_gmm_dlhs.2", 21 * MS, 21.5 * MS,
             "bf16[1024,2560] custom-call(...)"),
    tr.Event("bf_moe_gmm_drhs.1", 22 * MS, 23 * MS,
             "f32[8,2560,768] custom-call(...)"),
    tr.Event("fusion.7", 24 * MS, 25 * MS, "bf16[4096,2560] fusion(...)"),
]


def test_the_grouped_products_cost_is_counted_at_the_held_rows():
    common = spec.load_module("layer_metrics/ling_common.py")
    ctx = _context(EVENTS)
    kinds = [common.product_cost(ctx, e) for e in common.product_events(ctx)]
    assert [k for k, _ in kinds] == ["rows", "rows", "weights"]
    rows = 4096 * 8 * 8 // 512      # an even router's share: 64 an expert
    assert rows == 512 == 8 * 64
    assert all(c["flops"] == 2 * rows * 2560 * 768 for _, c in kinds)
    assert kinds[2][1] == flops_ling.grouped_product(
        "weights", config=ctx.cell.config, tokens=4096, inner=2560,
        outer=768, out_itemsize=4)
    odd = [tr.Event("bf_moe_gmm_fwd.1", 0, MS, "f32[7,9] custom-call(")]
    assert common.product_cost(_context(odd), odd[0]) == (None, None)


def test_chunk_roofline_holds_the_scope_to_three_passes_a_mixer():
    peaks = spec.peak_row("TPU v5 lite")
    config = spec.load_cell(STANDS_FOR).config
    ctx = _context(EVENTS)
    ctx.xing_scope_ms = {"bf.kda.chunk": 40.0, "bf.kda.qkv": 9.0}
    passes = [flops_ling.kda_chunk(kind, config=config, tokens=4096,
                                   chunk=64) for kind in ("fwd", "bwd",
                                                          "fwd")]
    least = 5 * sum(max(c["flops"] / peaks["bf16_flops_per_s"],
                        c["bytes"] / peaks["hbm_bytes_per_s"])
                    for c in passes)
    got = spec.layer_metric_reader("kda_chunk_roofline")(ctx)
    assert got == pytest.approx(100 * least * 1e3 / 40.0)
    assert 0 < got < 100
    ctx.xing_scope_ms = {"bf.ssm.scan": 40.0}
    assert spec.layer_metric_reader("kda_chunk_roofline")(ctx) is None


def test_scope_readers_sum_their_families_and_fall_silent_without_them():
    program = spec.load_module("layer_metrics/program_common.py")
    ctx = _context(EVENTS)
    ctx.program = program.Program([], {}, {}, {
        "bf_kda_chunks_total": 960.0,
        'bf_moe_route_groups_total{kept="4"}': 80.0})
    ctx.xing_scope_ms = {
        "bf.kda.qkv": 20.0, "bf.kda.conv": 5.0, "bf.kda.gate": 9.0,
        "bf.kda.chunk": 40.0, "bf.kda.norm": 8.0, "bf.kda.out": 7.0,
        "bf.mla.q": 3.0, "bf.mla.kv": 2.0, "bf.mla.rope": 1.0,
        "bf.mla.attend": 10.0, "bf.mla.gate": 0.5, "bf.mla.out": 2.0,
        "bf.moe.route": 1.0}
    assert spec.layer_metric_reader("kda_device_ms")(ctx) == 89.0
    assert spec.layer_metric_reader("gated_mla_device_ms")(ctx) == 18.5
    ctx.moe_scope_ms = {"bf.moe.route": 1.0, "bf.moe.experts": 4.0,
                        "bf.moe.shared": 2.0, "bf.moe.layer": 0.5}
    assert spec.layer_metric_reader("group_moe_device_ms")(ctx) == 7.5
    # latent attention without the gate is another cell's; a route that
    # scored no groups too; a program without the scopes (the parent's)
    # says nothing
    del ctx.xing_scope_ms["bf.mla.gate"]
    assert spec.layer_metric_reader("gated_mla_device_ms")(ctx) is None
    ctx.program = program.Program([], {}, {}, {})
    assert spec.layer_metric_reader("group_moe_device_ms")(ctx) is None
    ctx.xing_scope_ms = {"bf.attn.attend": 20.0}
    assert spec.layer_metric_reader("kda_device_ms")(ctx) is None
    assert spec.layer_metric_reader("kda_chunk_roofline")(ctx) is None
