"""The way to the first step, as the program records it: every trace,
lowering and compile by the program's name (``bf_program_build_seconds``,
``bf.build.<stage>``), ``bf.init()`` and ``opt.init()`` as spans and as
``bf_startup_seconds{part}``, the compile cache's hits and misses, and the
Pallas kernels' stagings.  Runs on the CPU mesh (kernels in the
interpreter): names and counts, no time."""

import json
import os
import subprocess
import sys

import jax
import jax.monitoring
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax._src import monitoring as _monitoring

import bluefog_tpu as bf
from bluefog_tpu.utils import config, telemetry, timeline

STAGES = ("trace", "lower", "compile")


@pytest.fixture(autouse=True)
def _fresh_registry():
    telemetry.reset()
    yield
    telemetry.reset()


def _count(program, stage):
    return telemetry.snapshot().get(
        f'bf_program_build_seconds_count{{program="{program}",'
        f'stage="{stage}"}}', 0)


def _seconds(program, stage):
    return telemetry.snapshot().get(
        f'bf_program_build_seconds_sum{{program="{program}",'
        f'stage="{stage}"}}')


def _builds(program):
    return telemetry.snapshot().get(
        f'bf_step_program_builds_total{{program="{program}"}}', 0)


def _double(x):
    return x * 2


def _job(n=4, **opt_kw):
    bf.init(devices=jax.devices()[:n])
    params = {"w": np.ones((n, 4, 3), np.float32)}
    opt = bf.optim.DistributedAdaptThenCombineOptimizer(
        optax.sgd(0.01, momentum=0.9), **opt_kw)
    return opt, params


# ---------------------------------------------------------------------------
# bf_program_build_seconds{program, stage}
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("stage", STAGES)
def test_first_call_books_a_stage_once_and_the_second_nothing(stage):
    bf.init(devices=jax.devices()[:4])
    mapped = bf.rank_map(_double)
    assert _count("bf_rank_map__double", stage) == 0    # a jit object only
    x = np.ones((4, 3), np.float32)
    mapped(x)
    assert _count("bf_rank_map__double", stage) == 1
    assert _seconds("bf_rank_map__double", stage) > 0
    mapped(x)
    assert _count("bf_rank_map__double", stage) == 1


@pytest.mark.parametrize("stage", STAGES)
def test_new_shape_is_a_second_compile_under_the_same_name(stage):
    """What an operator calls a recompile: the per-program ``_count`` goes
    past 1 while the ``jax.jit`` object stays the one that was built."""
    bf.init(devices=jax.devices()[:4])
    mapped = bf.rank_map(_double)
    mapped(np.ones((4, 3), np.float32))
    mapped(np.ones((4, 5), np.float32))
    assert _count("bf_rank_map__double", stage) == 2
    assert _builds("rank_map") == 1


@pytest.mark.parametrize("dynamic", [True, False], ids=["dynamic", "static"])
def test_a_dynamic_topology_books_a_compile_per_phase(dynamic):
    opt, params = _job(use_dynamic_topology=dynamic)
    state = opt.init(params)
    period = opt._schedule().period if dynamic else 1
    assert period == (2 if dynamic else 1)      # one-peer Exp2 over 4 ranks
    for _ in range(2 * period):
        params, state = opt.step(params, params, state)
    for stage in STAGES:
        # the first step takes numpy leaves, the later ones what it returned
        assert _count("bf_optim_step", stage) >= period
    assert _builds("optim_step") == period
    before = _count("bf_optim_step", "compile")
    for _ in range(2 * period):
        params, state = opt.step(params, params, state)
    assert _count("bf_optim_step", "compile") == before


@pytest.mark.parametrize("stage", STAGES)
def test_optimizer_init_is_a_program_of_its_own(stage):
    opt, params = _job()
    opt.init(params)
    assert _count("bf_optim_init", stage) == 1
    assert _count("bf_optim_step", stage) == 0


def test_a_function_of_the_user_lands_in_other():
    bf.init(devices=jax.devices()[:1])

    @jax.jit
    def helper(x):
        return jnp.tanh(x) + 1

    @jax.jit
    def mine(x):                # jnp functions and helper trace inside it
        return helper(jnp.sin(x)) * 2
    mine(jnp.ones(3) * 1.0)
    snap = telemetry.snapshot()
    programs = {k.split('program="')[1].split('"')[0] for k in snap
                if k.startswith("bf_program_build_seconds_count")}
    assert programs == {"other"}
    # jnp.ones, the multiply and mine: each a trace, a lowering, a compile;
    # the traces inside mine's are part of its seconds and not booked
    for stage in STAGES:
        assert _count("other", stage) == _count("other", "compile") >= 1
    assert _count("other", "trace_nested") == 0


def test_a_library_program_traced_inside_another_is_booked_as_nested():
    bf.init(devices=jax.devices()[:1])

    def bf_inner(x):
        return x + 1

    def bf_outer(x):
        return jax.jit(bf_inner)(x) * 2
    jax.jit(bf_outer)(np.ones(3, np.float32))
    assert _count("bf_outer", "trace") == 1
    assert _count("bf_inner", "trace_nested") == 1
    assert _count("bf_inner", "trace") == _count("bf_inner", "lower") == 0
    assert _seconds("bf_inner", "trace_nested") <= _seconds("bf_outer",
                                                            "trace")


def _listeners():
    return (_monitoring.get_event_time_span_listeners().count(
                timeline._on_build_stage),
            _monitoring.get_event_listeners().count(timeline._on_cache_event))


def test_two_inits_leave_one_listener_and_shutdown_none():
    assert _listeners() == (0, 0)
    bf.init(devices=jax.devices()[:2])
    bf.init(devices=jax.devices()[:4])
    assert _listeners() == (1, 1)
    bf.shutdown()
    assert _listeners() == (0, 0)
    jax.jit(_double)(np.ones(7, np.float32))    # nothing listens: no series
    assert not any("build" in k for k in telemetry.snapshot())


def test_the_compile_cache_reports_its_hits_and_misses():
    """jax's events, as the persistent cache sends them (a CPU mesh is
    given no cache directory, so they are sent here by hand)."""
    bf.init(devices=jax.devices()[:1])
    jax.monitoring.record_event("/jax/compilation_cache/cache_misses")
    for _ in range(2):
        jax.monitoring.record_event("/jax/compilation_cache/cache_hits")
    jax.monitoring.record_event("/jax/compilation_cache/tasks_using_cache")
    snap = telemetry.snapshot()
    assert snap['bf_compile_cache_total{result="miss"}'] == 1
    assert snap['bf_compile_cache_total{result="hit"}'] == 2
    assert len([k for k in snap if k.startswith("bf_compile_cache")]) == 2


def test_disabled_telemetry_records_nothing(monkeypatch):
    monkeypatch.setenv("BLUEFOG_TPU_TELEMETRY", "0")
    config.reload()
    try:
        opt, params = _job()
        state = opt.init(params)
        opt.step(params, {"w": bf.rank_map(_double)(params["w"])}, state)
        from bluefog_tpu.ops.flash_attention import flash_attention
        q = jnp.ones((1, 16, 2, 8))
        flash_attention(q, q, q, block_q=8, block_k=8)
        assert telemetry._registry.hists == {}
        assert telemetry.snapshot() == {}
    finally:
        monkeypatch.delenv("BLUEFOG_TPU_TELEMETRY")
        config.reload()


# ---------------------------------------------------------------------------
# bf_startup_seconds{part}, bf.init.*, bf.optim.init
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("part", ["init_devices", "init_topology",
                                  "optim_init"])
def test_startup_parts_are_gauges(part):
    opt, params = _job()
    key = f'bf_startup_seconds{{part="{part}"}}'
    assert (key in telemetry.snapshot()) == (part != "optim_init")
    opt.init(params)
    snap = telemetry.snapshot()
    assert snap[key] > 0
    if part == "optim_init":    # it holds its program's three stages
        assert snap[key] >= sum(_seconds("bf_optim_init", s) for s in STAGES)


def test_the_import_is_a_startup_part_of_a_fresh_process():
    done = subprocess.run(
        [sys.executable, "-c",
         "import json, bluefog_tpu as bf; "
         "print(json.dumps(bf.telemetry.snapshot()))"],
        capture_output=True, text=True, timeout=300,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    assert done.returncode == 0, done.stderr[-2000:]
    snap = json.loads(done.stdout.strip().splitlines()[-1])
    assert snap == {'bf_startup_seconds{part="import"}':
                    pytest.approx(snap['bf_startup_seconds{part="import"}'])}
    assert 0 < snap['bf_startup_seconds{part="import"}'] < 120


# ---------------------------------------------------------------------------
# The chrome-JSON timeline: bf.build.<stage> on a lane of its own
# ---------------------------------------------------------------------------

@pytest.fixture
def timeline_events(tmp_path, monkeypatch):
    """The events of a timeline that was open over ``bf.init()``,
    ``opt.init()``, one gradient call and one step."""
    monkeypatch.setenv("BLUEFOG_TPU_PYTHON_TIMELINE", "1")
    path = tmp_path / "tl.json"
    assert timeline.start_timeline(str(path))
    try:
        opt, params = _job()
        state = opt.init(params)
        opt.step(params, {"w": bf.rank_map(_double)(params["w"])}, state)
    finally:
        timeline.stop_timeline()
    return json.loads(path.read_text())


@pytest.mark.parametrize("stage", STAGES)
def test_timeline_holds_the_build_spans_inside_their_callers(
        timeline_events, stage):
    events = timeline_events
    builds = [e for e in events if e["name"] == f"bf.build.{stage}"]
    assert {e["ph"] for e in builds} == {"X"}
    assert len({e["tid"] for e in builds}) == 1             # one lane
    lane, = [e for e in events if e["name"] == "thread_name"
             and e["tid"] == builds[0]["tid"]]
    assert lane["args"] == {"name": "bf.build"}
    by_program = {e["cat"]: e for e in builds}
    assert {"bf_optim_init", "bf_optim_step",
            "bf_rank_map__double"} <= set(by_program)

    def edges(cat, name):
        begin, end = [next(e["ts"] for e in events if e["ph"] == ph and
                           (e.get("cat"), e["name"]) == (cat, name))
                      for ph in "BE"]
        return begin, end
    # wall clock brought onto the timeline's: each build lies inside the
    # span that called its program, to the millisecond
    for program, caller in (("bf_optim_init", ("optim", "init")),
                            ("bf_optim_step", ("optim", "launch")),
                            ("bf_rank_map__double", ("rank_map", "launch"))):
        begin, end = edges(*caller)
        span = by_program[program]
        assert begin - 1000 <= span["ts"]
        assert span["ts"] + span["dur"] <= end + 1000


def test_timeline_holds_the_init_spans_in_order(timeline_events):
    opened = [(e["cat"], e["name"]) for e in timeline_events
              if e["ph"] == "B"]
    assert opened[:3] == [("init", "devices"), ("init", "topology"),
                          ("optim", "init")]


# ---------------------------------------------------------------------------
# bf_kernel_stagings_total{kernel}
# ---------------------------------------------------------------------------

MOE_KERNELS = ("bf_moe_gmm_fwd", "bf_moe_gmm_dlhs", "bf_moe_gmm_drhs")
FLASH_KERNELS = ("bf_flash_fwd", "bf_flash_dq", "bf_flash_dkv")


def _stagings():
    return {k.split('kernel="')[1][:-2]: v
            for k, v in telemetry.snapshot().items()
            if k.startswith("bf_kernel_stagings_total")}


@pytest.fixture(scope="module")
def staged():
    """The counts after each of: a bare gradient, the same again, the
    gradient behind ``jax.jit``, the same again, a new shape."""
    from bluefog_tpu.ops.flash_attention import flash_attention
    from bluefog_tpu.parallel import moe
    sizes = jnp.array([10, 6], jnp.int32)
    matrices = jnp.ones((2, 8, 8))

    def product(rows):
        return moe.grouped_matmul(rows, matrices, sizes).sum()

    def attend(q):
        return flash_attention(q, q, q, block_q=8, block_k=8).sum()
    out = {}
    for kernels, fn, small, large in (
            (MOE_KERNELS, product, jnp.ones((16, 8)), jnp.ones((32, 8))),
            (FLASH_KERNELS, attend, jnp.ones((1, 16, 2, 8)),
             jnp.ones((1, 32, 2, 8)))):
        telemetry.reset()
        counts = []
        jitted = jax.jit(jax.grad(fn))
        for call, x in ((jax.grad(fn), small), (jax.grad(fn), small),
                        (jitted, small), (jitted, small), (jitted, large)):
            call(x)
            counts.append(_stagings())
        for kernel in kernels:
            out[kernel] = [c.get(kernel, 0) for c in counts]
    return out


@pytest.mark.parametrize("kernel", MOE_KERNELS + FLASH_KERNELS)
def test_a_bare_kernel_is_staged_once_a_call(staged, kernel):
    assert staged[kernel][:2] == [1, 2]


@pytest.mark.parametrize("kernel", MOE_KERNELS + FLASH_KERNELS)
def test_a_kernel_behind_jit_is_staged_once_a_shape(staged, kernel):
    assert staged[kernel][2:] == [3, 3, 4]


# ---------------------------------------------------------------------------
# bf_flash_tiles_total{kernel, kind}
# ---------------------------------------------------------------------------

# One staged call on (B, S, H, D) = (1, 64, 2, 8): two heads, a grid of
# 2 x 4 (block_q 32, block_k 16) or 4 x 2 tiles a head.  By hand, causal at
# 32/16: q-block 0 (rows 0..31) crosses k-blocks 0 and 1 and skips 2 and 3;
# q-block 1 (rows 32..63) has k-blocks 0 and 1 interior and crosses 2 and 3.
# At 16/32 each k-block of 32 is crossed by its two q-blocks; k-block 0 is
# interior to q-blocks 2 and 3, k-block 1 skipped by q-blocks 0 and 1.
@pytest.mark.parametrize("causal, blocks, by_hand", [
    (True, (32, 16), {"skipped": 2, "crossed": 4, "interior": 2}),
    (True, (16, 32), {"skipped": 2, "crossed": 4, "interior": 2}),
    (True, (64, 64), {"skipped": 0, "crossed": 1, "interior": 0}),
    (False, (32, 16), {"skipped": 0, "crossed": 0, "interior": 8}),
    (False, (16, 32), {"skipped": 0, "crossed": 0, "interior": 8}),
])
def test_a_staged_flash_call_counts_its_tiles_by_kind(causal, blocks,
                                                      by_hand):
    from bluefog_tpu.ops.flash_attention import flash_attention
    block_q, block_k = blocks
    heads, grid = 2, (64 // block_q) * (64 // block_k)

    def attend(q):
        return flash_attention(q, q, q, causal=causal, block_q=block_q,
                               block_k=block_k).sum()
    telemetry.reset()
    jax.jit(jax.grad(attend))(jnp.ones((1, 64, heads, 8)))
    staged, snap = _stagings(), telemetry.snapshot()
    for kernel in FLASH_KERNELS:
        # the forward is staged once by the rule's forward alone
        assert staged[kernel] == 1
        counted = {kind: snap['bf_flash_tiles_total{kernel="%s",kind="%s"}'
                              % (kernel, kind)] for kind in by_hand}
        assert counted == {kind: heads * n for kind, n in by_hand.items()}
        assert sum(counted.values()) == heads * grid
