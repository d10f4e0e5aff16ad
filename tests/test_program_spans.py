"""What the collective training path says about itself, on one clock: the
host spans ``bf.*`` of ``utils/timeline.op_span`` inside a ``jax.profiler``
trace, the names of the step's programs (``jit_bf_*``), the device scopes in
their metadata, and the ``op="optimizer_step"`` comm counters.  Runs on the
CPU mesh: names, nesting and counts, no time."""

import contextlib
import glob
import json
import re
import threading

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

import bluefog_tpu as bf
from bluefog_tpu import topology_util
from bluefog_tpu.utils import telemetry, timeline


@pytest.fixture(autouse=True)
def _fresh_registry():
    telemetry.reset()
    yield
    telemetry.reset()


def _job(n=4, **opt_kw):
    """A tiny model through the library's own entry points."""
    bf.init(devices=jax.devices()[:n])
    params = {"w": np.ones((n, 4, 3), np.float32),
              "b": np.zeros((n, 3), np.float32)}
    x = np.ones((n, 2, 4), np.float32)

    def loss(p, x):
        return jnp.sum((x @ p["w"] + p["b"]) ** 2)
    opt_kw.setdefault("use_dynamic_topology", True)
    opt = bf.optim.DistributedAdaptThenCombineOptimizer(
        optax.sgd(0.01, momentum=0.9), **opt_kw)
    return bf.rank_map(jax.grad(loss)), opt, params, x


def _host_spans(trace_dir):
    """``(name, start, end, thread, args)`` of every ``bf.*`` host event."""
    from jax.profiler import ProfileData
    path, = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)
    out = []
    for plane in ProfileData.from_file(path).planes:
        for i, line in enumerate(plane.lines):   # a line is a thread
            out += [(e.name, e.start_ns, e.start_ns + e.duration_ns,
                     f"{line.name}#{i}", {k: str(v) for k, v in e.stats})
                    for e in line.events if e.name.startswith("bf.")]
    return sorted(out, key=lambda s: s[1])


def _trace(tmp_path, body):
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        body()
    finally:
        jax.profiler.stop_trace()
    return _host_spans(tmp_path)


# ---------------------------------------------------------------------------
# Host side: the spans of a step, nested, on the profiler's clock
# ---------------------------------------------------------------------------

def test_step_spans_nest_on_one_thread_with_rising_step(tmp_path):
    grad, opt, params, x = _job()
    state = opt.init(params)
    for _ in range(opt._schedule().period):     # builds, a program a phase
        params, state = opt.step(params, grad(params, x), state)

    def three_steps():
        nonlocal params, state
        for _ in range(3):
            params, state = opt.step(params, grad(params, x), state)
        jax.block_until_ready(params)
    spans = _trace(tmp_path, three_steps)
    by_name = lambda name: [s for s in spans if s[0] == name]  # noqa: E731
    steps, places = by_name("bf.optim.step"), by_name("bf.optim.place")
    launches = by_name("bf.optim.launch")
    assert len(steps) == len(places) == len(launches) == 3
    assert len(by_name("bf.rank_map.launch")) == 3
    assert not by_name("bf.optim.build") and not by_name("bf.rank_map.build")
    for step, place, launch in zip(steps, places, launches):
        assert step[3] == place[3] == launch[3]             # one thread
        assert step[1] <= place[1] and place[2] <= launch[1] \
            and launch[2] <= step[2]                        # nested, in order
        assert launch[4]["step"] == step[4]["step"]
        assert place[4]["leaves"] == "4"                    # params + grads
    assert [int(s[4]["step"]) for s in steps] == [2, 3, 4]


@pytest.mark.parametrize("dynamic", [True, False],
                         ids=["dynamic", "static"])
def test_build_spans_fire_on_the_cache_miss_only(tmp_path, dynamic):
    """A dynamic topology builds one step program per phase, each on the
    first step that runs its phase; a static one builds one."""
    grad, opt, params, x = _job(use_dynamic_topology=dynamic)
    state = opt.init(params)
    period = opt._schedule().period if dynamic else 1
    assert period == (2 if dynamic else 1)   # one-peer Exp2 over 4 ranks

    def two_periods():
        nonlocal params, state
        for _ in range(2 * period):
            params, state = opt.step(params, grad(params, x), state)
    spans = _trace(tmp_path, two_periods)
    builds = [s for s in spans if s[0].endswith(".build")]
    assert sorted(s[0] for s in builds) == (
        ["bf.optim.build"] * period + ["bf.rank_map.build"])
    keys = [s[4]["key"] for s in builds if s[0] == "bf.optim.build"]
    assert all(k.startswith("(") and "False" in k for k in keys)
    assert len(set(keys)) == period            # the cache key: phase last
    snap = telemetry.snapshot()
    assert snap['bf_step_program_builds_total{program="rank_map"}'] == 1
    assert snap['bf_step_program_builds_total{program="optim_step"}'] \
        == period
    bf.set_topology(topology_util.RingGraph(bf.size()))    # new version
    opt.step(params, grad(params, x), state)
    snap = telemetry.snapshot()
    assert snap['bf_step_program_builds_total{program="optim_step"}'] \
        == period + 1
    assert snap['bf_step_program_builds_total{program="rank_map"}'] == 1


_READS = "bf_optim_phase_reads_total"


@pytest.mark.parametrize("order,override", [
    ("atc", False), ("atc", True), ("awc", False), ("awc", True)])
def test_phase_reads_count_the_states_not_followed(order, override):
    """``step()`` reads the counter from the device once per state it did
    not return itself (a fresh ``init``, a restore), never in between."""
    n = 4
    bf.init(devices=jax.devices()[:n])
    opt = bf.optim.DistributedOptimizer(
        optax.sgd(0.01), order=order, use_dynamic_topology=True)
    w = np.full((n, n), 0.25) if override else None
    params = {"w": np.ones((n, 3), np.float32)}
    state = opt.init(params)
    assert _READS not in telemetry.snapshot()
    for _ in range(10):
        params, state = opt.step(params, params, state, src_weights=w)
    assert telemetry.snapshot()[_READS] == 1
    state = opt.init(params)
    for _ in range(3):
        params, state = opt.step(params, params, state, src_weights=w)
    snap = telemetry.snapshot()
    assert snap[_READS] == 2
    assert snap['bf_step_program_builds_total{program="optim_step"}'] == 2


@pytest.mark.parametrize("n,dynamic", [(4, False), (1, True)],
                         ids=["static", "period-1"])
def test_one_phase_needs_no_read_and_one_program(n, dynamic):
    bf.init(devices=jax.devices()[:n])
    opt = bf.optim.DistributedAdaptThenCombineOptimizer(
        optax.sgd(0.01), use_dynamic_topology=dynamic)
    params = {"w": np.ones((n, 3), np.float32)}
    state = opt.init(params)
    for _ in range(5):
        params, state = opt.step(params, params, state)
    state = opt.init(params)            # a state it did not return
    params, state = opt.step(params, params, state)
    snap = telemetry.snapshot()
    assert _READS not in snap
    assert snap['bf_step_program_builds_total{program="optim_step"}'] == 1
    assert opt._followed == (None, 0)


def test_eager_op_spans_reach_the_profiler_too(tmp_path):
    bf.init(devices=jax.devices()[:4])
    x = np.ones((4, 2), np.float32)
    spans = _trace(tmp_path, lambda: bf.synchronize(
        bf.neighbor_allreduce_nonblocking(x)))
    names = [s[0] for s in spans]
    assert "bf.neighbor_allreduce.ENQUEUE" in names
    assert "bf.synchronize.COMMUNICATE" in names


def test_prefetch_spans_carry_the_batch_number_on_both_threads(tmp_path):
    from bluefog_tpu.data import prefetch_to_device
    bf.init(devices=jax.devices()[:4])
    batches = [np.full((4, 2), i, np.float32) for i in range(3)]

    def consume():
        got = list(prefetch_to_device(iter(batches), size=1))
        assert [float(b[0, 0]) for b in got] == [0.0, 1.0, 2.0]
    spans = _trace(tmp_path, consume)
    place = {s[4]["batch"]: s for s in spans if s[0] == "bf.data.place"}
    wait = {s[4]["batch"]: s for s in spans if s[0] == "bf.data.wait"}
    assert sorted(place) == ["0", "1", "2"]
    assert sorted(wait) == ["0", "1", "2", "3"]     # the last finds the end
    for batch in place:
        assert place[batch][3] != wait[batch][3]    # two threads
        assert place[batch][2] <= wait[batch][2]    # placed, then handed over


def test_timeline_file_and_profiler_get_the_same_spans(tmp_path, monkeypatch):
    monkeypatch.setenv("BLUEFOG_TPU_PYTHON_TIMELINE", "1")
    grad, opt, params, x = _job()
    state = opt.init(params)
    path = tmp_path / "tl.json"
    assert timeline.start_timeline(str(path))
    try:
        spans = _trace(tmp_path / "prof", lambda: opt.step(
            params, grad(params, x), state))
    finally:
        timeline.stop_timeline()
    events = [e for e in json.loads(path.read_text()) if e["ph"] == "B"]
    from_file = sorted(f"bf.{e['cat']}.{e['name']}" for e in events)
    assert from_file == sorted(s[0] for s in spans)
    launch = next(e for e in events
                  if (e["cat"], e["name"]) == ("optim", "launch"))
    assert launch["args"] == {"step": 0}


def test_user_activity_and_op_span_share_the_profiler_side(tmp_path):
    """``timeline_start_activity`` and ``op_span`` open their spans through
    the one helper: both land in a profiler trace, each under its name."""
    assert timeline.start_timeline(str(tmp_path / "tl.json"))
    try:
        def body():
            with timeline.timeline_context("grad_sync", "USER"):
                with timeline.op_span("optim", "step", step=7):
                    pass
        spans = _trace(tmp_path / "prof", body)
    finally:
        timeline.stop_timeline()
    assert [(s[0], s[4]) for s in spans] == [("bf.optim.step",
                                             {"step": "7"})]
    from jax.profiler import ProfileData
    path, = glob.glob(f"{tmp_path}/prof/**/*.xplane.pb", recursive=True)
    names = {e.name for plane in ProfileData.from_file(path).planes
             for line in plane.lines for e in line.events}
    assert "grad_sync:USER" in names


def test_op_span_reports_only_outermost_to_the_hook():
    seen = []
    timeline.set_op_span_hook(lambda op, ph, s: seen.append((op, ph)))
    try:
        with timeline.op_span("optim", "step", step=0):
            with timeline.op_span("optim", "place", leaves=2):
                pass
        done = threading.Thread(target=lambda: timeline.op_span(
            "data", "place", batch=0).__enter__().__exit__(None, None, None))
        done.start()
        done.join()
    finally:
        timeline.set_op_span_hook(None)
    assert seen == [("optim", "step"), ("data", "place")]


def test_donated_state_leaves_the_inflight_window_clean(caplog):
    """The in-flight window used to hold the smallest leaf of the step's
    result, ``state.step``, which the next step donates: a warning a step
    on CPU meshes.  It holds a parameter leaf now."""
    from bluefog_tpu import basics
    grad, opt, params, x = _job(donate=True)
    state = opt.init(params)
    with caplog.at_level("WARNING"):
        for _ in range(basics._max_inflight() + 3):
            params, state = opt.step(params, grad(params, x), state)
        jax.block_until_ready(params)
    assert "in-flight window" not in caplog.text
    assert telemetry.snapshot()["bf_throttle_waits_total"] >= 1


# ---------------------------------------------------------------------------
# Device side: program names and scopes, and nothing but names
# ---------------------------------------------------------------------------

def _step_text(opt, params, state):
    return opt._step_callable(False).lower(params, params, state
                                           ).compile().as_text()


def test_step_program_is_named_and_scoped():
    grad, opt, params, x = _job()
    state = opt.init(params)
    text = _step_text(opt, params, state)
    assert text.startswith("HloModule jit_bf_optim_step,")
    op_names = " ".join(re.findall(r'op_name="([^"]*)"', text))
    for scope in ("bf.optim.update", "bf.optim.fuse", "bf.optim.combine",
                  "bf.optim.unfuse"):
        assert scope in op_names, scope
    # the exchange and its scale/add both sit under combine
    assert re.search(r'collective-permute[^\n]*bf\.optim\.combine', text)
    assert grad.lower(params, x).compile().as_text().startswith(
        "HloModule jit_bf_rank_map_loss,")


@pytest.mark.parametrize("order", ["awc", "gradient_allreduce", "unfused"])
def test_other_orders_carry_the_scopes(monkeypatch, order):
    bf.init(devices=jax.devices()[:4])
    params = {"w": np.ones((4, 3), np.float32),
              "b": np.ones((4, 2), np.float32)}
    if order == "gradient_allreduce":
        opt = bf.optim.DistributedGradientAllreduceOptimizer(optax.sgd(0.1))
        want = ("bf.optim.update", "bf.optim.fuse", "bf.optim.combine")
    elif order == "awc":
        opt = bf.optim.DistributedNeighborAllreduceOptimizer(optax.sgd(0.1))
        want = ("bf.optim.update", "bf.optim.fuse", "bf.optim.combine",
                "bf.optim.unfuse")
    else:
        # threshold 0: every leaf is exchanged alone, nothing is packed
        from bluefog_tpu.optim import functional
        monkeypatch.setattr(functional, "_DIRECT_LEAF_BYTES", 0)
        opt = bf.optim.DistributedNeighborAllreduceOptimizer(optax.sgd(0.1))
        want = ("bf.optim.update", "bf.optim.combine")
    lowered = opt._step_callable(False).lower(
        params, params, opt.init(params)).as_text(debug_info=True)
    for scope in want:
        assert scope in lowered, scope
    if order == "unfused":
        assert "bf.optim.fuse" not in lowered


def test_no_collective_program_is_called_jit_run(caplog):
    bf.init(devices=jax.devices()[:4])
    x = np.ones((4, 2), np.float32)
    with jax.log_compiles(), caplog.at_level("WARNING"):
        bf.synchronize(bf.neighbor_allreduce_nonblocking(x))
        bf.allreduce(x)
        bf.allgather_v([np.ones((i + 1, 2), np.float32) for i in range(4)])
        opt = bf.optim.DistributedNeighborAllreduceOptimizer(optax.sgd(0.1))
        opt.step({"w": x}, {"w": x}, opt.init({"w": x}))
        bf.rank_map(lambda a: a * 2)(x)
    compiled = set(re.findall(r"Compiling jit\(([^)]+)\)", caplog.text))
    assert {"bf_neighbor_allreduce", "bf_allreduce", "bf_allgather_v",
            "bf_optim_init", "bf_optim_step",
            "bf_rank_map_<lambda>"} <= compiled, compiled
    assert "run" not in compiled


def test_rank_map_names_a_callable_without_a_name():
    import functools
    bf.init(devices=jax.devices()[:4])
    mapped = bf.rank_map(functools.partial(jnp.multiply, 2.0))
    text = mapped.lower(np.ones((4, 2), np.float32)).compile().as_text()
    assert text.startswith("HloModule jit_bf_rank_map_fn,")


class _Pending(np.ndarray):
    """An argument the launch did not find ready."""
    def is_ready(self):
        return False


@pytest.mark.parametrize("case,ready,waits", [
    ("every launch held until its arguments were ready", True, 2),
    ("held launches that returned before their arguments", False, 0),
])
def test_rank_map_waits_first_once_the_runtime_held_its_launches(
        monkeypatch, tmp_path, case, ready, waits):
    """``_HELD_LAUNCHES`` launches in a row that took ``_HELD_SECONDS`` and
    returned with every argument ready: the next calls wait for their
    arguments before they launch.  A launch that returned ahead of an
    argument counts for nothing."""
    from bluefog_tpu import basics
    bf.init(devices=jax.devices()[:4])
    monkeypatch.setattr(basics, "_HELD_SECONDS", 0.0)
    mapped = bf.rank_map(lambda a, rest: a * 2)
    x = np.ones((4, 2), np.float32)
    rest = () if ready else (np.zeros((4, 1), np.float32).view(_Pending),)
    out = []

    def calls():
        for _ in range(basics._HELD_LAUNCHES + 2):
            out.append(mapped(x, rest))
    names = [s[0] for s in _trace(tmp_path, calls)
             if s[0].startswith("bf.rank_map.")]
    np.testing.assert_array_equal(out[-1], 2 * x)
    assert names.count("bf.rank_map.launch") == basics._HELD_LAUNCHES + 2
    assert names.count("bf.rank_map.wait") == waits
    assert telemetry.snapshot().get("bf_rank_map_waits_total", 0) == waits
    if waits:
        # the wait comes before its launch, from the fourth call on
        assert names[-4:] == ["bf.rank_map.wait", "bf.rank_map.launch"] * 2


def test_rank_map_a_quick_launch_starts_the_count_again(monkeypatch):
    from bluefog_tpu import basics
    bf.init(devices=jax.devices()[:4])
    mapped = bf.rank_map(lambda a: a * 2)
    x = jnp.ones((4, 2), np.float32)
    for _ in range(3):
        for held in (0.0,) * (basics._HELD_LAUNCHES - 1) + (3600.0,):
            monkeypatch.setattr(basics, "_HELD_SECONDS", held)
            x = mapped(x)   # two held, then one that no clock calls held
    assert "bf_rank_map_waits_total" not in telemetry.snapshot()


def _stripped(text):
    """A compiled module's text less what a name may change: the module's
    name, metadata, and the tables of source locations."""
    text = text[text.index("\n\n%"):] if "\n\n%" in text else text
    text = re.sub(r", metadata=\{[^{}]*\}", "", text)
    return re.sub(r"HloModule \S+", "HloModule _", text)


def test_scopes_change_no_instruction(monkeypatch):
    grad, opt, params, x = _job()
    state = opt.init(params)
    scoped = _step_text(opt, params, state)
    monkeypatch.setattr(timeline, "device_scope",
                        lambda name: contextlib.nullcontext())
    _, plain_opt, _, _ = _job()
    plain = _step_text(plain_opt, params, plain_opt.init(params))
    assert "bf.optim." in scoped and "bf.optim." not in plain
    assert _stripped(scoped) == _stripped(plain)


@pytest.mark.parametrize("B", [1, 2])
def test_lm_gradient_program_carries_the_loss_scope(B):
    from bluefog_tpu import models
    from bluefog_tpu.ops.chunked_loss import chunked_softmax_cross_entropy
    bf.init(devices=jax.devices()[:2])
    cfg = models.TransformerConfig(vocab_size=64, num_layers=1, num_heads=2,
                                   embed_dim=16, max_seq_len=16,
                                   dtype=jnp.float32)
    model = models.TransformerLM(cfg)
    tokens = np.zeros((2, B, 16), np.int32)

    def loss(params, tokens):
        hidden = model.apply({"params": params}, tokens, return_hidden=True)
        return chunked_softmax_cross_entropy(
            hidden, params["lm_head"]["kernel"], jnp.roll(tokens, -1, 1),
            chunk=8)
    params = bf.rank_map(lambda t: model.init(
        jax.random.PRNGKey(0), t)["params"])(tokens)
    text = bf.rank_map(jax.value_and_grad(loss)).lower(
        params, tokens).compile().as_text()
    assert text.startswith("HloModule jit_bf_rank_map_loss,")
    op_names = re.findall(r'op_name="([^"]*)"', text)
    scoped = [o for o in op_names if "bf.loss.chunked" in o]
    # forward, and the transpose that the backward makes of it
    assert any("transpose" in o for o in scoped)
    assert any("transpose" not in o for o in scoped)


def test_flash_kernels_are_named():
    from bluefog_tpu.ops.flash_attention import flash_attention
    q = jnp.ones((1, 128, 2, 64), jnp.float32)

    def loss(q):
        return flash_attention(q, q, q, causal=True).sum()
    text = jax.jit(jax.grad(loss)).lower(q).as_text(debug_info=True)
    for name in ("bf_flash_fwd", "bf_flash_dq", "bf_flash_dkv"):
        assert name in text, name


# ---------------------------------------------------------------------------
# Counters: the optimizer step feeds bf_comm_*_total{op="optimizer_step"}
# ---------------------------------------------------------------------------

N = 8
ROW = (4 * 3 + 3) * 4       # bytes of one rank's row of the tree


@pytest.mark.parametrize("case, opt_kw, comm, edges_per_step", [
    # static Exp2 on 8 ranks: every rank sends to +1, +2, +4
    ("static_exp2", dict(use_dynamic_topology=False), None, 3 * N),
    # one-peer dynamic Exp2: one out-edge per rank a step
    ("one_peer_dynamic_exp2", dict(use_dynamic_topology=True), None, N),
    # every second step communicates: half a call a step
    ("every_second_step", dict(use_dynamic_topology=True,
                               num_steps_per_communication=2), None, N / 2),
    ("identity_combine", dict(), "empty", 0),
])
def test_optimizer_step_wire_bytes(case, opt_kw, comm, edges_per_step):
    bf.init(devices=jax.devices()[:N],
            topology_fn=lambda: topology_util.ExponentialTwoGraph(N))
    params = {"w": np.ones((N, 4, 3), np.float32),
              "b": np.zeros((N, 3), np.float32)}
    args = () if comm is None else (bf.optim.CommunicationType[comm],)
    opt = bf.optim.DistributedAdaptThenCombineOptimizer(
        optax.sgd(0.01), *args, **opt_kw)
    state = opt.init(params)
    k = 6
    for _ in range(k):
        params, state = opt.step(params, params, state)
    snap = telemetry.snapshot()
    wire = snap.get('bf_comm_wire_bytes_total{op="optimizer_step"}', 0.0)
    assert wire == k * edges_per_step * ROW
    calls = snap['bf_comm_calls_total{op="optimizer_step"}']
    assert calls == (k / 2 if case == "every_second_step" else k)
    tree = 0 if case == "identity_combine" else N * ROW
    assert snap['bf_comm_bytes_total{op="optimizer_step"}'] == calls * tree


def test_compression_halves_the_counted_wire_bytes():
    bf.init(devices=jax.devices()[:N],
            topology_fn=lambda: topology_util.ExponentialTwoGraph(N))
    params = {"w": np.ones((N, 4, 3), np.float32),
              "b": np.zeros((N, 3), np.float32)}
    opt = bf.optim.DistributedAdaptThenCombineOptimizer(
        optax.sgd(0.01), use_dynamic_topology=True, compression="bf16")
    opt.step(params, params, opt.init(params))
    assert telemetry.snapshot()[
        'bf_comm_wire_bytes_total{op="optimizer_step"}'] == N * ROW / 2


def test_synced_sample_books_no_phases():
    """``profile_every`` keeps the synced step total and the straggler
    gather; the host's dispatch time is no ``optimizer-update`` phase."""
    from bluefog_tpu.utils import profiler
    profiler._reset_for_tests()
    grad, opt, params, x = _job(profile_every=1)
    opt.step(params, grad(params, x), opt.init(params))
    snap = telemetry.snapshot()
    assert snap["bf_step_seconds_count"] == 1
    assert snap["bf_straggler_reports_total"] == 1
    assert not [k for k in snap if k.startswith("bf_step_phase_seconds")]
    profiler._reset_for_tests()
