"""What a launch records about the regime it ran in (``basics.rank_map``:
the allocator's state and ``held`` on ``bf.rank_map.launch``, the launch's
and the two waits' seconds as histograms, the counters of launches and held
launches) and the names of the held share's two branches
(``parallel/moe.py::_branch``).  Runs on the CPU mesh with a stubbed
``memory_stats()``: names, arguments and counts, no time."""

import json
import re

import jax
import numpy as np
import pytest

import bluefog_tpu as bf
from bluefog_tpu import basics
from bluefog_tpu.utils import config, telemetry, timeline
from test_program_spans import _job, _trace

GB = 10 ** 9


@pytest.fixture(autouse=True)
def _fresh_registry():
    telemetry.reset()
    yield
    telemetry.reset()


class _Chip:
    """A device whose allocator keeps an account."""

    def __init__(self, *in_use):
        self.in_use = list(in_use)

    def memory_stats(self):
        return {"bytes_limit": 16 * GB, "bytes_in_use": self.in_use.pop(0),
                "bytes_reserved": 3 * GB, "peak_bytes_in_use": 15 * GB,
                "largest_free_block_bytes": GB // 2, "num_allocs": 7}


def _launches(spans):
    return [s[4] for s in spans if s[0] == "bf.rank_map.launch"]


def test_a_launch_carries_the_allocators_state_and_the_gauges_follow(
        tmp_path):
    bf.init(devices=jax.devices()[:4])
    basics._require_init().memory_device = _Chip(9 * GB, 12 * GB, 10 * GB)
    mapped = bf.rank_map(lambda a: a * 2)
    x = np.ones((4, 2), np.float32)
    rooms = []

    def calls():
        for _ in range(3):
            mapped(x)
            rooms.append(telemetry.snapshot()["bf_launch_headroom_min_bytes"])
    args = _launches(_trace(tmp_path, calls))
    assert [a["in_use"] for a in args] == [str(9 * GB), str(12 * GB),
                                           str(10 * GB)]
    for a in args:
        assert (a["limit"], a["reserved"], a["largest_free"], a["held"]) == (
            str(16 * GB), str(3 * GB), str(GB // 2), "0")
        assert set(a) == {"in_use", "reserved", "largest_free", "limit",
                          "held"}
    # the least room any launch has found only falls; the kinds are the last
    assert rooms == [4 * GB, 1 * GB, 1 * GB]
    snap = telemetry.snapshot()
    assert snap['bf_launch_memory_bytes{kind="in_use"}'] == 10 * GB
    assert snap['bf_launch_memory_bytes{kind="largest_free"}'] == GB // 2
    assert snap['bf_launch_memory_bytes{kind="limit"}'] == 16 * GB
    # ... until bf.init() starts the account again
    bf.init(devices=jax.devices()[:4])
    basics._require_init().memory_device = _Chip(5 * GB)
    _trace(tmp_path / "again", lambda: mapped(x))
    assert telemetry.snapshot()["bf_launch_headroom_min_bytes"] == 8 * GB


def test_nothing_is_sampled_while_nobody_listens():
    """A ``memory_stats()`` call and the readiness checks cost a launch 80
    to 105 us on a v5e host (PR 52): more than its spans, so they wait for
    a listener.  The histograms and the count of launches do not."""
    bf.init(devices=jax.devices()[:4])
    basics._require_init().memory_device = _Chip()      # asked: IndexError
    mapped = bf.rank_map(lambda a, rest: a * 2)
    x = np.ones((4, 2), np.float32)
    assert not timeline.listening()
    for _ in range(2):
        mapped(x, (x.copy().view(_Late),))
    snap = telemetry.snapshot()
    assert snap["bf_rank_map_launches_total"] == 2
    assert snap["bf_rank_map_launch_seconds_count"] == 2
    assert not [k for k in snap if "held" in k or "memory" in k
                or "headroom" in k]
    seen = []
    timeline.set_op_span_hook(lambda *a: seen.append(a))
    try:
        assert timeline.listening()
        with pytest.raises(IndexError):
            mapped(x, (x,))
    finally:
        timeline.set_op_span_hook(None)


def test_a_platform_without_an_account_records_nothing(tmp_path):
    """``memory_stats()`` is None on the CPU meshes of tier-1."""
    bf.init(devices=jax.devices()[:4])
    assert basics._require_init().memory_device.memory_stats() is None
    mapped = bf.rank_map(lambda a: a * 2)
    args = _launches(_trace(tmp_path, lambda: mapped(
        np.ones((4, 2), np.float32))))
    assert args == [{"held": "0"}]
    assert not [k for k in telemetry.snapshot() if "memory" in k
                or "headroom" in k]


class _Late(np.ndarray):
    """An argument still being computed when the launch begins and ready
    when it returns."""
    asked = 0

    def is_ready(self):
        self.asked += 1
        return self.asked > 1


class _Pending(np.ndarray):
    def is_ready(self):
        return False


@pytest.mark.parametrize("case,view,held", [
    ("the launch outlasted its arguments", _Late, 1),
    ("the arguments were ready before it", np.ndarray, 0),
    ("an argument is still pending after it", _Pending, 0)])
def test_held_says_whether_a_launch_outlasted_its_arguments(
        tmp_path, case, view, held):
    """No threshold: the rule that makes ``rank_map`` wait keeps its own
    (``_HELD_SECONDS``), and none of these launches counts for it."""
    bf.init(devices=jax.devices()[:4])
    mapped = bf.rank_map(lambda a, rest: a * 2)
    x = np.ones((4, 2), np.float32)
    mapped(x, (x,))          # built: the launches below are launches only
    telemetry.reset()
    out = []

    def calls():
        for _ in range(2):
            # the batch comes first and is ready: every argument is asked
            out.append(mapped(x, {"params": (x.copy().view(view),)}))
    args = _launches(_trace(tmp_path, calls))
    np.testing.assert_array_equal(out[-1], 2 * x)
    assert [a["held"] for a in args] == [str(held)] * 2
    snap = telemetry.snapshot()
    assert snap["bf_rank_map_launches_total"] == 2
    assert snap.get("bf_rank_map_held_launches_total", 0) == 2 * held
    assert "bf_rank_map_waits_total" not in snap


def test_one_leaf_stands_for_its_argument():
    """``_pending`` asks the first leaf of each argument and walks no
    tree."""
    asked = []

    class Leaf:
        def __init__(self, name, ready):
            self.name, self.ready = name, ready

        def is_ready(self):
            asked.append(self.name)
            return self.ready
    a, b, c, d = (Leaf("a", True), Leaf("b", True), Leaf("c", False),
                  Leaf("d", False))
    pending = basics._pending(({"x": a, "y": {"z": b}}, (), [None, (c, d)],
                               3.0))
    assert pending == (c,) and asked == ["a", "c"]
    assert basics._first_leaf({"k": [(), {}], "l": None}) is None


def _seconds(snap, name):
    return snap.get(f"{name}_count", 0)


@pytest.mark.parametrize("on", [True, False], ids=["on", "off"])
def test_the_histograms_and_counters_grow_by_one_a_step(monkeypatch, on):
    if not on:
        monkeypatch.setenv("BLUEFOG_TPU_TELEMETRY", "0")
        config.reload()
    try:
        monkeypatch.setattr(basics, "_HELD_SECONDS", 0.0)
        grad, opt, params, x = _job(use_dynamic_topology=False)
        state = opt.init(params)
        counts = []
        for _ in range(basics._HELD_LAUNCHES + 3):
            # ready arguments and a threshold of 0: from the fourth call on
            # the launch waits for its arguments first
            jax.block_until_ready(params)
            params, state = opt.step(params, grad(params, x), state)
            snap = telemetry.snapshot()
            counts.append([_seconds(snap, "bf_rank_map_launch_seconds"),
                           _seconds(snap, "bf_rank_map_wait_seconds"),
                           _seconds(snap, "bf_optim_wait_seconds"),
                           snap.get("bf_rank_map_launches_total", 0),
                           snap.get("bf_rank_map_held_launches_total", 0)])
    finally:
        monkeypatch.undo()
        config.reload()
    if not on:
        assert counts == [[0] * 5] * len(counts)
        return
    steps = list(range(1, len(counts) + 1))
    assert [c[0] for c in counts] == steps
    assert [c[3] for c in counts] == steps
    assert [c[1] for c in counts] == [0, 0, 0, 1, 2, 3]
    # step() waits for the step before the one it launched: from the second
    assert [c[2] for c in counts] == [0] + steps[:-1]
    assert [c[4] for c in counts] == [0] * len(counts)   # ready before


def test_the_late_arguments_reach_the_timeline_file_too(tmp_path,
                                                        monkeypatch):
    monkeypatch.setenv("BLUEFOG_TPU_PYTHON_TIMELINE", "1")
    bf.init(devices=jax.devices()[:4])
    basics._require_init().memory_device = _Chip(9 * GB)
    mapped = bf.rank_map(lambda a: a * 2)
    path = tmp_path / "tl.json"
    assert timeline.start_timeline(str(path))
    try:
        mapped(np.ones((4, 2), np.float32))
    finally:
        timeline.stop_timeline()
    edges = [e for e in json.loads(path.read_text())
             if (e.get("cat"), e["name"]) == ("rank_map", "launch")]
    assert [e["ph"] for e in edges] == ["B", "E"]
    assert edges[0]["args"]["in_use"] == 9 * GB
    assert edges[1]["args"] == {"held": 0}


# ---------------------------------------------------------------------------
# Device side: the held share's two branches carry their own names
# ---------------------------------------------------------------------------

def test_both_branches_are_named_in_every_pass_and_no_scope_moves():
    """``tiny-laguna`` holds 4 of 16 experts, so its share has a window
    (``tiny-xing`` holds half: no window, no conditional)."""
    import twins
    from benchmark import spec
    from bluefog_tpu.parallel import moe
    common = spec.load_module("layer_metrics/program_common.py")
    pass_of = spec.load_module("layer_metrics/regime_common.py").pass_of
    config, task, _ = twins.load("tiny-laguna")
    model = task.make_model(config)
    batch = {"sequences": 2, "seq_len": 128}

    def shapes(key):
        params, aux = task.init(model, key, config, batch)
        return params, aux, task.make_batch(key, config, batch)
    params, aux, one = jax.eval_shape(shapes, jax.random.PRNGKey(34))
    text = jax.jit(jax.value_and_grad(
        task.loss_fn(model, config), has_aux=True)).lower(
            params, aux, *one).compile().as_text()
    op_names = re.findall(r'op_name="([^"]*)"', text)
    # the recompute's overflow branch keeps nothing for the transpose
    # (``_held_fwd``): the compiler leaves no operation in it
    for marker, passes in (
            (moe.HELD_WINDOW, {"forward", "recompute", "transpose"}),
            (moe.HELD_OVERFLOW, {"forward", "transpose"})):
        mine = [o for o in op_names if marker in o]
        assert {pass_of(o) for o in mine} == passes, marker
        # inside the one conditional of its layer, and of no other shape
        assert all("/bf.moe.dispatch/cond/" in o for o in mine), marker
        assert not re.search(r"bf\.[a-z_]+\.[a-z_]+",
                             marker)      # no reader's scope
    assert not [o for o in op_names
                if moe.HELD_WINDOW in o and moe.HELD_OVERFLOW in o]
    # the readers of the scopes see what they saw
    stripped = text.replace("/" + moe.HELD_WINDOW, "").replace(
        "/" + moe.HELD_OVERFLOW, "")
    assert "bf_moe_held" not in stripped
    scopes = common.instruction_scopes(text)
    assert scopes == common.instruction_scopes(stripped)
    assert {"bf.moe.dispatch", "bf.moe.experts", "bf.moe.combine"} <= set(
        scopes.values())
