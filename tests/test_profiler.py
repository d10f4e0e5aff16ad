"""Step-profiler subsystem tests: histogram bucket math + Prometheus
rendering, snapshot/aggregate merge of histograms, StepProfiler phase
attribution on a fake clock, straggler z-scores on synthetic skew,
trace-merge clock alignment (and truncated-input repair) on hand-built
rank files, the timeline atexit close, and the zero-mutation guard for
``BLUEFOG_TPU_TELEMETRY=0``."""

import json
import os
import subprocess
import sys

import jax
import numpy as np
import optax
import pytest

import bluefog_tpu as bf
from bluefog_tpu import tools
from bluefog_tpu.utils import config, profiler, telemetry


@pytest.fixture(autouse=True)
def _fresh_state():
    telemetry.reset()
    profiler._reset_for_tests()
    yield
    telemetry.reset()
    profiler._reset_for_tests()
    telemetry.stop_http_server()


def _init(n=8):
    bf.init(devices=jax.devices()[:n])
    return n


# ---------------------------------------------------------------------------
# Histogram primitive: bucket math + Prometheus rendering
# ---------------------------------------------------------------------------

def test_histogram_buckets_cumulative_and_sum():
    telemetry.observe("bf_t_seconds", 0.0032, op="x")   # -> le=0.005
    telemetry.observe("bf_t_seconds", 0.9, op="x")      # -> le=1
    telemetry.observe("bf_t_seconds", 1e-7, op="x")     # -> le=1e-06
    telemetry.observe("bf_t_seconds", 999.0, op="x")    # -> overflow (+Inf)
    snap = telemetry.snapshot()
    assert snap['bf_t_seconds_bucket{le="1e-06",op="x"}'] == 1
    assert snap['bf_t_seconds_bucket{le="0.0025",op="x"}'] == 1
    assert snap['bf_t_seconds_bucket{le="0.005",op="x"}'] == 2
    assert snap['bf_t_seconds_bucket{le="1",op="x"}'] == 3
    assert snap['bf_t_seconds_bucket{le="50",op="x"}'] == 3
    assert snap['bf_t_seconds_bucket{le="+Inf",op="x"}'] == 4
    assert snap['bf_t_seconds_count{op="x"}'] == 4
    assert abs(snap['bf_t_seconds_sum{op="x"}'] - 999.9032001) < 1e-6


def test_histogram_boundary_value_lands_in_le_bucket():
    """Prometheus ``le`` is inclusive: an observation exactly on a boundary
    counts in that boundary's bucket."""
    telemetry.observe("bf_b_seconds", 0.001)
    snap = telemetry.snapshot()
    assert snap['bf_b_seconds_bucket{le="0.001"}'] == 1
    assert snap['bf_b_seconds_bucket{le="0.0005"}'] == 0


def test_histogram_buckets_log_spaced_and_clean_labels():
    bounds = telemetry._HIST_BUCKETS
    assert all(b2 > b1 for b1, b2 in zip(bounds, bounds[1:]))
    assert bounds[0] == 1e-6 and bounds[-1] == 50.0
    # decimal-literal boundaries: no float-noise labels like 2.4999999e-06
    for b in bounds:
        assert len(telemetry._fmt_le(b)) <= 8, telemetry._fmt_le(b)


def test_histogram_prometheus_rendering():
    telemetry.observe("bf_h_seconds", 0.02, op="a")
    text = telemetry.render_prometheus()
    lines = text.splitlines()
    assert "# TYPE bf_h_seconds histogram" in lines
    assert 'bf_h_seconds_bucket{le="0.025",op="a"} 1' in lines
    assert 'bf_h_seconds_bucket{le="+Inf",op="a"} 1' in lines
    assert 'bf_h_seconds_sum{op="a"} 0.02' in lines
    assert 'bf_h_seconds_count{op="a"} 1' in lines


def test_histogram_percentiles_interpolation():
    for _ in range(99):
        telemetry.observe("bf_p_seconds", 0.004)   # bucket (0.0025, 0.005]
    telemetry.observe("bf_p_seconds", 20.0)        # bucket (10, 25]
    pct = telemetry.histogram_percentiles("bf_p_seconds", (50.0, 99.0, 100.0))
    assert 0.0025 < pct[50.0] <= 0.005
    assert 0.0025 < pct[99.0] <= 0.005
    assert 10.0 < pct[100.0] <= 25.0
    assert telemetry.histogram_percentiles("bf_nope_seconds") is None


# ---------------------------------------------------------------------------
# Snapshot / aggregate merge
# ---------------------------------------------------------------------------

def test_aggregate_merge_adds_histograms():
    """The cross-rank merge record format: counters sum, gauges max,
    histogram buckets and sums add elementwise."""
    nb = len(telemetry._HIST_BUCKETS) + 1
    c1 = [0] * nb
    c1[3] = 2
    c2 = [0] * nb
    c2[3] = 1
    c2[5] = 4
    rec1 = {"c": [["bf_x_total", [], 1.0]], "g": [["bf_g", [], 2.0]],
            "h": [["bf_l_seconds", [["op", "a"]], c1, 0.5]]}
    rec2 = {"c": [["bf_x_total", [], 3.0]], "g": [["bf_g", [], 1.0]],
            "h": [["bf_l_seconds", [["op", "a"]], c2, 1.5]]}
    out = telemetry._merge_records([rec1, rec2])
    assert out["bf_x_total"] == 4.0
    assert out["bf_g"] == 2.0
    assert out['bf_l_seconds_count{op="a"}'] == 7.0
    assert out['bf_l_seconds_sum{op="a"}'] == 2.0
    b3 = telemetry._HIST_BUCKETS[3]
    assert out['bf_l_seconds_bucket{le="%s",op="a"}'
               % telemetry._fmt_le(b3)] == 3.0


def test_aggregate_snapshot_single_process_includes_histograms():
    n = _init()
    x = np.zeros((n, 2), np.float32)
    bf.neighbor_allreduce(x)
    agg = bf.telemetry_snapshot(aggregate=True)
    assert agg == bf.telemetry_snapshot()
    assert any(k.startswith("bf_comm_dispatch_seconds_bucket")
               for k in agg)


# ---------------------------------------------------------------------------
# StepProfiler phase attribution (fake clock)
# ---------------------------------------------------------------------------

class FakeClock:
    def __init__(self):
        self.t = 100.0

    def __call__(self):
        return self.t

    def advance(self, dt):
        self.t += dt


def test_step_profiler_phase_attribution_fake_clock():
    clock = FakeClock()
    with profiler.step_profile(straggler=False, clock=clock) as p:
        with p.phase("gossip-communicate"):
            clock.advance(0.25)
        with p.phase("optimizer-update"):
            clock.advance(0.1)
        clock.advance(0.05)  # unattributed remainder -> grad-compute
    phases = p.phases()
    assert abs(phases["gossip-communicate"] - 0.25) < 1e-9
    assert abs(phases["optimizer-update"] - 0.1) < 1e-9
    assert abs(phases["grad-compute"] - 0.05) < 1e-9
    snap = telemetry.snapshot()
    assert abs(snap['bf_step_phase_seconds_sum{phase="gossip-communicate"}']
               - 0.25) < 1e-9
    assert snap['bf_step_phase_seconds_count{phase="grad-compute"}'] == 1
    assert abs(snap["bf_step_seconds_sum"] - 0.4) < 1e-9


def test_step_profiler_attributes_op_spans():
    """While a profiler is active, timeline.op_span durations land in the
    mapped phases even with no timeline file."""
    from bluefog_tpu.utils import timeline
    with profiler.step_profile(straggler=False) as p:
        with timeline.op_span("neighbor_allreduce", "ENQUEUE"):
            pass
        with timeline.op_span("synchronize", "COMMUNICATE"):
            pass
        with timeline.op_span("win_update.w", "UPDATE"):
            pass
    phases = p.phases()
    assert "gossip-communicate" in phases
    assert "host-sync" in phases
    assert "optimizer-update" in phases
    # hook cleared after exit: spans outside a profiler attribute nothing
    assert timeline._span_hook is None


def test_nested_op_spans_attribute_once():
    """Per-edge window spans nest inside the op-level span on the same
    thread; only the OUTERMOST span may report, or the same wall time
    double-counts into gossip-communicate."""
    import time as _time

    from bluefog_tpu.utils import timeline
    with profiler.step_profile(straggler=False) as p:
        with timeline.op_span("win_put.w", "COMMUNICATE"):
            with timeline.op_span("win_put.w.0->1", "COMMUNICATE"):
                _time.sleep(0.02)
            with timeline.op_span("win_put.w.0->2", "COMMUNICATE"):
                _time.sleep(0.02)
    comm = p.phases()["gossip-communicate"]
    assert 0.04 <= comm < 0.08, comm  # outer span once, not outer + edges


def test_peer_driven_win_apply_spans_not_attributed():
    """Drain-thread win_apply spans are a NEIGHBOR's traffic landing here;
    they must not bill the step being profiled."""
    from bluefog_tpu.utils import timeline
    with profiler.step_profile(straggler=False) as p:
        with timeline.op_span("win_apply.w.3->0", "COMMUNICATE"):
            pass
    assert "gossip-communicate" not in p.phases()


def test_wrapped_profile_every_gathers_once():
    """opt.step inside bf.step_profile() with profile_every: the outer
    context owns the record — one straggler gather and one bf_step_seconds
    sample per profiled step, host-sync credited to the outer profiler."""
    n = _init()
    params = {"w": np.ones((n, 4), np.float32)}
    grads = {"w": np.full((n, 4), 0.01, np.float32)}
    opt = bf.optim.DistributedNeighborAllreduceOptimizer(
        optax.sgd(0.01), profile_every=2)
    state = opt.init(params)
    profilers = []
    for _ in range(4):
        with bf.step_profile() as p:
            params, state = opt.step(params, grads, state)
        profilers.append(p)
    snap = bf.telemetry_snapshot()
    assert snap["bf_step_seconds_count"] == 4      # once per profiled step
    assert snap["bf_straggler_reports_total"] == 2  # sampled steps only
    assert "host-sync" in profilers[1].phases()     # synced sample credited


def test_request_straggler_respects_explicit_false():
    """An explicit straggler=False opted OUT of collectives (async loops
    are not lockstep); a profile_every sample must not override it."""
    p = profiler.StepProfiler(straggler=False)
    p.request_straggler()
    assert p._straggler is False
    q = profiler.StepProfiler()  # default None: upgradeable
    q.request_straggler()
    assert q._straggler is True


def test_classify_span_mapping():
    assert profiler._classify_span("x", "ENQUEUE") == "gossip-communicate"
    assert profiler._classify_span("win_apply.w.0->1", "COMMUNICATE") \
        == "gossip-communicate"
    assert profiler._classify_span("synchronize", "COMMUNICATE") \
        == "host-sync"
    assert profiler._classify_span("win_update.w", "UPDATE") \
        == "optimizer-update"
    # the training step's own host phases (bf.optim.*, bf.rank_map.*, ...)
    assert profiler._classify_span("optim", "step") == "optimizer-update"
    assert profiler._classify_span("rank_map", "launch") == "grad-compute"
    assert profiler._classify_span("throttle", "wait") == "host-sync"
    assert profiler._classify_span("data", "wait") == "host-sync"


# ---------------------------------------------------------------------------
# Straggler math + end-to-end report
# ---------------------------------------------------------------------------

def test_straggler_zscore_on_synthetic_skew():
    times = [0.1] * 7 + [0.4]
    rep = profiler.straggler_report(times)
    assert rep["slowest_rank"] == 7
    assert rep["straggler_score"] > 2.0
    assert rep["z_scores"][7] == rep["straggler_score"]
    assert all(z < 0 for i, z in enumerate(rep["z_scores"]) if i != 7)
    assert abs(rep["mean_sec"] - np.mean(times)) < 1e-9
    # the ratio carries magnitude the (sqrt(n-1)-capped) z-score cannot:
    assert abs(rep["slowest_over_mean"] - 0.4 / np.mean(times)) < 1e-3
    # a uniform fleet has no straggler
    uniform = profiler.straggler_report([0.2] * 8)
    assert uniform["straggler_score"] == 0.0
    assert uniform["slowest_over_mean"] == 1.0
    assert uniform["z_scores"] == [0.0] * 8


def test_optimizer_profile_every_emits_straggler_and_histograms():
    n = _init()
    params = {"w": np.ones((n, 4), np.float32)}
    grads = {"w": np.full((n, 4), 0.01, np.float32)}
    opt = bf.optim.DistributedNeighborAllreduceOptimizer(
        optax.sgd(0.01), profile_every=2)
    state = opt.init(params)
    for _ in range(4):
        params, state = opt.step(params, grads, state)
    snap = bf.telemetry_snapshot()
    assert snap['bf_optimizer_step_seconds_count{family="collective"}'] == 4
    assert snap["bf_step_seconds_count"] == 2  # steps 2 and 4 synced
    assert "bf_straggler_score" in snap
    assert snap["bf_straggler_reports_total"] == 2
    rep = profiler.last_straggler_report()
    assert rep is not None and len(rep["step_seconds"]) == n
    # single process: every rank reports the same duration -> score 0
    assert rep["straggler_score"] == 0.0
    # surfaced in /healthz ...
    hz = telemetry.health()
    assert hz["straggler"]["slowest_rank"] == rep["slowest_rank"]
    # ... and in %bfstat
    from bluefog_tpu.run.cluster_repl import bfstat_text
    assert "straggler: score" in bfstat_text()


def test_window_optimizer_step_histogram():
    n = _init()
    params = {"w": np.ones((n, 4), np.float32)}
    grads = {"w": np.zeros((n, 4), np.float32)}
    opt = bf.optim.DistributedWinPutOptimizer(optax.sgd(0.0))
    state = opt.init(params)
    try:
        _, state = opt.step(params, grads, state)
    finally:
        opt.free()
    snap = bf.telemetry_snapshot()
    assert snap['bf_optimizer_step_seconds_count{family="window"}'] == 1
    assert 'bf_win_wait_seconds_count' in snap


# ---------------------------------------------------------------------------
# Trace tooling
# ---------------------------------------------------------------------------

def _write_rank_file(path, anchor_mono, anchor_unix, spans, truncate=False):
    """Hand-build a python-writer-format timeline: anchor + B/E spans."""
    events = [{"name": "bf_clock_anchor", "ph": "M", "ts": anchor_mono,
               "pid": 4242, "tid": 0,
               "args": {"monotonic_us": anchor_mono,
                        "unix_us": anchor_unix, "rank": 0}}]
    for name, b, e in spans:
        events.append({"name": name, "cat": "op", "ph": "B", "ts": b,
                       "pid": 4242, "tid": 1})
        events.append({"name": name, "cat": "op", "ph": "E", "ts": e,
                       "pid": 4242, "tid": 1})
    text = "[\n" + ",\n".join(json.dumps(e) for e in events) + "\n]\n"
    if truncate:
        text = text[: text.rfind("},") + 1]  # killed mid-write: no ]
    with open(path, "w") as f:
        f.write(text)


def test_trace_merge_aligns_clocks_across_ranks(tmp_path):
    prefix = str(tmp_path / "tl_")
    # Rank 0: monotonic origin ~0, wall anchor at unix=1_000_000 µs.
    _write_rank_file(prefix + "0.json", 1000, 1_000_000,
                     [("COMMUNICATE", 1000, 2000)])
    # Rank 1: very different monotonic origin; its span starts 600 µs of
    # WALL time after rank 0's.
    _write_rank_file(prefix + "1.json", 500_000, 1_000_500,
                     [("COMMUNICATE", 500_100, 500_400)])
    out = tools.trace_merge(prefix)
    merged = json.load(open(out))  # valid strict JSON
    spans = [e for e in merged if e.get("ph") == "B"]
    by_rank = {e["pid"]: e for e in spans}
    assert set(by_rank) == {0, 1}, "one process lane per rank"
    assert by_rank[0]["ts"] == 0
    assert by_rank[1]["ts"] == 600  # aligned wall skew, not raw clock delta
    names = [(e["pid"], e["args"]["name"]) for e in merged
             if e.get("name") == "process_name"]
    assert (0, "rank 0") in names and (1, "rank 1") in names


def test_trace_merge_repairs_truncated_input(tmp_path):
    prefix = str(tmp_path / "tl_")
    _write_rank_file(prefix + "0.json", 0, 5_000_000,
                     [("ENQUEUE", 10, 20)])
    _write_rank_file(prefix + "1.json", 0, 5_000_000,
                     [("ENQUEUE", 10, 20), ("COMMUNICATE", 30, 40)],
                     truncate=True)
    with pytest.raises(ValueError):
        json.load(open(prefix + "1.json"))  # really is broken JSON
    out = tools.trace_merge(prefix, str(tmp_path / "m.json"))
    merged = json.load(open(out))
    assert {e["pid"] for e in merged if e.get("ph") == "B"} == {0, 1}


def test_trace_merge_reads_sidecar_anchor(tmp_path):
    """The native writer cannot carry the anchor in-band; it lands in a
    ``<file>.anchor.json`` sidecar that trace-merge must honor."""
    prefix = str(tmp_path / "tl_")
    _write_rank_file(prefix + "0.json", 1000, 1_000_000,
                     [("COMMUNICATE", 1000, 2000)])
    # rank 1: no inline anchor (native-writer format), sidecar instead
    events = [{"name": "COMMUNICATE", "cat": "op", "ph": p, "ts": t,
               "pid": 7, "tid": 1}
              for p, t in (("B", 500_100), ("E", 500_400))]
    with open(prefix + "1.json", "w") as f:
        json.dump(events, f)
    with open(prefix + "1.json.anchor.json", "w") as f:
        json.dump({"monotonic_us": 500_000, "unix_us": 1_000_500,
                   "rank": 1}, f)
    out = tools.trace_merge(prefix)
    merged = json.load(open(out))
    starts = {e["pid"]: e["ts"] for e in merged if e.get("ph") == "B"}
    assert starts == {0: 0, 1: 600}  # wall-aligned via the sidecar


def test_trace_summary_warns_on_unmatched_begin(tmp_path):
    prefix = str(tmp_path / "tl_")
    events = [
        {"name": "ENQUEUE", "cat": "op", "ph": "B", "ts": 10, "pid": 0,
         "tid": 1},
        {"name": "ENQUEUE", "cat": "op", "ph": "E", "ts": 30, "pid": 0,
         "tid": 1},
        # a B whose E was dropped (writer overload / truncation)
        {"name": "COMMUNICATE", "cat": "op", "ph": "B", "ts": 40, "pid": 0,
         "tid": 1},
    ]
    path = prefix + "x.json"
    with open(path, "w") as f:
        json.dump(events, f)
    table = tools.trace_summary(path)
    assert "WARNING: 1 begin event(s)" in table


def test_trace_summary_percentiles(tmp_path):
    prefix = str(tmp_path / "tl_")
    spans = [("COMMUNICATE", i * 1000, i * 1000 + 100 + i) for i in range(10)]
    _write_rank_file(prefix + "0.json", 0, 0, spans)
    out = tools.trace_merge(prefix)
    table = tools.trace_summary(out)
    assert "COMMUNICATE" in table
    assert "p50_ms" in table and "p99_ms" in table
    durs, unmatched = tools.phase_durations(json.load(open(out)))
    assert sorted(durs["COMMUNICATE"]) == [100 + i for i in range(10)]
    assert unmatched == 0


def test_trace_merge_cli(tmp_path, capsys):
    prefix = str(tmp_path / "tl_")
    _write_rank_file(prefix + "0.json", 0, 0, [("ENQUEUE", 1, 2)])
    assert tools.main(["trace-merge", prefix]) == 0
    assert "1 rank lane(s)" in capsys.readouterr().out
    assert tools.main(["trace-summary", prefix + "merged.json"]) == 0
    assert "ENQUEUE" in capsys.readouterr().out


def test_live_timeline_merges_per_rank(tmp_path, monkeypatch):
    """End-to-end: a real profiled run's timeline (with the new clock
    anchor) merges into valid JSON whose spans carry the rank lane."""
    from bluefog_tpu.utils import timeline
    monkeypatch.setenv("BLUEFOG_TPU_PYTHON_TIMELINE", "1")
    config.reload()
    prefix = str(tmp_path / "live_")
    try:
        n = _init()
        assert timeline.start_timeline(prefix + "0.json")
        x = np.zeros((n, 2), np.float32)
        bf.neighbor_allreduce(x)
    finally:
        timeline.stop_timeline()
        monkeypatch.delenv("BLUEFOG_TPU_PYTHON_TIMELINE")
        config.reload()
    out = tools.trace_merge(prefix)
    merged = json.load(open(out))
    assert {e["pid"] for e in merged if e.get("ph") in ("B", "E")} == {0}
    assert not any(e.get("name") == "bf_clock_anchor" for e in merged)


_ATEXIT_SCRIPT = """\
import sys
sys.path.insert(0, {repo!r})
import os
os.environ["BLUEFOG_TPU_PYTHON_TIMELINE"] = "1"
import jax
jax.config.update("jax_platforms", "cpu")
from bluefog_tpu.utils import timeline
timeline.start_timeline({path!r})
timeline.timeline_start_activity("t", "USER")
timeline.timeline_end_activity("t", "USER")
# NO stop_timeline(): the atexit hook must close the JSON array.
"""


def test_timeline_atexit_closes_json(tmp_path):
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    path = str(tmp_path / "tl_atexit.json")
    script = tmp_path / "atexit_case.py"
    script.write_text(_ATEXIT_SCRIPT.format(repo=repo, path=path))
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    out = subprocess.run([sys.executable, str(script)], env=env,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    events = json.load(open(path))  # strict parse: the array was closed
    assert any(e.get("name") == "USER" for e in events)
    assert any(e.get("name") == "bf_clock_anchor" for e in events)


# ---------------------------------------------------------------------------
# Disabled path: BLUEFOG_TPU_TELEMETRY=0 mutates nothing
# ---------------------------------------------------------------------------

def test_disabled_observe_and_profile_mutate_nothing(monkeypatch):
    n = _init()
    x = np.zeros((n, 2), np.float32)
    bf.allreduce(x)  # warm caches
    telemetry.reset()
    monkeypatch.setenv("BLUEFOG_TPU_TELEMETRY", "0")
    config.reload()
    try:
        telemetry.observe("bf_nothing_seconds", 0.1, op="x")
        with profiler.step_profile() as p:
            bf.allreduce(x)
            p.attribute("gossip-communicate", 1.0)
        assert telemetry._registry.counters == {}
        assert telemetry._registry.gauges == {}
        assert telemetry._registry.hists == {}
        assert telemetry.snapshot() == {}
        assert profiler.profile_period(5) == 0  # even an explicit period
        assert profiler.last_straggler_report() is None
        from bluefog_tpu.utils import timeline
        assert timeline._span_hook is None  # hook never installed
        # ... and a framework span with no jax.profiler trace, no timeline
        # file and no hook leaves no trace of itself anywhere
        monkeypatch.delenv("BLUEFOG_TIMELINE", raising=False)
        before = (dict(timeline._active), timeline._writer,
                  getattr(timeline._span_depth, "d", 0))
        with timeline.op_span("optim", "step", step=3):
            with timeline.op_span("optim", "launch", step=3):
                pass
        assert (dict(timeline._active), timeline._writer,
                getattr(timeline._span_depth, "d", 0)) == before
        assert before[1] is None and not timeline.timeline_enabled()
        assert telemetry.snapshot() == {}
    finally:
        monkeypatch.delenv("BLUEFOG_TPU_TELEMETRY")
        config.reload()


def test_healthz_overdue_ops_and_straggler_shapes():
    """The /healthz payload carries overdue op NAMES + seconds (the stall
    monitor's live view) alongside the straggler block."""
    hz = telemetry.health()
    assert hz["overdue_ops"] == []
    assert "straggler" not in hz  # no report gathered yet
    profiler._record_straggler(np.array([0.1, 0.1, 0.3, 0.1]))
    hz = telemetry.health()
    assert hz["straggler"]["slowest_rank"] == 2
    assert hz["straggler"]["straggler_score"] > 1.0
    snap = telemetry.snapshot()
    assert snap["bf_straggler_rank"] == 2
