"""Communication microbenchmark: the gossip hot path's compiled schedule.

Measures what an end-to-end training benchmark (``benchmark/run.py``)
cannot isolate: the round count, edge count and per-op walltime of
``neighbor_allreduce`` under the naive shift-distance schedule vs the
min-round repack (``ops/schedule_opt.py``), across the topology families
that matter — shift-structured (ring, Exp2: already optimal, the repack
must be a no-op), star (irregular hub) and random-regular (the stress
case: ~n naive rounds vs degree optimal).

``--transport`` / ``--transport-smoke`` instead run the DCN window
transport loopback microbench (no jax needed): ``WindowTransport``
endpoints on localhost exchange gossip rows across a small-row size
sweep (64 B / 256 B / 4 KB) in three modes — ``legacy`` (one blocking
native RPC + one Python apply per row), ``python`` (the PR-4 coalesced
path: Python sender workers, OP_BATCH frames, vectorized zero-copy
drain) and ``native`` (the C++ hot path: per-peer queues, frame encode,
drain decode + same-slot fold all in ``winsvc.cc``) — plus a
concurrent-peers axis (N client transports round-robin into one server)
reporting msgs/s, MB/s and the drain-burst p50/p99 per configuration.
The smoke variant is the CI gate (``make transport-smoke``): tiny
counts, asserts batched delivery happened, the native path actually
engaged when available, and the batch + native telemetry series exist —
no timing assertion (shared CI boxes jitter); the full variant asserts
the >= 5x native messages/s win over the Python coalesced path for
<= 256 B rows (10x target), and additionally runs the ``ffi`` leg
(below) when the capability is present.

``--ffi`` / ``--ffi-smoke`` run the zero-copy XLA put-path microbench
(``make ffi-smoke``): window puts of DEVICE arrays through a loopback
store in three modes — ``legacy`` (Python coalesced sender), ``native``
(the PR-9 host-staged put feeding the C++ sender) and ``ffi``
(``BLUEFOG_TPU_WIN_XLA``: the XLA buffer pointer handed straight to the
native put-plan executor) — reporting put-side dispatch us/row (flush
factored out of the clock) and end-to-end msgs/s.  The smoke asserts
the FFI path engaged and ``bf_win_host_copy_bytes_total`` reports ZERO
put-side staging bytes for dense f32 rows; the full variant also
asserts the >= 2x dispatch win over the native path for rows >= 4 KiB.

``--hier`` / ``--hier-smoke`` run the hierarchical-gossip report
(``make hier-smoke``): flat static Exp2 vs the two-level mode (dense ICI
inner, sparse one-peer DCN outer with cadence + compression) on
simulated 2x(4x8) and 4x(4x4) multi-slice tori — per-step DCN wire
rows, modeled inter-slice serial link time and simulated consensus
distance, asserting >= 4x DCN reduction at equal-or-better consensus,
plus the end-to-end product-topology equivalence and the sparse codec
OP_BATCH round-trip.

CPU-runnable by design: ppermute schedules compile and execute on the
virtual host-platform mesh, so schedule regressions are caught by
``make bench-comm-smoke`` with no accelerator attached.  On CPU the script
forces ``--n`` virtual devices itself (before jax imports); on a real
backend it uses the attached devices and clamps ``--n`` to them.

Prints ONE JSON line:
  {"metric": "gossip_schedule_opt_round_reduction_random_regular",
   "value": <naive_rounds / optimized_rounds>, "unit": "x", ...}
with per-topology detail: rounds/edges before/after, per-op walltime for
both schedules, and the max |naive - optimized| output difference
(must be <= 1e-6 at fp32 — the repack is output-equivalent).
"""

import argparse
import json
import os
import time


def _parse_args():
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--n", type=int, default=None,
                   help="mesh/topology size (default: 32 on CPU, else the "
                        "attached device count)")
    p.add_argument("--degree", type=int, default=4,
                   help="random-regular degree (default 4)")
    p.add_argument("--payload", type=int, default=2048,
                   help="per-rank f32 payload elements (default 2048)")
    p.add_argument("--iters", type=int, default=10,
                   help="timed iterations per schedule (default 10)")
    p.add_argument("--reps", type=int, default=2,
                   help="op applications fused per timed call (amortizes "
                        "dispatch; default 2 — naive schedules on irregular "
                        "topologies chain O(n) ppermutes per application, "
                        "and XLA compile time grows with the chain)")
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--smoke", action="store_true",
                   help="tiny fast configuration for CI (n=8, few iters)")
    p.add_argument("--transport", action="store_true",
                   help="run the window-transport loopback microbench "
                        "(coalescing on vs off) instead of the schedule "
                        "bench; asserts the >= 2x messages/s win")
    p.add_argument("--transport-smoke", action="store_true",
                   help="tiny CI variant of --transport: asserts batched "
                        "delivery + metric presence, no timing assertion")
    p.add_argument("--ffi", action="store_true",
                   help="run the zero-copy XLA put-path microbench "
                        "(BLUEFOG_TPU_WIN_XLA): put-side dispatch us/row "
                        "and msgs/s of the legacy / PR-9 native / FFI "
                        "window put paths through a loopback store; "
                        "asserts the >= 2x dispatch win for rows >= 4 KiB "
                        "and zero staging-copy bytes on the FFI leg")
    p.add_argument("--ffi-smoke", action="store_true",
                   help="tiny CI variant of --ffi (`make ffi-smoke`): "
                        "asserts the FFI path engaged + zero staging-copy "
                        "bytes, no timing assertion; graceful skip when "
                        "jax.ffi or the native bf_xla symbols are absent")
    p.add_argument("--async-smoke", action="store_true",
                   help="structural CI gate of the barrier-free async "
                        "gossip mode (`make async-smoke`): a loopback "
                        "two-transport rig drives real accumulates whose "
                        "origin-step clock is pinned behind the "
                        "receiver's (the injected delay), asserts the "
                        "bounded-staleness fold rejected them into the "
                        "stale-residual store with the counters on "
                        "/metrics + the async /healthz block, that "
                        "win_fold_stale_residuals restores mass exactly, "
                        "and that BLUEFOG_TPU_TELEMETRY=0 leaves the "
                        "registry untouched")
    p.add_argument("--tracerec-smoke", action="store_true",
                   help="CI gate of message-level tracing "
                        "(`make tracerec-smoke`): flight recorder on + "
                        "sampled wire trace tags through a loopback "
                        "window-store pair — asserts the per-edge "
                        "contribution-age histograms appear on /metrics "
                        "and /healthz, the recorder dump decodes into a "
                        "valid merged trace with flow arrows, and the "
                        "BLUEFOG_TPU_TELEMETRY=0 zero-mutation guard")
    p.add_argument("--stripe-smoke", action="store_true",
                   help="CI gate of the multi-stream striped transport "
                        "(`make stripe-smoke`): asserts >= 2 stripes "
                        "engage on the loopback rig with per-stripe "
                        "telemetry present, and that a pinned "
                        "BLUEFOG_TPU_WIN_STRIPES=1 leg reproduces the "
                        "pre-stripe wire behavior exactly")
    p.add_argument("--rows", type=int, default=5000,
                   help="transport bench: messages per mode (default 5000)")
    p.add_argument("--row-bytes", type=int, default=4096,
                   help="transport bench: payload bytes per message "
                        "(default 4096 — the small-gossip-row regime)")
    p.add_argument("--placement", action="store_true",
                   help="run the physical-placement cost-model report "
                        "(modeled link-load naive vs optimized across "
                        "ring/Exp2/star/random-regular on simulated 4x8 "
                        "and 8x8 tori) plus an end-to-end output-"
                        "equivalence check on the virtual CPU mesh")
    p.add_argument("--placement-smoke", action="store_true",
                   help="CI variant of --placement (same assertions, "
                        "same tori — the cost model is pure host math)")
    p.add_argument("--placement-iters", type=int, default=1000,
                   help="simulated-annealing refinement iterations for "
                        "the placement search (default 1000)")
    p.add_argument("--hier", action="store_true",
                   help="run the hierarchical-gossip report: per-step DCN "
                        "wire rows, modeled inter-slice serial link time "
                        "and simulated consensus distance of flat exp2 vs "
                        "the two-level mode on simulated 2x(4x8) and "
                        "4x(4x4) multi-slice tori, plus an end-to-end "
                        "product-topology equivalence check on the "
                        "virtual CPU mesh; asserts >= 4x DCN reduction "
                        "at equal-or-better consensus")
    p.add_argument("--hier-smoke", action="store_true",
                   help="CI variant of --hier (same assertions — the "
                        "cost model and consensus simulation are pure "
                        "host math)")
    p.add_argument("--synth", action="store_true",
                   help="run the schedule-synthesis report: modeled "
                        "serial_link_time naive / congestion-packed / "
                        "synthesized across ring/Exp2/star/random-regular "
                        "on simulated 4x8, 8x8 and multi-slice tori, plus "
                        "an end-to-end output-equivalence check of a "
                        "synthesized schedule on the virtual CPU mesh")
    p.add_argument("--synth-smoke", action="store_true",
                   help="CI variant of --synth (same assertions — the "
                        "cost model is pure host math)")
    p.add_argument("--sharded", action="store_true",
                   help="run the sharded-gossip report: simulated MoE "
                        "trees at 25/50/75%% replicated fraction assert "
                        "per-step DCN bytes scale with the replicated "
                        "fraction only (sharded slices never cross "
                        "replica groups), plus an executor leg on the "
                        "8-device CPU mesh checking the dense oracle, "
                        "the per-shard telemetry split and the "
                        "BLUEFOG_TPU_SHARDED_GOSSIP=0 bitwise hatch")
    p.add_argument("--sharded-smoke", action="store_true",
                   help="CI variant of --sharded (same assertions — "
                        "`make sharded-smoke`)")
    return p.parse_args()


def _transport_one_mode(mode: str, rows: int, row_bytes: int,
                        peers: int = 1, stripes: int = 1,
                        windows: int = 8, trace_every: int = 0,
                        recorder: bool = False) -> dict:
    """Loopback exchange of ``peers x rows`` messages in one mode.

    Modes: ``legacy`` (per-message blocking sends, coalescing off),
    ``python`` (PR-4 coalesced path: Python sender workers + batched
    drain, ``BLUEFOG_TPU_WIN_NATIVE=0``) and ``native`` (the C++ hot
    path: per-peer queues, frame encode, drain decode + fold all in
    ``winsvc.cc``).  ``peers`` distinct client transports feed ONE server
    round-robin — N TCP connections, N reader threads, interleaved
    frames: the drain-side concurrency axis.  (One producer thread drives
    them all: N Python sender threads would measure GIL convoying, not
    the receive path.)

    End-to-end timing: the clock stops when the LAST message has been
    applied at the receiver, so the drain side (per-message Python apply
    vs vectorized batch apply vs native fold) is part of what's measured
    — exactly the halves the tentpole moved to C++.  Returns rates plus
    the server's drain-burst p50/p99 for the run."""
    import threading

    import numpy as np

    from bluefog_tpu.ops.transport import (OP_ACCUMULATE, OP_TRACE_FLAG,
                                           WindowTransport, make_trace_tag)
    from bluefog_tpu.utils import config, flightrec, telemetry

    prev_native = os.environ.get("BLUEFOG_TPU_WIN_NATIVE")
    prev_coalesce = os.environ.get("BLUEFOG_TPU_WIN_COALESCE")
    prev_stripes = os.environ.get("BLUEFOG_TPU_WIN_STRIPES")
    prev_trace = os.environ.get("BLUEFOG_TPU_TRACE_SAMPLE")
    os.environ["BLUEFOG_TPU_WIN_COALESCE"] = \
        "0" if mode == "legacy" else "1"
    os.environ["BLUEFOG_TPU_WIN_NATIVE"] = \
        "1" if mode == "native" else "0"
    os.environ["BLUEFOG_TPU_WIN_STRIPES"] = str(max(1, stripes))
    if trace_every > 0:
        os.environ["BLUEFOG_TPU_TRACE_SAMPLE"] = str(trace_every)
    else:
        os.environ.pop("BLUEFOG_TPU_TRACE_SAMPLE", None)
    # Long linger: the bench flushes explicitly (as window ops do at op
    # boundaries), so batch sizes reflect the queue, not the clock.
    os.environ.setdefault("BLUEFOG_TPU_WIN_COALESCE_LINGER_MS", "5")
    config.reload()
    telemetry.reset()  # per-mode isolation for the drain histograms

    state = {"n": 0, "batches": 0}
    done = threading.Event()
    target = [0]
    lock = threading.Lock()

    def count(k):
        with lock:
            state["n"] += k
            if state["n"] >= target[0]:
                done.set()

    def apply(op, name, src, dst, weight, p_weight, payload):
        count(1)

    def apply_batch(msgs):
        state["batches"] += 1
        count(len(msgs))

    def apply_items(items):
        n = 0
        for kind, payload in items:
            n += (payload[5] + payload[6]) if kind else 1
        count(n)

    server = WindowTransport(apply, apply_batch=apply_batch,
                             apply_items=apply_items, drain_interval=0.0005)
    # Several windows + rotating src ranks so the (window, row) shard
    # actually spreads across stripes (one window/one row would pin a
    # single stripe and measure nothing).
    names = [f"bench{w}" for w in range(max(1, windows))]
    for nm in names:
        server.register_window(nm, row_bytes // 4)
    clients = [WindowTransport(lambda *a: None) for _ in range(peers)]
    if recorder:
        flightrec.enable()
        flightrec.reset()  # this cell's events only
    try:
        row = np.arange(row_bytes // 4, dtype=np.float32)
        row_blob = row.tobytes()
        host, port = "127.0.0.1", server.port
        nw = len(names)

        def payload_for(i):
            # Sampled wire trace tag, exactly as the window layer appends
            # it (the 1-in-N tobytes+concat IS the sender-side tagging
            # cost this cell measures).
            tag = make_trace_tag(i % 8)
            if tag is None:
                return OP_ACCUMULATE, row
            return (OP_ACCUMULATE | OP_TRACE_FLAG,
                    np.frombuffer(row_blob + tag, np.uint8))

        def exchange(count_per_client):
            done.clear()
            total = count_per_client * peers
            target[0] = state["n"] + total
            if state["n"] >= target[0]:
                done.set()
            t0 = time.perf_counter()
            if trace_every > 0:
                sends = [c.send for c in clients]
                for i in range(total):
                    op, payload = payload_for(i)
                    sends[i % peers](host, port, op, names[i % nw],
                                     i % 8, 1, 1.0, payload)
            elif peers == 1:
                send = clients[0].send
                for i in range(count_per_client):
                    send(host, port, OP_ACCUMULATE, names[i % nw],
                         i % 8, 1, 1.0, row)
            else:
                sends = [c.send for c in clients]
                for i in range(total):
                    sends[i % peers](host, port, OP_ACCUMULATE,
                                     names[i % nw], i % 8, 1, 1.0, row)
            for c in clients:
                c.flush()
            assert done.wait(timeout=300), \
                f"only {state['n']}/{target[0]} messages arrived"
            return time.perf_counter() - t0

        exchange(min(rows // 10 + 1, 200))  # warm the connection pool
        dt = exchange(rows)
        total = rows * peers
        for c in clients:
            c.stop()
        server.stop()  # final telemetry pump before the histogram read
        clients.clear()
        burst = telemetry.histogram_percentiles(
            "bf_win_drain_burst_seconds", qs=(50.0, 99.0)) or {}
        snap = telemetry.snapshot() if telemetry.enabled() else {}
        engaged = {k.split('stripe="', 1)[1].split('"', 1)[0]
                   for k in snap
                   if k.startswith("bf_win_tx_stripe_bytes_total")}
        res = {
            "mode": mode,
            "peers": peers,
            "stripes": stripes,
            "stripes_engaged": len(engaged),
            "row_bytes": row_bytes,
            "native_engaged": bool(server.native_path),
            "decode_threads": int(getattr(server, "decode_threads", 0)),
            "msgs_per_s": round(total / dt, 1),
            "mb_per_s": round(total * row_bytes / dt / 1e6, 2),
            "batches_seen": state["batches"],
            "drain_burst_p50_ms": round(burst.get(50.0, 0.0) * 1e3, 3),
            "drain_burst_p99_ms": round(burst.get(99.0, 0.0) * 1e3, 3),
        }
        if recorder:
            # Per-edge one-way delay (enqueue → drain decode) from the
            # flight-recorder events — sender and receiver share this
            # process, so one pseudo-dump at offset 0 joins both ends.
            from bluefog_tpu.tools import tracegossip
            ev = flightrec.snapshot()
            delays = tracegossip.edge_delays(
                [{"rank": 0, "offset_us": 0, "events": ev}])
            res["tracing"] = {
                "rec_events": int(len(ev)),
                "sample_every": trace_every,
                "edges": {f"{s}->{d}": {
                    "tags": int(len(v)),
                    "p50_ms": round(float(np.percentile(v, 50)) / 1e3, 3),
                    "p99_ms": round(float(np.percentile(v, 99)) / 1e3, 3)}
                    for (s, d), v in delays.items()},
            }
        return res
    finally:
        for c in clients:
            c.stop()
        try:
            server.stop()
        except Exception:  # noqa: BLE001 — double-stop after success path
            pass
        for var, prev in (("BLUEFOG_TPU_WIN_NATIVE", prev_native),
                          ("BLUEFOG_TPU_WIN_COALESCE", prev_coalesce),
                          ("BLUEFOG_TPU_WIN_STRIPES", prev_stripes),
                          ("BLUEFOG_TPU_TRACE_SAMPLE", prev_trace)):
            if prev is None:
                os.environ.pop(var, None)
            else:
                os.environ[var] = prev
        config.reload()


def transport_main(args) -> int:
    """Loopback transport microbench (and the `make transport-smoke` CI
    gate): the small-row size sweep (64 B / 256 B / 4 KB) across the
    legacy / Python-coalesced / native paths, plus a concurrent-peers
    axis on the native path.  The full variant asserts the native hot
    path's >= 5x messages/s win over the PR-4 Python coalesced path for
    <= 256 B rows (10x target); the smoke variant asserts structure only
    (batched delivery happened, the native path actually engaged when
    available, the telemetry series exist) — shared CI boxes jitter too
    much for timing gates."""
    import sys

    from bluefog_tpu import native
    from bluefog_tpu.utils import telemetry

    smoke = args.transport_smoke
    rows = min(args.rows, 300) if smoke else args.rows
    if not native.available():
        print(json.dumps({
            "metric": "win_transport_native_speedup",
            "value": None, "unit": "x", "status": "no_native",
            "detail": {"reason": "native core not built"}}))
        return 0 if smoke else 2
    # An explicit BLUEFOG_TPU_WIN_NATIVE=0 in the launch environment pins
    # the whole run to the Python fallback (the `make transport-smoke`
    # native-off leg): the native modes are skipped, nothing native is
    # asserted — the same behavior as a host whose .so lacks the symbols.
    native_ok = (native.has_win_native()
                 and os.environ.get("BLUEFOG_TPU_WIN_NATIVE") != "0")

    sizes = [64, 256, 4096]
    modes = ["python"] + (["native"] if native_ok else [])
    sweep = []
    failures = []

    # Legacy reference at the CLI row size (fewer rows: one blocking RPC
    # per message is ~15x slower) — the PR-4 coalesce ratio stays visible
    # in the trajectory.
    legacy = _transport_one_mode("legacy", max(rows // 4, 50),
                                 args.row_bytes)
    if legacy["batches_seen"] != 0:
        failures.append("legacy path delivered batch frames")

    for row_bytes in sizes:
        for mode in modes:
            res = _transport_one_mode(mode, rows, row_bytes)
            sweep.append(res)
            if mode == "python" and res["batches_seen"] == 0:
                failures.append(
                    f"python coalescing on but no batch frame arrived "
                    f"({row_bytes} B)")
            if mode == "native" and not res["native_engaged"]:
                failures.append(
                    f"native path available but did not engage "
                    f"({row_bytes} B)")

    # Telemetry presence (from the LAST run's registry — reset per mode):
    # the batch series must exist on whichever path ran last.
    snap = telemetry.snapshot() if telemetry.enabled() else {}
    for series in ("bf_win_tx_batches_total", "bf_win_tx_batched_msgs_total",
                   "bf_win_tx_batch_size", "bf_win_rx_batches_total"):
        if not any(k.startswith(series) for k in snap):
            failures.append(f"expected telemetry series {series!r}")
    if native_ok:
        for series in ("bf_win_native_tx_frames_total",
                       "bf_win_native_rx_frames_total"):
            if not any(k.startswith(series) for k in snap):
                failures.append(
                    f"native path engaged but series {series!r} missing")

    # Concurrent-peers axis (drain-side scaling): p99 drain burst should
    # stay flat as senders multiply — the folded commit path does per-RUN
    # Python work, not per-message.
    peer_axis = [1, 2] if smoke else [1, 4, 8]
    peers_tbl = []
    if native_ok:
        for p in peer_axis:
            peers_tbl.append(_transport_one_mode(
                "native", max(rows // p, 50), 256, peers=p))

    # Stripe axis (multi-stream transport): 1/2/4 stripes x 4 KiB/64 KiB/
    # 256 KiB rows x 1/8 concurrent peers on the native path — the
    # regime where a single fat link is bounded by one stream.  Reported
    # as msgs/s + drain p99 per cell; the headline ratio is best-striped
    # vs single-stream at >= 64 KiB rows under 8 peers.
    stripe_tbl = []
    stripe_speedup = None
    if native_ok and not smoke:
        for row_bytes in (4096, 65536, 262144):
            # Scale the message count down with the row so every cell
            # moves a comparable byte volume.
            per = max(80, int(rows * 4096 / max(row_bytes, 4096)))
            for p in (1, 8):
                for st in (1, 2, 4):
                    stripe_tbl.append(_transport_one_mode(
                        "native", max(per // p, 40), row_bytes, peers=p,
                        stripes=st))

        def _cell(row_bytes, p, st):
            for r in stripe_tbl:
                if (r["row_bytes"], r["peers"], r["stripes"]) == \
                        (row_bytes, p, st):
                    return r
            return None

        ratios_sp = []
        for row_bytes in (65536, 262144):
            base = _cell(row_bytes, 8, 1)
            cands = [c for c in (_cell(row_bytes, 8, s) for s in (2, 4))
                     if c]
            if base and cands:
                best = max(cands, key=lambda c: c["msgs_per_s"])
                ratios_sp.append(best["msgs_per_s"] / base["msgs_per_s"])
        if ratios_sp:
            stripe_speedup = round(max(ratios_sp), 2)

    def _rate(mode, row_bytes):
        for r in sweep:
            if r["mode"] == mode and r["row_bytes"] == row_bytes:
                return r["msgs_per_s"]
        return None

    ratios = {}
    for row_bytes in sizes:
        py, nat = _rate("python", row_bytes), _rate("native", row_bytes)
        if py and nat:
            ratios[row_bytes] = round(nat / py, 2)
    small_ratio = max((v for k, v in ratios.items() if k <= 256),
                      default=None)

    # FFI leg (full runs only — it needs jax + the loopback store): the
    # zero-copy XLA put path vs the PR-9 native and legacy Python put
    # paths, folded into this report's detail.  Capability-gated with a
    # graceful skip, like every other degraded mode here.
    ffi_detail = None
    ffi_value = None
    if not smoke and native_ok:
        from bluefog_tpu import native as _native
        if _native.has_win_xla() \
                and os.environ.get("BLUEFOG_TPU_WIN_XLA") != "0":
            os.environ.setdefault("JAX_PLATFORMS", "cpu")
            flags = os.environ.get("XLA_FLAGS", "")
            if "xla_force_host_platform_device_count" not in flags:
                os.environ["XLA_FLAGS"] = (
                    flags + " --xla_force_host_platform_device_count=8")
            # armed() is the full capability check (it also catches a
            # non-CPU jax backend, where auto-disarm is the documented
            # degraded mode): skip, never fail, when it says no.
            from bluefog_tpu.ops import xlaffi as _xlaffi
            if _xlaffi.armed():
                ffi_value, ffi_detail, ffi_failures = _ffi_report(
                    smoke=False)
                failures.extend(f"ffi leg: {f}" for f in ffi_failures)
            else:
                ffi_detail = {"skipped": _xlaffi.disarm_reason()}
        else:
            ffi_detail = {"skipped": "jax.ffi or bf_xla symbols absent"}

    # Tracing leg — LAST, because arming the flight recorder is
    # process-sticky and must not touch the cells above.  Two readouts:
    # the 4 KiB / 8-peer overhead pair (recorder on + 1/64 sampled trace
    # tags vs plain — the acceptance cell for the <= 2% regression bound
    # on real hardware; reported, not asserted, on shared CI boxes) and
    # the per-edge one-way-delay p50/p99 from every-message tags
    # (detail.tracing — the direct per-link latency sensor that confirms
    # the PR-11 stripe win on the restored multi-host rig).
    tracing_detail = None
    if native_ok:
        t_rows = max(rows // 8, 50)
        base = _transport_one_mode("native", t_rows, 4096, peers=8)
        traced = _transport_one_mode("native", t_rows, 4096, peers=8,
                                     trace_every=64, recorder=True)
        delay_leg = _transport_one_mode("native", max(t_rows // 2, 50),
                                        4096, peers=2, trace_every=1,
                                        recorder=True)
        tracing_detail = {
            "overhead_cell": {
                "row_bytes": 4096, "peers": 8, "sample_every": 64,
                "base_msgs_per_s": base["msgs_per_s"],
                "traced_msgs_per_s": traced["msgs_per_s"],
                "ratio": round(traced["msgs_per_s"]
                               / max(base["msgs_per_s"], 1e-9), 3),
            },
        }
        tracing_detail.update(delay_leg.get("tracing", {}))
        if not delay_leg.get("tracing", {}).get("edges"):
            failures.append(
                "tracing leg produced no per-edge delay readout")

    # Link-observatory leg — the same rig, judged on the ONLINE estimator
    # (utils/linkobs.py).  Two readouts next to detail.tracing:
    #   (a) the overhead pair: the traced 4 KiB / 8-peer cell with
    #       BLUEFOG_TPU_LINK_OBS=0 vs 1 — the acceptance bound (<= 2% on
    #       quiet hardware) is reported, not asserted, on shared CI
    #       boxes; the OFF cell is asserted bitwise inert (not one
    #       bf_link_* series), the ON cell must publish tx goodput;
    #   (b) the flight recorder's per-edge delay samples fed through
    #       linkobs.note_delay (the loopback rig bypasses the window
    #       commit path that feeds the estimator in-process), reported
    #       as the same link table bf.link_report() serves.
    links_detail = None
    if native_ok and tracing_detail is not None:
        from bluefog_tpu.tools import tracegossip
        from bluefog_tpu.utils import config, flightrec, linkobs, telemetry
        prev_obs = os.environ.get("BLUEFOG_TPU_LINK_OBS")
        # The goodput gauge publishes once per >= 0.5 s rate window —
        # longer than a whole smoke cell.  Shrink the window (read at
        # call time) so the ON cell publishes deterministically.
        prev_win = linkobs._GOODPUT_WINDOW_S
        try:
            os.environ["BLUEFOG_TPU_LINK_OBS"] = "0"
            off = _transport_one_mode("native", t_rows, 4096, peers=8,
                                      trace_every=64)
            snap = telemetry.snapshot() if telemetry.enabled() else {}
            inert = not any(k.startswith("bf_link_") for k in snap)
            if not inert:
                failures.append(
                    "BLUEFOG_TPU_LINK_OBS=0 leg still published bf_link_* "
                    "series (the off-switch is not bitwise inert)")
            os.environ["BLUEFOG_TPU_LINK_OBS"] = "1"
            linkobs._GOODPUT_WINDOW_S = 0.02
            on = _transport_one_mode("native", t_rows, 4096, peers=8,
                                     trace_every=64)
            snap = telemetry.snapshot() if telemetry.enabled() else {}
            if not any(k.startswith("bf_link_goodput_bytes")
                       for k in snap):
                failures.append(
                    "link observatory armed but the tx path published no "
                    "bf_link_goodput_bytes series")
            linkobs.reset()
            delays = tracegossip.edge_delays(
                [{"rank": 0, "offset_us": 0,
                  "events": flightrec.snapshot()}])
            for (s, d), samples in sorted(delays.items()):
                for us in samples:
                    linkobs.note_delay(int(s), int(d), float(us))
            rep = linkobs.local_report()
            if not rep.get("edges"):
                failures.append(
                    "link observatory produced no edge table from the "
                    "recorder's delay samples")
            links_detail = {
                "overhead_cell": {
                    "row_bytes": 4096, "peers": 8, "sample_every": 64,
                    "off_msgs_per_s": off["msgs_per_s"],
                    "on_msgs_per_s": on["msgs_per_s"],
                    "ratio": round(on["msgs_per_s"]
                                   / max(off["msgs_per_s"], 1e-9), 3),
                    "off_inert": inert,
                },
                "report": rep,
            }
        finally:
            linkobs._GOODPUT_WINDOW_S = prev_win
            linkobs.reset()
            if prev_obs is None:
                os.environ.pop("BLUEFOG_TPU_LINK_OBS", None)
            else:
                os.environ["BLUEFOG_TPU_LINK_OBS"] = prev_obs
            config.reload()

    rc = 0
    for f in failures:
        print(f"bench_comm --transport: {f}", file=sys.stderr)
        rc = 1
    if not smoke and native_ok and (small_ratio is None
                                    or small_ratio < 5.0):
        print(f"bench_comm: native transport speedup {small_ratio}x < 5x "
              "for <=256 B rows", file=sys.stderr)
        rc = 1
    print(json.dumps({
        "metric": "win_transport_native_speedup",
        "value": small_ratio,
        "unit": "x",
        "detail": {
            "rows": rows,
            "smoke": smoke,
            "native_available": native_ok,
            "ratios_by_row_bytes": ratios,
            "legacy": legacy,
            "sweep": sweep,
            "peers": peers_tbl,
            "stripes": stripe_tbl,
            "stripe_speedup_64k_plus_8p": stripe_speedup,
            "ffi_dispatch_speedup": ffi_value,
            "ffi": ffi_detail,
            "tracing": tracing_detail,
            "links": links_detail,
        },
    }))
    return rc


def stripe_main(args) -> int:
    """`make stripe-smoke`: the multi-stream striped transport CI gate.

    Three structural assertions, no timing (shared CI boxes jitter):
      1. a 2-stripe loopback run actually engages >= 2 stripes (distinct
         per-stripe telemetry series carried bytes) and, on the native
         path, the drain decode pool is live with its busy gauge present;
      2. per-stripe series exist: `bf_win_tx_stripe_bytes_total` and the
         (peer, stripe)-labeled `bf_win_tx_queue_depth` gauges;
      3. a pinned BLUEFOG_TPU_WIN_STRIPES=1 leg reproduces the pre-stripe
         wire exactly — one sender, send-order delivery with identical
         fields and payload bytes, fence weight 0.0.
    """
    import sys
    import threading

    import numpy as np

    from bluefog_tpu import native
    from bluefog_tpu.utils import telemetry

    if not native.available():
        print(json.dumps({
            "metric": "win_transport_stripes_engaged",
            "value": None, "unit": "stripes", "status": "no_native",
            "detail": {"reason": "native core not built"}}))
        return 0
    native_ok = (native.has_win_native()
                 and os.environ.get("BLUEFOG_TPU_WIN_NATIVE") != "0")
    failures = []

    # -- leg 1: striped run, >= 2 stripes engaged + telemetry ---------------
    mode = "native" if native_ok else "python"
    res = _transport_one_mode(mode, 300, 4096, peers=2, stripes=2)
    if res["stripes_engaged"] < 2:
        failures.append(
            f"only {res['stripes_engaged']} stripe(s) engaged with "
            "BLUEFOG_TPU_WIN_STRIPES=2")
    if native_ok and not res["native_engaged"]:
        failures.append("native path available but did not engage")
    snap = telemetry.snapshot() if telemetry.enabled() else {}
    for series in ("bf_win_tx_stripe_bytes_total",):
        stripes_seen = {k.split('stripe="', 1)[1].split('"', 1)[0]
                        for k in snap if k.startswith(series)}
        if len(stripes_seen) < 2:
            failures.append(
                f"expected >= 2 stripe labels on {series!r}, "
                f"got {sorted(stripes_seen)}")
    if not any(k.startswith("bf_win_tx_queue_depth") and 'stripe="' in k
               for k in snap):
        failures.append("per-stripe bf_win_tx_queue_depth gauges missing")
    if native_ok and res["decode_threads"] > 0 and not any(
            k.startswith("bf_win_rx_decode_pool_busy") for k in snap):
        failures.append("bf_win_rx_decode_pool_busy gauge missing with a "
                        "live decode pool")

    # -- leg 2: STRIPES=1 pinned — the pre-stripe wire, exactly -------------
    from bluefog_tpu.ops import transport as T
    from bluefog_tpu.ops import window as W
    from bluefog_tpu.utils import config as _config
    prev = {v: os.environ.get(v) for v in
            ("BLUEFOG_TPU_WIN_STRIPES", "BLUEFOG_TPU_WIN_NATIVE",
             "BLUEFOG_TPU_WIN_COALESCE_LINGER_MS")}
    os.environ["BLUEFOG_TPU_WIN_STRIPES"] = "1"
    os.environ["BLUEFOG_TPU_WIN_NATIVE"] = "0"
    os.environ["BLUEFOG_TPU_WIN_COALESCE_LINGER_MS"] = "2"
    _config.reload()
    got = []
    cv = threading.Condition()

    def apply(op, name, src, dst, weight, p_weight, payload):
        with cv:
            got.append((op, name, src, dst, weight, bytes(payload)))
            cv.notify_all()

    def apply_batch(msgs):
        for m in msgs:
            apply(*m)

    server = T.WindowTransport(apply, apply_batch=apply_batch)
    client = T.WindowTransport(lambda *a: None)
    try:
        if client.n_stripes != 1:
            failures.append(
                f"STRIPES=1 leg resolved {client.n_stripes} stripes")
        host, port = "127.0.0.1", server.port
        expect = []
        for i in range(8):
            row = np.arange(16, dtype=np.float32) * (i + 1)
            client.send(host, port, T.OP_PUT, "w", i, 1, 0.5, row)
            expect.append((T.OP_PUT, "w", i, 1, 0.5, row.tobytes()))
        client.send(host, port, T.OP_FENCE_REQ, "", 0, -1,
                    W._fanout_weight(1), np.zeros(0, np.float32))
        expect.append((T.OP_FENCE_REQ, "", 0, -1, 0.0, b""))
        client.flush()
        with cv:
            ok = cv.wait_for(lambda: len(got) >= len(expect), timeout=30)
        if not ok or got != expect:
            failures.append(
                "STRIPES=1 wire differs from the pre-stripe transport "
                f"(got {len(got)} messages)")
        if sorted(k[2] for k in client._senders) not in ([], [0]):
            failures.append("STRIPES=1 leg created stripe senders > 0")
    finally:
        client.stop()
        server.stop()
        for var, val in prev.items():
            if val is None:
                os.environ.pop(var, None)
            else:
                os.environ[var] = val
        _config.reload()

    rc = 0
    for f in failures:
        print(f"bench_comm --stripe-smoke: {f}", file=sys.stderr)
        rc = 1
    print(json.dumps({
        "metric": "win_transport_stripes_engaged",
        "value": res["stripes_engaged"],
        "unit": "stripes",
        "detail": {
            "native_available": native_ok,
            "striped_cell": res,
            "single_stripe_wire_ok": all(
                "STRIPES=1" not in f for f in failures),
        },
    }))
    return rc


def async_main(args) -> int:
    """`make async-smoke`: the barrier-free async gossip CI gate.

    Structural assertions, no timing — a loopback two-transport rig
    (real win_accumulate through the real coalesced/native drain path)
    with the async mode armed:
      1. a FRESH round (origin-step clock == receiver clock) commits
         into staging on the exact legacy arithmetic path;
      2. a STALE round — the sender's origin-step clock pinned behind
         the receiver's (the injected delay: exactly what a straggler's
         gossip looks like on the wire) — is rejected into the
         stale-residual store, with `bf_win_stale_rejected_total{src}`
         on /metrics and the "async" block (step, lag, policy) in
         /healthz;
      3. win_fold_stale_residuals folds the held mass back into staging
         EXACTLY (wire + residual + folded == input, the conservation
         invariant, proven on real wire frames);
      4. a BLUEFOG_TPU_TELEMETRY=0 leg runs the same traffic with the
         registry left completely untouched (the policy still applies —
         it is state, not telemetry).
    """
    import sys
    import threading
    import urllib.error
    import urllib.request

    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8")
    prev = {v: os.environ.get(v) for v in (
        "BLUEFOG_TPU_ASYNC", "BLUEFOG_TPU_ASYNC_STALENESS_STEPS",
        "BLUEFOG_TPU_ASYNC_STALENESS_POLICY", "BLUEFOG_TPU_TRACE_SAMPLE",
        "BLUEFOG_TPU_TELEMETRY", "BLUEFOG_TPU_WIN_COALESCE_LINGER_MS")}
    os.environ.update({
        "BLUEFOG_TPU_ASYNC": "1",
        "BLUEFOG_TPU_ASYNC_STALENESS_STEPS": "4",
        "BLUEFOG_TPU_ASYNC_STALENESS_POLICY": "reject",
        "BLUEFOG_TPU_TRACE_SAMPLE": "1",
        "BLUEFOG_TPU_TELEMETRY": "1",
        "BLUEFOG_TPU_WIN_COALESCE_LINGER_MS": "100",
    })
    import numpy as np

    import bluefog_tpu as bf
    from bluefog_tpu import topology as topo
    from bluefog_tpu.ops import transport as T
    from bluefog_tpu.ops import window as W
    from bluefog_tpu.utils import config as _config
    from bluefog_tpu.utils import telemetry
    _config.reload()
    failures = []
    bf.init(lambda: topo.RingGraph(8))
    telemetry.reset()

    def drive(rounds):
        """Real accumulate streams through the loopback store; each round
        is (origin_step, rows 8xD).  Returns the committed window state.
        The window is created pre-directory so one store serves both
        wire ends (the tracerec/test_win_xla pattern)."""
        applied = [0]
        cv = threading.Condition()

        def bump(k):
            with cv:
                applied[0] += k
                cv.notify_all()

        def apply(op, name, src, dst, weight, p_weight, payload):
            W._apply_inbound(op, name, src, dst, weight, p_weight, payload)
            bump(1)

        def apply_batch(msgs):
            W._apply_inbound_batch(msgs)
            bump(len(msgs))

        def apply_items(items):
            W._apply_inbound_items(items)
            bump(sum((p[5] + p[6]) if k else 1 for k, p in items))

        server = T.WindowTransport(apply, apply_batch=apply_batch,
                                   apply_items=apply_items)
        client = T.WindowTransport(lambda *a: None)
        saved = W._store.distrib
        try:
            assert bf.win_create(np.zeros((8, 6), np.float32), "asmoke",
                                 zero_init=True)
            server.register_window("asmoke", 6)
            W._store.distrib = W._Distrib(
                client, rank_owner={r: r % 2 for r in range(8)},
                proc_addr={0: ("127.0.0.1", 1),
                           1: ("127.0.0.1", server.port)},
                my_proc=0)
            W.configure_async()
            # The receiver's step clock: contributions age against it.
            W.set_async_step(100)
            total = 0
            for origin_step, t in rounds:
                # Injected delay: pin the SENDER-side origin-step clock
                # (both encoders) behind the receiver's — each tag now
                # says "I was computed at step <origin_step>".
                T.set_trace_origin_step(origin_step)
                bf.win_accumulate(t, "asmoke")
                total += 8  # the ring's 8 remote (even->odd) edges
                with cv:
                    assert cv.wait_for(lambda: applied[0] >= total,
                                       timeout=30), (applied[0], total)
            win = W._store.get("asmoke")
            with win.lock:
                return (
                    {k: v.copy() for k, v in win.staging.items()},
                    {k: v.copy() for k, v in win.stale_residual.items()},
                    W.win_fold_stale_residuals("asmoke"),
                    {k: v.copy() for k, v in win.staging.items()},
                )
        finally:
            W._store.distrib = saved
            bf.win_free("asmoke")
            client.stop()
            server.stop()

    fresh = np.random.RandomState(5).randn(8, 6).astype(np.float32)
    stale = np.random.RandomState(6).randn(8, 6).astype(np.float32)
    staging, residual, folded, after = drive(
        [(99, fresh), (50, stale)])    # ages 1 (fresh) and 50 (stale)
    # The ring's 8 remote (even-src -> odd-dst) edges, wraparound included.
    remote = sorted({((s + step) % 8, s)
                     for s in range(0, 8, 2) for step in (1, -1)})
    n_stale_edges = 0
    for key in remote:
        d, s = key
        exp_fresh = fresh[s]
        exp_stale = stale[s]
        if not np.array_equal(staging.get(key), exp_fresh):
            failures.append(f"edge {key}: fresh round not committed "
                            "on the legacy path")
        if key in residual:
            n_stale_edges += 1
            if not np.array_equal(residual[key], exp_stale):
                failures.append(f"edge {key}: stale residual mismatch")
        if not np.array_equal(after.get(key), exp_fresh + exp_stale):
            failures.append(f"edge {key}: fold did not restore mass "
                            "exactly")
    if n_stale_edges == 0:
        failures.append("no edge ever hit the staleness policy")

    # -- /metrics + /healthz surfaces ---------------------------------------
    port = telemetry.start_http_server(0)
    with urllib.request.urlopen(
            f"http://127.0.0.1:{port}/metrics", timeout=10) as r:
        text = r.read().decode()
    try:
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/healthz", timeout=10) as r:
            hz = json.loads(r.read().decode())
    except urllib.error.HTTPError as e:   # degraded status is still JSON
        hz = json.loads(e.read().decode())
    if "bf_win_stale_rejected_total" not in text:
        failures.append("bf_win_stale_rejected_total missing on /metrics")
    ablock = hz.get("async")
    if not ablock:
        failures.append("no async block in /healthz")
    elif ablock.get("staleness_steps") != 4 or "stale_rejected" not in \
            ablock:
        failures.append(f"async /healthz block incomplete: {ablock}")

    # -- BLUEFOG_TPU_TELEMETRY=0 zero-mutation guard ------------------------
    os.environ["BLUEFOG_TPU_TELEMETRY"] = "0"
    _config.reload()
    telemetry.reset()
    W.clear_async_staleness()
    _, residual0, _, _ = drive([(40, stale)])
    leaked = telemetry.snapshot()
    if not residual0:
        failures.append("TELEMETRY=0 leg: policy did not apply (it is "
                        "state, not telemetry)")
    if leaked:
        failures.append("BLUEFOG_TPU_TELEMETRY=0 leg mutated the "
                        f"registry: {sorted(leaked)[:5]}")

    for var, val in prev.items():
        if val is None:
            os.environ.pop(var, None)
        else:
            os.environ[var] = val
    _config.reload()
    W.configure_async()
    W.clear_async_staleness()
    T.set_trace_origin_step(-1)
    telemetry.stop_http_server()

    rc = 0
    for f in failures:
        print(f"bench_comm --async-smoke: {f}", file=sys.stderr)
        rc = 1
    print(json.dumps({
        "metric": "win_async_stale_edges",
        "value": n_stale_edges,
        "unit": "edges",
        "detail": {
            "healthz_async": ablock,
            "fold_restored_exactly": rc == 0,
            "zero_mutation_ok": not leaked,
        },
    }))
    return rc


def tracerec_main(args) -> int:
    """`make tracerec-smoke`: the message-level observability CI gate.

    Structural assertions, no timing:
      1. with the flight recorder armed and trace tags sampled at 1/2, a
         loopback window-store pair (real win_put/win_accumulate through
         the real drain path) lands `bf_win_contribution_age_seconds{src}`
         histograms + freshest/stalest gauges on /metrics and the
         contribution_age block in /healthz;
      2. the recorder ring carries the event chain (enqueue ... commit)
         and its dump decodes into a valid merged chrome trace with at
         least one matched flow arrow (trace-gossip);
      3. BLUEFOG_TPU_TELEMETRY=0 zero-mutation guard: the same traffic
         leaves the registry completely untouched (the recorder is an
         independent knob and may still record).
    """
    import sys
    import tempfile
    import threading
    import urllib.request

    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8")
    prev = {v: os.environ.get(v) for v in (
        "BLUEFOG_TPU_TRACE_SAMPLE", "BLUEFOG_TPU_FLIGHT_RECORDER",
        "BLUEFOG_TPU_TELEMETRY", "BLUEFOG_TPU_WIN_COALESCE_LINGER_MS")}
    os.environ.update({
        "BLUEFOG_TPU_TRACE_SAMPLE": "2",
        "BLUEFOG_TPU_FLIGHT_RECORDER": "1",
        "BLUEFOG_TPU_TELEMETRY": "1",
        "BLUEFOG_TPU_WIN_COALESCE_LINGER_MS": "200",
    })
    import numpy as np

    import bluefog_tpu as bf
    from bluefog_tpu import native
    from bluefog_tpu import topology as topo
    from bluefog_tpu.ops import transport as T
    from bluefog_tpu.ops import window as W
    from bluefog_tpu.tools import tracegossip
    from bluefog_tpu.utils import config as _config
    from bluefog_tpu.utils import flightrec, telemetry
    _config.reload()
    if not native.available():
        print(json.dumps({
            "metric": "win_tracing_age_edges",
            "value": None, "unit": "edges", "status": "no_native",
            "detail": {"reason": "native core not built"}}))
        return 0
    failures = []
    bf.init(lambda: topo.RingGraph(8))
    telemetry.reset()

    def drive(n_steps=4):
        """A real put/accumulate stream through the loopback store (the
        window created pre-directory, so one store serves both wire
        ends — the test_win_xla pattern)."""
        applied = [0]
        cv = threading.Condition()

        def bump(k):
            with cv:
                applied[0] += k
                cv.notify_all()

        def apply(op, name, src, dst, weight, p_weight, payload):
            W._apply_inbound(op, name, src, dst, weight, p_weight, payload)
            bump(1)

        def apply_batch(msgs):
            W._apply_inbound_batch(msgs)
            bump(len(msgs))

        def apply_items(items):
            W._apply_inbound_items(items)
            bump(sum((p[5] + p[6]) if k else 1 for k, p in items))

        server = T.WindowTransport(apply, apply_batch=apply_batch,
                                   apply_items=apply_items)
        client = T.WindowTransport(lambda *a: None)
        saved = W._store.distrib
        rng = np.random.RandomState(7)
        try:
            assert bf.win_create(rng.randn(8, 6).astype(np.float32),
                                 "trc", zero_init=True)
            server.register_window("trc", 6)
            W._store.distrib = W._Distrib(
                client, rank_owner={r: r % 2 for r in range(8)},
                proc_addr={0: ("127.0.0.1", 1),
                           1: ("127.0.0.1", server.port)},
                my_proc=0)
            total = 0
            for step in range(n_steps):
                t = np.random.RandomState(100 + step) \
                    .randn(8, 6).astype(np.float32)
                if step % 2:
                    bf.win_accumulate(t, "trc")
                else:
                    bf.win_put(t, "trc")
                total += 8  # the ring's 8 remote (even->odd) edges per op
                with cv:
                    assert cv.wait_for(lambda: applied[0] >= total,
                                       timeout=30), (applied[0], total)
        finally:
            W._store.distrib = saved
            bf.win_free("trc")
            client.stop()
            server.stop()

    flightrec.reset()
    W.clear_contribution_age()
    drive()

    # -- leg 1: age telemetry on /metrics + /healthz ------------------------
    port = telemetry.start_http_server(0)
    with urllib.request.urlopen(
            f"http://127.0.0.1:{port}/metrics", timeout=10) as r:
        text = r.read().decode()
    with urllib.request.urlopen(
            f"http://127.0.0.1:{port}/healthz", timeout=10) as r:
        hz = json.loads(r.read().decode())
    for series in ("bf_win_contribution_age_seconds_bucket",
                   "bf_win_contribution_freshest_age_seconds",
                   "bf_win_contribution_stalest_age_seconds"):
        if series not in text:
            failures.append(f"missing {series} on /metrics")
    ages = hz.get("contribution_age")
    if not ages:
        failures.append("no contribution_age block in /healthz")
    n_edges = len(ages or {})

    # -- leg 2: recorder chain + merged-trace decode ------------------------
    ev = flightrec.snapshot()
    etypes = set(int(e) for e in ev["etype"])
    want = {flightrec.ENQUEUE, flightrec.COMMIT}
    if not want <= etypes:
        failures.append(
            f"recorder event chain incomplete: have {sorted(etypes)}, "
            f"need at least {sorted(want)}")
    with tempfile.TemporaryDirectory() as tmp:
        prefix = os.path.join(tmp, "flightrec")
        path = flightrec.dump(path=f"{prefix}.0.bin", reason="smoke")
        if path is None:
            failures.append("flight recorder dump failed")
        else:
            out, stats = tracegossip.merge_gossip(prefix)
            with open(out) as f:
                json.load(f)  # valid chrome-trace JSON
            if stats["flows_matched"] < 1:
                failures.append(
                    f"no flow arrows matched in the merged trace "
                    f"({stats})")

    # -- leg 3: BLUEFOG_TPU_TELEMETRY=0 zero-mutation guard -----------------
    os.environ["BLUEFOG_TPU_TELEMETRY"] = "0"
    _config.reload()
    telemetry.reset()
    W.clear_contribution_age()
    drive(n_steps=2)
    leaked = telemetry.snapshot()
    if leaked:
        failures.append(
            "BLUEFOG_TPU_TELEMETRY=0 leg mutated the registry: "
            f"{sorted(leaked)[:5]}")

    for var, val in prev.items():
        if val is None:
            os.environ.pop(var, None)
        else:
            os.environ[var] = val
    _config.reload()
    telemetry.stop_http_server()

    rc = 0
    for f in failures:
        print(f"bench_comm --tracerec-smoke: {f}", file=sys.stderr)
        rc = 1
    print(json.dumps({
        "metric": "win_tracing_age_edges",
        "value": n_edges,
        "unit": "edges",
        "detail": {
            "contribution_age": ages,
            "rec_events": int(len(ev)),
            "etypes": sorted(etypes),
            "zero_mutation_ok": not leaked,
        },
    }))
    return rc


def _ffi_one_mode(mode: str, elems: int, bursts: int, per_burst: int):
    """Put-side microbench of one window put path through a loopback
    store: ``legacy`` (Python coalesced sender, WIN_NATIVE=0), ``native``
    (the PR-9 C++ sender fed by the host-staged put loop) and ``ffi``
    (the zero-copy XLA plan dispatch, WIN_XLA=1).

    Two numbers per mode:
      * ``dispatch_us_per_row`` — the put-side HOST overhead: min over
        bursts of the per-put dispatch wall time with the op-boundary
        flush factored OUT of the clock (queued frames ship once per
        burst outside it), so wire + drain time — identical across
        modes — cannot mask the host-path difference the tentpole
        targets;
      * ``msgs_per_s`` — end-to-end blocking-put throughput (clock stops
        at the last receiver apply), reported for context (no assertion:
        on a 2-core CI box it measures scheduler contention as much as
        the path).
    """
    import threading

    import numpy as np
    import jax.numpy as jnp

    import bluefog_tpu as bf
    from bluefog_tpu import topology as topo
    from bluefog_tpu.ops import transport as T
    from bluefog_tpu.ops import window as W
    from bluefog_tpu.ops import xlaffi
    from bluefog_tpu.utils import config, telemetry

    saved_env = {k: os.environ.get(k) for k in
                 ("BLUEFOG_TPU_WIN_COALESCE",
                  "BLUEFOG_TPU_WIN_COALESCE_LINGER_MS",
                  "BLUEFOG_TPU_WIN_NATIVE", "BLUEFOG_TPU_WIN_XLA",
                  "BLUEFOG_TPU_WIN_COMPRESSION")}
    os.environ.update(
        BLUEFOG_TPU_WIN_COALESCE="1",
        # Long linger: nothing ships inside the timed dispatch region;
        # the per-burst flush (outside the clock) puts it on the wire.
        BLUEFOG_TPU_WIN_COALESCE_LINGER_MS="2000",
        BLUEFOG_TPU_WIN_NATIVE="0" if mode == "legacy" else "1",
        BLUEFOG_TPU_WIN_XLA="1" if mode == "ffi" else "0",
        BLUEFOG_TPU_WIN_COMPRESSION="none")
    config.reload()
    xlaffi._reset_for_tests()
    telemetry.reset()
    bf.init(lambda: topo.RingGraph(8))
    applied = [0]
    cv = threading.Condition()

    def bump(k):
        with cv:
            applied[0] += k
            cv.notify_all()

    server = T.WindowTransport(
        lambda *a: bump(1),
        apply_batch=lambda m: bump(len(m)),
        apply_items=lambda it: bump(
            sum((p[5] + p[6]) if k else 1 for k, p in it)))
    client = T.WindowTransport(lambda *a: None)
    saved_distrib = W._store.distrib
    real_flush = W._flush_transport
    x = np.zeros((8, elems), np.float32)
    try:
        assert bf.win_create(x, "ffibench", zero_init=True)
        server.register_window("ffibench", elems)
        # Even ranks owned here; odd ranks' owner is the loopback server
        # feeding the same store — the ring's 8 even->odd out-edges all
        # travel the wire.
        W._store.distrib = W._Distrib(
            client, {r: r % 2 for r in range(8)},
            {0: ("127.0.0.1", 1), 1: ("127.0.0.1", server.port)}, 0)
        t = jnp.asarray(np.random.RandomState(0)
                        .randn(8, elems).astype(np.float32))
        t.block_until_ready()
        win = W._store.get("ffibench")
        edges = W._resolve_edge_weights(None, win.out_nbrs, 1.0,
                                        ranks=win.owned)
        W._do_put("ffibench", t, edges, False, False)  # warm plan/keys
        total_puts = 1
        times = []
        for _ in range(bursts):
            W._flush_transport = lambda *a, **k: None
            t0 = time.perf_counter()
            for _ in range(per_burst):
                W._do_put("ffibench", t, edges, False, False)
            times.append((time.perf_counter() - t0) / per_burst)
            W._flush_transport = real_flush
            W.win_flush()
            total_puts += per_burst
            with cv:
                assert cv.wait_for(
                    lambda: applied[0] >= total_puts * 8, timeout=120), \
                    (applied[0], total_puts * 8)
        # End-to-end throughput: blocking puts, clock to the last apply.
        e2e_puts = max(per_burst // 2, 20)
        before = applied[0]
        t0 = time.perf_counter()
        for _ in range(e2e_puts):
            bf.win_put(t, "ffibench", require_mutex=False)
        with cv:
            assert cv.wait_for(
                lambda: applied[0] >= before + e2e_puts * 8, timeout=120)
        e2e_dt = time.perf_counter() - t0
        snap = telemetry.snapshot()
        copies = {p: snap.get(
            f'bf_win_host_copy_bytes_total{{path="{p}"}}', 0)
            for p in ("device_get", "edge_temp", "enqueue")}
        return {
            "mode": mode,
            "row_bytes": elems * 4,
            "dispatch_us_per_put": round(min(times) * 1e6, 2),
            "dispatch_us_per_row": round(min(times) * 1e6 / 8, 3),
            "msgs_per_s": round(e2e_puts * 8 / e2e_dt, 1),
            "ffi_engaged": snap.get("bf_win_xla_puts_total", 0) > 0,
            "host_copy_bytes": copies,
        }
    finally:
        W._flush_transport = real_flush
        W._store.distrib = saved_distrib
        bf.win_free("ffibench")
        client.stop()
        server.stop()
        for k, v in saved_env.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
        config.reload()
        xlaffi._reset_for_tests()


def ffi_main(args) -> int:
    """The zero-copy XLA put-path report (and the `make ffi-smoke` CI
    gate).  Graceful skip — not a failure — when jax.ffi or the native
    ``bf_xla`` symbols are absent: that is the documented degraded mode
    (the host-staged PR-9 path serves every put)."""
    import sys

    smoke = args.ffi_smoke
    # The loopback store runs on the CPU backend's virtual mesh; size it
    # BEFORE jax initializes (same rule as the schedule bench).
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8")

    from bluefog_tpu import native

    if not (native.available() and native.has_win_xla()):
        reason = ("native core lacks bf_xla symbols"
                  if native.available() else "native core not built")
        print(json.dumps({
            "metric": "win_put_ffi_dispatch_speedup", "value": None,
            "unit": "x", "status": "skipped",
            "detail": {"reason": reason}}))
        return 0
    from bluefog_tpu.ops import xlaffi
    if not xlaffi.armed():
        print(json.dumps({
            "metric": "win_put_ffi_dispatch_speedup", "value": None,
            "unit": "x", "status": "skipped",
            "detail": {"reason": xlaffi.disarm_reason()}}))
        return 0

    value, detail, failures = _ffi_report(smoke)
    rc = 0
    for f in failures:
        print(f"bench_comm --ffi: {f}", file=sys.stderr)
        rc = 1
    print(json.dumps({
        "metric": "win_put_ffi_dispatch_speedup",
        "value": value,
        "unit": "x",
        "detail": detail,
    }))
    return rc


def _ffi_report(smoke: bool):
    """Run the FFI put-path sweep; returns ``(speedup, detail,
    failures)``.  Shared by ``--ffi[-smoke]`` and the full
    ``--transport`` run's ffi leg."""
    bursts, per_burst = (3, 30) if smoke else (10, 100)
    sizes = [1024] if smoke else [256, 1024, 16384]  # f32 elems per row
    sweep, failures = [], []
    for elems in sizes:
        for mode in (["native", "ffi"] if smoke
                     else ["legacy", "native", "ffi"]):
            res = _ffi_one_mode(mode, elems, bursts, per_burst)
            sweep.append(res)
            if mode == "ffi":
                if not res["ffi_engaged"]:
                    failures.append(
                        f"FFI path armed but did not engage ({elems} elems)")
                bad = {p: b for p, b in res["host_copy_bytes"].items()
                       if b > 0}
                if bad:
                    failures.append(
                        f"FFI leg reported staging copies {bad} "
                        f"({elems} elems) — the zero-copy contract broke")

    def _us(mode, elems):
        for r in sweep:
            if r["mode"] == mode and r["row_bytes"] == elems * 4:
                return r["dispatch_us_per_row"]
        return None

    ratios = {}
    for elems in sizes:
        nat, ffi = _us("native", elems), _us("ffi", elems)
        if nat and ffi:
            ratios[elems * 4] = round(nat / ffi, 2)
    big_ratio = min((v for k, v in ratios.items() if k >= 4096),
                    default=None)
    if not smoke and (big_ratio is None or big_ratio < 2.0):
        failures.append(
            f"FFI put dispatch speedup {big_ratio}x < 2x vs the PR-9 "
            "native path for rows >= 4 KiB")
    detail = {"smoke": smoke, "ratios_by_row_bytes": ratios,
              "sweep": sweep}
    return big_ratio, detail, failures


def _effective_w(sched, n):
    """Reconstruct the effective weight matrix a compiled schedule applies
    (the repack-equivalence oracle: regrouping rounds must never change it)."""
    import numpy as np
    w = np.zeros((n, n))
    w[np.arange(n), np.arange(n)] = sched.self_scale
    for rnd in sched.rounds:
        for s, d in rnd.pairs:
            w[s, d] = rnd.send_scale[s]
    return w


def placement_main(args) -> int:
    """Physical-placement report (and the `make placement-smoke` CI gate).

    Part 1 is pure host math (no jax): for each simulated torus and each
    topology family, compare modeled max-link-load under identity
    placement vs the optimized permutation vs optimized + congestion-aware
    round packing; assert random-regular improves >= 2x on the 8x8 torus,
    shift-structured families are never made worse, and the effective
    weight matrix survives the repack bit-identically.  Part 2 drives the
    real op on the virtual 8-device CPU mesh: placement on (fake torus)
    must produce BIT-IDENTICAL outputs vs BLUEFOG_TPU_PLACEMENT=0 (the
    permutation only moves ranks to other devices), and the congestion
    repack stays within 1e-6 (fp summation order only)."""
    import numpy as np

    from bluefog_tpu import topology as topo
    from bluefog_tpu.ops import placement as PL
    from bluefog_tpu.ops import schedule as S
    from bluefog_tpu.ops import schedule_opt as SO

    smoke = args.placement_smoke
    seed = args.seed
    tori = {}
    for dims in ((4, 8), (8, 8)):
        n = dims[0] * dims[1]
        model = PL.synthetic_torus(dims)
        per_topo = {}
        for name, make in _topo_families(topo, n, seed):
            w = topo.weight_matrix(make())
            sched = S._build_schedule(w, optimize=True)
            res = PL.optimize_placement(model, sched, n,
                                        iters=args.placement_iters,
                                        seed=seed)
            packed = SO.congestion_aware_repack(
                sched, model, res.perm, budget_factor=2.0)
            pc = PL.schedule_cost(model, packed, res.perm)
            assert np.array_equal(_effective_w(sched, n),
                                  _effective_w(packed, n)), \
                f"{name}@{dims}: repack changed the effective weight matrix"
            assert (res.optimized_cost.max_link_load
                    <= res.identity_cost.max_link_load), \
                f"{name}@{dims}: placement made max-link-load WORSE"
            assert pc.max_link_load <= res.optimized_cost.max_link_load, \
                f"{name}@{dims}: congestion repack made max-link-load WORSE"
            per_topo[name] = {
                "max_link_load_naive": res.identity_cost.max_link_load,
                "max_link_load_placed": res.optimized_cost.max_link_load,
                "max_link_load_packed": pc.max_link_load,
                "hop_bytes_naive": res.identity_cost.hop_bytes,
                "hop_bytes_opt": res.optimized_cost.hop_bytes,
                "rounds": len(sched.rounds),
                "rounds_packed": len(packed.rounds),
                "identity_placement": res.is_identity,
                "improvement_ratio": round(
                    res.identity_cost.max_link_load
                    / max(pc.max_link_load, 1e-12), 3),
            }
        tori["x".join(map(str, dims))] = per_topo

    rr = tori["8x8"]["random_regular"]
    assert rr["improvement_ratio"] >= 2.0, (
        "placement+packing must cut modeled max-link-load >= 2x for "
        f"random-regular(4, 64) on the 8x8 torus, got "
        f"{rr['improvement_ratio']}x")

    # ---- Part 2: end-to-end output equivalence on the virtual CPU mesh.
    os.environ["JAX_PLATFORMS"] = "cpu"
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8")
    import jax
    jax.config.update("jax_platforms", "cpu")

    import bluefog_tpu as bf
    from bluefog_tpu.utils import config

    topo_fn = lambda: topo.RandomRegularGraph(8, 4, seed=1)
    x = np.random.default_rng(seed).standard_normal((8, 64)).astype(
        np.float32)
    knobs = ("BLUEFOG_TPU_PLACEMENT", "BLUEFOG_TPU_FAKE_TORUS",
             "BLUEFOG_TPU_PLACEMENT_ROUND_BUDGET")
    saved = {k: os.environ.get(k) for k in knobs}

    def run(**env):
        for k in knobs:
            os.environ.pop(k, None)
        os.environ.update(env)
        config.reload()
        bf.init(topo_fn)
        out = np.asarray(bf.neighbor_allreduce(x))
        info = bf.placement_info()
        bf.shutdown()
        return out, info

    try:
        out_off, info_off = run(BLUEFOG_TPU_PLACEMENT="0",
                                BLUEFOG_TPU_FAKE_TORUS="2x4")
        out_place, info_on = run(BLUEFOG_TPU_PLACEMENT="1",
                                 BLUEFOG_TPU_FAKE_TORUS="2x4",
                                 BLUEFOG_TPU_PLACEMENT_ROUND_BUDGET="0")
        out_pack, _ = run(BLUEFOG_TPU_PLACEMENT="1",
                          BLUEFOG_TPU_FAKE_TORUS="2x4")
    finally:
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
        config.reload()
    assert info_off is None, "PLACEMENT=0 must disable the physical model"
    assert info_on is not None and (info_on["max_link_load_opt"]
                                    <= info_on["max_link_load_naive"])
    assert np.array_equal(out_off, out_place), (
        "placement permutation must be BIT-identical to enumeration order "
        "(it only moves ranks to other devices)")
    pack_diff = float(np.abs(out_off - out_pack).max())
    assert pack_diff <= 1e-6, \
        f"congestion repack drifted outputs by {pack_diff} (> 1e-6)"

    print(json.dumps({
        "metric": "gossip_placement_max_link_load_reduction_random_regular",
        "value": rr["improvement_ratio"],
        "unit": "x",
        "detail": {
            "smoke": smoke,
            "tori": tori,
            "e2e": {
                "mesh": "8-device CPU, fake torus 2x4",
                "bit_identical_placement_only": True,
                "packed_max_output_diff": pack_diff,
                "placement_info": info_on,
            },
        },
    }))
    return 0


def _dcn_serial_time(model, sched) -> float:
    """Modeled inter-slice serial link time of one application of
    ``sched``: sum over rounds of the busiest DCN link's weighted load —
    the ICI portion deliberately excluded (the DCN links are the scarce
    pod-scale resource this report isolates)."""
    import numpy as np
    node = np.asarray(model.device_node, np.int64)
    first_dcn = model.first_dcn_link
    total = 0.0
    for rnd in sched.rounds:
        loads = np.zeros(model.n_links)
        for s, d in rnd.pairs:
            r = model.route(int(node[s]), int(node[d]))
            np.add.at(loads, r, 1.0)
        dcn = loads[first_dcn:] * model.dcn_link_cost
        if dcn.size:
            total += float(dcn.max())
    return total


def _dcn_rows(w, n_slices) -> int:
    """Directed inter-slice edges of one application of a flat weight
    matrix over slice-contiguous rank blocks."""
    import numpy as np
    n = w.shape[0]
    slice_of = np.arange(n) // (n // n_slices)
    srcs, dsts = np.nonzero(w)
    return int(sum(1 for s, d in zip(srcs, dsts)
                   if s != d and slice_of[s] != slice_of[d]))


def _simulate_hier_consensus(ht, w_flat, steps, frac, seed, dim=8):
    """Consensus distance (mean per-rank L2 to the global mean) of flat
    gossip vs the two-level mode after ``steps`` applications, simulated
    exactly on the per-step effective operators (the sparse outer level
    applies the block-restricted exchange per coordinate, matching the
    compiled executor)."""
    import math

    import numpy as np
    n = ht.n
    rng = np.random.default_rng(seed)
    x0 = rng.standard_normal((n, dim))
    kk = max(1, int(math.ceil(frac * dim)))
    nblocks = max(1, -(-dim // kk))
    w_in_full = ht.inner_full_matrix()

    def dist(x):
        return float(np.linalg.norm(x - x.mean(axis=0, keepdims=True),
                                    axis=1).mean())

    xf = x0.copy()
    xh = x0.copy()
    for step in range(steps):
        xf = w_flat.T @ xf
        xh = w_in_full.T @ xh
        if ht.is_outer_step(step):
            outer_step = step // ht.outer_every
            rot = (np.arange(kk) + (outer_step % nblocks) * kk) % dim
            p = ht.outer_phase_index(step, sweep_len=nblocks)
            wo = ht.outer_full_matrix(p)
            xh[:, rot] = wo.T @ xh[:, rot]
    return dist(xf), dist(xh)


def hier_main(args) -> int:
    """Hierarchical-gossip report (and the `make hier-smoke` CI gate).

    Part 1 is pure host math: on simulated multi-slice tori (2 slices of
    4x8, 4 slices of 4x4 — 64 ranks each) compare flat static Exp2
    against the two-level mode (dense inner exp2 over ICI, one-peer exp2
    outer over DCN at cadence 2 with sparse:0.5 outer compression and the
    cadence-corrected self weight sqrt(1/2) -> 1/2 per exchange — exact
    pairwise averaging, so a full outer phase sweep annihilates every
    inter-slice mode).  Asserts, per torus: per-step DCN wire rows AND
    modeled inter-slice serial link time both drop >= 4x, at
    equal-or-better simulated consensus distance after a fixed step
    budget.

    Part 2 drives the real executor on the 8-device virtual CPU mesh:
    dense/uncompressed/cadence-1 hierarchical_gossip must match flat
    neighbor_allreduce over the product topology <= 1e-6, the
    BLUEFOG_TPU_HIER=0 flat path must be BIT-identical to the unset-knob
    tree, and the sparse:<frac> wire codec must round-trip bit-exact
    through the OP_BATCH framing."""
    import math

    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8")

    import numpy as np

    from bluefog_tpu import topology as topo
    from bluefog_tpu.ops import placement as PL
    from bluefog_tpu.ops import schedule as S

    smoke = args.hier_smoke
    outer_every = 2
    frac = 0.5
    # Cadence-corrected: theta**outer_every == 0.5 per exchange — exact
    # pairwise averaging, the weight under which a full one-peer exp2
    # sweep is an exact inter-slice average.
    theta = math.sqrt(0.5)
    budget_steps = 24
    tori = {
        "2x(4x8)": ((4, 8), 2),
        "4x(4x4)": ((4, 4), 4),
    }
    detail = {}
    worst_bytes_ratio = None
    for tname, (dims, n_slices) in tori.items():
        model = PL.synthetic_torus(dims, n_slices=n_slices)
        n = len(model.device_node)
        ht = topo.hierarchical_two_level(
            n, n_slices, outer_every=outer_every, outer_self_weight=theta)
        w_flat = topo.weight_matrix(topo.ExponentialTwoGraph(n))
        flat_sched = S._build_schedule(w_flat, optimize=True)

        # -- per-step DCN wire rows (row-bytes at unit payload) ------------
        flat_rows = _dcn_rows(w_flat, n_slices)
        hier_rows = ht.dcn_edges_per_outer_step() * frac / outer_every
        bytes_ratio = flat_rows / max(hier_rows, 1e-12)

        # -- modeled inter-slice serial link time per step -----------------
        flat_dcn_serial = _dcn_serial_time(model, flat_sched)
        outer_scheds = [
            S._build_schedule(ht.outer_full_matrix(p), optimize=True)
            for p in range(len(ht.outer_phases))]
        hier_dcn_serial = (sum(_dcn_serial_time(model, s)
                               for s in outer_scheds)
                           / max(len(outer_scheds), 1)
                           * frac / outer_every)
        serial_ratio = flat_dcn_serial / max(hier_dcn_serial, 1e-12)

        # -- consensus distance after the fixed step budget ----------------
        flat_dist, hier_dist = _simulate_hier_consensus(
            ht, w_flat, budget_steps, frac, args.seed)

        assert bytes_ratio >= 4.0, (
            f"{tname}: hierarchical DCN wire rows must drop >= 4x vs "
            f"flat exp2, got {bytes_ratio:.2f}x")
        assert serial_ratio >= 4.0, (
            f"{tname}: modeled inter-slice serial time must drop >= 4x, "
            f"got {serial_ratio:.2f}x")
        assert hier_dist <= flat_dist + 1e-12, (
            f"{tname}: hierarchical consensus distance {hier_dist:.3e} "
            f"worse than flat {flat_dist:.3e} after {budget_steps} steps")
        worst_bytes_ratio = (bytes_ratio if worst_bytes_ratio is None
                             else min(worst_bytes_ratio, bytes_ratio))
        detail[tname] = {
            "n": n, "n_slices": n_slices,
            "dcn_rows_flat_per_step": flat_rows,
            "dcn_rows_hier_per_step": hier_rows,
            "dcn_rows_reduction": round(bytes_ratio, 3),
            "dcn_serial_flat": flat_dcn_serial,
            "dcn_serial_hier": round(hier_dcn_serial, 4),
            "dcn_serial_reduction": round(serial_ratio, 3),
            "consensus_flat": flat_dist,
            "consensus_hier": hier_dist,
            "steps": budget_steps,
            "policy": {"inner": "exp2", "outer": "exp2 one-peer",
                       "outer_every": outer_every,
                       "outer_compression": f"sparse:{frac}",
                       "outer_self_weight_per_exchange": 0.5},
        }

    # ---- Part 2a: sparse wire codec through the OP_BATCH framing --------
    from bluefog_tpu.ops import transport as T
    rng = np.random.default_rng(args.seed)
    row = rng.standard_normal(64).astype(np.float32)
    idx = np.argsort(-np.abs(row))[:16].astype(np.int32)
    idx.sort()
    payload = T.sparse_encode(row[idx], idx)
    msgs = [(T.OP_ACCUMULATE | T.OP_SPARSE_FLAG, "w", 0, 1, 1.0, 0.0,
             payload.tobytes())]
    decoded = T._decode_batch(T._encode_batch(msgs))
    d_idx, d_val = T.sparse_decode(decoded[0][6])
    assert np.array_equal(d_idx, idx) and np.array_equal(
        d_val.view(np.int32), row[idx].view(np.int32)), \
        "sparse payload must round-trip BIT-exact through OP_BATCH framing"

    # ---- Part 2b: end-to-end executor equivalence on the CPU mesh -------
    import jax
    jax.config.update("jax_platforms", os.environ["JAX_PLATFORMS"])
    import bluefog_tpu as bf
    from bluefog_tpu.utils import config
    knobs = ("BLUEFOG_TPU_HIER", "BLUEFOG_TPU_HIER_OUTER_EVERY",
             "BLUEFOG_TPU_HIER_OUTER_COMPRESSION")
    saved = {k: os.environ.get(k) for k in knobs}
    x8 = np.random.default_rng(args.seed).standard_normal(
        (8, 16)).astype(np.float32)
    e2e = {}
    try:
        for k in knobs:
            os.environ.pop(k, None)
        config.reload()
        bf.init(lambda: topo.ExponentialGraph(8), local_size=4)
        out_unset = np.asarray(bf.neighbor_allreduce(x8))
        bf.shutdown()

        os.environ["BLUEFOG_TPU_HIER"] = "1"
        config.reload()
        bf.init(lambda: topo.ExponentialGraph(8), local_size=4)
        out_flat = np.asarray(bf.neighbor_allreduce(x8))
        assert np.array_equal(out_unset, out_flat), (
            "flat neighbor_allreduce must be BIT-identical with "
            "BLUEFOG_TPU_HIER on vs unset (the knob gates only the "
            "hierarchical path)")
        ht8 = topo.hierarchical_two_level(8, 2)
        max_diff = 0.0
        for step in range(4):
            out_h = np.asarray(bf.hierarchical_gossip(x8, step))
            expect = np.asarray(bf.neighbor_allreduce(
                x8, src_weights=ht8.effective_weight_matrix(step)))
            max_diff = max(max_diff,
                           float(np.abs(out_h - expect).max()))
        assert max_diff <= 1e-6, (
            f"dense cadence-1 hierarchical gossip drifted {max_diff} "
            "(> 1e-6) from the flat product topology")
        e2e = {"mesh": "8-device CPU, 2 slices of 4",
               "product_equivalence_max_diff": max_diff,
               "hier_info": bf.hierarchical_gossip_info()}
        bf.shutdown()
    finally:
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
        config.reload()

    print(json.dumps({
        "metric": "hier_gossip_dcn_wire_reduction_worst_torus",
        "value": round(worst_bytes_ratio, 3),
        "unit": "x",
        "detail": {"smoke": smoke, "tori": detail, "e2e": e2e},
    }))
    return 0


def _topo_families(topo, n, seed, degree=4):
    """The four benchmark topology families every report sweeps."""
    return (
        ("ring", lambda: topo.RingGraph(n)),
        ("exp2", lambda: topo.ExponentialTwoGraph(n)),
        ("star", lambda: topo.StarGraph(n)),
        ("random_regular",
         lambda: topo.RandomRegularGraph(n, degree, seed=seed)),
    )


def synth_main(args) -> int:
    """Schedule-synthesis report (and the `make synth-smoke` CI gate).

    Part 1 is pure host math: for each simulated torus (4x8, 8x8, a
    2-slice 4x8 and a 4-slice 4x4) and each topology family, compare
    modeled serial_link_time of the König schedule, the congestion-aware
    repack, and the sketch-synthesis selection, all under identity
    placement (isolating the round-assignment axis).  Asserts the
    selection NEVER loses to the packed schedule, beats it strictly on
    the acceptance cases (exp2 + random-regular on the tori with
    headroom), and — where it ties on exp2/random-regular — that the
    packed schedule already sits on the provable busiest-link-total lower
    bound, i.e. no schedule could do better.  Effective weight matrices
    must survive synthesis bit-identically and round budgets must hold.

    Part 2 drives a genuinely synthesized schedule end-to-end through the
    real ppermute executor on a 32-device virtual CPU mesh and asserts
    output equivalence <= 1e-6 vs the naive schedule, then checks the
    `BLUEFOG_TPU_SCHEDULE_SYNTH=0` hatch restores the PR-5 dispatch path
    (no synthesis info, no synthesis gauges) with equivalent outputs."""
    import math as _math

    # The e2e leg needs a >= 32-device virtual mesh: size it BEFORE any
    # jax import (same contract as the schedule bench below).
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=32")

    import numpy as np

    from bluefog_tpu import topology as topo
    from bluefog_tpu.ops import placement as PL
    from bluefog_tpu.ops import schedule as S
    from bluefog_tpu.ops import schedule_opt as SO
    from bluefog_tpu.ops import synthesis as SY

    smoke = args.synth_smoke
    budget = 2.0
    tori = {
        "4x8": PL.synthetic_torus((4, 8)),
        "8x8": PL.synthetic_torus((8, 8)),
        "2x(4x8)": PL.synthetic_torus((4, 8), n_slices=2),
        "4x(4x4)": PL.synthetic_torus((4, 4), n_slices=4),
    }
    # The acceptance cases: exp2 + random-regular(4) must win strictly
    # wherever the packed schedule is NOT already at the lower bound.
    detail = {}
    strict_wins = []
    for tname, model in tori.items():
        n = len(model.device_node)
        per_topo = {}
        for name, make in _topo_families(topo, n, args.seed):
            w = topo.weight_matrix(make())
            naive = S._build_schedule(w, optimize=False)
            konig = SO.optimize_schedule(naive)
            packed = SO.congestion_aware_repack(
                konig, model, None, budget_factor=budget, record=False)
            chosen, ratio = SY.select_schedule(konig, packed, model, None,
                                               budget_factor=budget)
            ks = PL.schedule_cost(model, konig).serial_link_time
            ps = PL.schedule_cost(model, packed).serial_link_time
            cs = PL.schedule_cost(model, chosen).serial_link_time
            lb = SY.serial_lower_bound(model, konig)
            assert cs <= ps + 1e-9, \
                f"{name}@{tname}: synthesis selection made serial WORSE"
            assert np.array_equal(_effective_w(naive, n),
                                  _effective_w(chosen, n)), \
                f"{name}@{tname}: synthesis changed the weight matrix"
            assert len(chosen.rounds) <= max(
                len(konig.rounds),
                _math.ceil(budget * SO.min_rounds(konig))), \
                f"{name}@{tname}: synthesis exceeded the round budget"
            if name in ("exp2", "random_regular"):
                if cs < ps - 1e-9:
                    strict_wins.append(f"{name}@{tname}")
                else:
                    # No win allowed ONLY at provable optimality.
                    assert ps <= lb + 1e-9, (
                        f"{name}@{tname}: synthesis tied the packed "
                        f"schedule at {ps} > lower bound {lb} — headroom "
                        "left on the table")
            per_topo[name] = {
                "serial_konig": ks, "serial_packed": ps,
                "serial_synth": cs, "lower_bound": lb,
                "rounds_synth": len(chosen.rounds),
                "provenance": S.schedule_provenance(chosen),
                "improvement_ratio": round(ps / max(cs, 1e-12), 3),
            }
        detail[tname] = per_topo
    for required in ("exp2@8x8", "random_regular@8x8",
                     "random_regular@4x(4x4)"):
        assert required in strict_wins, (
            f"synthesis must beat congestion_aware_repack strictly on "
            f"{required}; wins: {strict_wins}")

    # ---- Part 2a: synthesized schedule through the real ppermute path.
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, PartitionSpec as P
    jax.config.update("jax_platforms", os.environ["JAX_PLATFORMS"])
    devs = jax.devices()
    e2e = {}
    if len(devs) >= 32:
        n = 32
        mesh = Mesh(np.asarray(devs[:n]), ("r",))
        from bluefog_tpu.ops import collective as C
        model = PL.synthetic_torus((4, 8))
        w = topo.weight_matrix(topo.ExponentialTwoGraph(n))
        naive = S._build_schedule(w, optimize=False)
        konig = SO.optimize_schedule(naive)
        packed = SO.congestion_aware_repack(konig, model, None,
                                            budget_factor=budget,
                                            record=False)
        chosen, ratio = SY.select_schedule(konig, packed, model, None,
                                           budget_factor=budget)
        assert S.schedule_provenance(chosen).startswith("synthesized"), \
            "e2e leg expected a synthesized win for exp2(32) on 4x8"
        x = jnp.asarray(np.random.default_rng(args.seed)
                        .standard_normal((n, 256)), jnp.float32)

        def run(sched):
            return np.asarray(jax.jit(jax.shard_map(
                lambda b: C.neighbor_allreduce(b[0], sched, "r")[None],
                mesh=mesh, in_specs=P("r"), out_specs=P("r"),
                check_vma=False))(x))
        diff = float(np.abs(run(naive) - run(chosen)).max())
        assert diff <= 1e-6, \
            f"synthesized schedule drifted outputs by {diff} (> 1e-6)"
        e2e["synth_vs_naive_max_diff"] = diff
        e2e["synth_provenance"] = S.schedule_provenance(chosen)
        e2e["synth_serial"] = PL.schedule_cost(model, chosen).serial_link_time
        e2e["packed_serial"] = PL.schedule_cost(model, packed).serial_link_time

    # ---- Part 2b: the env hatch restores the PR-5 dispatch path.
    import bluefog_tpu as bf
    from bluefog_tpu.utils import config, telemetry
    knobs = ("BLUEFOG_TPU_SCHEDULE_SYNTH", "BLUEFOG_TPU_FAKE_TORUS",
             "BLUEFOG_TPU_PLACEMENT")
    saved = {k: os.environ.get(k) for k in knobs}
    topo_fn = lambda: topo.RandomRegularGraph(8, 4, seed=1)
    x8 = np.random.default_rng(args.seed).standard_normal(
        (8, 64)).astype(np.float32)

    def run_ctx(**env):
        for k in knobs:
            os.environ.pop(k, None)
        os.environ.update(env)
        config.reload()
        bf.init(topo_fn, devices=jax.devices()[:8])
        out = np.asarray(bf.neighbor_allreduce(x8))
        info = bf.synthesis_info()
        snap = telemetry.snapshot() if telemetry.enabled() else {}
        bf.shutdown()
        return out, info, snap

    try:
        out_off, info_off, snap_off = run_ctx(
            BLUEFOG_TPU_SCHEDULE_SYNTH="0", BLUEFOG_TPU_FAKE_TORUS="2x4")
        out_on, info_on, snap_on = run_ctx(
            BLUEFOG_TPU_SCHEDULE_SYNTH="1", BLUEFOG_TPU_FAKE_TORUS="2x4")
    finally:
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
        config.reload()
    assert info_off is None, \
        "SCHEDULE_SYNTH=0 must disable the synthesis pipeline entirely"
    assert "bf_schedule_synth_improvement_ratio" not in snap_off
    assert info_on is not None and info_on["improvement_ratio"] >= 1.0
    assert snap_on.get("bf_schedule_synth_improvement_ratio", 0) >= 1.0
    hatch_diff = float(np.abs(out_off - out_on).max())
    assert hatch_diff <= 1e-6, \
        f"env hatch outputs drifted by {hatch_diff} (> 1e-6)"
    e2e["hatch_max_diff"] = hatch_diff

    rr = detail["8x8"]["random_regular"]
    print(json.dumps({
        "metric": "gossip_schedule_synth_serial_time_reduction_rr_8x8",
        "value": rr["improvement_ratio"],
        "unit": "x",
        "detail": {
            "smoke": smoke,
            "strict_wins": strict_wins,
            "tori": detail,
            "e2e": e2e,
        },
    }))
    return 0


def sharded_main(args) -> int:
    """Sharded-gossip report (and the `make sharded-smoke` CI gate).

    Part 1 is pure host math: on a simulated 16-rank MoE mesh (4 replica
    groups of 4 — i.e. 4-way expert sharding) build trees whose
    replicated byte fraction is 25/50/75% and assert, through the
    ``ShardPlan`` planner and the per-group compiled schedules, that
    per-step DCN bytes scale with the replicated fraction ONLY: the
    sharded slices ride in-group edges exclusively, so a 50%-sharded
    tree gossips <= ~50% of the all-replicated path's DCN bytes.

    Part 2 drives the real executor on the 8-device virtual CPU mesh
    (2 replica groups of 4): the replicated leaf must match the dense
    ``W^T x`` oracle <= 1e-6, each rank's own shard slice must match the
    per-group oracle with its ghost region bit-untouched, the
    ``bf_comm_level_bytes_total{shard=...}`` split must bill exactly
    rep_row_bytes x dcn_edges x steps to the DCN (and never a sharded
    byte), and BLUEFOG_TPU_SHARDED_GOSSIP=0 — or a fully replicated
    tree — must be BIT-identical to the no-spec path."""
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8")

    import numpy as np

    from bluefog_tpu import topology as topo
    from bluefog_tpu.ops import schedule as S
    from bluefog_tpu.ops import sharded as SH

    smoke = args.sharded_smoke

    # ---- Part 1: planner byte model on a simulated 16-rank MoE mesh -----
    n, n_shards = 16, 4
    groups = SH.default_groups(n, n_shards)
    sched = S.compile_static(topo.ExponentialTwoGraph(n))
    total_cols = 4096  # floats per rank across the whole tree
    detail = {}
    baseline_dcn = None  # all-replicated DCN bytes per step
    for frac in (1.0, 0.75, 0.5, 0.25):
        rep_cols = int(total_cols * frac)
        sh_cols = (total_cols - rep_cols) // n_shards
        tree = {"router": np.zeros((n, rep_cols), np.float32)}
        specs = {"router": None}
        if sh_cols:
            tree["experts"] = np.zeros((n, n_shards, sh_cols), np.float32)
            specs["experts"] = ("ep", None)
        plan = SH.build_plan(tree, specs, n=n, n_shards=n_shards,
                             groups=groups)
        assert abs(plan.replicated_fraction - frac) < 1e-9, (
            frac, plan.replicated_fraction)
        rep_ici, rep_dcn = SH.edge_level_counts(plan.coords, sched)
        rep_row = plan.rep_bytes / n
        sh_row = plan.sh_bytes / n / n_shards if plan.any_sharded else 0.0
        dcn_bytes = rep_row * rep_dcn  # sharded slices: in-group only
        gsched, per_group = SH.compile_group_schedules(n, groups)
        g_ici, g_dcn = SH.edge_level_counts(plan.coords, gsched)
        assert g_dcn == 0.0, (
            "per-group schedules must never emit a cross-group (DCN) "
            f"edge, got {g_dcn}")
        if frac == 1.0:
            baseline_dcn = dcn_bytes
        else:
            ratio = dcn_bytes / baseline_dcn
            assert abs(ratio - frac) < 1e-9, (
                f"DCN bytes must scale with the replicated fraction: "
                f"frac={frac} ratio={ratio}")
        detail[f"{int(frac * 100)}%"] = {
            "replicated_fraction": frac,
            "rep_row_bytes": rep_row,
            "sharded_row_bytes": sh_row,
            "dcn_bytes_per_step": dcn_bytes,
            "dcn_vs_all_replicated": round(dcn_bytes / baseline_dcn, 4),
            "ici_bytes_per_step": rep_row * rep_ici + sh_row * g_ici,
            "group_rounds": [len(sub.rounds) for _g, sub in per_group],
            "merged_rounds": len(gsched.rounds),
        }

    # ---- Part 2: executor leg on the 8-device CPU mesh ------------------
    import jax
    import jax.numpy as jnp
    jax.config.update("jax_platforms", os.environ["JAX_PLATFORMS"])
    from jax.sharding import PartitionSpec as P
    import optax

    import bluefog_tpu as bf
    from bluefog_tpu.utils import config, telemetry

    knobs = ("BLUEFOG_TPU_TELEMETRY", "BLUEFOG_TPU_SHARDED_GOSSIP")
    saved = {k: os.environ.get(k) for k in knobs}
    rng = np.random.default_rng(args.seed)
    steps = 2 if smoke else 4
    e2e = {}
    try:
        os.environ["BLUEFOG_TPU_TELEMETRY"] = "1"
        os.environ.pop("BLUEFOG_TPU_SHARDED_GOSSIP", None)
        config.reload()
        bf.init()
        n8 = bf.size()
        params = {"a": jnp.asarray(rng.standard_normal((n8, 5)),
                                   jnp.float32),
                  "b": jnp.asarray(rng.standard_normal((n8, 4, 8)),
                                   jnp.float32)}
        specs = {"a": P(), "b": P(None, "tp")}
        grads = jax.tree.map(jnp.zeros_like, params)

        def drive(shard_specs, num_shards):
            opt = bf.optim.DistributedNeighborAllreduceOptimizer(
                optax.sgd(0.0), shard_specs=shard_specs,
                num_shards=num_shards)
            state = opt.init(params)
            p = params
            for _ in range(steps):
                p, state = opt.step(p, grads, state)
            return p

        telemetry.reset()
        out = drive(specs, 2)
        snap = telemetry.snapshot()

        # Dense oracle for the replicated leaf: one step is W^T x.
        W = topo.weight_matrix(bf.load_topology())
        exp_a = np.asarray(params["a"])
        for _ in range(steps):
            exp_a = W.T @ exp_a
        rep_err = float(np.abs(np.asarray(out["a"]) - exp_a).max())
        assert rep_err <= 1e-6, rep_err

        # Per-group oracle for each rank's own slice; ghost untouched.
        plan = SH.build_plan(params, specs, n=n8, n_shards=2)
        _g, per = SH.compile_group_schedules(n8, plan.groups)
        Wg = np.zeros((n8, n8))
        for g, _sub in per:
            sw = topo.weight_matrix(topo.ExponentialTwoGraph(len(g)))
            for i, gi in enumerate(g):
                for j, gj in enumerate(g):
                    Wg[gi, gj] = sw[i, j]
        b0, b1 = np.asarray(params["b"]), np.asarray(out["b"])
        chunk = b0.shape[-1] // 2
        sh_err = 0.0
        for r in range(n8):
            c = plan.coords[r]
            own = b0[:, :, c * chunk:(c + 1) * chunk]
            exp = own.copy()
            for _ in range(steps):
                exp = np.einsum("sr,s...->r...", Wg, exp)
            got = b1[r, :, c * chunk:(c + 1) * chunk]
            sh_err = max(sh_err, float(np.abs(got - exp[r]).max()))
            ghost = b1[r, :, (1 - c) * chunk:(2 - c) * chunk]
            assert np.array_equal(
                ghost, b0[r, :, (1 - c) * chunk:(2 - c) * chunk]), (
                f"rank {r}: ghost region must be bit-untouched")
        assert sh_err <= 1e-6, sh_err

        # Telemetry: DCN carries exactly the replicated rows, never a
        # sharded byte.
        plan8 = plan
        sched8 = S.compile_static(bf.load_topology())
        ici8, dcn8 = SH.edge_level_counts(plan8.coords, sched8)
        rep_row8 = plan8.rep_bytes / n8
        key_dcn = ('bf_comm_level_bytes_total'
                   '{level="dcn",shard="replicated"}')
        got_dcn = snap.get(key_dcn, 0.0)
        want_dcn = rep_row8 * dcn8 * steps
        assert abs(got_dcn - want_dcn) < 1e-6, (got_dcn, want_dcn)
        assert not any('shard="sharded"' in k and '"dcn"' in k
                       for k in snap), (
            "sharded bytes must never be billed to the DCN")

        # Bitwise hatches: knob off, and a fully replicated tree.
        base = drive(None, None)
        os.environ["BLUEFOG_TPU_SHARDED_GOSSIP"] = "0"
        config.reload()
        off = drive(specs, 2)
        os.environ.pop("BLUEFOG_TPU_SHARDED_GOSSIP", None)
        config.reload()
        allrep = drive({"a": P(), "b": P()}, 2)
        for k in base:
            assert np.array_equal(np.asarray(off[k]),
                                  np.asarray(base[k])), (
                f"{k}: BLUEFOG_TPU_SHARDED_GOSSIP=0 must be BIT-identical "
                "to the no-spec path")
            assert np.array_equal(np.asarray(allrep[k]),
                                  np.asarray(base[k])), (
                f"{k}: a fully replicated tree must be BIT-identical to "
                "the no-spec path")
        e2e = {
            "mesh": f"{n8}-device CPU, 2 replica groups of 4",
            "steps": steps,
            "replicated_oracle_max_err": rep_err,
            "sharded_oracle_max_err": sh_err,
            "dcn_bytes": got_dcn,
            "dcn_bytes_expected": want_dcn,
            "replicated_fraction": plan8.replicated_fraction,
        }
        bf.shutdown()
    finally:
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
        config.reload()

    half = detail["50%"]
    print(json.dumps({
        "metric": "sharded_gossip_dcn_bytes_fraction_at_50pct",
        "value": half["dcn_vs_all_replicated"],
        "unit": "x",
        "detail": {"smoke": smoke, "fractions": detail, "e2e": e2e},
    }))
    return 0


def main():
    args = _parse_args()
    if args.ffi or args.ffi_smoke:
        return ffi_main(args)
    if args.async_smoke:
        return async_main(args)
    if args.tracerec_smoke:
        return tracerec_main(args)
    if args.stripe_smoke:
        return stripe_main(args)
    if args.transport or args.transport_smoke:
        return transport_main(args)
    if args.placement or args.placement_smoke:
        return placement_main(args)
    if args.synth or args.synth_smoke:
        return synth_main(args)
    if args.hier or args.hier_smoke:
        return hier_main(args)
    if args.sharded or args.sharded_smoke:
        return sharded_main(args)
    if args.smoke:
        args.n = args.n or 8
        args.payload = min(args.payload, 1024)
        args.iters = min(args.iters, 5)
        args.reps = min(args.reps, 4)

    # Backend selection BEFORE jax import: default to CPU (this is a
    # schedule benchmark, not a bandwidth one) and size the virtual mesh
    # to the requested topology so the numeric-equivalence check runs at
    # full scale.
    platform = os.environ.get("JAX_PLATFORMS") or "cpu"
    os.environ["JAX_PLATFORMS"] = platform
    if platform == "cpu":
        n = args.n or 32
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags + f" --xla_force_host_platform_device_count={n}")

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, PartitionSpec as P

    jax.config.update("jax_platforms", platform)
    devs = jax.devices()
    n = min(args.n or len(devs), len(devs))
    if n < 4:
        import sys
        print(f"bench_comm: needs >= 4 ranks to build its topologies, have "
              f"{n} device(s) on backend {jax.default_backend()!r}; run "
              "with JAX_PLATFORMS=cpu (the script self-sizes a virtual "
              "mesh) or pass --n on a larger mesh", file=sys.stderr)
        return 2
    mesh = Mesh(np.asarray(devs[:n]), ("r",))

    from bluefog_tpu import topology as topo
    from bluefog_tpu.ops import collective as C
    from bluefog_tpu.ops import schedule as S
    from bluefog_tpu.ops import schedule_opt as SO
    from bluefog_tpu.utils import telemetry

    # Random-regular needs n * degree even: drop the clamped degree by one
    # for parity, and fail with a usable message if that empties it.
    rr_degree = min(args.degree, n - 1)
    if (n * rr_degree) % 2:
        rr_degree -= 1
    if rr_degree < 1:
        raise SystemExit(
            f"bench_comm: no valid random-regular degree at n={n} with "
            f"--degree {args.degree} (n * degree must be even and "
            "0 < degree < n); use an even --n or a larger --degree")

    topologies = dict(_topo_families(topo, n, args.seed,
                                     degree=rr_degree))

    rng = np.random.default_rng(args.seed)
    x = jnp.asarray(rng.standard_normal((n, args.payload)), jnp.float32)

    def run_op(sched):
        def body(b):
            out = b[0]
            for _ in range(args.reps):
                out = C.neighbor_allreduce(out, sched, "r")
            return out[None]
        return jax.jit(jax.shard_map(
            body, mesh=mesh, in_specs=P("r"), out_specs=P("r"),
            check_vma=False))

    def time_op(fn):
        out = fn(x)
        jax.block_until_ready(out)  # compile + warm
        t0 = time.perf_counter()
        for _ in range(args.iters):
            out = fn(x)
        jax.block_until_ready(out)
        dt = time.perf_counter() - t0
        return dt / (args.iters * args.reps) * 1e3  # ms per op

    detail = {}
    for name, make in topologies.items():
        w = topo.weight_matrix(make())
        naive = S._build_schedule(w, optimize=False)
        opt = S._build_schedule(w, optimize=True)
        r0, e0 = C.schedule_wire_stats(naive)[:2]
        r1, e1 = C.schedule_wire_stats(opt)[:2]
        assert e0 == e1, f"{name}: repack changed the edge set ({e0} -> {e1})"
        assert r1 <= r0, f"{name}: repack emitted MORE rounds ({r0} -> {r1})"
        assert r1 == SO.min_rounds(opt), \
            f"{name}: {r1} rounds, König bound {SO.min_rounds(opt)}"
        f_naive, f_opt = run_op(naive), run_op(opt)
        out_naive = np.asarray(f_naive(x))
        out_opt = np.asarray(f_opt(x))
        max_diff = float(np.abs(out_naive - out_opt).max())
        assert max_diff <= 1e-6, \
            f"{name}: outputs differ by {max_diff} (> 1e-6)"
        detail[name] = {
            "rounds_naive": r0, "rounds_optimized": r1,
            "edges": e0,
            "round_reduction": round(r0 / max(r1, 1), 3),
            "ms_per_op_naive": round(time_op(f_naive), 4),
            "ms_per_op_optimized": round(time_op(f_opt), 4),
            "max_output_diff": max_diff,
        }

    rr = detail["random_regular"]
    snap = telemetry.snapshot() if telemetry.enabled() else {}
    print(json.dumps({
        "metric": "gossip_schedule_opt_round_reduction_random_regular",
        "value": rr["round_reduction"],
        "unit": "x",
        "detail": {
            "n": n,
            "payload_f32": args.payload,
            "backend": jax.default_backend(),
            "per_topology": detail,
            "schedule_opt_rounds_saved_total": snap.get(
                "bf_schedule_opt_rounds_saved_total", 0),
        },
    }))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
