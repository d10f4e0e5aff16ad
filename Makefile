# Test / bench matrix (the role of the reference's Makefile:28-51, which ran
# every pytest file under `mpirun -np 4`; here the fast tier runs on the
# virtual 8-device CPU mesh in-process and the slow tier adds the real
# multi-process `bfrun` launches).

PYTEST = python -m pytest -q

.PHONY: test test-fast test-slow test-all chip-smoke bench-comm \
        bench-comm-smoke native telemetry-smoke prof-smoke transport-smoke \
        stripe-smoke tracerec-smoke async-smoke ffi-smoke \
        placement-smoke synth-smoke hier-smoke sharded-smoke \
        chaos-smoke chaos links-smoke tune-smoke metrics-lint

# Fast gate: ~3 min on the CPU mesh (in-process virtual-mesh tests only;
# grew a few oracle tests in round 4); run on every change, plus the
# schedule-regression smoke (bench_comm asserts the min-round repack is
# output-equivalent and never worse than naive — a broken repack fails
# here loudly, not as a silent slowdown).  `native` runs first so the
# window-transport hot path is fresh (graceful skip without a toolchain —
# every native consumer has a Python fallback).
test: native test-fast bench-comm-smoke prof-smoke transport-smoke \
      stripe-smoke tracerec-smoke async-smoke ffi-smoke \
      placement-smoke synth-smoke hier-smoke sharded-smoke \
      chaos-smoke links-smoke tune-smoke metrics-lint
test-fast:
	$(PYTEST) tests/ -m "not slow"

# Slow tier: multi-process bfrun launches, example e2e runs, heavy model
# grids.
test-slow:
	$(PYTEST) tests/ -m "slow"

test-all:
	$(PYTEST) tests/

# The chip check: trainer, gossip, compiled flash kernels and the 2048-wide
# LM on every TPU chip of this host; exits non-zero without a TPU.
chip-smoke:
	python chip_smoke.py

# Gossip hot-path microbench: rounds/edges/walltime, naive shift-distance
# schedule vs the min-round repack (ops/schedule_opt.py), CPU-runnable.
bench-comm:
	python bench_comm.py

# Tiny-mesh CI smoke of the same: fails loudly on any schedule regression
# (more rounds than naive, off the König bound, or output drift > 1e-6).
bench-comm-smoke:
	env JAX_PLATFORMS=cpu python bench_comm.py --smoke

# End-to-end telemetry check: start the /metrics endpoint, drive one
# collective, scrape /metrics + /healthz and assert the core series exist.
telemetry-smoke:
	env JAX_PLATFORMS=cpu \
	    XLA_FLAGS="--xla_force_host_platform_device_count=8" \
	    python -m bluefog_tpu.utils.telemetry

# End-to-end profiler check: tiny CPU-backed profiled loop — asserts the
# bf_step_phase_seconds histogram appears in /metrics, the straggler
# report in /healthz, and that trace-merge emits valid JSON with one
# process lane per rank.
prof-smoke:
	env JAX_PLATFORMS=cpu \
	    XLA_FLAGS="--xla_force_host_platform_device_count=8" \
	    python -m bluefog_tpu.utils.profiler

# Physical-placement CI gate: modeled link-load report on simulated 4x8
# and 8x8 tori (asserts the optimizer+packer cut random-regular max-link-
# load >= 2x and never worsen shift-structured placements) plus an end-to-
# end check that the placement permutation is BIT-identical to enumeration
# order on the virtual CPU mesh.
placement-smoke:
	env JAX_PLATFORMS=cpu python bench_comm.py --placement-smoke

# Schedule-synthesis CI gate: modeled serial-link-time report across
# ring/Exp2/star/random-regular on simulated 4x8, 8x8 and multi-slice
# tori — asserts the sketch synthesis strictly beats the congestion
# repack on the acceptance cases (and ties ONLY at the provable
# busiest-link-total lower bound), preserves the effective weight matrix
# bit-identically, stays within the round budget, drives a synthesized
# schedule end-to-end on the virtual CPU mesh (<= 1e-6), and that
# BLUEFOG_TPU_SCHEDULE_SYNTH=0 restores the PR-5 dispatch path.
synth-smoke:
	env JAX_PLATFORMS=cpu python bench_comm.py --synth-smoke

# Hierarchical-gossip CI gate: on simulated 2x(4x8) and 4x(4x4) multi-
# slice tori the two-level mode (dense ICI inner exp2, sparse one-peer
# DCN outer at cadence 2 with sparse:0.5 compression) must cut per-step
# DCN wire rows AND modeled inter-slice serial link time >= 4x vs flat
# exp2 at equal-or-better simulated consensus distance; plus the e2e
# product-topology equivalence (<= 1e-6), the BLUEFOG_TPU_HIER=0
# bit-identity check, and the sparse:<frac> OP_BATCH round-trip.
hier-smoke:
	env JAX_PLATFORMS=cpu python bench_comm.py --hier-smoke

# Sharded-gossip CI gate: the ShardPlan byte model must scale per-step
# DCN bytes with the replicated fraction ONLY (25/50/75% MoE trees on a
# simulated 16-rank, 4-group mesh; per-group schedules never emit a
# cross-group edge), and the 8-device executor leg must match the dense
# replicated oracle and the per-group sharded oracle <= 1e-6, bill
# exactly rep_row_bytes x dcn_edges x steps to {level="dcn"} with NO
# sharded byte on the DCN, and be BIT-identical to the no-spec path
# under BLUEFOG_TPU_SHARDED_GOSSIP=0 or a fully replicated tree.
sharded-smoke:
	env JAX_PLATFORMS=cpu python bench_comm.py --sharded-smoke

# CPU-runnable loopback two-transport exchange over the coalesced DCN
# path, run twice: native hot path allowed (asserts the C++ batch/drain/
# fold path actually ENGAGED when available, batched delivery happened,
# and the batch + bf_win_native_* telemetry series exist) and pinned to
# the Python fallback (BLUEFOG_TPU_WIN_NATIVE=0 must restore the PR-4
# path exactly).  No timing assertion — `python bench_comm.py --transport`
# full runs gate the >= 5x small-row messages/s win of the native path.
transport-smoke:
	python bench_comm.py --transport-smoke
	env BLUEFOG_TPU_WIN_NATIVE=0 python bench_comm.py --transport-smoke

# Multi-stream striped transport CI gate: asserts >= 2 stripes engage on
# the loopback rig (independent sockets/workers/arenas per peer, frames
# sharded by (window, row)) with the per-stripe telemetry series present
# (bf_win_tx_stripe_bytes_total, (peer, stripe)-labeled queue-depth
# gauges, the decode-pool busy gauge), and that a pinned
# BLUEFOG_TPU_WIN_STRIPES=1 leg reproduces the pre-stripe wire exactly
# (one sender, send-order delivery, fence weight 0.0).  No timing
# assertion; `python bench_comm.py --transport` full runs carry the
# 1/2/4-stripe x row-size x concurrent-peers sweep.
stripe-smoke:
	python bench_comm.py --stripe-smoke
	env BLUEFOG_TPU_WIN_NATIVE=0 python bench_comm.py --stripe-smoke

# Message-level tracing CI gate: flight recorder armed + wire trace tags
# sampled at 1/2 through a loopback window-store pair — asserts the
# per-edge contribution-age histograms/gauges land on /metrics and in
# /healthz, the recorder dump decodes into a valid merged chrome trace
# with matched cross-rank flow arrows (trace-gossip), and that a
# BLUEFOG_TPU_TELEMETRY=0 leg leaves the registry completely untouched.
# With BLUEFOG_TPU_TRACE_SAMPLE unset and the recorder off, nothing in
# this PR runs at all — the wire stays bitwise identical (unit-tested).
tracerec-smoke:
	env JAX_PLATFORMS=cpu python bench_comm.py --tracerec-smoke

# Barrier-free async gossip CI gate: a loopback two-transport rig with
# BLUEFOG_TPU_ASYNC=1 and the sender's origin-step clock pinned behind
# the receiver's (the injected delay) — asserts the bounded-staleness
# fold rejects the over-age accumulates into the stale-residual store
# (bf_win_stale_rejected_total on /metrics, the "async" block in
# /healthz), that win_fold_stale_residuals restores the held mass into
# staging EXACTLY (push-sum conservation on real wire frames), and that
# a BLUEFOG_TPU_TELEMETRY=0 leg leaves the registry untouched.  Run on
# the native hot path AND pinned to the Python fallback.
async-smoke:
	env JAX_PLATFORMS=cpu python bench_comm.py --async-smoke
	env JAX_PLATFORMS=cpu BLUEFOG_TPU_WIN_NATIVE=0 \
	    python bench_comm.py --async-smoke

# Zero-copy XLA put-path CI gate: loopback window-store puts of DEVICE
# arrays through the BLUEFOG_TPU_WIN_XLA plan dispatch — asserts the FFI
# path engaged and bf_win_host_copy_bytes_total reports ZERO put-side
# staging bytes for dense f32 rows.  Graceful skip (not a failure) when
# jax.ffi, the bf_xla native symbols, or the toolchain are absent — the
# documented degraded mode.  No timing assertion here;
# `python bench_comm.py --ffi` full runs gate the >= 2x dispatch-overhead
# win over the PR-9 native put path for rows >= 4 KiB.
ffi-smoke:
	env JAX_PLATFORMS=cpu python bench_comm.py --ffi-smoke

# Churn-controller CI gate: a real 4-process `bfrun --chaos` gang on the
# CPU backend, one rank SIGKILLed mid-gossip — asserts the survivors reach
# failure consensus (a committed membership epoch in /healthz), re-plan
# onto a survivor topology without a global restart within a bounded
# number of steps, converge to the survivor-consensus optimum, and keep
# post-recovery step time within 1.5x the pre-failure median.  The
# delay leg runs the same gang under a `delay:` fault in BOTH gossip
# modes: synchronous survivors must DEGRADE toward the slowest rank's
# cadence while BLUEFOG_TPU_ASYNC=1 survivors hold the no-fault step
# time, the merely-slow rank is NOT evicted even with step-lag eviction
# armed (the widened async bound), and both modes reach the same
# consensus optimum (matched final loss through rejection + backstop).
# The JOIN leg (elastic scale-up, ops/gang.py) runs a coordinator-free
# `bfrun --elastic` gang, kills rank 2 mid-training, admits a fresh
# `bfrun --join` process through the persisted endpoint directory and
# asserts exactly one committed grow epoch + convergence to the
# FULL-gang optimum; the KILL-RANK-0 leg kills rank 0 instead — the
# gang must survive (membership/bootstrap never touch a coordinator)
# and admit a replacement for rank 0 the same way.
chaos-smoke:
	env JAX_PLATFORMS=cpu python -m bluefog_tpu.tools chaos --smoke
	env JAX_PLATFORMS=cpu python -m bluefog_tpu.tools chaos --delay-smoke
	env JAX_PLATFORMS=cpu python -m bluefog_tpu.tools chaos --join-smoke
	env JAX_PLATFORMS=cpu python -m bluefog_tpu.tools chaos --kill0-smoke

# Link-observatory CI gate: a real 4-process `bfrun --chaos` gang on the
# CPU backend with a `linkdelay:` fault holding one rank's outbound DATA
# links at +60ms — asserts the online estimator's per-edge delay EWMAs
# converge on the injected delay on the affected edges while unaffected
# edges stay flat, measured-vs-modeled divergence crosses the alert
# threshold, exactly the matching BLUEFOG_TPU_SLO rule fires on the
# receiver ranks (bf_slo_breaches_total + degraded /healthz links block
# + one flight-recorder dump) while a co-armed quiet rule stays silent,
# every rank computes the IDENTICAL merged link matrix
# (bf.link_report() agreement), and `tools top` renders one complete
# frame against the live gang's /metrics endpoints.  The second leg
# pins BLUEFOG_TPU_LINK_OBS=0 through the transport smoke: the
# off-switch must be bitwise inert (not one bf_link_* series).
links-smoke:
	env JAX_PLATFORMS=cpu python -m bluefog_tpu.tools chaos --links-smoke
	env BLUEFOG_TPU_LINK_OBS=0 python bench_comm.py --transport-smoke

# Self-tuning control-plane smoke: the same 4-proc gang started on a
# full mesh (the wrong topology for the coming fault), run twice.  With
# BLUEFOG_TPU_TUNE=1 the tuner must measure the hot edges, commit
# EXACTLY ONE numbered adaptation epoch agreed by every rank (re-route
# + knob moves), recover >= 2x of the delayed rank's lost gossip
# throughput without a restart, and surface the epoch in the /healthz
# "tuner" block and the `tools top` tune column.  With
# BLUEFOG_TPU_TUNE=0 pinned, the identical fault must leave the send
# schedule bitwise unchanged and register zero bf_tune_* series — the
# default-off contract (both legs run inside the one driver).
tune-smoke:
	env JAX_PLATFORMS=cpu python -m bluefog_tpu.tools chaos --tune-smoke

# Metrics/doc drift gate: AST-scan every bf_* series the package
# registers against the docs/observability.md inventory, BOTH ways —
# fails on an undocumented metric or a stale inventory row.
metrics-lint:
	python -m bluefog_tpu.tools.metrics_lint

# Full interactive chaos demo (same harness, bigger run; see
# `python -m bluefog_tpu.tools chaos --help` for kill/delay/partition
# fault specs).
chaos:
	env JAX_PLATFORMS=cpu python -m bluefog_tpu.tools chaos

# Native core (+ the _bf_fastcall hot-path module when Python.h exists).
# Graceful skip with a clear log line when no C++ toolchain is present:
# every native consumer (schedule compile, timeline, window transport)
# carries a pure-Python fallback, so `make test` still runs — the
# transport smoke simply exercises the fallback path.
native:
	@if command -v $(CXX) >/dev/null 2>&1 || command -v g++ >/dev/null 2>&1; \
	then $(MAKE) -C bluefog_tpu/native; \
	else echo "make native: no C++ toolchain found (CXX=$(CXX)) — SKIPPING" \
	          "the native build; Python fallbacks stay in use"; fi
