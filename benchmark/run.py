"""The benchmark's one command.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Runs one cell of ``BENCHMARK.json`` on the machine it is started on: the
gossip trainer's training step through ``bf.init`` + ``bf.rank_map`` +
``bf.optim.Distributed*Optimizer.step``, built from the cell's configuration
and traffic files alone (``benchmark/spec.py`` says which name finds which
file).  ``--trace 0`` times the step and prints the cell's end-to-end
metrics; ``--trace 1`` records a short profiler trace and prints its
per-layer metrics.  The last line of standard output is one JSON object
``{"correct", "attempted", "failed", "metrics", "device"[, "breakdown"]}``;
everything a reader might want beside it is on the lines before.

A cell of ``BENCHMARK.json`` needs a TPU with at least the chips it asks
for, of a kind ``benchmark/peaks.json`` knows; otherwise the command exits
non-zero and prints no result.  The tiny twins of
``benchmark/selftest/workloads.json`` rehearse the same code on a CPU mesh.
"""

import time
_PROCESS_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import traceback  # noqa: E402

import numpy as np  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

WARMUP_STEPS = 3
TRACE_BLOCKED_STEPS = 4
TRACE_FREE_GROUPS = 2


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def gate(cell):
    """The devices the cell runs on and the seconds the runtime took to hand
    them over, or exit: a cell of ``BENCHMARK.json`` takes TPU chips only,
    and never fewer than it asks for."""
    import jax
    want = cell.platform or "tpu"
    t0 = time.perf_counter()
    devices = jax.devices()
    runtime_s = time.perf_counter() - t0
    if devices[0].platform != want:
        raise SystemExit(
            f"benchmark: workload {cell.name!r} needs platform {want!r}; "
            f"jax found {devices[0].platform!r}")
    if len(devices) < cell.chips:
        raise SystemExit(
            f"benchmark: workload {cell.name!r} needs {cell.chips} chip(s); "
            f"jax found {len(devices)}")
    return devices, runtime_s


def measure(cell, task, devices, args, peaks, clock) -> dict:
    """Set up and run every phase (the last one alone when tracing); leaves
    the last phase's job open for the checks."""
    from benchmark import layers, loop, programs
    from benchmark.build import Job
    import jax
    phases = cell.phases if not args.trace else cell.phases[-1:]
    out = {"timed": [], "failures": [], "peak_bytes": 0, "trace": None}
    for i, phase in enumerate(phases):
        t0 = time.perf_counter()
        job = Job(cell, task, devices[:phase["devices"]], args.seed)
        loop.warm_up(job, WARMUP_STEPS)
        compiled = programs.step_programs(job)
        memory = programs.peak_bytes(job, compiled)
        out["peak_bytes"] = max(out["peak_bytes"], memory["peak"])
        print(f"phase {phase['name']}: {job.n} chip(s) ready in "
              f"{time.perf_counter() - t0:.1f}s; {clock.line()}; cache dir "
              f"{jax.config.jax_compilation_cache_dir}; bytes on one chip: "
              f"gradient program {memory['grad']}, optimizer program "
              f"{memory['step']}", flush=True)
        compiles_before = clock.compiles
        if args.trace:
            out["trace"] = layers.run(
                job, cell, task, peaks, compiled,
                os.path.join(ROOT, ".bench_trace", cell.name),
                blocked_steps=TRACE_BLOCKED_STEPS,
                free_groups=TRACE_FREE_GROUPS)
        else:
            result = loop.timed(job, phase["name"],
                                args.seconds * phase["share"])
            out["timed"].append(result)
            q1, q2, q3 = result.quartiles() if result.groups else (0, 0, 0)
            print(f"phase {phase['name']}: {len(result.groups)} groups of "
                  f"{loop.GROUP} steps in {result.seconds:.2f}s; group "
                  f"seconds q1 {q1:.5f} median {q2:.5f} q3 {q3:.5f}; step "
                  f"median {q2 / loop.GROUP * 1e3:.3f} ms", flush=True)
            if result.error:
                out["failures"].append(
                    f"phase {result.name}: {result.error}")
        if clock.compiles > compiles_before:
            out["failures"].append(
                f"{clock.compiles - compiles_before} compilation(s) inside "
                f"the measured window of phase {phase['name']}")
        if i + 1 < len(phases):
            job.close()
    out.update(job=job, compiled=compiled, end=time.perf_counter())
    return out


def run_checks(cell, task, measured) -> list:
    """What decides ``correct``, after the window and not part of
    ``setup_s``; returns the failures."""
    from benchmark import checks, spec
    job, failures = measured["job"], []

    def check(name, fn):
        t0 = time.perf_counter()
        try:
            print(f"check {name}: ok {fn()} "
                  f"({time.perf_counter() - t0:.1f}s)", flush=True)
        except Exception as e:  # noqa: BLE001 - recorded: correct is false
            if not isinstance(e, checks.Failed):
                traceback.print_exc(file=sys.stdout)
            failures.append(f"{name}: {e}")
            print(f"check {name}: FAILED {e}", flush=True)

    for result in measured["timed"]:
        check(f"losses[{result.name}]", lambda r=result: checks.losses(r))
    check("programs", lambda: checks.programs(job, measured["compiled"]))
    check("placement", lambda: checks.placement(job))
    check("mixing", lambda: checks.mixing(job, spec.mixing_reference(cell)))
    job.close()     # the next two bring their own trees
    check("model", lambda: checks.model(job, task,
                                        spec.reference_module(cell)))
    check("step", lambda: checks.step(job, spec.optimizer_reference(cell),
                                      spec.mixing_reference(cell)))
    return failures


def main(argv=None) -> int:
    args = parse(argv)
    from benchmark import spec
    cell = spec.load_cell(args.workload)
    import jax
    devices, runtime_s = gate(cell)
    kind = devices[0].device_kind
    peaks = spec.peak_row(cell.peaks_of or kind)
    # Persist every program, however quick to compile: the second run of a
    # cell in a checkout finds all of them (bf.init() names the directory).
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    # The system under test: where it is absent (a directory that holds the
    # benchmark alone), fail here, before a line is printed.
    import bluefog_tpu  # noqa: F401
    from benchmark import programs
    task = spec.task_module(cell)
    clock = programs.CompileClock()
    print(f"device: platform {devices[0].platform}, kind {kind}, "
          f"{len(devices)} found, {cell.chips} used, handed over by the "
          f"runtime in {runtime_s:.2f}s (not part of setup_s); jax "
          f"{jax.__version__}", flush=True)

    measured = measure(cell, task, devices, args, peaks, clock)
    failures = measured["failures"] + run_checks(cell, task, measured)
    stats = devices[0].memory_stats() or {}
    print(f"checks took {time.perf_counter() - measured['end']:.1f}s; "
          f"{clock.line()}; memory_stats peak_bytes_in_use "
          f"{stats.get('peak_bytes_in_use')} beside the computed peak "
          f"{measured['peak_bytes']}", flush=True)

    timed = measured["timed"]
    device = {"platform": devices[0].platform, "kind": kind,
              "count": len(devices),
              "memory_peak_bytes": measured["peak_bytes"]}
    out = {"correct": not failures,
           "attempted": sum(r.attempted for r in timed),
           "failed": sum(int((~np.isfinite(r.losses).all(axis=1)).sum())
                         + (1 if r.error else 0) for r in timed),
           "metrics": {}, "device": device}
    if args.trace:
        traced = measured["trace"]
        values, wanted = traced["metrics"], cell.per_layer
        out["attempted"] = traced["steps"]
        device.update(busy_s=traced["busy_s"], window_s=traced["window_s"])
        out["breakdown"] = traced["breakdown"]
    else:
        values = end_to_end(cell, task, timed, measured["peak_bytes"],
                            measured["end"] - _PROCESS_START - runtime_s,
                            peaks)
        wanted = cell.end_to_end
    for m in wanted:
        if values.get(m["name"]) is not None:
            out["metrics"][m["name"]] = {"value": values[m["name"]],
                                         "unit": m["unit"]}
    for f in failures:
        print(f"NOT CORRECT: {f}")
    print(json.dumps(out), flush=True)
    return 0


def end_to_end(cell, task, timed_phases, peak_bytes, own_seconds,
               peaks) -> dict:
    """The end-to-end metrics of an untraced run, and the plain MFU on an
    earlier line.  ``own_seconds``: process start to the end of the last
    timed phase, less the runtime's own start-up (``jax.devices()``, printed
    on the first line: it was most of the run-to-run spread, and no change
    to the program moves it)."""
    from benchmark import loop
    items = task.items_per_step(cell.traffic["batch"])
    rate = {r.name: items * loop.GROUP / r.quartiles()[1]
            for r in timed_phases if r.groups}
    last = timed_phases[-1].name
    values = {
        "throughput_per_chip": rate.get(last),
        "peak_hbm_gib": peak_bytes / 2 ** 30,
        "setup_s": own_seconds - sum(r.seconds for r in timed_phases),
    }
    ratio = cell.traffic.get("scaling_efficiency")
    if ratio and ratio["over"] in rate and ratio["of"] in rate:
        values["scaling_efficiency"] = rate[ratio["of"]] / rate[ratio["over"]]
    if rate.get(last):
        per_item = task.step_flops(cell.config,
                                   cell.traffic["batch"])["flops"] / items
        peak = peaks["bf16_flops_per_s"]
        print(f"throughput {rate[last]:.2f} {task.ITEM}s/s/chip; "
              f"{per_item / 1e9:.3f} GFLOP required per {task.ITEM}; plain "
              f"MFU {100 * rate[last] * per_item / peak:.2f}% of "
              f"{peak / 1e12:.0f} TFLOP/s")
    return values


if __name__ == "__main__":
    sys.exit(main())
