"""Compile a cell's two step programs for a described v5e, without a chip.

    JAX_PLATFORMS=cpu python3 benchmark/selftest/compile_v5e.py \
        --workload lm-s4096-gossip-4chip [--layers 2 3 4] [--hlo-dir DIR]

The TPU compiler is installed here and compiles for a topology that is
described and not attached (``v5e:2x2``).  The script hands ``bf.init`` the
described devices, builds the model, the gradient program and the optimizer
exactly as ``benchmark/run.py`` does, lowers both programs on shapes (there
is no device to hold an array) and prints, per program, the bytes on one
chip from ``memory_analysis()``, those bytes plus the trees that are resident
but not an argument, the collectives in the HLO and the Mosaic kernels.  The
compiler refuses a program that does not fit 16 GB, so this is where the
depth of ``internlm2-1.8b`` was fixed: ``--layers`` overrides
``num_hidden_layers`` to try others.  Nothing runs: no time, no result.
"""

import argparse
import os
import re
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.experimental import topologies  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

HBM = 16909336064   # memory_stats()["bytes_limit"] of a v5e chip (15.75 GiB)


def compile_cell(cell, chips: int, hlo_dir=None) -> dict:
    import bluefog_tpu as bf
    from bluefog_tpu.optim import functional as F
    from benchmark import programs, spec
    from benchmark.build import make_optimizer

    task = spec.task_module(cell)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    bf.init(devices=list(topo.devices)[:chips])
    n = bf.size()
    rank = NamedSharding(bf.mesh(), P(bf.mesh().axis_names[0]))
    model = task.make_model(cell.config)
    batch, pool = cell.traffic["batch"], cell.traffic["pool"]["size"]

    def shapes(key):
        params, aux = task.init(model, key, cell.config, batch)
        return params, aux, task.make_batch(key, cell.config, batch)

    def rank_major(tree):
        return jax.tree.map(lambda s: jax.ShapeDtypeStruct(
            (n,) + s.shape, s.dtype, sharding=rank), tree)

    params, aux, one_batch = jax.eval_shape(shapes, jax.random.PRNGKey(0))
    opt = make_optimizer(cell.traffic["optimizer"], n)
    state = jax.eval_shape(lambda p: F.dist_init(opt.base, p), params)
    params, aux, one_batch, state = map(
        rank_major, (params, aux, one_batch, state))
    vgrad = bf.rank_map(jax.value_and_grad(
        task.loss_fn(model, cell.config), has_aux=True))
    compiled = {
        "grad": vgrad.lower(params, aux, *one_batch).compile(),
        "step": opt._step_callable(with_weights=False).lower(
            params, params, state).compile()}

    per = lambda tree: sum(  # noqa: E731
        int(np.prod(s.shape)) * jnp.dtype(s.dtype).itemsize
        for s in jax.tree.leaves(tree)) // n
    others = (pool - 1) * per(one_batch)
    resident = {"grad": per(state) + others,
                "step": others + per(one_batch) + per(aux)}
    out = {}
    for name, prog in compiled.items():
        own = programs.program_bytes(prog)
        text = prog.as_text()
        out[name] = {"program": own, "with_resident": own + resident[name],
                     "collectives": programs.collective_counts(prog),
                     "mosaic_calls": text.count("tpu_custom_call")}
        print(f"  {name}: program {own / 2**30:.3f} GiB, with resident "
              f"trees {(own + resident[name]) / 2**30:.3f} GiB of "
              f"{HBM / 2**30:.2f}; {prog.memory_analysis()}")
        print(f"  {name}: collectives {out[name]['collectives']}, "
              f"{out[name]['mosaic_calls']} mentions of tpu_custom_call")
        if hlo_dir:
            os.makedirs(hlo_dir, exist_ok=True)
            with open(os.path.join(hlo_dir, f"{cell.name}.{name}.hlo.txt"),
                      "w") as f:
                f.write(text)
    print(f"  parameters per rank: {per(params) // 4 / 1e6:.1f}M (float32)")
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--layers", type=int, nargs="*", default=None)
    ap.add_argument("--hlo-dir", default=None)
    args = ap.parse_args()
    from benchmark import spec
    # A compile for an absent chip is written to the persistent cache but
    # cannot be read back; keep the cache out of it.
    jax.config.update("jax_enable_compilation_cache", False)
    cell = spec.load_cell(args.workload)
    fits = {}
    for layers in args.layers or [cell.config.get("num_hidden_layers")]:
        if layers is not None:
            cell.config["num_hidden_layers"] = layers
        for phase in cell.phases:
            print(f"{cell.name}: num_hidden_layers {layers}, phase "
                  f"{phase['name']} on {phase['devices']} chip(s)",
                  flush=True)
            try:
                out = compile_cell(cell, phase["devices"], args.hlo_dir)
                ok = all(v["with_resident"] <= HBM for v in out.values())
            except Exception as e:  # noqa: BLE001 - the compiler's refusal
                msg = re.sub(r"\s+", " ", str(e))[:400]
                print(f"  refused: {type(e).__name__}: {msg}")
                ok = False
            fits[(layers, phase["name"])] = ok
    print("fits:", {f"L={k[0]} {k[1]}": v for k, v in fits.items()})
    return 0 if all(fits.values()) else 1


if __name__ == "__main__":
    sys.exit(main())
