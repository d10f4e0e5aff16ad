"""On how many assignments do the program and the plain reference differ?

    python3 benchmark/selftest/olmoe_assignments.py [--seed N] [--seq 2048]
        [--config olmoe-1b-7b | --tiny]

A top-k choice is not continuous: where a token's k-th and (k+1)-th router
probabilities nearly tie, the bfloat16 rounding of the router's input picks
the other expert, and the program's output and gradients then differ from the
float32 reference's by that expert's whole share of the token, however exact
everything else is.  The ``model`` check of ``benchmark/run.py`` compares
loss and gradients and cannot say how much of their error is that; this
tool counts it.  One sequence at the configuration's widths, seeded weights
plus the check's noise, the forward pass alone: the program's router logits
(``capture_intermediates``) against the experts the reference chose
(``aux["experts"]``), per layer, as a share of the ``S * k`` assignments,
with the margin between the k-th and (k+1)-th probability beside it.

``--grads`` adds what the flips cost: the relative errors of the loss and of
every gradient leaf (program against reference, as the ``model`` check takes
them) for the configuration as it is, and again with every expert chosen for
every token (``num_experts_per_tok = num_experts``: the same weights, widths
and kernels, and no choice left to flip), which is what bfloat16 rounding
alone costs.  A third reading says what the nearest precision below would
give: the float32 reference itself with nothing but its matrices rounded to
``float8_e4m3fn`` (activations and accumulation stay float32, so this is less
than an 8-bit computation would lose) against the same reference unrounded;
the configuration's ``model_check`` bounds have to refuse it.  Runs on
whatever device jax finds; what it prints on a CPU is not the chip's.
"""

import argparse
import functools
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from benchmark import checks, spec  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", default="olmoe-1b-7b")
    ap.add_argument("--tiny", action="store_true",
                    help="selftest/configs/tiny-olmoe.json instead")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seq", type=int, default=2048)
    ap.add_argument("--grads", action="store_true")
    args = ap.parse_args()
    path = (os.path.join(spec.HERE, "selftest", "configs", "tiny-olmoe.json")
            if args.tiny else
            os.path.join(spec.HERE, "configs", args.config + ".json"))
    config = spec.read_json(path)
    task = spec.load_module(os.path.join("tasks", config["task"] + ".py"))
    reference = spec.load_module(os.path.join(
        "reference", config.get("reference", config["name"]) + ".py"))
    # forward only: no remat wrapper between the router and the capture
    config["model"]["args"]["remat"] = False
    model = task.make_model(config)
    k, n = config["num_experts_per_tok"], config["num_experts"]
    sizes = {"sequences": 1, "seq_len": args.seq}
    key = jax.random.PRNGKey(args.seed)

    @jax.jit
    def make(key):
        params, aux = task.init(model, jax.random.fold_in(key, 0), config,
                                sizes)
        noise = checks.seeded_leaves(
            jax.random.fold_in(key, 1), 3,
            [x.shape for x in jax.tree.leaves(params)], checks.MODEL_NOISE)
        params = jax.tree.map(jnp.add, params, jax.tree.unflatten(
            jax.tree.structure(params), noise))
        tokens, = task.make_batch(jax.random.fold_in(key, 2), config, sizes)
        return params, aux, tokens

    params, aux, tokens = make(key)

    @jax.jit
    def program_logits(params, tokens):
        _, seen = model.apply(
            {"params": params}, tokens, return_hidden=True,
            capture_intermediates=lambda m, _: m.name == "router")
        return [seen["intermediates"][f"block_{i}"]["moe"]["router"][
            "__call__"][0] for i in range(config["num_hidden_layers"])]

    logits = program_logits(params, tokens)
    with jax.default_matmul_precision("highest"):
        _, ref_aux = jax.jit(functools.partial(reference.loss, cfg=config))(
            params, aux, tokens)
    out = {"device": jax.devices()[0].device_kind, "seq": args.seq,
           "assignments_per_layer": args.seq * k, "layers": []}
    for layer, lg in enumerate(logits):
        probs = jax.nn.softmax(lg.astype(jnp.float32), axis=-1)
        top, chosen = jax.lax.top_k(probs, k + 1)
        ours = jax.nn.one_hot(chosen[:, :k], n).sum(axis=1)
        theirs = jax.nn.one_hot(ref_aux["experts"][layer].reshape(-1, k),
                                n).sum(axis=1)
        differ = float(args.seq * k - (ours * theirs).sum())
        margin = np.asarray((top[:, k - 1] - top[:, k]) / top[:, k - 1])
        out["layers"].append({
            "differing_assignments": int(differ),
            "share": differ / (args.seq * k),
            "tokens_with_a_flip": int((np.asarray(
                (ours * theirs).sum(axis=1)) < k).sum()),
            "relative_margin_k_to_k_plus_1": {
                "median": float(np.median(margin)),
                "share_under_1e-2": float((margin < 1e-2).mean()),
                "share_under_1e-3": float((margin < 1e-3).mean())}})
    if args.grads:
        every = dict(config, num_experts_per_tok=n)

        def program(config):
            return task.loss_fn(task.make_model(config), config)

        def float8_matrices(params, aux, tokens):
            rounded = jax.tree.map(
                lambda x: x.astype(jnp.float8_e4m3fn).astype(x.dtype)
                if x.ndim >= 2 else x, params)
            with jax.default_matmul_precision("highest"):
                return reference.loss(rounded, aux, tokens, cfg=config)

        bounds = config["model_check"]
        out["errors"] = {
            "as_configured": errors(program(config), reference, config,
                                    params, aux, tokens),
            "every_expert_chosen": errors(program(every), reference, every,
                                          params, aux, tokens),
            "reference_with_float8_matrices": errors(
                float8_matrices, reference, config, params, aux, tokens)}
        low = out["errors"]["reference_with_float8_matrices"]
        out["bounds"] = dict(
            bounds, refuse_float8=bool(
                low["loss_rel_err"] > bounds["loss_rtol"]
                or low["grad_rel_err_median"] > bounds["grad_rtol"]))
    print(json.dumps(out))
    return 0


def errors(candidate, reference, config, params, aux, tokens) -> dict:
    """Relative error of the candidate's loss, and in the 2-norm of each of
    its gradient leaves, against the float32 reference's."""
    (loss, _), grads = jax.jit(jax.value_and_grad(
        candidate, has_aux=True))(params, aux, tokens)
    with jax.default_matmul_precision("highest"):
        (want, _), want_grads = jax.jit(jax.value_and_grad(
            functools.partial(reference.loss, cfg=config), has_aux=True))(
                params, aux, tokens)
    errs = [float(jnp.linalg.norm((g - w).ravel())
                  / jnp.linalg.norm(w.ravel()))
            for g, w in zip(jax.tree.leaves(grads),
                            jax.tree.leaves(want_grads))]
    return {"loss_rel_err": abs(float(loss) - float(want)) / abs(float(want)),
            "grad_rel_err_median": float(np.median(errs)),
            "grad_rel_err_max": max(errs)}


if __name__ == "__main__":
    sys.exit(main())
