"""The tiny twins of the benchmark's configurations
(``benchmark/selftest/configs/tiny-*.json``) and the whole-model cases every
one of them is held to, written once: loss and every gradient leaf against
the configuration's plain float32 reference (``benchmark/reference/``, which
imports nothing of ``bluefog_tpu``), bfloat16 inside the twin's
``model_check`` bounds, float8-rounded matrices outside them, two steps of the
twin's own optimizer under ATC on four devices against ``W @`` the
hand-written update, the shares of a held expert layer adding up to the uncut
one, and the digest of the gradient jaxpr.

A test file names the twins it runs in ``TWINS`` and imports the cases;
``conftest.pytest_generate_tests`` hands each case, as ``twin``, those of
the file's twins whose row has what the case ``needs``.  The twins are
spread over the family files so that no file holds all the slow cases (the
run is as long as its longest file).  What differs by twin is a row of
``TWINS`` here."""

import copy
import functools
import hashlib
import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import spec  # noqa: E402
from bluefog_tpu.models import transformer as T  # noqa: E402

HIGHEST = functools.partial(jax.default_matmul_precision, "highest")


def normal(key, i, shape, scale=1.0):
    return scale * jax.random.normal(jax.random.fold_in(key, i), shape)


def with_dtype(config, dtype, remat=None):
    config = copy.deepcopy(config)
    config["model"]["args"]["dtype"] = dtype
    if remat is not None:
        config["model"]["args"]["remat"] = remat
    return config


def rel(a, b):
    return float(jnp.linalg.norm((a - b).ravel())
                 / jnp.linalg.norm(b.ravel()))


def _with_bias(ref, y, params, bias, cfg):
    return ref._experts(y, params, bias, cfg)[0]


def _without_bias(ref, y, params, bias, cfg):
    return ref._experts(y, params, cfg)[0]


# seed: of the file the twin's cases came from, so that they draw the values
#   they drew there.
# images: of a batch, where the twin takes images and no sequences.
# f32: the float32 case's sequence length (one that is no multiple of the
#   twin's blocks, windows or chunks), remat where the program is the same
#   numbers at half the compile, and its tolerances on the loss, the worst
#   gradient leaf and the median one.
# has / lacks: leaves the float32 case looks for, with their shapes.
# load: (expert layers, router width) of the statistics.
# f8: of the float8 case, the share of leaves over the bound and how many
#   of 50 samples of 8 leaves may pass (no case where the toy cannot set
#   the precisions apart: ``tiny-ling3``, its ``model_check.why``).
# traffic: the twin's own optimizer (``benchmark/selftest/traffic``).
# held, experts: the key that says how many experts are held, and the
#   reference's expert layer.
# documents: of a row of ``seq`` tokens, where the twin's task packs its
#   rows (no boundary on a multiple of a block).
TWINS = {
    "tiny-lm": dict(
        seed=45, f32=dict(seq=200, loss=1e-5, worst=1e-4, median=1e-5),
        has={"block_0/q/kernel": (64, 64), "block_0/kv/kernel": (64, 64),
             "block_1/gate/kernel": (64, 256), "lm_head/kernel": (64, 512)},
        lacks=("wpe", "block_0/moe"), leaves=19, f8=(0.5, 1),
        traffic="tiny-tokens-scaling"),
    "tiny-olmoe": dict(
        seed=45, f32=dict(seq=200, loss=1e-5, worst=1e-4, median=1e-5),
        has={"block_0/qkv/kernel": (64, 192),
             "block_0/moe/gate": (8, 64, 32),
             "block_1/moe/router/kernel": (64, 8)},
        lacks=("block_0/gate",), load=(2, 8), leaves=23, f8=(0.5, 1),
        traffic="tiny-tokens-adamw"),
    "tiny-resnet": dict(
        seed=45, images=8, f32=dict(loss=1e-5, worst=2e-4, median=1e-4),
        has={"conv_init/kernel": (7, 7, 3, 8), "Dense_0/kernel": (256, 10)},
        lacks=(), leaves=161),
    "tiny-xing": dict(
        seed=31, f32=dict(seq=64, loss=1e-5, worst=1e-3, median=1e-5),
        has={"block_0/mla": None, "block_0/hc_attn": None,
             "block_0/hc_ffn": None, "block_0/gate": None,
             "block_1/moe/gate": (4, 64, 32),
             "block_1/moe/router/kernel": (64, 8)},
        lacks=("block_0/moe",), load=(2, 8), f8=(0.7, 0),
        traffic="tiny-tokens-1row-adamw",
        held="n_routed_experts", experts=_with_bias),
    "tiny-lfm2": dict(
        seed=34, f32=dict(seq=72, loss=1e-5, worst=1e-4, median=1e-5),
        has={"block_0/conv": None, "block_1/moe/gate": (4, 64, 32),
             "block_1/moe/router/kernel": (64, 8)},
        lacks=("lm_head", "block_0/moe", "block_1/moe/shared_gate",
               "block_1/moe/shared_up", "block_1/moe/shared_down"),
        load=(4, 8), f8=(0.5, 1), traffic="tiny-tokens-2row-adamw",
        held="num_experts", experts=_with_bias),
    "tiny-laguna": dict(
        seed=40, f32=dict(seq=192, loss=1e-6, worst=1e-5, median=1e-5),
        has={"block_0/q/kernel": (64, 6 * 16),
             "block_1/q/kernel": (64, 8 * 16),
             "block_1/attn_gate": None, "block_4/moe": None,
             "block_1/moe/gate": (4, 64, 16),
             "block_1/moe/router/kernel": (64, 16),
             "lm_head/kernel": (64, 512)},
        lacks=("block_0/moe",), load=(4, 16), leaves=64, f8=(0.5, 1),
        traffic="tiny-tokens-1row-adamw",
        held="num_experts", experts=_without_bias),
    "tiny-twotower": dict(
        seed=42,
        f32=dict(seq=200, remat=False, loss=1e-5, worst=1e-4, median=1e-5),
        has={"block_0/mamba": None, "block_1/moe/up": (4, 64, 24),
             "block_1/moe/router/kernel": (64, 16), "block_5/q": None,
             "block_5/kv": None, "block_5/proj": None},
        lacks=("wpe", "block_0/RMSNorm_1", "block_1/RMSNorm_1",
               "block_5/RMSNorm_1", "block_1/moe/gate"),
        load=(4, 16), leaves=67, f8=(0.5, 1),
        traffic="tiny-tokens-1row-adamw",
        held="n_routed_experts", experts=_with_bias),
    "tiny-kanana2": dict(
        seed=47, f32=dict(seq=200, loss=1e-5, worst=1e-4, median=1e-5),
        has={"block_0/mla/q/kernel": (64, 4 * 24), "block_0/gate": None,
             "block_1/moe/gate": (2, 64, 32),
             "block_1/moe/shared_gate/kernel": (64, 64),
             "block_1/moe/router/kernel": (64, 16)},
        lacks=("block_0/moe", "block_0/mla/q_a", "block_0/hc_attn", "wpe"),
        load=(2, 16), f8=(0.5, 1), traffic="tiny-tokens-packed-adamw",
        held="n_routed_experts", experts=_with_bias,
        documents=lambda seq: [seq - seq // 3 - seq // 5 - seq // 9,
                               seq // 3, seq // 5, seq // 9]),
    "tiny-ling3": dict(
        seed=51, f32=dict(seq=200, loss=1e-5, worst=1e-4, median=2e-5),
        has={"block_0/kda/qkv/kernel": (64, 3 * 64),
             "block_0/kda/conv_w": (3 * 64, 4),
             "block_0/kda/f/kernel": (64, 64),
             "block_0/kda/A_log": (4,), "block_0/kda/norm_scale": (16,),
             "block_0/gate": None, "block_4/kda": None,
             "block_5/mla/q/kernel": (64, 4 * 24),
             "block_5/mla/attn_gate/kernel": (64, 4),
             "block_1/moe/gate": (2, 64, 32),
             "block_1/moe/shared_gate/kernel": (64, 32),
             "block_1/moe/router/kernel": (64, 16)},
        lacks=("block_0/moe", "block_0/mla", "block_5/kda",
               "block_5/mla/q_a", "wpe"),
        load=(5, 16), leaves=104, traffic="tiny-tokens-1row-adamw",
        held="num_experts", experts=_with_bias),
}


@functools.lru_cache(maxsize=None)
def load(name):
    """The twin's configuration, its task and its reference."""
    config = spec.read_json(os.path.join(
        spec.HERE, "selftest", "configs", name + ".json"))
    return (config, spec.load_module(f"tasks/{config['task']}.py"),
            spec.load_module(f"reference/{config['reference']}.py"))


@pytest.fixture(scope="module")
def toy(request):
    """The first of the file's ``TWINS``: the family's own."""
    config, task, ref = load(request.module.TWINS[0])
    return copy.deepcopy(config), task, ref


def _at(tree, path):
    for key in path.split("/"):
        if key not in tree:
            return None
        tree = tree[key]
    return tree


def model_case(toy, key, dtype, batch, remat=None):
    """A twin at ``dtype`` on one batch from values that no leaf keeps from
    its initialiser, a drawn router bias where it has one: ``(config, params,
    aux, batch, program, reference)``."""
    config, task, ref = toy
    config = with_dtype(config, dtype, remat)
    model = task.make_model(config)
    params, aux = task.init(model, key, config, batch)
    params = jax.tree.map(lambda p: p + 0.02 * jax.random.uniform(
        jax.random.fold_in(key, p.size), p.shape, minval=-1.0, maxval=1.0),
        params)
    if "bias" in aux:
        aux = dict(aux, bias=normal(key, 30, aux["bias"].shape, 0.05))
    batch = task.make_batch(jax.random.fold_in(key, 31), config, batch)
    program = jax.jit(jax.value_and_grad(task.loss_fn(model, config),
                                         has_aux=True))
    reference = jax.jit(jax.value_and_grad(
        functools.partial(ref.loss, cfg=config), has_aux=True))
    return config, params, aux, batch, program, reference


def _twin_case(twin, dtype, seq, remat=None):
    row = TWINS[twin]
    batch = ({"images": row["images"]} if "images" in row
             else {"sequences": 2, "seq_len": seq})
    if "documents" in row:
        batch["documents"] = row["documents"](seq)
    return model_case(load(twin), jax.random.PRNGKey(row["seed"]), dtype,
                      batch, remat)


def test_toy_model_loss_and_every_gradient_leaf_in_float32(twin):
    """The loss, the expert layers' statistics, the moved bias and every
    gradient leaf, at a length the twin's tiles do not divide."""
    row = TWINS[twin]
    case = dict(row["f32"])
    tol = {k: case.pop(k) for k in ("loss", "worst", "median")}
    seq = case.pop("seq", None)
    config, params, aux, batch, program, reference = _twin_case(
        twin, "float32", seq, **case)
    with HIGHEST():
        (loss, new), grads = program(params, aux, *batch)
        (want, ref_new), ref_grads = reference(params, aux, *batch)
    for path, shape in row["has"].items():
        leaf = _at(params, path)
        assert leaf is not None, path
        assert shape is None or leaf.shape == shape, path
    for path in row["lacks"]:
        assert _at(params, path) is None, path
    assert abs(float(loss) - float(want)) / float(want) < tol["loss"]
    if "load" in row:
        np.testing.assert_array_equal(new["load"], ref_new["load"])
        assert new["load"].shape == row["load"]
        assert int(new["load"][0].sum()) \
            == 2 * seq * config["num_experts_per_tok"]    # all are counted
    if "bias" in aux:
        np.testing.assert_allclose(new["bias"], ref_new["bias"], atol=1e-7)
        assert float(jnp.abs(new["bias"] - aux["bias"]).max()) \
            == pytest.approx(config["router_bias_update_rate"], rel=1e-3)
    for term in ("balance_loss", "z_loss"):
        if term in ref_new:
            assert float(new[term]) == pytest.approx(float(ref_new[term]),
                                                     rel=1e-5)
    errs = jax.tree.map(rel, grads, ref_grads)
    assert len(jax.tree.leaves(errs)) == len(jax.tree.leaves(params)) \
        == row.get("leaves", len(jax.tree.leaves(params)))
    worst = max(jax.tree_util.tree_leaves_with_path(errs),
                key=lambda kv: kv[1])
    assert worst[1] < tol["worst"], jax.tree_util.keystr(worst[0])
    assert float(np.median(jax.tree.leaves(errs))) < tol["median"]


def test_toy_model_in_bfloat16_is_inside_the_twin_bounds(twin):
    config, params, aux, batch, program, reference = _twin_case(
        twin, "bfloat16", 256)
    (loss, _), grads = program(params, aux, *batch)
    with HIGHEST():
        (want, _), ref_grads = reference(params, aux, *batch)
    bounds = config["model_check"]
    assert abs(float(loss) - float(want)) / float(want) < bounds["loss_rtol"]
    errs = jax.tree.leaves(jax.tree.map(rel, grads, ref_grads))
    assert max(errs) < bounds["grad_rtol"]
    assert float(np.median(errs)) < bounds["grad_rtol"] / 2


def sampled(errs, bound, draws=50):
    """How many of ``draws`` samples of 8 leaves the check would pass."""
    rng = np.random.default_rng(0)
    errs = np.asarray(errs)
    return sum(errs[rng.choice(len(errs), 8, replace=False)].max() <= bound
               for _ in range(draws))


def test_float8_rounded_matrices_fail_the_bounds(twin):
    """The nearest precision below: the float32 reference with nothing but
    its matrices rounded to float8_e4m3fn, against itself unrounded, is
    outside the twin's gradient bound in so many leaves that hardly a sample
    of 8 passes; a cell's own bound was read on the chip (``model_check.why``
    of its configuration)."""
    share, passes = TWINS[twin]["f8"]
    config, params, aux, batch, _, reference = _twin_case(
        twin, "float32", 256)
    bound = config["model_check"]["grad_rtol"]
    rounded = jax.tree.map(
        lambda p: p.astype(jnp.float8_e4m3fn).astype(p.dtype)
        if p.ndim >= 2 else p, params)
    with HIGHEST():
        _, ref_grads = reference(params, aux, *batch)
        _, grads = reference(rounded, aux, *batch)
    errs = jax.tree.leaves(jax.tree.map(rel, grads, ref_grads))
    assert float(np.median(errs)) > bound
    assert sum(e > bound for e in errs) > share * len(errs)
    assert sampled(errs, bound) <= passes


test_float8_rounded_matrices_fail_the_bounds.needs = "f8"


def test_atc_on_four_devices_is_w_times_the_handwritten_update(twin, devices):
    """``bf.init`` + ``bf.rank_map`` + ``DistributedAdaptThenCombineOptimizer``
    over the twin's own base optimizer on four CPU devices, two steps on the
    twin's tree from seeded values that differ by rank, against ``W_t @`` the
    update written out in ``reference/optim_*.py``: the benchmark's own
    ``step`` check."""
    from benchmark import checks
    from benchmark.build import Job
    row = TWINS[twin]
    config, task, _ = load(twin)
    cell = spec.Cell(
        name=twin, chips=1, config_name=twin, traffic_name=row["traffic"],
        config=config, platform="cpu", traffic=spec.read_json(os.path.join(
            spec.HERE, "selftest", "traffic", row["traffic"] + ".json")))
    assert cell.traffic["order"] == "atc"
    job = Job(cell, task, devices[:4], row["seed"])
    assert job.n == 4
    for path in row["has"]:
        assert _at(job.params, path) is not None, path
    report = checks.step(job, spec.optimizer_reference(cell),
                         spec.mixing_reference(cell))
    assert report["leaves"] == len(jax.tree.leaves(job.params))
    assert report["worst_share_of_update"] <= checks.STEP_TOL
    loss, grads = job.grad(job.next_batch())
    assert np.asarray(loss).shape == (4,) and np.isfinite(loss).all()
    assert jax.tree.structure(grads) == jax.tree.structure(job.params)


test_atc_on_four_devices_is_w_times_the_handwritten_update.needs = "traffic"


def test_the_shares_add_up_to_the_uncut_layer(twin):
    """The twin's expert layer in shares of as many experts as the twin
    holds, a shared expert (where it has one) counted once, gives the
    reference's layer told that it holds them all; and each share alone what
    the reference gives for that share."""
    row = TWINS[twin]
    config, task, ref = load(twin)
    key = jax.random.PRNGKey(row["seed"])
    width, count = config["router_width"], config[row["held"]]

    def cut(held, first):
        return dict(config, **{row["held"]: held}, experts_first=first)

    def layer(held, first):
        return T.DroplessMoe(task.make_model(
            with_dtype(cut(held, first), "float32")).cfg)

    y = normal(key, 22, (2, 40, config["hidden_size"]))
    variables = layer(width, 0).init(key, y)
    params = jax.tree.map(
        lambda p: p + 0.1 * normal(key, p.size, p.shape), variables["params"])
    bias = normal(key, 23, (width,), 0.1)
    state = {"router_state": {"bias": bias}} \
        if "router_state" in variables else {}
    stacked = [k for k in ("gate", "up", "down") if k in params]

    def mine(first, p):
        return jax.jit(lambda p, y: layer(count, first).apply(
            {"params": p, **state}, y, mutable=["intermediates"])[0])(p, y)

    with HIGHEST():
        want = jax.jit(lambda p, y: row["experts"](
            ref, y, p, bias, cut(width, 0)))(params, y)
        parts = []
        for first in range(0, width, count):
            share = dict(params, **{k: params[k][first:first + count]
                                    for k in stacked})
            parts.append(mine(first, share))
            np.testing.assert_allclose(
                parts[-1], jax.jit(lambda p, y: row["experts"](  # noqa: B023
                    ref, y, p, bias, cut(count, first)))(share, y),
                rtol=1e-5, atol=2e-5)
        # what every share adds beside its experts: their ``down`` at zero
        shared = mine(0, dict(share, down=jnp.zeros_like(share["down"])))
    total = sum(p - shared for p in parts) + shared
    np.testing.assert_allclose(total, want, rtol=1e-5, atol=2e-5)
    assert float(jnp.abs(parts[0] - parts[1]).max()) > 1e-3


test_the_shares_add_up_to_the_uncut_layer.needs = "held"


# sha256 of ``str(jax.make_jaxpr(value_and_grad(loss)))`` (addresses cut) of
# the tiny twins, taken at the parent of PR 34 (commit 739c0c9) with
# ``benchmark.spec``'s own task and configuration files; the cells' own
# programs were compared at their full shapes the same way (``CHANGES.md``).
# A PR that means to change one of these programs replaces its digest: PR 37
# replaced the three that run the flash kernels (their tile bodies and the
# ``jax.jit`` around each call), PR 41 the four that run ``apply_rope`` (a
# product with a constant half-swap and a written transpose where two
# half-width slices and a concatenate were; ``tests/test_rope.py`` holds the
# values to the bit).  ``tiny-resnet`` is as at 739c0c9; ``tiny-laguna`` was
# taken at the parent of PR 45 (commit 9d5d981), and so was ``tiny-twotower``
# until PR 49 replaced it (the Mamba-2 mixer's gate and grouped norm are the
# kernels of ``ops/gated_norm.py`` where float32 ``jax.numpy`` was; the six
# others are untouched: none builds a ``Mamba2Mixer``).
PARENT_JAXPR = {
    ("tiny-lm", "causal_lm"): "a4de682606a8a8cd",
    ("tiny-olmoe", "moe_causal_lm"): "88f1130aabf52fc8",
    ("tiny-xing", "latent_moe_causal_lm"): "8b4108d454904332",
    ("tiny-resnet", "image_classification"): "cd85047ddb144986",
    ("tiny-lfm2", "hybrid_moe_causal_lm"): "c593f86020260dce",
    ("tiny-laguna", "window_moe_causal_lm"): "a9e7d4e84fdd23c8",
    ("tiny-twotower", "ssm_moe_causal_lm"): "066d40cd04bb55ee",
}


def grad_jaxpr_digest(config_name: str) -> str:
    config, task, _ = load(config_name)
    model = task.make_model(config)
    batch = ({"images": 2} if config["task"] == "image_classification"
             else {"sequences": 2, "seq_len": 128})

    def shapes(key):
        params, aux = task.init(model, key, config, batch)
        return params, aux, task.make_batch(key, config, batch)
    params, aux, one = jax.eval_shape(shapes, jax.random.PRNGKey(34))
    text = str(jax.make_jaxpr(jax.value_and_grad(
        task.loss_fn(model, config), has_aux=True))(params, aux, *one))
    return hashlib.sha256(
        re.sub(r"0x[0-9a-f]+", "0x", text).encode()).hexdigest()[:16]
