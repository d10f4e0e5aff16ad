"""CPU rehearsal of ``chip_smoke.py``: its leg functions at toy sizes on 4
virtual devices, with the Pallas kernels in interpret mode (passed
explicitly — the script itself has no CPU mode), plus the compile-cache
helper ``bf.init()`` calls."""

import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

import bluefog_tpu as bf
from bluefog_tpu import basics, models

import chip_smoke

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def four_ranks(devices):
    bf.init(devices=devices[:4])
    return 4


@pytest.fixture(scope="module")
def trained(devices):
    """One toy trainer run shared by the checks that inspect it (the
    autouse reset tears the context down between tests; the arrays and the
    compiled programs outlive it)."""
    bf.init(devices=devices[:4])
    return chip_smoke.trainer_leg(
        model=models.ResNet18(num_classes=10, dtype=jnp.float32),
        image=32, batch=4, classes=10, steps=8)


def test_trainer_leg_loss_finite_and_falling(trained):
    assert len(trained["losses"]) == 8
    assert trained["losses"][-1] < trained["losses"][0]


def test_every_leaf_one_rank_row_per_device(trained, four_ranks):
    assert chip_smoke.check_placement(trained["trees"]) > 60
    # and the check is not vacuous: a tree gathered onto one device fails it
    one_device = jax.device_put(
        jax.tree_util.tree_leaves(trained["trees"])[0], jax.devices()[0])
    with pytest.raises(AssertionError, match="of 4 devices"):
        chip_smoke.check_placement([one_device])


def test_hlo_collective_counts(trained, four_ranks):
    counts = chip_smoke.check_programs(trained["grad_program"],
                                       trained["step_program"])
    assert not any(counts["grad"].values())
    assert counts["step"]["collective-permute"] >= 1


def test_jit_vmap_gradient_would_fail_the_hlo_check(four_ranks):
    """What the check is for: the SPMD partitioner splits ``jit(vmap(grad))``
    of a conv model with all-gathers; ``bf.rank_map`` has none."""
    model = models.LeNet5()
    x = jnp.zeros((4, 2, 28, 28, 1))
    params = bf.rank_map(lambda: model.init(jax.random.PRNGKey(0), x[0]))()
    loss = lambda p, x: model.apply(p, x).sum()  # noqa: E731
    x = basics._place(x)
    vmapped = jax.jit(jax.vmap(jax.grad(loss))).lower(params, x).compile()
    mapped = bf.rank_map(jax.grad(loss)).lower(params, x).compile()
    assert chip_smoke.collective_counts(vmapped)["all-gather"] > 0
    assert not any(chip_smoke.collective_counts(mapped).values())


def test_neighbor_allreduce_equals_mixing_matrix_product(four_ranks):
    assert chip_smoke.check_mixing() >= 1
    bf.set_topology(bf.topology_util.RingGraph(4), is_weighted=True)
    assert chip_smoke.check_mixing() >= 1


def test_flash_leg_interpreted():
    out = chip_smoke.flash_leg((1, 128, 2, 32), interpret=True, block=64)
    assert out["fwd_err"] <= chip_smoke.FLASH_TOL_FWD


def test_lm_leg_interpreted(four_ranks):
    out = chip_smoke.lm_leg(interpret=True, width=64, heads=2, seq=64,
                            vocab=128, layers=1, batch=2, steps=3)
    assert len(out["losses"]) == 3


def test_ring_leg_interpreted(four_ranks):
    out = chip_smoke.ring_leg(compiled=False, seq_per_chip=32, heads=2,
                              head_dim=16)
    assert out["err"] <= chip_smoke.RING_TOL


def test_main_exits_nonzero_without_a_tpu():
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py")],
        env={**os.environ, "JAX_PLATFORMS": "cpu"}, cwd=REPO,
        capture_output=True, text=True, timeout=120)
    assert out.returncode != 0
    assert '"ok"' not in out.stdout and "no TPU" in out.stderr


# -- the compile-cache helper ------------------------------------------------

class _TpuDevice:
    platform = "tpu"


@pytest.fixture
def cache_updates(monkeypatch):
    """Record ``jax.config.update`` calls instead of applying them, and make
    the mesh look like TPUs."""
    updates = []
    monkeypatch.setattr(jax.config, "update",
                        lambda k, v: updates.append((k, v)))
    monkeypatch.setattr(basics._ctx, "devices", [_TpuDevice()])
    return updates


def test_cache_dir_from_env_sets_nothing_in_code(cache_updates, monkeypatch):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/x")
    basics._configure_compile_cache()
    assert cache_updates == []


def test_cache_dir_defaults_to_checkout(cache_updates, monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    basics._configure_compile_cache()
    assert cache_updates == [
        ("jax_compilation_cache_dir", os.path.join(REPO, ".jax_cache"))]


def test_cpu_mesh_writes_no_cache_entry(four_ranks, monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    assert jax.config.jax_compilation_cache_dir is None
    bf.neighbor_allreduce(jnp.ones((4, 8)))
    cache = os.path.join(REPO, ".jax_cache")
    assert not os.path.isdir(cache) or not os.listdir(cache)
