"""Sharded-aware gossip (``ops/sharded.py`` + ``BLUEFOG_TPU_SHARDED_GOSSIP``).

Planner unit tests (partition-spec -> gossip mask, per-group schedule
compilation, slice row extract/scatter, induced window weights), the
eager collective and window paths against dense / per-group oracles,
the bit-identity hatches (knob off, fully replicated tree) and the
per-shard telemetry split.  The slow bfrun leg drives a simulated
MoE tree across real processes and asserts replicated consensus with
experts mixing inside their replica group only.
"""

import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import PartitionSpec as P

import bluefog_tpu as bf
from bluefog_tpu import topology as topo
from bluefog_tpu.ops import schedule as S
from bluefog_tpu.ops import sharded as SH
from bluefog_tpu.utils import config

N = 8

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _tree(n=N, seed=0):
    rng = np.random.RandomState(seed)
    return {"a": jnp.asarray(rng.randn(n, 5), jnp.float32),
            "b": jnp.asarray(rng.randn(n, 4, 8), jnp.float32)}


SPECS = {"a": P(), "b": P(None, "tp")}


# ---------------------------------------------------------------------------
# Planner
# ---------------------------------------------------------------------------

def test_build_plan_mask_dims_fraction():
    tree = _tree()
    plan = SH.build_plan(tree, SPECS, n=N, n_shards=2)
    # tree-flatten order is alphabetical: a then b.
    assert plan.mask == (False, True)
    assert plan.dims == (None, 1)
    assert plan.any_sharded
    assert plan.n_shards == 2
    assert plan.groups == ((0, 1, 2, 3), (4, 5, 6, 7))
    # bytes: a = 5 f32, b = 32 f32 per rank row.
    assert plan.rep_bytes == N * 5 * 4
    assert plan.sh_bytes == N * 32 * 4
    assert abs(plan.replicated_fraction - 5 / 37) < 1e-12
    assert plan.decisions == ("replicated", "sharded(dim=1)")


def test_build_plan_signature_keys_cache():
    tree = _tree()
    p1 = SH.build_plan(tree, SPECS, n=N, n_shards=2)
    p2 = SH.build_plan(tree, SPECS, n=N, n_shards=2)
    assert p1.signature == p2.signature
    assert hash(p1.signature) == hash(p2.signature)
    p3 = SH.build_plan(tree, {"a": P(), "b": P()}, n=N, n_shards=2)
    assert p3.signature != p1.signature


def test_build_plan_indivisible_falls_back_to_replicated():
    tree = {"w": jnp.zeros((N, 7, 3), jnp.float32)}
    plan = SH.build_plan(tree, {"w": P("ep", None)}, n=N, n_shards=2)
    assert plan.mask == (False,)
    assert not plan.any_sharded
    assert "indivisible" in plan.decisions[0]
    assert plan.replicated_fraction == 1.0


def test_build_plan_requires_grouping_when_sharded():
    tree = _tree()
    with pytest.raises(ValueError, match="n_shards"):
        SH.build_plan(tree, SPECS, n=N)


def test_build_plan_keeps_groups_for_all_replicated_tree():
    """An all-replicated plan under explicit groups still classifies
    edges by those groups — the smoke's DCN ratio baseline."""
    tree = {"a": jnp.zeros((N, 3), jnp.float32)}
    plan = SH.build_plan(tree, {"a": P()}, n=N, n_shards=2)
    assert not plan.any_sharded
    assert plan.groups == ((0, 1, 2, 3), (4, 5, 6, 7))


def test_default_groups_and_validation():
    assert SH.default_groups(8, 4) == ((0, 1), (2, 3), (4, 5), (6, 7))
    with pytest.raises(ValueError):
        SH.default_groups(8, 3)
    with pytest.raises(ValueError):  # not a partition of range(n)
        SH.build_plan(_tree(), SPECS, n=N, groups=((0, 1), (1, 2)))


def test_group_schedules_never_cross_groups():
    groups = SH.default_groups(N, 2)
    merged, per_group = SH.compile_group_schedules(N, groups)
    coords = tuple(0 if r < 4 else 1 for r in range(N))
    gsets = [set(g) for g in groups]
    for rnd in merged.rounds:
        for (s, d) in rnd.pairs:
            assert any(s in g and d in g for g in gsets), (s, d)
    assert len(per_group) == 2
    assert per_group[0][0] == (0, 1, 2, 3)
    # merged rounds = max over groups (round r of every group merges).
    assert len(merged.rounds) == max(
        len(sub.rounds) for _g, sub in per_group)
    ici, dcn = SH.edge_level_counts(coords, merged)
    assert dcn == 0.0 and ici > 0


def test_edge_level_counts_exp2_8():
    coords = tuple(0 if r < 4 else 1 for r in range(N))
    sched = S.compile_static(topo.ExponentialTwoGraph(N))
    ici, dcn = SH.edge_level_counts(coords, sched)
    assert (ici, dcn) == (10.0, 14.0)


def test_own_shard_rows_roundtrip():
    rng = np.random.RandomState(3)
    leaf = rng.randn(N, 4, 8).astype(np.float32)
    coords = tuple(0 if r < 4 else 1 for r in range(N))
    rows = SH.own_shard_rows(leaf, 1, coords, 2)
    assert rows.shape == (N, 4 * 4)
    for r in range(N):
        c = coords[r]
        np.testing.assert_array_equal(
            rows[r], leaf[r, :, c * 4:(c + 1) * 4].ravel())
    back = SH.scatter_shard_rows(leaf, rows, 1, coords, 2)
    np.testing.assert_array_equal(back, leaf)


def test_induced_window_weights_in_group_only():
    plan = SH.build_plan(_tree(), SPECS, n=N, n_shards=2)
    put_edges, self_w, nbr_w = SH.induced_window_weights(
        plan, topo.ExponentialTwoGraph(N))
    gsets = [set(g) for g in plan.groups]
    for (s, d) in put_edges:
        assert any(s in g and d in g for g in gsets), (s, d)
    indeg = np.zeros(N)
    for (d, _s) in nbr_w:
        indeg[d] += 1
    np.testing.assert_allclose(self_w, 1.0 / (indeg + 1))
    for (d, s), w in nbr_w.items():
        assert w == self_w[d]


# ---------------------------------------------------------------------------
# Eager collective path
# ---------------------------------------------------------------------------

def test_collective_dense_oracle_and_ghost_isolation():
    bf.init(lambda: topo.ExponentialTwoGraph(N))
    params = _tree()
    grads = jax.tree.map(jnp.zeros_like, params)
    opt = bf.optim.DistributedNeighborAllreduceOptimizer(
        optax.sgd(0.0), shard_specs=SPECS, num_shards=2)
    out, _ = opt.step(params, grads, opt.init(params))

    W = topo.weight_matrix(bf.load_topology())
    exp_a = W.T @ np.asarray(params["a"])
    assert np.abs(np.asarray(out["a"]) - exp_a).max() < 1e-6

    plan = opt._shard_plan(params)
    _m, per = SH.compile_group_schedules(N, plan.groups)
    Wg = np.zeros((N, N))
    for g, _sub in per:
        sw = topo.weight_matrix(topo.ExponentialTwoGraph(len(g)))
        for i, gi in enumerate(g):
            for j, gj in enumerate(g):
                Wg[gi, gj] = sw[i, j]
    b0, b1 = np.asarray(params["b"]), np.asarray(out["b"])
    for r in range(N):
        c = plan.coords[r]
        own = b0[:, :, c * 4:(c + 1) * 4]
        exp = np.einsum("s,s...->...", Wg[:, r], own)
        assert np.abs(b1[r, :, c * 4:(c + 1) * 4] - exp).max() < 1e-6, r
        # Ghost region (the other coordinate's chunk) is bit-untouched.
        o = 1 - c
        np.testing.assert_array_equal(
            b1[r, :, o * 4:(o + 1) * 4], b0[r, :, o * 4:(o + 1) * 4])


def test_collective_fully_replicated_bitwise_knob_both_ways(monkeypatch):
    bf.init(lambda: topo.ExponentialTwoGraph(N))
    params = _tree()
    grads = jax.tree.map(jnp.zeros_like, params)

    def drive(specs=None, num_shards=None):
        opt = bf.optim.DistributedNeighborAllreduceOptimizer(
            optax.sgd(0.0), shard_specs=specs, num_shards=num_shards)
        out, _ = opt.step(params, grads, opt.init(params))
        return out

    base = drive()
    allrep = drive({"a": P(), "b": P()}, 2)
    monkeypatch.setenv("BLUEFOG_TPU_SHARDED_GOSSIP", "0")
    config.reload()
    try:
        off = drive(SPECS, 2)
    finally:
        monkeypatch.delenv("BLUEFOG_TPU_SHARDED_GOSSIP")
        config.reload()
    for k in base:
        np.testing.assert_array_equal(np.asarray(allrep[k]),
                                      np.asarray(base[k]), err_msg=k)
        np.testing.assert_array_equal(np.asarray(off[k]),
                                      np.asarray(base[k]), err_msg=k)


def test_gradient_allreduce_rejects_shard_specs():
    bf.init(lambda: topo.ExponentialTwoGraph(N))
    with pytest.raises(ValueError, match="shard"):
        bf.optim.DistributedGradientAllreduceOptimizer(
            optax.sgd(0.1), shard_specs=SPECS, num_shards=2)


def test_shard_telemetry_labels(monkeypatch):
    from bluefog_tpu.utils import telemetry
    monkeypatch.setenv("BLUEFOG_TPU_TELEMETRY", "1")
    config.reload()
    try:
        bf.init(lambda: topo.ExponentialTwoGraph(N))
        telemetry.reset()
        params = _tree()
        grads = jax.tree.map(jnp.zeros_like, params)
        opt = bf.optim.DistributedNeighborAllreduceOptimizer(
            optax.sgd(0.0), shard_specs=SPECS, num_shards=2)
        state = opt.init(params)
        steps = 2
        p = params
        for _ in range(steps):
            p, state = opt.step(p, grads, state)
        snap = telemetry.snapshot()
        rep_row = 5 * 4  # leaf a: 5 f32 per rank row
        sh_row = 32 * 4 / 2  # leaf b: own slice rows
        key = 'bf_comm_level_bytes_total{level="%s",shard="%s"}'
        assert snap[key % ("dcn", "replicated")] == rep_row * 14 * steps
        assert snap[key % ("ici", "replicated")] == rep_row * 10 * steps
        assert snap[key % ("ici", "sharded")] == sh_row * 16 * steps
        # A sharded byte on the DCN is a planner regression.
        assert key % ("dcn", "sharded") not in snap
    finally:
        monkeypatch.delenv("BLUEFOG_TPU_TELEMETRY")
        config.reload()


# ---------------------------------------------------------------------------
# Eager window path
# ---------------------------------------------------------------------------

def test_window_sharded_in_group_oracle():
    bf.init(lambda: topo.ExponentialTwoGraph(N))
    params = _tree(seed=1)
    grads = jax.tree.map(jnp.zeros_like, params)
    opt = bf.optim.DistributedWinPutOptimizer(
        optax.sgd(0.0), shard_specs=SPECS, num_shards=2)
    state = opt.init(params)
    assert opt._names == ["winput.fused", "winput.sharded"]
    out, _ = opt.step(params, grads, state)

    W = topo.weight_matrix(bf.load_topology())
    exp_a = W.T @ np.asarray(params["a"])
    assert np.abs(np.asarray(out["a"]) - exp_a).max() < 1e-5

    plan = opt._shard_plan
    _pe, self_w, nbr_w = SH.induced_window_weights(
        plan, bf.load_topology())
    b0, b1 = np.asarray(params["b"]), np.asarray(out["b"])
    for r in range(N):
        c = plan.coords[r]
        own = b0[:, :, c * 4:(c + 1) * 4]
        exp = self_w[r] * own[r]
        for (d, s), w in nbr_w.items():
            if d == r:
                exp = exp + w * own[s]
        assert np.abs(b1[r, :, c * 4:(c + 1) * 4] - exp).max() < 1e-5, r
        o = 1 - c
        np.testing.assert_array_equal(
            b1[r, :, o * 4:(o + 1) * 4], b0[r, :, o * 4:(o + 1) * 4])
    opt.free()


def test_window_fully_replicated_bitwise():
    bf.init(lambda: topo.ExponentialTwoGraph(N))
    params = _tree(seed=1)
    grads = jax.tree.map(jnp.zeros_like, params)
    o1 = bf.optim.DistributedWinPutOptimizer(
        optax.sgd(0.0), window_prefix="w1",
        shard_specs={"a": P(), "b": P()}, num_shards=2)
    p1, _ = o1.step(params, grads, o1.init(params))
    o1.free()
    o2 = bf.optim.DistributedWinPutOptimizer(
        optax.sgd(0.0), window_prefix="w2")
    p2, _ = o2.step(params, grads, o2.init(params))
    o2.free()
    for k in p1:
        np.testing.assert_array_equal(np.asarray(p1[k]),
                                      np.asarray(p2[k]), err_msg=k)


# ---------------------------------------------------------------------------
# Multi-process MoE convergence (slow)
# ---------------------------------------------------------------------------

_MOE_SCRIPT = r"""
import sys
sys.path.insert(0, "@REPO@")
import jax
jax.config.update("jax_platforms", "cpu")
import jax.numpy as jnp
import numpy as np
import optax
import bluefog_tpu as bf
from jax.sharding import PartitionSpec as P

bf.init_distributed()
n = bf.size()
assert n == 8, n
rng = np.random.RandomState(11)
# Simulated MoE transformer block: replicated attention + router,
# 2-way expert-sharded FFN. Groups: ranks 0-3 hold expert slice 0,
# ranks 4-7 hold slice 1 — each group starts from its own expert
# values, and only in-group gossip may mix them.
params = {"attn": jnp.asarray(rng.randn(n, 16), jnp.float32),
          "experts": jnp.asarray(rng.randn(n, 4, 8), jnp.float32)}
grads = jax.tree.map(jnp.zeros_like, params)
opt = bf.optim.DistributedNeighborAllreduceOptimizer(
    optax.sgd(0.0), shard_specs={"attn": P(), "experts": P(None, "ep")},
    num_shards=2)
state = opt.init(params)
p = params
for _ in range(24):
    p, state = opt.step(p, grads, state)

attn = bf.to_numpy(p["attn"]) if hasattr(bf, "to_numpy") else np.asarray(p["attn"])
experts = bf.to_numpy(p["experts"]) if hasattr(bf, "to_numpy") else np.asarray(p["experts"])
a0 = np.asarray(params["attn"])
e0 = np.asarray(params["experts"])

# Replicated consensus: every rank converges to the global mean.
target = a0.mean(axis=0)
spread = np.abs(attn - target).max()
assert spread < 1e-3, f"replicated leaf did not reach consensus: {spread}"

# Sharded consensus is PER GROUP and per slice: each rank's own slice
# converges to its group's mean of that slice; the ghost slice is
# bit-untouched (still the initial values).
groups = [list(range(0, 4)), list(range(4, 8))]
for gi, g in enumerate(groups):
    for c, sl in ((gi, slice(gi * 4, gi * 4 + 4)),):
        tgt = e0[g][:, :, sl].mean(axis=0)
        for r in g:
            d = np.abs(experts[r, :, sl] - tgt).max()
            assert d < 1e-3, f"rank {r} slice {c}: {d}"
            other = slice((1 - gi) * 4, (1 - gi) * 4 + 4)
            np.testing.assert_array_equal(experts[r, :, other],
                                          e0[r, :, other])

# Cross-group isolation: the two groups' slice means stay DIFFERENT
# (nothing leaked across the expert boundary).
m0 = e0[0:4][:, :, 0:4].mean(axis=0)
m1 = e0[4:8][:, :, 4:8].mean(axis=0)
assert np.abs(m0 - m1).max() > 1e-3
print("MOE_SHARDED_OK")
"""


@pytest.mark.slow
def test_multiprocess_moe_sharded_convergence(tmp_path):
    script = tmp_path / "prog.py"
    script.write_text(_MOE_SCRIPT.replace("@REPO@", REPO))
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    out = subprocess.run(
        [sys.executable, "-m", "bluefog_tpu.run", "-np", "2",
         "--devices-per-proc", "4", sys.executable, str(script)],
        capture_output=True, text=True, timeout=600, cwd=REPO, env=env)
    assert out.returncode == 0, \
        f"stdout={out.stdout}\nstderr={out.stderr[-4000:]}"
    assert "MOE_SHARDED_OK" in out.stdout
