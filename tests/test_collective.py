"""Collective op tests on an 8-device virtual mesh.

Case inventory mirrors reference ``test/torch_ops_test.py``: broadcast(:71),
allreduce(:136-209), allgather(:285), neighbor_allreduce static/dynamic
(:365-1022), neighbor_allgather(:1023), pair_gossip(:1067).  Oracles are
closed-form expected averages computed from the weight matrix.
"""

import numpy as np
import pytest

import bluefog_tpu as bf
from bluefog_tpu import topology as topo
from bluefog_tpu.ops import schedule as S


N = 8


@pytest.fixture(autouse=True)
def _init():
    bf.init()
    yield
    bf.shutdown()


def rank_tensors(shape=(4,), dtype=np.float32):
    """x[i] = i (the reference's standard per-rank fill)."""
    return np.stack([np.full(shape, i, dtype) for i in range(N)])


def test_size_rank():
    assert bf.size() == N
    assert bf.initialized()
    assert bf.local_size() == N
    assert bf.machine_size() == 1


def test_allreduce_avg():
    x = rank_tensors()
    out = np.asarray(bf.allreduce(x))
    np.testing.assert_allclose(out, (N - 1) / 2.0, rtol=1e-6)


def test_allreduce_sum():
    x = rank_tensors()
    out = np.asarray(bf.allreduce(x, average=False))
    np.testing.assert_allclose(out, N * (N - 1) / 2.0, rtol=1e-6)


@pytest.mark.parametrize("root", [0, 3, 7])
def test_broadcast(root):
    x = rank_tensors((2, 3))
    out = np.asarray(bf.broadcast(x, root))
    np.testing.assert_allclose(out, root)


def test_allgather():
    x = rank_tensors((2,))
    out = np.asarray(bf.allgather(x))
    assert out.shape == (N, N * 2)
    expected = np.repeat(np.arange(N), 2).astype(np.float32)
    for i in range(N):
        np.testing.assert_allclose(out[i], expected)


def _expected_neighbor_allreduce(x, w):
    """out[dst] = sum_src w[src, dst] * x[src] (incl. diagonal)."""
    return np.einsum("sd,s...->d...", w, x)


@pytest.mark.parametrize("graph_fn", [
    lambda: topo.RingGraph(N, 0),
    lambda: topo.ExponentialTwoGraph(N),
    lambda: topo.StarGraph(N),
    lambda: topo.MeshGrid2DGraph(N),
])
def test_neighbor_allreduce_weighted(graph_fn):
    G = graph_fn()
    bf.set_topology(G, is_weighted=True)
    x = rank_tensors((3,))
    out = np.asarray(bf.neighbor_allreduce(x))
    expected = _expected_neighbor_allreduce(x, topo.weight_matrix(G))
    np.testing.assert_allclose(out, expected, rtol=1e-5)


def test_neighbor_allreduce_uniform_default():
    """is_weighted=False -> uniform 1/(indeg+1), reference default."""
    G = topo.RingGraph(N, 0)
    bf.set_topology(G, is_weighted=False)
    x = rank_tensors((3,))
    out = np.asarray(bf.neighbor_allreduce(x))
    w = S.uniform_weights(topo.weight_matrix(G))
    np.testing.assert_allclose(
        out, _expected_neighbor_allreduce(x, w), rtol=1e-5)
    # ring: avg of (i-1, i, i+1)/3 except wrap ranks
    np.testing.assert_allclose(out[3], (2 + 3 + 4) / 3.0, rtol=1e-5)


def test_neighbor_allreduce_matrix_override():
    bf.set_topology(topo.RingGraph(N, 2))  # right ring: i -> i+1
    w = np.zeros((N, N))
    for i in range(N):
        w[i, (i + 1) % N] = 0.25
        w[i, i] = 0.75
    x = rank_tensors((2,))
    out = np.asarray(bf.neighbor_allreduce(x, src_weights=w))
    np.testing.assert_allclose(
        out, _expected_neighbor_allreduce(x, w), rtol=1e-5)
    np.testing.assert_allclose(out[3], 0.75 * 3 + 0.25 * 2, rtol=1e-5)


def test_neighbor_allreduce_preserves_mean_doubly_stochastic():
    G = topo.MeshGrid2DGraph(N)  # symmetric MH weights => doubly stochastic
    bf.set_topology(G, is_weighted=True)
    rng = np.random.default_rng(0)
    x = rng.normal(size=(N, 5)).astype(np.float32)
    out = np.asarray(bf.neighbor_allreduce(x))
    np.testing.assert_allclose(out.mean(axis=0), x.mean(axis=0), atol=1e-5)


def test_consensus_convergence():
    """Repeated neighbor averaging converges to the global mean — the
    reference's pytorch_average_consensus.py e2e config."""
    G = topo.ExponentialTwoGraph(N)
    bf.set_topology(G, is_weighted=True)
    rng = np.random.default_rng(1)
    x = rng.normal(size=(N, 4)).astype(np.float32)
    target = x.mean(axis=0)
    cur = x
    for _ in range(50):
        cur = np.asarray(bf.neighbor_allreduce(cur))
    np.testing.assert_allclose(cur, np.broadcast_to(target, cur.shape), atol=1e-4)


def test_dynamic_neighbor_allreduce_one_peer():
    """One-peer dynamic Exp2: each step out = (x[i] + x[i - 2^k]) / 2."""
    G = topo.ExponentialTwoGraph(N)
    bf.set_topology(G)
    x = rank_tensors((2,))
    for step in range(6):
        out = np.asarray(bf.dynamic_neighbor_allreduce(x, step))
        d = 2 ** (step % 3)
        for i in range(N):
            expected = (x[i] + x[(i - d) % N]) / 2.0
            np.testing.assert_allclose(out[i], expected, rtol=1e-5)


def test_dynamic_consensus_convergence():
    """Dynamic one-peer Exp2 reaches exact consensus in log2(N) steps when
    walking distances 1,2,4 (the Exp2 mixing property)."""
    bf.set_topology(topo.ExponentialTwoGraph(N))
    rng = np.random.default_rng(2)
    cur = rng.normal(size=(N, 3)).astype(np.float32)
    target = cur.mean(axis=0)
    for step in range(12):
        cur = np.asarray(bf.dynamic_neighbor_allreduce(cur, step))
    np.testing.assert_allclose(cur, np.broadcast_to(target, cur.shape), atol=1e-4)


def test_neighbor_allgather():
    G = topo.RingGraph(N, 0)
    bf.set_topology(G)
    x = rank_tensors((2,))
    out = np.asarray(bf.neighbor_allgather(x))
    assert out.shape == (N, 2, 2)  # (rank, indegree, *shape)
    for i in range(N):
        srcs = sorted([(i - 1) % N, (i + 1) % N])
        for k, s in enumerate(srcs):
            np.testing.assert_allclose(out[i, k], s)


def test_neighbor_allgather_irregular_padding():
    G = topo.StarGraph(N)
    bf.set_topology(G)
    x = rank_tensors((2,))
    out = np.asarray(bf.neighbor_allgather(x))
    assert out.shape == (N, N - 1, 2)  # center indegree N-1
    # center (rank 0) receives 1..N-1 in order
    for k in range(N - 1):
        np.testing.assert_allclose(out[0, k], k + 1)
    # leaf rank 3 receives only rank 0, rest zero-padded
    np.testing.assert_allclose(out[3, 0], 0.0)
    np.testing.assert_allclose(out[3, 1:], 0.0)


def test_pair_gossip():
    x = rank_tensors((2,))
    # pair i <-> i^1 (0-1, 2-3, ...)
    targets = [i ^ 1 for i in range(N)]
    out = np.asarray(bf.pair_gossip(x, targets))
    for i in range(N):
        np.testing.assert_allclose(out[i], (i + (i ^ 1)) / 2.0, rtol=1e-5)


def test_pair_gossip_partial_and_weighted():
    x = rank_tensors((2,))
    targets = [1, 0] + [-1] * (N - 2)
    out = np.asarray(bf.pair_gossip(x, targets, self_weight=0.75,
                                    target_weight=0.25))
    np.testing.assert_allclose(out[0], 0.75 * 0 + 0.25 * 1, rtol=1e-5)
    np.testing.assert_allclose(out[1], 0.75 * 1 + 0.25 * 0, rtol=1e-5)
    np.testing.assert_allclose(out[5], 5.0)


def test_nonblocking_handles():
    x = rank_tensors()
    h = bf.allreduce_nonblocking(x)
    out = bf.synchronize(h)
    assert bf.poll(h)
    np.testing.assert_allclose(np.asarray(out), (N - 1) / 2.0, rtol=1e-6)
    bf.barrier()


def test_broadcast_parameters():
    params = {"w": rank_tensors((3,)), "b": rank_tensors((1,))}
    out = bf.broadcast_parameters(params, root_rank=2)
    np.testing.assert_allclose(np.asarray(out["w"]), 2.0)
    np.testing.assert_allclose(np.asarray(out["b"]), 2.0)


def test_set_topology_validation():
    with pytest.raises(ValueError):
        bf.set_topology(topo.RingGraph(N + 1))


def test_bfloat16_neighbor_allreduce():
    import jax.numpy as jnp
    bf.set_topology(topo.ExponentialTwoGraph(N), is_weighted=True)
    x = jnp.asarray(rank_tensors((4,))).astype(jnp.bfloat16)
    out = bf.neighbor_allreduce(x)
    assert out.dtype == jnp.bfloat16
    w = topo.weight_matrix(topo.ExponentialTwoGraph(N))
    expected = _expected_neighbor_allreduce(rank_tensors((4,)), w)
    np.testing.assert_allclose(np.asarray(out, dtype=np.float32), expected,
                               atol=0.1)


# ---------------------------------------------------------------------------
# dtype grid (reference torch_ops_test.py runs every collective x dtype,
# e.g. :136-209 allreduce over the self.dtypes list)
# ---------------------------------------------------------------------------

_FLOAT_DTYPES = [np.float32, np.float16, "bfloat16"]
_INT_DTYPES = [np.int32, np.uint8]


def _mk(dtype):
    import jax.numpy as jnp
    return jnp.asarray(rank_tensors((4,), np.float32)).astype(dtype)


def _name(dtype) -> str:
    return "bfloat16" if dtype == "bfloat16" else np.dtype(dtype).name


@pytest.mark.parametrize("dtype", _FLOAT_DTYPES + _INT_DTYPES)
def test_broadcast_dtype_grid(dtype):
    x = _mk(dtype)
    out = bf.broadcast(x, root_rank=3)
    assert str(out.dtype) == _name(dtype)
    got = np.asarray(out.astype("float32"))
    np.testing.assert_allclose(got, np.full((N, 4), 3.0))


@pytest.mark.parametrize("dtype", _FLOAT_DTYPES + _INT_DTYPES)
def test_allreduce_sum_dtype_grid(dtype):
    x = _mk(dtype)
    out = bf.allreduce(x, average=False)
    assert str(out.dtype) == _name(dtype)
    got = np.asarray(out.astype("float32"))
    np.testing.assert_allclose(got, np.full((N, 4), sum(range(N))))


@pytest.mark.parametrize("dtype", _FLOAT_DTYPES + _INT_DTYPES)
def test_allgather_dtype_grid(dtype):
    x = _mk(dtype)
    out = bf.allgather(x)
    assert out.shape == (N, N * 4)
    got = np.asarray(out.astype("float32"))
    expected = np.repeat(np.arange(N, dtype=np.float32), 4)[None].repeat(N, 0)
    np.testing.assert_allclose(got, expected)


@pytest.mark.parametrize("dtype", _FLOAT_DTYPES)
def test_neighbor_allreduce_dtype_grid(dtype):
    """Weighted averaging: float dtypes only (as in the reference, where the
    weighted path requires floating tensors, torch/mpi_ops.py:433-489)."""
    x = _mk(dtype)
    out = bf.neighbor_allreduce(x)
    assert str(out.dtype) == _name(dtype)
    got = np.asarray(out.astype("float32"))
    x = rank_tensors((4,))
    # default init: unweighted topology -> uniform 1/(indeg+1) combine
    w = np.zeros((N, N))
    for dst in range(N):
        nbrs = bf.in_neighbor_ranks(dst) + [dst]
        w[nbrs, dst] = 1.0 / len(nbrs)
    expected = _expected_neighbor_allreduce(x, w)
    tol = 5e-2 if dtype != np.float32 else 1e-5
    np.testing.assert_allclose(got, expected, atol=tol)


@pytest.mark.parametrize("dtype", _FLOAT_DTYPES)
def test_pair_gossip_dtype_grid(dtype):
    x = _mk(dtype)
    targets = [(r + 1) % N if r % 2 == 0 else (r - 1) % N for r in range(N)]
    out = bf.pair_gossip(x, targets)
    got = np.asarray(out.astype("float32"))
    expected = np.stack([np.full(4, (r + targets[r]) / 2.0) for r in range(N)])
    np.testing.assert_allclose(got, expected, atol=2e-2)


@pytest.mark.parametrize("dim", [1, 2, 3])
@pytest.mark.parametrize("dtype", [np.float32, np.int32, np.float64])
def test_allgather_variable_size(dim, dtype):
    """Variable-first-dim allgather (reference
    ``torch_ops_test.py:321-364``): rank i contributes sizes[i] rows filled
    with i; everyone receives the rank-ordered concatenation."""
    sizes = [17, 32, 81, 12, 15, 23, 22, 9][:N]
    tensors = [np.full([sizes[r]] + [17] * (dim - 1), r, dtype)
               for r in range(N)]
    out = np.asarray(bf.allgather_v(tensors))
    assert out.shape == (N, sum(sizes)) + (17,) * (dim - 1)
    for row in range(N):  # gather semantics: every rank sees the same
        off = 0
        for i in range(N):
            seg = out[row, off:off + sizes[i]]
            assert seg.shape == (sizes[i],) + (17,) * (dim - 1)
            assert seg.min() == i and seg.max() == i
            off += sizes[i]


def test_allgather_v_uniform_matches_allgather():
    x = rank_tensors((3, 2))
    out_v = np.asarray(bf.allgather_v(list(x)))
    out = np.asarray(bf.allgather(x))
    np.testing.assert_array_equal(out_v, out)


def test_allgather_v_validation():
    with pytest.raises(ValueError, match="one tensor per rank"):
        bf.allgather_v([np.zeros((2, 3))] * (N - 1))
    bad = [np.zeros((r + 1, 3), np.float32) for r in range(N)]
    bad[3] = np.zeros((2, 4), np.float32)  # trailing dim mismatch
    with pytest.raises(ValueError, match="FIRST dim may vary"):
        bf.allgather_v(bad)


def test_neighbor_allgather_variable_size():
    """Ragged neighbor allgather on a directed ring: each rank receives its
    single in-neighbor's variable-size tensor (reference
    ``MPI_Neighbor_allgatherv``, ``mpi_controller.cc:251-293``)."""
    bf.set_topology(topo.RingGraph(N, connect_style=1))  # edges i -> i-1
    sizes = [3, 7, 1, 5, 2, 8, 4, 6][:N]
    tensors = [np.full((sizes[r], 2), r, np.float32) for r in range(N)]
    out = bf.neighbor_allgather_v(tensors)
    assert len(out) == N
    for dst in range(N):
        src = (dst + 1) % N
        got = np.asarray(out[dst])
        assert got.shape == (sizes[src], 2)
        np.testing.assert_array_equal(got, np.full((sizes[src], 2), src))


def test_neighbor_allgather_v_multi_neighbor_ascending_order():
    """Undirected ring: two in-neighbors, concatenated ascending by src."""
    bf.set_topology(topo.RingGraph(N, connect_style=0))
    sizes = [3, 7, 1, 5, 2, 8, 4, 6][:N]
    tensors = [np.full((sizes[r],), float(r), np.float32) for r in range(N)]
    out = bf.neighbor_allgather_v(tensors)
    for dst in range(N):
        srcs = sorted([(dst - 1) % N, (dst + 1) % N])
        expected = np.concatenate(
            [np.full((sizes[s],), float(s), np.float32) for s in srcs])
        np.testing.assert_array_equal(np.asarray(out[dst]), expected)


def test_neighbor_allgather_v_zero_weight_edge():
    """A weighted topology with an explicit zero-weight edge sends nothing
    on it; the ragged gather's src attribution must use the same effective
    edge set as the compiled schedule (regression: slot misassignment)."""
    import networkx as nx
    G = nx.DiGraph()
    G.add_nodes_from(range(N))
    for i in range(N):
        G.add_edge(i, i, weight=0.5)
        G.add_edge((i + 1) % N, i, weight=0.5)   # real edge: src = i+1
        G.add_edge((i + 2) % N, i, weight=0.0)   # dead edge: src = i+2
    bf.set_topology(G, is_weighted=True)
    sizes = [3, 7, 1, 5, 2, 8, 4, 6][:N]
    tensors = [np.full((sizes[r],), float(r), np.float32) for r in range(N)]
    out = bf.neighbor_allgather_v(tensors)
    for dst in range(N):
        src = (dst + 1) % N
        np.testing.assert_array_equal(
            np.asarray(out[dst]),
            np.full((sizes[src],), float(src), np.float32))


def test_neighbor_allgather_v_zero_weight_edge_unweighted():
    """Same regression with is_weighted=False: the uniform schedule also
    drops zero-weight edges, so src attribution must too."""
    import networkx as nx
    G = nx.DiGraph()
    G.add_nodes_from(range(N))
    for i in range(N):
        G.add_edge(i, i, weight=0.5)
        G.add_edge((i + 1) % N, i, weight=0.5)
        G.add_edge((i + 2) % N, i, weight=0.0)  # dead edge
    bf.set_topology(G, is_weighted=False)
    sizes = [3, 7, 1, 5, 2, 8, 4, 6][:N]
    tensors = [np.full((sizes[r],), float(r), np.float32) for r in range(N)]
    out = bf.neighbor_allgather_v(tensors)
    for dst in range(N):
        src = (dst + 1) % N
        np.testing.assert_array_equal(
            np.asarray(out[dst]),
            np.full((sizes[src],), float(src), np.float32))


def test_owned_ranks_single_process():
    bf.init()
    assert bf.owned_ranks() == list(range(N))
    assert bf.rank() == bf.owned_ranks()[0]


def test_is_homogeneous_detects_uneven_placement():
    """Forged heterogeneous placement: uneven per-HOST device counts must
    flip is_homogeneous to False (the reference probes actual placement,
    mpi_controller.cc:71-96; round-2 review: the old check could never
    return False).  In bfrun slot mode every process owns ONE device, so
    the per-host aggregation — not per-process counts — carries the
    signal."""
    import types
    bf.init()
    assert bf.is_homogeneous()
    from bluefog_tpu import basics

    # bfrun -H host1:3,host2:5: 8 single-device processes, uneven hosts.
    basics._ctx.host_device_counts = {"host1": 3, "host2": 5}
    assert not bf.is_homogeneous()
    basics._ctx.host_device_counts = {"host1": 4, "host2": 4}
    assert bf.is_homogeneous()

    # Fallback path (no gathered placement): per-process device counts.
    basics._ctx.host_device_counts = None

    def stub(proc):
        return types.SimpleNamespace(process_index=proc)
    basics._ctx.devices = [stub(0)] * 3 + [stub(1)] * 5
    assert not bf.is_homogeneous()
    basics._ctx.devices = [stub(0)] * 4 + [stub(1)] * 4
    assert bf.is_homogeneous()


def test_owned_ranks_respects_forged_placement():
    import types
    bf.init()
    from bluefog_tpu import basics

    def stub(proc):
        return types.SimpleNamespace(process_index=proc)
    # jax.process_index() is 0 in this suite; ranks 2,5 owned by "us"
    basics._ctx.devices = [stub(1), stub(1), stub(0), stub(1), stub(1),
                           stub(0), stub(1), stub(1)]
    assert bf.owned_ranks() == [2, 5]
    assert bf.rank() == 2


def test_sparse_neighbor_allreduce_full_k_matches_dense(devices):
    """k == size: the sparse exchange is the dense neighbor averaging
    exactly (same schedule, same weights)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, PartitionSpec as P
    from bluefog_tpu.ops import collective as C
    from bluefog_tpu.ops import schedule as S
    from bluefog_tpu import topology as topo
    n, D = 8, 12
    sched = S.compile_static(topo.ExponentialTwoGraph(n),
                             use_topo_weights=False)
    x = jnp.asarray(np.random.RandomState(0).randn(n, D), jnp.float32)
    mesh = Mesh(np.asarray(devices), ("dp",))
    dense = jax.jit(jax.shard_map(
        lambda a: C.neighbor_allreduce(a, sched, "dp"), mesh=mesh,
        in_specs=P("dp"), out_specs=P("dp"), check_vma=False))(x)
    sparse = jax.jit(jax.shard_map(
        lambda a: C.sparse_neighbor_allreduce(a[0], sched, "dp", k=D)[None],
        mesh=mesh, in_specs=P("dp"), out_specs=P("dp"),
        check_vma=False))(x)
    np.testing.assert_allclose(np.asarray(sparse), np.asarray(dense),
                               rtol=1e-5, atol=1e-6)


def test_sparse_neighbor_allreduce_topk_semantics(devices):
    """k < size: the combine equals self_weight * x + the weighted scatter
    of each in-neighbor's top-k entries (manual oracle)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, PartitionSpec as P
    from bluefog_tpu.ops import collective as C
    from bluefog_tpu.ops import schedule as S
    from bluefog_tpu import topology as topo
    n, D, K = 8, 10, 3
    G = topo.RingGraph(n)
    sched = S.compile_static(G, use_topo_weights=False)
    rng = np.random.RandomState(1)
    x = rng.randn(n, D).astype(np.float32)

    def topk_dense(row):
        q = np.zeros_like(row)
        ix = np.argsort(-np.abs(row))[:K]
        q[ix] = row[ix]
        return q

    w = S.uniform_weights(topo.weight_matrix(G))
    # The combine runs ENTIRELY on the compressed reps (self term on q_i
    # too — the difference-compression wrapper needs row-stochastic W on q).
    expect = np.stack([
        w[i, i] * topk_dense(x[i])
        + sum(w[j, i] * topk_dense(x[j])
              for j in ((i - 1) % n, (i + 1) % n))
        for i in range(n)])

    mesh = Mesh(np.asarray(devices), ("dp",))
    out, q = jax.jit(jax.shard_map(
        lambda a: tuple(t[None] for t in C.sparse_neighbor_allreduce(
            a[0], sched, "dp", k=K, return_sent=True)),
        mesh=mesh, in_specs=P("dp"), out_specs=(P("dp"), P("dp")),
        check_vma=False))(jnp.asarray(x))
    np.testing.assert_allclose(np.asarray(out), expect, rtol=1e-5,
                               atol=1e-6)
    np.testing.assert_allclose(np.asarray(q),
                               np.stack([topk_dense(r) for r in x]),
                               rtol=1e-6)


def test_dynamic_sparse_neighbor_allreduce_full_k_matches_dense(devices):
    """Full index block (k == size): the dynamic sparse exchange equals
    the dense dynamic neighbor averaging at EVERY phase of the period,
    and the sent representation q equals x (zero residual)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, PartitionSpec as P
    from bluefog_tpu.ops import collective as C
    from bluefog_tpu.ops import schedule as S
    from bluefog_tpu import topology as topo
    n, D = 8, 12
    dyn = S.compile_dynamic(topo.one_peer_exp2_phases(n), n)
    x = jnp.asarray(np.random.RandomState(3).randn(n, D), jnp.float32)
    mesh = Mesh(np.asarray(devices), ("dp",))
    pos = jnp.arange(D, dtype=jnp.int32)
    for step in range(dyn.period * 2):
        t = jnp.asarray(step, jnp.int32)
        dense = jax.jit(jax.shard_map(
            lambda a: C.dynamic_neighbor_allreduce(a, t, dyn, "dp"),
            mesh=mesh, in_specs=P("dp"), out_specs=P("dp"),
            check_vma=False))(x)
        sparse, q = jax.jit(jax.shard_map(
            lambda a: tuple(r[None] for r in C.dynamic_sparse_neighbor_allreduce(
                a[0], t, dyn, "dp", indices=pos, return_sent=True)),
            mesh=mesh, in_specs=P("dp"), out_specs=(P("dp"), P("dp")),
            check_vma=False))(x)
        np.testing.assert_allclose(np.asarray(sparse), np.asarray(dense),
                                   rtol=1e-5, atol=1e-6)
        np.testing.assert_allclose(np.asarray(q), np.asarray(x), rtol=1e-6)


def test_dynamic_sparse_partial_block_oracle(devices):
    """k < size on a one-peer phase: the combine equals the dense one-peer
    averaging restricted to the aligned block; off-block coordinates carry
    0.5 * x_i (the self scale applied to q_i which is zero there)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, PartitionSpec as P
    from bluefog_tpu.ops import collective as C
    from bluefog_tpu.ops import schedule as S
    from bluefog_tpu import topology as topo
    n, D, K = 8, 12, 5
    dyn = S.compile_dynamic(topo.one_peer_exp2_phases(n), n)
    rng = np.random.RandomState(4)
    x = rng.randn(n, D).astype(np.float32)
    mesh = Mesh(np.asarray(devices), ("dp",))
    for step in range(dyn.period):
        pos_np = (np.arange(K) + step * K) % D
        pos = jnp.asarray(pos_np, jnp.int32)
        t = jnp.asarray(step, jnp.int32)
        out, q = jax.jit(jax.shard_map(
            lambda a: tuple(r[None] for r in C.dynamic_sparse_neighbor_allreduce(
                a[0], t, dyn, "dp", indices=pos, return_sent=True)),
            mesh=mesh, in_specs=P("dp"), out_specs=(P("dp"), P("dp")),
            check_vma=False))(jnp.asarray(x))
        d = 2 ** (step % dyn.period)
        mask = np.zeros(D, np.float32)
        mask[pos_np] = 1.0
        for i in range(n):
            qi, qj = x[i] * mask, x[(i - d) % n] * mask
            np.testing.assert_allclose(np.asarray(out)[i],
                                       0.5 * qi + 0.5 * qj,
                                       rtol=1e-5, atol=1e-6)
            np.testing.assert_allclose(np.asarray(q)[i], qi, rtol=1e-6)


# --- the exchange of a list of parts is one pipeline (C._apply_rounds) -------

# Per-rank shapes of one exchange: a leading axis the block rows do not
# divide, a 1-D part, a scalar, few rows longer than a block, a part under
# the block size, and the largest last but one in the list.
_PIPE_SHAPES = [(13, 6), (50,), (), (2, 40), (3,), (9, 4, 2)]
_PIPE_BLOCK = 64        # bytes: every shape but () and (3,) is cut


def _pipe_parts(dtype, shapes=_PIPE_SHAPES, seed=0):
    import jax.numpy as jnp
    rng = np.random.RandomState(seed)
    return [jnp.asarray(rng.randn(N, *s), jnp.float32).astype(dtype)
            for s in shapes]


def _pipe_case(kind):
    """``(fn(parts) -> parts, W)`` of one way to reach the pipeline, where
    ``fn`` runs inside ``shard_map`` on per-rank parts and ``W`` is the
    mixing matrix it applies (``out = W^T x``)."""
    import jax.numpy as jnp
    from bluefog_tpu.ops import collective as C
    G = topo.ExponentialTwoGraph(N)
    if kind == "static":            # three rounds a call
        sched = S.compile_static(G, use_topo_weights=True)
        assert len(sched.rounds) == 3
        return (lambda p: C.neighbor_allreduce(p, sched, "dp"),
                topo.weight_matrix(G))
    dyn = S.compile_dynamic(topo.dynamic_phase_table(G), N)
    w = np.zeros((N, N))
    for i in range(N):              # phase 1 of the one-peer walk: i -> i+2
        w[i, i] = w[i, (i + 2) % N] = 0.5
    if kind == "phase":
        return lambda p: C.neighbor_allreduce(p, dyn.phases[1], "dp"), w
    if kind == "switch":
        return (lambda p: C.dynamic_neighbor_allreduce(
            p, jnp.asarray(4, jnp.int32), dyn, "dp"), w)
    assert kind == "override"
    over = np.random.RandomState(3).uniform(0.2, 0.8, (N, N))
    used = np.zeros((N, N))
    for i in range(N):
        used[i, i], used[i, (i + 2) % N] = over[i, i], over[i, (i + 2) % N]
    return (lambda p: C.neighbor_allreduce_matrix(
        p, jnp.asarray(over, jnp.float32), dyn.phases[1], "dp"), used)


def _pipe_run(devices, fn, parts):
    import jax
    from jax.sharding import Mesh, PartitionSpec as P
    mesh = Mesh(np.asarray(devices), ("dp",))
    spec = [P("dp")] * len(parts)
    return jax.jit(jax.shard_map(
        lambda *p: [o[None] for o in fn([x[0] for x in p])], mesh=mesh,
        in_specs=tuple(spec), out_specs=spec, check_vma=False))(*parts)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("kind", ["static", "phase", "switch", "override"])
def test_cut_exchange_is_the_uncut_exchange_bit_for_bit(
        monkeypatch, devices, kind, dtype):
    """A list with cut parts, ordered and chained, gives every part the
    bits of that part exchanged alone and whole, and ``W^T x`` written out:
    over several rounds a call, one phase of a dynamic schedule, the traced
    ``lax.switch`` and a weight override."""
    from bluefog_tpu.ops import collective as C
    fn, w = _pipe_case(kind)
    parts = _pipe_parts(dtype)
    monkeypatch.setattr(C, "_BLOCK_BYTES", _PIPE_BLOCK)
    assert [len(C._blocks(p[0])) for p in parts] == (
        [7, 4, 1, 2, 1, 5] if dtype == "float32" else [3, 2, 1, 2, 1, 3])
    cut = _pipe_run(devices, fn, parts)
    monkeypatch.setattr(C, "_BLOCK_BYTES", 1 << 40)
    for x, got in zip(parts, cut):
        alone, = _pipe_run(devices, fn, [x])
        assert got.dtype == x.dtype and got.shape == x.shape
        np.testing.assert_array_equal(np.asarray(got.astype("float32")),
                                      np.asarray(alone.astype("float32")))
        np.testing.assert_allclose(
            np.asarray(got.astype("float32")),
            _expected_neighbor_allreduce(
                np.asarray(x.astype("float32")), w),
            rtol=2e-2 if dtype == "bfloat16" else 1e-5,
            atol=2e-2 if dtype == "bfloat16" else 1e-6)


@pytest.mark.parametrize("shape", [(13, 6), (50,), ()])
def test_a_list_of_one_part_cut_or_not(monkeypatch, devices, shape):
    """One part alone: cut where it is over the block size (a scalar never
    is), and the same bits either way."""
    from bluefog_tpu.ops import collective as C
    fn, _ = _pipe_case("static")
    part = _pipe_parts("float32", [shape])
    monkeypatch.setattr(C, "_BLOCK_BYTES", _PIPE_BLOCK)
    cut, = _pipe_run(devices, fn, part)
    monkeypatch.setattr(C, "_BLOCK_BYTES", 1 << 40)
    whole, = _pipe_run(devices, fn, part)
    np.testing.assert_array_equal(np.asarray(cut), np.asarray(whole))


def test_blocks_are_whole_rows_of_the_leading_axis():
    """The rule of ``_blocks``: at most the block size where a row allows
    it, a multiple of 8 rows where a block holds 8, the last block shorter;
    a part is never reshaped, so rows longer than a block go one by one;
    the size is 64 MiB."""
    import jax
    import jax.numpy as jnp
    from bluefog_tpu.ops import collective as C
    assert C._BLOCK_BYTES == 64 << 20

    def blocks(shape, dtype=jnp.float32):
        return C._blocks(jax.ShapeDtypeStruct(shape, dtype))
    assert blocks((2048, 8192)) == [(0, 2048)]              # 64 MiB: whole
    big = blocks((92544, 2048))                             # 8 KiB a row
    assert big[0] == (0, 8192) and big[-1] == (90112, 92544)
    assert len(big) == 12
    wide = blocks((2048, 92544))                            # 361.5 KiB a row
    assert wide[0] == (0, 176) and wide[-1] == (1936, 2048)
    assert len(wide) == 12
    assert blocks((3, 40 << 20)) == [(0, 1), (1, 2), (2, 3)]
    assert blocks((1 << 25,), jnp.bfloat16) == [(0, 1 << 25)]
    assert blocks((1 << 26,), jnp.bfloat16) == [(0, 1 << 25),
                                                (1 << 25, 1 << 26)]
    assert blocks(()) == [(0, 1)]


def test_one_part_under_the_block_size_lowers_without_a_barrier(devices):
    """The eager op on one small array: a scale and a permute a round and
    one sum, as before the pipeline."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, PartitionSpec as P
    fn, _ = _pipe_case("static")
    mesh = Mesh(np.asarray(devices), ("dp",))
    text = jax.jit(jax.shard_map(
        lambda x: fn(x[0])[None], mesh=mesh, in_specs=P("dp"),
        out_specs=P("dp"), check_vma=False)).lower(
            jnp.zeros((N, 4, 4), jnp.float32)).as_text()
    assert text.count("stablehlo.collective_permute") == 3
    assert "optimization_barrier" not in text
    assert "dynamic_update_slice" not in text
