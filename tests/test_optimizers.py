"""Optimizer family tests.

Mirrors the reference's end-to-end convergence strategy
(``test/torch_optimizer_test.py:100-180``): a synthetic linear-regression
problem where each rank sees a different data shard; train and assert the
final global MSE beats a threshold.  Grid over {AWC, ATC} x {empty, allreduce,
neighbor_allreduce, gradient_allreduce} plus dynamic-topology, hierarchical,
local-aggregation and the async window/push-sum optimizers.
"""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

import bluefog_tpu as bf
from bluefog_tpu import topology as topo
from bluefog_tpu.optim import CommunicationType

N = 8
DIM = 4
SAMPLES = 16  # per rank


def make_problem(seed=0):
    """Per-rank least squares: y_i = A_i w* + noise; rank-major tensors."""
    rng = np.random.RandomState(seed)
    w_star = rng.randn(DIM, 1)
    A = rng.randn(N, SAMPLES, DIM)
    y = A @ w_star + 0.01 * rng.randn(N, SAMPLES, 1)
    return jnp.asarray(A), jnp.asarray(y), w_star


def global_mse(w, A, y):
    """MSE of each rank's model on the FULL dataset (tests consensus)."""
    pred = np.einsum('msd,ndo->mnso', np.asarray(A), np.asarray(w))
    err = pred - np.asarray(y)[:, None]  # model n on data shard m vs shard m's labels
    return float(np.mean(err ** 2))


def grad_fn(A, y):
    def loss(w_leaf, A_r, y_r):
        return jnp.mean((A_r @ w_leaf - y_r) ** 2)

    g = jax.vmap(jax.grad(loss))

    def compute(params):
        return {"w": g(params["w"], A, y)}
    return jax.jit(compute)


def run_training(opt, A, y, *, steps=120, grads_at=None, seed=1,
                 broadcast_init=False):
    rng = np.random.RandomState(seed)
    # Deliberately diverse inits: consensus must pull the ranks together.
    params = {"w": jnp.asarray(rng.randn(N, DIM, 1) * 2.0)}
    if broadcast_init:
        # Gradient-allreduce never mixes parameters, so ranks must start
        # identical (reference: bf.broadcast_parameters before training).
        params = bf.broadcast_parameters(params, 0)
    state = opt.init(params)
    compute_grads = grad_fn(A, y)
    for _ in range(steps):
        at = grads_at(params) if grads_at is not None else params
        grads = compute_grads(at)
        params, state = opt.step(params, grads, state)
    return params, state


SCENARIOS = [
    ("awc", CommunicationType.neighbor_allreduce),
    ("awc", CommunicationType.allreduce),
    ("awc", CommunicationType.empty),
    ("atc", CommunicationType.neighbor_allreduce),
    ("atc", CommunicationType.allreduce),
    ("gradient_allreduce", CommunicationType.allreduce),
]


@pytest.mark.parametrize("order,comm", SCENARIOS,
                         ids=[f"{o}-{c.name}" for o, c in SCENARIOS])
def test_optimizer_converges(order, comm):
    bf.init(lambda: topo.ExponentialGraph(N))
    A, y, _ = make_problem()
    if order == "gradient_allreduce":
        opt = bf.optim.DistributedGradientAllreduceOptimizer(optax.sgd(0.05))
    else:
        cls = (bf.optim.DistributedAdaptWithCombineOptimizer if order == "awc"
               else bf.optim.DistributedAdaptThenCombineOptimizer)
        opt = cls(optax.sgd(0.05), comm)
    params, _ = run_training(opt, A, y,
                             broadcast_init=order == "gradient_allreduce")
    mse = global_mse(params["w"], A, y)
    # "empty" = local SGD on disjoint shards: no consensus, higher global MSE.
    threshold = 0.5 if comm == CommunicationType.empty else 0.05
    assert mse < threshold, f"{order}/{comm}: global MSE {mse}"
    if comm != CommunicationType.empty:
        w = np.asarray(params["w"])
        spread = np.abs(w - w.mean(axis=0, keepdims=True)).max()
        assert spread < 0.15, f"ranks did not reach consensus: spread {spread}"


def test_neighbor_beats_local():
    """Decentralized averaging must beat no-communication local SGD."""
    bf.init(lambda: topo.ExponentialGraph(N))
    A, y, _ = make_problem()
    nbr = bf.optim.DistributedNeighborAllreduceOptimizer(optax.sgd(0.05))
    loc = bf.optim.DistributedAdaptWithCombineOptimizer(
        optax.sgd(0.05), CommunicationType.empty)
    p_nbr, _ = run_training(nbr, A, y)
    p_loc, _ = run_training(loc, A, y)
    assert global_mse(p_nbr["w"], A, y) < global_mse(p_loc["w"], A, y)


def _set_threshold(monkeypatch, nbytes):
    """Leaves of ``nbytes`` and more are exchanged alone: 0 is every leaf
    alone, the program a user without packing would run."""
    from bluefog_tpu.optim import functional
    monkeypatch.setattr(functional, "_DIRECT_LEAF_BYTES", nbytes)


def _set_block(monkeypatch, nbytes):
    """Parts of more than ``nbytes`` go on the wire as blocks of at most
    that many (``collective._BLOCK_BYTES``; 64 MiB on the chip)."""
    from bluefog_tpu.ops import collective
    monkeypatch.setattr(collective, "_BLOCK_BYTES", nbytes)


@pytest.mark.parametrize("order", ["awc", "atc"])
@pytest.mark.parametrize("dynamic", [False, True])
def test_fusion_matches_unfused(monkeypatch, order, dynamic):
    """Fused single-buffer communication must be numerically identical to
    per-parameter communication (reference fusion oracle tests,
    ``torch_ops_test.py:210-284,962``) — over a multi-leaf pytree so the
    ravel actually concatenates."""
    bf.init(lambda: topo.ExponentialTwoGraph(N))
    rng = np.random.RandomState(3)
    params0 = {"a": jnp.asarray(rng.randn(N, DIM, 1)),
               "b": jnp.asarray(rng.randn(N, 3)),
               "c": jnp.asarray(rng.randn(N, 2, 2))}
    grads = {k: jnp.asarray(rng.randn(*np.asarray(v).shape))
             for k, v in params0.items()}

    outs = {}
    for fusion in (True, False):
        _set_threshold(monkeypatch, (1 << 20) if fusion else 0)
        opt = bf.optim.DistributedOptimizer(
            optax.sgd(0.05, momentum=0.9),
            CommunicationType.neighbor_allreduce, order=order,
            use_dynamic_topology=dynamic)
        p, s = params0, opt.init(params0)
        for _ in range(3):
            p, s = opt.step(p, grads, s)
        outs[fusion] = p
    for k in params0:
        np.testing.assert_allclose(np.asarray(outs[True][k]),
                                   np.asarray(outs[False][k]),
                                   rtol=1e-6, atol=1e-7)


# --- large leaves direct, small leaves packed (functional._fused_apply) ------

# Per-rank bytes of _split_problem's leaves: 256, 12, 16, 192, 8.  With the
# threshold patched to 128 bytes "a" and "d" go direct and the rest is packed.
_SMALL_THRESHOLD = 128


def _split_problem(seed=5):
    rng = np.random.RandomState(seed)
    shapes = {"a": (8, 8), "b": (3,), "c": (2, 2), "d": (6, 8), "e": (2,)}
    params = {k: jnp.asarray(rng.randn(N, *v), jnp.float32)
              for k, v in shapes.items()}
    grads = {k: jnp.asarray(rng.randn(N, *v), jnp.float32)
             for k, v in shapes.items()}
    return params, grads


def _run_split(order, dynamic, kw, steps=4):
    comm = (CommunicationType.allreduce if order == "gradient_allreduce"
            else CommunicationType.neighbor_allreduce)
    params, grads = _split_problem()
    opt = bf.optim.DistributedOptimizer(
        optax.sgd(0.05, momentum=0.9), comm, order=order,
        use_dynamic_topology=dynamic, **kw)
    state = opt.init(params)
    for _ in range(steps):
        # donate=True consumes the gradients it is handed
        params, state = opt.step(params, jax.tree.map(jnp.copy, grads),
                                 state)
    return {k: np.asarray(v) for k, v in params.items()}


@pytest.mark.parametrize("order,dynamic,kw", [
    ("awc", False, {}),
    ("awc", True, {}),
    ("atc", False, {}),
    ("atc", True, {}),
    ("gradient_allreduce", False, {}),
    ("awc", True, {"num_steps_per_communication": 2}),
    ("gradient_allreduce", False, {"num_steps_per_communication": 2}),
    ("atc", True, {"compression": "bf16"}),
    ("awc", False, {"compression": "bf16"}),
    ("atc", True, {"donate": True}),
], ids=lambda v: v if isinstance(v, str) else
    ("dynamic" if v is True else "static" if v is False else
     "-".join(f"{k}={x}" for k, x in v.items()) or "default"))
def test_direct_leaves_match_unfused(monkeypatch, order, dynamic, kw):
    """A tree with leaves on both sides of the threshold: large leaves
    direct and small ones packed give bit for bit the parameters of every
    leaf alone (threshold 0) and of every leaf packed: the same multiply,
    permute and add on every element."""
    bf.init(lambda: topo.ExponentialTwoGraph(N))
    _set_threshold(monkeypatch, _SMALL_THRESHOLD)
    mixed = _run_split(order, dynamic, kw)
    _set_threshold(monkeypatch, 0)
    unfused = _run_split(order, dynamic, kw)
    _set_threshold(monkeypatch, 1 << 40)
    all_packed = _run_split(order, dynamic, kw)
    # One case is held to float32 rounding (every leaf alone against the
    # buffer: 31 elements of 119 off by an ulp after four steps): XLA's CPU
    # backend contracts accumulate, average and update into other fused
    # multiply-adds when the leaves sit in a buffer.
    tol = (1e-6 if order == "gradient_allreduce"
           and "num_steps_per_communication" in kw else 0.0)
    for k in mixed:
        for other in (unfused, all_packed):
            np.testing.assert_allclose(mixed[k], other[k], rtol=tol,
                                       atol=tol, err_msg=k)


def test_direct_threshold_is_one_mebibyte_inclusive():
    """A leaf of exactly the threshold goes direct, one byte under is
    packed; the threshold is 1 MiB."""
    from bluefog_tpu.optim import functional as F
    assert F._DIRECT_LEAF_BYTES == 1 << 20
    leaves = [jax.ShapeDtypeStruct((512, 512), jnp.float32),    # 1 MiB
              jax.ShapeDtypeStruct(((1 << 20) - 1,), jnp.uint8),
              jax.ShapeDtypeStruct((512, 1024), jnp.bfloat16),  # 1 MiB
              jax.ShapeDtypeStruct((), jnp.float32)]
    assert F._split_direct(leaves) == ([0, 2], [1, 3])


_CUT_CASES = pytest.mark.parametrize("order,dynamic,override,kw", [
    ("atc", False, False, {}),
    ("awc", False, False, {}),
    ("atc", True, False, {}),
    ("awc", True, False, {}),
    ("atc", True, True, {}),
    ("awc", False, True, {}),
    ("atc", True, False, {"compression": "bf16"}),
    ("awc", False, False, {"compression": "bf16"}),
    ("atc", True, False, {"donate": True}),
], ids=lambda v: v if isinstance(v, str) else
    (("dynamic" if v else "static") if isinstance(v, bool) else
     "-".join(f"{k}={x}" for k, x in v.items()) or "plain"))


@_CUT_CASES
def test_cut_leaves_match_uncut(monkeypatch, order, dynamic, override, kw):
    """With blocks of 64 bytes the two direct leaves of the tree go as 4
    and 3 blocks, ordered and chained with the packed buffer; the
    parameters after four steps are bit for bit those of the same tree
    with no leaf cut: static rounds, the per-phase programs, a weight
    override, ``bf16`` on top, ``atc`` and ``awc``."""
    bf.init(lambda: topo.ExponentialTwoGraph(N))
    _set_threshold(monkeypatch, _SMALL_THRESHOLD)
    w = _override_matrix() if override else None

    def run():
        params, grads = _split_problem()
        opt = bf.optim.DistributedOptimizer(
            optax.sgd(0.05, momentum=0.9),
            CommunicationType.neighbor_allreduce, order=order,
            use_dynamic_topology=dynamic, **kw)
        state = opt.init(params)
        for _ in range(4):
            params, state = opt.step(
                params, jax.tree.map(jnp.copy, grads), state, src_weights=w)
        return {k: np.asarray(v) for k, v in params.items()}
    _set_block(monkeypatch, 64)
    cut = run()
    _set_block(monkeypatch, 1 << 40)
    whole = run()
    # Under ``bf16`` the two are held to bfloat16 rounding: XLA keeps a
    # fused bfloat16 multiply-add in float32 and rounds where a fusion ends
    # (``xla_allow_excess_precision``; with it off the bits are equal), and
    # a block's sum ends its fusion where the whole part's does not.
    tol = 2e-2 if kw.get("compression") == "bf16" else 0.0
    for k in cut:
        np.testing.assert_allclose(cut[k], whole[k], rtol=tol, atol=tol,
                                   err_msg=k)


def _permuted_types(text):
    """The operand types of a lowered program's ``collective_permute``s,
    in program order."""
    import re
    return re.findall(
        r"stablehlo\.collective_permute.*?: \(tensor<([^>]*)>\)", text)


@pytest.mark.parametrize("order", ["atc", "awc"])
def test_permutes_lower_in_wire_order(monkeypatch, order):
    """In the step program's StableHLO the permutes stand in ascending
    order of their part's bytes, whatever the flatten order, with a cut
    leaf's blocks adjacent and in row order (the last one shorter)."""
    bf.init(lambda: topo.ExponentialTwoGraph(N))
    _set_threshold(monkeypatch, 48)
    _set_block(monkeypatch, 64)
    rng = np.random.RandomState(1)
    shapes = {"a_wide": (7, 12),    # 336 B: rows of 48 B, seven blocks
              "b_bias": (3,),       # packed
              "c_square": (9, 8),   # 288 B: rows of 32 B, 2 + 2 + 2 + 2 + 1
              "d_whole": (4, 4),    # 64 B: direct, not cut
              "e_bias": (5,)}       # packed: 32 B with b_bias
    params = {k: jnp.asarray(rng.randn(N, *v), jnp.float32)
              for k, v in shapes.items()}
    opt = bf.optim.DistributedOptimizer(
        optax.sgd(0.05), CommunicationType.neighbor_allreduce, order=order,
        use_dynamic_topology=True)
    text = _lowered_step(opt, params, params)
    assert _permuted_types(text) == (
        ["8xf32", "4x4xf32"] + ["2x8xf32"] * 4 + ["1x8xf32"]
        + ["1x12xf32"] * 7)
    assert "stablehlo.optimization_barrier" in text


@pytest.mark.parametrize("order,base,kw", [
    ("atc", "sgdm", {"use_dynamic_topology": True}),
    ("atc", "adamw", {"use_dynamic_topology": True}),
    ("awc", "sgdm", {"use_dynamic_topology": True}),
    ("atc", "sgdm", {}),
    ("atc", "sgdm", {"use_dynamic_topology": True, "compression": "bf16"}),
], ids=["atc-dynamic", "adamw", "awc", "static", "bf16"])
def test_one_device_step_holds_no_pipeline(monkeypatch, order, base, kw):
    """On one device a schedule has no round and the exchange no wire: the
    step program scales each part where it stands, in flatten order, and
    lowers to the same text whatever the block size, with no barrier, no
    block written back and no permute (the one-chip cells run this
    program)."""
    bf.init(devices=jax.devices()[:1])
    assert bf.size() == 1
    _set_threshold(monkeypatch, _SMALL_THRESHOLD)
    rng = np.random.RandomState(2)
    params = {k: jnp.asarray(rng.randn(1, *v), jnp.float32)
              for k, v in {"a": (8, 8), "b": (3,), "d": (6, 8)}.items()}
    make = {"sgdm": lambda: optax.sgd(0.05, momentum=0.9),
            "adamw": lambda: optax.adamw(1e-3)}[base]
    texts = []
    for block in (64, 1 << 40):
        _set_block(monkeypatch, block)
        opt = bf.optim.DistributedOptimizer(
            make(), CommunicationType.neighbor_allreduce, order=order, **kw)
        texts.append(_lowered_step(opt, params, params))
    assert texts[0] == texts[1]
    for word in ("collective_permute", "optimization_barrier",
                 "dynamic_update_slice"):
        assert word not in texts[0], word


def _lowered_step(opt, params, grads):
    return opt._step_callable(with_weights=False).lower(
        params, grads, opt.init(params)).as_text()


@pytest.mark.parametrize("large", [1, 2, 5])
def test_dynamic_exchange_is_one_switch(monkeypatch, large):
    """The one phase switch of a dynamic topology is the host's: the class
    builds a step program per phase, none holds a ``case``, and each holds
    the permutes the pipeline's rule gives: two blocks for every large leaf
    (256 bytes against blocks of 128) and one for the packed buffer."""
    bf.init(lambda: topo.ExponentialTwoGraph(N))
    _set_threshold(monkeypatch, _SMALL_THRESHOLD)
    _set_block(monkeypatch, 128)
    rng = np.random.RandomState(0)
    params = {f"w{i}": jnp.asarray(rng.randn(N, 8, 8), jnp.float32)
              for i in range(large)}
    params["bias"] = jnp.asarray(rng.randn(N, 3), jnp.float32)
    opt = bf.optim.DistributedAdaptThenCombineOptimizer(
        optax.sgd(0.05), use_dynamic_topology=True)
    state = opt.init(params)
    period = 3                              # one-peer Exp2 over 8 ranks
    assert opt._schedule().period == period
    texts = [opt._step_program(False, None, phase)[0].lower(
        params, params, state).as_text() for phase in range(period)]
    assert texts[0] == _lowered_step(opt, params, params)
    assert len(set(texts)) == period        # each its own peers
    for text in texts:
        assert "stablehlo.case" not in text
        assert text.count("stablehlo.collective_permute") == 2 * large + 1


def _traced_switch_step(order, base, n_w):
    """The whole step under one ``jit`` of the caller's own, where the
    counter is traced and the dynamic combiner's ``lax.switch`` chooses the
    phase: what ``functional.step_fn`` gives a caller outside the class
    (``n_w``: 1 if it takes a weight matrix, else 0)."""
    from jax.sharding import PartitionSpec as P
    from bluefog_tpu import basics
    from bluefog_tpu.ops import schedule as S
    from bluefog_tpu.optim import functional as F
    ctx = basics._require_init()
    sched = S.compile_dynamic(topo.dynamic_phase_table(ctx.topology), N)
    inner = F.step_fn(order, base, F.make_combiner(
        CommunicationType.neighbor_allreduce, axis_name=basics.RANK_AXIS,
        sched=sched), axis_name=basics.RANK_AXIS)

    def run(params, grads, state, *w):
        p, g, s = jax.tree.map(lambda x: x[0], (params, grads, state))
        out = inner(p, g, s, weights=w[0] if w else None)
        return jax.tree.map(lambda x: x[None], out)
    spec = P(basics.RANK_AXIS)
    return jax.jit(jax.shard_map(
        run, mesh=ctx.mesh, in_specs=(spec,) * 3 + (P(),) * n_w,
        out_specs=(spec, spec)))


def _override_matrix(seed=11):
    """A full weight matrix, rows and columns of no special sum: the phase's
    edges pick their entries."""
    return np.random.RandomState(seed).uniform(0.2, 0.8, (N, N))


_PHASE_CASES = pytest.mark.parametrize("order,override", [
    ("atc", False), ("atc", True), ("awc", False), ("awc", True)],
    ids=lambda v: v if isinstance(v, str) else
    ("override" if v else "schedule-weights"))


@_PHASE_CASES
def test_phase_programs_match_the_traced_switch(monkeypatch, order,
                                                override):
    """Over two periods and a step the class (one program per phase, the
    host choosing) gives bit for bit the parameters of the traced switch."""
    bf.init(lambda: topo.ExponentialTwoGraph(N))
    _set_threshold(monkeypatch, _SMALL_THRESHOLD)
    base = optax.sgd(0.05, momentum=0.9)
    opt = bf.optim.DistributedOptimizer(
        base, CommunicationType.neighbor_allreduce, order=order,
        use_dynamic_topology=True)
    w = _override_matrix() if override else None
    extra = (jnp.asarray(w, jnp.float32),) if override else ()
    ref = _traced_switch_step(order, base, len(extra))
    params, grads = _split_problem()
    ref_params, ref_state = params, opt.init(params)
    state = opt.init(params)
    for t in range(2 * opt._schedule().period + 1):
        params, state = opt.step(params, grads, state, src_weights=w)
        ref_params, ref_state = ref(ref_params, grads, ref_state, *extra)
        for k in params:
            np.testing.assert_array_equal(
                np.asarray(params[k]), np.asarray(ref_params[k]),
                err_msg=f"step {t}, leaf {k}")
    np.testing.assert_array_equal(np.asarray(state.step),
                                  np.asarray(ref_state.step))


@_PHASE_CASES
def test_phase_follows_the_counter_of_the_state_handed_in(order, override):
    """The benchmark's ``step`` check in small: after k steps a fresh
    ``init`` state starts at phase 0 again, and a state whose counter says 5
    runs phase ``5 % period``; each is what the mixing matrix of that step
    gives, written out here from the phase table."""
    graph = topo.ExponentialTwoGraph(N)
    bf.init(lambda: graph)
    table = topo.dynamic_phase_table(graph)
    opt = bf.optim.DistributedOptimizer(
        optax.sgd(0.0), CommunicationType.neighbor_allreduce, order=order,
        use_dynamic_topology=True)      # no update: a step is W_t x
    w = _override_matrix() if override else None

    def mixed(x, t):
        phase, out = table[t % len(table)], np.zeros_like(x)
        for dst in range(N):
            srcs = phase.recv_from(dst)
            if override:
                out[dst] = w[dst, dst] * x[dst] + sum(
                    w[src, dst] * x[src] for src in srcs)
            else:
                out[dst] = (x[dst] + sum(x[src] for src in srcs)) / (
                    len(srcs) + 1)
        return out

    x = np.random.RandomState(2).randn(N, 5).astype(np.float32)
    params = {"w": jnp.asarray(x)}
    zeros = {"w": jnp.zeros_like(params["w"])}
    state = opt.init(params)
    for _ in range(4):                  # leaves the class at counter 4
        params, state = opt.step(params, zeros, state, src_weights=w)
    for counter in (0, 5, 0):
        fresh = opt.init({"w": jnp.asarray(x)})
        if counter:
            fresh = fresh._replace(
                step=jnp.full_like(fresh.step, counter))
        got, after = opt.step({"w": jnp.asarray(x)}, zeros, fresh,
                              src_weights=w)
        np.testing.assert_allclose(np.asarray(got["w"]), mixed(x, counter),
                                   rtol=1e-6, atol=1e-6)
        # ... and is followed from there without another read
        got, _ = opt.step(got, zeros, after, src_weights=w)
        np.testing.assert_allclose(
            np.asarray(got["w"]), mixed(mixed(x, counter), counter + 1),
            rtol=1e-5, atol=1e-6)
    assert len(table) == 3 and not np.allclose(mixed(x, 0), mixed(x, 2))


def _combiner_on_mesh(kind):
    """``(Combiner, mesh, spec of a rank-major array)`` over the 8 ranks."""
    from jax.sharding import PartitionSpec as P
    from bluefog_tpu import basics
    from bluefog_tpu.ops import schedule as S
    from bluefog_tpu.optim import functional as F
    ctx = basics._require_init()
    rank, machine, local = (basics.RANK_AXIS, basics.MACHINE_AXIS,
                            basics.LOCAL_AXIS)
    if kind == "hierarchical":
        sched = S.compile_static(ctx.machine_topology, use_topo_weights=False)
        return (F.make_combiner(
            CommunicationType.hierarchical_neighbor_allreduce,
            axis_name=machine, sched=sched, local_axis=local,
            machine_axis=machine), ctx.hier_mesh, P((machine, local)))
    kw = {}
    if kind == "static":
        kw["sched"] = S.compile_static(ctx.topology, use_topo_weights=True)
    elif kind == "dynamic":
        kw["sched"] = S.compile_dynamic(
            topo.dynamic_phase_table(ctx.topology), N)
    comm = (CommunicationType[kind] if kind in ("empty", "allreduce")
            else CommunicationType.neighbor_allreduce)
    return F.make_combiner(comm, axis_name=rank, **kw), ctx.mesh, P(rank)


@pytest.mark.parametrize("compression", ["none", "bf16"])
@pytest.mark.parametrize("kind", ["empty", "allreduce", "static", "dynamic",
                                  "hierarchical"])
def test_every_combiner_maps_parts(kind, compression):
    """The one protocol of ``functional.Combiner``: a list of arrays in, a
    list of the same shapes and dtypes out, bit for bit what the combiner
    gives each array as a list of one.  The dynamic combiner serves the
    whole list under ONE phase switch; a codec hands it one part at a time
    (``compress_combiner``), so there each part has its own."""
    from jax.sharding import PartitionSpec as P
    from bluefog_tpu.optim import functional as F
    bf.init(lambda: topo.ExponentialTwoGraph(N), local_size=2)
    comb, mesh, spec = _combiner_on_mesh(kind)
    comb = F.compress_combiner(comb, compression)
    rng = np.random.RandomState(7)
    parts = [jnp.asarray(rng.randn(N, *shape), dtype) for shape, dtype in
             (((4, 3), jnp.float32), ((5,), jnp.float32),
              ((2, 2, 2), jnp.bfloat16))]

    def whole(step, *xs):
        out = comb.combine([x[0] for x in xs], step, None)
        assert isinstance(out, list) and len(out) == len(xs)
        return tuple(o[None] for o in out)

    def one_by_one(step, *xs):
        return tuple(comb.combine([x[0]], step, None)[0][None] for x in xs)

    def on_mesh(fn):
        return jax.jit(jax.shard_map(
            fn, mesh=mesh, in_specs=(P(),) + (spec,) * len(parts),
            out_specs=(spec,) * len(parts)))
    for step in (0, 1, 2):      # every phase of the dynamic schedule
        step = jnp.asarray(step, jnp.int32)
        got, want = on_mesh(whole)(step, *parts), on_mesh(one_by_one)(
            step, *parts)
        for x, g, w in zip(parts, got, want):
            assert (g.shape, g.dtype) == (x.shape, x.dtype)
            np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
    if kind != "empty":
        assert not np.array_equal(np.asarray(got[0]), np.asarray(parts[0]))
    if kind == "dynamic":
        text = on_mesh(whole).lower(step, *parts).as_text()
        assert text.count("stablehlo.case") == (
            1 if compression == "none" else len(parts))


@pytest.mark.parametrize("dynamic", [False, True], ids=["static", "dynamic"])
def test_tree_without_large_leaf_lowers_as_all_packed(monkeypatch, dynamic):
    """A tree with no leaf of 1 MiB lowers to the program it lowers to with
    the threshold above every leaf: one concatenate, one permute set."""
    bf.init(lambda: topo.ExponentialTwoGraph(N))
    params, grads = _split_problem()

    def counts():
        opt = bf.optim.DistributedAdaptThenCombineOptimizer(
            optax.sgd(0.05), use_dynamic_topology=dynamic)
        text = _lowered_step(opt, params, grads)
        return (text.count("stablehlo.concatenate"),
                text.count("stablehlo.collective_permute"))
    default = counts()
    _set_threshold(monkeypatch, 1 << 40)
    assert counts() == default
    assert default[0] == 1
    _set_threshold(monkeypatch, _SMALL_THRESHOLD)
    assert counts()[1] == 3 * default[1]    # "a", "d" and one buffer


@pytest.mark.parametrize("dynamic", [False, True], ids=["static", "dynamic"])
def test_sparse_compression_with_direct_leaf_reaches_consensus(
        monkeypatch, dynamic):
    """``sparse:<frac>`` rotates its block within each part: a tree with a
    direct leaf and a packed buffer still mixes every coordinate."""
    bf.init(lambda: topo.ExponentialGraph(N))
    _set_threshold(monkeypatch, _SMALL_THRESHOLD)
    rng = np.random.RandomState(6)
    # Parts of 64 and 8 elements: four blocks each, which shares no factor
    # with the three phases of the dynamic schedule (a block count that
    # does would tie each block to one phase, whatever the packing).
    params = {"w": jnp.asarray(rng.randn(N, 8, 8), jnp.float32),
              "b": jnp.asarray(rng.randn(N, 3), jnp.float32),
              "c": jnp.asarray(rng.randn(N, 5), jnp.float32)}
    zeros = jax.tree.map(jnp.zeros_like, params)
    opt = bf.optim.DistributedNeighborAllreduceOptimizer(
        optax.sgd(0.05), compression="sparse:0.25",
        use_dynamic_topology=dynamic)
    state = opt.init(params)
    mean = {k: np.asarray(v).mean(axis=0) for k, v in params.items()}
    for _ in range(240):
        params, state = opt.step(params, zeros, state)
    for k, v in params.items():
        v = np.asarray(v)
        assert np.abs(v - v.mean(axis=0, keepdims=True)).max() < 1e-3, k
        np.testing.assert_allclose(v.mean(axis=0), mean[k], atol=1e-4)


@pytest.mark.parametrize("threshold,block,kw,want,transfers", [
    (_SMALL_THRESHOLD, None, {},
     {"direct": (2, 448), "packed": (3, 36), "cut": (0, 0)}, 3),
    (_SMALL_THRESHOLD, 64, {},
     {"direct": (2, 448), "packed": (3, 36), "cut": (2, 448)}, 8),
    (_SMALL_THRESHOLD, 64, {"compression": "bf16"},
     {"direct": (2, 448), "packed": (3, 36), "cut": (2, 448)}, 5),
    (0, None, {},
     {"direct": (5, 484), "packed": (0, 0), "cut": (0, 0)}, 5),
    (_SMALL_THRESHOLD, 64, {"communication_type": CommunicationType.empty},
     {"direct": (0, 0), "packed": (0, 0), "cut": (0, 0)}, 0),
    (_SMALL_THRESHOLD, 64,
     {"communication_type": CommunicationType.allreduce},
     {"direct": (2, 448), "packed": (3, 36), "cut": (0, 0)}, 3),
], ids=["fused", "cut", "cut-bf16", "unfused", "identity", "allreduce"])
def test_exchange_path_gauges(monkeypatch, threshold, block, kw, want,
                              transfers):
    """``bf_optim_exchange_leaves/bytes{path}`` read the leaves and the
    per-rank bytes of the tree the step program was built for, ``cut``
    those of the direct leaves that go as blocks (256 and 192 bytes against
    64: four and three blocks; as bfloat16 two each), and
    ``bf_optim_exchange_transfers`` the pieces a round moves."""
    from bluefog_tpu.utils import telemetry
    bf.init(lambda: topo.ExponentialTwoGraph(N))
    telemetry.reset()
    _set_threshold(monkeypatch, threshold)
    if block is not None:
        _set_block(monkeypatch, block)
    params, grads = _split_problem()
    opt = bf.optim.DistributedOptimizer(optax.sgd(0.05), **kw)
    opt.step(params, grads, opt.init(params))
    snap = telemetry.snapshot()
    for path, (leaves, nbytes) in want.items():
        assert snap[f'bf_optim_exchange_leaves{{path="{path}"}}'] == leaves
        assert snap[f'bf_optim_exchange_bytes{{path="{path}"}}'] == nbytes
    assert snap["bf_optim_exchange_transfers"] == transfers


def test_one_rank_cuts_and_transfers_nothing(monkeypatch):
    """One rank: the schedule has no round, so no leaf is cut and no piece
    moves, whatever the block size (the one-chip cells read 0)."""
    from bluefog_tpu.utils import telemetry
    bf.init(devices=jax.devices()[:1])
    telemetry.reset()
    _set_threshold(monkeypatch, _SMALL_THRESHOLD)
    _set_block(monkeypatch, 64)
    params, grads = jax.tree.map(lambda x: x[:1], _split_problem())
    opt = bf.optim.DistributedOptimizer(optax.sgd(0.05),
                                        use_dynamic_topology=True)
    opt.step(params, grads, opt.init(params))
    snap = telemetry.snapshot()
    assert snap['bf_optim_exchange_leaves{path="cut"}'] == 0
    assert snap['bf_optim_exchange_bytes{path="cut"}'] == 0
    assert snap["bf_optim_exchange_transfers"] == 0


@pytest.mark.parametrize("factory,kind", [
    (lambda b: bf.optim.DistributedNeighborAllreduceOptimizer(
        b, compression="bf16"), "neighbor"),
    (lambda b: bf.optim.DistributedGradientAllreduceOptimizer(
        b, compression="bf16"), "gradient"),
])
def test_bf16_compression_converges_and_compresses(factory, kind):
    """compression='bf16' halves the wire payload (the reference family's
    fp16 compression role) without breaking convergence, and the lowered
    program really carries bf16 over the collective."""
    bf.init(lambda: topo.ExponentialTwoGraph(N))
    A, y, _ = make_problem()
    opt = factory(optax.sgd(0.05))
    params, state = run_training(opt, A, y,
                                 broadcast_init=(kind == "gradient"))
    assert global_mse(params["w"], A, y) < 0.05

    # the compiled program carries bf16 (this problem is f32 end-to-end, so
    # any bf16 in the lowering comes from the compression casts around the
    # collective); the uncompressed control has none
    grads = {"w": jnp.zeros_like(params["w"])}
    lowered = opt._step_callable(False).lower(params, grads, state).as_text()
    assert "collective_permute" in lowered or "all_reduce" in lowered
    assert "bf16" in lowered
    plain = factory(optax.sgd(0.05))
    plain.compression = "none"
    st0 = plain.init(params)
    assert "bf16" not in plain._step_callable(False).lower(
        params, grads, st0).as_text()


def test_unknown_compression_rejected():
    with pytest.raises(ValueError, match="compression"):
        bf.optim.DistributedOptimizer(optax.sgd(0.1), compression="fp8")


def test_compress_combiner_residual_exact_for_identity():
    """Difference compression: with combine=identity the wrapper is exact
    (a rank's own master weights are never truncated by its own rounds);
    without the residual it quantizes."""
    from bluefog_tpu.optim.functional import Combiner, compress_combiner
    x = jnp.asarray(np.random.RandomState(0).randn(64).astype(np.float32))
    ident = lambda parts, step, weights: parts  # noqa: E731

    def run(replica_identical):
        wrapped = compress_combiner(
            Combiner(ident, replica_identical=replica_identical), "bf16")
        return np.asarray(wrapped.combine([x], None, None)[0])
    np.testing.assert_array_equal(run(False), np.asarray(x))
    # a replica-identical combiner gets no per-rank residual back
    assert not np.array_equal(run(True), np.asarray(x))
    np.testing.assert_array_equal(
        run(True), np.asarray(x.astype(jnp.bfloat16).astype(jnp.float32)))


def test_dynamic_topology_optimizer():
    bf.init(lambda: topo.ExponentialGraph(N))
    A, y, _ = make_problem()
    opt = bf.optim.DistributedNeighborAllreduceOptimizer(
        optax.sgd(0.05), use_dynamic_topology=True)
    params, state = run_training(opt, A, y, steps=150)
    assert int(state.step[0]) == 150
    assert global_mse(params["w"], A, y) < 0.05


def test_adam_base_optimizer():
    """Any optax transformation slots in (the reference hand-codes each
    torch optimizer's math per execution order; optax composes instead)."""
    bf.init(lambda: topo.ExponentialGraph(N))
    A, y, _ = make_problem()
    opt = bf.optim.DistributedAdaptThenCombineOptimizer(
        optax.adam(0.05), CommunicationType.neighbor_allreduce)
    params, _ = run_training(opt, A, y, steps=200)
    assert global_mse(params["w"], A, y) < 0.05


def test_local_aggregation_counts_communication():
    """J=4 must still converge (communicate every 4th step)."""
    bf.init(lambda: topo.ExponentialGraph(N))
    A, y, _ = make_problem()
    opt = bf.optim.DistributedNeighborAllreduceOptimizer(
        optax.sgd(0.05), num_steps_per_communication=4)
    params, _ = run_training(opt, A, y, steps=200)
    assert global_mse(params["w"], A, y) < 0.05


def test_hierarchical_optimizer():
    bf.init(lambda: topo.ExponentialGraph(N), local_size=2)
    A, y, _ = make_problem()
    opt = bf.optim.DistributedHierarchicalNeighborAllreduceOptimizer(
        optax.sgd(0.05))
    params, _ = run_training(opt, A, y, steps=150)
    assert global_mse(params["w"], A, y) < 0.05


def test_step_weight_mutation_no_recompile():
    """Per-step weight kwargs are traced: mutate them every step."""
    bf.init(lambda: topo.RingGraph(N))
    A, y, _ = make_problem()
    opt = bf.optim.DistributedNeighborAllreduceOptimizer(optax.sgd(0.05))
    rng = np.random.RandomState(3)
    params = {"w": jnp.asarray(rng.randn(N, DIM, 1))}
    state = opt.init(params)
    compute_grads = grad_fn(A, y)
    for t in range(60):
        grads = compute_grads(params)
        sw = 0.5 if t % 2 == 0 else 0.4
        nbr_w = (1.0 - sw) / 2.0  # ring: 2 in-neighbors
        w_mat = np.zeros((N, N))
        for r in range(N):
            w_mat[(r - 1) % N, r] = nbr_w
            w_mat[(r + 1) % N, r] = nbr_w
            w_mat[r, r] = sw
        params, state = opt.step(params, grads, state, src_weights=w_mat)
    assert global_mse(params["w"], A, y) < 0.05


def test_explicit_phases_dynamic_optimizer():
    """phases= path: pass a custom phase table (regression: unhashable key)."""
    bf.init(lambda: topo.ExponentialGraph(N))
    A, y, _ = make_problem()
    phases = topo.one_peer_exp2_phases(N)
    opt = bf.optim.DistributedNeighborAllreduceOptimizer(
        optax.sgd(0.05), use_dynamic_topology=True, phases=phases)
    params, _ = run_training(opt, A, y, steps=150)
    assert global_mse(params["w"], A, y) < 0.05


def test_gradient_allreduce_local_aggregation_keeps_replicas_identical():
    """J>1 gradient averaging: accumulate locally, apply the identical
    averaged aggregate on every rank (regression: replica drift)."""
    bf.init(lambda: topo.ExponentialGraph(N))
    A, y, _ = make_problem()
    opt = bf.optim.DistributedGradientAllreduceOptimizer(
        optax.sgd(0.05), num_steps_per_communication=3)
    params, _ = run_training(opt, A, y, steps=150, broadcast_init=True)
    w = np.asarray(params["w"])
    spread = np.abs(w - w[0]).max()
    assert spread < 1e-5, f"replicas drifted: {spread}"
    assert global_mse(params["w"], A, y) < 0.05


def test_weight_override_rejected_for_allreduce():
    """Weight kwargs only make sense for neighbor averaging (regression:
    silently discarded)."""
    bf.init(lambda: topo.ExponentialGraph(N))
    A, y, _ = make_problem()
    opt = bf.optim.DistributedAllreduceOptimizer(optax.sgd(0.05))
    params = {"w": jnp.zeros((N, DIM, 1))}
    state = opt.init(params)
    grads = {"w": jnp.zeros((N, DIM, 1))}
    w_mat = np.eye(N)
    with pytest.raises(ValueError, match="not supported"):
        opt.step(params, grads, state, src_weights=w_mat)


def test_win_put_optimizer_converges():
    bf.init(lambda: topo.ExponentialGraph(N))
    A, y, _ = make_problem()
    opt = bf.optim.DistributedWinPutOptimizer(optax.sgd(0.05))
    params, _ = run_training(opt, A, y, steps=120)
    opt.free()
    assert global_mse(params["w"], A, y) < 0.05


def test_pull_get_optimizer_converges():
    bf.init(lambda: topo.ExponentialGraph(N))
    A, y, _ = make_problem()
    opt = bf.optim.DistributedPullGetOptimizer(optax.sgd(0.05))
    params, _ = run_training(opt, A, y, steps=120)
    opt.free()
    assert global_mse(params["w"], A, y) < 0.05


def test_push_sum_optimizer_converges():
    """Push-sum on a directed ring (column-stochastic only): the de-biased
    iterates must converge to a consensus minimizer."""
    bf.init(lambda: topo.RingGraph(N, connect_style=1))  # directed ring
    A, y, _ = make_problem()
    opt = bf.optim.DistributedPushSumOptimizer(optax.sgd(0.05))
    params, _ = run_training(opt, A, y, steps=150, grads_at=None)
    debiased = opt.debias(params)
    p = opt.associated_p()
    opt.free()
    assert np.all(np.asarray(p) > 0)
    assert global_mse(debiased["w"], A, y) < 0.1
    w = np.asarray(debiased["w"])
    spread = np.abs(w - w.mean(axis=0, keepdims=True)).max()
    assert spread < 0.2, f"push-sum consensus failed: spread {spread}"


def test_donate_matches_undonated():
    """``donate=True`` (buffer aliasing for billion-param configs) must be
    numerically identical to the default step."""
    bf.init(lambda: topo.ExponentialGraph(N))
    A, y, _ = make_problem()
    outs = {}
    for donate in (False, True):
        opt = bf.optim.DistributedNeighborAllreduceOptimizer(
            optax.sgd(0.05), donate=donate)
        params = {"w": jnp.asarray(
            np.random.RandomState(1).randn(N, DIM, 1) * 2.0)}
        state = opt.init(params)
        compute_grads = grad_fn(A, y)
        for _ in range(5):
            grads = compute_grads(params)
            params, state = opt.step(params, grads, state)
        outs[donate] = np.asarray(params["w"]).copy()
    np.testing.assert_array_equal(outs[True], outs[False])


def test_win_put_optimizer_overlap_converges():
    """overlap=True: the put runs behind the caller's compute (one step of
    staleness — the reference's actual async operating mode); convergence
    must survive."""
    bf.init(lambda: topo.ExponentialGraph(N))
    A, y, _ = make_problem()
    opt = bf.optim.DistributedWinPutOptimizer(optax.sgd(0.05), overlap=True)
    params, _ = run_training(opt, A, y, steps=150)
    opt.free()
    assert global_mse(params["w"], A, y) < 0.1


def test_push_sum_optimizer_window_checkpoint_resume():
    """Push-sum optimizer state (incl. window staging + associated-P)
    survives a checkpoint/re-init/restore cycle bit-exactly."""
    bf.init(lambda: topo.RingGraph(N, connect_style=1))
    A, y, _ = make_problem()
    opt = bf.optim.DistributedPushSumOptimizer(optax.sgd(0.05))
    params = {"w": jnp.asarray(
        np.random.RandomState(1).randn(N, DIM, 1).astype(np.float32) * 2.0)}
    state = opt.init(params)
    compute_grads = grad_fn(A, y)
    for _ in range(10):
        params, state = opt.step(params, compute_grads(params), state)
    win_snap = opt.window_state_dict()
    p_mid, s_mid = params, state
    for _ in range(10):
        params, state = opt.step(params, compute_grads(params), state)
    ref = np.asarray(params["w"]).copy()
    p_ref = np.asarray(opt.associated_p()).copy()
    opt.free()
    bf.shutdown()

    bf.init(lambda: topo.RingGraph(N, connect_style=1))
    opt2 = bf.optim.DistributedPushSumOptimizer(optax.sgd(0.05))
    params2 = jax.tree.map(jnp.asarray, p_mid)
    opt2.init(params2)  # recreate windows (zero state)
    opt2.load_window_state_dict(win_snap)
    state2 = s_mid
    for _ in range(10):
        params2, state2 = opt2.step(params2, compute_grads(params2), state2)
    np.testing.assert_array_equal(np.asarray(params2["w"]), ref)
    np.testing.assert_array_equal(np.asarray(opt2.associated_p()), p_ref)
    opt2.free()


def test_window_state_dict_guards():
    """Snapshot/restore misuse fails loudly: no windows, or a snapshot
    taken under a different fuse/prefix layout."""
    bf.init(lambda: topo.ExponentialGraph(N))
    opt = bf.optim.DistributedWinPutOptimizer(optax.sgd(0.05))
    with pytest.raises(RuntimeError, match="no windows exist"):
        opt.window_state_dict()
    params = {"w": jnp.zeros((N, DIM, 1))}
    opt.init(params)
    snap = opt.window_state_dict()
    opt.free()
    with pytest.raises(RuntimeError, match="no windows exist"):
        opt.load_window_state_dict(snap)
    # different layout: per-leaf windows cannot consume a fused snapshot
    opt2 = bf.optim.DistributedWinPutOptimizer(optax.sgd(0.05), fuse=False)
    opt2.init(params)
    with pytest.raises(ValueError, match="fuse= setting or window_prefix"):
        opt2.load_window_state_dict(snap)
    opt2.free()


def test_sparse_compression_converges():
    """compression='sparse:<frac>' on the decentralized family: only 25%
    of entries cross the wire each round (a step-rotating aligned block of
    values + indices over the compiled edge schedule), the residual keeps
    unsent coordinates locally intact; training still reaches the global
    solution."""
    bf.init(lambda: topo.ExponentialGraph(N))
    A, y, _ = make_problem()
    opt = bf.optim.DistributedNeighborAllreduceOptimizer(
        optax.sgd(0.05), compression="sparse:0.25")
    # Each round mixes one block; a full sweep takes ceil(1/frac) rounds.
    params, _ = run_training(opt, A, y, steps=300)
    assert global_mse(params["w"], A, y) < 0.05


def test_sparse_compression_rejects_unsupported_combos():
    """sparse needs a neighbor edge schedule + residual feedback: the
    replica-identical allreduce and the non-converging magnitude-only
    'topk' refuse loudly."""
    bf.init(lambda: topo.ExponentialGraph(N))
    A, y, _ = make_problem()
    params = {"w": jnp.asarray(
        np.random.RandomState(1).randn(N, DIM, 1) * 2.0)}
    opt2 = bf.optim.DistributedAllreduceOptimizer(
        optax.sgd(0.05), compression="sparse:0.25")
    with pytest.raises(ValueError, match="neighbor_allreduce|residual"):
        opt2.step(params, grad_fn(A, y)(params), opt2.init(params))
    opt3 = bf.optim.DistributedNeighborAllreduceOptimizer(
        optax.sgd(0.05), compression="topk:0.25")
    with pytest.raises(ValueError, match="sparse:<frac>"):
        opt3.step(params, grad_fn(A, y)(params), opt3.init(params))


def test_sparse_compression_dynamic_topology_converges():
    """compression='sparse:<frac>' composes with use_dynamic_topology:
    each one-peer Exp2 phase ships only the rotating aligned block over
    its single live edge (k*4 bytes instead of the dense payload), the
    residual keeps unsent coordinates locally intact, and training still
    reaches the global solution with full consensus — the flagship bench
    configuration's compressed mode."""
    bf.init(lambda: topo.ExponentialGraph(N))
    A, y, _ = make_problem()
    opt = bf.optim.DistributedNeighborAllreduceOptimizer(
        optax.sgd(0.05), use_dynamic_topology=True,
        compression="sparse:0.25")
    params, _ = run_training(opt, A, y, steps=400)
    assert global_mse(params["w"], A, y) < 0.05
    w = np.asarray(params["w"])
    spread = np.abs(w - w.mean(axis=0, keepdims=True)).max()
    assert spread < 0.15, f"no consensus under dynamic sparse: {spread}"


def test_sparse_compression_with_local_aggregation_sweeps_all_coords():
    """sparse + num_steps_per_communication > 1: the block must rotate by
    the COMMUNICATION-round index — rotating by the raw step would alias
    (gcd(J*k, size)) and leave whole coordinate blocks unmixed forever."""
    bf.init(lambda: topo.ExponentialGraph(N))
    A, y, _ = make_problem()
    opt = bf.optim.DistributedNeighborAllreduceOptimizer(
        optax.sgd(0.05), compression="sparse:0.25",
        num_steps_per_communication=4)
    params, _ = run_training(opt, A, y, steps=1200)
    assert global_mse(params["w"], A, y) < 0.05
    w = np.asarray(params["w"])
    spread = np.abs(w - w.mean(axis=0, keepdims=True)).max()
    assert spread < 0.1, f"aliased rotation left coords unmixed: {spread}"


def test_sparse_compression_malformed_fraction_rejected():
    bf.init(lambda: topo.ExponentialGraph(N))
    A, y, _ = make_problem()
    params = {"w": jnp.asarray(
        np.random.RandomState(1).randn(N, DIM, 1) * 2.0)}
    for bad in ("sparse:abc", "sparse", "sparse:0", "sparse:1.5"):
        opt = bf.optim.DistributedNeighborAllreduceOptimizer(
            optax.sgd(0.05), compression=bad)
        with pytest.raises(ValueError, match="frac|fraction"):
            opt.step(params, grad_fn(A, y)(params), opt.init(params))


def test_compression_string_validated_even_for_empty_communication():
    """Malformed/rejected compression strings fail fast regardless of the
    communication type — and a valid compression on empty communication
    keeps the identity fast path (no wasted wrap)."""
    from bluefog_tpu.optim import functional as F
    bf.init(lambda: topo.ExponentialGraph(N))
    ident = F.make_combiner(F.CommunicationType.empty, axis_name="bf_rank")
    for bad in ("sparse:abc", "sparse", "topk:0.25", "garbage"):
        with pytest.raises(ValueError):
            F.compress_combiner(ident, bad)
    for ok in ("bf16", "sparse:0.25", "none"):
        assert F.compress_combiner(ident, ok).identity, ok


def test_step_returns_when_the_step_before_is_over(monkeypatch):
    """``step()`` launches its program and waits for the step BEFORE it:
    the host runs one step ahead of the device and no further (so that a
    device near its memory's end is never asked for a third tree of
    gradients).  Held by what the call waits on: nothing at the first step,
    then a leaf of the parameters the previous call returned, inside the
    span ``bf.optim.wait``."""
    from bluefog_tpu.utils import timeline
    bf.init()
    opt = bf.optim.DistributedAdaptThenCombineOptimizer(optax.sgd(0.1))
    params = {"w": jnp.ones((N, DIM, 1)), "b": jnp.zeros((N, 1))}
    state = opt.init(params)
    waited, spans = [], []
    ready = jax.block_until_ready
    monkeypatch.setattr(jax, "block_until_ready",
                        lambda x: waited.append(x) or ready(x))
    timeline.set_op_span_hook(lambda op, phase, s: spans.append((op, phase)))
    try:
        returned = []
        for _ in range(3):
            params, state = opt.step(params, params, state)
            returned.append(params)
    finally:
        timeline.set_op_span_hook(None)
    # the smallest leaf of the step before: ready when its program is
    assert len(waited) == 2
    assert waited[0] is returned[0]["b"] and waited[1] is returned[1]["b"]
    assert spans.count(("optim", "wait")) == 2
    assert spans.count(("optim", "step")) == 3
