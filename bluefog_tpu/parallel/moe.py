"""Expert parallelism: switch-routed mixture-of-experts over a mesh axis.

Completes the framework's parallelism axes (dp / sp / tp / pp / **ep**) —
all beyond the data-parallel-only reference (SURVEY §2.3).  One expert per
``axis_name`` rank; routing is top-1 (Switch Transformer) with a static
capacity so every shape is fixed under jit:

* every rank evaluates the (replicated) router identically — SPMD means
  there is nothing to negotiate, the dispatch plan is born globally
  consistent (the same fact that deletes the reference's coordinator);
* rank e gathers its tokens with its row of the dense one-hot dispatch
  tensor (a matmul, MXU-friendly, no gather/scatter), applies its local
  expert, and scatters results back with the transpose;
* one ``psum`` over the axis recombines — overflow tokens (beyond
  ``capacity``) drop to zero exactly as in Switch.

Differentiable end-to-end (the straight-through is unnecessary: top-1
selection is constant w.r.t. parameters at a point; router gradients flow
through the combine weights as in the Switch paper).

**Dropless top-k** (``dropless_moe``, what ``models.transformer.DroplessMoe``
runs): the other semantics, for the open sparse LMs of today (OLMoE,
Moonlight, ...).  Every token goes to its ``k`` most probable experts and no
expert has a capacity, so nothing is ever dropped and the cost is linear in
the ``T * k`` assignments: the assignments are sorted by expert (stable), the
rows gathered into that order, three grouped matmuls over the ragged groups
(``grouped_matmul``: the Pallas kernels of ``jax.experimental.pallas.ops.
tpu.megablox``) apply the SwiGLU experts, and the rows return to token
order weighted by their router probabilities.  All experts live on the
calling rank; the permutations are gathers in both directions (their
transposes are written out), so no scatter runs forward or backward.
Without a gate (``dropless_moe(..., gate=None, ...)``: Nemotron-H's
``relu2`` experts, ``down(relu(up x)^2)``) the same path runs two grouped
products a pass.

**A held share** (``dropless_moe(..., held=(first, count))``): the layer is
told which contiguous range of the ``E`` experts this rank holds, routes over
all ``E``, and computes the part of the result its own ``count`` experts
give.  It is what expert parallelism asks of the layer, without the
exchange: summed over the ranks that share a layer the parts are the whole
layer's result.  The sort puts the held experts' rows in one run of the
order, whose place is data (``load[:first].sum()``) and whose length is
bounded by a shape: the share works on a **window** of ``C =
held_window(T * k, count, E)`` rows from the run's start (twice the even
share, in whole 256-row tiles).  It gathers ``C`` rows of ``x``, runs the
three grouped products and the SwiGLU over ``(C, .)`` arrays with the held
groups' sizes, and sums the ``(C, d)`` result into its tokens: the
window's rows know their tokens, so they are sorted by assignment (``C``
keys), gathered into that order and added a tile of tokens at a time by
the Pallas kernel ``bf_moe_token_sum`` (the transpose of the dispatch's
gather, and the same function gives ``d x`` from the window's ``d rows``);
no array has a row for an assignment that is not held, and a token without
a held assignment gets zero.  A step whose run is longer
than ``C`` takes, behind one ``lax.cond``, the overflow branch: the same
work window after window until the run is covered, the windows' sums added
in float32, so nothing is dropped at
any load and no capacity enters the result.  Where ``C`` would be all ``T *
k`` rows (half the experts held, or all) there is no window and the layer is
the dropless layer above.  **Sigmoid scoring with a bias**
(``route_topk(..., scoring="sigmoid", bias=b)``, DeepSeek-V3's ``noaux_tc``):
the experts are chosen on ``sigmoid(logits) + b`` and weighted by the scores
alone; ``b`` is state that no gradient reaches and ``update_router_bias``
moves toward an even load.  With ``n_group`` > 1 the choice is
**group-limited**: the experts lie in consecutive groups, a token keeps its
``topk_group`` best groups (by the sum of a group's two largest ``score +
b``) and chooses among their experts alone.
"""

from __future__ import annotations

import functools
import importlib
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from bluefog_tpu.utils import telemetry, timeline

__all__ = ["moe_apply", "switch_dispatch", "load_balance_loss",
           "topk_load_balance_loss", "router_z_loss", "route_topk",
           "grouped_matmul", "dropless_moe", "observe_load", "Routing",
           "update_router_bias", "held_window"]

# The module, not the package's ``gmm`` (a custom_vjp of its own that names
# nothing): the kernels are called unjitted so that each takes the name of
# the scope around it (``bf_moe_gmm_fwd.3``), as ``bf_flash_*`` do.
_megablox = importlib.import_module(
    "jax.experimental.pallas.ops.tpu.megablox.gmm")


def load_balance_loss(router_logits, valid=None):
    """Switch Transformer load-balancing auxiliary loss (eq. 4):
    ``E * sum_e f_e * p_e`` over (T, E) logits, where ``f_e`` is the
    fraction of tokens whose top-1 choice is expert ``e`` (PRE-capacity —
    the clipped dispatch would saturate the gradient exactly when an
    expert overflows) and ``p_e`` the mean router probability.  Minimized
    (= 1) at a perfectly uniform router; add ``aux_weight *`` this to the
    training loss or the router collapses onto one expert and capacity
    drops become the only regularizer.

    ``valid``: optional (T,) {0,1} mask — padding tokens are excluded from
    both statistics (an all-zero pad row argmaxes to expert 0 and would
    otherwise skew the balance toward it)."""
    E = router_logits.shape[-1]
    probs = jax.nn.softmax(router_logits, axis=-1)
    routed = jax.nn.one_hot(jnp.argmax(probs, axis=-1), E,
                            dtype=probs.dtype)
    if valid is None:
        return E * (routed.mean(axis=0) * probs.mean(axis=0)).sum()
    w = valid.astype(probs.dtype)
    w = w / jnp.maximum(w.sum(), 1.0)
    f = (routed * w[:, None]).sum(axis=0)
    p = (probs * w[:, None]).sum(axis=0)
    return E * (f * p).sum()


def topk_load_balance_loss(probs, load, k: int):
    """Load-balancing loss of a top-``k`` router: ``E * sum_e f_e * P_e``,
    ``f_e`` the share of the ``T * k`` assignments that went to expert ``e``
    (``load`` holds their counts, a constant for the gradient) and ``P_e``
    the mean router probability over the ``T`` tokens of ``probs`` (T, E).
    1 at a uniform router for every ``k``; with ``k == 1`` it is
    ``load_balance_loss``."""
    T, E = probs.shape
    f = lax.stop_gradient(load.astype(jnp.float32)) / (T * k)
    return E * (f * probs.mean(axis=0)).sum()


def router_z_loss(router_logits):
    """Router z-loss (ST-MoE): the mean over tokens of
    ``logsumexp(router_logits)^2``; keeps the logits small enough for the
    float32 softmax to stay exact."""
    z = jax.nn.logsumexp(router_logits.astype(jnp.float32), axis=-1)
    return jnp.mean(z * z)


def switch_dispatch(router_logits, n_experts: int, capacity: int,
                    valid=None):
    """Top-1 dispatch plan: ``(combine, dispatch)`` from (T, E) logits.

    ``dispatch``: (E, C, T) one-hot — slot c of expert e takes token t.
    ``combine``: (T, E, C) — same plan weighted by the router probability
    (the gradient path to the router).  Tokens past ``capacity`` for their
    expert are dropped (all-zero rows), per Switch semantics.  ``valid``:
    optional (T,) {0,1} mask — padding tokens route nowhere and occupy no
    capacity slots (otherwise an all-zero pad row argmaxes to expert 0 and
    real tokens behind it in the queue get dropped)."""
    gate, keep, slot = _plan(router_logits, n_experts, capacity, valid)
    dispatch = jnp.einsum("te,tc->ect", keep, slot)         # (E, C, T)
    combine = jnp.einsum("t,ect->tec", gate, dispatch)      # (T, E, C)
    return combine, dispatch


def _plan(router_logits, n_experts: int, capacity: int, valid=None):
    """O(T*(E+C)) routing plan: ``(gate, keep, slot)`` — ranks slice out
    their own expert's column instead of materializing the dense (E, C, T)
    tensors (which are O(T^2) at the default capacity)."""
    T, E = router_logits.shape
    if E != n_experts:
        raise ValueError(
            f"router emits {E} expert logits but the layer has "
            f"{n_experts} experts")
    probs = jax.nn.softmax(router_logits, axis=-1)          # (T, E)
    expert = jnp.argmax(probs, axis=-1)                     # (T,)
    onehot = jax.nn.one_hot(expert, E, dtype=probs.dtype)   # (T, E)
    if valid is not None:
        onehot = onehot * valid.astype(probs.dtype)[:, None]
    # Position of each token within its expert's queue (masked-out tokens
    # are routed nowhere, so they consume no queue positions).
    pos = (jnp.cumsum(onehot, axis=0) - onehot) * onehot    # (T, E)
    keep = (pos < capacity) * onehot                        # (T, E)
    slot = jax.nn.one_hot(pos.sum(-1), capacity,
                          dtype=probs.dtype)                # (T, C)
    gate = (probs * keep).sum(-1)                           # (T,)
    return gate, keep, slot


def moe_apply(expert_fn, expert_params, x, router_logits, *,
              axis_name: str = "ep", capacity: int | None = None,
              with_aux: bool = False):
    """Apply this rank's expert within an ``axis_name``-wide MoE layer.

    ``x``: (T, d) tokens, replicated over the axis; ``router_logits``:
    (T, E) from a replicated router (E == axis size).  Returns (T, d) — the
    gated sum of expert outputs, identical on every rank — or, with
    ``with_aux=True``, ``(y, aux)`` where ``aux`` is the Switch
    load-balancing loss for these logits (replicated; fold
    ``aux_weight * aux`` into the training objective).

    **Gradient convention.**  When every rank computes the SAME loss from
    the psum'd output, differentiating that per-rank loss inflates every
    gradient by ``axis_size`` (the psum transpose psums the replicated
    cotangent — you are differentiating the sum of E identical losses).
    Divide the per-rank objective by ``lax.axis_size(axis_name)``:
    local-expert grads then come out exact with no extra collective, and
    replicated-router grads are exact after a ``psum`` over the axis.
    Report ``lax.psum(loss, axis_name)`` to recover the true loss value.
    ``tests/test_parallel.py::test_moe_composes_with_decentralized_dp``
    pins this against a dense single-device oracle."""
    E = lax.axis_size(axis_name)
    me = lax.axis_index(axis_name)
    T = x.shape[0]
    if capacity is None:
        capacity = max(1, (2 * T) // E)                     # factor-2 default

    gate, keep, slot = _plan(router_logits, E, capacity)
    my_keep = lax.dynamic_index_in_dim(keep, me, axis=1,
                                       keepdims=False)       # (T,)
    my_dispatch = slot.T * my_keep[None, :]                  # (C, T)
    xe = my_dispatch @ x                                     # (C, d)
    ye = expert_fn(expert_params, xe)                        # (C, d)
    my_combine = (gate * my_keep)[:, None] * slot            # (T, C)
    y = my_combine @ ye                                      # (T, d)
    y = lax.psum(y, axis_name)
    if with_aux:
        return y, load_balance_loss(router_logits)
    return y


# --- dropless top-k ----------------------------------------------------------

class Routing(NamedTuple):
    """A top-``k`` routing plan over ``T`` tokens and ``E`` experts."""
    weights: jax.Array      # (T, k) float32: the chosen probabilities
    experts: jax.Array      # (T, k) int32: the chosen experts
    order: jax.Array        # (T*k,) int32: assignments (t*k + j) by expert
    inverse: jax.Array      # (T*k,) int32: where each assignment landed
    load: jax.Array         # (E,) int32: assignments per expert, sums to T*k
    balance_loss: jax.Array  # topk_load_balance_loss of this plan
    z_loss: jax.Array        # router_z_loss of these logits


def route_topk(router_logits, k: int, *, renormalize: bool = False,
               scoring: str = "softmax", bias=None, scale: float = 1.0,
               renorm_eps: float = 1e-20, n_group: int = 1,
               topk_group: int = 1) -> Routing:
    """Top-``k`` routing of (T, E) logits with no capacity: softmax in
    float32, the ``k`` largest probabilities of each token (left as they
    are, or ``renormalize``d to sum to one), the ``T * k`` assignments
    sorted by expert with ties in token order, and the per-expert counts.

    ``scoring="sigmoid"``: the scores are ``sigmoid(logits)``, the choice is
    made on ``scores + bias`` (``bias``: (E,), a constant for the gradient;
    None = no bias) and the weights are the chosen experts' scores alone,
    ``renormalize``d as ``w / (sum w + renorm_eps)`` (DeepSeek-V3's 1e-20;
    LFM2 takes 1e-6).  Under either scoring the weights are multiplied by
    ``scale`` last (a routed scaling factor).
    ``balance_loss`` then takes the scores normalised over the experts as
    its probabilities.

    ``n_group`` > 1 (sigmoid scoring; DeepSeek-V3's group-limited choice):
    the ``E`` experts lie in ``n_group`` consecutive groups of ``E /
    n_group``; a group's score is the sum of its two largest ``scores +
    bias``, a token's ``topk_group`` best groups stay (ties to the lower
    group) and the ``k`` experts are chosen among theirs alone: no expert of
    another group can be chosen, whatever its score.  The weights are the
    chosen experts' scores as above.  With one group the staged program is
    the one without the argument.  ``bf_moe_route_groups_total{kept}``
    counts the groups a traced route scores, at trace time."""
    if scoring not in ("softmax", "sigmoid"):
        raise ValueError(f"scoring {scoring!r} not in ('softmax', 'sigmoid')")
    T, E = router_logits.shape
    if n_group > 1 and (scoring != "sigmoid" or E % n_group
                        or E // n_group < 2
                        or not 1 <= topk_group <= n_group
                        or topk_group * (E // n_group) < k):
        raise ValueError(
            f"route_topk: n_group {n_group} with topk_group {topk_group} "
            f"needs scoring='sigmoid' (got {scoring!r}), {E} experts in "
            f"whole groups of two or more, and top-{k} to fit the groups "
            "that stay")
    logits = router_logits.astype(jnp.float32)
    if scoring == "sigmoid":
        scores = jax.nn.sigmoid(logits)
        chosen_on = scores if bias is None else scores + lax.stop_gradient(
            bias.astype(jnp.float32))
        if n_group > 1:
            telemetry.inc("bf_moe_route_groups_total", n_group,
                          kept=str(topk_group))
            by_group = chosen_on.reshape(T, n_group, E // n_group)
            _, kept = lax.top_k(lax.top_k(by_group, 2)[0].sum(axis=-1),
                                topk_group)
            stays = jax.nn.one_hot(kept, n_group, dtype=jnp.bool_).any(
                axis=1)
            chosen_on = jnp.where(stays[..., None], by_group,
                                  -jnp.inf).reshape(T, E)
        _, experts = lax.top_k(chosen_on, k)
        weights = jnp.take_along_axis(scores, experts, axis=-1)
        if renormalize:
            weights = weights / (weights.sum(axis=-1, keepdims=True)
                                 + renorm_eps)
        weights = weights * scale
        probs = scores / scores.sum(axis=-1, keepdims=True)
    else:
        if bias is not None:
            raise ValueError("route_topk: a bias belongs to "
                             "scoring='sigmoid'")
        probs = jax.nn.softmax(logits, axis=-1)
        weights, experts = lax.top_k(probs, k)
        if renormalize:
            weights = weights / weights.sum(axis=-1, keepdims=True)
        if scale != 1.0:        # 1.0: the program it always was, to the bit
            weights = weights * scale
    flat = experts.reshape(-1)
    order = jnp.argsort(flat, stable=True).astype(jnp.int32)
    inverse = jnp.argsort(order).astype(jnp.int32)
    load = jax.nn.one_hot(flat, E, dtype=jnp.int32).sum(axis=0)
    return Routing(weights, experts.astype(jnp.int32), order, inverse, load,
                   topk_load_balance_loss(probs, load, k),
                   router_z_loss(logits))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _take_rows(x, index, back, fan: int):
    """``x[index // fan]``: each row of ``x`` appears ``fan`` times in the
    result, and ``back`` says where (``index[back] == arange``).  The
    transpose is then a gather too, ``dy[back]`` summed over each row's
    ``fan`` copies, where autodiff would emit a scatter-add."""
    return x[index // fan] if fan > 1 else x[index]


def _take_rows_fwd(x, index, back, fan):
    return _take_rows(x, index, back, fan), (index, back)


def _take_rows_bwd(fan, res, dy):
    _, back = res
    dx = dy[back]
    if fan > 1:
        dx = dx.reshape(-1, fan, dx.shape[-1]).sum(axis=1, dtype=dy.dtype)
    return dx, None, None


_take_rows.defvjp(_take_rows_fwd, _take_rows_bwd)


def _fit(dim: int, target: int) -> int:
    """The largest tile of ``target``, ``target / 2``, ... down to 128 that
    divides ``dim``; failing that the whole dimension."""
    tile = target
    while tile >= 128:
        if dim % tile == 0:
            return tile
        tile //= 2
    return dim


def _tiles(rows: int, k: int, n: int, dtype, *, whole_k: bool) -> tuple:
    """Row, ``k`` and ``n`` tiles of a kernel over ``rows`` padded rows.
    One v5e chip, 65536 rows in 64 groups, 2048 x 1024 and 1024 x 2048
    bfloat16 matrices (PR 26 trial): 256 rows against the whole ``k`` and
    1024 columns took 1.99 / 2.08 ms a product where megablox's default
    128 x 128 x 128 took 28 and 512 x 1024 x 1024 2.41; the matrices'
    gradient keeps a float32 ``k x n`` tile and took 2.81 ms at 256 x 1024
    x 1024.  Wider tiles overflow the 16 MiB of scoped VMEM; float32
    operands get half the width."""
    wide = 4096 // jnp.dtype(dtype).itemsize
    tm = next((t for t in (256, 128, 64, 32, 16, 8) if rows % t == 0))
    return tm, _fit(k, wide if whole_k else wide // 2), _fit(n, wide // 2)


def held_window(assignments: int, count: int, experts: int) -> int:
    """Rows of the window a share of ``count`` of ``experts`` experts works
    on when the layer routes ``assignments`` rows: twice the even share, in
    whole 256-row tiles (the kernels' row tile, ``_tiles``), at most all of
    them.  Twice, because a router that balances stays inside it (a layer
    of ``lfm2-s8192-1chip`` sent its held eighth at most 1.26 times the
    even share in PR 35's runs, and ``update_router_bias`` pulls every
    expert toward the mean) while the window is still a quarter of the rows
    at an eighth held; a step over it takes several windows and loses
    nothing (5 of 160 layer-steps in ``xing4-s4096-1chip``, whose router
    collapses on uniform ids).
    ``dropless_moe`` sizes its window and ``observe_load`` counts the
    overflows by this one rule."""
    even = -(-assignments * count // experts)
    return min(assignments, -(-2 * even // 256) * 256)


def _kernel(name: str, fn, *args, **kw):
    """Stage one Pallas kernel under its name.  This runs where the Python
    wrapper runs, at trace time: once a shape behind ``jax.jit``, once a
    call for a bare kernel, and each staging is one Mosaic lowering."""
    telemetry.inc("bf_kernel_stagings_total", kernel=name)
    with timeline.device_scope(name):
        return fn(*args, **kw)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def grouped_matmul(rows, matrices, group_sizes, first: int = None):
    """``rows[i] @ matrices[group of row i]``: (m, k) rows sorted by group,
    (E, k, n) matrices, (E,) int32 ``group_sizes`` that sum to ``m``;
    returns (m, n) in ``rows``' dtype.  The matrices are cast to that dtype
    for the product and accumulated in float32; their gradient comes back
    in their own dtype.  An empty group costs nothing and a full one takes
    every row: no capacity.

    ``first``: the matrices are those of the groups ``first .. first + n``
    of the ``E`` in ``group_sizes`` (a held share, ``n <= E``): the kernels
    visit those groups' rows only, the result's other rows are zero, and
    the matrices' gradient has ``n`` groups.

    Three Pallas kernels (megablox), named in the compiled program
    ``bf_moe_gmm_fwd`` (also the remat recompute), ``bf_moe_gmm_dlhs`` (the
    rows' gradient: the same kernel on the transposed matrices) and
    ``bf_moe_gmm_drhs`` (the matrices' gradient, each group's rows
    contracted).  Off the TPU they run in the Pallas interpreter."""
    return _grouped_fwd(rows, matrices, group_sizes, first)[0]


def _padded(rows):
    """``rows`` padded to a whole number of the smallest row tile; the
    padding belongs to no group and no kernel visits it."""
    pad = -rows.shape[0] % 8
    return jnp.pad(rows, ((0, pad), (0, 0))) if pad else rows


def _interpret(x) -> bool:
    from bluefog_tpu.ops.flash_attention import platform_in_use
    return platform_in_use(x) != "tpu"


def _share(first) -> dict:
    """megablox's own argument for a held share of the groups."""
    return {} if first is None else {"group_offset": jnp.int32(first)}


def _grouped_fwd(rows, matrices, group_sizes, first):
    m, k = rows.shape
    n = matrices.shape[2]
    lhs, rhs = _padded(rows), matrices.astype(rows.dtype)
    out = _kernel(
        "bf_moe_gmm_fwd", _megablox.gmm.__wrapped__, lhs, rhs, group_sizes,
        rows.dtype, _tiles(lhs.shape[0], k, n, rows.dtype, whole_k=True),
        interpret=_interpret(rows), **_share(first))
    # the empty array carries the matrices' dtype to the backward pass
    return out[:m], (lhs, rhs, group_sizes, jnp.zeros((0,), matrices.dtype))


def _grouped_bwd(first, res, d_out):
    lhs, rhs, group_sizes, like = res
    share = _share(first)
    held = {} if first is None else {"num_actual_groups": rhs.shape[0]}
    interpret = _interpret(d_out)
    m, (k, n) = d_out.shape[0], rhs.shape[1:]
    d_out = _padded(d_out)
    padded = lhs.shape[0]
    d_rows = _kernel(
        "bf_moe_gmm_dlhs", _megablox.gmm.__wrapped__, d_out, rhs,
        group_sizes, lhs.dtype,
        _tiles(padded, n, k, lhs.dtype, whole_k=True),
        transpose_rhs=True, interpret=interpret, **share)
    d_matrices = _kernel(
        "bf_moe_gmm_drhs", _megablox.tgmm.__wrapped__, lhs.swapaxes(0, 1),
        d_out, group_sizes, like.dtype,
        _tiles(padded, k, n, lhs.dtype, whole_k=False), interpret=interpret,
        **share, **held)
    return d_rows[:m], d_matrices, None


grouped_matmul.defvjp(_grouped_fwd, _grouped_bwd)

# The same two functions behind ``jax.jit`` for the held window, whose
# program calls each product from four places (the first pass, the remat
# recompute and both branches): a jitted function is traced and lowered
# once for each shape, where a kernel that is called bare is lowered again
# at every call (0.6 s each in a warm ``setup_s`` of the benchmark).
_product = jax.jit(_grouped_fwd, static_argnums=3)
_product_transpose = jax.jit(_grouped_bwd, static_argnums=0)


def update_router_bias(bias, load, rate: float):
    """The rule that moves a sigmoid router's bias (DeepSeek-V3's
    auxiliary-loss-free balancing): ``bias_e + rate * sign(mean(load) -
    load_e)`` over the counts of all ``E`` experts, so an expert that took
    fewer assignments than the mean is chosen more often at the next step.
    No gradient is involved; the caller keeps the bias outside the
    parameter tree."""
    load = lax.stop_gradient(load).astype(jnp.float32)
    return bias + rate * jnp.sign(load.mean(axis=-1, keepdims=True) - load)


def dropless_moe(x, router_logits, gate, up, down, *, k: int,
                 renormalize: bool = False, held: tuple = None,
                 scoring: str = "softmax", bias=None, scale: float = 1.0,
                 renorm_eps: float = 1e-20, n_group: int = 1,
                 topk_group: int = 1):
    """A dropless top-``k`` mixture of experts on this rank.

    ``x``: (T, d) tokens in the compute dtype; ``router_logits``: (T, E);
    ``gate``, ``up``: (E, d, f) and ``down``: (E, f, d), cast to ``x``'s
    dtype for the products (float32 accumulation: ``grouped_matmul``).
    Returns ``(y, routing)`` with ``y = sum_j w_j * down_j(silu(gate_j x) *
    up_j x)`` over each token's ``k`` experts, (T, d) in ``x``'s dtype, and
    the ``Routing`` that holds the per-expert load and the two auxiliary
    losses.  No token is
    dropped at any load: an expert takes as many rows as choose it.

    ``gate=None``: the experts are un-gated, ``down_j(relu(up_j x)^2)``
    (Nemotron-H's ``relu2``): the same dispatch, window and combine with two
    grouped products a pass where a gated expert has three (``_activate``).

    ``held=(first, count)``: this rank holds the experts ``first .. first +
    count`` of the ``E`` the router scores, and ``gate``, ``up``, ``down``
    are theirs, ``(count, ...)``.  The routing is over all ``E`` (and
    ``routing.load`` counts all ``E``); an assignment to an absent expert
    adds nothing to ``y`` and nothing is computed or stored for it.  The
    dispatch, the products, the SwiGLU, the combine and their transposes
    work on a window of ``held_window(T * k, count, E)`` rows of the sorted
    assignments (twice the even share ``T * k * count / E``, in 256-row
    tiles), which begins at the held experts' first row: the token side is
    the window's rows summed into their tokens (``_sum_to_tokens``: a sort
    of ``C`` keys, a gather of ``C`` rows and the kernel
    ``bf_moe_token_sum``; float32 weights, a token's rows summed in
    float32 and rounded once), so no ``(T * k, d)`` array exists and
    ``routing.inverse`` has no reader.  When a step
    sends the held experts more rows than the window has, the layer covers
    their run window after window (the overflow branch of its one
    ``lax.cond``), each window summed into its tokens and the windows'
    sums added in float32: ``y`` and every gradient are then what one
    window as long as the run would give, but for the order of a token's
    float32 terms, and nothing is as long as the run.  With a window
    as large as ``T * k`` (``count * 2 >= E``) the layer is the one it is
    with ``held=None`` over the held matrices.  ``None``: all ``E`` are
    held.  ``scoring``, ``bias``, ``scale``, ``renorm_eps``, ``n_group``
    and ``topk_group`` go to ``route_topk`` (the group-limited choice is
    made over all ``E``, before the held share's window).

    Device scopes: ``bf.moe.route``, ``bf.moe.dispatch``, ``bf.moe.experts``
    and ``bf.moe.combine``; the caller wraps the layer (the router matmul
    included) in ``bf.moe``.  ``bf_moe_token_sum`` runs under
    ``bf.moe.combine`` (the combine) and ``bf.moe.dispatch`` (``d x``)."""
    T, d = x.shape
    dt = x.dtype
    first = None
    if held is not None:
        first, count = held
        E = router_logits.shape[-1]
        if not (0 <= first and 0 < count and first + count <= E
                and all(m.shape[0] == count for m in (gate, up, down)
                        if m is not None)):
            raise ValueError(
                f"dropless_moe: held={held} of {E} experts with "
                f"{[m.shape[0] for m in (gate, up, down) if m is not None]}"
                " matrices")
    with timeline.device_scope("bf.moe.route"):
        plan = route_topk(router_logits, k, renormalize=renormalize,
                          scoring=scoring, bias=bias, scale=scale,
                          renorm_eps=renorm_eps, n_group=n_group,
                          topk_group=topk_group)
    if held is not None and held_window(T * k, count, E) < T * k:
        return _held_share(x, plan.weights, gate, up, down, plan.order,
                           plan.load, k, first), plan
    return _whole_share(x, plan.weights, gate, up, down, plan.order,
                        plan.inverse, plan.load, k, first), plan


def _combine(back, weights, dtype):
    """A token's ``k`` rows of ``back`` (T*k, d), weighted and summed in
    float32 in slot order."""
    T, k = weights.shape
    y = (back.reshape(T, k, -1).astype(jnp.float32)
         * weights[..., None]).sum(axis=1)
    return y.astype(dtype)


def _activate(g, u):
    """What goes into an expert's down projection: SwiGLU of the gate and
    up products, or without a gate (``g`` None) the squared ReLU of the up
    product alone."""
    if g is None:
        return jnp.square(jax.nn.relu(u))
    return jax.nn.silu(g) * u


def _whole_share(x, weights, gate, up, down, order, inverse, load, k: int,
                 first):
    """The held experts' part of the layer's result over all ``T * k``
    sorted assignments: every row is gathered, the products visit the held
    groups' rows (``first``: ``grouped_matmul``'s) and zero the others, and
    every row returns to its token.  The layer where all experts are held,
    or so many that the window would be the whole order."""
    with timeline.device_scope("bf.moe.dispatch"):
        rows = _take_rows(x, order, inverse, k)                 # (T*k, d)
    with timeline.device_scope("bf.moe.experts"):
        g = None if gate is None else grouped_matmul(rows, gate, load,
                                                     first)
        u = grouped_matmul(rows, up, load, first)
        out = grouped_matmul(_activate(g, u), down, load, first)  # (T*k, d)
    with timeline.device_scope("bf.moe.combine"):
        return _combine(_take_rows(out, inverse, order, 1), weights,
                        x.dtype)


class _Window(NamedTuple):
    """``C`` consecutive rows of the sorted assignments, from inside the
    held experts' run on."""
    start: jax.Array    # () int32: its first row in the sorted order
    rows: jax.Array     # () int32: how many of its rows the run covers
    sizes: jax.Array    # (count,) int32: the held groups' rows inside it


class _Saved(NamedTuple):
    """What a window's transpose needs of its forward, all ``(C, .)``."""
    win: jax.Array      # (C,) int32: its assignments, ``t * k + j``
    rows: jax.Array     # (C, d): their tokens' rows of ``x``
    g: jax.Array        # (C, f); None for un-gated experts
    u: jax.Array        # (C, f)
    out: jax.Array      # (C, d): the experts' result


def _held_rows(load, first: int, count: int):
    with timeline.device_scope("bf.moe.dispatch"):
        return load[first:first + count].sum()


def _window(load, first: int, count: int, size: int, index) -> _Window:
    """The ``index``-th window of ``size`` rows over the held run; the
    first is the whole run when the run fits."""
    with timeline.device_scope("bf.moe.dispatch"):
        held = load[first:first + count]
        ends, lo = jnp.cumsum(held), index * size
        sizes = (jnp.clip(ends, lo, lo + size)
                 - jnp.clip(ends - held, lo, lo + size))
        return _Window(load[:first].sum() + lo, sizes.sum(), sizes)


# Tokens, rows and columns a tile of ``bf_moe_token_sum``.  One v5e chip,
# 16384 window rows of 2048 into 16384 tokens, weighted (PR 44 trial, a call
# from the host included): 128 x 128 took 0.37 ms, 128 x 256 0.46, 256 x 256
# 0.49, 256 x 512 0.74: the (tokens, rows) matrix is built on the vector
# unit once a visit and a piece, so small tiles win; the width stays whole
# (3584 columns fit the 16 MiB of scoped VMEM at these tiles).
_TILES = (128, 128, 4096)


def _token_sum_kernel(offsets, groups, tiles, tok, *refs, tt: int, tm: int):
    """One visit of the grid: the rows of row tile ``tiles[i]`` that belong
    to the tokens of token tile ``groups[i]``, added to the tile's float32
    sums by a product with a ``(tt, tm)`` matrix that holds row ``r``'s
    weight at ``(its token, r)`` and zeros elsewhere.  A weight goes in as
    three bfloat16 pieces that sum to it exactly, so a product of bfloat16
    rows is the float32 product; wider rows take one product at the highest
    precision.  Rows of the tile outside the token tile's run are zeroed
    and not multiplied by zero: what they hold reaches no sum."""
    *scale, rows, out, acc = refs
    i, last = pl.program_id(1), pl.num_programs(1) - 1
    group = groups[i]

    @pl.when((i == 0) | (groups[jnp.maximum(i - 1, 0)] != group))
    def _first_visit():
        acc[...] = jnp.zeros_like(acc)

    lo, hi = offsets[group], offsets[group + 1]

    @pl.when(hi > lo)
    def _add():
        at = tiles[i] * tm + lax.broadcasted_iota(jnp.int32, (tm, 1), 0)
        v = rows[...]
        v = jnp.where((at >= lo) & (at < hi), v, jnp.zeros((), v.dtype))
        mine = (tok[...] - group * tt
                == lax.broadcasted_iota(jnp.int32, (tt, tm), 0))
        if v.dtype != jnp.bfloat16:
            weight = scale[0][...] if scale else 1.0
            acc[...] += jnp.dot(
                jnp.where(mine, weight, 0.0).astype(jnp.float32),
                v.astype(jnp.float32), precision=lax.Precision.HIGHEST,
                preferred_element_type=jnp.float32)
            return
        rest = scale[0][...] if scale else jnp.ones((1, tm), jnp.float32)
        for _ in range(3 if scale else 1):
            piece = rest.astype(v.dtype).astype(jnp.float32)
            acc[...] += jnp.dot(jnp.where(mine, piece, 0.0).astype(v.dtype),
                                v, preferred_element_type=jnp.float32)
            rest = rest - piece

    @pl.when((i == last) | (groups[jnp.minimum(i + 1, last)] != group))
    def _last_visit():
        out[...] = acc[...].astype(out.dtype)


def _visits(tok, groups: int, tt: int, tm: int) -> tuple:
    """The grid of ``bf_moe_token_sum`` over ``tok`` (ascending tokens of
    the rows, a whole number of row tiles): ``(offsets, group, tile)`` and
    how many visits there are.  Token tile ``g`` owns the rows
    ``offsets[g] .. offsets[g + 1]``; visit ``i`` adds the rows of row tile
    ``tile[i]`` to token tile ``group[i]``; a token tile's visits follow one
    another, one for each row tile its rows touch, and a token tile without
    rows is visited once, to be written as zeros.  (megablox's
    ``make_group_metadata`` gives the same lists; its histogram and repeats
    take three times as long to trace and lower as the kernel's body.)"""
    tiles = tok.shape[0] // tm
    sizes = jax.nn.one_hot(tok // tt, groups, dtype=jnp.int32).sum(axis=0)
    ends = lax.cumsum(sizes)
    offsets = jnp.concatenate([jnp.zeros((1,), jnp.int32), ends])
    first = jnp.minimum(offsets[:-1] // tm, tiles - 1)
    last = jnp.where(sizes > 0, (ends - 1) // tm, first)
    begin = lax.cumsum(last - first + 1) - (last - first + 1)
    at = jnp.arange(tiles + groups - 1, dtype=jnp.int32)
    group = jnp.minimum(
        (begin[None, :] <= at[:, None]).sum(axis=1, dtype=jnp.int32) - 1,
        groups - 1)
    tile = jnp.minimum(first[group] + at - begin[group], tiles - 1)
    return (offsets, group, tile), (last - first + 1).sum()


@functools.partial(jax.jit, static_argnames=("tokens", "dtype", "interpret"))
def _token_sum(tok, scale, rows, *, tokens: int, dtype, interpret: bool):
    """``out[t] = sum of scale[c] * rows[c] over the c with tok[c] == t``
    (``scale`` None: of ``rows[c]``) for ``tok`` (C,) ascending, ``rows``
    (C, d); a ``tok`` of ``tokens`` or more names no token.  (tokens, d) in
    ``dtype``, summed in float32 and rounded once.  The Pallas kernel
    ``bf_moe_token_sum``: the grid walks the row tiles a tile of tokens at
    a time (``_visits``), so its cost is that of ``C`` rows.  Behind
    ``jax.jit`` for the reason ``_product`` is."""
    telemetry.inc("bf_kernel_stagings_total", kernel="bf_moe_token_sum")
    (C, d), T = rows.shape, tokens
    tt, tm, tn = _TILES
    tt = min(tt, -(-T // 8) * 8)    # no larger than the tokens need
    tn = d if d <= tn else _fit(d, tn // 2)
    G = -(-T // tt)
    pad = -C % tm
    tok = jnp.pad(jnp.where(tok < T, tok, G * tt), (0, pad),
                  constant_values=G * tt)
    metadata, visits = _visits(tok, G, tt, tm)
    by_row = pl.BlockSpec((1, tm), lambda n, i, offsets, groups, tiles:
                          (0, tiles[i]))
    operands = [tok[None, :]] + ([] if scale is None else [
        jnp.pad(scale.astype(jnp.float32), (0, pad))[None, :]])
    out = pl.pallas_call(
        functools.partial(_token_sum_kernel, tt=tt, tm=tm),
        name="bf_moe_token_sum",
        out_shape=jax.ShapeDtypeStruct((G * tt, d), dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(d // tn, visits),
            in_specs=[by_row] * len(operands) + [pl.BlockSpec(
                (tm, tn), lambda n, i, offsets, groups, tiles:
                (tiles[i], n))],
            out_specs=pl.BlockSpec(
                (tt, tn), lambda n, i, offsets, groups, tiles:
                (groups[i], n)),
            scratch_shapes=[pltpu.VMEM((tt, tn), jnp.float32)]),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(*metadata, *operands, jnp.pad(rows, ((0, pad), (0, 0))))
    return out[:T]


def _held_keys(win, w: _Window, assignments: int):
    """The window's assignments with its rows past the held run renamed to
    assignments that do not exist (each its own, all after the last)."""
    at = jnp.arange(win.shape[0], dtype=jnp.int32)
    return jnp.where(at < w.rows, win, assignments + at)


def _sum_to_tokens(values, win, w: _Window, tokens: int, k: int, dtype,
                   weights=None):
    """``out[t] = sum of values[c] over the rows c < w.rows of the window
    with win[c] // k == t``, the transpose of the dispatch's ``x[win //
    k]``: ``values`` (C, d) in window order, ``win`` (C,) its assignments,
    (tokens, d) in ``dtype``.  With ``weights`` (tokens, k) float32, row
    ``c`` is weighted by its assignment's; a token's rows are weighted and
    summed in float32 and rounded once.  The window's rows are sorted by
    assignment (``C`` keys: token order, slot order inside a token),
    gathered into that order and summed by ``_token_sum``; whatever the
    kernels left in the rows past ``w.rows`` reaches no sum."""
    C = values.shape[0]
    operands = (_held_keys(win, w, tokens * k),
                jnp.arange(C, dtype=jnp.int32))
    if weights is not None:
        operands += (weights.reshape(-1)[win],)
    key, at, *scale = lax.sort(operands, num_keys=1)
    return _token_sum(key // k, *(scale or [None]), values[at],
                      tokens=tokens, dtype=dtype,
                      interpret=_interpret(values))


def _cast(matrices, dtype) -> tuple:
    """The held matrices in the compute dtype, once for both branches
    (None stays None: un-gated experts have no gate)."""
    with timeline.device_scope("bf.moe.experts"):
        return tuple(None if m is None else m.astype(dtype)
                     for m in matrices)


def _sizes(cast, order, load) -> tuple:
    """How many experts are held, and the window's rows."""
    count = cast[2].shape[0]
    return count, held_window(order.shape[0], count, load.shape[0])


def _window_experts(x, cast, order, load, k, first, index=0):
    """One window's rows through the held experts (``cast``: their gate,
    up and down matrices in ``x``'s dtype, the gate None where they have
    none)."""
    count, size = _sizes(cast, order, load)
    w = _window(load, first, count, size, index)
    with timeline.device_scope("bf.moe.dispatch"):
        # padded, so that a window at the tail of the order is not clamped
        # back; what it takes past the run belongs to no group
        win = lax.dynamic_slice(
            jnp.concatenate([order, jnp.zeros((size,), order.dtype)]),
            (w.start,), (size,))
        rows = x[win // k]                                      # (C, d)
    with timeline.device_scope("bf.moe.experts"):
        g = None if cast[0] is None else _product(rows, cast[0], w.sizes,
                                                  None)[0]
        u = _product(rows, cast[1], w.sizes, None)[0]
        out = _product(_activate(g, u), cast[2], w.sizes, None)[0]
    return w, _Saved(win, rows, g, u, out)


def _window_transpose(saved: _Saved, w: _Window, cast, like, dy, weights, k):
    """A window's part of the transpose, in window order: the gradients
    of its rows (C, d) and of its assignments' weights (C,), and of the
    three matrices (in the dtype of ``like``, their own)."""
    with timeline.device_scope("bf.moe.combine"):
        dyw = dy[saved.win // k].astype(jnp.float32)            # (C, d)
        d_out = (dyw * weights.reshape(-1)[saved.win][:, None]).astype(
            dy.dtype)
        d_weights = (dyw * saved.out.astype(jnp.float32)).sum(axis=-1)
    with timeline.device_scope("bf.moe.experts"):
        h, activate_t = jax.vjp(_activate, saved.g, saved.u)
        d_h, d_down, _ = _product_transpose(
            None, (h, cast[2], w.sizes, like), d_out)
        d_g, d_u = activate_t(d_h)
        d_rows, d_up, _ = _product_transpose(
            None, (saved.rows, cast[1], w.sizes, like), d_u)
        d_gate = None
        if d_g is not None:
            d_rows_g, d_gate, _ = _product_transpose(
                None, (saved.rows, cast[0], w.sizes, like), d_g)
            d_rows = d_rows + d_rows_g
    return d_rows, d_weights, d_gate, d_up, d_down


def _to_tokens(d_rows, d_weights, win, w: _Window, weights, dtype):
    """The gradients of ``x`` and of the weights from those of a window's
    rows and assignments: the rows summed into their tokens, the ``C``
    scalars placed at their assignments (one each) among zeros."""
    T, k = weights.shape
    with timeline.device_scope("bf.moe.combine"):
        d_weights = jnp.zeros((T * k,), d_weights.dtype).at[
            _held_keys(win, w, T * k)].set(
                d_weights, mode="drop", unique_indices=True).reshape(T, k)
    with timeline.device_scope("bf.moe.dispatch"):
        return _sum_to_tokens(d_rows, win, w, T, k, dtype), d_weights


def _window_fwd(x, weights, cast, order, load, k, first):
    """The share of a step whose held run fits the window."""
    w, saved = _window_experts(x, cast, order, load, k, first)
    with timeline.device_scope("bf.moe.combine"):
        return _sum_to_tokens(saved.out, saved.win, w, x.shape[0], k,
                              x.dtype, weights), saved


def _window_bwd(weights, cast, like, load, k, first, saved, dy):
    w = _window(load, first, cast[2].shape[0], saved.win.shape[0], 0)
    d_rows, d_weights, *d_matrices = _window_transpose(
        saved, w, cast, like, dy, weights, k)
    return _to_tokens(d_rows, d_weights, saved.win, w, weights,
                      dy.dtype) + tuple(d_matrices)


def _overflow(load, first: int, count: int, size: int, window, *carry):
    """``window(index, *carry)`` over the windows of ``size`` rows that
    the held run takes."""
    with timeline.device_scope("bf.moe.dispatch"):
        windows = (_held_rows(load, first, count) + size - 1) // size
    return lax.fori_loop(0, windows, lambda i, c: window(i, *c), carry)


def _overflow_fwd(x, weights, cast, order, load, k, first):
    """The share of a step whose held run is longer than the window:
    window after window, each summed into its tokens as the one window is
    and the windows' sums added in float32, so no row is dropped at any
    load, a token's rows are still rounded once, and nothing is as long as
    the run."""
    count, size = _sizes(cast, order, load)

    def window(index, y):
        w, saved = _window_experts(x, cast, order, load, k, first, index)
        with timeline.device_scope("bf.moe.combine"):
            return y + _sum_to_tokens(saved.out, saved.win, w, x.shape[0],
                                      k, jnp.float32, weights),
    y, = _overflow(load, first, count, size, window,
                   jnp.zeros(x.shape, jnp.float32))
    with timeline.device_scope("bf.moe.combine"):
        return y.astype(x.dtype)


def _overflow_bwd(x, weights, cast, like, order, load, k, first, dy):
    """Its transpose keeps nothing of its forward: each window is run
    again (the step is the rare one), and the windows' parts of every
    gradient add up in float32."""
    count, size = _sizes(cast, order, load)

    def window(index, *sums):
        w, saved = _window_experts(x, cast, order, load, k, first, index)
        d_rows, d_weights, *d_matrices = _window_transpose(
            saved, w, cast, like, dy, weights, k)
        mine = _to_tokens(d_rows, d_weights, saved.win, w, weights,
                          jnp.float32) + tuple(d_matrices)
        with timeline.device_scope("bf.moe.experts"):
            return tuple(None if a is None else a + b
                         for a, b in zip(sums, mine))
    d_x, *rest = _overflow(
        load, first, count, size, window, jnp.zeros(x.shape, jnp.float32),
        jnp.zeros(weights.shape, jnp.float32),
        *(None if m is None else jnp.zeros(m.shape, like.dtype)
          for m in cast))
    with timeline.device_scope("bf.moe.dispatch"):
        return (d_x.astype(dy.dtype),) + tuple(rest)


# What every operation of a branch carries in its ``op_name``, forward,
# recompute and transpose: a device trace says by them which path a step's
# held share took, without the loads on the host.  Not of the shape
# ``bf.<layer>.<name>``, so an operation's scope stays the one it had.
HELD_WINDOW = "bf_moe_held_window"
HELD_OVERFLOW = "bf_moe_held_overflow"


def _marked(marker: str, branch):
    def run():
        with timeline.device_scope(marker):
            return branch()
    return run


def _branch(load, first, count, size, window, overflow):
    """One ``lax.cond`` on whether the held run fits the window.  The
    barrier keeps the two branches' ends apart: XLA moves a tail that both
    share out of the conditional, and a branch then hands over the tail's
    operands where it hands over its result."""
    with timeline.device_scope("bf.moe.dispatch"):
        return lax.cond(
            _held_rows(load, first, count) <= size,
            _marked(HELD_WINDOW, window),
            _marked(HELD_OVERFLOW,
                    lambda: lax.optimization_barrier(overflow())))


def _held_args(x, weights, gate, up, down, order, load, k, first):
    """What both branches take, and the window's size."""
    return ((x, weights, _cast((gate, up, down), x.dtype), order, load, k,
             first),
            held_window(order.shape[0], down.shape[0], load.shape[0]))


@functools.partial(jax.custom_vjp, nondiff_argnums=(7, 8))
def _held_share(x, weights, gate, up, down, order, load, k: int,
                first: int):
    """The held experts' part of the layer's result over a window of the
    sorted assignments (module docstring).  The transpose is written out,
    because autodiff of a ``lax.cond`` keeps the residuals of both
    branches: here the window branch keeps its ``(C, .)`` arrays and the
    overflow branch nothing."""
    args, size = _held_args(x, weights, gate, up, down, order, load, k,
                            first)
    return _branch(load, first, down.shape[0], size,
                   lambda: _window_fwd(*args)[0],
                   lambda: _overflow_fwd(*args))


def _held_fwd(x, weights, gate, up, down, order, load, k, first):
    args, size = _held_args(x, weights, gate, up, down, order, load, k,
                            first)
    saved = jax.eval_shape(lambda: _window_fwd(*args)[1])
    y, saved = _branch(
        load, first, down.shape[0], size, lambda: _window_fwd(*args),
        lambda: (_overflow_fwd(*args), jax.tree.map(
            lambda s: jnp.zeros(s.shape, s.dtype), saved)))
    # the empty array carries the matrices' dtype to the backward pass
    return y, (args[:5], jnp.zeros((0,), down.dtype), saved)


def _held_bwd(k, first, res, dy):
    (x, weights, cast, order, load), like, saved = res
    return _branch(
        load, first, cast[2].shape[0], saved.win.shape[0],
        lambda: _window_bwd(weights, cast, like, load, k, first, saved, dy),
        lambda: _overflow_bwd(x, weights, cast, like, order, load, k, first,
                              dy)) + (None, None)


_held_share.defvjp(_held_fwd, _held_bwd)


def observe_load(load, held: tuple = None) -> float:
    """Publish per-expert assignment counts a training loop has fetched:
    ``load`` is ``(E,)`` or ``(..., E)`` (layers, ranks: summed).  Adds to
    the counter ``bf_moe_assignments_total{expert}`` and sets the gauge
    ``bf_moe_load_max_over_mean`` (1 at a perfectly even load, ``E`` when
    one expert takes everything), which it returns.  With ``held=(first,
    count)`` it also adds the assignments that went to the held experts to
    ``bf_moe_held_assignments_total`` and sets the gauge
    ``bf_moe_held_share`` to their share of all (``count / E`` at an even
    router); and, each row of ``load`` being one layer's counts of one
    step, adds the rows whose held count is over the layer's window
    (``held_window`` of the row's sum: the steps that took the whole path)
    to ``bf_moe_held_window_overflow_total`` and sets the gauge
    ``bf_moe_held_window_fill`` to the largest held count over its window
    (0.5 at an even router with an eighth held)."""
    by_row = np.asarray(load, np.float64)
    by_row = by_row.reshape(-1, by_row.shape[-1])
    counts = by_row.sum(axis=0)
    for e, n in enumerate(counts):
        telemetry.inc("bf_moe_assignments_total", float(n), expert=str(e))
    if held is not None:
        first, count = held
        mine = by_row[:, first:first + count].sum(axis=1)
        telemetry.inc("bf_moe_held_assignments_total", float(mine.sum()))
        telemetry.set_gauge("bf_moe_held_share",
                            float(mine.sum() / counts.sum())
                            if counts.sum() > 0 else 0.0)
        window = np.array([max(1, held_window(int(n), count, len(counts)))
                           for n in by_row.sum(axis=1)])
        telemetry.inc("bf_moe_held_window_overflow_total",
                      float((mine > window).sum()))
        telemetry.set_gauge("bf_moe_held_window_fill",
                            float((mine / window).max()))
    mean = counts.mean()
    ratio = float(counts.max() / mean) if mean > 0 else 0.0
    telemetry.set_gauge("bf_moe_load_max_over_mean", ratio)
    return ratio
