"""The comparisons that decide ``correct``.  They run after the last timed
phase and are not part of ``setup_s``.

* ``mixing``: the library's eager averaging op on rank-major test rows
  against ``W_t @ x``, ``W_t`` written out in ``reference/mixing_<name>.py``.
* ``step``: two ``opt.step`` calls on the cell's real tree (seeded values
  that differ by rank, the optimizer's own step counter choosing the phase)
  against ``W_t @`` a hand-written update (``reference/optim_<name>.py``).
* ``model``: loss and gradients of the system's gradient program against the
  configuration's plain float32 reference on a small seeded sample.
* ``programs``: collectives in the two compiled programs; ``placement``: one
  rank row per chip; ``losses``: finite and falling.

Tolerances are written beside each check with their reason.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.build import effective_hyper
from benchmark.programs import collective_counts

# mixing: float32 averaging of values of order n; 1e-5 relative is a few
# float32 roundings, and a wrong weight or peer is off by order 1.
MIXING_RTOL, MIXING_ATOL = 1e-5, 1e-6

# step: the largest error of a leaf after two steps, as a share of the size
# of one base-optimizer update of that leaf.  Float32 rounding of the
# parameters (|p| ~ 0.1: 1e-8) against an update of 3e-4 (AdamW) or more is
# about 1e-4 of an update; an update computed otherwise (a wrong bias
# correction, decay, momentum or phase) is off by a few percent of one at
# the least.  1e-3 separates the two.
STEP_TOL = 1e-3
_P_SCALE = 0.1

# model: relative error of the loss, and of each sampled gradient leaf in the
# 2-norm, between the program (bfloat16 compute, float32 weights) and the
# float32 reference on the same weights and sample.  The bounds belong to the
# configuration (``model_check`` in its file, with the reason and the errors
# a chip run measured): how far bfloat16 rounding grows depends on the depth
# and on the normalisation layers it passes through.
MODEL_GRAD_LEAVES = 8
MODEL_NOISE = 0.02


class Failed(AssertionError):
    pass


def _require(cond, msg):
    if not cond:
        raise Failed(msg)


# --- mixing -----------------------------------------------------------------

def mixing(job, mix_ref) -> dict:
    """The library's eager op that the mixing's file names as its twin
    (``eager``) against the matrices the same file writes out."""
    n = job.n
    rng = np.random.default_rng(job.seed)
    x = (np.arange(n)[:, None] + rng.normal(size=(n, 8))).astype(np.float32)
    phases = mix_ref.period(n)
    for t in range(2 * phases):
        got = mix_ref.eager(x, t)
        want = mix_ref.matrix(n, t) @ x
        _require(np.allclose(got, want, rtol=MIXING_RTOL, atol=MIXING_ATOL),
                 f"mixing: step {t}: max |got - W_t x| = "
                 f"{np.abs(got - want).max():.3g}")
    return {"phases": phases}


# --- step -------------------------------------------------------------------

def mix(w: np.ndarray, x):
    """``W @ x`` over the rank axis of a rank-major array, one roll per
    non-zero diagonal of ``W``."""
    n, out = w.shape[0], 0.0
    for shift in range(n):
        coef = np.array([w[i, (i - shift) % n] for i in range(n)])
        if coef.any():
            coef = coef.reshape((n,) + (1,) * (x.ndim - 1)).astype(x.dtype)
            out = out + coef * jnp.roll(x, shift, axis=0)
    return out


def _groups(sizes, limit: int = 2 ** 28) -> list:
    """Consecutive leaves in groups ``(start, stop)`` of at most ``limit``
    elements a rank (a larger leaf is a group of its own): a small tree is
    one group and one compile, and a large one never holds more than a
    group's regenerated values beside the result."""
    groups, start = [], 0
    while start < len(sizes):
        stop, total = start + 1, sizes[start]
        while stop < len(sizes) and total + sizes[stop] <= limit:
            total += sizes[stop]
            stop += 1
        groups.append((start, stop))
        start = stop
    return groups


def _seeded_flat(key, tag, first, total: int, scale):
    """``total`` uniform float32 values in ``(-scale, scale)`` that depend
    on nothing but the key, the tag and the group's first leaf."""
    key = jax.random.fold_in(jax.random.fold_in(key, tag), first)
    return scale * jax.random.uniform(key, (total,), jnp.float32, -1.0, 1.0)


def seeded_leaves(key, tag, shapes, scale) -> list:
    """One rank's seeded leaves of the given shapes, each group of leaves
    cut from one flat draw."""
    sizes = [int(np.prod(s)) for s in shapes]
    out = []
    for a, b in _groups(sizes):
        flat = _seeded_flat(key, tag, a, sum(sizes[a:b]), scale)
        cuts = np.cumsum([0] + sizes[a:b])
        out += [flat[cuts[i]:cuts[i + 1]].reshape(shapes[a + i])
                for i in range(b - a)]
    return out


def step(job, opt_ref, mix_ref, steps: int = 2) -> dict:
    """On a closed job: the check's own trees take the place of the job's,
    so it needs no more memory than the timed step did."""
    import bluefog_tpu as bf
    n, traffic = job.n, job.cell.traffic
    hyper = effective_hyper(traffic["optimizer"], n)
    order = traffic["order"]
    _require(order in ("atc", "awc", "gradient_allreduce"),
             f"step: unknown order {order!r}")
    leaves, treedef = jax.tree.flatten(
        jax.eval_shape(job.fresh, job.keys)[0])
    _require(all(x.dtype == jnp.float32 for x in leaves),
             "step: the check draws float32 values; a leaf is not float32")
    shapes = [x.shape[1:] for x in leaves]
    sizes = [int(np.prod(s)) for s in shapes]
    opt = job.opt
    keys = np.asarray(jax.random.split(
        jax.random.PRNGKey(job.seed + 0x5eed), n))
    gen = bf.rank_map(lambda k, tag, scale: treedef.unflatten(
        seeded_leaves(k, tag, shapes, scale)))

    def tree(tag, scale):
        return gen(keys, np.full(n, tag, np.int32),
                   np.full(n, scale, np.float32))

    params = tree(0, _P_SCALE)
    state = opt.init(params)
    t0 = int(np.asarray(state.step).reshape(-1)[0])
    for k in range(1, steps + 1):
        params, state = opt.step(params, tree(k, 1.0), state)
    del state

    @functools.partial(jax.jit, static_argnames=("first",))
    def group_error(gots, keys, *, first):
        """(max |got - expected|, size of the first base update) over one
        group of rank-major leaves, everything regenerated from the seed and
        worked on as one flat rank-major array."""
        got = jnp.concatenate([g.reshape(n, -1) for g in gots], axis=1)
        gen = lambda tag, scale: jax.vmap(lambda k: _seeded_flat(  # noqa
            k, tag, first, got.shape[1], jnp.float32(scale)))(keys)
        p = gen(0, _P_SCALE)
        s, size = opt_ref.init(p), None
        for k in range(1, steps + 1):
            w, g = mix_ref.matrix(n, t0 + k - 1), gen(k, 1.0)
            if order == "awc":
                p = mix(w, p)
            if order == "gradient_allreduce":
                g = mix(np.full((n, n), 1.0 / n), g)
            moved, s = opt_ref.update(p, g, s, hyper)
            if size is None:
                size = jnp.abs(moved - p).max()
            p = mix(w, moved) if order == "atc" else moved
        return jnp.abs(got - p).max(), size

    got, worst = jax.tree.leaves(params), 0.0
    groups = _groups(sizes)
    for a, b in groups:
        err, size = group_error(got[a:b], keys, first=a)
        share = float(err) / max(float(size), 1e-30)
        _require(share <= STEP_TOL,
                 f"step: leaves {a}..{b - 1}: error {float(err):.3g} is "
                 f"{share:.3g} of one update ({float(size):.3g}) after "
                 f"{steps} steps from counter {t0}")
        worst = max(worst, share)
    return {"leaves": len(got), "groups": len(groups),
            "worst_share_of_update": worst, "first_counter": t0}


# --- model ------------------------------------------------------------------

def rank_rows(tree, r: int = 0):
    """Rank ``r``'s rows of a rank-major tree as its device holds them,
    leading axis of one and all: no copy is made."""
    def rows(x):
        for shard in x.addressable_shards:
            if (shard.index[0].start or 0) == r:
                return shard.data
        raise ValueError(f"no addressable shard holds rank {r}")
    return jax.tree.map(rows, tree)


def model(job, task, reference) -> dict:
    """On a closed job, at its seeded initial weights plus seeded uniform
    noise of ``MODEL_NOISE`` (no leaf stays exactly zero: the last batch-norm
    scale of a residual block starts there), so that the point of comparison
    does not depend on how many steps the window trained."""
    import bluefog_tpu as bf
    cell = job.cell
    loss_rtol = cell.config["model_check"]["loss_rtol"]
    grad_rtol = cell.config["model_check"]["grad_rtol"]
    sample_batch = task.check_batch(cell.traffic["batch"])
    batch = bf.rank_map(lambda k: task.make_batch(
        k[1], cell.config, sample_batch))(job.keys)
    params, aux = job.fresh(job.keys)
    params = bf.rank_map(lambda k, p: jax.tree.map(
        jnp.add, p, jax.tree.unflatten(jax.tree.structure(p), seeded_leaves(
            k[1], 3, [x.shape for x in jax.tree.leaves(p)], MODEL_NOISE))
    ))(job.keys, params)
    (loss, _), grads = job.vgrad(params, aux, *batch)
    ref_fn = jax.value_and_grad(
        functools.partial(reference.loss, cfg=cell.config), has_aux=True)

    @jax.jit
    def compare(params, aux, batch, grads):
        """The reference's loss, and per leaf the relative 2-norm error of
        the program's gradient, on rank 0's rows and on its device."""
        row = functools.partial(jax.tree.map, lambda x: x[0])
        (ref_loss, _), want = ref_fn(row(params), row(aux), *row(batch))
        return ref_loss, [
            jnp.linalg.norm((g[0] - w).ravel()) / jnp.linalg.norm(w.ravel())
            for g, w in zip(jax.tree.leaves(grads), jax.tree.leaves(want))]

    with jax.default_matmul_precision("highest"):
        ref_loss, errs = compare(*rank_rows((params, aux, batch, grads)))
    errs = np.asarray(errs, np.float64)
    loss0, ref_loss = float(np.asarray(loss)[0]), float(ref_loss)
    loss_err = abs(loss0 - ref_loss) / abs(ref_loss)
    rng = np.random.default_rng(job.seed)
    sample = sorted(map(int, rng.choice(
        len(errs), size=min(MODEL_GRAD_LEAVES, len(errs)), replace=False)))
    report = {"loss": loss0, "reference_loss": ref_loss,
              "loss_rel_err": loss_err, "loss_bound": loss_rtol,
              "sampled_leaves": sample,
              "grad_rel_err_sampled": float(errs[sample].max()),
              "grad_bound": grad_rtol,
              "grad_rel_err_median_of_all": float(np.median(errs)),
              "grad_rel_err_max_of_all": float(errs.max())}
    _require(np.isfinite(loss_err) and loss_err <= loss_rtol
             and np.isfinite(errs[sample]).all()
             and errs[sample].max() <= grad_rtol, f"model: {report}")
    return report


# --- programs, placement, losses ----------------------------------------------

def programs(job, compiled: dict) -> dict:
    """The traffic file says what each program may and must hold, and from
    how many chips on (``from_chips``, default 1) the rule applies."""
    want = job.cell.traffic["programs"]
    counts = {name: collective_counts(prog)
              for name, prog in compiled.items()}
    for name, rule in want.items():
        if job.n < rule.get("from_chips", 1):
            continue
        for kind, least in rule.get("min", {}).items():
            _require(counts[name][kind] >= least,
                     f"programs: {name} holds {counts[name][kind]} {kind}, "
                     f"fewer than {least}")
        for kind, most in rule.get("max", {}).items():
            _require(counts[name][kind] <= most,
                     f"programs: {name} holds {counts[name][kind]} {kind}, "
                     f"more than {most}")
    return counts


def placement(job) -> int:
    """Every leaf spans the phase's chips, one rank row per chip."""
    leaves = jax.tree.leaves((job.params, job.state, job.aux))
    for leaf in leaves:
        _require(len(leaf.sharding.device_set) == job.n,
                 f"placement: leaf {leaf.shape} on "
                 f"{len(leaf.sharding.device_set)} of {job.n} chips")
        for shard in leaf.addressable_shards:
            _require(shard.data.shape[0] == 1,
                     f"placement: leaf {leaf.shape}: {shard.device} holds "
                     f"{shard.data.shape[0]} rank rows")
    return len(leaves)


def losses(phase) -> dict:
    """Finite at every step, and lower over the phase's last quarter than
    over its first (means over steps and ranks)."""
    x = phase.losses
    _require(len(x) >= 8, f"losses: phase {phase.name} has {len(x)} steps")
    _require(np.isfinite(x).all(),
             f"losses: phase {phase.name}: {int((~np.isfinite(x)).sum())} "
             "values are not finite")
    q = len(x) // 4
    first, last = float(x[:q].mean()), float(x[-q:].mean())
    _require(last < first, f"losses: phase {phase.name}: {first:.4f} over "
             f"the first quarter, {last:.4f} over the last")
    return {"first": first, "last": last}
