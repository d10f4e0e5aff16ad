"""The chip check: does the gossip trainer still start on the TPU?

    python chip_smoke.py

One process, every chip ``jax.devices()`` returns (1 or 4 on a v5e host), no
child process, no ``JAX_PLATFORMS`` / ``XLA_FLAGS`` of its own.  It drives the
collective (in-program gossip) training path through the entry points a user
calls — ``bf.init()``, ``bf.rank_map``, ``bf.optim.Distributed*Optimizer`` —
at full model width, and fails unless what comes out is right:

* trainer: ResNet-50 / 1000 classes / 224 px / bf16 / 64 images per chip
  (the reference protocol, BASELINE.md), 8 ATC steps of dynamic one-peer
  neighbor averaging; loss finite and falling;
* gossip: every state leaf lives one rank row per chip, peak memory is
  balanced, ``neighbor_allreduce`` equals the mixing matrix times ``x``, the
  local-gradient program holds no cross-device collective and the optimizer
  step holds the permutes;
* kernels: the Pallas flash attention forward and backward compiled by Mosaic
  (``interpret=False``) against dense attention on the device, a 2048-wide
  2-layer ``TransformerLM`` taking 3 steps through the same optimizer, and
  ring attention over all chips under the default ``check_vma=True``.

Without a TPU it exits non-zero before doing any work: there is no CPU mode
(``tests/test_chip_smoke.py`` rehearses the legs at toy sizes on a CPU mesh).
The last line of stdout is the verdict, one JSON object with exactly these
keys: ``{"ok": true, "device": {"platform", "kind", "count"}}``; the line
before it, ``summary: {...}``, carries the per-leg results, compile seconds
and the compile cache's directory and entries.  Out of scope: the one-sided window
family, which stages through the host on a TPU (not run on the chip).
"""

from __future__ import annotations

import json
import os
import re
import sys
import time
import traceback

_COLLECTIVE = re.compile(
    r"\s(all-gather|all-reduce|all-to-all|collective-permute)(?:-start)?\(")
_MOSAIC = "tpu_custom_call"

# Stated tolerances, max-abs against f32 dense attention on the device for
# N(0,1) bf16 inputs.  Results are bf16: gradients reach ~4, where one bf16
# step is 0.031, and a v5e run of these shapes measured 0.011 forward and
# 0.031 backward (CHANGES.md, PR 21).
FLASH_TOL_FWD = 0.05
FLASH_TOL_BWD = 0.1
RING_TOL = 0.05


def collective_counts(compiled) -> dict:
    """Cross-device collective ops in a compiled program's HLO, by kind."""
    counts = dict.fromkeys(
        ("all-gather", "all-reduce", "all-to-all", "collective-permute"), 0)
    for m in _COLLECTIVE.finditer(compiled.as_text()):
        counts[m.group(1)] += 1
    return counts


class CompileClock:
    """Seconds spent in XLA compilation (persistent-cache retrieval
    included) and the cache's hits and misses, from jax's own monitoring
    events."""

    def __init__(self):
        import jax.monitoring
        self.seconds = 0.0
        self.hits = 0
        self.misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, seconds, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += seconds

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1


def _check(cond, msg):
    if not cond:
        raise AssertionError(msg)


def _rank_keys(seed):
    """One PRNG key per rank (ranks start from different seeds)."""
    import jax
    import bluefog_tpu as bf
    return jax.random.split(jax.random.PRNGKey(seed), bf.size())


def _gossip_optimizer():
    import optax
    import bluefog_tpu as bf
    return bf.optim.DistributedAdaptThenCombineOptimizer(
        optax.sgd(0.0125 * bf.size(), momentum=0.9),
        bf.optim.CommunicationType.neighbor_allreduce,
        use_dynamic_topology=True, donate=True)


# ---------------------------------------------------------------------------
# Leg 1: the trainer
# ---------------------------------------------------------------------------

def trainer_leg(*, model=None, image=224, batch=64, classes=1000, steps=8):
    """``steps`` of gradient -> ``opt.step`` on one fixed seeded batch per
    rank.  Returns what the gossip leg inspects."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax
    import bluefog_tpu as bf
    from bluefog_tpu import models

    if model is None:
        model = models.ResNet50(num_classes=classes, dtype=jnp.bfloat16)
    keys = _rank_keys(0)

    def make(key):
        k_init, k_x, k_y = jax.random.split(key, 3)
        x = jax.random.normal(k_x, (batch, image, image, 3), model.dtype)
        y = jax.random.randint(k_y, (batch,), 0, classes)
        return model.init(k_init, x[:2]), x, y

    variables, x, y = bf.rank_map(make)(keys)
    params, bstats = variables["params"], variables["batch_stats"]

    def loss_fn(p, bs, x, y):
        logits, new = model.apply({"params": p, "batch_stats": bs}, x,
                                  train=True, mutable=["batch_stats"])
        return optax.softmax_cross_entropy_with_integer_labels(
            logits, y).mean(), new["batch_stats"]

    vgrad = bf.rank_map(jax.value_and_grad(loss_fn, has_aux=True))
    opt = _gossip_optimizer()
    state = opt.init(params)
    losses = []
    for step in range(steps):
        t0 = time.perf_counter()
        (loss, bstats), grads = vgrad(params, bstats, x, y)
        params, state = opt.step(params, grads, state)
        loss = np.asarray(bf.to_numpy(loss), np.float64)
        jax.block_until_ready(params)
        print(f"  step {step + 1}: loss per rank {np.round(loss, 4).tolist()}"
              f"  ({time.perf_counter() - t0:.2f}s wall, compile included)",
              flush=True)
        _check(np.isfinite(loss).all(), f"step {step + 1}: loss {loss}")
        losses.append(float(loss.mean()))
    _check(losses[-1] < losses[0],
           f"loss did not fall: step 1 {losses[0]:.4f} -> "
           f"step {steps} {losses[-1]:.4f}")
    # The two programs of the step, as compiled for these very arguments
    # (gradients have the parameters' shapes and placement; lowering does
    # not consume the donated buffers).
    return {"losses": losses, "trees": (params, state, bstats),
            "grad_program": vgrad.lower(params, bstats, x, y).compile(),
            "step_program": opt._step_callable(with_weights=False).lower(
                params, params, state).compile()}


# ---------------------------------------------------------------------------
# Leg 2: the chips really take part
# ---------------------------------------------------------------------------

def check_placement(trees) -> int:
    """Every leaf spans all ranks' devices, one rank row per shard, and the
    optimizer's per-call re-placement of such a leaf moves no bytes (same
    device buffers before and after)."""
    import jax
    import bluefog_tpu as bf
    from bluefog_tpu import basics
    n = bf.size()
    buffers = lambda a: [  # noqa: E731
        (s.device, s.data.unsafe_buffer_pointer())
        for s in a.addressable_shards]
    leaves = jax.tree_util.tree_leaves(trees)
    for leaf in leaves:
        _check(len(leaf.sharding.device_set) == n,
               f"leaf {leaf.shape} on {len(leaf.sharding.device_set)} of "
               f"{n} devices")
        for shard in leaf.addressable_shards:
            _check(shard.data.shape[0] == 1,
                   f"leaf {leaf.shape}: shard on {shard.device} holds "
                   f"{shard.data.shape[0]} rank rows")
        _check(buffers(basics._place(leaf)) == buffers(leaf),
               f"re-placing an already placed leaf {leaf.shape} copied it")
    return len(leaves)


def check_mixing():
    """``bf.neighbor_allreduce`` and ``bf.dynamic_neighbor_allreduce`` on the
    rank-major array whose row ``i`` is ``i``, against numpy products with
    the mixing matrices read off ``bf.load_topology()``."""
    import numpy as np
    import bluefog_tpu as bf
    from bluefog_tpu import basics, topology_util
    n = bf.size()
    topo = bf.load_topology()
    x = np.repeat(np.arange(n, dtype=np.float32)[:, None], 8, axis=1)

    def receive_matrix(senders_of):
        """W[dst, src]: uniform 1/(indegree+1) over self and senders."""
        w = np.zeros((n, n))
        for dst in range(n):
            srcs = [dst] + senders_of(dst)
            w[dst, srcs] = 1.0 / len(srcs)
        return w

    if basics.is_topo_weighted():
        w = topology_util.weight_matrix(topo).T
    else:
        w = receive_matrix(lambda dst: topology_util.in_neighbor_ranks(
            topo, dst))
    got = np.asarray(bf.to_numpy(bf.neighbor_allreduce(x)))
    np.testing.assert_allclose(got, w @ x, rtol=1e-5, atol=1e-6,
                               err_msg="neighbor_allreduce != W @ x")
    phases = topology_util.dynamic_phase_table(topo)
    for step in range(2 * len(phases)):
        ph = phases[step % len(phases)]
        w = receive_matrix(ph.recv_from)
        got = np.asarray(bf.to_numpy(bf.dynamic_neighbor_allreduce(x, step)))
        np.testing.assert_allclose(
            got, w @ x, rtol=1e-5, atol=1e-6,
            err_msg=f"dynamic_neighbor_allreduce step {step} != W_t @ x")
    return len(phases)


def check_programs(grad_program, step_program) -> dict:
    """0 cross-device collectives in the local gradient; on more than one
    chip, at least one ``collective-permute`` in the optimizer step."""
    import bluefog_tpu as bf
    grad, step = (collective_counts(grad_program),
                  collective_counts(step_program))
    print(f"  collectives in the local-gradient program: {grad}")
    print(f"  collectives in the optimizer-step program: {step}")
    _check(not any(grad.values()),
           f"local-gradient program crosses devices: {grad}")
    if bf.size() > 1:
        _check(step["collective-permute"] >= 1,
               f"optimizer step holds no collective-permute: {step}")
    return {"grad": grad, "step": step}


def check_peak_memory(limit=1.25) -> list:
    """Per-device peak HBM, as the runtime reports it; balanced within
    ``limit`` across the chips."""
    import bluefog_tpu as bf
    peaks = []
    for d in bf.mesh().devices.flat:
        stats = d.memory_stats()
        _check(stats and "peak_bytes_in_use" in stats,
               f"{d}: no peak_bytes_in_use in memory_stats()")
        peaks.append(int(stats["peak_bytes_in_use"]))
        print(f"  {d}: peak {peaks[-1] / 2**30:.3f} GiB in use")
    _check(max(peaks) <= limit * min(peaks),
           f"peak memory max/min {max(peaks) / min(peaks):.2f} > {limit}")
    return peaks


# ---------------------------------------------------------------------------
# Leg 3: the kernels compile
# ---------------------------------------------------------------------------

def _dense_attention(q, k, v):
    import jax
    import jax.numpy as jnp
    import numpy as np
    S, D = q.shape[1], q.shape[-1]
    s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32), precision="highest") / np.sqrt(D)
    s = jnp.where(jnp.tril(jnp.ones((S, S), bool))[None, None], s, -1e30)
    return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1),
                      v.astype(jnp.float32), precision="highest")


def flash_leg(shape, *, interpret, block=1024):
    """Causal flash attention forward and backward at ``shape`` =
    (B, S, H, D), bf16, against dense attention computed on the device."""
    import jax
    import jax.numpy as jnp
    from bluefog_tpu.ops.flash_attention import flash_attention
    q, k, v, do = (jax.random.normal(kk, shape, jnp.bfloat16)
                   for kk in jax.random.split(jax.random.PRNGKey(1), 4))
    dof = do.astype(jnp.float32)

    def flash(q, k, v):
        return flash_attention(q, k, v, causal=True, block_q=block,
                               block_k=block, interpret=interpret)

    fwd = jax.jit(flash)
    bwd = jax.jit(jax.grad(
        lambda q, k, v: (flash(q, k, v).astype(jnp.float32) * dof).sum(),
        argnums=(0, 1, 2)))
    if not interpret:
        for name, f in (("forward", fwd), ("backward", bwd)):
            _check(_MOSAIC in f.lower(q, k, v).as_text(),
                   f"flash {name} at {shape}: no Mosaic custom call in the "
                   "lowering")
    err = lambda a, b: float(jnp.abs(  # noqa: E731
        a.astype(jnp.float32) - b.astype(jnp.float32)).max())
    e_fwd = err(fwd(q, k, v), jax.jit(_dense_attention)(q, k, v))
    ref = jax.jit(jax.grad(
        lambda q, k, v: (_dense_attention(q, k, v) * dof).sum(),
        argnums=(0, 1, 2)))(q, k, v)
    e_bwd = max(err(a, b) for a, b in zip(bwd(q, k, v), ref))
    print(f"  flash {shape} blocks {block}: max abs err forward {e_fwd:.4f} "
          f"(tol {FLASH_TOL_FWD}), backward {e_bwd:.4f} "
          f"(tol {FLASH_TOL_BWD})")
    _check(e_fwd <= FLASH_TOL_FWD, f"flash forward err {e_fwd} at {shape}")
    _check(e_bwd <= FLASH_TOL_BWD, f"flash backward err {e_bwd} at {shape}")
    return {"fwd_err": e_fwd, "bwd_err": e_bwd}


def lm_leg(*, interpret, width=2048, heads=16, seq=2048, vocab=32000,
           layers=2, batch=2, steps=3):
    """A ``TransformerLM`` (depth cut, width not) with the flash kernel,
    per-block remat and the chunked loss, through the gossip optimizer."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    import bluefog_tpu as bf
    from bluefog_tpu import models
    from bluefog_tpu.ops.chunked_loss import chunked_softmax_cross_entropy
    from bluefog_tpu.ops.flash_attention import flash_attention_impl

    cfg = models.TransformerConfig(
        vocab_size=vocab, num_layers=layers, num_heads=heads,
        embed_dim=width, max_seq_len=seq, remat=True)
    model = models.TransformerLM(
        cfg, attn_impl=flash_attention_impl(interpret=interpret))

    def make(key):
        k_init, k_tok = jax.random.split(key)
        tokens = jax.random.randint(k_tok, (batch, seq), 0, vocab)
        return model.init(k_init, tokens[:1])["params"], tokens

    params, tokens = bf.rank_map(make)(_rank_keys(2))

    def loss_fn(p, tokens):
        h = model.apply({"params": p}, tokens, return_hidden=True)
        return chunked_softmax_cross_entropy(
            h, p["lm_head"]["kernel"], jnp.roll(tokens, -1, axis=1))

    vgrad = bf.rank_map(jax.value_and_grad(loss_fn))
    if not interpret:
        _check(_MOSAIC in vgrad.lower(params, tokens).as_text(),
               "LM gradient: no Mosaic custom call in the lowering")
    opt = _gossip_optimizer()
    state = opt.init(params)
    losses = []
    for step in range(steps):
        loss, grads = vgrad(params, tokens)
        params, state = opt.step(params, grads, state)
        loss = np.asarray(bf.to_numpy(loss), np.float64)
        print(f"  LM step {step + 1}: loss per rank "
              f"{np.round(loss, 4).tolist()}", flush=True)
        _check(np.isfinite(loss).all(), f"LM step {step + 1}: loss {loss}")
        losses.append(float(loss.mean()))
    n_params = sum(int(np.prod(p.shape[1:]))
                   for p in jax.tree_util.tree_leaves(params))
    return {"losses": losses, "params": n_params}


def ring_leg(*, compiled, seq_per_chip=1024, heads=8, head_dim=128):
    """``parallel.ring_attention`` with the sequence split over every chip
    against ``local_attention``.  ``compiled``: the Mosaic kernels under the
    default ``check_vma=True``; otherwise the Pallas interpreter, whose
    in-kernel constants are not vma-tracked (``check_vma=False``)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, PartitionSpec as P
    import bluefog_tpu as bf
    from bluefog_tpu.models import local_attention
    from bluefog_tpu.parallel import ring_attention

    devices = list(bf.mesh().devices.flat)
    shape = (1, seq_per_chip * len(devices), heads, head_dim)
    q, k, v = (jax.random.normal(kk, shape, jnp.bfloat16)
               for kk in jax.random.split(jax.random.PRNGKey(3), 3))
    ring = jax.jit(jax.shard_map(
        lambda a, b, c: ring_attention(a, b, c, axis_name="sp", causal=True),
        mesh=Mesh(np.asarray(devices), ("sp",)),
        in_specs=(P(None, "sp"),) * 3, out_specs=P(None, "sp"),
        **({} if compiled else {"check_vma": False})))
    if compiled:
        _check(_MOSAIC in ring.lower(q, k, v).as_text(),
               "ring attention: no Mosaic custom call in the lowering")
    ref = local_attention(*(t.astype(jnp.float32) for t in (q, k, v)),
                          causal=True)
    e = float(jnp.abs(ring(q, k, v).astype(jnp.float32) - ref).max())
    print(f"  ring attention {shape} over {len(devices)} chip(s): max abs "
          f"err {e:.4f} (tol {RING_TOL})")
    _check(e <= RING_TOL, f"ring attention err {e}")
    return {"err": e}


# ---------------------------------------------------------------------------

def main() -> int:
    import jax
    if jax.default_backend() != "tpu":
        print("chip_smoke: jax found no TPU (default backend "
              f"{jax.default_backend()!r}); this check has no CPU mode",
              file=sys.stderr)
        return 2
    import jaxlib
    import bluefog_tpu as bf
    from bluefog_tpu import native

    t_start = time.perf_counter()
    clock = CompileClock()
    devices = jax.devices()
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    from importlib import metadata
    try:
        libtpu = metadata.version("libtpu")
    except metadata.PackageNotFoundError:
        libtpu = "not installed"
    print(f"device: {device}  jax {jax.__version__}  jaxlib "
          f"{jaxlib.__version__}  libtpu {libtpu}")
    print(f"native core: available={native.available()} "
          f"stale={native.is_stale()}")

    bf.init()
    n = bf.size()
    _check(n == len(devices), f"bf.size() {n} != {len(devices)} devices")
    cache_dir = jax.config.jax_compilation_cache_dir
    print(f"bf.init(): {n} rank(s), topology edges "
          f"{sorted(bf.load_topology().edges())}, compile cache {cache_dir}")

    legs, failed = {}, []

    def leg(name, fn):
        print(f"[{name}]", flush=True)
        t0 = time.perf_counter()
        try:
            out = fn()
        except Exception:  # noqa: BLE001 - recorded, and the run fails
            traceback.print_exc(file=sys.stdout)
            failed.append(name)
            out = None
        legs[name] = {"pass": name not in failed,
                      "seconds": round(time.perf_counter() - t0, 1)}
        print(f"[{name}] {'PASS' if legs[name]['pass'] else 'FAIL'} "
              f"in {legs[name]['seconds']}s", flush=True)
        return out

    trained = leg("trainer", trainer_leg)
    summary = {}

    def gossip():
        _check(trained is not None, "the trainer leg left nothing to inspect")
        leaves = check_placement(trained["trees"])
        print(f"  {leaves} leaves: one rank row per chip, re-placement "
              "moves no bytes")
        summary["peak_bytes"] = check_peak_memory()
        phases = check_mixing()
        print(f"  neighbor_allreduce == W @ x; dynamic == W_t @ x over "
              f"{phases} phase(s)")
        summary["collectives"] = check_programs(
            trained["grad_program"], trained["step_program"])

    leg("gossip", gossip)
    trained = None  # frees the ResNet state before the LM leg
    leg("flash_2048x16x128",
        lambda: flash_leg((1, 2048, 16, 128), interpret=False))
    leg("flash_8192x8x64",
        lambda: flash_leg((1, 8192, 8, 64), interpret=False))
    leg("lm_2048", lambda: lm_leg(interpret=False))
    leg("ring_attention", lambda: ring_leg(compiled=True))

    entries = len(os.listdir(cache_dir)) if cache_dir and os.path.isdir(
        cache_dir) else 0
    detail = {
        "n": n, "legs": legs,
        "compile_seconds": round(clock.seconds, 1),
        "cache": {"dir": cache_dir, "entries": entries,
                  "hits": clock.hits, "misses": clock.misses},
        "seconds": round(time.perf_counter() - t_start, 1), **summary,
    }
    print("summary: " + json.dumps(detail))
    # The verdict, alone on the last line: exactly these keys.
    print(json.dumps({"ok": not failed, "device": device}), flush=True)
    return 0 if not failed else 1


if __name__ == "__main__":
    sys.exit(main())
