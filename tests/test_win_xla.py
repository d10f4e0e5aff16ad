"""Zero-copy XLA window put path (BLUEFOG_TPU_WIN_XLA, ops/xlaffi.py +
native/src/xlacall.cc).

Covers the tentpole's contract surface:
  * the BLUEFOG_TPU_WIN_XLA=0/1 loopback-through-store BITWISE state
    equivalence oracle (with and without associated-P) — same wire
    frames, same staging/versions/P state whether the put rows left
    through the host-staged path or straight off the device buffer;
  * a property test that FFI-fed frames decode identically across the
    dense / bf16 / sparse:<frac> codecs (including the sender-side
    error-feedback residual sequence);
  * auto-disarm on a jax stub without jax.ffi (one warning, puts fall
    back, nothing raises);
  * the in-program ``bf_xla_win_put`` custom-call lowering;
  * the ctypes-fallback send heuristic (tobytes below the threshold,
    raw pointer above — satellite of this PR);
  * the ``bf_win_host_copy_bytes_total{path}`` staging-copy oracle:
    zero put-side bytes on the FFI leg for dense f32 rows.
"""

import ctypes
import threading

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import bluefog_tpu as bf
from bluefog_tpu import native
from bluefog_tpu import topology as topo
from bluefog_tpu.ops import transport as T
from bluefog_tpu.ops import window as W
from bluefog_tpu.ops import xlaffi
from bluefog_tpu.utils import config, telemetry

needs_xla = pytest.mark.skipif(
    not (native.available() and native.has_win_xla()),
    reason="native core lacks the bf_xla symbols")
needs_handler = pytest.mark.skipif(
    not native.has_xla_handler(),
    reason="build lacks the XLA FFI handler (jaxlib headers absent)")


@pytest.fixture
def xla_env(monkeypatch):
    """Set knobs, reload config, and reset every xlaffi cache after."""
    def set_env(**kv):
        for k, v in kv.items():
            monkeypatch.setenv(k, str(v))
        config.reload()
        xlaffi._reset_for_tests()
    yield set_env
    config.reload()
    xlaffi._reset_for_tests()


# ---------------------------------------------------------------------------
# Satellite: ctypes-fallback send heuristic
# ---------------------------------------------------------------------------

def test_ctypes_payload_threshold():
    """Below CTYPES_PTR_BYTES the ctypes fallback ships bytes (cheapest
    conversion, copy ~free); at/above it, the raw data pointer (the copy
    would dwarf the ~µs pointer extraction)."""
    small = np.arange(64, dtype=np.float32)
    arg, nbytes, keep = T._ctypes_payload(small)
    assert isinstance(arg, bytes) and nbytes == small.nbytes
    assert arg == small.tobytes()

    big = np.zeros(T.CTYPES_PTR_BYTES // 4, dtype=np.float32)
    assert big.nbytes >= T.CTYPES_PTR_BYTES
    arg, nbytes, keep = T._ctypes_payload(big)
    assert isinstance(arg, int) and arg == big.ctypes.data
    assert nbytes == big.nbytes and keep is big

    # Non-contiguous input: materialized first, then the same rule.
    strided = np.zeros((2, T.CTYPES_PTR_BYTES // 4), np.float32)[:, ::2]
    arg, nbytes, keep = T._ctypes_payload(strided)
    assert isinstance(arg, int)
    assert keep.flags.c_contiguous and nbytes == keep.nbytes


@needs_xla
def test_ctypes_pointer_path_delivers(xla_env):
    """A pointer-path payload (>= CTYPES_PTR_BYTES) arrives bit-identical
    through the native sender even with the fastcall module bypassed."""
    xla_env(BLUEFOG_TPU_WIN_COALESCE=1, BLUEFOG_TPU_WIN_NATIVE=1,
            BLUEFOG_TPU_WIN_COALESCE_LINGER_MS=200)
    got = []
    ev = threading.Event()

    def apply(op, name, src, dst, weight, p_weight, payload):
        got.append(bytes(payload))
        ev.set()

    server = T.WindowTransport(apply)
    client = T.WindowTransport(lambda *a: None)
    try:
        assert client.native_path
        client._fc_send = None  # force the ctypes fallback
        row = np.random.RandomState(0).randn(
            T.CTYPES_PTR_BYTES // 4 + 16).astype(np.float32)
        client.send("127.0.0.1", server.port, T.OP_PUT, "big", 0, 1, 1.0,
                    row)
        client.flush()
        assert ev.wait(20)
        assert got[0] == row.tobytes()
    finally:
        client.stop()
        server.stop()


# ---------------------------------------------------------------------------
# FFI-fed frames: codec property test
# ---------------------------------------------------------------------------

def _plan_lib():
    lib = native.lib()
    return lib


@needs_xla
@pytest.mark.parametrize("codec", ["none", "bf16", "sparse:0.4"])
def test_ffi_frames_decode_identically_across_codecs(xla_env, codec):
    """Frames fed by the native plan executor decode (through the Python
    drain) to EXACTLY the payload bytes the Python encoder produces for
    the same rows — dense raw, bf16 round-to-nearest-even, and the
    sparse error-feedback sequence (3 successive sends per edge, so the
    residual fold is exercised, not just the first selection)."""
    xla_env(BLUEFOG_TPU_WIN_COALESCE=1, BLUEFOG_TPU_WIN_NATIVE=0,
            BLUEFOG_TPU_WIN_COALESCE_LINGER_MS=200)
    lib = _plan_lib()
    elems, rounds = 11, 3
    name = f"cx_{codec.replace(':', '_').replace('.', '_')}"
    op = T.OP_ACCUMULATE
    codec_id = {"none": 0, "bf16": 1}.get(codec, 2)
    frac = 0.4 if codec.startswith("sparse") else 1.0

    got = []
    cv = threading.Condition()

    def apply(oper, nm, src, dst, weight, p_weight, payload):
        with cv:
            got.append((oper, nm, src, dst, weight, p_weight,
                        bytes(payload)))
            cv.notify_all()

    server = T.WindowTransport(apply)
    client = T.WindowTransport(lambda *a: None)
    try:
        # The native tx is required for plan dispatch even when the
        # server decodes in Python (the decode side is what's under
        # test here).
        assert client._tx is None  # WIN_NATIVE=0 pins the Python sender
        xla_env(BLUEFOG_TPU_WIN_COALESCE=1, BLUEFOG_TPU_WIN_NATIVE=1,
                BLUEFOG_TPU_WIN_COALESCE_LINGER_MS=200)
        client2 = T.WindowTransport(lambda *a: None)
        assert client2.native_path
        rng = np.random.RandomState(7)
        rows = [rng.randn(2, elems).astype(np.float32)
                for _ in range(rounds)]
        lib.bf_xla_drop_residuals(None)
        W._drop_ef_residuals()
        plan = lib.bf_xla_plan_new(name.encode(), elems, 2, codec_id, frac)
        assert plan > 0
        for i, (src, dst) in enumerate([(0, 1), (0, 2)]):
            assert lib.bf_xla_plan_edge(
                plan, i, b"127.0.0.1", server.port, op, src, dst,
                0.25 * (i + 1), i, 0) == 0
        total = 0
        for r in range(rounds):
            data = np.ascontiguousarray(rows[r])
            rc = lib.bf_xla_plan_run(plan, client2._tx, data.ctypes.data,
                                     data.size)
            assert rc == 0
            client2.flush()
            total += 2
        with cv:
            assert cv.wait_for(lambda: len(got) >= total, timeout=30)
        lib.bf_xla_plan_free(plan)
        client2.stop()

        # Reference: the Python encoder on the same row sequence.
        expect = []
        for r in range(rounds):
            for i, (src, dst) in enumerate([(0, 1), (0, 2)]):
                row = np.ascontiguousarray(rows[r][i])
                if codec == "bf16":
                    payload = row.astype(np.dtype(jnp.bfloat16)).tobytes()
                    eop = op | T.OP_BF16_FLAG
                elif codec.startswith("sparse"):
                    # Reference residual stream keyed off a DIFFERENT
                    # window name: _sparse_payload now folds in any
                    # native residual for its key (the cross-store
                    # hand-off), and the native sequence above already
                    # populated this name's native store.
                    payload = W._sparse_payload(
                        "ref_" + name, src, dst, row, frac).tobytes()
                    eop = op | T.OP_SPARSE_FLAG
                else:
                    payload = row.tobytes()
                    eop = op
                expect.append((eop, name, src, dst, 0.25 * (i + 1),
                               payload))
        assert len(got) == len(expect)
        for (g, e) in zip(got, expect):
            assert g[0] == e[0], "op byte (codec flag)"
            assert (g[1], g[2], g[3]) == (e[1], e[2], e[3])
            assert g[4] == e[4], "wire weight"
            assert g[6] == e[5], "payload bytes (bitwise)"
            if codec.startswith("sparse"):
                gi, gv = T.sparse_decode(g[6])
                ei, ev = T.sparse_decode(e[5])
                np.testing.assert_array_equal(gi, ei)
                np.testing.assert_array_equal(gv, ev)
    finally:
        W._drop_ef_residuals()
        try:
            client2.stop()
        except Exception:
            pass
        client.stop()
        server.stop()


# ---------------------------------------------------------------------------
# Loopback-through-store equivalence oracle (the =0/=1 contract)
# ---------------------------------------------------------------------------

def _fake_distrib(transport, server_port):
    """Rank directory for the loopback store: even ranks owned here
    (proc 0), odd ranks 'owned' by proc 1 — whose endpoint is the local
    server transport feeding the SAME store (the window was created
    before the directory install, so it carries every rank's slots)."""
    return W._Distrib(transport,
                      rank_owner={r: r % 2 for r in range(8)},
                      proc_addr={0: ("127.0.0.1", 1),
                                 1: ("127.0.0.1", server_port)},
                      my_proc=0)


def _drive_xla_store(xla_env, use_xla, with_p, codec="none"):
    """One deterministic put/accumulate stream of DEVICE arrays through
    the real window-op path into a loopback store; returns the window
    state snapshot (the =0/=1 oracle drives this twice)."""
    bf.init(lambda: topo.RingGraph(8))
    xla_env(BLUEFOG_TPU_WIN_COALESCE=1,
            BLUEFOG_TPU_WIN_COALESCE_LINGER_MS=500,
            BLUEFOG_TPU_WIN_NATIVE=1,
            BLUEFOG_TPU_WIN_XLA=1 if use_xla else 0,
            BLUEFOG_TPU_WIN_COMPRESSION=codec)
    if with_p:
        bf.turn_on_win_ops_with_associated_p()
    rng = np.random.RandomState(23)
    x = rng.randn(8, 6).astype(np.float32)
    applied = [0]
    cv = threading.Condition()

    def bump(k):
        with cv:
            applied[0] += k
            cv.notify_all()

    def apply(op, name, src, dst, weight, p_weight, payload):
        W._apply_inbound(op, name, src, dst, weight, p_weight, payload)
        bump(1)

    def apply_batch(msgs):
        W._apply_inbound_batch(msgs)
        bump(len(msgs))

    def apply_items(items):
        W._apply_inbound_items(items)
        bump(sum((p[5] + p[6]) if k else 1 for k, p in items))

    server = T.WindowTransport(apply, apply_batch=apply_batch,
                               apply_items=apply_items)
    client = T.WindowTransport(lambda *a: None)
    saved = W._store.distrib
    try:
        assert client.native_path, "native sender required for both legs"
        assert bf.win_create(x, "xeq", zero_init=True)
        server.register_window("xeq", 6)
        W._store.distrib = _fake_distrib(client, server.port)
        if use_xla:
            assert xlaffi.armed(), xlaffi.disarm_reason()
        total = 0
        for step in range(6):
            srng = np.random.RandomState(300 + step)
            t = jnp.asarray(srng.randn(8, 6).astype(np.float32))
            # The (bidirectional) ring's out-edges from owned (even) srcs
            # all target odd dsts: 8 remote edges per op.
            if step % 2:
                bf.win_accumulate(t, "xeq",
                                  self_weight=0.5 if step == 3 else None,
                                  require_mutex=False)
            else:
                bf.win_put(t, "xeq", require_mutex=False)
            total += 8
            with cv:
                assert cv.wait_for(lambda: applied[0] >= total,
                                   timeout=30), (applied[0], total)
        if use_xla:
            snap = telemetry.snapshot()
            assert any(k.startswith("bf_win_xla_puts_total")
                       for k in snap), "FFI path did not engage"
        return bf.win_state_dict("xeq")
    finally:
        W._store.distrib = saved
        bf.win_free("xeq")
        client.stop()
        server.stop()
        if with_p:
            bf.turn_off_win_ops_with_associated_p()


@needs_xla
@pytest.mark.parametrize("with_p", [False, True])
@pytest.mark.parametrize("codec", ["none", "bf16", "sparse:0.5"])
def test_xla_vs_host_path_state_equivalence_bitwise(xla_env, with_p,
                                                    codec):
    """The BLUEFOG_TPU_WIN_XLA=0/1 oracle: the same device-array put
    stream lands BIT-IDENTICAL window state — staging rows, version
    counters, associated-P — whether the rows left through the
    host-staged PR-9 path or straight off the XLA buffer, across every
    wire codec (sparse rides accumulate edges with unique-magnitude
    random rows, so the top-k selection is deterministic on both
    sides)."""
    ffi = _drive_xla_store(xla_env, use_xla=True, with_p=with_p,
                           codec=codec)
    host = _drive_xla_store(xla_env, use_xla=False, with_p=with_p,
                            codec=codec)
    for part in ("staging", "versions", "p_staging", "main", "p_main"):
        assert set(host[part]) == set(ffi[part]), part
        for k, v in host[part].items():
            np.testing.assert_array_equal(
                np.asarray(ffi[part][k]), np.asarray(v),
                err_msg=f"{part}[{k}] (bitwise)")


@needs_xla
def test_xla_put_zero_staging_copies_dense(xla_env):
    """The staging-copy oracle: a dense-f32 FFI-fed put stream reports
    ZERO put-side bytes in bf_win_host_copy_bytes_total (device_get /
    edge_temp / enqueue all bypassed)."""
    telemetry.reset()
    _drive_xla_store(xla_env, use_xla=True, with_p=False)
    snap = telemetry.snapshot()
    for path in ("device_get", "edge_temp", "enqueue"):
        key = f'bf_win_host_copy_bytes_total{{path="{path}"}}'
        assert snap.get(key, 0) == 0, (key, snap.get(key))


@needs_xla
def test_host_path_reports_staging_copies(xla_env):
    """The same stream through the Python coalesced sender DOES count
    enqueue copies — the counter is live, not trivially zero."""
    telemetry.reset()
    bf.init(lambda: topo.RingGraph(8))
    xla_env(BLUEFOG_TPU_WIN_COALESCE=1, BLUEFOG_TPU_WIN_NATIVE=0,
            BLUEFOG_TPU_WIN_XLA=0,
            BLUEFOG_TPU_WIN_COALESCE_LINGER_MS=200)
    done = threading.Event()

    def apply(*a):
        done.set()

    server = T.WindowTransport(apply)
    client = T.WindowTransport(lambda *a: None)
    saved = W._store.distrib
    x = np.zeros((8, 6), np.float32)
    try:
        assert bf.win_create(x, "hc", zero_init=True)
        W._store.distrib = _fake_distrib(client, server.port)
        bf.win_put(jnp.asarray(x), "hc", require_mutex=False)
        assert done.wait(20)
        snap = telemetry.snapshot()
        assert snap.get('bf_win_host_copy_bytes_total{path="enqueue"}',
                        0) > 0
    finally:
        W._store.distrib = saved
        bf.win_free("hc")
        client.stop()
        server.stop()


# ---------------------------------------------------------------------------
# Arming diagnostics
# ---------------------------------------------------------------------------

def test_disarm_reason_on_knob_off(xla_env):
    xla_env(BLUEFOG_TPU_WIN_XLA=0)
    assert not xlaffi.armed()
    assert xlaffi.disarm_reason() == "BLUEFOG_TPU_WIN_XLA=0"
    info = bf.win_xla_info()
    assert info["armed"] is False and info["reason"]


# ---------------------------------------------------------------------------
# In-program lowering (bf_xla_win_put custom call)
# ---------------------------------------------------------------------------

@needs_handler
def test_in_program_ffi_put(xla_env):
    """The put lowered INTO a jitted program: the XLA custom call runs
    the same native plan mid-program and the rows arrive bit-identical
    at the peer."""
    xla_env(BLUEFOG_TPU_WIN_COALESCE=1, BLUEFOG_TPU_WIN_NATIVE=1,
            BLUEFOG_TPU_WIN_COALESCE_LINGER_MS=200)
    lib = _plan_lib()
    got = []
    cv = threading.Condition()

    def apply(op, name, src, dst, weight, p_weight, payload):
        with cv:
            got.append((src, dst, bytes(payload)))
            cv.notify_all()

    server = T.WindowTransport(apply)
    client = T.WindowTransport(lambda *a: None)
    try:
        assert client.native_path
        plan = lib.bf_xla_plan_new(b"jitw", 5, 2, 0, 1.0)
        for i, (src, dst) in enumerate([(0, 1), (0, 3)]):
            assert lib.bf_xla_plan_edge(plan, i, b"127.0.0.1", server.port,
                                        T.OP_PUT, src, dst, 1.0, i, 0) == 0
        run = xlaffi.xla_put_program(plan, client._tx)
        assert run is not None

        @jax.jit
        def step(x):
            st = run(x)
            return x * 2.0, st

        x = jnp.asarray(np.random.RandomState(3).randn(2, 5)
                        .astype(np.float32))
        y, st = step(x)
        assert int(np.asarray(st)[0]) == 0
        client.flush()
        with cv:
            assert cv.wait_for(lambda: len(got) >= 2, timeout=30)
        xh = np.asarray(x)
        assert got[0] == (0, 1, xh[0].tobytes())
        assert got[1] == (0, 3, xh[1].tobytes())
        np.testing.assert_array_equal(np.asarray(y), xh * 2.0)
        lib.bf_xla_plan_free(plan)
    finally:
        client.stop()
        server.stop()


# ---------------------------------------------------------------------------
# Commit-side re-entry
# ---------------------------------------------------------------------------

def test_commit_to_jax_values_and_accounting(xla_env):
    """commit_to_jax returns the exact values and, where the runtime
    aliases host arrays (CPU jax), counts no commit copy."""
    xla_env(BLUEFOG_TPU_WIN_XLA=1)
    telemetry.reset()
    arr = np.random.RandomState(5).randn(4, 3).astype(np.float32)
    out = xlaffi.commit_to_jax(arr.copy())
    np.testing.assert_array_equal(np.asarray(out), arr)
    assert xlaffi._commit_mode[0] in ("verify", "dlpack")
    snap = telemetry.snapshot()
    copied = snap.get('bf_win_host_copy_bytes_total{path="commit"}', 0)
    # On this runtime jnp.asarray aliases (or dlpack rescues): zero-copy.
    assert copied in (0, arr.nbytes)


@needs_xla
def test_sparse_residuals_survive_path_switch(xla_env):
    """Error-feedback mass must not strand when one edge's put stream
    switches between the native (FFI) and host encoders: the two
    residual stores hand off additively, so the summed wire mass over
    any mixed sequence equals the summed input mass."""
    xla_env(BLUEFOG_TPU_WIN_COALESCE=1, BLUEFOG_TPU_WIN_NATIVE=1,
            BLUEFOG_TPU_WIN_COALESCE_LINGER_MS=200)
    lib = native.lib()
    elems, frac = 10, 0.3
    name = "resx"
    rng = np.random.RandomState(17)
    rows = [rng.randn(elems).astype(np.float32) for _ in range(4)]
    W._drop_ef_residuals()
    lib.bf_xla_drop_residuals(None)

    got = []
    cv = threading.Condition()

    def apply(op, nm, src, dst, w, pw, payload):
        with cv:
            got.append(bytes(payload))
            cv.notify_all()

    server = T.WindowTransport(apply)
    client = T.WindowTransport(lambda *a: None)
    try:
        assert client.native_path
        plan = lib.bf_xla_plan_new(name.encode(), elems, 1, 2, frac)
        assert lib.bf_xla_plan_edge(plan, 0, b"127.0.0.1", server.port,
                                    T.OP_ACCUMULATE, 0, 1, 1.0, 0, 0) == 0
        wire_mass = np.zeros(elems, np.float64)
        sent_native = 0
        # Alternate: native sends (rounds 0, 2) and host-encoder sends
        # (rounds 1, 3) — each side must fold the other's residual.
        for r, row in enumerate(rows):
            if r % 2 == 0:
                data = np.ascontiguousarray(row)
                assert lib.bf_xla_plan_run(plan, client._tx,
                                           data.ctypes.data, elems) == 0
                client.flush()
                sent_native += 1
                want = sent_native
                with cv:
                    assert cv.wait_for(lambda: len(got) >= want,
                                       timeout=30)
                payload = got[-1]
            else:
                payload = W._sparse_payload(name, 0, 1, row, frac).tobytes()
            idx, vals = T.sparse_decode(payload)
            np.add.at(wire_mass, idx, vals.astype(np.float64))
        # Remaining residual may live in EITHER store; drain both.
        res = np.zeros(elems, np.float64)
        nat = xlaffi.take_native_residual(name, 0, 1, elems)
        if nat is not None:
            res += nat
        with W._ef_lock:
            r = W._ef_residuals.pop((name, 0, 1), None)
        if r is not None:
            res += r
        total_in = np.sum(rows, axis=0, dtype=np.float64)
        np.testing.assert_allclose(wire_mass + res, total_in, rtol=1e-5,
                                   err_msg="mass stranded across stores")
        lib.bf_xla_plan_free(plan)
    finally:
        W._drop_ef_residuals()
        client.stop()
        server.stop()


@needs_xla
def test_plan_p_masses_rezeroed_after_p_disable(xla_env):
    """A cached plan that shipped associated-P masses must ship p=0.0
    on the wire again after turn_off_win_ops_with_associated_p() — the
    host-path oracle's exact wire behavior (stale cached masses would
    silently fold phantom P at any peer whose toggle lags)."""
    bf.init(lambda: topo.RingGraph(8))
    xla_env(BLUEFOG_TPU_WIN_COALESCE=1, BLUEFOG_TPU_WIN_NATIVE=1,
            BLUEFOG_TPU_WIN_XLA=1,
            BLUEFOG_TPU_WIN_COALESCE_LINGER_MS=200)
    wire_p = []
    cv = threading.Condition()

    def apply(op, nm, src, dst, w, pw, payload):
        with cv:
            wire_p.append(float(pw))
            cv.notify_all()

    server = T.WindowTransport(apply)  # raw recorder: no store apply,
    client = T.WindowTransport(lambda *a: None)  # no win registration
    saved = W._store.distrib
    x = np.zeros((8, 4), np.float32)
    try:
        assert bf.win_create(x, "pz", zero_init=True)
        W._store.distrib = _fake_distrib(client, server.port)
        t = jnp.asarray(np.ones((8, 4), np.float32))
        bf.turn_on_win_ops_with_associated_p()
        bf.win_accumulate(t, "pz", require_mutex=False)
        with cv:
            assert cv.wait_for(lambda: len(wire_p) >= 8, timeout=30)
        assert all(p == 1.0 for p in wire_p[:8]), wire_p[:8]
        bf.turn_off_win_ops_with_associated_p()
        bf.win_accumulate(t, "pz", require_mutex=False)
        with cv:
            assert cv.wait_for(lambda: len(wire_p) >= 16, timeout=30)
        assert all(p == 0.0 for p in wire_p[8:16]), wire_p[8:16]
    finally:
        W._store.distrib = saved
        bf.win_free("pz")
        client.stop()
        server.stop()
        bf.turn_off_win_ops_with_associated_p()


def test_optimizer_payloads_stay_on_device_when_armed(xla_env,
                                                      monkeypatch):
    """The window optimizers keep their put payloads as jax arrays (the
    fused concatenate compiles into the step) exactly when the FFI path
    is armed for a multi-process all-f32 tree — and fall back to the
    legacy numpy payloads (bitwise-identical rows) otherwise."""
    from bluefog_tpu.optim import window_optimizers as WO
    import optax
    bf.init(lambda: topo.RingGraph(8))
    opt = WO.DistributedWinPutOptimizer(optax.sgd(0.1))
    opt._rows = 8
    tree = {"a": jnp.ones((8, 3), jnp.float32),
            "b": jnp.zeros((8, 2, 2), jnp.float32)}
    # Single-process (no distrib): legacy numpy payloads.
    assert not opt._device_payloads_ok(tree)
    legacy = opt._payloads(tree)
    assert isinstance(legacy[0], np.ndarray)
    # Fake a live distrib + armed path: payloads stay on device.
    monkeypatch.setattr(W._store, "distrib", object())
    monkeypatch.setattr(xlaffi, "armed", lambda: True)
    assert opt._device_payloads_ok(tree)
    dev = opt._payloads(tree)
    assert isinstance(dev[0], jax.Array) and dev[0].dtype == jnp.float32
    np.testing.assert_array_equal(np.asarray(dev[0]), legacy[0])
    # A mixed-dtype tree must NOT take the device path (numpy promotion
    # would differ from jnp's): falls back.
    tree["c"] = jnp.zeros((8, 2), jnp.int32)
    assert not opt._device_payloads_ok(tree)


def test_win_update_returns_usable_array(xla_env):
    """win_update's zero-copy return stays a normal jax array: consumable
    by jnp ops and by the optimizers' _rebuild round-trip."""
    xla_env(BLUEFOG_TPU_WIN_XLA=1)
    bf.init(lambda: topo.RingGraph(8))
    x = np.random.RandomState(2).randn(8, 3).astype(np.float32)
    assert bf.win_create(x, "zc")
    try:
        bf.win_put(x, "zc")
        out = bf.win_update("zc")
        assert isinstance(out, jax.Array)
        _ = jnp.sum(out)  # participates in further jax math
        ref = np.asarray(out)
        assert ref.shape == x.shape
    finally:
        bf.win_free("zc")
