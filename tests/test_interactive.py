"""Interactive-mode story: suspend/resume semantics + the ibfrun launcher.

Parity: reference ``common/basics.py:497-515`` (suspend/resume) and
``run/interactive_run.py:34-90`` (ibfrun).  The TPU rebuild is
single-controller, so "interactive" = any REPL/kernel; these tests drive a
real piped REPL session through the launcher.
"""

import os
import subprocess
import sys

import numpy as np
import pytest

import bluefog_tpu as bf

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def ctx():
    bf.init()
    yield
    if bf.initialized() and bf.suspended():
        bf.resume()


def test_suspend_blocks_comm_resume_restores(ctx):
    x = np.ones((bf.size(), 4), np.float32)
    before = np.asarray(bf.neighbor_allreduce(x))
    bf.suspend()
    assert bf.suspended()
    with pytest.raises(RuntimeError, match="suspended"):
        bf.neighbor_allreduce(x)
    with pytest.raises(RuntimeError, match="suspended"):
        bf.allreduce(x)
    # identity/topology queries stay available while suspended
    assert bf.size() >= 1 and bf.rank() >= 0
    assert bf.load_topology() is not None
    bf.resume()
    assert not bf.suspended()
    after = np.asarray(bf.neighbor_allreduce(x))
    np.testing.assert_allclose(after, before)


def test_suspend_idempotent_and_drains_window_handles(ctx):
    n = bf.size()
    x = np.arange(n * 3, dtype=np.float32).reshape(n, 3)
    bf.win_create(x, "susp_w")
    h = bf.win_put_nonblocking(x, "susp_w")
    bf.suspend()
    bf.suspend()  # idempotent
    assert bf.win_wait(h)  # already drained by suspend's quiesce
    with pytest.raises(RuntimeError, match="suspended"):
        bf.win_put_nonblocking(x, "susp_w")
    bf.resume()
    bf.resume()  # idempotent
    h2 = bf.win_put_nonblocking(x, "susp_w")
    assert bf.win_wait(h2)
    bf.win_free("susp_w")


def test_suspend_requires_init():
    bf.shutdown()
    with pytest.raises(RuntimeError, match="not initialized"):
        bf.suspend()


def test_shutdown_unpauses_stall_watchdog():
    """suspend -> shutdown -> init must not leave the (module-level) stall
    watchdog paused forever: resume() on the fresh context is a no-op."""
    from bluefog_tpu.utils.stall import _monitor
    bf.init()
    bf.suspend()
    assert _monitor._paused
    bf.shutdown()
    assert not _monitor._paused
    bf.init()
    bf.resume()  # no-op on fresh context; watchdog already live
    assert not _monitor._paused


@pytest.mark.slow
def test_ibfrun_command_mode_virtual_mesh(tmp_path):
    """ibfrun -np 4 <cmd> prepares the virtual mesh for cmd (device count
    and platform, through the child's environment)."""
    script = tmp_path / "probe.py"
    script.write_text(
        "import bluefog_tpu as bf\n"
        "bf.init()\n"
        "print('DEVS', bf.size())\n")
    out = subprocess.run(
        [sys.executable, "-m", "bluefog_tpu.run.interactive", "-np", "4",
         sys.executable, str(script)],
        capture_output=True, text=True, timeout=300, cwd=REPO,
        env={**os.environ, "PYTHONPATH": REPO})
    assert out.returncode == 0, out.stderr
    assert "DEVS 4" in out.stdout


@pytest.mark.slow
def test_ibfrun_piped_repl_session(tmp_path):
    """A real interactive session: cells piped into the launched REPL —
    init (boot), consensus, suspend, blocked op, resume, consensus again."""
    cells = """
import numpy as np
x = np.arange(bf.size(), dtype=np.float32)[:, None]
for _ in range(60): x = np.asarray(bf.neighbor_allreduce(x))
print('CELL1', float(abs(x - x.mean()).max()) < 1e-3)
bf.suspend()
try:
    bf.neighbor_allreduce(x)
    print('CELL2 False')
except RuntimeError:
    print('CELL2 True')
bf.resume()
print('CELL3', float(np.asarray(bf.allreduce(x)).mean()) >= 0)
"""
    out = subprocess.run(
        [sys.executable, "-m", "bluefog_tpu.run.interactive", "-np", "4"],
        input=cells, capture_output=True, text=True, timeout=600, cwd=REPO)
    assert out.returncode == 0, f"stdout={out.stdout}\nstderr={out.stderr}"
    assert "rank(s) ready" in out.stdout, out.stdout
    for marker in ("CELL1 True", "CELL2 True", "CELL3 True"):
        assert marker in out.stdout, out.stdout


def test_helloworld_notebook_cells_execute():
    """The interactive helloworld notebook's code cells run top-to-bottom in
    one namespace (what a kernel would do) and reach consensus."""
    import io
    import json
    from contextlib import redirect_stdout
    nb = json.load(open(os.path.join(REPO, "examples",
                                     "interactive_helloworld.ipynb")))
    ns = {}
    buf = io.StringIO()
    with redirect_stdout(buf):
        for cell in nb["cells"]:
            if cell["cell_type"] == "code":
                exec("".join(cell["source"]), ns)
    out = buf.getvalue()
    assert "ranks: 8" in out, out
    assert "comm while suspended -> RuntimeError" in out, out
    dev = float(out.split("max deviation from mean:")[1].split()[0])
    assert dev < 1e-3, out
    assert not ns["bf"].suspended()


def test_cluster_repl_wire_roundtrip():
    """Length-prefixed JSON framing survives chunked reads."""
    import socket

    from bluefog_tpu.run.cluster_repl import _recv_msg, _send_msg
    a, b = socket.socketpair()
    try:
        msg = {"op": "exec", "src": "x = 1\n" * 100, "seq": 7}
        _send_msg(a, msg)
        assert _recv_msg(b) == msg
    finally:
        a.close()
        b.close()


def test_cluster_console_acks_and_error_reporting(capsys):
    """The REPL pairs acks by sequence number, reports worker errors per
    rank, drains stale acks from a slow cell, and drops a dead worker
    without killing the session."""
    import socket
    import threading

    from bluefog_tpu.run import cluster_repl as CR

    # Fail fast: a broken helper thread must not park _collect_acks for
    # the 600s production timeout.
    orig_timeout = CR._ACK_TIMEOUT
    CR._ACK_TIMEOUT = 3.0
    repl_sock, worker_sock = socket.socketpair()
    console = CR.ClusterConsole([(1, repl_sock)], locals={})

    def worker_one_cell(reply_ok=True, extra_stale=None):
        msg = CR._recv_msg(worker_sock)
        assert msg["op"] == "exec"
        if extra_stale is not None:
            # ok=False: if seq pairing regressed to first-reply-wins, the
            # stale ack would print 'raised' and fail the step directly.
            CR._send_msg(worker_sock, {"ok": False, "seq": extra_stale,
                                       "tb": "STALE"})
        if reply_ok:
            CR._send_msg(worker_sock, {"ok": True, "seq": msg["seq"]})
        else:
            CR._send_msg(worker_sock, {"ok": False, "seq": msg["seq"],
                                       "tb": "Trace\nValueError: boom"})

    try:
        # Normal cell: ack consumed, nothing printed.
        t = threading.Thread(target=worker_one_cell)
        t.start()
        assert console.runsource("a = 1") is False
        t.join(timeout=5)
        assert not t.is_alive()
        assert "[ibfrun]" not in capsys.readouterr().err

        # Worker error: reported with the rank and the traceback tail.
        t = threading.Thread(target=worker_one_cell, kwargs={"reply_ok": False})
        t.start()
        console.runsource("a = 2")
        t.join(timeout=5)
        assert not t.is_alive()
        err = capsys.readouterr().err
        assert "rank 1 raised: ValueError: boom" in err

        # A stale ack from an earlier slow cell is drained, the current
        # cell's ack still pairs correctly.
        t = threading.Thread(target=worker_one_cell,
                             kwargs={"extra_stale": 0})
        t.start()
        console.runsource("a = 3")
        t.join(timeout=5)
        assert not t.is_alive()
        assert "raised" not in capsys.readouterr().err

        # Dead worker: dropped with a notice; the next cell still runs.
        worker_sock.close()
        console.runsource("a = 4")
        err = capsys.readouterr().err
        assert "control channel lost" in err
        assert console._workers == []
        assert console.runsource("a = 5") is False  # solo REPL keeps going
        assert console.locals["a"] == 5
    finally:
        CR._ACK_TIMEOUT = orig_timeout
        repl_sock.close()
        try:
            worker_sock.close()
        except OSError:
            pass


def test_cluster_repl_gang_token_handshake(monkeypatch):
    """Workers exec() shipped cells, so the gang token must gate the
    connection — via a mutual HMAC handshake that never puts the token on
    the wire (the keyed-connection-file role of the reference's
    ipyparallel mode, run/interactive_run.py:271-420).  A rogue listener
    that harvests a worker's handshake bytes learns only HMAC(token,
    nonce) and cannot authenticate itself; a rogue client that cannot
    answer the challenge is rejected before any message — including
    'exit' — reaches the exec loop."""
    from bluefog_tpu.run import cluster_repl as CR
    monkeypatch.setenv("BFTPU_IBF_TOKEN", "s3cret")
    token = CR._gang_token()

    # -- mac primitives: keyed, nonce-bound, constant-time verified -------
    n1 = "aa" * 16
    assert CR._mac_ok(token, n1, CR._mac(token, n1))
    assert not CR._mac_ok(token, n1, CR._mac(token, "bb" * 16))  # wrong nonce
    assert not CR._mac_ok(token, n1, CR._mac("other", n1))       # wrong token
    assert not CR._mac_ok(token, n1, None)                       # no mac
    # The wire artifacts contain no token bytes.
    assert "s3cret" not in CR._mac(token, n1)

    # -- repl side rejects a client that cannot answer the challenge -----
    import socket
    import threading

    def repl_side(conn, results):
        """repl_main's per-connection handshake, verbatim protocol."""
        import secrets
        nonce = secrets.token_hex(16)
        CR._send_msg(conn, {"op": "challenge", "nonce": nonce})
        hello = CR._recv_msg(conn)
        ok = (hello.get("op") == "hello"
              and CR._mac_ok(token, nonce, hello.get("mac")))
        results.append(ok)
        if ok:
            CR._send_msg(conn, {"op": "welcome",
                                "mac": CR._mac(token,
                                               str(hello.get("nonce", "")))})

    # Rogue client: replays a mac from ANOTHER session's nonce — rejected.
    a, b = socket.socketpair()
    res = []
    t = threading.Thread(target=repl_side, args=(a, res), daemon=True)
    t.start()
    CR._recv_msg(b)  # the challenge (nonce is fresh, replay won't match)
    CR._send_msg(b, {"op": "hello", "rank": 1, "nonce": "cc" * 16,
                     "mac": CR._mac(token, n1)})  # stale/replayed mac
    t.join(timeout=5)
    assert res == [False]
    a.close(); b.close()

    # Honest worker: answers the live challenge, verifies the welcome.
    a, b = socket.socketpair()
    res = []
    t = threading.Thread(target=repl_side, args=(a, res), daemon=True)
    t.start()
    ch = CR._recv_msg(b)
    import secrets
    wn = secrets.token_hex(16)
    CR._send_msg(b, {"op": "hello", "rank": 1, "nonce": wn,
                     "mac": CR._mac(token, str(ch["nonce"]))})
    welcome = CR._recv_msg(b)
    t.join(timeout=5)
    assert res == [True]
    assert CR._mac_ok(token, wn, welcome.get("mac"))  # server authenticated
    # ...and a rogue LISTENER without the token cannot forge that welcome.
    assert not CR._mac_ok(token, wn, CR._mac("", wn))
    a.close(); b.close()
