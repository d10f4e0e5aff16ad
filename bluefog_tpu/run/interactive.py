"""``ibfrun``: interactive sessions on the TPU mesh.

Parity: reference ``run/interactive_run.py:34-90`` — ``ibfrun start -np 4``
boots an ipcontroller plus mpirun'd ipengines so a notebook can drive the MPI
world, paired with ``bf.suspend()/bf.resume()`` to park the background thread
between cells.

The TPU rebuild is single-controller SPMD: ONE Python process drives every
device, so there is no engine fleet to boot and no ipyparallel dependency —
any Jupyter kernel or plain REPL that imports ``bluefog_tpu`` *is* the
interactive mode.  What this launcher adds is the environment bootstrap the
reference's ``ibfrun start`` performed:

* ``ibfrun`` — drop into an IPython (fallback: ``python -i``) shell with
  ``bf`` imported and ``bf.init()`` already run over the real devices.
* ``ibfrun -np 8`` — same, over a virtual 8-device CPU mesh (the testing
  topology-development loop; XLA device-count flags must be set before JAX
  loads, which is exactly why this is a launcher and not a helper function).
* ``ibfrun -np 8 jupyter notebook`` (any command) — run that command inside
  the prepared environment instead of a REPL; kernels started by it inherit
  the virtual mesh.
* ``ibfrun -np 4 --hosts h1:2,h2:2`` — MULTI-MACHINE interactive mode
  (reference ``interactive_run.py:271-420`` ``multiple_machines_launch``):
  ranks 1..n-1 run exec-loop workers launched over the same ``--rsh``/ssh
  transport as ``bfrun``, rank 0 is a REPL that ships every complete cell
  to the fleet before running it locally, so collectives inside a cell run
  SPMD across the gang (``run/cluster_repl.py``).  With ``--hosts``, ``-np``
  counts processes (as in bfrun) and ``--devices-per-proc`` adds a virtual
  mesh per process.
* ``ibfrun -np 4 --hosts h1:2,h2:2 --kernel-file /tmp/bf-kernel.json`` —
  multi-machine JUPYTER mode: rank 0 becomes a real ipykernel in front of
  the same cell-shipping channel; connect any notebook/console client to
  the connection file and every executed cell drives the whole gang (the
  reference's ipcontroller+ipengines role).  See
  ``examples/cluster_notebook.ipynb``.

Inside the session, ``bf.suspend()`` / ``bf.resume()`` quiesce and re-enable
communication between cells (reference ``common/basics.py:497-515``).
"""

from __future__ import annotations

import argparse
import os
import shutil
import subprocess
import sys
import time
import uuid

from bluefog_tpu.run.run import virtual_mesh_env

__all__ = ["main", "build_parser"]

_BOOT = ("import bluefog_tpu as bf; bf.init(); "
         "print('bluefog_tpu interactive: %d rank(s) ready; "
         "bf.suspend()/bf.resume() park the session' % bf.size())")


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="ibfrun", description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter)
    p.add_argument("-np", "--num-proc", type=int, default=None,
                   help="virtual CPU device count; with --hosts: number of "
                        "processes (bfrun semantics)")
    p.add_argument("--no-init", action="store_true",
                   help="prepare the environment but skip bf.init()")
    p.add_argument("-H", "--hosts", default=None,
                   help="multi-machine mode: comma-separated host[:slots] "
                        "entries; rank 0 is the local REPL, the rest are "
                        "exec-loop workers")
    p.add_argument("--rsh", default=None,
                   help="remote-shell command for --hosts workers "
                        "(default: ssh -p <ssh-port>).  Must forward "
                        "stdin to the remote command like ssh does — the "
                        "per-gang auth token travels that way, never on "
                        "a command line")
    p.add_argument("--ssh-port", type=int, default=22)
    p.add_argument("--devices-per-proc", type=int, default=None,
                   help="virtual CPU devices per process (--hosts mode)")
    p.add_argument("--kernel-file", default=None,
                   help="--hosts mode: run rank 0 as a JUPYTER KERNEL "
                        "writing this connection file instead of a line "
                        "REPL — connect a notebook client to it and every "
                        "executed cell runs SPMD on the whole "
                        "multi-machine gang")
    p.add_argument("command", nargs=argparse.REMAINDER,
                   help="command to run instead of a REPL")
    return p


def _cluster(args) -> int:
    """Launch the multi-machine interactive gang: rank-0 REPL locally, the
    other ranks as cluster_repl workers over the rsh/ssh transport (the
    launch/kill/env machinery is bfrun's — one remote code path to trust)."""
    from bluefog_tpu.run import run as R
    n = args.num_proc or 1
    placement = R.parse_hosts(args.hosts, n)
    coord_host = placement[0][0]
    if not R.is_local_host(coord_host):
        # Rank 0 (REPL + coordinator + control socket) always runs HERE;
        # fail fast instead of letting workers dial a host where nothing
        # listens and time out opaquely two minutes later.
        print(f"ibfrun: the first --hosts entry ({coord_host}) must be this "
              "machine — rank 0 is the local REPL", file=sys.stderr)
        return 2
    rsh = R.rsh_argv(args.rsh, args.ssh_port)
    coord = f"{coord_host}:{R._free_port()}"
    ctrl = f"{coord_host}:{R._free_port()}"
    tag = f"ibfrun-gang-{uuid.uuid4().hex[:12]}"
    # Per-gang shared secret: workers exec() shipped cells, so both sides
    # of the control channel prove possession via an HMAC challenge-
    # response at connect time (cluster_repl handshake); rides
    # remote_run_cmd's BFTPU_ env replication.
    import secrets
    token = secrets.token_hex(16)
    host_slots = {}
    for host, _ in placement:
        host_slots[host] = host_slots.get(host, 0) + 1

    def child_env(rank, local_rank, local_size):
        env = dict(os.environ)
        env["BFTPU_COORDINATOR"] = coord
        env["BFTPU_NUM_PROCESSES"] = str(n)
        env["BFTPU_PROCESS_ID"] = str(rank)
        env["BFTPU_LOCAL_ID"] = str(local_rank)
        env["BFTPU_LOCAL_SIZE"] = str(local_size)
        env["BFTPU_GANG_TAG"] = tag
        env["BFTPU_IBF_TOKEN"] = token
        if args.devices_per_proc:
            virtual_mesh_env(env, args.devices_per_proc)
        else:
            R.tpu_slot_env(env, local_rank, local_size)
        return env

    wcmd = [sys.executable, "-m", "bluefog_tpu.run.cluster_repl",
            "--ctrl", ctrl]
    entries = []
    try:
        for rank, (host, local_rank) in enumerate(placement):
            if rank == 0:
                continue  # the REPL below
            env = child_env(rank, local_rank, host_slots[host])
            if R.is_local_host(host):
                # Local children get the token via the env DICT (never a
                # command line); remote ones read it from the rsh stdin
                # below — remote_run_cmd refuses to inline it into argv,
                # where /proc would expose it to every local user.
                entries.append((subprocess.Popen(wcmd, env=env), host,
                                False))
            else:
                run_cmd = ("IFS= read -r BFTPU_IBF_TOKEN && "
                           "export BFTPU_IBF_TOKEN && "
                           + R.remote_run_cmd(env, wcmd))
                remote = R._launch_shell(tag, rank, run_cmd)
                p = subprocess.Popen(rsh + [host, remote],
                                     stdin=subprocess.PIPE, text=True)
                # Register BEFORE feeding the token: a dead rsh client
                # (bad host, instant ssh failure) raises BrokenPipeError
                # on the write, and the cleanup below must reach this
                # child too.
                entries.append((p, host, True))
                p.stdin.write(token + "\n")
                p.stdin.close()
        front = (["--kernel-file", args.kernel_file] if args.kernel_file
                 else ["--repl"])
        rc = subprocess.call(
            [sys.executable, "-m", "bluefog_tpu.run.cluster_repl"] + front
            + ["--ctrl", ctrl, "--expect", str(n - 1)],
            env=child_env(0, placement[0][1], host_slots[coord_host]))
    except KeyboardInterrupt:
        print("ibfrun: interrupted; stopping the gang", file=sys.stderr)
        R._kill_gang(entries, rsh, tag)
        return 130
    except OSError as e:
        # A failing rsh client (e.g. BrokenPipeError writing the gang
        # token) must not leak the already-launched workers: kill the
        # gang, then surface the real error.
        print(f"ibfrun: gang launch failed ({e}); stopping the gang",
              file=sys.stderr)
        R._kill_gang(entries, rsh, tag)
        raise
    # REPL exit ends the session: workers exit on control-channel EOF.
    deadline = time.monotonic() + 15
    for p, _, _ in entries:
        try:
            p.wait(timeout=max(0.1, deadline - time.monotonic()))
        except subprocess.TimeoutExpired:
            pass
    if any(p.poll() is None for p, _, _ in entries):
        R._kill_gang(entries, rsh, tag)
    return rc


def main(argv=None) -> int:
    args = build_parser().parse_args(argv)
    cmd = args.command
    if cmd and cmd[0] == "--":
        cmd = cmd[1:]
    if args.hosts:
        if cmd or args.no_init:
            # The fleet protocol IS the session: an arbitrary command has
            # no cell stream to broadcast, and workers must init to
            # rendezvous.  Refuse rather than silently ignore.
            print("ibfrun: --hosts mode drives a REPL only; a command and "
                  "--no-init are not supported with it", file=sys.stderr)
            return 2
        return _cluster(args)
    if args.kernel_file:
        print("ibfrun: --kernel-file drives the multi-machine gang and "
              "needs --hosts (single-machine notebooks just start any "
              "kernel under `ibfrun -np N jupyter ...`)", file=sys.stderr)
        return 2
    env = dict(os.environ)
    if args.num_proc:
        virtual_mesh_env(env, args.num_proc)
    if cmd:
        return subprocess.call(cmd, env=env)

    boot = "" if args.no_init else _BOOT
    if shutil.which("ipython"):
        argv = ["ipython", "-i", "-c", boot] if boot else ["ipython"]
    else:
        argv = [sys.executable, "-i"] + (["-c", boot] if boot else [])
    return subprocess.call(argv, env=env)


if __name__ == "__main__":
    sys.exit(main())
