"""Checkpoint / resume (orbax).

The reference has no in-framework checkpointing (SURVEY §5.4) — it only
offers ``broadcast_parameters`` / ``broadcast_optimizer_state`` to re-sync
after a torch-native restore.  Here checkpointing is a first-class subsystem:
rank-major pytrees (params + optimizer state + step) save/restore through
orbax, and the decentralized-specific concerns are handled explicitly:

  * ``save``: optionally consensus-average the replicas first (a decentralized
    run's ranks legitimately differ; the averaged model is the publishable
    artifact, matching how BlueFog papers evaluate).
  * ``restore``: returns the saved tree; ``broadcast_to_ranks`` re-expands a
    consensus checkpoint back into per-rank replicas (the parity path for
    ``broadcast_parameters``, reference ``torch/utility.py:22-52``).
"""

from __future__ import annotations

import os
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["save", "restore", "latest_step", "list_steps",
           "broadcast_to_ranks", "consensus_average", "AsyncSaver",
           "has_global_shards", "restore_host", "leaf_shapes"]


def _checkpointer():
    import orbax.checkpoint as ocp
    return ocp.PyTreeCheckpointer()


def _is_global(x: Any) -> bool:
    """True for a jax.Array whose shards span processes (GSPMD state)."""
    return (isinstance(x, jax.Array) and not x.is_fully_addressable
            and not x.is_fully_replicated)


def has_global_shards(tree: Any) -> bool:
    """True when any leaf is globally sharded (multihost orbax territory)."""
    return any(_is_global(x) for x in jax.tree.leaves(tree))


def _host_copy(tree: Any) -> Any:
    """Copy a pytree to host numpy, rejecting globally-sharded arrays early.

    An array whose shards live on other hosts cannot be host-copied here,
    and silently zero-filling the missing rows would write corrupt data.
    ``save``/``restore`` handle such state through orbax's multihost path
    (every process writes its own shards into ONE coordinated checkpoint) —
    this strict copy is for the paths that need a host snapshot, e.g.
    ``AsyncSaver`` (which must decouple the write from live device buffers
    the caller may donate on the next step)."""
    def one(x):
        if _is_global(x):
            raise ValueError(
                "checkpoint: array with non-addressable shards "
                f"(shape {x.shape}, sharding {x.sharding}); this path "
                "needs a host copy — use the synchronous sharded save "
                "(checkpoint.save handles global arrays via orbax "
                "multihost) or gather first "
                "(multihost_utils.process_allgather)")
        return np.asarray(x)
    return jax.tree.map(one, tree)


def _prepare_for_save(tree: Any) -> Any:
    """Host-copy addressable leaves; pass globally-sharded jax.Arrays
    through untouched — orbax writes each process's shards into a single
    coordinated checkpoint (the multihost path the reference era handled by
    torch-native per-rank files)."""
    return jax.tree.map(lambda x: x if _is_global(x) else np.asarray(x),
                        tree)


def consensus_average(tree):
    """Average the rank replicas (leading axis) of every leaf."""
    return jax.tree.map(lambda x: jnp.mean(x, axis=0), tree)


def broadcast_to_ranks(tree, n: int):
    """Expand a consensus tree back to rank-major replicas."""
    return jax.tree.map(
        lambda x: jnp.broadcast_to(jnp.asarray(x)[None],
                                   (n,) + jnp.asarray(x).shape), tree)


def save(path: str, tree: Any, *, step: Optional[int] = None,
         average_ranks: bool = False, force: bool = True) -> str:
    """Save a pytree; returns the concrete directory written.

    ``average_ranks=True`` stores the consensus-averaged model instead of all
    replicas (smaller and the usual evaluation artifact).

    Globally-sharded leaves (GSPMD tensor-parallel state) are saved through
    orbax's multihost path: every process calls ``save`` with the same
    arguments and writes its own shards into one coordinated checkpoint."""
    if average_ranks:
        if has_global_shards(tree):
            raise ValueError(
                "checkpoint: average_ranks with globally-sharded state is "
                "ambiguous (the leading axis is a sharded model axis, not "
                "rank replicas) — save the sharded state directly")
        tree = consensus_average(tree)
    tree = _prepare_for_save(tree)  # host numpy; global shards stay lazy
    if jax.process_count() > 1 and has_global_shards(tree):
        # A coordinated checkpoint stores exactly ONE copy of each
        # non-sharded leaf (orbax writes it from the primary process).  A
        # per-process-distinct value would silently collapse to process
        # 0's on restore — fail loudly instead.
        host_leaves = [x for x in jax.tree.leaves(tree)
                       if not _is_global(x)]
        if host_leaves:
            from jax.experimental import multihost_utils
            multihost_utils.assert_equal(
                host_leaves,
                fail_message="checkpoint: non-sharded leaves differ across "
                "processes; a coordinated sharded checkpoint stores one "
                "copy — shard such leaves, make them identical, or save "
                "them per-process separately")
    path = os.path.abspath(path)
    if step is not None:
        path = os.path.join(path, f"step_{step:010d}")
    _checkpointer().save(path, tree, force=force)
    return path


def restore(path: str, *, step: Optional[int] = None,
            target: Any = None) -> Any:
    """Restore a pytree.

    Without ``target``, orbax returns generic dicts/lists — fine for plain
    dict trees, but NamedTuples (e.g. ``DistOptState``) and optax state
    tuples lose their structure.  Pass ``target`` (a matching tree of arrays,
    e.g. a freshly-initialized optimizer state) to get the original structure
    back, ready for ``opt.step``.

    Target leaves that are globally-sharded jax.Arrays are restored AS
    global arrays with the target leaf's sharding (each process reads only
    its own shards) — tensor-parallel training state round-trips without
    ever materializing on one host."""
    path = os.path.abspath(path)
    if step is not None:
        path = os.path.join(path, f"step_{step:010d}")
    ckpt = _checkpointer()
    if target is None:
        return ckpt.restore(path)
    import orbax.checkpoint as ocp

    def item_of(x):
        if _is_global(x):
            return jax.ShapeDtypeStruct(x.shape, x.dtype,
                                        sharding=x.sharding)
        return np.asarray(x)

    def restore_arg(x):
        if _is_global(x):
            return ocp.ArrayRestoreArgs(sharding=x.sharding,
                                        global_shape=x.shape)
        return ocp.RestoreArgs()

    restored = ckpt.restore(
        path, args=ocp.args.PyTreeRestore(
            item=jax.tree.map(item_of, target),
            restore_args=jax.tree.map(restore_arg, target)))
    # Re-attach the target's tree structure (NamedTuple/custom nodes).
    return jax.tree.unflatten(jax.tree.structure(target),
                              jax.tree.leaves(restored))


def restore_host(path: str, *, step: Optional[int] = None) -> Any:
    """Restore every leaf as host numpy, regardless of how it was saved.

    A checkpoint written by a DIFFERENT device geometry (more chips, a
    different mesh) cannot be restored as jax.Arrays — orbax would look for
    the original devices.  Forcing numpy reads all shards from (shared)
    storage instead; the world-size resharding path of ``utils.elastic``
    fits the result to the live geometry afterwards."""
    import orbax.checkpoint as ocp

    path = os.path.abspath(path)
    if step is not None:
        path = os.path.join(path, f"step_{step:010d}")
    ckpt = _checkpointer()
    meta = ckpt.metadata(path).item_metadata.tree
    restore_args = jax.tree.map(
        lambda m: ocp.RestoreArgs(restore_type=np.ndarray), meta)
    return ckpt.restore(path,
                        args=ocp.args.PyTreeRestore(restore_args=restore_args))


def leaf_shapes(path: str, *, step: Optional[int] = None) -> list:
    """Shapes of the saved leaves in tree-leaf order, WITHOUT reading data
    (orbax metadata only) — lets a restarting run detect that a checkpoint
    was written by a different world geometry before attempting restore."""
    path = os.path.abspath(path)
    if step is not None:
        path = os.path.join(path, f"step_{step:010d}")
    meta = _checkpointer().metadata(path).item_metadata.tree
    return [tuple(m.shape) for m in jax.tree.leaves(meta)]


def list_steps(path: str) -> list:
    """Sorted step numbers of the ``step_*`` checkpoints under ``path``."""
    if not os.path.isdir(path):
        return []
    return sorted(int(d.split("_")[1]) for d in os.listdir(path)
                  if d.startswith("step_") and d.split("_")[1].isdigit())


class AsyncSaver:
    """Background checkpoint writer: at most one write in flight.

    ``save`` copies the tree to host SYNCHRONOUSLY (callers may donate or
    overwrite device buffers on the next step), then hands the file write
    to a single worker thread.  The previous write is always joined before
    a new one starts, so step order on disk is preserved; ``flush`` joins
    the outstanding write and surfaces its error on the calling thread —
    and clears it either way, so a failed write raises exactly once.
    """

    def __init__(self):
        from concurrent.futures import ThreadPoolExecutor
        self._pool = ThreadPoolExecutor(max_workers=1,
                                        thread_name_prefix="bf-ckpt-save")
        self._pending = None

    def save(self, path: str, tree: Any, *, step: Optional[int] = None,
             wait: bool = False, after=None) -> None:
        host = _host_copy(tree)

        def write():
            save(path, host, step=step)
            if after is not None:
                after()

        self.flush()
        self._pending = self._pool.submit(write)
        if wait:
            self.flush()

    def flush(self) -> None:
        if self._pending is not None:
            fut, self._pending = self._pending, None
            fut.result()

    def shutdown(self) -> None:
        try:
            self.flush()
        finally:
            self._pool.shutdown(wait=True)


def latest_step(path: str) -> Optional[int]:
    """Newest ``step_*`` subdirectory under ``path``, or None."""
    steps = list_steps(path)
    return steps[-1] if steps else None
