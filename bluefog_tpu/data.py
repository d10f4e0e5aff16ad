"""Input pipeline: rank-partitioned sampling + host-async device prefetch.

The reference delegates data loading to torch (``DistributedSampler`` +
``DataLoader``, ``examples/pytorch_mnist.py:100-120``); a standalone TPU
framework needs its own feed.  Two pieces:

* :class:`DistributedSampler` — epoch-seeded global permutation partitioned
  across ranks, same contract as the torch sampler the reference's examples
  use (``set_epoch`` reshuffles; ``drop_last`` keeps shards equal — SPMD
  requires identical shapes on every rank anyway).
* :func:`prefetch_to_device` / :class:`ShardedLoader` — a background thread
  assembles the next batches and ``jax.device_put``\\ s them with the
  rank-major sharding while the current step computes, hiding host→HBM
  transfer behind the MXU.  (flax's ``jax_utils.prefetch_to_device`` is
  pmap-era and GPU-gated; this one targets ``NamedSharding`` over the rank
  mesh and works on any backend.)

* :func:`pack_documents` — documents of any length in, rows of ``seq_len``
  tokens out with the ids and positions that keep them apart
  (``models.TransformerLM(..., positions=, segment_ids=)``); stack its rows
  and hand them to either of the above as any other array.

Batches are **rank-major**: leading dim ``bf.size()``, row ``r`` is rank
``r``'s per-device batch — the same convention as every eager op
(``docs/ops.md``).
"""

from __future__ import annotations

import itertools
import queue
import threading
from typing import Callable, Iterable, Iterator, Optional, Sequence

import jax
import numpy as np

from bluefog_tpu.utils import telemetry
from bluefog_tpu.utils.timeline import op_span

__all__ = ["DistributedSampler", "ShardedLoader", "prefetch_to_device",
           "document_layout", "pack_documents"]


def document_layout(lengths: Sequence[int]) -> tuple:
    """``(segment_ids, positions)`` of a row that holds documents of these
    ``lengths`` one after the other, each ``(sum(lengths),)`` int32: token
    ``t`` lies in document ``segment_ids[t]`` (0, 1, ... in the row's order,
    so the ids never decrease: what ``ops.flash_attention`` asks) at
    ``positions[t]``, counted from its document's first token."""
    lengths = np.asarray(lengths, np.int64)
    segment_ids = np.repeat(np.arange(len(lengths)), lengths)
    starts = np.repeat(np.cumsum(lengths) - lengths, lengths)
    return (segment_ids.astype(np.int32),
            (np.arange(lengths.sum()) - starts).astype(np.int32))


def pack_documents(documents: Iterable, seq_len: int) -> Iterator[tuple]:
    """Pack ``documents`` (an iterable of 1-D token arrays of any lengths)
    into rows of exactly ``seq_len`` tokens: yields ``(tokens, segment_ids,
    positions)``, each ``(seq_len,)``, the latter two int32 as
    :func:`document_layout` gives them.

    Concatenate and chunk: documents fill a row in arrival order; the one
    that reaches the row's end is cut there and its rest opens the next row
    as a document of its own (id 0, positions from 0: the row cannot see
    the part it lacks).  Nothing is padded, so the tokens left when
    ``documents`` ends, fewer than a row, are not yielded.  Empty documents
    are skipped.  Stack rows into ``(N, seq_len)`` arrays for
    :class:`ShardedLoader`, or into rank-major batches for
    :func:`prefetch_to_device`.

    Span ``bf.data.pack`` around each row's assembly; counters
    ``bf_pack_documents_total`` (documents and parts of documents laid into
    yielded rows) and ``bf_pack_tokens_total``."""
    parts, held = [], 0      # what the open row holds so far
    for doc in documents:
        doc = np.asarray(doc).reshape(-1)
        while doc.size:
            take = doc[:seq_len - held]
            parts.append(take)
            held += take.size
            doc = doc[take.size:]
            if held == seq_len:
                with op_span("data", "pack", documents=len(parts)):
                    row = (np.concatenate(parts),) + document_layout(
                        [p.size for p in parts])
                telemetry.inc("bf_pack_documents_total", len(parts))
                telemetry.inc("bf_pack_tokens_total", seq_len)
                parts, held = [], 0
                yield row


class DistributedSampler:
    """Partition ``num_samples`` indices across ranks with per-epoch shuffles.

    Parity: ``torch.utils.data.distributed.DistributedSampler`` as used by
    the reference's examples (``pytorch_mnist.py:100-104``) — but this one
    yields the index matrix for ALL ranks at once (rank-major row ``r`` =
    rank ``r``'s indices), matching the single-controller data model.
    """

    def __init__(self, num_samples: int, *, num_ranks: Optional[int] = None,
                 shuffle: bool = True, seed: int = 0,
                 drop_last: bool = True, static_shards: bool = False):
        if num_ranks is None:
            from bluefog_tpu import basics
            num_ranks = basics.size()
        if num_samples < num_ranks:
            raise ValueError(
                f"cannot shard {num_samples} samples over {num_ranks} ranks")
        self.num_samples = num_samples
        self.num_ranks = num_ranks
        self.shuffle = shuffle
        self.seed = seed
        self.drop_last = drop_last
        # static_shards pins shard membership: rank r always owns the r-th
        # contiguous block, and per-epoch shuffling happens *within* shards.
        # This is the heterogeneous-data decentralized-DP setting; the torch
        # sampler (and static_shards=False) re-partitions globally each
        # epoch, which makes rank data IID over time.
        self.static_shards = static_shards
        self.epoch = 0
        self.per_rank = num_samples // num_ranks
        if not drop_last and num_samples % num_ranks:
            # pad by wrapping (torch sampler semantics: repeat early samples)
            self.per_rank += 1

    def set_epoch(self, epoch: int) -> None:
        """Reseed the shuffle (call once per epoch, every process — the
        permutation must be identical everywhere, like the torch sampler's
        ``seed + epoch`` contract)."""
        self.epoch = int(epoch)

    def indices(self) -> np.ndarray:
        """``(num_ranks, per_rank)`` int array; row ``r`` = rank ``r``."""
        total = self.per_rank * self.num_ranks
        if self.static_shards:
            perm = np.arange(self.num_samples)
            if total > perm.size:
                perm = np.concatenate([perm, perm[:total - perm.size]])
            shards = perm[:total].reshape(self.num_ranks, self.per_rank)
            if self.shuffle:
                rng = np.random.RandomState(self.seed + self.epoch)
                for r in range(self.num_ranks):  # shuffle within shard only
                    shards[r] = shards[r][rng.permutation(self.per_rank)]
            return shards
        if self.shuffle:
            rng = np.random.RandomState(self.seed + self.epoch)
            perm = rng.permutation(self.num_samples)
        else:
            perm = np.arange(self.num_samples)
        if total > perm.size:  # wrap-pad (drop_last=False)
            perm = np.concatenate([perm, perm[:total - perm.size]])
        return perm[:total].reshape(self.num_ranks, self.per_rank)

    def __iter__(self) -> Iterator[np.ndarray]:
        """Yield ``(num_ranks,)`` index columns one sample position at a
        time (rarely what you want — prefer :class:`ShardedLoader`)."""
        return iter(self.indices().T)

    def __len__(self) -> int:
        return self.per_rank


def prefetch_to_device(it: Iterable, *, size: int = 2,
                       sharding=None) -> Iterator:
    """Wrap a host iterator of (pytrees of) numpy batches: a daemon thread
    stays ``size`` batches ahead, placing each on device so the consumer
    never blocks on host→HBM transfer.

    ``sharding=None`` uses the framework's rank-major sharding (leading dim
    partitioned over the rank mesh); pass any ``jax.sharding.Sharding`` to
    override, or ``False`` to skip placement (raw numpy out).
    """
    if sharding is None:
        from bluefog_tpu import basics
        sharding = basics._rank_sharding()

    q: "queue.Queue" = queue.Queue(maxsize=max(1, size))
    _END = object()
    stop = threading.Event()  # consumer abandoned: let the producer exit

    def place(batch):
        if sharding is False:
            return batch
        return jax.tree.map(lambda x: jax.device_put(x, sharding), batch)

    def offer(item) -> bool:
        """Put unless the consumer went away; never blocks indefinitely."""
        while not stop.is_set():
            try:
                q.put(item, timeout=0.1)
                return True
            except queue.Full:
                continue
        return False

    # bf.data.place (this thread's host-to-device copy) and bf.data.wait
    # (the training thread, blocked on the queue) carry the batch's
    # sequence number: the two spans of one batch sit on different threads.
    def producer():
        try:
            for seq, batch in enumerate(it):
                with op_span("data", "place", batch=seq):
                    batch = place(batch)
                if not offer(batch):
                    return
        except Exception as e:  # surface in the consumer, not the thread
            offer(e)
            return
        offer(_END)

    threading.Thread(target=producer, daemon=True,
                     name="bf-data-prefetch").start()

    def consumer():
        try:
            for seq in itertools.count():
                with op_span("data", "wait", batch=seq):
                    item = q.get()
                if item is _END:
                    return
                if isinstance(item, Exception):
                    raise item
                yield item
        finally:
            # Early break / error in the training loop: release the producer
            # (it may be blocked in a pre-stop put) and drop staged batches.
            stop.set()
            try:
                while True:
                    q.get_nowait()
            except queue.Empty:
                pass

    return consumer()


class ShardedLoader:
    """Batched, shuffled, prefetched feed over in-memory arrays.

    ``arrays`` is a pytree of numpy arrays with matching leading dimension
    (the sample axis).  Each yielded batch is the pytree with leaves of
    shape ``(num_ranks, batch_size, ...)`` placed on device with the
    rank-major sharding — drop-in for the training loops in ``examples/``.

    ``transform`` (optional) maps the raw numpy batch before device
    placement (augmentation, dtype casts) and runs on the prefetch thread,
    off the critical path.
    """

    def __init__(self, arrays, batch_size: int, *,
                 num_ranks: Optional[int] = None, shuffle: bool = True,
                 seed: int = 0, drop_last: bool = True,
                 static_shards: bool = False,
                 transform: Optional[Callable] = None,
                 prefetch: int = 2, sharding=None):
        leaves = jax.tree.leaves(arrays)
        if not leaves:
            raise ValueError("empty dataset")
        n = leaves[0].shape[0]
        for leaf in leaves:
            if leaf.shape[0] != n:
                raise ValueError("all leaves need the same sample axis; got "
                                 f"{leaf.shape[0]} vs {n}")
        self.arrays = arrays
        self.batch_size = batch_size
        self.drop_last = drop_last
        self.sampler = DistributedSampler(
            n, num_ranks=num_ranks, shuffle=shuffle, seed=seed,
            drop_last=drop_last, static_shards=static_shards)
        self.transform = transform
        self.prefetch = prefetch
        # None = rank-major framework sharding; False = raw numpy (host-side
        # loaders, or num_ranks != bf.size()); any Sharding = explicit.
        self.sharding = sharding
        if drop_last and self.sampler.per_rank < batch_size:
            raise ValueError(
                f"per-rank shard ({self.sampler.per_rank}) smaller than "
                f"batch_size ({batch_size})")

    def set_epoch(self, epoch: int) -> None:
        self.sampler.set_epoch(epoch)

    @property
    def steps_per_epoch(self) -> int:
        if self.drop_last:
            return self.sampler.per_rank // self.batch_size
        # drop_last=False: wrap-pad the batch axis too, so the tail trains —
        # SPMD needs static shapes, so a short final batch is not an option.
        return -(-self.sampler.per_rank // self.batch_size)

    def _batches(self) -> Iterator:
        idx = self.sampler.indices()  # (ranks, per_rank)
        need = self.steps_per_epoch * self.batch_size
        if need > idx.shape[1]:  # drop_last=False tail: wrap within shards
            idx = np.concatenate([idx, idx[:, :need - idx.shape[1]]], axis=1)
        for s in range(self.steps_per_epoch):
            take = idx[:, s * self.batch_size:(s + 1) * self.batch_size]
            batch = jax.tree.map(lambda a: a[take], self.arrays)
            if self.transform is not None:
                batch = self.transform(batch)
            yield batch

    def __iter__(self) -> Iterator:
        return prefetch_to_device(self._batches(), size=self.prefetch,
                                  sharding=self.sharding)

    def __len__(self) -> int:
        return self.steps_per_epoch
