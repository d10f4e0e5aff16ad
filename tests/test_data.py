"""Input-pipeline tests: rank partitioning, epoch shuffling, prefetch
placement (parity: the torch DistributedSampler contract the reference's
examples rely on, ``examples/pytorch_mnist.py:100-120``)."""

import numpy as np
import pytest

import jax
import bluefog_tpu as bf
from bluefog_tpu.data import DistributedSampler, ShardedLoader, \
    prefetch_to_device


@pytest.fixture(autouse=True)
def _init():
    if not bf.initialized():
        bf.init()
    yield


def test_sampler_partitions_disjoint_and_complete():
    s = DistributedSampler(64, num_ranks=8, shuffle=True, seed=3)
    idx = s.indices()
    assert idx.shape == (8, 8)
    flat = np.sort(idx.ravel())
    np.testing.assert_array_equal(flat, np.arange(64))  # disjoint + complete


def test_sampler_epoch_reshuffles_deterministically():
    s = DistributedSampler(40, num_ranks=4, seed=7)
    a = s.indices()
    s.set_epoch(1)
    b = s.indices()
    assert not np.array_equal(a, b)
    s2 = DistributedSampler(40, num_ranks=4, seed=7)
    s2.set_epoch(1)
    np.testing.assert_array_equal(b, s2.indices())  # same everywhere


def test_sampler_drop_last_vs_wrap():
    dropped = DistributedSampler(30, num_ranks=4, drop_last=True)
    assert dropped.per_rank == 7
    wrapped = DistributedSampler(30, num_ranks=4, drop_last=False,
                                 shuffle=False)
    assert wrapped.per_rank == 8
    idx = wrapped.indices()
    # wrap-pad: every sample present at least once, 2 duplicates total
    assert idx.size == 32
    np.testing.assert_array_equal(np.unique(idx), np.arange(30))


def test_sharded_loader_shapes_and_sharding():
    n = bf.size()
    x = np.arange(n * 6 * 3, dtype=np.float32).reshape(n * 6, 3)
    y = np.arange(n * 6, dtype=np.int32)
    loader = ShardedLoader({"x": x, "y": y}, batch_size=2, shuffle=False)
    assert loader.steps_per_epoch == 3 and len(loader) == 3
    batches = list(loader)
    assert len(batches) == 3
    b0 = batches[0]
    assert b0["x"].shape == (n, 2, 3) and b0["y"].shape == (n, 2)
    assert isinstance(b0["x"], jax.Array)
    # placed with the rank-major sharding: row r on device r
    assert b0["x"].sharding.is_equivalent_to(
        bf.basics._rank_sharding(), ndim=3)
    # unshuffled: rank r's first batch rows are its shard's first samples
    got = np.asarray(b0["y"])
    np.testing.assert_array_equal(
        got, np.arange(n * 6).reshape(n, 6)[:, :2])


def test_sharded_loader_epoch_coverage():
    n = bf.size()
    y = np.arange(n * 4, dtype=np.int64)
    loader = ShardedLoader({"y": y}, batch_size=2, seed=11)
    seen = np.concatenate(
        [np.asarray(b["y"]).ravel() for b in loader])
    np.testing.assert_array_equal(np.sort(seen), y)  # every sample, once


def test_sharded_loader_transform_runs_off_thread():
    n = bf.size()
    x = np.ones((n * 2, 2), np.float32)

    def tf(batch):
        return {"x": batch["x"] * 3.0}

    loader = ShardedLoader({"x": x}, batch_size=2, transform=tf)
    (batch,) = list(loader)
    np.testing.assert_allclose(np.asarray(batch["x"]), 3.0)


def test_prefetch_propagates_errors():
    def gen():
        yield np.zeros((bf.size(), 1), np.float32)
        raise RuntimeError("boom")

    it = prefetch_to_device(gen())
    next(it)
    with pytest.raises(RuntimeError, match="boom"):
        next(it)


def test_prefetch_raw_numpy_mode():
    batches = [np.zeros((2, 2)), np.ones((2, 2))]
    out = list(prefetch_to_device(iter(batches), sharding=False))
    assert len(out) == 2 and isinstance(out[0], np.ndarray)


def test_sampler_too_few_samples_raises():
    with pytest.raises(ValueError, match="cannot shard"):
        DistributedSampler(3, num_ranks=8)


def test_static_shards_fix_membership_across_epochs():
    s = DistributedSampler(32, num_ranks=4, static_shards=True, seed=5)
    e0 = s.indices()
    s.set_epoch(1)
    e1 = s.indices()
    for r in range(4):  # same members every epoch (decentralized-DP)...
        np.testing.assert_array_equal(np.sort(e0[r]), np.arange(8 * r, 8 * r + 8))
        np.testing.assert_array_equal(np.sort(e1[r]), np.sort(e0[r]))
    assert not np.array_equal(e0, e1)  # ...but shuffled within the shard


def test_loader_drop_last_false_trains_every_sample():
    """drop_last=False must not silently drop the tail: batches wrap-pad so
    each of the 30 samples appears at least once per epoch."""
    y = np.arange(30, dtype=np.int64)
    loader = ShardedLoader({"y": y}, batch_size=3, num_ranks=4,
                           drop_last=False, seed=2, sharding=False)
    assert loader.steps_per_epoch == 3  # ceil(8 / 3)
    seen = np.concatenate([np.asarray(b["y"]).ravel() for b in loader])
    assert seen.size == 4 * 3 * 3
    np.testing.assert_array_equal(np.unique(seen), np.arange(30))
    # constant shapes throughout (SPMD requirement)
    for b in loader:
        assert b["y"].shape == (4, 3)


def test_prefetch_abandoned_consumer_releases_producer():
    """Breaking out of a training loop mid-epoch must not leak the prefetch
    thread blocked on the bounded queue."""
    import threading
    import time

    produced = []

    def gen():
        for i in range(100):
            produced.append(i)
            yield np.zeros((2, 2))

    it = prefetch_to_device(gen(), size=1, sharding=False)
    next(it)
    it.close()  # abandon (same path as `break` + GC of the generator)
    deadline = time.monotonic() + 10
    while time.monotonic() < deadline:
        if not any(t.name == "bf-data-prefetch" and t.is_alive()
                   for t in threading.enumerate()):
            break
        time.sleep(0.05)
    assert not any(t.name == "bf-data-prefetch" and t.is_alive()
                   for t in threading.enumerate()), "producer thread leaked"
    assert len(produced) < 100  # it stopped early, not after exhausting gen


# --- packing documents into rows ------------------------------------------------

def _documents(lengths, seed=0):
    rng = np.random.RandomState(seed)
    return [rng.randint(0, 1000, size=n) for n in lengths]


def test_document_layout_restarts_at_each_document():
    from bluefog_tpu.data import document_layout
    segment_ids, positions = document_layout([3, 1, 4])
    assert segment_ids.tolist() == [0, 0, 0, 1, 2, 2, 2, 2]
    assert positions.tolist() == [0, 1, 2, 0, 0, 1, 2, 3]
    assert segment_ids.dtype == positions.dtype == np.int32
    assert all(len(a) == 0 for a in document_layout([]))


@pytest.mark.parametrize("lengths, seq_len", [
    ([5, 9, 3, 20, 2, 1], 8),          # a document over two and three rows
    ([8, 8, 8], 8),                    # boundaries on the rows' ends
    ([3, 0, 2, 7, 30, 4, 11, 7], 16),  # an empty document is skipped
    ([100], 32),                       # one document, cut and cut again
])
def test_pack_documents_cuts_and_continues_and_pads_nothing(lengths, seq_len):
    from bluefog_tpu.data import pack_documents
    docs = _documents(lengths)
    rows = list(pack_documents(iter(docs), seq_len))
    stream = np.concatenate(docs)
    assert len(rows) == len(stream) // seq_len      # the tail is no row
    for tokens, segment_ids, positions in rows:
        assert tokens.shape == segment_ids.shape == positions.shape \
            == (seq_len,)
        # contiguous ids from 0 that never decrease; positions restart at
        # each id and count on inside it
        assert segment_ids[0] == 0 and positions[0] == 0
        steps = np.diff(segment_ids)
        assert set(steps.tolist()) <= {0, 1}
        assert (positions[1:][steps == 1] == 0).all()
        assert (np.diff(positions)[steps == 0] == 1).all()
    # nothing lost, nothing added, nothing out of order
    packed = np.concatenate([r[0] for r in rows]) if rows else stream[:0]
    np.testing.assert_array_equal(packed, stream[:len(packed)])
    # a document cut at a row's end opens the next row as a new document
    ends = np.cumsum([n for n in lengths if n])
    for i, (_, segment_ids, positions) in enumerate(rows[1:], start=1):
        cut = i * seq_len not in ends
        first = int((segment_ids == 0).sum())
        if cut:     # the rest of the cut document, or a row's worth of it
            left = ends[np.searchsorted(ends, i * seq_len)] - i * seq_len
            assert first == min(left, seq_len)


def test_pack_documents_counts_and_composes_with_the_loaders():
    from bluefog_tpu.data import pack_documents
    from bluefog_tpu.utils import telemetry

    def counted():
        snap = telemetry.snapshot()
        return (snap.get("bf_pack_documents_total", 0),
                snap.get("bf_pack_tokens_total", 0))

    before = counted()
    rows = list(pack_documents(_documents([40, 7, 30, 60, 9, 50]), 16))
    after = counted()
    assert len(rows) == 12
    assert after[1] - before[1] == 12 * 16
    assert after[0] - before[0] == sum(
        int(segment_ids[-1]) + 1 for _, segment_ids, _ in rows)
    # stacked, the rows are arrays like any other: ShardedLoader batches
    # them rank-major, prefetch_to_device passes batches through
    tokens, segment_ids, positions = (np.stack(c) for c in zip(*rows))
    loader = ShardedLoader((tokens, segment_ids, positions), batch_size=3,
                           num_ranks=2, shuffle=False, sharding=False)
    batches = list(loader)
    assert len(batches) == 2
    for batch in batches:
        assert [b.shape for b in batch] == [(2, 3, 16)] * 3
    np.testing.assert_array_equal(batches[0][1][0], segment_ids[:3])
    fed = list(prefetch_to_device(
        ({"tokens": t[None], "segment_ids": s[None], "positions": p[None]}
         for t, s, p in rows), sharding=False))
    assert len(fed) == 12 and fed[3]["positions"].shape == (1, 16)
