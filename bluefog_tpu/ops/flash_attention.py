"""Flash attention: three Pallas TPU kernels (forward, dq, dk/dv) behind one
custom VJP.

The hot op of the long-context path.  ``parallel.ring_attention`` and
``parallel.ulysses`` shard the *sequence*; these kernels make the per-device
block attention itself O(S) in memory by streaming K/V blocks through VMEM
with the online-softmax recurrence — logits never materialize in HBM.

Forward (``bf_flash_fwd``): grid (batch*head, q-block, k-block) with the
online-softmax state (acc, m, l) carried in f32 VMEM scratch across the
sequential k dimension — every operand is a block, so VMEM stays O(block)
regardless of S.

Backward: two kernels recomputing probabilities blockwise from the saved
per-row logsumexp (the standard flash backward) — ``bf_flash_dq`` over
(batch*head, q-block) scanning K blocks, and ``bf_flash_dkv`` over
(batch*head, k-block) scanning Q blocks from the causal frontier.  Nothing
S x S ever touches HBM.

A tile does only what that tile needs.  Every product takes float32
operands and accumulates in float32, and the softmax state, ``lse``,
``delta``, ``exp`` and the mask are float32.  On the chip the array rounds
a default-precision float32 operand to bfloat16 as it enters, one pass, so
the casts of bfloat16 inputs cost nothing there (PR 37: products on the
operands as they arrive gave the same bits in the same time); off the chip
they keep the products exact.  A causal tile is *skipped* when its first
key is past its last query (``pl.when``; its K/V DMAs are elided by clamping
the index map to the frontier), *interior* when its last key is at or
before its first query, and *crossed* otherwise.  An interior tile builds
no mask.  A crossed tile (8 of 36 tiles a head at S 8192, 16 of 136 at S
16384, 4 of 10 at S 4096) knows where the diagonal runs through it, so it
works in chunks of rows and gives each chunk only the keys at or before its
last query: five eighths of the tile's products in the backward, three
quarters in the forward.  ``bf_flash_tiles_total{kernel, kind}`` counts the
three kinds at staging.  ``bf_flash_dkv`` computes its scores as ``k q^T``,
keys along the rows as its accumulators have them, so it transposes no
tile.

A window (``window=W``, with ``causal``: query ``i`` sees the keys ``i - W <
j <= i``) runs as ``bf_flash_win_fwd / dq / dkv``.  The band of visible
pairs has two edges, and a tile is crossed by the diagonal, by the window's
lower edge or by both (a body at each static offset, as above), interior
between them, and does not exist beyond them: the grid's reduction
dimension covers the blocks a block reaches and no more (``_Band``), so the
cost grows with ``S x W``.  The forward of a narrow band (up to
``_BAND_KEYS`` keys a query block) has no reduction dimension at all: a grid
step is handed the band's key blocks as pieces and each row chunk runs one
softmax over its visible range (``_fwd_band_kernel``), since under a narrow
window a step of the running state cost more than the products it served.

A document mask (``segment_ids``, with ``causal``: query ``i`` sees the keys
``j <= i`` of its own document; packed rows) runs as ``bf_flash_seg_fwd / dq
/ dkv``.  Where the visible pairs end is data, so a packed call runs a
*list of live work made from the ids* on the device, inside the call's own
``jax.jit`` (``_doc_work``).  The ids give every position's *bound* (a
query's: its document's first position; a key's, in ``bf_flash_seg_dkv``:
its document's last).  Documents are contiguous, so the blocks of the other
dimension that hold a pair visible to a block of own positions are a range
without a hole: a query block's from the key block where its first query's
document begins to the diagonal's, a key block's from its frontier to the
query block where its last key's document ends.  The ranges are compacted,
own block after own block, into the list ``(own block, other block, kind,
the crossed chunks' ranges)`` in SMEM, and the grid's second dimension is
the list's length, a traced scalar: **no grid step is dead** (the cell's
row fills 33 of the 72 steps a head's list can hold; the rows of a batch
pad to the longest list of the call, and a padded step repeats its row's
last tile and runs nothing).  A packed call's query blocks are fitted to
512 at most and its key blocks stay what the caller asked for (query blocks
of 1024 held too few tiles inside one document, key blocks under 1024 paid
a step of the softmax state too often).  A tile inside one document runs
the causal bodies above as they are.  A tile that a boundary crosses works
in chunks of 256 own rows, each on its visible range of the other dimension
rounded out to whole 256 (a body for each length, the start traced), one
step of the softmax state a chunk.  The kernels alone on the cell's ten
documents, 32 heads of 192 / 128 at S 8192 (one v5e chip, PR 48, forward /
dq / dkv in ms by the device's clock): 2.65 / 2.88 / 3.54, where the static
grid of every tile under the diagonal at 1024 x 1024 and 1024 x 512 took
2.91 / 4.12 / 4.71; one document of 8192: 6.36 / 8.55 / 10.11 for 5.81 /
8.99 / 10.49; ten documents of 819: 2.10 / 1.95 / 2.50 for 2.41 / 3.37 /
3.46.

What bounds a tile (one v5e chip, PR 37, ``PERF.md``): the backward runs its
products at 85 to 91% of the array's peak; the forward spends 1.1 to 1.4 us
of every 4.2 us tile on the softmax state (two reductions along the lanes,
the ``(BQ, 1)`` columns), which neither fewer vector operations nor a
lane-dense state took off it.  A head of 64 fills half the 128-wide array,
so its ceiling is half the peak.

Layout: ``(B, S, H, D)`` like ``models.local_attention``; internally
``(B*H, S, D)``.  The values may have a last dim ``Dv`` of their own (latent
attention: query-key heads of 192, value heads of 128): ``o``, the
accumulator, ``do`` and ``dv`` take it.
"""

from __future__ import annotations

import functools
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from bluefog_tpu.utils import telemetry

__all__ = ["flash_attention", "flash_attention_lse",
           "flash_attention_impl", "platform_in_use", "segment_steps",
           "segment_tiles"]

_NEG_INF = -1e30

# Rows of one chunk of a tile that the diagonal crosses (such a tile computes,
# chunk by chunk, only the keys at or before the chunk's last query).  The
# forward's products lose more to short chunks than the skipped keys save
# under 512 rows; the backward's do not (one v5e chip, PR 37: PERF.md).
_FWD_CHUNK = 512
_BWD_CHUNK = 256
_LANES = 128


def _crossings(block_q: int, block_k: int, window: int = None) -> list:
    """The offsets ``first query - first key`` at which an edge of the
    visible pairs runs through a ``(block_q, block_k)`` tile: the multiples
    of the blocks' gcd strictly between ``-block_q`` (the first key is past
    the last query: *skipped*) and ``block_k - 1`` (the last key is at or
    before the first query: *interior*).  One for equal blocks, two where
    one is twice the other (the backward at heads over 128 runs 1024 x
    512).  Under a ``window`` the pairs are the band ``0 <= query - key <
    window``: the interior ends at ``window - block_q``, and from there to
    ``window + block_k - 1`` (the last key is ``window`` or more before the
    first query: the tile is dead again) the lower edge crosses.  A tile
    may be crossed by both edges (a window of 512 under blocks of 1024
    always is)."""
    g = math.gcd(block_q, block_k)
    if window is None:
        return [m * g for m in range(1 - block_q // g, block_k // g)
                if m * g < block_k - 1]
    return [m * g for m in range(1 - block_q // g,
                                 -(-(window + block_k - 1) // g))
            if not block_k - 1 <= m * g <= window - block_q]


class _Band(NamedTuple):
    """The reduction dimension of one windowed grid.  The ``own`` blocks
    are the query blocks, which reach ``window - 1`` positions back to their
    keys and none ahead, or (``by_keys``: ``bf_flash_dkv``) the key blocks,
    which reach none back and ``window - 1`` ahead to their queries.  The
    grid's steps count from the first block an own block reaches; its index
    maps, kernel bodies and tile counts share the one rule."""
    window: int
    own: int        # positions of an own block
    other: int      # positions of a block of the reduction dimension
    by_keys: bool
    seq: int

    def reach(self, np_, i) -> tuple:
        """``(first, last)`` blocks that own block ``i`` reaches; ``np_`` is
        ``numpy`` at staging and ``jax.numpy`` for a grid index."""
        back, ahead = ((0, self.window - 1) if self.by_keys
                       else (self.window - 1, 0))
        return (np_.maximum(i * self.own - back, 0) // self.other,
                np_.minimum((i + 1) * self.own - 1 + ahead,
                            self.seq - 1) // self.other)

    @classmethod
    def pair(cls, window, block_q: int, block_k: int, seq: int) -> tuple:
        """The bands by query blocks (``bf_flash_fwd``, ``bf_flash_dq``)
        and by key blocks (``bf_flash_dkv``); None twice without a
        window."""
        if window is None:
            return None, None
        return (cls(window, block_q, block_k, False, seq),
                cls(window, block_k, block_q, True, seq))

    @property
    def steps(self) -> int:
        """The most blocks any own block reaches."""
        first, last = self.reach(np, np.arange(self.seq // self.own))
        return int((last - first + 1).max())

    def block(self, np_, i, step) -> tuple:
        """``(block, live)`` of step ``step`` of own block ``i``: a step
        past the last block it reaches is not live."""
        first, last = self.reach(np_, i)
        return first + step, first + step <= last


# A packed call's query blocks are fitted to ``_DOC_BLOCK`` positions at
# most; its key blocks stay what the caller asked for.  Of a tile that a
# document boundary crosses: the own rows of one chunk, and the unit its
# visible range of the other dimension is rounded to (one v5e chip, PR 48:
# PERF.md).
_DOC_BLOCK = 512
_DOC_ROWS = 256
_DOC_KEYS = 256

# ``_Docs.kind_of``'s bits
_FIRST, _LAST, _INSIDE = 1, 2, 4


def _doc_chunk(block: int, want: int) -> int:
    return want if block % want == 0 else block


def _doc_bounds(np_, ids, by_keys: bool):
    """Of every position of the packed rows ``ids`` ``(B, S)`` the *bound* of
    what it sees of the other dimension under the causal mask: a query its
    document's first position (it sees the keys from there to itself), a
    key, ``by_keys``, its document's last (the queries from itself to
    there); found from where the ids change along a row.  ``np_`` is
    ``jax.numpy`` inside a call and ``numpy`` on the host."""
    B, S = ids.shape
    at = np_.arange(S, dtype=np_.int32)
    changes = ids[:, 1:] != ids[:, :-1]
    edge = np_.ones((B, 1), bool)
    if by_keys:
        ends = np_.where(np_.concatenate([changes, edge], axis=1), at, S - 1)
        if np_ is np:
            return np.minimum.accumulate(ends[:, ::-1], axis=1)[:, ::-1]
        return jax.lax.cummin(ends, axis=1, reverse=True)
    starts = np_.where(np_.concatenate([edge, changes], axis=1), at, 0)
    if np_ is np:
        return np.maximum.accumulate(starts, axis=1)
    return jax.lax.cummax(starts, axis=1)


def _doc_reach(np_, bounds, own: int, other: int, by_keys: bool) -> tuple:
    """``(lo, hi)`` ``(B, S / own)``: the blocks of ``other`` positions of
    the other dimension that hold a pair visible to each block of ``own``
    positions.  A query block's run from the block where its first query's
    document begins to the diagonal's, a key block's from its frontier to
    the block where its last key's document ends.  Documents are contiguous,
    so the bounds do not decrease and the range has no hole."""
    at = np_.arange(bounds.shape[1] // own, dtype=np_.int32) * own
    if by_keys:
        hi = bounds[:, own - 1::own] // other
        return np_.broadcast_to(at // other, hi.shape), hi
    lo = bounds[:, ::own] // other
    return lo, np_.broadcast_to((at + own - 1) // other, lo.shape)


class _Docs(NamedTuple):
    """What a kernel of a packed call (``segment_ids``) knows of the
    documents: the list of live work of its row of the batch, made from the
    ids ahead of the grid (``_doc_work``), and its own positions' bounds.
    The *own* dimension is the queries, and the keys in ``bf_flash_dkv``
    (``by_keys``).  In SMEM, ``capacity`` steps a row of the batch, a row
    after the other: ``own_of`` and ``other_of``, the tile of every step;
    ``kind_of`` (read once: ``kind``), whether the step is its own block's
    ``_FIRST`` (the accumulators start), its ``_LAST`` (the results are
    stored), and whether the tile lies ``_INSIDE`` one document;
    ``range_of``, of every chunk of the own rows of a tile that a boundary
    crosses, the chunk's visible range of the tile's other dimension in
    units of ``_DOC_KEYS``: ``start * (units + 1) + count``.  ``bounds``
    (VMEM, ``(own block, 1)``): of every own position of the tile
    (``_doc_bounds``)."""
    own_of: object
    other_of: object
    range_of: object
    bounds: object
    by_keys: bool
    at: object      # this step's place in ``own_of`` and ``other_of``
    kind: object

    @classmethod
    def of(cls, packed, refs) -> tuple:
        """``(docs, the kernel's other refs)`` from ``packed = (heads,
        by_keys, capacity)``; ``(None, refs)`` for a call that is not
        packed."""
        if packed is None:
            return None, refs
        heads, by_keys, capacity = packed
        own_of, other_of, kind_of, range_of, bounds = refs[:5]
        at = pl.program_id(0) // heads * capacity + pl.program_id(1)
        return cls(own_of, other_of, range_of, bounds, by_keys, at,
                   kind_of[at]), refs[5:]

    def tile(self) -> tuple:
        """``(own block, other block, first, last)`` of this grid step."""
        return (self.own_of[self.at], self.other_of[self.at],
                (self.kind & _FIRST) != 0, (self.kind & _LAST) != 0)

    def on_tiles(self, tile, rows, qi, kb, block_q: int, block_k: int):
        """Run the tile ``(qi, kb)`` of a packed grid as it needs.  ``tile``
        as ``_on_tiles`` runs it where the tile holds one document.  Where a
        boundary crosses it: ``rows`` on each chunk of own rows and the
        chunk's visible range of the other dimension, rounded out to whole
        ``_DOC_KEYS`` (a body for each length, the start traced), the
        scores masked by the rows' bounds and by the diagonal.  Nothing on a
        step past the row's list."""
        q_at, k_at = qi * block_q, kb * block_k
        own_at, other_at, block, other = (
            (k_at, q_at, block_k, block_q) if self.by_keys
            else (q_at, k_at, block_q, block_k))
        _on_tiles(tile, qi, kb, causal=True, block_q=block_q,
                  block_k=block_k, live=(self.kind & _INSIDE) != 0)
        chunk = _doc_chunk(block, _DOC_ROWS)
        unit = _doc_chunk(other, _DOC_KEYS)
        units = other // unit
        for c in range(block // chunk):
            reach = self.range_of[self.at * (block // chunk) + c]
            start = pl.multiple_of(reach // (units + 1) * unit, unit)
            own = slice(c * chunk, (c + 1) * chunk)
            see = functools.partial(
                self.visible, own=own, own_at=own_at + c * chunk,
                other_at=other_at + start)
            for n in range(1, units + 1):
                pl.when(reach % (units + 1) == n)(functools.partial(
                    rows, own, pl.ds(start, n * unit), see))

    def visible(self, s, *, own, own_at, other_at):
        """``s`` (own positions along the rows, from ``own_at``; the others
        along the lanes, from ``other_at``: both traced) with the pairs of
        two documents and those whose key is past their query at
        ``-1e30``."""
        along = lambda axis, first: first + jax.lax.broadcasted_iota(
            jnp.int32, tuple(n if a == axis else 1
                             for a, n in enumerate(s.shape)), axis)
        rows, others, bounds = along(0, own_at), along(1, other_at), \
            self.bounds[own, :]
        seen = ((others <= bounds) & (others >= rows) if self.by_keys
                else (others >= bounds) & (others <= rows))
        return jnp.where(seen, s, _NEG_INF)


def _doc_capacity(block_q: int, block_k: int, seq: int) -> int:
    """The steps a head's list holds at most: the tiles at or under the
    diagonal (one document fills it), by query blocks or by key blocks."""
    return sum(((i + 1) * block_q - 1) // block_k + 1
               for i in range(seq // block_q))


def _doc_work(ids, block_q: int, block_k: int, by_keys: bool) -> tuple:
    """The live work of a packed call, made from the ids ``(B, S)`` on the
    device: ``(steps, (own_of, other_of, kind_of, range_of), bounds)`` as
    ``_Docs`` has them.  Own block after own block, each block's range of
    ``_doc_reach`` in order, and nothing else: ``steps``, the grid's second
    dimension, is the longest row's list, and a shorter row's steps past its
    end repeat its last tile (no block moves) with no bit in ``kind_of`` and
    no range in ``range_of``: they run nothing."""
    own, other = (block_k, block_q) if by_keys else (block_q, block_k)
    bounds = _doc_bounds(jnp, ids, by_keys)
    lo, hi = _doc_reach(jnp, bounds, own, other, by_keys)
    ends = jnp.cumsum(hi - lo + 1, axis=1)                  # (B, S / own)
    t = jnp.arange(_doc_capacity(block_q, block_k, ids.shape[1]),
                   dtype=jnp.int32)[None]
    own_of = jnp.minimum((t[:, :, None] >= ends[:, None, :]).sum(
        axis=-1, dtype=jnp.int32), ends.shape[1] - 1)       # (B, capacity)
    of_own = lambda x: jnp.take_along_axis(x, own_of, axis=1)
    other_of = jnp.minimum(of_own(hi) - (of_own(ends) - 1 - t), of_own(hi))
    live = t < ends[:, -1:]
    bound_at = lambda at: jnp.take_along_axis(bounds, at, axis=1)
    own_at, other_at = own_of * own, other_of * other
    inside = (other_at + other - 1 <= bound_at(own_at) if by_keys
              else other_at >= bound_at(own_at + own - 1))
    kind_of = (_FIRST * (live & (other_of == of_own(lo)))
               + _LAST * (live & (other_of == of_own(hi)))
               + _INSIDE * (live & inside))
    chunk, unit = _doc_chunk(own, _DOC_ROWS), _doc_chunk(other, _DOC_KEYS)
    ranges = []
    for r0 in range(0, own, chunk):
        first, last = own_at + r0, own_at + r0 + chunk - 1
        see_lo, see_hi = ((first, bound_at(last)) if by_keys
                          else (bound_at(first), last))
        start = jnp.maximum(see_lo - other_at, 0) // unit
        count = jnp.minimum(see_hi - other_at, other - 1) // unit + 1 - start
        ranges.append(jnp.where(live & ~inside & (count > 0),
                                start * (other // unit + 1) + count, 0))
    flat = lambda x: x.astype(jnp.int32).reshape(-1)
    return (ends[:, -1].max(), (flat(own_of), flat(other_of), flat(kind_of),
                                flat(jnp.stack(ranges, axis=-1))),
            bounds[:, :, None])


def _step_of(band, i, step) -> tuple:
    """``(block, live, window)`` of a grid step: the step itself, nothing
    to be live for and no window without a ``band``."""
    if band is None:
        return step, None, None
    return band.block(jnp, i, step) + (band.window,)


def _on_tiles(tile, qi, kb, *, causal: bool, block_q: int, block_k: int,
              window: int = None, live=None):
    """Run ``tile(offset)`` as the tile ``(qi, kb)`` needs.  ``offset`` is
    ``None`` for an *interior* tile and every tile of a non-causal call: no
    score is masked, so it builds no iota, compare or select.  A *crossed*
    tile gets its offset as a Python int, a body for each of the blocks'
    crossings, so the mask and the keys it may skip are static.  A
    *skipped* tile runs nothing.  Under a ``window`` the interior ends
    where the lower edge begins to cross, and ``live`` (a step of a
    windowed grid past its block's reach is not) joins every condition."""
    if not causal:
        tile(None)
        return
    offset = qi * block_q - kb * block_k
    when = lambda hit: pl.when(hit if live is None else live & hit)
    interior = offset >= block_k - 1
    if window is not None:
        interior &= offset <= window - block_q
    if window is None or block_k - 1 <= window - block_q:
        when(interior)(functools.partial(tile, None))
    for crossing in _crossings(block_q, block_k, window):
        when(offset == crossing)(functools.partial(tile, crossing))


def _chunk_rows(offset, block: int, want: int) -> int:
    """Rows of a chunk of a tile's own dimension: ``want`` for a crossed
    tile whose block it divides, the whole block otherwise."""
    return want if offset is not None and block % want == 0 else block


def _keys_of(offset, q0: int, rows: int, block_k: int, window: int = None):
    """``(lo, hi, masked)`` for the queries ``[q0, q0 + rows)`` of a tile:
    they need the keys ``[lo, hi)`` (whole lane tiles; ``hi == lo``: none),
    and ``masked`` says whether some pair among those is not visible."""
    if offset is None:
        return 0, block_k, False
    first, last = offset + q0, offset + q0 + rows - 1  # as keys of the tile
    hi = min(block_k, -(-(last + 1) // _LANES) * _LANES)
    lo = 0 if window is None else max(
        (first - window + 1) // _LANES * _LANES, 0)
    if hi <= lo:
        return 0, 0, False
    return lo, hi, first < hi - 1 or (window is not None
                                      and lo <= last - window)


def _queries_of(offset, k0: int, rows: int, block_q: int,
                window: int = None):
    """``(lo, hi, masked)`` for the keys ``[k0, k0 + rows)`` of a tile:
    they are seen by the queries ``[lo, hi)`` (``hi == lo``: none)."""
    if offset is None:
        return 0, block_q, False
    lo = max((k0 - offset) // _LANES * _LANES, 0)
    hi = block_q if window is None else min(
        block_q, -(-(k0 + rows - 1 - offset + window) // _LANES) * _LANES)
    if hi <= lo:
        return 0, 0, False
    return lo, hi, k0 + rows - 1 > lo + offset or (
        window is not None and k0 <= hi - 1 + offset - window)


def _visible(s, *, key0, query0, keys_axis: int, window: int = None,
             floor=None):
    """``s`` with the pairs that are not visible at ``-1e30``: those whose
    key is past their query and, under a ``window``, those whose key is
    ``window`` or more before it.  Keys run along ``keys_axis`` from
    ``key0``, queries along the other axis from ``query0`` (the tile's
    offset included); an edge that does not run through ``s`` costs no
    compare, and ``s`` comes back as it is where none does.  ``floor`` (a
    traced scalar): keys under it do not exist."""
    n_keys, n_queries = s.shape[keys_axis], s.shape[1 - keys_axis]
    along = lambda axis, first: first + jax.lax.broadcasted_iota(
        jnp.int32, tuple(n if a == axis else 1 for a, n in enumerate(s.shape)),
        axis)
    edges = []
    if key0 + n_keys - 1 > query0:
        edges.append(along(keys_axis, key0) <= along(1 - keys_axis, query0))
    if window is not None and key0 <= query0 + n_queries - 1 - window:
        edges.append(along(keys_axis, key0) > along(1 - keys_axis,
                                                    query0 - window))
    if floor is not None:
        edges.append(along(keys_axis, key0) >= floor)
    if not edges:
        return s
    return jnp.where(functools.reduce(jnp.logical_and, edges), s, _NEG_INF)


def _fwd_kernel(*refs, scale: float, causal: bool, block_q: int,
                block_k: int, band: _Band = None, packed: tuple = None):
    """Grid (bh, q-block, k-block): online-softmax recurrence with the
    running (acc, m, l) state in f32 VMEM scratch across the sequential
    innermost k dimension.  Every operand is a block — VMEM stays O(block),
    so sequence length is bounded by HBM, not VMEM.  In a windowed grid
    (``band``) the innermost dimension counts from the first key block the
    query block reaches; in a ``packed`` one (``_Docs``) from the first key
    block that holds a document of the query block's."""
    docs, (q_ref, k_ref, v_ref, o_ref, lse_ref, acc_ref, m_ref,
           l_ref) = _Docs.of(packed, refs)
    if docs is None:
        qi, step = pl.program_id(1), pl.program_id(2)
        kb, live, window = _step_of(band, qi, step)
    else:
        (qi, kb, first, last), window = docs.tile(), None

    @pl.when(step == 0 if docs is None else first)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)

    def rows(own, keys, see=None):
        """One step of the state of the queries ``own`` over ``keys``;
        ``see`` masks the scores."""
        q = q_ref[own, :].astype(jnp.float32)          # (rows, D)
        k = k_ref[keys, :].astype(jnp.float32)         # (keys, D)
        v = v_ref[keys, :].astype(jnp.float32)         # (keys, Dv)
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
        if see is not None:
            s = see(s)
        m_prev = m_ref[own, :]
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_ref[own, :] = l_ref[own, :] * corr + p.sum(
            axis=-1, keepdims=True)
        acc_ref[own, :] = acc_ref[own, :] * corr + jnp.dot(
            p, v, preferred_element_type=jnp.float32)
        m_ref[own, :] = m_new

    def tile(offset):
        chunk = _chunk_rows(offset, block_q, _FWD_CHUNK)
        for q0 in range(0, block_q, chunk):
            lo, hi, masked = _keys_of(offset, q0, chunk, block_k, window)
            if hi == lo:
                continue
            rows(slice(q0, q0 + chunk), slice(lo, hi),
                 functools.partial(_visible, key0=lo, query0=q0 + offset,
                                   keys_axis=1, window=window)
                 if masked else None)

    if docs is None:
        _on_tiles(tile, qi, kb, causal=causal, block_q=block_q,
                  block_k=block_k, window=window, live=live)
    else:
        docs.on_tiles(tile, rows, qi, kb, block_q, block_k)

    @pl.when(step == pl.num_programs(2) - 1 if docs is None else last)
    def _store():
        l = jnp.maximum(l_ref[:], 1e-30)
        o_ref[:] = (acc_ref[:] / l).astype(o_ref.dtype)
        # (block_q, 1): the trailing singleton keeps the block's minor dim
        # equal to the array's (Mosaic requires minor block dims be
        # (8,128)-tiled or full) — a flat (block_q,) lse block fails to
        # lower on TPU.
        lse_ref[:] = m_ref[:] + jnp.log(l)


# Keys of a narrow band: up to this many (a query block's own and the
# window's before them) the windowed forward takes a row chunk's whole
# visible range in one softmax pass (512 rows x 2048 float32 scores are 4
# MiB of VMEM); a wider band goes block by block through the grid.
_BAND_KEYS = 2048


def _fwd_band_kernel(q_ref, *refs, scale: float, block_q: int, block_k: int,
                     window: int, pieces: int, first: int):
    """Grid (bh, q-block), no reduction dimension: the forward of a narrow
    window.  ``refs`` are ``pieces`` key blocks, as many value blocks, then
    ``o`` and ``lse``: piece ``j`` is key block ``qi * block_q / block_k +
    first + j`` (``first <= 0``: the window's blocks before the query
    block's own), so its offset from the query block is static.  A row
    chunk takes the lane tiles of keys between its two edges from the
    pieces they lie in and runs ONE softmax over them: no running state,
    nothing rescaled (one v5e chip, PR 40: a step of the online state cost
    1.3 us a 512-row chunk, more than the chunk's products)."""
    k_refs, v_refs = refs[:pieces], refs[pieces:2 * pieces]
    o_ref, lse_ref = refs[2 * pieces:]
    offset = -first * block_k           # first query - first key of piece 0
    # piece j of the first query blocks may lie before the sequence: its
    # block index was clamped to 0 and its keys do not exist
    floor = -(pl.program_id(1) * block_q - offset)
    rows = _chunk_rows(offset, block_q, _FWD_CHUNK)
    for q0 in range(0, block_q, rows):
        lo, hi, _ = _keys_of(offset, q0, rows, pieces * block_k, window)
        own = slice(q0, q0 + rows)
        q = q_ref[own, :].astype(jnp.float32)              # (rows, D)
        scores, values = [], []
        for j in range(pieces):
            a, b = max(lo, j * block_k), min(hi, (j + 1) * block_k)
            if b <= a:
                continue
            part = slice(a - j * block_k, b - j * block_k)
            k = k_refs[j][part, :].astype(jnp.float32)
            s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
            scores.append(_visible(
                s, key0=a, query0=q0 + offset, keys_axis=1, window=window,
                floor=floor if j < -first else None))
            values.append(v_refs[j][part, :].astype(jnp.float32))
        m = functools.reduce(jnp.maximum, [
            s.max(axis=-1, keepdims=True) for s in scores])
        probs = [jnp.exp(s - m) for s in scores]
        l = sum(p.sum(axis=-1, keepdims=True) for p in probs)
        acc = sum(jnp.dot(p, v, preferred_element_type=jnp.float32)
                  for p, v in zip(probs, values))
        o_ref[own, :] = (acc / l).astype(o_ref.dtype)
        lse_ref[own, :] = m + jnp.log(l)


def _band_pieces(window, block_q: int, block_k: int):
    """``(pieces, first)`` of the banded forward, or None where the call
    goes through the grid: no window, blocks that do not nest, or a band of
    more than ``_BAND_KEYS`` keys."""
    if window is None or block_q % block_k:
        return None
    first = (1 - window) // block_k
    pieces = block_q // block_k - first
    return (pieces, first) if pieces * block_k <= _BAND_KEYS else None


def _fit_block(want: int, seq_len: int) -> int:
    """Largest block <= ``want`` that divides ``seq_len`` (halving down), so
    the default 1024 still serves S=768/1280/... by dropping to 256/128.
    Raises when the fit degrades past Mosaic's tiling floor (second-minor
    block dims must be multiples of 8, or the full dimension)."""
    b = min(want, seq_len)
    while seq_len % b:
        b //= 2
    if b % 8 and b != seq_len:
        raise ValueError(
            f"seq len {seq_len} has no TPU-tileable block <= {want}: the "
            f"largest power-of-two divisor is {b}, below Mosaic's multiple-"
            "of-8 floor. Pad the sequence or pass explicit block sizes.")
    return b


def _window_block(want: int, window) -> int:
    """The key block of a windowed forward: no longer than the window
    rounded up to a power of two (128 lanes at least), so that no piece of
    a band is crossed by both edges.  From the window alone;
    ``window=None`` leaves ``want``."""
    if window is None:
        return want
    return min(want, max(_LANES, 1 << (window - 1).bit_length()))


def _grid_offsets(n_qb: int, n_kb: int, block_q: int, block_k: int,
                  band: _Band = None) -> list:
    """Of every step of one head's grid the offset ``first query - first
    key`` of its tile (None: a step of a windowed grid past its block's
    reach); of a banded forward, of every piece of every query block."""
    if band is None:
        return [qi * block_q - kb * block_k
                for qi in range(n_qb) for kb in range(n_kb)]
    pieces = not band.by_keys and _band_pieces(band.window, block_q, block_k)
    if pieces:      # a piece before the sequence's start is a dead step
        return [-(pieces[1] + j) * block_k
                if qi * block_q + (pieces[1] + j) * block_k >= 0 else None
                for qi in range(n_qb) for j in range(pieces[0])]
    sign = -1 if band.by_keys else 1
    offsets = []
    for i in range(band.seq // band.own):
        for step in range(band.steps):
            j, live = band.block(np, i, step)
            offsets.append(sign * int(i * band.own - j * band.other)
                           if live else None)
    return offsets


def _kernel_name(kind: str, band, packed: bool = False) -> str:
    """``bf_flash_<kind>``, ``bf_flash_win_<kind>`` for a windowed call or
    ``bf_flash_seg_<kind>`` for a packed one: the device trace tells a
    model's window layers from its full ones, and packed rows from whole."""
    return (f"bf_flash_{'seg_' if packed else ''}"
            f"{'win_' if band is not None else ''}{kind}")


def _staged(kind: str, heads: int, seq: int, block_q: int, block_k: int,
            causal: bool, band: _Band = None, packed: bool = False):
    """Count one staging of the kernel ``kind`` and the tiles of its call
    by kind (``heads`` grids of ``_grid_offsets``; a non-causal call's are
    all interior, a windowed grid's dead steps are skipped; a ``packed``
    call counts the steps a head's list can hold at most, the tiles at or
    under the diagonal, as ``by_data``: the documents decide on the device
    how many of them the list holds).  A wrapper's Python runs at trace
    time: once a shape behind ``jax.jit``, once a call for a bare
    kernel, and each staging is a Mosaic lowering."""
    kernel = _kernel_name(kind, band, packed)
    telemetry.inc("bf_kernel_stagings_total", kernel=kernel)
    offsets = _grid_offsets(seq // block_q, seq // block_k, block_q, block_k,
                            band)
    last_interior = math.inf if band is None else band.window - block_q
    skipped = sum(o is None or (causal and o <= -block_q) for o in offsets)
    if packed:      # its grid has no tile past the diagonal
        telemetry.inc("bf_flash_tiles_total",
                      heads * (len(offsets) - skipped), kernel=kernel,
                      kind="by_data")
        return
    interior = sum(o is not None and (
        not causal or block_k - 1 <= o <= last_interior) for o in offsets)
    for tiles, n in (("skipped", skipped), ("interior", interior),
                     ("crossed", len(offsets) - skipped - interior)):
        telemetry.inc("bf_flash_tiles_total", heads * n, kernel=kernel,
                      kind=tiles)


def _reduction_index(band, causal_clamp):
    """``(i, j) ->`` the block of the reduction dimension that step ``j`` of
    own block ``i`` loads.  Without a window the step itself, clamped into
    the causal range by ``causal_clamp`` (falsy: not causal): a skipped
    step then repeats the previous block index and Pallas elides the DMA —
    without this, masked tiles would still stream their blocks from HBM (~2x
    input traffic).  In a windowed grid the steps count from the first
    block that ``i`` reaches and stop at the last."""
    if band is not None:
        def reached(i, j):
            first, last = band.reach(jnp, i)
            return jnp.minimum(first + j, last)
        return reached
    return causal_clamp or (lambda i, j: j)


# Each kernel call sits behind ``jax.jit``: the stagings of one shape (a
# layer's primal, its rule's forward and its remat recompute, layer after
# layer) then trace the kernel's tile bodies once and not once each.
_STATIC = ("scale", "causal", "block_q", "block_k", "interpret", "vma",
           "window")


@functools.partial(jax.jit, static_argnames=_STATIC)
def _fwd_call(qf, kf, vf, *, scale, causal, block_q, block_k, interpret,
              vma, window=None):
    """``bf_flash_fwd`` on folded ``(B*H, S, D)`` operands: ``o`` and the
    per-row logsumexp ``(B*H, S, 1)``."""
    bh, S, D = qf.shape
    Dv = vf.shape[-1]
    band, _ = _Band.pair(window, block_q, block_k, S)
    own = lambda b, i, *_: (b, i, 0)
    out = dict(
        out_specs=[pl.BlockSpec((None, block_q, Dv), own),
                   pl.BlockSpec((None, block_q, 1), own)],
        out_shape=[jax.ShapeDtypeStruct((bh, S, Dv), qf.dtype, vma=vma),
                   jax.ShapeDtypeStruct((bh, S, 1), jnp.float32, vma=vma)],
        interpret=interpret)
    pieces = _band_pieces(window, block_q, block_k)
    if pieces:
        n, first = pieces
        piece = lambda dim, j: pl.BlockSpec(
            (None, block_k, dim), lambda b, i: (b, jnp.maximum(
                i * (block_q // block_k) + first + j, 0), 0))
        return pl.pallas_call(
            functools.partial(_fwd_band_kernel, scale=scale, block_q=block_q,
                              block_k=block_k, window=window, pieces=n,
                              first=first),
            name=_kernel_name("fwd", band), grid=(bh, S // block_q),
            in_specs=[pl.BlockSpec((None, block_q, D), own)]
            + [piece(D, j) for j in range(n)]
            + [piece(Dv, j) for j in range(n)],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel")),
            **out,
        )(qf, *[kf] * n, *[vf] * n)
    # never past this q-block's diagonal
    red = _reduction_index(band, causal and (lambda i, j: jnp.minimum(
        j, ((i + 1) * block_q - 1) // block_k)))
    kv_idx = lambda b, i, j: (b, red(i, j), 0)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, scale=scale, causal=causal,
                          block_q=block_q, block_k=block_k, band=band),
        name=_kernel_name("fwd", band),
        grid=(bh, S // block_q, band.steps if band else S // block_k),
        in_specs=[
            pl.BlockSpec((None, block_q, D), own),
            pl.BlockSpec((None, block_k, D), kv_idx),
            pl.BlockSpec((None, block_k, Dv), kv_idx),
        ],
        scratch_shapes=[pltpu.VMEM((block_q, Dv), jnp.float32),
                        pltpu.VMEM((block_q, 1), jnp.float32),
                        pltpu.VMEM((block_q, 1), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        **out,
    )(qf, kf, vf)


def _fwd(q, k, v, *, causal, block_q, block_k, interpret, vma=None,
         scale=None, window=None, ids=None):
    B, S, H, D = q.shape
    Dv = v.shape[-1]
    if scale is None:
        scale = 1.0 / np.sqrt(D)
    bh = B * H
    fold = lambda t: t.transpose(0, 2, 1, 3).reshape(bh, S, t.shape[-1])
    qf, kf, vf = fold(q), fold(k), fold(v)
    if ids is not None:
        block_q = min(block_q, _DOC_BLOCK)
    block_q = _fit_block(block_q, S)
    block_k = _fit_block(_window_block(block_k, window), S)
    _staged("fwd", bh, S, block_q, block_k, causal,
            _Band.pair(window, block_q, block_k, S)[0], ids is not None)
    call = _fwd_call if ids is None else functools.partial(
        _packed_fwd_call, ids=ids)
    o, lse = call(qf, kf, vf, scale=float(scale), causal=causal,
                  block_q=block_q, block_k=block_k, interpret=interpret,
                  vma=vma, window=window)
    lse = lse[..., 0]
    unfold = lambda t: t.reshape(B, H, S, Dv).transpose(0, 2, 1, 3)
    return unfold(o), (qf, kf, vf, o, lse, (B, S, H, D, scale, causal))


def _dq_kernel(*refs, scale: float, causal: bool, block_q: int,
               block_k: int, band: _Band = None, packed: tuple = None):
    """Grid (bh, q-block, k-block): recompute P from the saved logsumexp and
    accumulate ds @ K into a f32 VMEM scratch across the (sequential,
    innermost) k dimension; one cast-and-store to the output block on the
    last step.  Every operand is a block — VMEM stays O(block), never
    O(S)."""
    docs, (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
           acc_ref) = _Docs.of(packed, refs)
    if docs is None:
        qi, step = pl.program_id(1), pl.program_id(2)
        kb, live, window = _step_of(band, qi, step)
    else:
        (qi, kb, first, last), window = docs.tile(), None

    @pl.when(step == 0 if docs is None else first)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    def rows(own, keys, see=None):
        q = q_ref[own, :].astype(jnp.float32)          # (rows, D)
        k = k_ref[keys, :].astype(jnp.float32)         # (keys, D)
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
        if see is not None:
            s = see(s)
        p = jnp.exp(s - lse_ref[own, :])               # masked -> 0
        dp = jnp.dot(do_ref[own, :].astype(jnp.float32),
                     v_ref[keys, :].astype(jnp.float32).T,
                     preferred_element_type=jnp.float32)
        ds = p * (dp - delta_ref[own, :]) * scale
        acc_ref[own, :] += jnp.dot(ds, k,
                                   preferred_element_type=jnp.float32)

    def tile(offset):
        chunk = _chunk_rows(offset, block_q, _BWD_CHUNK)
        for q0 in range(0, block_q, chunk):
            lo, hi, masked = _keys_of(offset, q0, chunk, block_k, window)
            if hi == lo:
                continue
            rows(slice(q0, q0 + chunk), slice(lo, hi),
                 functools.partial(_visible, key0=lo, query0=q0 + offset,
                                   keys_axis=1, window=window)
                 if masked else None)

    if docs is None:
        _on_tiles(tile, qi, kb, causal=causal, block_q=block_q,
                  block_k=block_k, window=window, live=live)
    else:
        docs.on_tiles(tile, rows, qi, kb, block_q, block_k)

    @pl.when(step == pl.num_programs(2) - 1 if docs is None else last)
    def _store():
        dq_ref[:] = acc_ref[:].astype(dq_ref.dtype)


def _dkv_kernel(*refs, scale: float, causal: bool, block_q: int,
                block_k: int, band: _Band = None, packed: tuple = None):
    """Grid (bh, k-block, q-block): accumulate P.T @ dO and ds.T @ Q into f32
    VMEM scratches across the (sequential, innermost) q dimension.  The
    scores are computed as ``k q^T``, (BK, BQ) with the keys along the rows
    as the accumulators have them, so no (BQ, BK) tile is transposed; the
    per-query ``lse`` and ``delta`` columns are turned to rows once a
    tile.  In a ``packed`` grid the steps stop at the last query block that
    holds a document of the key block's."""
    docs, (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dk_ref, dv_ref,
           dk_acc, dv_acc) = _Docs.of(packed, refs)
    if docs is None:
        kb, step = pl.program_id(1), pl.program_id(2)
        qi, live, window = _step_of(band, kb, step)
    else:
        (kb, qi, first, last), window = docs.tile(), None

    @pl.when(step == 0 if docs is None else first)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    def rows(own, queries, see=None, turned=None):
        """The keys ``own`` against ``queries``; ``turned``: the tile's
        ``lse`` and ``delta`` as rows ``(1, BQ)`` where the caller has
        turned them, else the queries' own are turned here."""
        if turned is None:
            lse, delta, per_query = lse_ref, delta_ref, \
                lambda ref: ref[queries, :].T
        else:
            (lse, delta), per_query = turned, lambda row: row[:, queries]
        q = q_ref[queries, :].astype(jnp.float32)      # (queries, D)
        do = do_ref[queries, :].astype(jnp.float32)
        st = jnp.dot(k_ref[own, :].astype(jnp.float32), q.T,
                     preferred_element_type=jnp.float32) * scale
        if see is not None:
            st = see(st)
        pt = jnp.exp(st - per_query(lse))              # masked -> 0
        dpt = jnp.dot(v_ref[own, :].astype(jnp.float32), do.T,
                      preferred_element_type=jnp.float32)
        dst = pt * (dpt - per_query(delta)) * scale
        dv_acc[own, :] += jnp.dot(pt, do,
                                  preferred_element_type=jnp.float32)
        dk_acc[own, :] += jnp.dot(dst, q,
                                  preferred_element_type=jnp.float32)

    def tile(offset):
        lse, delta = lse_ref[:].T, delta_ref[:].T          # (1, BQ)
        chunk = _chunk_rows(offset, block_k, _BWD_CHUNK)
        for k0 in range(0, block_k, chunk):
            lo, hi, masked = _queries_of(offset, k0, chunk, block_q, window)
            if hi == lo:
                continue
            rows(slice(k0, k0 + chunk), slice(lo, hi),
                 functools.partial(_visible, key0=k0, query0=lo + offset,
                                   keys_axis=0, window=window)
                 if masked else None, (lse, delta))

    if docs is None:
        _on_tiles(tile, qi, kb, causal=causal, block_q=block_q,
                  block_k=block_k, window=window, live=live)
    else:
        docs.on_tiles(tile, rows, qi, kb, block_q, block_k)

    @pl.when(step == pl.num_programs(2) - 1 if docs is None else last)
    def _store():
        dk_ref[:] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[:] = dv_acc[:].astype(dv_ref.dtype)


@functools.partial(jax.jit, static_argnames=_STATIC)
def _bwd_calls(qf, kf, vf, dof, lse3, delta, *, scale, causal, block_q,
               block_k, interpret, vma, window=None):
    """``bf_flash_dq`` and ``bf_flash_dkv`` on folded operands, the per-row
    ``lse3`` and ``delta`` as ``(B*H, S, 1)`` float32: ``dq, dk, dv``."""
    bh, S, D = qf.shape
    Dv = vf.shape[-1]
    n_qb, n_kb = S // block_q, S // block_k
    by_queries, by_keys = _Band.pair(window, block_q, block_k, S)

    # index helpers: i = this kernel's "own" block dim, j = reduction dim
    # (``_reduction_index``: clamped into the un-masked range).
    at = lambda block, dim: lambda sel: pl.BlockSpec(
        (None, block, dim), lambda b, i, j: (b, sel(i, j), 0))
    q_at, k_at = at(block_q, D), at(block_k, D)
    do_at, v_at = at(block_q, Dv), at(block_k, Dv)
    r_at = at(block_q, 1)
    own = lambda i, j: i
    # dq grid: j = k-block; never past this q-block's diagonal.
    red_dq = _reduction_index(by_queries, causal and (
        lambda i, j: jnp.minimum(j, ((i + 1) * block_q - 1) // block_k)))
    # dkv grid: j = q-block; never before this k-block's frontier.
    red_kv = _reduction_index(by_keys, causal and (
        lambda i, j: jnp.maximum(j, (i * block_k) // block_q)))

    params = dict(compiler_params=pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary")))
    kernel = dict(scale=scale, causal=causal, block_q=block_q,
                  block_k=block_k)

    dq = pl.pallas_call(
        functools.partial(_dq_kernel, **kernel, band=by_queries),
        name=_kernel_name("dq", by_queries),
        grid=(bh, n_qb, by_queries.steps if by_queries else n_kb),
        in_specs=[q_at(own), k_at(red_dq), v_at(red_dq), do_at(own),
                  r_at(own), r_at(own)],
        out_specs=q_at(own),
        out_shape=jax.ShapeDtypeStruct((bh, S, D), qf.dtype, vma=vma),
        scratch_shapes=[pltpu.VMEM((block_q, D), jnp.float32)],
        interpret=interpret, **params,
    )(qf, kf, vf, dof, lse3, delta)

    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, **kernel, band=by_keys),
        name=_kernel_name("dkv", by_keys),
        grid=(bh, n_kb, by_keys.steps if by_keys else n_qb),
        in_specs=[q_at(red_kv), k_at(own), v_at(own), do_at(red_kv),
                  r_at(red_kv), r_at(red_kv)],
        out_specs=[k_at(own), v_at(own)],
        out_shape=[
            jax.ShapeDtypeStruct((bh, S, D), kf.dtype, vma=vma),
            jax.ShapeDtypeStruct((bh, S, Dv), vf.dtype, vma=vma),
        ],
        scratch_shapes=[pltpu.VMEM((block_k, D), jnp.float32),
                        pltpu.VMEM((block_k, Dv), jnp.float32)],
        interpret=interpret, **params,
    )(qf, kf, vf, dof, lse3, delta)
    return dq, dk, dv


def _bwd(block_q, block_k, interpret, vma, window, res, cotangents,
         ids=None):
    """Flash backward as two Pallas kernels (dq accumulating over k-blocks;
    dk/dv accumulating over q-blocks) — O(block) VMEM, O(S) HBM, and no
    S x S materialization anywhere.

    Takes cotangents for BOTH outputs ``(do, dlse)``.  A non-zero ``dlse``
    (sequence-parallel consumers weight partial results by their logsumexp,
    e.g. the ring-attention merge) folds into the delta term:
    ``d lse_i / d s_ij = p_ij``, so ``ds += dlse_i * p_ij`` — i.e.
    ``delta_eff = delta - dlse``."""
    qf, kf, vf, o, lse, (B, S, H, D, scale, causal) = res
    Dv = vf.shape[-1]
    do, dlse = cotangents
    bh = B * H
    dof = do.transpose(0, 2, 1, 3).reshape(bh, S, Dv)
    delta = jnp.sum(dof.astype(jnp.float32) * o.astype(jnp.float32),
                    axis=-1, keepdims=True)               # (bh, S, 1)
    delta = delta - dlse.astype(jnp.float32).transpose(0, 2, 1) \
        .reshape(bh, S)[..., None]

    if ids is not None:
        block_q = min(block_q, _DOC_BLOCK)
    block_q = _fit_block(block_q, S)
    block_k = _fit_block(block_k, S)
    if D > 128 and block_q * block_k > 512 * 1024:
        # Heads wider than one tile of 128 lanes take two: the float32
        # (block_q, block_k) score tiles beside q, k and their accumulators
        # at 1024 x 1024 then overflow the 16 MiB of scoped VMEM (the v5e
        # compiler refuses the dq kernel at D = 192), so the backward takes
        # half as many keys a tile.
        block_k = _fit_block(512, S)
    for kind, band in zip(("dq", "dkv"),
                          _Band.pair(window, block_q, block_k, S)):
        _staged(kind, bh, S, block_q, block_k, causal, band, ids is not None)
    calls = _bwd_calls if ids is None else functools.partial(
        _packed_bwd_calls, ids=ids)
    # (the residuals' Python scalars come back as jax literals: not hashable)
    dq, dk, dv = calls(qf, kf, vf, dof, lse[..., None], delta,
                       scale=float(scale), causal=bool(causal),
                       block_q=block_q, block_k=block_k,
                       interpret=interpret, vma=vma, window=window)
    unfold = lambda t: t.reshape(B, H, S, t.shape[-1]).transpose(0, 2, 1, 3)
    return unfold(dq), unfold(dk), unfold(dv)


# which of a step's two blocks an operand of a packed grid moves with
# (``_packed_specs``)
_OWN, _OTHER = 0, 1


def _packed_specs(heads: int, capacity: int) -> tuple:
    """Block specs of a packed grid's operands: ``at(block, dim)(of)`` as
    in ``_bwd_calls``, ``of`` ``_OWN`` for the own block of the grid's step
    and ``_OTHER`` for the other (``_Docs.own_of`` and ``other_of``, the list's scalars
    behind the grid's indices), and ``column(block)`` for the own block of
    the bounds ``(B, S, 1)``, one row of the batch for its heads."""
    step = lambda b, t: b // heads * capacity + t
    at = lambda block, dim: lambda of: pl.BlockSpec(
        (None, block, dim), lambda b, t, *lists: (
            b, lists[of][step(b, t)], 0))
    column = lambda block: pl.BlockSpec(
        (None, block, 1), lambda b, t, own_of, *_: (
            b // heads, own_of[step(b, t)], 0))
    return at, column


@functools.partial(jax.jit, static_argnames=_STATIC)
def _packed_fwd_call(qf, kf, vf, *, ids, scale, causal, block_q, block_k,
                     interpret, vma, window=None):
    """``bf_flash_seg_fwd`` on folded operands and ``ids`` ``(B, S)``: ``o``
    and the per-row logsumexp ``(B*H, S, 1)``."""
    bh, S, D = qf.shape
    Dv, heads = vf.shape[-1], bh // ids.shape[0]
    steps, lists, bounds = _doc_work(ids, block_q, block_k, False)
    capacity = _doc_capacity(block_q, block_k, S)
    at, column = _packed_specs(heads, capacity)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, scale=scale, causal=True,
                          block_q=block_q, block_k=block_k,
                          packed=(heads, False, capacity)),
        name=_kernel_name("fwd", None, True),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4, grid=(bh, steps),
            in_specs=[column(block_q), at(block_q, D)(_OWN),
                      at(block_k, D)(_OTHER), at(block_k, Dv)(_OTHER)],
            out_specs=[at(block_q, Dv)(_OWN), at(block_q, 1)(_OWN)],
            scratch_shapes=[pltpu.VMEM((block_q, Dv), jnp.float32),
                            pltpu.VMEM((block_q, 1), jnp.float32),
                            pltpu.VMEM((block_q, 1), jnp.float32)]),
        out_shape=[jax.ShapeDtypeStruct((bh, S, Dv), qf.dtype, vma=vma),
                   jax.ShapeDtypeStruct((bh, S, 1), jnp.float32, vma=vma)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(*lists, bounds, qf, kf, vf)


@functools.partial(jax.jit, static_argnames=_STATIC)
def _packed_bwd_calls(qf, kf, vf, dof, lse3, delta, *, ids, scale, causal,
                      block_q, block_k, interpret, vma, window=None):
    """``bf_flash_seg_dq`` and ``bf_flash_seg_dkv``: ``dq, dk, dv``."""
    bh, S, D = qf.shape
    Dv, heads = vf.shape[-1], bh // ids.shape[0]
    params = dict(
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret)
    kernel = dict(scale=scale, causal=True, block_q=block_q, block_k=block_k)
    capacity = _doc_capacity(block_q, block_k, S)
    at, column = _packed_specs(heads, capacity)
    q_at, k_at = at(block_q, D), at(block_k, D)
    do_at, v_at, r_at = at(block_q, Dv), at(block_k, Dv), at(block_q, 1)

    steps, lists, bounds = _doc_work(ids, block_q, block_k, False)
    dq = pl.pallas_call(
        functools.partial(_dq_kernel, **kernel,
                          packed=(heads, False, capacity)),
        name=_kernel_name("dq", None, True),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4, grid=(bh, steps),
            in_specs=[column(block_q), q_at(_OWN), k_at(_OTHER), v_at(_OTHER),
                      do_at(_OWN), r_at(_OWN), r_at(_OWN)],
            out_specs=q_at(_OWN),
            scratch_shapes=[pltpu.VMEM((block_q, D), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((bh, S, D), qf.dtype, vma=vma),
        **params,
    )(*lists, bounds, qf, kf, vf, dof, lse3, delta)

    steps, lists, bounds = _doc_work(ids, block_q, block_k, True)
    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, **kernel,
                          packed=(heads, True, capacity)),
        name=_kernel_name("dkv", None, True),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4, grid=(bh, steps),
            in_specs=[column(block_k), q_at(_OTHER), k_at(_OWN), v_at(_OWN),
                      do_at(_OTHER), r_at(_OTHER), r_at(_OTHER)],
            out_specs=[k_at(_OWN), v_at(_OWN)],
            scratch_shapes=[pltpu.VMEM((block_k, D), jnp.float32),
                            pltpu.VMEM((block_k, Dv), jnp.float32)]),
        out_shape=[
            jax.ShapeDtypeStruct((bh, S, D), kf.dtype, vma=vma),
            jax.ShapeDtypeStruct((bh, S, Dv), vf.dtype, vma=vma),
        ],
        **params,
    )(*lists, bounds, qf, kf, vf, dof, lse3, delta)
    return dq, dk, dv


def _lse_bsh(lse, B, S, H):
    return lse.reshape(B, H, S).transpose(0, 2, 1)         # -> (B, S, H)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8, 9))
def _flash(q, k, v, causal, block_q, block_k, interpret, vma=None,
           scale=None, window=None):
    out, (_, _, _, _, lse, (B, S, H, _, _, _)) = _fwd(
        q, k, v, causal=causal, block_q=block_q, block_k=block_k,
        interpret=interpret, vma=vma, scale=scale, window=window)
    return out, _lse_bsh(lse, B, S, H)


def _flash_fwd(q, k, v, causal, block_q, block_k, interpret, vma=None,
               scale=None, window=None):
    out, res = _fwd(q, k, v, causal=causal, block_q=block_q,
                    block_k=block_k, interpret=interpret, vma=vma,
                    scale=scale, window=window)
    B, S, H = res[5][0], res[5][1], res[5][2]
    return (out, _lse_bsh(res[4], B, S, H)), res


def _flash_bwd(causal, block_q, block_k, interpret, vma, scale, window, res,
               cotangents):
    del scale       # the residuals carry the one the forward used
    return _bwd(block_q, block_k, interpret, vma, window, res, cotangents)


_flash.defvjp(_flash_fwd, _flash_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8))
def _flash_packed(q, k, v, ids, block_q, block_k, interpret, vma=None,
                  scale=None):
    """``_flash`` under the causal mask and the documents ``ids`` ``(B,
    S)``: a rule of its own, so that a call without ids stages what it
    staged before there were any."""
    return _flash_packed_fwd(q, k, v, ids, block_q, block_k, interpret, vma,
                             scale)[0]


def _flash_packed_fwd(q, k, v, ids, block_q, block_k, interpret, vma=None,
                      scale=None):
    out, res = _fwd(q, k, v, causal=True, block_q=block_q, block_k=block_k,
                    interpret=interpret, vma=vma, scale=scale, ids=ids)
    B, S, H = res[5][0], res[5][1], res[5][2]
    return (out, _lse_bsh(res[4], B, S, H)), res + (ids,)


def _flash_packed_bwd(block_q, block_k, interpret, vma, scale, res,
                      cotangents):
    del scale       # the residuals carry the one the forward used
    *res, ids = res
    # integer ids have no tangent space but float0's
    return _bwd(block_q, block_k, interpret, vma, None, tuple(res),
                cotangents, ids) + (np.zeros(ids.shape, jax.dtypes.float0),)


_flash_packed.defvjp(_flash_packed_fwd, _flash_packed_bwd)


def platform_in_use(x) -> str:
    """Platform of the devices a computation on ``x`` will run on: a
    concrete array's own devices; under tracing (no devices to read), those
    of the ``bf.init()`` mesh, and jax's default devices before that.  Never
    the process default alone — ``bf.init(devices=jax.devices("cpu"))`` on a
    TPU host and a TPU mesh in a CPU-default process must both resolve to
    the mesh."""
    if isinstance(x, jax.Array) and not isinstance(x, jax.core.Tracer):
        return next(iter(x.devices())).platform
    from bluefog_tpu import basics
    return basics._mesh_platform()


def flash_attention(q, k, v, *, causal: bool = True, block_q: int = 1024,
                    block_k: int = 1024, interpret: bool = None, vma=None,
                    scale: float = None, window: int = None,
                    segment_ids=None):
    """Memory-O(S) exact attention; ``q`` and ``k`` ``(B, S, H, D)``, ``v``
    ``(B, S, H, Dv)`` (``Dv`` is ``D`` unless the values have a head dim of
    their own), result ``(B, S, H, Dv)``.  ``scale`` multiplies the scores
    before the softmax; ``None`` means ``1 / sqrt(D)``.

    ``window=W`` (with ``causal``): query ``i`` sees the keys ``i - W < j <=
    i``.  The kernels are then ``bf_flash_win_fwd / dq / dkv`` on grids that
    cover only the blocks a window reaches, so the cost grows with ``S x
    W``; the forward of a band of at most 2048 keys a query block takes it
    in pieces of ``W`` rounded up to a power of two, one softmax pass a row
    chunk.  ``W >= S`` is the causal kernel.

    ``segment_ids`` (with ``causal``): ``(B, S)`` integers, the document of
    every token of a packed row; query ``i`` sees the keys ``j <= i`` of its
    own document.  **The ids must not decrease along a sequence** (documents
    are contiguous, as ``data.pack_documents`` lays them); the kernels do
    not check data, and an id that comes back after another gives a
    document of its own or wrong tiles, not an error.
    The kernels are then ``bf_flash_seg_fwd / dq / dkv``, on query blocks of
    at most 512 (``block_q`` fitted down) and the key blocks asked for.
    They run a list of live work that the call makes from the ids on the
    device: for every block of own positions the range of blocks of the
    other dimension that hold a visible pair, one after the other; the
    grid's second dimension is that list's length, so a tile whose keys all
    lie in earlier documents than all its queries is no step at all, and
    the rows of a batch pad to the longest list.  A tile inside one
    document runs what the causal kernel runs; a tile that a boundary
    crosses works in chunks of 256 own rows, each on its visible range of
    keys rounded out to whole 256 in one step of the softmax state, masked
    by document beside the diagonal (``segment_steps`` counts on the host
    how much of its capacity a layout's list fills, ``segment_tiles`` the
    tiles of the three kinds).  Together with ``window``, or without
    ``causal``, it raises.

    ``interpret=None`` compiles the Mosaic kernel when the devices in use
    (:func:`platform_in_use`) are TPUs and runs the Pallas interpreter
    anywhere else; pass it explicitly to pin either.  ``vma``: the mesh axis
    names the outputs vary over inside ``shard_map``; default: those the
    inputs vary over.

    Block sizes default to 1024 (fitted down to divide S): every tile pays
    one step of the softmax state, 1.1 to 1.4 us of the forward's 4.2 us at
    1024 x 1024, and a step over half the keys costs as much (one v5e chip,
    PR 37, the kernels alone on causal bfloat16 ``(B*H, S, D)``; forward /
    dq / dkv in ms: (64, 8192, 64) 11.1 / 13.7 / 15.7; (16, 16384, 128) 9.0 /
    10.9 / 13.2; (32, 4096, 128) 1.44 / 1.79 / 2.03; (32, 4096, 192) with
    values of 128, the backward at 1024 x 512, 2.22 / 3.29 / 3.43; PR 48,
    (32, 8192, 192) with values of 128 packed from ten documents of 2961
    to 47, at 512 x 1024: 2.65 / 2.88 / 3.54, and 2.44 forward with
    ``block_k=2048``)."""
    return flash_attention_lse(q, k, v, causal=causal, block_q=block_q,
                               block_k=block_k, interpret=interpret,
                               vma=vma, scale=scale, window=window,
                               segment_ids=segment_ids)[0]


def flash_attention_lse(q, k, v, *, causal: bool = True, block_q: int = 1024,
                        block_k: int = 1024, interpret: bool = None,
                        vma=None, scale: float = None, window: int = None,
                        segment_ids=None):
    """Like :func:`flash_attention` but also returns the per-row logsumexp
    ``(B, S, H)`` — the merge weight sequence-parallel consumers need
    (``parallel.ring_attention`` combines per-hop partials with it).
    Differentiable in both outputs (the lse cotangent folds into the
    backward's delta term).

    ``vma``: frozenset of mesh axis names the outputs vary over inside
    ``shard_map(..., check_vma=True)`` (Pallas outputs must declare their
    varying axes); default: the axes the inputs vary over."""
    if window is not None:
        if not causal or window < 1:
            raise ValueError(
                f"flash_attention: window={window} needs causal=True and at "
                "least one key (a query's own): the window reaches back "
                "from the diagonal")
        window = int(window) if window < q.shape[1] else None
    if interpret is None:
        interpret = platform_in_use(q) != "tpu"
    if vma is None:
        vma = frozenset().union(*(jax.typeof(t).vma for t in (q, k, v)))
    scale = None if scale is None else float(scale)
    if segment_ids is None:
        return _flash(q, k, v, causal, block_q, block_k, interpret, vma,
                      scale, window)
    if not causal or window is not None:
        raise NotImplementedError(
            "flash_attention: segment_ids run under the causal mask alone: "
            "a packed grid starts each query block at the first key block "
            "of its documents and ends it at the diagonal, and has neither "
            "the tiles past the diagonal (causal=False) nor a band's two "
            f"edges (window={window})")
    if segment_ids.shape != q.shape[:2]:
        raise ValueError(
            f"flash_attention: segment_ids {segment_ids.shape} are not one "
            f"id a token of q {q.shape}: (B, S)")
    return _flash_packed(q, k, v, segment_ids.astype(jnp.int32), block_q,
                         block_k, interpret, vma, scale)


def segment_tiles(segment_ids, block_q: int = 1024, block_k: int = 1024
                  ) -> dict:
    """Of the tiles at or under the diagonal that ``(block_q, block_k)``
    blocks cut the packed rows ``segment_ids`` ``(B, S)`` into (one head's;
    blocks fitted to ``S`` as the kernels fit them), how many are ``dead``
    (every key in an earlier document than every query: no product, no
    block moved), ``crossed`` by a document boundary (chunks masked by id)
    and ``inside`` one document (the causal kernel's work).  Computed on the
    host with numpy: for logging, tests and the benchmark's readers."""
    ids = np.asarray(segment_ids)
    S = ids.shape[1]
    block_q, block_k = _fit_block(block_q, S), _fit_block(block_k, S)
    q_first, q_last = ids[:, ::block_q], ids[:, block_q - 1::block_q]
    k_first, k_last = ids[:, ::block_k], ids[:, block_k - 1::block_k]
    qi, kb = np.meshgrid(np.arange(S // block_q), np.arange(S // block_k),
                         indexing="ij")
    under = kb * block_k <= (qi + 1) * block_q - 1
    dead = k_last[:, None, :] < q_first[:, :, None]
    inside = k_first[:, None, :] == q_last[:, :, None]
    count = lambda hit: int((hit & under).sum())
    return {"dead": count(dead), "crossed": count(~dead & ~inside),
            "inside": count(inside)}


def segment_steps(segment_ids, block_q: int = _DOC_BLOCK,
                  block_k: int = 1024) -> dict:
    """Of the steps a head's list can hold at ``(block_q, block_k)`` blocks
    (``capacity``: the tiles at or under the diagonal, what
    ``bf_flash_tiles_total{kind="by_data"}`` counts a head at staging), how
    many the packed rows ``segment_ids`` ``(B, S)`` fill (``listed``: what
    ``segment_tiles`` counts as ``crossed`` or ``inside``), by the rule the
    device applies (``_doc_reach``), summed over the rows; the grid's
    second dimension is the longest row's.  The blocks are fitted to ``S``
    and no further: a packed call's query blocks are ``_DOC_BLOCK`` at
    most, the default.  Computed on the host with numpy: for logging and tests."""
    ids = np.asarray(segment_ids)
    S = ids.shape[1]
    block_q, block_k = _fit_block(block_q, S), _fit_block(block_k, S)
    lo, hi = _doc_reach(np, _doc_bounds(np, ids, False), block_q, block_k,
                        False)
    return {"listed": int((hi - lo + 1).sum()),
            "capacity": len(ids) * _doc_capacity(block_q, block_k, S)}


def flash_attention_impl(block_q: int = 1024, block_k: int = 1024,
                         interpret: bool = None):
    """``attn_impl`` for ``models.TransformerLM`` / ``parallel.ulysses``:
    ``flash_attention`` at these blocks, with whatever else it takes."""
    return functools.partial(flash_attention, block_q=block_q,
                             block_k=block_k, interpret=interpret)
