"""Ragged row buffers: the level-batched task form of a traversal level.

One task per (predicate, level) reads every parent's posting list in a
single batched call (LocalCache.uids_many) and hands back the whole level
as (flat_uids, offsets): row i — parent i's destination uids — is
``flat[offsets[i]:offsets[i+1]]``. Downstream per-row work (merge, filter
intersect, pagination, counts) then runs as vectorized ops over the flat
buffer + offsets (np.diff / cumsum / searchsorted) instead of Python
per-row loops — the same amortization lever the reference gets from one
goroutine per (attr, uid-chunk) task (worker/task.go), shaped for wide
vector units instead of goroutines.

`RaggedRows` is the drop-in `uid_matrix` view: a sequence whose rows are
zero-copy slices of the flat buffer, so encoders / cascade pruning keep
their List[np.ndarray] contract while the hot path never materializes a
Python list of arrays.
"""

from __future__ import annotations

from typing import List, Optional, Tuple

import numpy as np

EMPTY = np.zeros((0,), np.uint64)


class RaggedRows:
    """Sequence view over a ragged (flat, offsets) level buffer.

    Quacks like List[np.ndarray]: len(), indexing (a zero-copy slice),
    iteration, truthiness. Consumers that need to REPLACE rows (cascade
    pruning, facet filtering) assign a plain list back to the field —
    both shapes satisfy the same read contract."""

    __slots__ = ("flat", "offs")

    def __init__(self, flat: np.ndarray, offs: np.ndarray):
        self.flat = flat
        self.offs = offs

    def __len__(self) -> int:
        return len(self.offs) - 1

    def __getitem__(self, i: int) -> np.ndarray:
        n = len(self.offs) - 1
        if i < 0:
            i += n
        if not 0 <= i < n:
            raise IndexError(i)
        return self.flat[self.offs[i] : self.offs[i + 1]]

    def __iter__(self):
        for i in range(len(self.offs) - 1):
            yield self.flat[self.offs[i] : self.offs[i + 1]]

    def row_lens(self) -> np.ndarray:
        return np.diff(self.offs)


def pack_rows(rows: List[np.ndarray]) -> Tuple[np.ndarray, np.ndarray]:
    """List-of-rows -> (flat, offsets). The adapter for paths still
    producing per-row lists (per-uid escape hatch, device fallbacks)."""
    n = len(rows)
    offs = np.zeros((n + 1,), np.int64)
    if n:
        np.cumsum([len(r) for r in rows], out=offs[1:])
    if not n or not offs[-1]:
        return EMPTY, offs
    flat = np.concatenate(rows).astype(np.uint64, copy=False)
    return flat, offs


def row_views(flat: np.ndarray, offs: np.ndarray) -> List[np.ndarray]:
    """Materialize the per-row list as zero-copy views (for code paths
    that mutate rows in place: edge facets, per-row ordering)."""
    return [
        flat[offs[i] : offs[i + 1]] for i in range(len(offs) - 1)
    ]


def merge_flat(flat: np.ndarray, offs: np.ndarray) -> np.ndarray:
    """Sorted-unique union of every row — dest_uids of the level. Same
    strategy split as subgraph._merge_rows: many rows -> one host unique
    beats the k-way merge's per-list walk; few rows -> native k-way merge
    directly over the flat buffer (no per-row marshaling)."""
    if not flat.size:
        return EMPTY
    lens = np.diff(offs)
    nonempty = int(np.count_nonzero(lens))
    if nonempty <= 1:
        return flat.astype(np.uint64, copy=False)
    if nonempty > 64:
        return np.unique(flat).astype(np.uint64, copy=False)
    from dgraph_tpu import native

    return native.merge_sorted_flat(flat, lens).astype(
        np.uint64, copy=False
    )


def apply_mask(
    flat: np.ndarray, offs: np.ndarray, mask: np.ndarray
) -> Tuple[np.ndarray, np.ndarray]:
    """Keep flat[mask], recomputing offsets — the vectorized form of a
    per-row filter: a row's new start is the number of kept ids before
    its old one, one searchsorted of the offsets into the kept ids'
    positions (no running count over every id)."""
    nz = np.flatnonzero(mask)
    return flat[nz], np.searchsorted(nz, offs)


def paginate(
    flat: np.ndarray,
    offs: np.ndarray,
    first: Optional[int],
    offset: Optional[int],
    after: Optional[int],
) -> Tuple[np.ndarray, np.ndarray]:
    """Vectorized per-row pagination over the ragged buffer — offsets
    arithmetic instead of n Python _paginate calls. Semantics match
    subgraph._paginate exactly: after > strictly, negative offset = 0,
    negative first keeps the LAST |first| uids."""
    if after is not None:
        flat, offs = apply_mask(flat, offs, flat > np.uint64(after))
    lens = np.diff(offs)
    starts = offs[:-1].copy()
    if offset and offset > 0:
        take = np.minimum(lens, offset)
        starts += take
        lens = lens - take
    if first is not None:
        if first >= 0:
            lens = np.minimum(lens, first)
        else:
            drop = np.maximum(lens + first, 0)
            starts += drop
            lens = lens - drop
    new_offs = np.zeros((len(lens) + 1,), np.int64)
    np.cumsum(lens, out=new_offs[1:])
    total = int(new_offs[-1])
    if total == flat.size and np.array_equal(starts, offs[:-1]):
        return flat, offs
    idx = np.repeat(starts, lens) + (
        np.arange(total, dtype=np.int64) - np.repeat(new_offs[:-1], lens)
    )
    return flat[idx], new_offs
