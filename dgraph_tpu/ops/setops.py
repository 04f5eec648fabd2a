"""Device sorted-set algebra over padded uint32 UID arrays.

TPU-native replacement for the reference's adaptive scalar intersect loops
(/root/reference/algo/uidlist.go:142 IntersectWith, :297 IntersectSorted,
:332 Difference, :448 MergeSorted) and the compressed-domain variants
(algo/packed.go). Instead of per-pair adaptive linear/jump/binary strategies,
every op is a fixed-shape, fully-vectorized XLA program that is `vmap`-ped
over a *batch* of list pairs, so one device dispatch covers an entire
`handleUidPostings`-style fan-out (/root/reference/worker/task.go:783).

Representation
--------------
A list is a sorted uint32 array padded to a static size with UINT32_MAX,
plus an explicit int32 length. Validity is *always* judged by index < length,
never by sentinel value, so UINT32_MAX is still a legal UID. Padding must be
UINT32_MAX so the padded array stays sorted (searchsorted correctness).

64-bit UIDs are handled one level up (codec/uidpack.py): lists are segmented
by the high 32 bits — mirroring the reference's block-split rule when the 32
MSBs differ (codec/codec.go:117) — and ops run per matching segment in the
32-bit local space.

All functions are jit-friendly (static shapes, no data-dependent control
flow). The batch dispatcher (query/dispatch.py) runs them in two forms:

- vmapped over a stack of padded rows: the pair buckets (`intersect`,
  `difference`, `union`, every row with its own `b`) and `union#shared`
  (one shared `b`, unbatched: a row's union needs the row's own output
  width). Under the vmap a sort-based search sorts a copy of `b` with every
  row, so a stack costs rows x (row width + b's width) elements.
- flat, no vmap: `intersect#shared` and `difference#shared`. With one
  shared `b`, `membership` does not care which row an id sits in, so the
  level's ids go in as ONE array (not sorted as a whole: only `b` has to
  be) and the mask comes out; the host keeps or drops by it and cuts the
  rows back at their offsets. No `compact` on the device.
"""

from __future__ import annotations

import functools

import numpy as np
import jax
import jax.numpy as jnp

UINT32_MAX = np.uint32(0xFFFFFFFF)


def pad_sorted(arr: np.ndarray, size: int) -> np.ndarray:
    """Host helper: pad a sorted uint32 array to `size` with UINT32_MAX."""
    arr = np.asarray(arr, dtype=np.uint32)
    if arr.shape[0] > size:
        raise ValueError(f"array of length {arr.shape[0]} > pad size {size}")
    out = np.full((size,), UINT32_MAX, dtype=np.uint32)
    out[: arr.shape[0]] = arr
    return out


def scoped(name: str, fn):
    """`fn`, traced under `jax.named_scope(name)`: the profiler's
    name-scope lines then group the program's fusions and sorts under a
    name of ours (`setop.<op>.<family>`), whatever XLA calls them. HLO
    metadata only; the computation is the same."""

    @functools.wraps(fn)  # the jitted program keeps fn's name
    def run(*args):
        with jax.named_scope(name):
            return fn(*args)

    return run


def _iota_mask(n: int, length) -> jnp.ndarray:
    return jnp.arange(n, dtype=jnp.int32) < length


def _searchsorted(b, a):
    """Shape-adaptive search: unrolled binary search when the query side is
    much smaller than the target (log2(n) vectorized steps), sort-based
    search when both sides are large (one fused sort amortizes better on
    the TPU) — the static-shape analog of the reference's linear/jump/binary
    strategy pick (algo/uidlist.go:142-168)."""
    if a.shape[0] * 32 <= b.shape[0]:
        return jnp.searchsorted(b, a, method="scan_unrolled")
    return jnp.searchsorted(b, a, method="sort")


def membership(a, la, b, lb):
    """mask[i] = (i < la) and (a[i] in b[:lb]).

    Vectorized binary search replaces the scalar jump/binary loops of
    algo/uidlist.go:195,226.
    """
    idx = _searchsorted(b, a)
    idx_c = jnp.minimum(idx, b.shape[0] - 1)
    hit = (idx < lb) & (jnp.take(b, idx_c) == a)
    return hit & _iota_mask(a.shape[0], la)


def compact(a, keep):
    """Stable-compact elements of `a` where `keep`; returns (padded, count).

    Uses a stable argsort on the keep mask (members first) — a sort-based
    stream compaction that XLA maps onto the TPU well; padding is restored
    to UINT32_MAX to preserve the sortedness invariant.
    """
    order = jnp.argsort(~keep, stable=True)
    out = jnp.take(a, order)
    n = jnp.sum(keep, dtype=jnp.int32)
    out = jnp.where(_iota_mask(a.shape[0], n), out, UINT32_MAX)
    return out, n


def intersect(a, la, b, lb):
    """Sorted-set intersection -> (padded result sized like a, count).

    Replaces algo/uidlist.go:142 IntersectWith (and the compressed
    IntersectCompressedWith path used by posting/list.go:1799).
    """
    return compact(a, membership(a, la, b, lb))


def difference(a, la, b, lb):
    """a \\ b -> (padded result sized like a, count). Ref algo/uidlist.go:332."""
    keep = _iota_mask(a.shape[0], la) & ~membership(a, la, b, lb)
    return compact(a, keep)


def union(a, la, b, lb):
    """Sorted-set union -> (padded result sized len(a)+len(b), count).

    Ref algo/uidlist.go:448 MergeSorted (2-way case): concatenate, single
    sort with invalid-last composite key, adjacent-dedupe, compact.
    """
    x = jnp.concatenate([a, b])
    valid = jnp.concatenate(
        [_iota_mask(a.shape[0], la), _iota_mask(b.shape[0], lb)]
    )
    order = jnp.lexsort((x, ~valid))
    xs = jnp.take(x, order)
    vs = jnp.take(valid, order)
    prev_diff = jnp.concatenate(
        [jnp.ones((1,), dtype=bool), xs[1:] != xs[:-1]]
    )
    return compact(xs, vs & prev_diff)


def merge_sorted(lists, lengths):
    """K-way sorted union. lists: (k, n) uint32, lengths: (k,) int32.

    Replaces the threaded 10-way heap merge of algo/uidlist.go:465-542 with
    one flattened sort + dedupe on device.
    """
    k, n = lists.shape
    x = lists.reshape(-1)
    valid = (
        jnp.arange(n, dtype=jnp.int32)[None, :] < lengths[:, None]
    ).reshape(-1)
    order = jnp.lexsort((x, ~valid))
    xs = jnp.take(x, order)
    vs = jnp.take(valid, order)
    prev_diff = jnp.concatenate(
        [jnp.ones((1,), dtype=bool), xs[1:] != xs[:-1]]
    )
    return compact(xs, vs & prev_diff)


def intersect_many(lists, lengths):
    """Intersection of k sorted lists. lists: (k, n), lengths: (k,).

    Replaces algo/uidlist.go:297 IntersectSorted (smallest-first fold) with a
    membership-count formulation: an element of list 0 survives iff it is
    found in all k lists. One searchsorted per list, fully batched.
    """
    k, n = lists.shape
    a = lists[0]
    la = lengths[0]

    def body(i, cnt):
        m = membership(a, la, lists[i], lengths[i])
        return cnt + m.astype(jnp.int32)

    cnt = jax.lax.fori_loop(1, k, body, jnp.zeros((n,), jnp.int32))
    keep = _iota_mask(n, la) & (cnt == k - 1)
    return compact(a, keep)


def index_of(a, la, u):
    """Position of u in a[:la], or -1. Ref algo/uidlist.go:546."""
    idx = jnp.searchsorted(a, u, method="scan_unrolled")
    idx_c = jnp.minimum(idx, a.shape[0] - 1)
    hit = (idx < la) & (jnp.take(a, idx_c) == u)
    return jnp.where(hit, idx, -1)


# ---------------------------------------------------------------------------
# Batched (vmapped) forms — one device dispatch per fan-out level.
# ---------------------------------------------------------------------------

batch_membership = jax.vmap(membership)
batch_intersect = jax.vmap(intersect)
batch_difference = jax.vmap(difference)
batch_union = jax.vmap(union)
batch_merge_sorted = jax.vmap(merge_sorted)
batch_intersect_many = jax.vmap(intersect_many)
