"""Randomized equivalence suite for the compressed-domain set ops.

Packed (block-skip over UidPack, ops/packed_setops.py) intersect /
difference / membership must be element-exact against the decoded path
(ops/setops.py kernels / numpy exact ops) — including 32-bit segment
boundaries, UINT32_MAX as a legal UID, empty/singleton blocks, and
adversarial block-alignment cases.
"""

import numpy as np
import pytest

from dgraph_tpu.codec import uidpack
from dgraph_tpu.ops import packed_setops as ps
from dgraph_tpu.ops import setops
from dgraph_tpu.query.dispatch import PackedOperand, SetOpDispatcher


def _rand(rng, n, hi=1 << 33):
    return np.unique(rng.integers(1, hi, size=n, dtype=np.uint64))


def _check_all(a, b):
    """Packed results (array-vs-pack and pack-vs-pack) == numpy exact."""
    pa, pb = uidpack.encode(a), uidpack.encode(b)
    want_i = np.intersect1d(a, b, assume_unique=True)
    want_d = np.setdiff1d(a, b, assume_unique=True)
    np.testing.assert_array_equal(ps.intersect_packed(a, pb), want_i)
    np.testing.assert_array_equal(ps.intersect_packed(pa, pb), want_i)
    np.testing.assert_array_equal(ps.difference_packed(a, pb), want_d)
    np.testing.assert_array_equal(ps.difference_packed(pa, pb), want_d)
    np.testing.assert_array_equal(
        ps.membership_packed(a, pb),
        np.isin(a, b, assume_unique=True),
    )


@pytest.mark.parametrize("seed", range(8))
def test_randomized_equivalence(seed):
    rng = np.random.default_rng(seed)
    na = int(rng.integers(0, 3000))
    nb = int(rng.integers(0, 50000))
    hi = int(rng.choice([1 << 20, 1 << 32, 1 << 34, 1 << 45]))
    a, b = _rand(rng, na, hi), _rand(rng, nb, hi)
    if seed % 2 and len(b):
        # force heavy overlap so results are non-trivial
        a = np.unique(
            np.concatenate([a, rng.choice(b, min(len(b), 64), replace=False)])
        )
    _check_all(a, b)


def test_selective_case_skips_blocks():
    """10-vs-1M: candidate search must decode a tiny fraction of blocks."""
    rng = np.random.default_rng(42)
    b = _rand(rng, 1_100_000, hi=1 << 31)[:1_000_000]
    a = np.sort(rng.choice(b, 10, replace=False))
    pb = uidpack.encode(b)
    ps.reset_counters()
    np.testing.assert_array_equal(ps.intersect_packed(a, pb), a)
    c = ps.counters()
    assert c["decoded_bytes"] * 50 < c["full_decode_bytes"], c


def test_segment_boundaries_and_sentinels():
    """Hi-32 boundary straddles, UINT32_MAX-valued lo words, and the
    all-ones UID are all legal and exact (codec.go:117 split rule)."""
    m = 0xFFFFFFFF
    a = np.array(
        [1, m, (1 << 32), (1 << 32) | m, (2 << 32), (1 << 64) - 1],
        np.uint64,
    )
    b = np.array(
        [m, m + 1, (1 << 32) | m, (3 << 32) | 7, (1 << 64) - 1], np.uint64
    )
    _check_all(a, b)
    _check_all(b, a)
    # and against the decoded device kernels (per-segment uint32 space)
    seg_a = uidpack.split_segments(a)
    seg_b = uidpack.split_segments(b)
    got = ps.intersect_packed(a, uidpack.encode(b))
    dev = []
    for h in sorted(set(seg_a) & set(seg_b)):
        x, y = seg_a[h], seg_b[h]
        px, py = 8, 8
        out, n = setops.intersect(
            setops.pad_sorted(x, px), len(x), setops.pad_sorted(y, py), len(y)
        )
        lo = np.asarray(out)[: int(n)]
        dev.append((np.uint64(h) << np.uint64(32)) | lo.astype(np.uint64))
    want = np.concatenate(dev) if dev else np.zeros((0,), np.uint64)
    np.testing.assert_array_equal(got, want)


def test_empty_and_singleton_blocks():
    empty = np.zeros((0,), np.uint64)
    one = np.array([7], np.uint64)
    _check_all(empty, empty)
    _check_all(one, empty)
    _check_all(empty, one)
    _check_all(one, one)
    _check_all(one, np.array([8], np.uint64))


def test_adversarial_block_alignment():
    """Exact multiples of BLOCK_SIZE, ranges that touch at block borders,
    and interleaved disjoint runs (every block overlaps, nothing matches —
    the worst case for range-based skipping must still be exact)."""
    bs = uidpack.BLOCK_SIZE
    # b = dense run; a = exactly the block-boundary elements
    b = np.arange(1, 10 * bs + 1, dtype=np.uint64)
    a = b[::bs].copy()
    _check_all(a, b)
    # interleaved evens/odds: block ranges overlap, zero matches
    evens = np.arange(0, 4 * bs, 2, dtype=np.uint64)
    odds = np.arange(1, 4 * bs, 2, dtype=np.uint64)
    _check_all(evens, odds)
    # a touches only the first/last element of each b block
    starts = b.reshape(10, bs)[:, 0]
    ends = b.reshape(10, bs)[:, -1]
    _check_all(np.unique(np.concatenate([starts, ends])), b)


def test_block_metadata():
    rng = np.random.default_rng(5)
    u = _rand(rng, 3000, hi=1 << 40)
    p = uidpack.encode(u)
    maxes = uidpack.block_maxes(p)
    assert maxes.shape == (p.nblocks,)
    # ranges are disjoint ascending and tile the uid set
    assert np.all(p.bases <= maxes)
    assert np.all(maxes[:-1] < p.bases[1:])
    # partial decode of every block == full decode
    np.testing.assert_array_equal(
        uidpack.decode_blocks(p, np.arange(p.nblocks)), u
    )
    # arbitrary subset
    idxs = np.array([0, p.nblocks - 1], np.int64)
    want = np.concatenate(
        [
            u[: int(p.counts[0])],
            u[len(u) - int(p.counts[-1]) :],
        ]
    )
    np.testing.assert_array_equal(uidpack.decode_blocks(p, idxs), want)


def test_merge_packs_multipart():
    rng = np.random.default_rng(6)
    u = _rand(rng, 5000, hi=1 << 34)
    parts = [uidpack.encode(c) for c in np.array_split(u, 7)]
    merged = uidpack.merge_packs(parts)
    np.testing.assert_array_equal(uidpack.decode(merged), u)
    assert merged.num_uids == len(u)


# ---------------------------------------------------------------------------
# Dispatcher integration: packed operands through run_chain / run_pairs.
# ---------------------------------------------------------------------------


def test_dispatcher_packed_chain_and_pairs():
    rng = np.random.default_rng(9)
    b = _rand(rng, 200_000, hi=1 << 33)
    a = np.sort(rng.choice(b, 25, replace=False))
    pop = PackedOperand(uidpack.encode(b))
    d = SetOpDispatcher()
    np.testing.assert_array_equal(
        d.run_chain("intersect", [a, pop]),
        np.intersect1d(a, b, assume_unique=True),
    )
    np.testing.assert_array_equal(
        d.run_chain("union", [a, pop]), np.union1d(a, b)
    )
    got = d.run_pairs("difference", [(a, pop)])
    np.testing.assert_array_equal(
        got[0], np.setdiff1d(a, b, assume_unique=True)
    )
    # mixed chain: two packed + one dense
    c = _rand(rng, 150_000, hi=1 << 33)
    popc = PackedOperand(uidpack.encode(c))
    want = np.intersect1d(
        np.intersect1d(a, b, assume_unique=True), c, assume_unique=True
    )
    np.testing.assert_array_equal(
        d.run_chain("intersect", [pop, a, popc]), want
    )


def test_dispatcher_packed_fallback_below_crossover():
    """A dense (ratio ~1) ARRAY x pack pair must take the full-decode
    path — the packed counters stay at zero packed ops. (Pack x pack
    pairs have no such cliff: the per-block engine keeps both sides
    compressed at every ratio — tests/test_bitmap_setops.py
    test_dispatcher_dense_pair_stays_compressed.)"""
    rng = np.random.default_rng(10)
    a = _rand(rng, 5000, hi=1 << 30)
    b = _rand(rng, 5000, hi=1 << 30)
    pop = PackedOperand(uidpack.encode(b))
    d = SetOpDispatcher()
    ps.reset_counters()
    got = d.run_pairs("intersect", [(a, pop)])
    np.testing.assert_array_equal(
        got[0], np.intersect1d(a, b, assume_unique=True)
    )
    assert ps.counters()["packed_ops"] == 0


def test_dispatcher_prefers_dense_when_decode_is_sunk():
    """Once a packed operand's full decode is memoized (on the operand /
    owning PostingList), the dispatcher must take the free dense path
    instead of re-running block-skip every query."""
    rng = np.random.default_rng(13)
    b = _rand(rng, 200_000, hi=1 << 33)
    a = np.sort(rng.choice(b, 20, replace=False))
    pop = PackedOperand(uidpack.encode(b))
    d = SetOpDispatcher()
    ps.reset_counters()
    r1 = d.run_pairs("intersect", [(a, pop)])[0]
    assert ps.counters()["packed_ops"] == 1  # cold operand: packed path
    pop._uids = b  # decode cost now sunk
    r2 = d.run_pairs("intersect", [(a, pop)])[0]
    assert ps.counters()["packed_ops"] == 1  # memoized: dense path
    np.testing.assert_array_equal(r1, r2)


def test_posting_list_block_cache_and_packed_view():
    import dgraph_tpu.posting.pl as plmod
    from dgraph_tpu.posting.lists import LocalCache
    from dgraph_tpu.posting.pl import Posting, PostingList, rollup_writes
    from dgraph_tpu.storage.kv import MemKV

    from dgraph_tpu.x import keys

    rng = np.random.default_rng(11)
    uids = _rand(rng, 5000, hi=1 << 33)
    key = keys.DataKey("friend", 1)
    kv = MemKV()
    old = plmod.MAX_PART_UIDS
    plmod.MAX_PART_UIDS = 1000  # force a multi-part split
    try:
        for k, ts, rec in rollup_writes(key, uids, [], 5):
            kv.put(k, ts, rec)
    finally:
        plmod.MAX_PART_UIDS = old
    p = PostingList.from_versions(
        key, kv.versions(key, 10), kv=kv, read_ts=10
    )
    assert len(p.part_packs) > 1
    mp = p.merged_pack()
    np.testing.assert_array_equal(uidpack.decode(mp), uids)
    idxs = np.array([0, 2, mp.nblocks - 1], np.int64)
    first = p.decode_blocks(mp, idxs)
    np.testing.assert_array_equal(first, uidpack.decode_blocks(mp, idxs))
    assert len(p._block_cache) == 3  # cached for the next traversal
    np.testing.assert_array_equal(p.decode_blocks(mp, idxs), first)
    np.testing.assert_array_equal(p.uids(), uids)

    cache = LocalCache(kv, 10)
    pop = cache.packed_operand(key)
    assert pop is not None and len(pop) == len(uids)
    # a txn-local uid delta makes the packed view stale -> refused
    cache.add_delta(key, Posting(uid=123))
    assert cache.packed_operand(key) is None
    # value-only deltas keep the uid set exact -> still packed
    cache2 = LocalCache(kv, 10)
    cache2.add_delta(key, Posting(uid=(1 << 64) - 1, value=b"v"))
    assert cache2.packed_operand(key) is not None


def test_native_bulk_load_feeds_stats(tmp_path):
    """The C++ bulk path must emit index selectivity records and the
    loader must ingest them at load finish, or eq plans run on default
    selectivities until the first commits."""
    from dgraph_tpu import native

    if not native.NATIVE_AVAILABLE:
        pytest.skip("native toolchain unavailable")
    from dgraph_tpu.api.server import Server
    from dgraph_tpu.loaders.bulk2 import ParallelBulkLoader

    s = Server()
    s.alter("name: string @index(exact) .")
    rdf = [f'<0x{i+1:x}> <name> "n{i % 5}" .' for i in range(200)]
    ld = ParallelBulkLoader(s, workdir=str(tmp_path / "w"), workers=1)
    assert ld._native_ok()
    ld.load_text("\n".join(rdf))
    for t in range(5):
        est = s.stats.estimate("name", b"\x02" + f"n{t}".encode())
        assert est >= 40, (t, est)
