"""Device cache + batched chain dispatch + round-1 advisory fixes.

Covers the device-resident pack cache and true level batching, plus
reindex aggregation, the commit visibility barrier, oracle GC and
corrupt-record validation.
"""

import numpy as np
import pytest

from dgraph_tpu.api.server import Server
from dgraph_tpu.query import dispatch
from dgraph_tpu.query.dispatch import DISPATCHER, DeviceCache
from dgraph_tpu.zero.zero import ZeroLite


def _mk_sorted(rng, n, lim=1 << 40):
    return np.unique(rng.integers(1, lim, n, dtype=np.uint64))


def test_run_chain_intersect_matches_numpy():
    rng = np.random.default_rng(7)
    parts = [_mk_sorted(rng, 5000, 1 << 20) for _ in range(4)]
    want = parts[0]
    for p in parts[1:]:
        want = np.intersect1d(want, p, assume_unique=True)
    got = DISPATCHER.run_chain("intersect", parts)
    np.testing.assert_array_equal(got, want)


def test_run_chain_union_matches_numpy():
    rng = np.random.default_rng(8)
    parts = [_mk_sorted(rng, 3000, 1 << 20) for _ in range(5)]
    want = parts[0]
    for p in parts[1:]:
        want = np.union1d(want, p)
    got = DISPATCHER.run_chain("union", parts)
    np.testing.assert_array_equal(got, want)


def test_run_chain_small_host_path():
    a = np.array([1, 2, 3, 9], np.uint64)
    b = np.array([2, 3, 4], np.uint64)
    c = np.array([3, 2], np.uint64)  # unsorted tiny -> host path sorts? no:
    c.sort()
    np.testing.assert_array_equal(
        DISPATCHER.run_chain("intersect", [a, b, c]), [2, 3]
    )
    np.testing.assert_array_equal(DISPATCHER.run_chain("intersect", []), [])
    np.testing.assert_array_equal(DISPATCHER.run_chain("union", [a]), a)


def test_device_cache_hit_and_invalidate(monkeypatch):
    monkeypatch.setattr(dispatch, "_DEVICE_MIN_TOTAL", 1)
    monkeypatch.setattr(dispatch, "_FORCE_DEVICE", True)
    d = dispatch.SetOpDispatcher()
    rng = np.random.default_rng(3)
    rows = [_mk_sorted(rng, 200, 1 << 20) for _ in range(8)]
    toks = [(b"k%d" % i, 7) for i in range(8)]
    b = _mk_sorted(rng, 1000, 1 << 20)

    r1 = d.run_rows_vs_one("intersect", rows, b, row_tokens=toks, b_token=(b"big", 3))
    h0 = d.device_cache.hits
    r2 = d.run_rows_vs_one("intersect", rows, b, row_tokens=toks, b_token=(b"big", 3))
    assert d.device_cache.hits >= h0 + 2  # stacked rows + b both reused
    for x, y in zip(r1, r2):
        np.testing.assert_array_equal(x, y)
    # commit invalidation by key drops entries referencing it
    d.device_cache.invalidate([b"k3"])
    n_before = d.device_cache.stats()["entries"]
    r3 = d.run_rows_vs_one("intersect", rows, b, row_tokens=toks, b_token=(b"big", 3))
    for x, y in zip(r1, r3):
        np.testing.assert_array_equal(x, y)


def test_device_cache_lru_bound():
    c = DeviceCache(max_bytes=1000)
    for i in range(10):
        c.put(("t", i), [b"k%d" % i], ("arr",), 300)
    assert c.stats()["bytes"] <= 1000


def test_reindex_aggregates_shared_tokens():
    """ADVICE r1 high: alter() adding an index on a predicate where two
    entities share a value must index BOTH uids."""
    s = Server()
    s.alter(schema_text="name: string .")
    t = s.new_txn()
    t.mutate_rdf(set_rdf='_:a <name> "bob" .\n_:b <name> "bob" .', commit_now=True)
    s.alter(schema_text="name: string @index(exact) .")
    out = s.query('{ q(func: eq(name, "bob")) { count(uid) } }')
    assert out["data"]["q"][0]["count"] == 2


def test_zero_conflict_gc_bounded():
    z = ZeroLite()
    # overlapping registered txns: GC purges entries below the active floor
    for i in range(200):
        s1 = z.begin_txn()
        s2 = z.begin_txn()  # keeps _active non-empty at s1's commit
        z.commit(s1, [i])
        z.abort(s2)
    assert len(z._commits) < 200
    assert len(z._aborted) < 200


def test_read_ts_waits_for_applied():
    z = ZeroLite()
    s = z.begin_txn()
    cts = z.commit(s, [1], track=True)
    import threading, time

    got = []
    th = threading.Thread(target=lambda: got.append(z.read_ts()))
    th.start()
    time.sleep(0.05)
    assert not got  # reader parked until applied()
    z.applied(cts)
    th.join(timeout=5)
    assert got and got[0] > cts


def test_corrupt_record_raises():
    from dgraph_tpu.posting.pl import (
        CorruptRecordError,
        OP_SET,
        Posting,
        decode_record,
        encode_delta,
    )

    rec = encode_delta([Posting(uid=5, op=OP_SET)])
    decode_record(rec)  # sanity
    with pytest.raises(CorruptRecordError):
        decode_record(rec[: len(rec) - 3])
    with pytest.raises(CorruptRecordError):
        decode_record(b"\x07\x01\x00\x00\x00")


def test_cached_operands_transfer_zero_bytes_on_reuse(monkeypatch):
    """With version tokens present, a repeat dispatch of
    the same operands must perform ZERO new host->device transfers —
    the padded uploads are HBM-resident in the DeviceCache."""
    import jax.numpy as jnp_mod

    rng = np.random.default_rng(11)
    rows = [_mk_sorted(rng, 4000, 1 << 20) for _ in range(8)]
    b = _mk_sorted(rng, 200_000, 1 << 20)
    row_tokens = [((b"rk%d" % i), 7) for i in range(len(rows))]
    b_token = (b"bk", 7)

    d = dispatch.SetOpDispatcher()
    monkeypatch.setattr(dispatch, "_DEVICE_MIN_TOTAL", 1)
    monkeypatch.setattr(dispatch, "_FORCE_DEVICE", True)

    transfers = {"n": 0}
    real_asarray = jnp_mod.asarray
    real_put = dispatch.jax.device_put

    def count_asarray(x, *a, **k):
        if isinstance(x, np.ndarray) and x.size > 16:
            transfers["n"] += 1
        return real_asarray(x, *a, **k)

    def count_put(x, *a, **k):
        transfers["n"] += 1
        return real_put(x, *a, **k)

    monkeypatch.setattr(dispatch.jnp, "asarray", count_asarray)
    monkeypatch.setattr(dispatch.jax, "device_put", count_put)

    want = [np.intersect1d(r, b, assume_unique=True) for r in rows]
    got = d.run_rows_vs_one(
        "intersect", rows, b, row_tokens=row_tokens, b_token=b_token
    )
    for g, w in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g, np.uint64), w)
    warm = transfers["n"]
    assert warm > 0  # first call does upload

    transfers["n"] = 0
    got2 = d.run_rows_vs_one(
        "intersect", rows, b, row_tokens=row_tokens, b_token=b_token
    )
    for g, w in zip(got2, want):
        np.testing.assert_array_equal(np.asarray(g, np.uint64), w)
    assert transfers["n"] == 0, (
        f"cached operands re-uploaded: {transfers['n']} transfers"
    )
