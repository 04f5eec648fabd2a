"""Device cache + batched chain dispatch + round-1 advisory fixes.

Covers the device-resident pack cache and true level batching, plus
reindex aggregation, the commit visibility barrier, oracle GC and
corrupt-record validation.
"""

import random
import sys
import threading
from collections import OrderedDict

import numpy as np
import pytest

from dgraph_tpu.api.server import Server
from dgraph_tpu.query import dispatch
from dgraph_tpu.query.dispatch import DISPATCHER, DeviceCache
from dgraph_tpu.utils.observe import METRICS
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
    assert d.device_cache.hits == h0 + 2  # the rows' flat ids + b both reused
    for x, y in zip(r1, r2):
        np.testing.assert_array_equal(x, y)
    # the entry is the flat array: 4 bytes a padded id, one array, under
    # every row's key; b's beside it
    total = sum(len(r) for r in rows)
    stats = d.device_cache.stats()
    assert stats["entries"] == 2 and stats["keys"] == len(rows) + 1
    assert stats["bytes"] == 4 * (dispatch._pow4(total) + dispatch._pow2(len(b)))
    # commit invalidation by ONE row's key drops the flat entry, not b's
    d.device_cache.invalidate([b"k3"])
    stats = d.device_cache.stats()
    assert stats["entries"] == 1 and stats["keys"] == 1
    m0 = d.device_cache.misses
    r3 = d.run_rows_vs_one("intersect", rows, b, row_tokens=toks, b_token=(b"big", 3))
    assert d.device_cache.misses == m0 + 1 and d.device_cache.hits == h0 + 3
    assert d.device_cache.stats()["entries"] == 2
    for x, y in zip(r1, r3):
        np.testing.assert_array_equal(x, y)
    for x, r in zip(r1, rows):
        np.testing.assert_array_equal(x, np.intersect1d(r, b))


def test_device_cache_lru_bound():
    c = DeviceCache(max_bytes=1000)
    for i in range(10):
        c.put(("t", i), [b"k%d" % i], ("arr",), 300)
    assert c.stats()["bytes"] <= 1000


class _WalkCache:
    """The DeviceCache as it was before PR 27, kept as the plain
    reference: tokens in every key's set, and an eviction that walks
    all of them."""

    def __init__(self, max_bytes):
        self.max_bytes = max_bytes
        self.entries = OrderedDict()
        self.by_key = {}
        self.bytes = self.hits = self.misses = 0

    def get(self, token):
        got = self.entries.get(token)
        if got is None:
            self.misses += 1
            return None
        self.entries.move_to_end(token)
        self.hits += 1
        return got[0]

    def put(self, token, keys_involved, arrays, nbytes):
        if nbytes > self.max_bytes or token in self.entries:
            return
        self.entries[token] = (arrays, nbytes)
        self.bytes += nbytes
        for k in keys_involved:
            self.by_key.setdefault(k, set()).add(token)
        while self.bytes > self.max_bytes and self.entries:
            old_tok, (_, old_n) = self.entries.popitem(last=False)
            self.bytes -= old_n
            for toks in self.by_key.values():
                toks.discard(old_tok)

    def invalidate(self, keys):
        for k in keys:
            for tok in self.by_key.pop(k, ()):
                got = self.entries.pop(tok, None)
                if got is not None:
                    self.bytes -= got[1]

    def invalidate_prefix(self, prefixes):
        pfx = tuple(bytes(p) for p in prefixes)
        if pfx:
            self.invalidate(
                [k for k in self.by_key if bytes(k).startswith(pfx)])

    def clear(self):
        self.entries.clear()
        self.by_key.clear()
        self.bytes = 0


def _live(cache):
    """(token, arrays, nbytes) of a DeviceCache's entries, LRU first."""
    return [(t, e.arrays, e.nbytes) for t, e in cache._entries.items()]


def _live_keys(cache):
    return {k for e in cache._entries.values() for k in e.keys}


def _keys_of(token):
    """A token names its keys, as the dispatcher's do: ("b", (key, ts),
    ..) is put under that key, ("stack", .., row tokens) under each
    row's."""
    if token[0] == "b":
        return [token[1][0]]
    return [t[0] for t in token[-1]]


@pytest.mark.parametrize("seed", [0, 1, 2, 3, 4, 5, 6, 7])
def test_device_cache_matches_the_plain_walk(seed):
    """~300 random calls on a small byte bound: after every call the
    cache holds what the walk it replaced would hold: the entries in
    LRU order, the bytes, hits and misses; so an invalidation removed
    the same tokens. And it keeps no key without a live entry."""
    rng = random.Random(seed)
    keys = [b"\x00p%d\x00%d" % (p, u) for p in range(3) for u in range(8)]
    cache, ref = DeviceCache(max_bytes=1000), _WalkCache(1000)

    def token():
        if rng.random() < 0.3:
            return ("b", (rng.choice(keys), rng.randrange(2)), 0, 8)
        rows = tuple((k, rng.randrange(2))
                     for k in rng.sample(keys, rng.randrange(1, 5)))
        if rng.random() < 0.2:
            rows += rows[:1]  # one list twice in a level
        return ("stack", 0, 8, 4, rows)

    seen = [token() for _ in range(40)]
    for step in range(300):
        call = rng.choices(
            ["put", "get", "invalidate", "invalidate_prefix", "clear"],
            [45, 30, 15, 8, 2])[0]
        if call == "put":
            tok = rng.choice(seen)
            # 1100: over the bound, never admitted
            args = (tok, _keys_of(tok), ("arrays", step),
                    rng.choice([60, 150, 300, 450, 1000, 1100]))
        elif call == "get":
            args = (rng.choice(seen),)
        elif call == "invalidate":
            args = (rng.sample(keys, rng.randrange(0, 4)),)
        elif call == "invalidate_prefix":
            args = (rng.choice([[], [b"\x00p1\x00"], [b"\x00p0\x00", b"\x00p2\x00"],
                                [b"\x00p2\x001"], [b"zz"]]),)
        else:
            args = ()
        assert getattr(cache, call)(*args) == getattr(ref, call)(*args)
        assert _live(cache) == [
            (t, a, n) for t, (a, n) in ref.entries.items()], (step, call)
        st = cache.stats()
        assert (st["bytes"], st["hits"], st["misses"], st["entries"]) == (
            ref.bytes, ref.hits, ref.misses, len(ref.entries)), (step, call)
        assert st["bytes"] <= 1000
        assert set(cache._by_key) == _live_keys(cache), (step, call)
        assert st["keys"] == len(_live_keys(cache))
    assert cache.stats()["evictions"] > 0 and cache.hits > 0


class _CountedToken(tuple):
    """A token that counts how often it is hashed."""

    hashed = 0

    def __hash__(self):
        self.hashed += 1
        return super().__hash__()


def _fill_and_evict(n_keys):
    """A full cache over `n_keys` keys; then one put that evicts. The
    hash counts of the new token and of the evicted one."""
    keys = [b"k%d" % i for i in range(n_keys)]
    cache = DeviceCache(max_bytes=1000)
    per = n_keys // 10
    toks = []
    for i in range(10):
        mine = keys[i * per:(i + 1) * per]
        toks.append(_CountedToken(("stack", 0, 8, per,
                                   tuple((k, 1) for k in mine))))
        cache.put(toks[-1], mine, ("a",), 100)
    assert cache.stats()["keys"] == n_keys
    new = _CountedToken(("stack", 0, 8, per,
                         tuple((k, 2) for k in keys[:per])))
    before = toks[0].hashed
    assert cache.get(new) is None
    cache.put(new, keys[:per], ("a",), 100)
    st = cache.stats()
    assert st["evictions"] == 1 and st["entries"] == 10
    assert toks[0] not in cache._entries and new in cache._entries
    # the evicted entry was the only one under its keys; the new one
    # came under the same
    assert st["keys"] == n_keys
    return new.hashed, toks[0].hashed - before, cache, toks


@pytest.mark.parametrize("n_keys", [2000, 4000])
def test_put_hashes_a_token_a_constant_number_of_times(n_keys):
    """The walk hashed the evicted token once per key the cache had
    ever seen (and the new one once per key of its own). Now: the
    lookups in `_entries`, whatever the number of keys."""
    new, evicted, cache, toks = _fill_and_evict(n_keys)
    # get + put of the new token (2 lookups here were taken by the
    # test's own `in`); the evicted one leaves by popitem, unhashed
    assert new - 1 <= 4
    assert evicted - 1 <= 2
    # an invalidation hashes each token it removes once, not per key
    before = toks[5].hashed
    per = n_keys // 10
    cache.invalidate([b"k%d" % i for i in range(5 * per, 6 * per)])
    assert toks[5].hashed - before == 1
    assert cache.stats()["entries"] == 9
    assert cache.stats()["keys"] == n_keys - per


def test_hash_counts_do_not_grow_with_the_keys():
    small = _fill_and_evict(2000)[:2]
    assert _fill_and_evict(4000)[:2] == small
    assert _fill_and_evict(8000)[:2] == small


def test_device_cache_keeps_no_dead_keys():
    """Evicted and invalidated entries take their keys with them unless
    a live entry still has them: `_by_key` is what invalidate_prefix
    scans, and it used to grow to every key ever cached."""
    c = DeviceCache(max_bytes=1000)
    for i in range(50):  # 3 fit: 47 evictions
        c.put(("stack", i), [b"a%d" % i, b"shared"], ("arr",), 300)
    assert c.stats()["evictions"] == 47
    assert set(c._by_key) == {b"a47", b"a48", b"a49", b"shared"}
    assert c.stats()["keys"] == 4
    c.invalidate([b"a48"])
    assert c.stats()["keys"] == 3 and c.stats()["entries"] == 2
    c.invalidate_prefix([b"a4"])
    assert c.stats() == {"entries": 0, "bytes": 0, "hits": 0, "misses": 0,
                         "evictions": 47, "keys": 0}
    c.put(("b", 1), [b"x"], ("arr",), 10)
    c.clear()
    assert c.stats()["keys"] == 0 and c.stats()["entries"] == 0


def test_device_cache_under_eight_threads():
    """put / get / invalidate from 8 threads, a fixed number of
    operations each: the byte count is the live entries' and the bound
    holds at every look."""
    c = DeviceCache(max_bytes=2000)
    keys = [b"k%d" % i for i in range(24)]
    over = []
    errors = []

    def work(seed):
        rng = random.Random(seed)
        try:
            for i in range(1500):
                mine = rng.sample(keys, 3)
                tok = ("stack", tuple((k, 1) for k in sorted(mine)))
                r = rng.random()
                if r < 0.5:
                    c.put(tok, sorted(mine), (seed, i), rng.choice([100, 300, 700]))
                elif r < 0.85:
                    c.get(tok)
                elif r < 0.97:
                    c.invalidate(mine[:1])
                else:
                    c.invalidate_prefix([b"k1"])
                b = c.stats()["bytes"]
                if b > 2000 or b < 0:
                    over.append(b)
        except Exception as e:  # surfaced below: a thread's own are lost
            errors.append(e)

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=work, args=(s,)) for s in range(8)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        assert not any(t.is_alive() for t in threads)
    finally:
        sys.setswitchinterval(old)
    assert not errors and not over
    st = c.stats()
    assert st["bytes"] == sum(e.nbytes for e in c._entries.values()) <= 2000
    assert st["entries"] > 0
    assert set(c._by_key) == _live_keys(c)
    for k, held in c._by_key.items():
        assert held == {e for e in c._entries.values() if k in e.keys}


def test_device_cache_counters_move_with_stats():
    names = ("hits", "misses", "evictions")
    before = {n: METRICS.value(f"device_cache_{n}_total") for n in names}
    c = DeviceCache(max_bytes=1000)
    assert c.get(("t", 0)) is None
    for i in range(5):
        c.put(("t", i), [b"k%d" % i], ("arr", i), 300)
    assert c.get(("t", 4)) == ("arr", 4)
    assert c.get(("t", 0)) is None  # evicted
    c.invalidate([b"k4"])  # an invalidation is no eviction
    c.put(("t", 9), [b"k9"], ("arr",), 2000)  # over the bound: not admitted
    st = c.stats()
    assert (st["hits"], st["misses"], st["evictions"]) == (1, 2, 2)
    for n in names:
        assert METRICS.value(f"device_cache_{n}_total") - before[n] == st[n]


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
