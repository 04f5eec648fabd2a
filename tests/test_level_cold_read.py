"""A level's cold read (posting/memlayer.py read_many's miss path): the
unlocked native probe (storage/lsm.py versions_batch) and the one-pass
decoder (posting/pl.py decode_cold) against the locked per-key probe and
the general decoder, record for record, over generated stores; sixteen
readers against a writer that flushes and compacts; the counters."""

import threading
import time

import numpy as np
import pytest

from dgraph_tpu import native
from dgraph_tpu.codec import uidpack
from dgraph_tpu.posting import pl as plmod
from dgraph_tpu.posting.lists import LocalCache
from dgraph_tpu.posting.memlayer import MemoryLayer
from dgraph_tpu.posting.pl import (
    OP_SET, VALUE_UID, Posting, PostingList, decode_cold, encode_delta,
    encode_rollup, lang_uid,
)
from dgraph_tpu.storage.kv import MemKV
from dgraph_tpu.storage.lsm import LsmKV
from dgraph_tpu.types.types import TypeID
from dgraph_tpu.utils.observe import METRICS, TRACER

FAST, GENERAL = ('level_cold_keys_total{path="fast"}',
                 'level_cold_keys_total{path="general"}')
HITS, MISSES = "memlayer_hits_total", "memlayer_misses_total"
FAR = 1 << 60


def value(text, uid=VALUE_UID, lang="", facets=None, tid=TypeID.STRING):
    return Posting(uid, OP_SET, text.encode(), tid, lang,
                   {k: v.encode() for k, v in (facets or {}).items()},
                   {k: TypeID.STRING for k in facets or {}})


def rollup(uids=(), posts=()):
    return encode_rollup(uidpack.encode(np.array(uids, np.uint64)),
                         list(posts))


# name -> (uids, postings): what one KIND_ROLLUP record holds
SHAPES = {
    "value": ((), [value("Maria")]),
    "uid1": ((7,), []),
    "uid3": ((7, 9, 1 << 33), []),
    "uid3_wide": ((5, 1 << 31, (1 << 32) - 1), []),
    "uid16": (tuple(range(100, 132, 2)), []),
    "uid17": (tuple(range(100, 134, 2)), []),
    "uid200": (tuple(range(1, 1400, 7)), []),
    "uid300": (tuple(range(10, 910, 3)), []),
    "dense_bitmap": (tuple(range(1000, 1200)), []),
    "two_segments": ((3, (1 << 32) + 3), []),
    "uids_and_value": ((4, 5), [value("x", tid=TypeID.DEFAULT)]),
    "two_values": ((), [value("a", uid=11), value("b", uid=12)]),
    "int_value": ((), [Posting(VALUE_UID, OP_SET, b"\x2a" + b"\0" * 7,
                               TypeID.INT)]),
    "empty": ((), []),
    "facets": ((21, 22), [Posting(21, OP_SET, None, TypeID.DEFAULT, "",
                                  {"since": b"2010"},
                                  {"since": TypeID.STRING})]),
    "value_facets": ((), [value("x", facets={"src": "web"})]),
    "lang": ((), [value("chat", uid=lang_uid("fr"), lang="fr"),
                  value("cat", uid=lang_uid("en"), lang="en")]),
}
GENERAL_SHAPES = {"facets", "value_facets", "lang"}


def key_of(name):
    return b"\x00k/" + name.encode()


def same_pack(a, b):
    assert a.num_uids == b.num_uids
    for f in ("bases", "counts", "offsets"):
        x, y = getattr(a, f), getattr(b, f)
        assert x.dtype == y.dtype and x.shape == y.shape, f
        assert np.array_equal(x, y), f


def same_list(got: PostingList, want: PostingList):
    """Every field a reader can see, and what the readers compute."""
    assert got.key == want.key
    same_pack(got.pack, want.pack)
    assert got.value_postings == want.value_postings
    assert got.deltas == want.deltas
    assert (got.min_ts, got.latest_ts) == (want.min_ts, want.latest_ts)
    assert got.split_starts == want.split_starts
    assert len(got.part_packs) == len(want.part_packs)
    for a, b in zip(got.part_packs, want.part_packs):
        same_pack(a, b)
    assert got.uids().dtype == want.uids().dtype == np.uint64
    assert np.array_equal(got.uids(), want.uids())
    assert got.get_all_values() == want.get_all_values()
    assert got.has_uid_deltas() == want.has_uid_deltas()
    same_pack(got.merged_pack(), want.merged_pack())


def reference(kv, key, read_ts=FAR) -> PostingList:
    """The path that stays: the locked per-key probe, the general decoder."""
    return PostingList.from_versions(
        key, kv.versions(key, read_ts), kv=kv, read_ts=read_ts)


def counters():
    return {n: METRICS.value(n) for n in (HITS, MISSES, FAST, GENERAL)}


def moved(before):
    return {n: v - before[n] for n, v in counters().items()}


@pytest.fixture
def store(tmp_path):
    kv = LsmKV(str(tmp_path / "l"))
    kv.put_batch([(key_of(n), 5, rollup(u, p)) for n, (u, p) in
                  SHAPES.items()])
    kv.flush()
    yield kv
    kv.close()


# -- the decoder alone -------------------------------------------------------


@pytest.mark.parametrize("name", sorted(SHAPES))
def test_decode_cold_equals_general_decoder(name):
    uids, posts = SHAPES[name]
    rec = rollup(uids, posts)
    want = PostingList.from_versions(key_of(name), [(5, rec)])
    got = decode_cold(key_of(name), 5, rec)
    if name in GENERAL_SHAPES:
        assert got is None  # the record says so: facets, @lang
        return
    same_list(got, want)
    if not uids:
        assert got.pack is uidpack.EMPTY  # one shared pack, not three arrays


def test_decode_cold_leaves_what_it_does_not_know():
    key = b"k"
    assert decode_cold(key, 5, encode_delta([value("d")])) is None
    split = encode_rollup(uidpack.EMPTY, [], split_starts=[1, 900])
    assert decode_cold(key, 5, split) is None
    no_tail = rollup((7,), [value("v")])[:-4]  # a record from before splits
    same_list(decode_cold(key, 5, no_tail),
              PostingList.from_versions(key, [(5, no_tail)]))
    good = rollup((7, 9), [value("Maria")])
    for cut in range(len(good)):
        if cut == len(good) - 4:
            continue  # the tail-less form above
        assert decode_cold(key, 5, good[:cut]) is None, cut
        with pytest.raises(ValueError):  # CorruptRecordError is one
            PostingList.from_versions(key, [(5, good[:cut])])
    assert decode_cold(key, 5, good + b"\0") is None  # bytes past the tail
    bad_type = bytearray(rollup((), [value("v")]))
    bad_type[5 + 16 + 4 + 9] = 200  # the posting's type id
    assert decode_cold(key, 5, bytes(bad_type)) is None
    with pytest.raises(ValueError):
        PostingList.from_versions(key, [(5, bytes(bad_type))])
    bad_count = bytearray(rollup((7, 9), []))
    bad_count[5 + 4] = 3  # the pack's num_uids against its block's 2
    assert decode_cold(key, 5, bytes(bad_count)) is None
    with pytest.raises(ValueError):
        PostingList.from_versions(key, [(5, bytes(bad_count))])


def test_empty_list_shares_one_pack():
    a, b = PostingList(b"a"), PostingList(b"b", pack=None)
    assert a.pack is b.pack is uidpack.EMPTY
    assert len(a.uids()) == 0 and a.latest_ts == 0
    for arr in (uidpack.EMPTY.bases, uidpack.EMPTY.counts,
                uidpack.EMPTY.offsets, uidpack.NO_UIDS):
        assert not arr.flags.writeable
    same_pack(uidpack.EMPTY, uidpack.encode(np.zeros((0,), np.uint64)))
    same_pack(uidpack.EMPTY,
              uidpack.deserialize(uidpack.serialize(uidpack.EMPTY)))


# -- the probe alone ---------------------------------------------------------


def test_versions_batch_equals_versions_over_a_store(tmp_path):
    """Every key of three overlapping tables and the memtable, keys that
    are absent (below the first, above the last, between two, refused by
    the bloom or let through by it), at three read timestamps."""
    rng = np.random.default_rng(11)
    kv = LsmKV(str(tmp_path / "l"), compact_at=99)
    keys = [b"k%05d" % i for i in range(0, 3000, 3)]
    for round_ in range(3):
        batch = []
        for k in rng.choice(len(keys), 500, replace=False):
            ts = int(rng.integers(1, 40))
            batch.append((keys[k], ts, b"r%d/%d" % (round_, ts)))
        kv.put_batch(batch)
        kv.flush()
    kv.put_batch([(keys[k], 50, b"mem") for k in range(0, len(keys), 17)])
    assert len(kv._tables) == 3 and kv._mem
    absent = [b"a", b"k", b"k00001", b"k01501x", b"k99999", b"z"]
    asked = keys + absent
    rng.shuffle(asked)
    for read_ts in (FAR, 20, 0):
        for lo in range(0, len(asked), 64):
            part = asked[lo:lo + 64] + asked[lo:lo + 2]  # duplicates too
            got = kv.versions_batch(part, read_ts)
            for k in part:
                assert got.get(k, []) == kv.versions(k, read_ts), k
    assert kv.versions_batch([], FAR) == {}
    kv.close()


def test_probe_needs_no_lock_and_leaves_tables_balanced(store):
    """The probe runs outside the store's lock (another thread can hold
    it meanwhile only because the tables are retained), and gives every
    reference back."""
    table = store._tables[0]
    entered, release = threading.Event(), threading.Event()
    real = table.versions_of_many

    def probe(keys):
        entered.set()
        assert release.wait(10)
        return real(keys)

    table.versions_of_many = probe
    out = {}
    t = threading.Thread(target=lambda: out.update(
        store.versions_batch([key_of("value")], FAR)))
    t.start()
    assert entered.wait(10)
    assert table._refs == 2  # the owner's and the reader's
    got_lock = store._mu.acquire(timeout=5)  # the reader does not hold it
    assert got_lock
    store._mu.release()
    store.put(b"other", 9, b"x")  # a write gets through meanwhile
    release.set()
    t.join(10)
    assert out[key_of("value")] == store.versions(key_of("value"), FAR)
    assert table._refs == 1


# -- the whole miss path, record for record ----------------------------------


@pytest.mark.parametrize("name", sorted(SHAPES))
def test_cold_read_equals_reference(store, name):
    key = key_of(name)
    mem = MemoryLayer()
    before = counters()
    got = mem.read_many(store, [key], FAR)[key]
    same_list(got, reference(store, key))
    want_path = GENERAL if name in GENERAL_SHAPES else FAST
    assert moved(before) == {HITS: 0, MISSES: 1, FAST: 0, GENERAL: 0,
                             want_path: 1}
    # the point read's miss is the same path; then both hit the entry
    assert MemoryLayer().read(store, key, FAR).uids().tolist() == \
        got.uids().tolist()
    assert moved(before)[want_path] == 2
    assert mem.read_many(store, [key], FAR)[key] is got
    assert mem.read(store, key, FAR) is got
    assert moved(before)[HITS] == 2


def test_whole_level_in_one_call(store):
    keys = [key_of(n) for n in SHAPES] + [b"\x00k/absent", b"zzz"]
    mem = MemoryLayer()
    before = counters()
    got = mem.read_many(store, keys + keys[:3], FAR)
    assert list(got) == keys
    for k in keys:
        same_list(got[k], reference(store, k))
    assert moved(before) == {
        HITS: 0, MISSES: len(keys),
        # a key with no record is the new path's too: nothing to decode
        FAST: len(SHAPES) - len(GENERAL_SHAPES) + 2,
        GENERAL: len(GENERAL_SHAPES)}
    assert (mem.hits, mem.misses) == (0, len(keys))
    again = mem.read_many(store, keys, FAR)
    assert all(again[k] is got[k] for k in keys)
    assert moved(before)[HITS] == len(keys)


def test_deltas_above_a_rollup_take_the_general_path(store):
    key = key_of("uid3")
    store.put(key, 8, encode_delta([Posting(77, OP_SET)]))
    store.put(key, 9, encode_delta([Posting(9, plmod.OP_DEL)]))
    mem = MemoryLayer()
    before = counters()
    got = mem.read_many(store, [key], FAR)[key]
    same_list(got, reference(store, key))
    assert got.uids().tolist() == [7, 77, 1 << 33]
    assert (got.min_ts, got.latest_ts) == (5, 9)
    assert moved(before)[GENERAL] == 1 and moved(before)[FAST] == 0
    # a reader below the deltas sees the rollup alone: one version, fast
    old = MemoryLayer().read_many(store, [key], 6)[key]
    same_list(old, reference(store, key, 6))
    assert old.uids().tolist() == [7, 9, 1 << 33]
    assert moved(before)[FAST] == 1
    # and one below the rollup sees nothing
    none = MemoryLayer().read_many(store, [key], 4)[key]
    same_list(none, reference(store, key, 4))
    assert none.is_empty() and none.latest_ts == 0


def test_split_list_takes_the_general_path(tmp_path, monkeypatch):
    monkeypatch.setattr(plmod, "MAX_PART_UIDS", 8)
    kv = LsmKV(str(tmp_path / "l"))
    from dgraph_tpu.x import keys

    big = keys.DataKey("knows", 1)
    uids = np.arange(1, 60, 2, dtype=np.uint64)
    kv.put_batch(plmod.rollup_writes(big, uids, [value("v")], 5))
    kv.flush()
    before = counters()
    got = MemoryLayer().read_many(kv, [big], FAR)[big]
    same_list(got, reference(kv, big))
    assert got.split_starts and got.uids().tolist() == uids.tolist()
    assert moved(before)[GENERAL] == 1 and moved(before)[FAST] == 0
    kv.close()


def test_markers_hide_records_from_the_cold_read(store):
    """drop_prefix and delete_below, each over a key that sits in a
    table: the batched probe honours them as the per-key one does."""
    store.put(b"\x00j/keep", 5, rollup((1,), []))
    store.put(key_of("uid1"), 7, rollup((8,), []))  # above the old rollup
    store.flush()
    store.delete_below(key_of("uid1"), 7)
    store.drop_prefix(b"\x00k/uid3")
    store.put(key_of("uid300"), 9, rollup((1, 2), []))  # after no drop
    mem = MemoryLayer()
    keys = [key_of(n) for n in SHAPES] + [b"\x00j/keep"]
    got = mem.read_many(store, keys, FAR)
    for k in keys:
        same_list(got[k], reference(store, k))
    for gone in ("uid3", "uid300", "uid3_wide"):
        want = [1, 2] if gone == "uid300" else []
        assert got[key_of(gone)].uids().tolist() == want
    assert got[key_of("uid1")].uids().tolist() == [8]
    assert got[key_of("uid1")].min_ts == 7
    assert got[b"\x00j/keep"].uids().tolist() == [1]
    # below the marker's ts the hidden rollup stays hidden: delete_below
    # removed it for every reader
    old = MemoryLayer().read_many(store, [key_of("uid1")], 6)
    same_list(old[key_of("uid1")], reference(store, key_of("uid1"), 6))
    assert old[key_of("uid1")].is_empty()


def test_read_ts_below_the_newest_version(store):
    key = key_of("value")
    store.put(key, 9, rollup((), [value("Mariam")]))
    store.flush()
    mem = MemoryLayer()
    for read_ts, text in ((FAR, b"Mariam"), (9, b"Mariam"), (8, b"Maria"),
                          (5, b"Maria")):
        got = mem.read_many(store, [key], read_ts)[key]
        same_list(got, reference(store, key, read_ts))
        assert got.get_all_values()[0].value == text, read_ts
    assert mem.read_many(store, [key], 4)[key].is_empty()
    # an older reader never gets the entry a newer one cached
    assert mem.read_many(store, [key], FAR)[key].min_ts == 9
    assert mem.read_many(store, [key], 8)[key].min_ts == 5


def test_newer_sequence_in_the_older_table(tmp_path):
    """One (key, ts) in two tables and a delta in the memtable; the
    table that sits OLDER in the list holds the newer sequence (a partial
    compaction reorders tables: sequence is the authority, not order)."""
    kv = LsmKV(str(tmp_path / "l"), compact_at=99)
    kv.put(b"k", 5, rollup((1, 2), []))
    kv.put(b"only_old", 5, rollup((4,), []))
    kv.flush()
    kv.put(b"k", 5, rollup((1, 2, 3), []))  # the rewrite: same ts, newer seq
    kv.flush()
    kv._tables.reverse()  # now the newer sequence is in the older table
    kv.put(b"k", 8, encode_delta([Posting(9, OP_SET)]))
    before = counters()
    got = MemoryLayer().read_many(kv, [b"k", b"only_old"], FAR)
    same_list(got[b"k"], reference(kv, b"k"))
    assert got[b"k"].uids().tolist() == [1, 2, 3, 9]
    same_list(got[b"only_old"], reference(kv, b"only_old"))
    # below the delta: two records of one ts still resolve by sequence,
    # and what is left is one version, which the fast decoder takes
    old = MemoryLayer().read_many(kv, [b"k"], 6)[b"k"]
    assert old.uids().tolist() == [1, 2, 3]
    assert moved(before) == {HITS: 0, MISSES: 3, FAST: 2, GENERAL: 1}
    kv.close()


@pytest.mark.parametrize("how", ["encrypted", "no_native", "memkv"])
def test_stores_that_keep_to_the_general_decoder(tmp_path, monkeypatch, how):
    """An encrypted store, a process without the native library and
    MemKV: same answers, all of it counted under path="general"."""
    if how == "no_native":
        monkeypatch.setattr(native, "sst_available", lambda: False)
    if how == "memkv":
        kv = MemKV()
    else:
        kv = LsmKV(str(tmp_path / "l"),
                   enc_key=b"k" * 32 if how == "encrypted" else None)
        assert kv.native_probe is False
    items = [(key_of(n), 5, rollup(u, p)) for n, (u, p) in SHAPES.items()]
    for k, ts, rec in items:
        kv.put(k, ts, rec)
    if how != "memkv":
        kv.flush()
        assert kv._tables[0]._native is False
    keys = [k for k, _, _ in items]
    before = counters()
    got = MemoryLayer().read_many(kv, keys, FAR)
    for k in keys:
        same_list(got[k], reference(kv, k))
    assert moved(before) == {HITS: 0, MISSES: len(keys), FAST: 0,
                             GENERAL: len(keys)}
    if how == "no_native":
        monkeypatch.undo()
        plain = LsmKV(str(tmp_path / "l"))  # the same files, natively
        assert plain.native_probe and plain._tables[0]._native
        fast = MemoryLayer().read_many(plain, keys, FAR)
        for k in keys:
            same_list(fast[k], got[k])
        plain.close()
    if how != "memkv":
        kv.close()


# -- readers against a writer ------------------------------------------------


def test_sixteen_readers_against_flush_and_compact(tmp_path):
    """16 reader threads over never-read keys while another thread puts,
    flushes and compacts: every read equals a single-threaded read at
    the same read_ts, and no table is left retained. Carries its own
    time limit."""
    limit = time.monotonic() + 60
    kv = LsmKV(str(tmp_path / "l"), compact_at=4)
    rng = np.random.default_rng(5)
    keys = [b"\x00c/%06d" % i for i in range(4000)]
    stable_ts = 10

    def record(i, gen):
        if i % 2:
            return rollup((), [value("v%d.%d" % (i, gen))])
        return rollup(sorted({i + 1, i + 2 + gen, 9000 + gen}), [])

    kv.put_batch([(k, stable_ts, record(i, 0)) for i, k in enumerate(keys)])
    kv.flush()
    # what a reader at stable_ts must see, whatever is written above it
    want = {k: reference(kv, k, stable_ts) for k in keys[::7]}
    stop = threading.Event()
    errors = []
    seen_tables = set()

    def writer():
        gen = 0
        try:
            while not stop.is_set():
                gen += 1
                ts = stable_ts + gen
                picks = rng.choice(len(keys), 300, replace=False)
                kv.put_batch([(keys[i], ts, record(int(i), gen))
                              for i in picks])
                kv.flush()
                seen_tables.update(id(t) for t in kv._tables)
                if gen % 3 == 0:
                    kv.compact()
        except Exception as e:  # pragma: no cover - the test's own report
            errors.append(("writer", repr(e)))

    def reader(n):
        mem = MemoryLayer()
        mine = keys[n::16]
        try:
            for lo in range(0, len(mine), 8):
                if time.monotonic() > limit:
                    raise TimeoutError("reader over its time limit")
                part = mine[lo:lo + 8]
                got = LocalCache(kv, stable_ts, mem=mem)
                got._resolve_many(part)
                for k in part:
                    pl = got.get(k)
                    ref = want.get(k)
                    if ref is None:
                        ref = reference(kv, k, stable_ts)
                    same_list(pl, ref)
                # and a reader at the newest ts equals the locked path
                # at a ts nothing is being written at any more
                top = kv.max_write_ts() - 1
                if top > stable_ts:
                    for k in part[:2]:
                        a = MemoryLayer().read_many(kv, [k], top)[k]
                        same_list(a, reference(kv, k, top))
        except Exception as e:
            errors.append((n, repr(e)))

    w = threading.Thread(target=writer)
    readers = [threading.Thread(target=reader, args=(n,)) for n in range(16)]
    w.start()
    for t in readers:
        t.start()
    for t in readers:
        t.join(max(0.1, limit - time.monotonic()))
    stop.set()
    w.join(30)
    assert not any(t.is_alive() for t in readers + [w]), "over the time limit"
    assert not errors, errors[:3]
    assert len(seen_tables) > 3  # tables did come and go under the readers
    # retain / release balance: the owner's reference alone is left
    assert all(t._refs == 1 and not t._closed for t in kv._tables)
    kv.close()


# -- the counters ------------------------------------------------------------


def test_counters_and_the_cold_attr(tmp_path, monkeypatch):
    """Over a run of known keys: hits + misses = keys asked, the two
    paths of level_cold_keys_total sum to the misses, and the level_task
    spans' `cold` sums to the same."""
    monkeypatch.setenv("DGRAPH_TPU_STORAGE", "lsm")
    from dgraph_tpu.api.server import Server
    from dgraph_tpu.loaders.bulk2 import ParallelBulkLoader

    s = Server(data_dir=str(tmp_path / "p"))
    s.alter("name: string .\nnick: string @lang .\nknows: [uid] @reverse .")
    n = 40
    rdf = []
    for i in range(1, n + 1):
        rdf.append(f'<0x{i:x}> <name> "p{i}" .')
        rdf.append(f'<0x{i:x}> <nick> "n{i}"@en .')
        for j in (i % n + 1, (i + 7) % n + 1):
            rdf.append(f'<0x{i:x}> <knows> <0x{j:x}> .')
    ParallelBulkLoader(s, workers=1).load_text("\n".join(rdf))
    assert isinstance(s.kv, LsmKV) and s.kv.native_probe
    roots = ", ".join(hex(i) for i in range(1, 11))
    query = "{ q(func: uid(%s)) { name nick@en knows { name } } }" % roots

    def run():
        before = counters()
        out = s.query(query)
        assert len(out["data"]["q"]) == 10
        spans = TRACER.trace_spans(int(out["extensions"]["trace_id"], 16))
        levels = [sp for sp in spans if sp["name"] == "level_task"]
        assert len(levels) == 4
        return moved(before), levels

    # the keys this query asks of the MemoryLayer: three predicates of
    # the ten roots, and the names of the friends that are not roots
    # (a root's name is in the query's own cache by then)
    friends = {j for i in range(1, 11) for j in (i % n + 1, (i + 7) % n + 1)}
    asked = 30 + len(friends - set(range(1, 11)))
    got, levels = run()
    assert got[HITS] + got[MISSES] == asked and got[HITS] == 0
    assert got[FAST] + got[GENERAL] == got[MISSES]
    assert sum(sp["attrs"].get("cold", 0) for sp in levels) == got[MISSES]
    by_attr = {(sp["attrs"]["attr"], sp["attrs"]["level"]):
               sp["attrs"].get("cold", 0) for sp in levels}
    assert by_attr == {("name", 1): 10, ("nick", 1): 10, ("knows", 1): 10,
                       ("name", 2): asked - 30}
    assert got[GENERAL] == 10  # nick@en: the record says @lang
    assert s.mem.stats()["misses"] >= got[MISSES]
    # the same query again: all hits, no span says cold
    again, levels2 = run()
    assert again == {HITS: asked, MISSES: 0, FAST: 0, GENERAL: 0}
    assert not any("cold" in sp["attrs"] for sp in levels2)
    s.kv.close()
