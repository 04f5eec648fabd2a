"""LsmKV: spill-to-disk storage engine (ref
BadgerDB's role at worker/server_state.go:95).
"""

import numpy as np
import pytest

from dgraph_tpu.storage.kv import MemKV
from dgraph_tpu.storage.lsm import LsmKV


def test_basic_mvcc_roundtrip(tmp_path):
    kv = LsmKV(str(tmp_path / "l"))
    kv.put(b"a", 5, b"v5")
    kv.put(b"a", 9, b"v9")
    kv.put(b"b", 3, b"w")
    assert kv.get(b"a", 4) is None
    assert kv.get(b"a", 5) == (5, b"v5")
    assert kv.get(b"a", 100) == (9, b"v9")
    assert kv.versions(b"a", 100) == [(9, b"v9"), (5, b"v5")]
    assert [k for k, _, _ in kv.iterate(b"", 100)] == [b"a", b"b"]
    kv.close()


def test_flush_and_reopen(tmp_path):
    d = str(tmp_path / "l")
    kv = LsmKV(d)
    for i in range(100):
        kv.put(b"k%03d" % i, i + 1, b"v%d" % i)
    kv.flush()
    kv.put(b"late", 500, b"mem-only")
    kv.close()
    kv2 = LsmKV(d)
    assert kv2.get(b"k042", 1000) == (43, b"v42")
    assert kv2.get(b"late", 1000) == (500, b"mem-only")  # WAL replay
    assert len(list(kv2.iterate(b"k", 1000))) == 100
    kv2.close()


def test_spill_under_small_memtable(tmp_path):
    kv = LsmKV(str(tmp_path / "l"), memtable_bytes=2048)
    for i in range(500):
        kv.put(b"key%05d" % i, i + 1, b"x" * 50)
    assert len(kv._tables) >= 1  # spilled
    assert kv._mem_size < 500 * 74  # memory bounded
    for i in (0, 123, 499):
        assert kv.get(b"key%05d" % i, 1 << 40) == (i + 1, b"x" * 50)
    kv.close()


def test_drop_prefix_across_flush(tmp_path):
    kv = LsmKV(str(tmp_path / "l"))
    kv.put(b"p/a", 1, b"1")
    kv.put(b"p/b", 2, b"2")
    kv.put(b"q/c", 3, b"3")
    kv.flush()
    kv.drop_prefix(b"p/")
    assert kv.get(b"p/a", 100) is None
    assert kv.get(b"q/c", 100) == (3, b"3")
    # a write AFTER the drop is visible
    kv.put(b"p/a", 10, b"new")
    assert kv.get(b"p/a", 100) == (10, b"new")
    kv.compact()
    assert kv.get(b"p/a", 100) == (10, b"new")
    assert kv.get(b"p/b", 100) is None
    kv.close()


def test_delete_below_gc(tmp_path):
    kv = LsmKV(str(tmp_path / "l"))
    for ts in (1, 5, 9):
        kv.put(b"k", ts, b"v%d" % ts)
    kv.flush()
    kv.delete_below(b"k", 9)
    assert kv.versions(b"k", 100) == [(9, b"v9")]
    kv.compact()
    assert kv.versions(b"k", 100) == [(9, b"v9")]
    kv.close()


def test_compaction_collapses_tables(tmp_path):
    kv = LsmKV(str(tmp_path / "l"), memtable_bytes=512, compact_at=3)
    for i in range(400):
        kv.put(b"c%04d" % i, i + 1, b"y" * 20)
    kv.flush()
    assert len(kv._tables) < 3  # auto-compaction kept the count bounded
    assert kv.get(b"c0000", 1 << 40) == (1, b"y" * 20)
    assert kv.get(b"c0399", 1 << 40) == (400, b"y" * 20)
    kv.close()


def test_parity_with_memkv_random_ops(tmp_path):
    rng = np.random.default_rng(0)
    lsm = LsmKV(str(tmp_path / "l"), memtable_bytes=1024)
    mem = MemKV()
    keys = [b"k%d" % i for i in range(30)]
    ts = 0
    for _ in range(600):
        ts += 1
        op = rng.integers(0, 10)
        k = keys[int(rng.integers(0, len(keys)))]
        if op < 8:
            v = b"v%d" % ts
            lsm.put(k, ts, v)
            mem.put(k, ts, v)
        elif op == 8:
            lsm.delete_below(k, max(1, ts - 20))
            mem.delete_below(k, max(1, ts - 20))
        else:
            lsm.flush()
    for k in keys:
        assert lsm.versions(k, ts) == mem.versions(k, ts), k
    got = [(k, t, v) for k, t, v in lsm.iterate(b"k", ts)]
    want = [(k, t, v) for k, t, v in mem.iterate(b"k", ts)]
    assert got == want
    lsm.close()


def test_engine_runs_on_lsm(tmp_path, monkeypatch):
    monkeypatch.setenv("DGRAPH_TPU_STORAGE", "lsm")
    from dgraph_tpu.api.server import Server

    s = Server(data_dir=str(tmp_path / "p"))
    s.alter("name: string @index(exact) .\nfriend: [uid] .")
    t = s.new_txn()
    t.mutate_rdf(
        set_rdf='<0x1> <name> "lsm-alice" .\n<0x1> <friend> <0x2> .\n'
        '<0x2> <name> "lsm-bob" .',
        commit_now=True,
    )
    out = s.query('{ q(func: eq(name, "lsm-alice")) { name friend { name } } }')
    assert out["data"]["q"][0]["friend"][0]["name"] == "lsm-bob"
    s.kv.close()
    # restart from disk
    s2 = Server(data_dir=str(tmp_path / "p"))
    out = s2.query('{ q(func: eq(name, "lsm-alice")) { name } }')
    assert out["data"]["q"][0]["name"] == "lsm-alice"
    s2.kv.close()


def test_compaction_same_ts_newest_seq_wins(tmp_path):
    """ADVICE r2 (high): rollup rewrites a key at the SAME ts as the latest
    version; compaction must keep the newest seq for a (key, ts) group, like
    the read path, or the rollup silently reverts to the pre-rollup value."""
    kv = LsmKV(str(tmp_path / "l"))
    kv.put(b"k", 5, b"old")
    kv.flush()
    kv.put(b"k", 5, b"ROLLUP")
    kv.compact()
    assert kv.get(b"k", 100) == (5, b"ROLLUP")
    # and it survives reopen
    kv.close()
    kv2 = LsmKV(str(tmp_path / "l"))
    assert kv2.get(b"k", 100) == (5, b"ROLLUP")
    kv2.close()


def test_iterate_survives_concurrent_compaction(tmp_path):
    """ADVICE r2 (medium): a live single-table iterator must not crash when
    a concurrent flush+compact unlinks the table it is scanning."""
    kv = LsmKV(str(tmp_path / "l"), compact_at=2)
    for i in range(500):
        kv.put(b"k%04d" % i, 1, b"v%d" % i)
    kv.compact()  # single table, no memtable: iterate takes the fast path
    it = kv.iterate(b"k", 10)
    got = [next(it) for _ in range(10)]  # iterator now mid-table
    # trigger flush + compaction, which closes+unlinks the old table
    for i in range(500):
        kv.put(b"j%04d" % i, 2, b"w%d" % i)
    kv.flush()
    kv.compact()
    rest = list(it)  # must finish cleanly on the retained mmap
    assert len(got) + len(rest) == 500
    assert rest[-1][0] == b"k0499"
    kv.close()
