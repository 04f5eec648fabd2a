"""Multi-part posting lists + sharded giant-operand dispatch.

Covers split keys (x/keys.go:512 SplitKey
semantics), rollup-time re-split (posting/list.go:1590), and routing
oversized operands through the row-sharded mesh kernels.
"""

import numpy as np
import pytest

import jax

from dgraph_tpu.posting import pl as plmod
from dgraph_tpu.posting.pl import (
    OP_SET,
    Posting,
    PostingList,
    decode_record,
    encode_delta,
    rollup_writes,
)
from dgraph_tpu.posting.rollup import rollup_key
from dgraph_tpu.storage.kv import MemKV
from dgraph_tpu.x import keys


def test_split_key_roundtrip():
    base = keys.DataKey("friend", 42)
    sk = keys.SplitKey(base, 7)
    got_base, start = keys.base_of_split(sk)
    assert got_base == base and start == 7
    pk = keys.parse_key(sk)
    assert pk.tag == keys.TAG_SPLIT
    assert pk.attr == "friend" and pk.uid == 42 and pk.split_start == 7
    # split keys sort outside the data region
    assert not sk.startswith(keys.DataPrefix("friend"))
    assert sk.startswith(keys.SplitPredicatePrefix("friend"))


def test_rollup_splits_and_reads_back(monkeypatch):
    monkeypatch.setattr(plmod, "MAX_PART_UIDS", 100)
    kv = MemKV()
    key = keys.DataKey("follows", 1)
    uids = np.arange(1, 501, dtype=np.uint64)  # 500 uids > 100 threshold
    for ts, u in enumerate(uids, start=2):
        kv.put(key, ts, encode_delta([Posting(uid=int(u), op=OP_SET)]))
    assert rollup_key(kv, key, 1000)
    # main record now holds split starts, parts live under SplitKey
    _, rec = kv.get(key, 1000)
    kind, pack, posts, splits = decode_record(rec)
    assert len(splits) == 10  # 500 / (100//2)
    for st in splits:
        assert kv.get(keys.SplitKey(key, st), 1000) is not None
    pl2 = PostingList.from_versions(key, kv.versions(key, 1000), kv=kv, read_ts=1000)
    np.testing.assert_array_equal(pl2.uids(), uids)


def test_resplit_after_growth(monkeypatch):
    monkeypatch.setattr(plmod, "MAX_PART_UIDS", 100)
    kv = MemKV()
    key = keys.DataKey("follows", 2)
    ts = 1
    for u in range(1, 201):
        ts += 1
        kv.put(key, ts, encode_delta([Posting(uid=u, op=OP_SET)]))
    assert rollup_key(kv, key, 1000)
    _, rec = kv.get(key, 1000)
    _, _, _, splits1 = decode_record(rec)
    # grow the list, rollup again: re-split with more parts, old parts gone
    for u in range(201, 501):
        ts += 1
        kv.put(key, ts, encode_delta([Posting(uid=u, op=OP_SET)]))
    assert rollup_key(kv, key, 2000)
    _, rec = kv.get(key, 2000)
    _, _, _, splits2 = decode_record(rec)
    assert len(splits2) > len(splits1)
    pl2 = PostingList.from_versions(key, kv.versions(key, 2000), kv=kv, read_ts=2000)
    np.testing.assert_array_equal(pl2.uids(), np.arange(1, 501, dtype=np.uint64))


def test_shrink_merges_back(monkeypatch):
    monkeypatch.setattr(plmod, "MAX_PART_UIDS", 100)
    kv = MemKV()
    key = keys.DataKey("follows", 3)
    ts = 1
    for u in range(1, 301):
        ts += 1
        kv.put(key, ts, encode_delta([Posting(uid=u, op=OP_SET)]))
    assert rollup_key(kv, key, 1000)
    from dgraph_tpu.posting.pl import OP_DEL

    for u in range(51, 301):  # delete down to 50 uids
        ts += 1
        kv.put(key, ts, encode_delta([Posting(uid=u, op=OP_DEL)]))
    assert rollup_key(kv, key, 2000)
    _, rec = kv.get(key, 2000)
    _, pack, _, splits = decode_record(rec)
    assert splits == []  # merged back into a single record
    pl2 = PostingList.from_versions(key, kv.versions(key, 2000), kv=kv, read_ts=2000)
    np.testing.assert_array_equal(pl2.uids(), np.arange(1, 51, dtype=np.uint64))


def test_bulk_rollup_writes_split(monkeypatch):
    monkeypatch.setattr(plmod, "MAX_PART_UIDS", 64)
    kv = MemKV()
    key = keys.DataKey("x", 9)
    uids = np.arange(10, 400, dtype=np.uint64)
    for k, ts, rec in rollup_writes(key, uids, [], 5):
        kv.put(k, ts, rec)
    pl2 = PostingList.from_versions(key, kv.versions(key, 10), kv=kv, read_ts=10)
    np.testing.assert_array_equal(pl2.uids(), uids)


def test_engine_query_over_split_list(monkeypatch):
    """A predicate whose posting list is split must answer queries
    identically (expansion + filter intersect path)."""
    monkeypatch.setattr(plmod, "MAX_PART_UIDS", 50)
    from dgraph_tpu.api.server import Server
    from dgraph_tpu.posting.rollup import rollup_all

    s = Server()
    s.alter("name: string @index(exact) .\nfollows: [uid] .")
    t = s.new_txn()
    rdf = ['<0x1> <name> "hub" .']
    for i in range(2, 202):
        rdf.append(f"<0x1> <follows> <0x{i:x}> .")
        rdf.append(f'<0x{i:x}> <name> "n{i}" .')
    t.mutate_rdf(set_rdf="\n".join(rdf), commit_now=True)
    rollup_all(s, min_deltas=1)
    # split actually happened
    _, rec = s.kv.get(keys.DataKey("follows", 1), 1 << 60)
    _, _, _, splits = decode_record(rec)
    assert len(splits) >= 2
    out = s.query('{ q(func: eq(name, "hub")) { follows { name } } }')
    assert len(out["data"]["q"][0]["follows"]) == 200
    out = s.query(
        '{ q(func: eq(name, "hub")) { c: count(follows) } }'
    )
    assert out["data"]["q"][0]["c"] == 200


@pytest.mark.skipif(len(jax.devices()) < 2, reason="needs multi-device mesh")
def test_sharded_rows_membership_4m():
    """>4M-uid operand on the 8-device virtual mesh."""
    from dgraph_tpu.parallel import mesh as pmesh
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    rng = np.random.default_rng(0)
    n_big = (1 << 22) + 12345  # > 4M
    big = np.sort(
        rng.choice(np.arange(1, 1 << 26, dtype=np.uint32), n_big, replace=False)
    )
    mesh = pmesh.make_mesh()
    ndev = mesh.devices.size
    tile = -(-n_big // ndev)
    tile = 1 << (tile - 1).bit_length()
    pb = tile * ndev
    from dgraph_tpu.ops import setops

    Bd = jax.device_put(
        jnp.asarray(setops.pad_sorted(big, pb)), NamedSharding(mesh, P("data"))
    )
    rows = np.full((4, 64), setops.UINT32_MAX, np.uint32)
    LA = np.zeros((4,), np.int32)
    for i in range(4):
        hits = rng.choice(big, 20, replace=False)
        misses = rng.integers(1 << 26, 1 << 27, 20, dtype=np.uint32)
        r = np.unique(np.concatenate([hits, misses]))
        rows[i, : len(r)] = r
        LA[i] = len(r)
    mask = np.asarray(
        pmesh.sharded_rows_membership(mesh, jnp.asarray(rows), LA, Bd, n_big)
    )
    bigset = set(big.tolist())
    for i in range(4):
        for j in range(LA[i]):
            assert mask[i, j] == (int(rows[i, j]) in bigset)


@pytest.mark.skipif(len(jax.devices()) < 2, reason="needs multi-device mesh")
def test_dispatcher_routes_giant_b_through_mesh(monkeypatch):
    from dgraph_tpu.query import dispatch

    monkeypatch.setattr(dispatch, "_SHARD_MIN_B", 1 << 16)
    d = dispatch.SetOpDispatcher()
    rng = np.random.default_rng(1)
    big = np.unique(rng.integers(1, 1 << 24, 1 << 17, dtype=np.uint64))
    rows = [
        np.unique(
            np.concatenate(
                [
                    rng.choice(big, 50, replace=False),
                    rng.integers(1 << 24, 1 << 25, 50, dtype=np.uint64),
                ]
            )
        )
        for _ in range(3)
    ]
    got = d.run_rows_vs_one("intersect", rows, big)
    want = [np.intersect1d(r, big, assume_unique=True) for r in rows]
    for g, w in zip(got, want):
        np.testing.assert_array_equal(g, w)
    got = d.run_rows_vs_one("difference", rows, big)
    want = [np.setdiff1d(r, big, assume_unique=True) for r in rows]
    for g, w in zip(got, want):
        np.testing.assert_array_equal(g, w)
