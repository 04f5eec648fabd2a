"""Index-assisted and device top-k ordering (ref
worker/sort.go:189 sortWithIndex, :245 sortWithoutIndex).
"""

import numpy as np
import pytest

from dgraph_tpu.api.server import Server

SCHEMA = """
name: string @index(exact) .
age: int @index(int) .
score: float @index(float) .
"""


@pytest.fixture(scope="module")
def server():
    s = Server()
    s.alter(SCHEMA)
    t = s.new_txn()
    rdf = []
    # ages 1..60 shuffled across uids; floats with sub-integer parts to
    # exercise lossy-bucket tiebreaks (float indexes at int granularity)
    rng = np.random.default_rng(5)
    ages = rng.permutation(np.arange(1, 61))
    for i, age in enumerate(ages, start=1):
        rdf.append(f'<0x{i:x}> <name> "p{i}" .')
        rdf.append(f'<0x{i:x}> <age> "{age}"^^<xs:int> .')
        rdf.append(f'<0x{i:x}> <score> "{age + (i % 10) / 10.0}"^^<xs:float> .')
    # one uid with no age: must sink to the end
    rdf.append('<0xff> <name> "ageless" .')
    t.mutate_rdf(set_rdf="\n".join(rdf), commit_now=True)
    return s


def _ages(out):
    return [x["age"] for x in out["data"]["q"] if "age" in x]


def test_orderasc_int_index_walk(server):
    out = server.query('{ q(func: has(name), orderasc: age) { name age } }')
    ages = _ages(out)
    assert ages == sorted(ages) and len(ages) == 60
    # nodes missing the sort value sort AFTER every valued one (golden
    # TestNegativeOffset pins keep-missing-last for predicate sorts)
    assert out["data"]["q"][-1]["name"] == "ageless"
    assert len(out["data"]["q"]) == 61


def test_orderdesc_with_first_early_stop(server):
    out = server.query(
        '{ q(func: has(age), orderdesc: age, first: 5) { age } }'
    )
    assert _ages(out) == [60, 59, 58, 57, 56]


def test_order_offset_window(server):
    out = server.query(
        '{ q(func: has(age), orderasc: age, offset: 10, first: 3) { age } }'
    )
    assert _ages(out) == [11, 12, 13]


def test_lossy_float_bucket_inner_sort(server):
    out = server.query('{ q(func: has(age), orderasc: score) { score } }')
    scores = [x["score"] for x in out["data"]["q"]]
    assert scores == sorted(scores)


def test_multi_key_order_reads_its_keys_in_one_batch(server, monkeypatch):
    """The generic (multi-key) sort reads every uid's sort values through
    ONE batched read, not one memory-layer read a uid: per-uid reads
    take the layer's lock once each, and concurrent requests then fall
    into a lock convoy."""
    single, batched, in_batch = [], [], []
    real_read, real_many = server.mem.read, server.mem.read_many

    def read(kv, key, read_ts):
        # (a backend with no batch API serves read_many key by key)
        if not in_batch:
            single.append(key)
        return real_read(kv, key, read_ts)

    def read_many(kv, keys_list, read_ts):
        keys_list = list(keys_list)
        batched.extend(keys_list)
        in_batch.append(1)
        try:
            return real_many(kv, keys_list, read_ts)
        finally:
            in_batch.pop()

    monkeypatch.setattr(server.mem, "read", read)
    monkeypatch.setattr(server.mem, "read_many", read_many)
    q = "{ q(func: has(name), orderdesc: age, orderasc: name) { name age } }"
    rows = server.query(q)["data"]["q"]
    ages = [x["age"] for x in rows if "age" in x]
    assert ages == sorted(ages, reverse=True) and len(ages) == 60
    assert len(rows) == 61 and rows[-1]["name"] == "ageless"
    # the root read every `name` list already; the 61 `age` lists are
    # what the sort still has to read, and it reads them in the batch
    assert sum(b"age" in k for k in batched) == 61
    assert not any(b"age" in k for k in single), len(single)


def test_device_topk_val_var_first():
    s = Server()
    s.alter("name: string @index(exact) .\nrank: int @index(int) .")
    t = s.new_txn()
    n = 6000  # above the 4096 device-top-k threshold
    rng = np.random.default_rng(11)
    ranks = rng.permutation(n) + 1
    rdf = []
    for i in range(1, n + 1):
        rdf.append(f'<0x{i:x}> <name> "u{i}" .')
        rdf.append(f'<0x{i:x}> <rank> "{ranks[i-1]}"^^<xs:int> .')
    t.mutate_rdf(set_rdf="\n".join(rdf), commit_now=True)
    out = s.query(
        """{
          v as var(func: has(rank)) { r as rank }
          q(func: uid(v), orderdesc: val(r), first: 4) { rank }
        }"""
    )
    got = [x["rank"] for x in out["data"]["q"]]
    assert got == [n, n - 1, n - 2, n - 3]
    out = s.query(
        """{
          v as var(func: has(rank)) { r as rank }
          q(func: uid(v), orderasc: val(r), first: 3) { rank }
        }"""
    )
    assert [x["rank"] for x in out["data"]["q"]] == [1, 2, 3]
