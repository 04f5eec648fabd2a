"""Durable raft + cluster recovery.

- raft WAL: hardstate/log persist before responses; restart-safe votes
- snapshot/compaction: snap_req catch-up for lagging peers, truncated log
- kill-all cluster restart recovering all committed data
- commit-intent journal replay (no FATAL partial commits)
"""

import os

import numpy as np
import pytest

from dgraph_tpu.raft.raft import LEADER, RaftCluster, RaftNode, InProcNetwork
from dgraph_tpu.raft.wal import RaftWal
from dgraph_tpu.worker.groups import DistributedCluster, IntentLog


# ---------------------------------------------------------------------------
# RaftWal unit behavior
# ---------------------------------------------------------------------------


def test_raft_wal_roundtrip(tmp_path):
    w = RaftWal(str(tmp_path / "n1"))
    w.save_hard(3, 2, 0, 0)
    w.append_entry(1, ("delta", [1, 2]))
    w.append_entry(2, ("delta", [3]))
    w.truncate_from(2)
    w.append_entry(3, ("delta", [4]))
    w.flush()
    w.close()
    w2 = RaftWal(str(tmp_path / "n1"))
    assert w2.load_hard() == (3, 2, 0, 0)
    si, st, entries = w2.replay_log()
    assert (si, st) == (0, 0)
    assert entries == [(1, ("delta", [1, 2])), (3, ("delta", [4]))]


def test_raft_wal_compaction_rewrite(tmp_path):
    w = RaftWal(str(tmp_path / "n2"))
    for i in range(10):
        w.append_entry(1, i)
    w.flush()
    w.rewrite_log(7, 1, [(1, 7), (1, 8), (1, 9)])
    si, st, entries = w.replay_log()
    assert si == 7 and st == 1
    assert [d for _, d in entries] == [7, 8, 9]
    w.save_snapshot(b"snapdata")
    assert w.load_snapshot() == b"snapdata"


def test_raft_wal_torn_tail(tmp_path):
    w = RaftWal(str(tmp_path / "n3"))
    w.append_entry(1, "a")
    w.flush()
    w.close()
    with open(str(tmp_path / "n3" / "log.wal"), "ab") as f:
        f.write(b"\x01\x99")  # torn record
    w2 = RaftWal(str(tmp_path / "n3"))
    _, _, entries = w2.replay_log()
    assert entries == [(1, "a")]


# ---------------------------------------------------------------------------
# Raft node durability + snapshots
# ---------------------------------------------------------------------------


def test_raft_restart_remembers_vote_and_log(tmp_path):
    # durable cluster: one WAL dir per node
    net = InProcNetwork()
    applied = {i: [] for i in (1, 2, 3)}

    def mk(i):
        net.register(i)
        return RaftNode(
            i, [1, 2, 3], net,
            lambda idx, d, _i=i: applied[_i].append(d),
            seed=i,
            wal=RaftWal(str(tmp_path / f"r{i}")),
        )

    nodes = {i: mk(i) for i in (1, 2, 3)}
    now = 0
    while not any(n.is_leader() for n in nodes.values()):
        now += 10
        for n in nodes.values():
            n.tick(now)
    leader = next(n for n in nodes.values() if n.is_leader())
    assert leader.propose(("w", 1))
    for _ in range(30):
        now += 10
        for n in nodes.values():
            n.tick(now)
    assert all(("w", 1) in a for a in applied.values())

    # "crash" node 2 and restart from its WAL: term/vote/log survive
    n2 = nodes[2]
    term_before, log_before = n2.term, [e.data for e in n2.log]
    n2.wal.close()
    net2 = InProcNetwork()
    net2.register(2)
    restarted = RaftNode(
        2, [1, 2, 3], net2, lambda idx, d: None, seed=2,
        wal=RaftWal(str(tmp_path / "r2")),
    )
    assert restarted.term == term_before
    assert [e.data for e in restarted.log] == log_before


def test_snapshot_compaction_and_lagging_catchup(tmp_path):
    kvs = {i: [] for i in (1, 2, 3)}

    def cbs(i):
        def apply(idx, d):
            kvs[i].append(d)

        return apply

    c = RaftCluster(
        3,
        apply_cbs=[cbs(1), cbs(2), cbs(3)],
    )
    # wire snapshot callbacks manually (state machine = applied list)
    import pickle

    def mk_restore(i):
        def restore(data, idx):
            kvs[i].clear()
            kvs[i].extend(pickle.loads(data))

        return restore

    for i, nd in c.nodes.items():
        nd.snapshot_cb = lambda _i=i: pickle.dumps(kvs[_i])
        nd.restore_cb = mk_restore(i)

    leader = c.elect()
    # partition node 3 away, write a bunch, compact
    dead = [i for i in c.nodes if i != leader.id][0]
    c.net.down.add(dead)
    for k in range(20):
        assert leader.propose(("set", k))
        c.pump(10, 5)
    assert c.run_until(lambda: leader.last_applied >= 20)
    leader.take_snapshot()
    assert leader.snap_index >= 20
    assert len(leader.log) <= 1
    # node 3 rejoins: needs the compacted entries -> snapshot install
    c.net.down.discard(dead)
    assert c.run_until(lambda: c.nodes[dead].snap_index >= 20, max_ms=30_000)
    assert c.run_until(lambda: kvs[dead] == kvs[leader.id], max_ms=30_000)
    # and replication continues past the snapshot
    assert leader.propose(("set", 99))
    assert c.run_until(lambda: ("set", 99) in kvs[dead])


# ---------------------------------------------------------------------------
# Durable distributed cluster
# ---------------------------------------------------------------------------


def _query_names(cluster, uid):
    out = cluster.query("{ q(func: uid(%s)) { name } }" % hex(uid))
    return [x.get("name") for x in out["data"]["q"]]


def test_cluster_kill_all_restart_recovers(tmp_path):
    d = str(tmp_path / "cluster")
    c = DistributedCluster(n_groups=2, replicas=3, data_dir=d)
    c.alter("name: string @index(exact) .\nfollows: [uid] .")
    t = c.new_txn()
    t.mutate_rdf(
        set_rdf='<0x1> <name> "alice" .\n<0x2> <name> "bob" .\n'
        "<0x1> <follows> <0x2> .",
        commit_now=True,
    )
    before = c.query('{ q(func: eq(name, "alice")) { name follows { name } } }')
    c.close()

    # full restart from disk
    c2 = DistributedCluster(n_groups=2, replicas=3, data_dir=d)
    after = c2.query('{ q(func: eq(name, "alice")) { name follows { name } } }')
    assert after == before
    assert after["data"]["q"][0]["follows"][0]["name"] == "bob"
    # leases recovered: new uids/ts don't collide
    t2 = c2.new_txn()
    uids = t2.mutate_rdf(set_rdf='_:x <name> "carol" .', commit_now=True)
    out = c2.query('{ q(func: eq(name, "carol")) { name } }')
    assert out["data"]["q"][0]["name"] == "carol"
    c2.close()


def test_intent_log_replay(tmp_path):
    path = str(tmp_path / "intents.log")
    il = IntentLog(path)
    il.append_intent(10, {1: [(b"k1", 10, b"v")], 2: [(b"k2", 10, b"v")]})
    il.append_intent(11, {1: [(b"k3", 11, b"v")]})
    il.mark_done(10)
    il.close()
    il2 = IntentLog(path)
    pending = il2.pending()
    assert list(pending) == [11]
    assert pending[11] == {1: [(b"k3", 11, b"v")]}
    il2.close()


def test_cluster_completes_interrupted_commit_on_restart(tmp_path):
    """Simulate a crash after journaling the intent but before any group
    applied: restart must complete the commit."""
    d = str(tmp_path / "c2")
    c = DistributedCluster(n_groups=2, replicas=3, data_dir=d)
    c.alter("name: string @index(exact) .")
    # forge an interrupted commit: journal an intent by hand
    from dgraph_tpu.posting.pl import OP_SET, Posting, encode_delta
    from dgraph_tpu.x import keys as xkeys

    c.zero.should_serve("name")
    gid = c.zero.belongs_to("name")
    cts = c.zero.zero.next_ts(5) + 4
    key = xkeys.DataKey("name", 0x77)
    from dgraph_tpu.types.types import TypeID, Val, to_binary

    rec = encode_delta(
        [
            Posting(
                uid=(1 << 64) - 1,
                op=OP_SET,
                value=to_binary(Val(TypeID.STRING, "ghost")),
                value_type=TypeID.STRING,
            )
        ]
    )
    c.intents.append_intent(cts, {gid: [(key, cts, rec)]})
    c.close()

    c2 = DistributedCluster(n_groups=2, replicas=3, data_dir=d)
    got = c2.query("{ q(func: uid(0x77)) { name } }")
    assert got["data"]["q"][0]["name"] == "ghost"
    # intent is now done: no pending left
    assert c2.intents.pending() == {}
    c2.close()


def test_cluster_compaction_in_engine(tmp_path):
    d = str(tmp_path / "c3")
    c = DistributedCluster(n_groups=1, replicas=3, data_dir=d, compact_every=5)
    c.alter("name: string @index(exact) .")
    for i in range(12):
        c.new_txn().mutate_rdf(
            set_rdf=f'<0x{i+1:x}> <name> "n{i}" .', commit_now=True
        )
    # leader compacted: log window bounded
    import time

    deadline = time.time() + 10
    while time.time() < deadline:
        lead = c.groups[1].leader()
        if lead is not None and lead.raft.snap_index > 0:
            break
        time.sleep(0.05)
    lead = c.groups[1].leader()
    assert lead.raft.snap_index > 0
    assert len(lead.raft.log) < 12
    out = c.query('{ q(func: eq(name, "n11")) { name } }')
    assert out["data"]["q"][0]["name"] == "n11"
    c.close()
