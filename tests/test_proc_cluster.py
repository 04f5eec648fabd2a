"""Real multi-OS-process cluster tests.

Spawns alpha replicas as separate python processes (ref
dgraphtest/local_cluster.go): cross-process raft over TCP, RPC reads with
hedging, leader-routed proposals, process-kill fault injection, durable
restart.
"""

import time

import pytest

from dgraph_tpu.conn.rpc import RpcPool, RpcServer
from dgraph_tpu.worker.harness import ProcCluster


# ---------------------------------------------------------------------------
# RPC layer
# ---------------------------------------------------------------------------


def test_rpc_roundtrip_and_errors():
    srv = RpcServer().start()
    srv.register("echo", lambda a: {"got": a})
    srv.register("boom", lambda a: 1 / 0)
    pool = RpcPool(timeout=3.0)
    out = pool.call(srv.addr, "echo", {"x": 1, "b": b"\x00\xff"})
    assert out["got"]["x"] == 1 and bytes(out["got"]["b"]) == b"\x00\xff"
    from dgraph_tpu.conn.rpc import RpcError

    with pytest.raises(RpcError):
        pool.call(srv.addr, "boom")
    with pytest.raises(RpcError):
        pool.call(srv.addr, "nope")
    assert pool.healthy(srv.addr)
    srv.close()
    pool.close()


def test_rpc_pool_health_marks_dead_peer():
    srv = RpcServer().start()
    pool = RpcPool(timeout=0.3, heartbeat_s=0.1, max_misses=2)
    pool.call(srv.addr, "ping")
    addr = srv.addr
    srv.close()
    # drop the pooled socket: the listener is gone, reconnects must fail
    # (an established handler thread would otherwise keep answering)
    pool.get(addr).close_conn()
    from dgraph_tpu.conn.rpc import RpcError

    for _ in range(3):
        try:
            pool.call(addr, "ping", timeout=0.3)
        except RpcError:
            pass
    assert not pool.healthy(addr)
    pool.close()


# ---------------------------------------------------------------------------
# Process cluster
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def cluster():
    c = ProcCluster(n_groups=2, replicas=3)
    yield c
    c.close()


SCHEMA = "name: string @index(exact) .\nfollows: [uid] .\nage: int @index(int) ."


def test_proc_cluster_end_to_end(cluster):
    cluster.alter(SCHEMA)
    t = cluster.new_txn()
    t.mutate_rdf(
        set_rdf=(
            '<0x1> <name> "alice" .\n'
            '<0x2> <name> "bob" .\n'
            '<0x1> <age> "30"^^<xs:int> .\n'
            "<0x1> <follows> <0x2> .\n"
        ),
        commit_now=True,
    )
    out = cluster.query(
        '{ q(func: eq(name, "alice")) { name age follows { name } } }'
    )
    q = out["data"]["q"][0]
    assert q["name"] == "alice" and q["age"] == 30
    assert q["follows"][0]["name"] == "bob"


def test_proc_cluster_survives_follower_kill(cluster):
    g = cluster.remote_groups[1]
    leader = g.leader_addr()
    victim = None
    for nid, cfg in cluster._cfgs.items():
        addr = tuple(cfg["rpc_addr"])
        if cfg["group_id"] == 1 and addr != leader:
            victim = nid
            break
    cluster.kill(victim)
    t = cluster.new_txn()
    t.mutate_rdf(set_rdf='<0x3> <name> "carol" .', commit_now=True)
    out = cluster.query('{ q(func: eq(name, "carol")) { name } }')
    assert out["data"]["q"][0]["name"] == "carol"
    cluster.restart(victim)
    time.sleep(0.5)


def test_proc_cluster_survives_leader_kill(cluster):
    g = cluster.remote_groups[1]
    leader = g.leader_addr()
    victim = None
    for nid, cfg in cluster._cfgs.items():
        if tuple(cfg["rpc_addr"]) == tuple(leader):
            victim = nid
            break
    cluster.kill(victim)
    # remaining two re-elect; commits keep working
    t = cluster.new_txn()
    t.mutate_rdf(set_rdf='<0x4> <name> "dave" .', commit_now=True)
    out = cluster.query('{ q(func: eq(name, "dave")) { name } }')
    assert out["data"]["q"][0]["name"] == "dave"
    cluster.restart(victim)
    time.sleep(0.5)


def test_proc_cluster_durable_restart(tmp_path):
    d = str(tmp_path / "pc")
    c = ProcCluster(n_groups=1, replicas=3, data_dir=d)
    try:
        c.alter("name: string @index(exact) .")
        c.new_txn().mutate_rdf(set_rdf='<0x9> <name> "zoe" .', commit_now=True)
        out = c.query('{ q(func: eq(name, "zoe")) { name } }')
        assert out["data"]["q"][0]["name"] == "zoe"
        # kill ALL replicas, respawn from disk
        for nid in list(c.procs):
            c.kill(nid)
        for nid in list(c.procs):
            c._spawn(nid)
        c._wait_healthy()
        out = c.query('{ q(func: eq(name, "zoe")) { name } }')
        assert out["data"]["q"][0]["name"] == "zoe"
    finally:
        c.close()


def test_proc_cluster_with_zero_quorum_processes(tmp_path):
    """Full cross-process topology: alphas AND the Zero quorum as OS
    processes (ref dgraph/cmd/zero run.go); leases/commits/tablets via
    zero.exec RPC; zero-leader kill tolerated."""
    c = ProcCluster(
        n_groups=1, replicas=3, replicated_zero=True, zero_replicas=3
    )
    try:
        c.alter("name: string @index(exact) .")
        t = c.new_txn()
        t.mutate_rdf(set_rdf='<0x1> <name> "zq-alice" .', commit_now=True)
        out = c.query('{ q(func: eq(name, "zq-alice")) { name } }')
        assert out["data"]["q"][0]["name"] == "zq-alice"
        # tablets decided by the zero quorum
        assert c.zero.belongs_to("name") == 1
        # kill the zero leader process: remaining two re-elect
        lead_addr = c.zero.zero._leader
        victim = next(
            nid
            for nid, cfg in c._cfgs.items()
            if cfg.get("_module", "").endswith("zero_process")
            and tuple(cfg["rpc_addr"]) == tuple(lead_addr)
        )
        c.kill(victim)
        t2 = c.new_txn()
        t2.mutate_rdf(set_rdf='<0x2> <name> "zq-bob" .', commit_now=True)
        out = c.query('{ q(func: eq(name, "zq-bob")) { name } }')
        assert out["data"]["q"][0]["name"] == "zq-bob"
    finally:
        c.close()


def test_proc_cluster_move_recovery_at_each_journaled_phase(cluster):
    """Coordinator death mid-move at every journaled phase: the move
    journal + recover_moves() resolve to exactly-once placement (the
    in-process analog restarts the whole cluster; here the same
    coordinator recovers after a simulated crash at the boundary)."""
    import pytest as _pytest

    from dgraph_tpu.conn import faults
    from dgraph_tpu.conn.faults import FaultPlan, InjectedCrash

    cluster.alter("crashy: string @index(exact) .")
    cluster.new_txn().mutate_rdf(
        set_rdf="\n".join(
            f'<0x{i:x}> <crashy> "c{i}" .' for i in range(0x80, 0x8c)
        ),
        commit_now=True,
    )
    try:
        for point in (
            "move.begin", "move.copy", "move.fence",
            "move.delta", "move.flip", "move.drop",
        ):
            src = cluster.zero.belongs_to("crashy")
            dst = next(g for g in cluster.remote_groups if g != src)
            faults.install(FaultPlan(seed=5, rules=[
                dict(point=point, action="crash", p=1.0, max=1)
            ]))
            with _pytest.raises(InjectedCrash):
                cluster.move_tablet("crashy", dst)
            faults.reset()
            assert cluster.zero.moves(), point  # journal survived
            cluster.recover_moves()
            assert cluster.zero.moves() == {}, point
            where = cluster.zero.belongs_to("crashy")
            # copy/fence phases roll back; post-flip phases roll forward
            assert where == (
                dst if point in ("move.flip", "move.drop") else src
            ), point
            out = cluster.query("{ q(func: has(crashy)) { uid } }")
            assert len(out["data"]["q"]) == 12, point
            out = cluster.query('{ q(func: eq(crashy, "c130")) { crashy } }')
            assert out["data"]["q"] == [{"crashy": "c130"}], point
    finally:
        faults.reset()


def test_proc_cluster_chunked_move_larger_than_frame_chunk(
    cluster, monkeypatch
):
    """A tablet bigger than one chunk streams in multiple bounded
    ('delta', chunk) proposals and paged source reads — the old mover
    shipped ONE proposal and hard-failed at the frame cap."""
    from dgraph_tpu.utils.observe import METRICS

    monkeypatch.setenv("DGRAPH_TPU_MOVE_CHUNK_BYTES", "4096")
    cluster.alter("bigmv: string @index(exact) .")
    pad = "y" * 180
    cluster.new_txn().mutate_rdf(
        set_rdf="\n".join(
            f'<0x{0x900 + i:x}> <bigmv> "b{i}{pad}" .' for i in range(120)
        ),
        commit_now=True,
    )
    src = cluster.zero.belongs_to("bigmv")
    dst = next(g for g in cluster.remote_groups if g != src)
    chunks0 = METRICS.value("tablet_move_chunks_total")
    assert cluster.move_tablet("bigmv", dst) is True
    assert METRICS.value("tablet_move_chunks_total") >= chunks0 + 3
    assert cluster.zero.belongs_to("bigmv") == dst
    out = cluster.query("{ q(func: has(bigmv)) { uid } }")
    assert len(out["data"]["q"]) == 120
    out = cluster.query(f'{{ q(func: eq(bigmv, "b7{pad}")) {{ uid }} }}')
    assert len(out["data"]["q"]) == 1


def test_proc_cluster_predicate_move(cluster):
    """Cross-process tablet move: stream out of the source group's
    replicas, raft-propose into the destination, flip, drop
    (ref worker/predicate_move.go)."""
    cluster.alter("movable: string @index(exact) .")
    t = cluster.new_txn()
    t.mutate_rdf(
        set_rdf="\n".join(
            f'<0x{i:x}> <movable> "m{i}" .' for i in range(0x60, 0x70)
        ),
        commit_now=True,
    )
    src = cluster.zero.belongs_to("movable")
    dst = next(g for g in cluster.remote_groups if g != src)
    cluster.move_tablet("movable", dst)
    assert cluster.zero.belongs_to("movable") == dst
    out = cluster.query('{ q(func: eq(movable, "m97")) { movable } }')
    assert out["data"]["q"][0]["movable"] == "m97"
    out = cluster.query("{ q(func: has(movable)) { uid } }")
    assert len(out["data"]["q"]) == 16
    # and writes keep landing on the new owner
    cluster.new_txn().mutate_rdf(
        set_rdf='<0x70> <movable> "m112" .', commit_now=True
    )
    out = cluster.query('{ q(func: eq(movable, "m112")) { movable } }')
    assert out["data"]["q"][0]["movable"] == "m112"
