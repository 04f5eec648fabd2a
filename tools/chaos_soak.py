#!/usr/bin/env python
"""Cluster chaos soak for the resilient read plane.

Runs a SEEDED randomized fault schedule against a real multi-OS-process
ProcCluster under the bank + query mix and asserts the three promises
the read plane makes:

  correctness    every response sampled from the default (follower-
                 routed) path is byte-identical to a leader-routed
                 control replay of the same query at the same pinned
                 read_ts (DGRAPH_TPU_FOLLOWER_READS=0), and the bank
                 ledger is exact — sum conserved always, per-account
                 equality when no transfer ack was ambiguous.

  availability   with the group leader SIGKILLed mid-workload,
                 watermark reads keep answering (served by verified
                 followers during the leaderless window); the gap until
                 the first successful read is measured and bounded.

  honesty        nothing surfaces as a non-retryable error: every
                 failure seen by the driver is a timeout, a retryable
                 RPC error, or a degraded-but-correct response.

Fault phases (long mode): baseline, leader SIGKILL + respawn, an
asymmetric partition (coordinator->follower blocked, raft plane up),
a delay-lagged follower (the EWMA routes around it), and a live tablet
move under traffic. Sanity mode trims to baseline + leader kill +
recovery and finishes in seconds — tier-1 and `tools/check.sh
--read-chaos-sanity` run exactly that slice.

    python tools/chaos_soak.py --sanity          # fixed-seed CI slice
    python tools/chaos_soak.py --long            # full schedule

Every per-phase row carries the follower-read / breaker / retry-budget
counters, so a regression in routing shows up as a counter delta even
when the asserts still pass.
"""

import argparse
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

os.environ.setdefault("JAX_PLATFORMS", "cpu")

from dgraph_tpu.conn import faults  # noqa: E402
from dgraph_tpu.conn.faults import FaultPlan  # noqa: E402
from dgraph_tpu.utils.observe import METRICS  # noqa: E402

N_ACCOUNTS = 8
START_BAL = 100

# the counters every phase row reports (acceptance: follower-read /
# breaker / retry-budget counters in every row)
ROW_COUNTERS = (
    "follower_reads_total",
    "leaderless_reads_total",
    "follower_read_stale_skips_total",
    "read_breaker_open_total",
    "read_breaker_close_total",
    "read_breaker_probe_total",
    "read_retry_budget_exhausted_total",
    "hedge_fired_total",
    "hedge_skipped_saturated_total",
    "degraded_queries_total",
)

RETRYABLE = (TimeoutError,)


def _retryable(exc) -> bool:
    """The honesty gate: an error the driver sees must be one a client
    is allowed to retry."""
    from dgraph_tpu.conn.rpc import RpcError

    if isinstance(exc, RETRYABLE):
        return True
    if getattr(exc, "retryable", False):
        return True
    # group-unavailable / exhausted-rotation reads are retryable by
    # contract: the response would have been degraded, never wrong
    return isinstance(exc, RpcError)


def _counters():
    return {k: int(METRICS.value(k)) for k in ROW_COUNTERS}


def _delta(before, after):
    return {k: after[k] - before[k] for k in before}


class Soak:
    def __init__(self, seed: int, sanity: bool):
        import numpy as np

        from dgraph_tpu.worker.harness import ProcCluster

        self.seed = seed
        self.sanity = sanity
        self.rng = np.random.default_rng(seed)
        self.n_groups = 1 if sanity else 2
        self.cluster = ProcCluster(
            n_groups=self.n_groups, replicas=3,
            replicated_zero=False,
        )
        self.ledger = {}
        self.ambiguous = 0
        self.transfers_ok = 0
        self.queries_ok = 0
        self.queries_degraded = 0
        self.queries_failed = 0
        self.identity_checked = 0
        self.deferred = []  # (query, ts, baseline_bytes) awaiting control
        self.rows = []
        self.failures = []

    # -- workload ---------------------------------------------------------

    def seed_data(self):
        c = self.cluster
        c.alter(
            "bal: int @upsert .\n"
            "acct: string @index(exact) @upsert .\n"
            "mv: string @index(exact) ."
        )
        rdf = []
        for i in range(1, N_ACCOUNTS + 1):
            rdf.append(f'<0x{i:x}> <acct> "a{i}" .')
            rdf.append(f'<0x{i:x}> <bal> "{START_BAL}"^^<xs:int> .')
            rdf.append(f'<0x{i:x}> <mv> "m{i}" .')
        c.new_txn().mutate_rdf(set_rdf="\n".join(rdf), commit_now=True)
        self.ledger = {i: START_BAL for i in range(1, N_ACCOUNTS + 1)}

    def transfer(self):
        frm, to = (
            int(x) + 1
            for x in self.rng.choice(N_ACCOUNTS, 2, replace=False)
        )
        amt = int(self.rng.integers(1, 20))
        t = self.cluster.new_txn()
        try:
            t.mutate_rdf(
                set_rdf=(
                    f'<0x{frm:x}> <bal> "{self.ledger[frm] - amt}"'
                    f"^^<xs:int> .\n"
                    f'<0x{to:x}> <bal> "{self.ledger[to] + amt}"'
                    f"^^<xs:int> ."
                ),
                commit_now=True,
            )
            self.ledger[frm] -= amt
            self.ledger[to] += amt
            self.transfers_ok += 1
        except Exception as e:
            if not _retryable(e):
                self.failures.append(
                    f"non-retryable transfer error: {type(e).__name__}: {e}"
                )
            self.ambiguous += 1  # may or may not have applied

    QUERIES = (
        "{ q(func: has(bal)) { uid bal } }",
        '{ q(func: eq(acct, "a3")) { acct bal } }',
        "{ q(func: has(mv)) { uid mv } }",
    )

    def query_once(self, identity: bool, timeout_s: float = 8.0):
        """One read at the pinned snapshot watermark. With `identity`,
        the response is also queued for a leader-routed control replay
        at the SAME ts (byte-identity proof obligation)."""
        c = self.cluster
        q = self.QUERIES[int(self.rng.integers(0, len(self.QUERIES)))]
        wm = c._snapshot_ts
        try:
            out = c.query(q, read_ts=wm, timeout_s=timeout_s)
        except Exception as e:
            if not _retryable(e):
                self.failures.append(
                    f"non-retryable query error: {type(e).__name__}: {e}"
                )
            self.queries_failed += 1
            return None
        ext = out.get("extensions", {})
        if ext.get("degraded"):
            self.queries_degraded += 1
            # degraded=True means PARTIAL (unreachable group) — never
            # identity-check those; "leaderless" responses are complete
            # and must pass the identity check like any other
            if ext["degraded"] is True:
                return out
        self.queries_ok += 1
        if identity:
            blob = json.dumps(out["data"], sort_keys=True)
            self.deferred.append((q, wm, blob))
        return out

    def replay_controls(self):
        """Leader-routed control replay of every deferred sample: same
        query, same pinned ts, FOLLOWER_READS off — the bytes must
        match what the default path served earlier. Run while the
        cluster is healthy (controls need a leader)."""
        c = self.cluster
        pending, self.deferred = self.deferred, []
        os.environ["DGRAPH_TPU_FOLLOWER_READS"] = "0"
        try:
            for q, ts, blob in pending:
                control = c.query(q, read_ts=ts, timeout_s=15.0)
                cblob = json.dumps(control["data"], sort_keys=True)
                if cblob != blob:
                    self.failures.append(
                        f"BYTE MISMATCH at ts={ts} for {q!r}:\n"
                        f"  default: {blob[:400]}\n"
                        f"  control: {cblob[:400]}"
                    )
                self.identity_checked += 1
        finally:
            os.environ["DGRAPH_TPU_FOLLOWER_READS"] = "1"

    def check_ledger(self):
        out = self.cluster.query("{ q(func: has(bal)) { uid bal } }",
                                 timeout_s=20.0)
        ext = out.get("extensions", {})
        if ext.get("degraded") is True:
            return  # partial view: sum check would be vacuous
        bals = {int(x["uid"], 16): x["bal"] for x in out["data"]["q"]}
        total = sum(bals.values())
        if total != N_ACCOUNTS * START_BAL:
            self.failures.append(
                f"LEDGER SUM BROKEN: {total} != {N_ACCOUNTS * START_BAL} "
                f"({bals})"
            )
        if self.ambiguous == 0 and bals != self.ledger:
            self.failures.append(
                f"LEDGER DRIFT with zero ambiguous acks: "
                f"{bals} != {self.ledger}"
            )

    # -- phases -----------------------------------------------------------

    def run_phase(self, name, steps, setup=None, teardown=None,
                  extra=None):
        t0 = time.perf_counter()
        before = _counters()
        info = {}
        if setup is not None:
            info.update(setup() or {})
        try:
            for step in range(steps):
                self.transfer()
                self.query_once(identity=(step % 2 == 0))
                if step % 5 == 4:
                    self.check_ledger()
        finally:
            if teardown is not None:
                info.update(teardown() or {})
        if extra is not None:
            info.update(extra() or {})
        row = {
            "phase": name,
            "steps": steps,
            "wall_s": round(time.perf_counter() - t0, 3),
            "counters": _delta(before, _counters()),
            **info,
        }
        self.rows.append(row)
        print(f"  [{name}] {json.dumps(row['counters'])}", flush=True)
        return row

    def _group1_leader_nid(self):
        c = self.cluster
        g = c.remote_groups[1]
        lead = g.leader_addr(timeout=10.0)
        if lead is None:
            return None
        for nid, cfg in c._cfgs.items():
            if tuple(cfg["rpc_addr"]) == tuple(lead):
                return nid
        return None

    def phase_leader_kill(self, steps):
        """SIGKILL group 1's leader mid-workload; watermark reads must
        keep answering from verified followers, and the first-success
        gap is bounded (breaker probe + discovery, plus CI slack)."""
        c = self.cluster
        killed = {"nid": None, "gap_s": None}

        def setup():
            nid = self._group1_leader_nid()
            assert nid is not None, "no leader to kill"
            # quiesce writes briefly: leader heartbeats carry the commit
            # index, so after ~2 rounds the followers have APPLIED the
            # floor and a health sweep proves it — only then can the
            # election window itself be follower-served
            time.sleep(0.7)
            self.query_once(identity=False)  # warms picker health rows
            c.kill(nid)
            killed["nid"] = nid
            # availability gap: time to the first successful read after
            # the kill (leaderless window included — followers serve)
            t0 = time.perf_counter()
            deadline = t0 + 30.0
            while time.perf_counter() < deadline:
                out = self.query_once(identity=False, timeout_s=5.0)
                if out is not None:
                    killed["gap_s"] = round(time.perf_counter() - t0, 3)
                    break
            if killed["gap_s"] is None:
                self.failures.append(
                    "reads never recovered within 30s of leader SIGKILL"
                )
            return {"killed_nid": killed["nid"]}

        def teardown():
            c.restart(killed["nid"])
            c._wait_healthy(timeout=90.0)
            return {"availability_gap_s": killed["gap_s"]}

        row = self.run_phase("leader_kill", steps, setup, teardown)
        # correctness obligation: the window actually exercised the
        # follower path (otherwise this phase proved nothing)
        served = (row["counters"]["follower_reads_total"]
                  + row["counters"]["leaderless_reads_total"])
        if served <= 0:
            self.failures.append(
                "leader_kill phase served no follower/leaderless reads "
                f"— counters: {row['counters']}"
            )
        return row

    def phase_asym_partition(self, steps):
        """Block coordinator->follower traffic for ONE follower of
        group 1 (its raft plane stays up, so it keeps applying). The
        breaker must open and route reads around it."""
        c = self.cluster
        g = c.remote_groups[1]
        state = {}

        def setup():
            lead = g.leader_addr(timeout=10.0)
            followers = [a for a in g.addrs if a != lead]
            victim = followers[0]
            plan = faults.active() or faults.install(
                FaultPlan(seed=self.seed)
            )
            plan.partition(victim, direction="to")
            state["victim"] = victim
            return {"partitioned": f"{victim[0]}:{victim[1]}"}

        def teardown():
            plan = faults.active()
            if plan is not None:
                plan.heal()
            return {}

        return self.run_phase("asym_partition", steps, setup, teardown)

    def phase_lagged_follower(self, steps):
        """Delay every RPC to one follower of group 1 by ~40ms: the
        latency EWMA must steer reads to the healthy replicas (the
        hedge pays the lag at most once per plan)."""
        c = self.cluster
        g = c.remote_groups[1]

        def setup():
            lead = g.leader_addr(timeout=10.0)
            followers = [a for a in g.addrs if a != lead]
            victim = followers[-1]
            faults.reset()
            faults.install(FaultPlan(seed=self.seed + 1, rules=[
                dict(point="send", action="delay", p=1.0, delay_ms=40,
                     peer=victim),
            ]))
            return {"lagged": f"{victim[0]}:{victim[1]}"}

        def teardown():
            faults.reset()
            return {}

        return self.run_phase("lagged_follower", steps, setup, teardown)

    def phase_live_move(self, steps):
        """Move the `mv` tablet to the other group mid-workload: the
        copy/delta stream is leader-only by contract; queries keep
        answering through the fence + flip."""
        c = self.cluster
        src = c.zero.belongs_to("mv")
        dst = 2 if src == 1 else 1
        state = {}

        def setup():
            import threading

            def mover():
                try:
                    c.move_tablet("mv", dst)
                    state["moved"] = True
                except Exception as e:
                    state["move_error"] = f"{type(e).__name__}: {e}"

            th = threading.Thread(target=mover, daemon=True)
            th.start()
            state["thread"] = th
            return {"move": f"mv: g{src} -> g{dst}"}

        def teardown():
            state["thread"].join(timeout=60.0)
            if state["thread"].is_alive():
                self.failures.append("tablet move hung past 60s")
            elif "move_error" in state:
                self.failures.append(
                    f"tablet move failed: {state['move_error']}"
                )
            elif c.zero.belongs_to("mv") != dst:
                self.failures.append("tablet map never flipped to dst")
            return {"move_done": state.get("moved", False)}

        return self.run_phase("live_move", steps, setup, teardown)

    # -- driver -----------------------------------------------------------

    def run(self):
        c = self.cluster
        try:
            self.seed_data()
            base_steps = 6 if self.sanity else 25
            self.run_phase("baseline", base_steps)
            self.replay_controls()

            self.phase_leader_kill(4 if self.sanity else 20)
            self.replay_controls()  # healthy again: controls valid now

            if not self.sanity:
                self.phase_asym_partition(20)
                self.replay_controls()
                self.phase_lagged_follower(20)
                self.replay_controls()
                self.phase_live_move(25)
                self.replay_controls()

            self.run_phase("recovery", 4 if self.sanity else 10)
            self.replay_controls()
            self.check_ledger()
        finally:
            faults.reset()
            c.close()
        if self.identity_checked == 0:
            self.failures.append("identity check never ran")
        return {
            "seed": self.seed,
            "mode": "sanity" if self.sanity else "long",
            "groups": self.n_groups,
            "replicas": 3,
            "phases": self.rows,
            "transfers_ok": self.transfers_ok,
            "transfers_ambiguous": self.ambiguous,
            "queries_ok": self.queries_ok,
            "queries_degraded": self.queries_degraded,
            "queries_failed": self.queries_failed,
            "identity_checked": self.identity_checked,
            "failures": self.failures,
        }


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--sanity", action="store_true",
                    help="short fixed-seed slice (tier-1 / check.sh)")
    ap.add_argument("--long", action="store_true",
                    help="full schedule")
    ap.add_argument("--seed", type=int, default=1234)
    args = ap.parse_args()
    if not (args.sanity or args.long):
        args.sanity = True

    # the soak drives follower routing explicitly; pin the knobs so the
    # run is self-describing regardless of ambient env
    os.environ["DGRAPH_TPU_FOLLOWER_READS"] = "1"

    t0 = time.perf_counter()
    result = Soak(args.seed, sanity=args.sanity).run()
    result["wall_s"] = round(time.perf_counter() - t0, 2)

    print(json.dumps(
        {k: v for k, v in result.items() if k != "phases"}, indent=2
    ))
    if result["failures"]:
        print("chaos_soak: FAILURES:", file=sys.stderr)
        for f in result["failures"]:
            print(f"  - {f}", file=sys.stderr)
        return 1
    print(
        f"chaos_soak: PASS ({result['mode']}, "
        f"{result['identity_checked']} identity checks, "
        f"{result['queries_ok']} queries, "
        f"{result['transfers_ok']} transfers, "
        f"{result['wall_s']}s)"
    )
    return 0


if __name__ == "__main__":
    sys.exit(main())
