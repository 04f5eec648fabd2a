"""The load generator: one child process, one thread per closed-loop
client, the bundled `DgraphClient` over the socket. It never initialises
a jax backend (the parent starts it with JAX_PLATFORMS=cpu and nothing
here imports jax), so it shares neither the chip nor the alpha's GIL.

A query kind that sets `WRITES = True` writes: its `request` also takes
the client's number and the draw's sequence number (`Client.draw`), and
its text is {"set": n-quads, "delete": n-quads (optional)}, sent as ONE
transaction committed at once (`ClientTxn.mutate(.., commit_now=True)`).
A 409 (`RetriableError`: the transaction aborted on a conflict) is sent
again with the same text, at most WRITE_RETRIES times; the record counts
`retries`, and keeps `commit_ts`, the server's `uids` and `attempt_sent`
(when the attempt that committed went out) for the history the reads are
judged against (`chipbench/history.py`). The committed writes of the
warm-up phases are kept and handed back with the window's records.

Commands arrive as JSON lines on stdin, replies leave as JSON lines on
stdout:
  first line   the spec: url, config, mix, seed
  {"cmd": "warm"}                      one request per client, concurrently
  {"cmd": "cover"}                     warm-up requests for every shape
                                       class the window's planned requests
                                       have and no warm-up request had yet
  {"cmd": "run", "seconds": s, "out": path}
                                       the window; its records and the
                                       warm-up's committed writes are
                                       pickled to `path`
  {"cmd": "stop"}
"""

from __future__ import annotations

import importlib
import json
import pickle
import sys
import threading
import time

import numpy as np

from chipbench import draw

REQUEST_TIMEOUT_S = 120
WRITE_RETRIES = 3  # a write that aborts on a conflict is sent again


def load_kinds(mix: dict):
    kinds = [importlib.import_module(f"chipbench.queries.{k['kind']}")
             for k in mix["kinds"]]
    weights = np.array([k["weight"] for k in mix["kinds"]], np.float64)
    return kinds, weights / weights.sum()


class Client:
    """One closed-loop client: its request sequence is a function of the
    seed, the phase and its number alone."""

    def __init__(self, spec: dict, number: int, catalog: dict, kinds, weights):
        from dgraph_tpu.client import DgraphClient

        self.mix = spec["mix"]
        self.number = number
        self.catalog, self.kinds, self.weights = catalog, kinds, weights
        self.conn = DgraphClient(spec["url"], timeout=REQUEST_TIMEOUT_S)
        self.streams = {phase: draw.stream(spec["seed"], phase, number)
                        for phase in (0, 1)}
        self.drawn = {0: 0, 1: 0}
        # the window's first requests, drawn ahead so that warm-up can
        # see which shape classes they have
        self.planned = [self.draw(1)
                        for _ in range(self.mix.get("lookahead", 0))]
        self.covered = set()

    def draw(self, phase: int):
        """The stream's next request: (kind index, key, text). A writing
        kind is also told the client's number and the draw's sequence
        number, 2n + phase for the phase's n-th draw: together they name
        one draw of a run, the same one under the same seed, so what a
        write creates can carry ids no other write of the run has."""
        rng = self.streams[phase]
        seq = 2 * self.drawn[phase] + phase
        self.drawn[phase] += 1
        ki = int(rng.choice(len(self.kinds), p=self.weights))
        kind, params = self.kinds[ki], self.mix["kinds"][ki]["params"]
        if getattr(kind, "WRITES", False):
            key, text = kind.request(self.catalog, params, rng,
                                     self.number, seq)
        else:
            key, text = kind.request(self.catalog, params, rng)
        return ki, key, text

    def shape(self, req):
        """The request's shape class: requests of one class drive the
        same compiled programs (None where the kind knows no classes)."""
        ki, key, _ = req
        kind = self.kinds[ki]
        if not hasattr(kind, "shape"):
            return None
        return ki, kind.shape(self.catalog, self.mix["kinds"][ki]["params"],
                              key)

    def send(self, req) -> dict:
        """Send one request and wait for its answer."""
        ki, key, text = req
        kind = self.kinds[ki]
        rec = {"client": self.number, "kind": ki, "key": key,
               "sent": time.perf_counter(), "answer": None, "error": None}
        try:
            if getattr(kind, "WRITES", False):
                self.write(kind, text, rec)
            else:
                rec["answer"] = kind.parse(self.conn.query(text))
        except Exception as e:  # the boundary: any failure is a failed request
            rec["error"] = f"{type(e).__name__}: {e}"[:300]
        rec["done"] = time.perf_counter()
        return rec

    def write(self, kind, text: dict, rec: dict) -> None:
        """One transaction, committed at once; an abort is sent again."""
        from dgraph_tpu.client import RetriableError

        rec["retries"] = 0
        while True:
            rec["attempt_sent"] = time.perf_counter()
            try:
                data = self.conn.txn().mutate(
                    set_rdf=text["set"], del_rdf=text.get("delete", ""),
                    commit_now=True)
                break
            except RetriableError:
                if rec["retries"] == WRITE_RETRIES:
                    raise
                rec["retries"] += 1
        rec["commit_ts"], rec["uids"] = data["commitTs"], data["uids"]
        rec["answer"] = kind.parse(data)

    def warm(self, req=None) -> dict:
        req = self.draw(0) if req is None else req
        self.covered.add(self.shape(req))
        return self.send(req)

    def next_window(self) -> dict:
        return self.send(self.planned.pop(0) if self.planned
                         else self.draw(1))


def in_threads(clients, fn) -> list:
    out = [None] * len(clients)

    def work(i):
        out[i] = fn(clients[i])

    threads = [threading.Thread(target=work, args=(i,))
               for i in range(len(clients))]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    return out


def window(clients, seconds: float):
    """All clients start together at t0 and send until t0 + seconds;
    each waits for its last answer."""
    t0 = time.perf_counter() + 0.05
    end = t0 + seconds

    def loop(c):
        recs = []
        time.sleep(max(0.0, t0 - time.perf_counter()))
        while time.perf_counter() < end:
            rec = c.next_window()
            recs.append(rec)
            if rec["error"]:
                time.sleep(0.05)  # a dead server must not spin the loop
        return recs

    recs = [r for per in in_threads(clients, loop) for r in per]
    return t0, recs


def cover(clients, cap_draws: int) -> dict:
    """Send warm-up requests (from the warm-up streams) of every shape
    class that the planned window requests have and no warm-up request
    has had. A class no draw under the cap reaches stays uncovered, and
    `compiles_in_window` will say so."""
    needed = {c.shape(r) for c in clients for r in c.planned} - {None}
    covered = set().union(*(c.covered for c in clients))
    missing = needed - covered
    found = []
    draws = 0
    while missing and draws < cap_draws:
        c = clients[draws % len(clients)]
        req = c.draw(0)
        draws += 1
        shape = c.shape(req)
        if shape in missing:
            missing.discard(shape)
            found.append((c, req))
    errors, sent = [], []
    for i in range(0, len(found), len(clients)):  # rounds, one to a client
        batch = found[i:i + len(clients)]
        recs = in_threads(batch, lambda cr: cr[0].warm(cr[1]))
        errors += [r["error"] for r in recs if r["error"]]
        sent += recs
    return {"classes": len(needed), "sent": len(found),
            "unreached": len(missing), "draws": draws, "errors": errors}, sent


def committed(recs) -> list:
    """The writes among `recs` that committed and were answered."""
    return [r for r in recs
            if r.get("commit_ts") is not None and r["error"] is None]


def main() -> int:
    spec = json.loads(sys.stdin.readline())
    maker = importlib.import_module(f"chipbench.data.{spec['config']['data']}")
    catalog = maker.catalog(spec["config"], spec["seed"])
    kinds, weights = load_kinds(spec["mix"])
    clients = [Client(spec, i, catalog, kinds, weights)
               for i in range(spec["mix"]["clients"])]

    def reply(obj):
        sys.stdout.write(json.dumps(obj) + "\n")
        sys.stdout.flush()

    reply({"ready": len(clients)})
    warm_writes = []
    for line in sys.stdin:
        cmd = json.loads(line)
        if cmd["cmd"] == "warm":
            recs = in_threads(clients, lambda c: c.warm())
            warm_writes += committed(recs)
            reply({"errors": [r["error"] for r in recs if r["error"]],
                   "seconds": max(r["done"] - r["sent"] for r in recs)})
        elif cmd["cmd"] == "cover":
            summary, recs = cover(clients, spec["mix"].get("cap_draws", 0))
            warm_writes += committed(recs)
            reply(summary)
        elif cmd["cmd"] == "run":
            t0, recs = window(clients, cmd["seconds"])
            with open(cmd["out"], "wb") as f:
                pickle.dump({"t0": t0, "records": recs,
                             "warm_writes": warm_writes}, f)
            reply({"t0": t0, "requests": len(recs)})
        elif cmd["cmd"] == "stop":
            break
    return 0


if __name__ == "__main__":
    sys.exit(main())
