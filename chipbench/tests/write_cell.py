"""A writing cell laid over a copy of the benchmark as new files only:
a maker whose plain model takes the persons that writes add, a query
kind that writes (a new person who knows `fanout` persons drawn
uniformly, each edge both ways with its `creationDate` facet: LDBC's
update 1 adds a person, update 8 a friendship), a mix that pairs it with
`is3` (a person's friends by that facet), a configuration that limits
the history's numbers, and a fault that serves every read one commit
behind. test_chipbench.py, beside this file, runs it on the CPU.

  python3 -m chipbench.tests.write_cell <dir> [--seed <n>] [--seconds <s>]

lays the copy at <dir> and runs its rehearsal there once, on whatever
platform jax has (on the chip's host: through the served alpha at the
rehearsal's sizes, never a result), and prints the run's last line."""

from __future__ import annotations

import argparse
import json
import os
import shutil
import subprocess
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
CHIPBENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(CHIPBENCH)
CELL = "small.friends"

MAKER = '''"""Data maker of a test: `snb`'s, n-quad for n-quad, whose plain model
also holds the persons that committed writes add (kind `new_friend`)."""
import numpy as np

from chipbench.data import snb


def __getattr__(name):
    return getattr(snb, name)


class Model(snb.Model):
    """`snb.Model` and the persons added since it was made: index n0 + j
    is the j-th added. `friends` and `hops` keep to the loaded graph."""

    def person(self, i):
        i = int(i)
        return super().person(i) if i < self.n0 else self.added[i - self.n0]

    def add_person(self, row, friends, at_ms):
        self._drawn()  # the seed's draws are of the loaded sizes
        new = self.n
        self.added.append(row)
        self.pairs = np.concatenate(
            [self.pairs, [[int(f), new] for f in friends]])
        drawn = self._drawn()
        drawn["knows_ms"] = np.concatenate(
            [drawn["knows_ms"], np.full(len(friends), at_ms, np.int64)])
        self.n += 1


def growing(model):
    model.__class__ = Model
    model.n0, model.added = model.n, []
    return model


def make(config, seed, rdf_path=None):
    return growing(snb.make(config, seed, rdf_path))


def catalog(config, seed):
    return {"model": make(config, seed)}


def install(config, seed, alpha, store_dir):
    model, info = snb.install(config, seed, alpha, store_dir)
    return growing(model), info
'''

NEW_FRIEND = '''"""Query kind of a test that WRITES: a new person who knows `fanout`
persons drawn uniformly, each edge both ways with its creationDate."""
from chipbench.data import snb
from chipbench.queries import snb_reads as reads

WRITES = True
NAMES = ["Ada", "Bo", "Cy", "Di"]


def request(catalog, params, rng, client, seq):
    model = catalog["model"]
    friends = sorted(rng.choice(model.n0, params["fanout"],
                                replace=False).tolist())
    sid = params["first_id"] + seq * 64 + client
    first, last = (NAMES[int(i)] for i in rng.integers(len(NAMES), size=2))
    at = snb.BASE_MS + int(rng.integers(60_000_000_000))
    facet = '(creationDate="%s"^^<xs:dateTime>)' % snb._dt(at)
    lines = ['_:p <fqid> "person_%d" .' % sid, '_:p <id> "%d"^^<xs:int> .' % sid,
             '_:p <firstName> "%s" .' % first, '_:p <lastName> "%s" .' % last,
             '_:p <dgraph.type> "person" .']
    for f in friends:
        u = snb.person_uid(f)
        lines += ["_:p <knows> <0x%x> %s ." % (u, facet),
                  "<0x%x> <knows> _:p %s ." % (u, facet)]
    return (sid, first, last, at, tuple(friends)), {"set": "\\n".join(lines)}


def parse(data):
    return data["uids"].get("p")


def apply(model, params, key, answer):
    sid, first, last, at, friends = key
    model.add_person({"id": sid, "firstName": first, "lastName": last,
                      "uid": int(answer, 16)}, friends, at)
    reads.friendships.cache_clear()


def check(model, params, keys, answers, captured=None):
    return {"writes_unnamed": [0.0 if a else 1.0 for a in answers]}
'''

READS_BEHIND = '''"""Fault of a test: every read is served one commit behind, at the
snapshot published before the newest."""


def plant():
    from dgraph_tpu.api.server import Server

    def seen(self):
        published = self.__dict__.setdefault("_published", [0])
        return published[-2] if len(published) > 1 else published[-1]

    def publish(self, ts):
        published = self.__dict__.setdefault("_published", [0])
        if ts != published[-1]:
            published.append(ts)

    Server._snapshot_ts = property(seen, publish)
'''

ADDED = {"configs/snb-small-w.json", "data/snb_growing.py",
         "queries/new_friend.py", "faults/reads_behind.py",
         "mixes/friends.json"}


def _at_least(limit):
    return {"agg": "sum", "op": ">=", "limit": limit}


def _at_most(limit):
    return {"agg": "sum", "op": "<=", "limit": limit}


def build(root: str) -> dict:
    """Copy `chipbench/` and BENCHMARK.json to `root` and add the
    writing cell as new files and entries; {path: bytes} of the copy's
    files before anything was added."""
    cb = os.path.join(root, "chipbench")
    shutil.copytree(CHIPBENCH, cb, ignore=shutil.ignore_patterns(
        ".store", "__pycache__"))
    before = {}
    for d, _, names in os.walk(cb):
        for n in names:
            with open(os.path.join(d, n), "rb") as f:
                before[os.path.join(d, n)] = f.read()
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry = next(c for c in bench["configs"] if c["name"] == "snb-sf1")
    with open(os.path.join(ROOT, entry["file"])) as f:
        config = json.load(f)
    config.update(
        name="snb-small-w", data="snb_growing",
        rehearsal={"persons": 1500, "knows_pairs": 12000, "posts": 3000,
                   "comments": 6000, "forums": 40},
        rehearsal_env={},
        checks={"wrong_answers": _at_most(0), "compared_is3": _at_least(1),
                "writes_unnamed": _at_most(0),
                "writes_committed": _at_least(1),
                "reads_changed_by_writes": _at_least(1),
                "order_violations": _at_most(0)})
    mix = {"name": "friends", "clients": 4,
           "kinds": [{"kind": "new_friend", "weight": 1,
                      "params": {"fanout": 150, "first_id": 10 ** 9}},
                     {"kind": "is3", "weight": 3, "params": {}}],
           "lookahead": 0, "cap_draws": 0, "compare_sample": 0,
           "trace_seconds": 2, "fault": "reads_behind"}
    for rel, text in (("configs/snb-small-w.json", json.dumps(config)),
                      ("data/snb_growing.py", MAKER),
                      ("queries/new_friend.py", NEW_FRIEND),
                      ("faults/reads_behind.py", READS_BEHIND),
                      ("mixes/friends.json", json.dumps(mix))):
        with open(os.path.join(cb, rel), "w") as f:
            f.write(text)
    bench["configs"].append({
        "name": "snb-small-w", "source": "test",
        "file": "chipbench/configs/snb-small-w.json",
        "reduced": config["reduced"], "why": "test"})
    bench["workloads"].append({"name": CELL, "config": "snb-small-w",
                               "traffic": "friends", "chips": 1,
                               "why": "test"})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return before


def drive(root: str, module: str, *args, seed: int = 9, seconds: float = 3):
    """`python -m <module> ... --workload small.friends` in the copy,
    on the CPU where jax has no other platform."""
    env = dict(os.environ, PYTHONPATH=os.pathsep.join([root, ROOT]))
    env.pop("BENCH_RUN", None)
    return subprocess.run(
        [sys.executable, "-m", module, *args, "--workload", CELL,
         "--seed", str(seed), "--seconds", str(seconds)],
        cwd=root, env=env, capture_output=True, text=True, timeout=600)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("dir")
    ap.add_argument("--seed", type=int, default=9)
    ap.add_argument("--seconds", type=float, default=10)
    args = ap.parse_args(argv)
    shutil.rmtree(args.dir, ignore_errors=True)
    build(args.dir)
    proc = drive(args.dir, "chipbench.run", "--trace", "0", "--rehearsal",
                 seed=args.seed, seconds=args.seconds)
    sys.stderr.write(proc.stderr[-6000:])
    print(proc.stdout.strip().splitlines()[-1] if proc.stdout.strip() else "")
    return proc.returncode


if __name__ == "__main__":
    sys.exit(main())
