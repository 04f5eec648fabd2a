"""LDBC SNB Interactive's read mix as the benchmark serves it
(configuration `snb-sf1-reads`, cell `snb.short16`): each short read's
plain reference (`chipbench/queries/is1.py` .. `is7.py`, numpy over
`chipbench/data/snb.Model`) against the served engine (`cli._server` on
`backend=lsm`, `HTTPServer`, `DgraphClient` over the socket) on a small
seeded store; the controls; the data kept equal to `snb-sf1`'s; the mix's
shares; and the cell run as `chipbench/tests` runs every cell (that
directory is outside tier-1).
"""

import argparse
import hashlib
import importlib
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from chipbench.data import snb
from chipbench.data import snb_reads as snb_reads_maker
from dgraph_tpu.utils.observe import METRICS

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# five messages a person: a dozen persons have none, one in eight has the
# eight an index walk could be tried for, a few have more than `first`
SMALL = {"persons": 2000, "knows_pairs": 10000, "posts": 3500,
         "comments": 6500, "forums": 300}
SEED = 2**31 + 29
KINDS = [f"is{i}" for i in range(1, 8)]
ORDER = ('order_single_total{path="values"}',
         'order_single_total{path="walked"}',
         'order_single_total{path="over_budget"}',
         "order_candidates_total", "order_buckets_total")


def _config(name):
    with open(os.path.join(ROOT, "chipbench", "configs", name + ".json")) as f:
        return json.load(f)


def _mix():
    with open(os.path.join(ROOT, "chipbench", "mixes", "short16.json")) as f:
        return json.load(f)


def _kind(name):
    return importlib.import_module(f"chipbench.queries.{name}")


def _params(name):
    return next(k["params"] for k in _mix()["kinds"] if k["kind"] == name)


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    """(model, DgraphClient) of a small store behind the served path."""
    from dgraph_tpu import cli
    from dgraph_tpu.api.http_server import HTTPServer
    from dgraph_tpu.client import DgraphClient
    from dgraph_tpu.loaders.bulk2 import ParallelBulkLoader

    tmp = tmp_path_factory.mktemp("snb_reads")
    config = dict(_config("snb-sf1-reads"), sizes=SMALL)
    rdf = str(tmp / "snb.rdf")
    model = snb.make(config, SEED, rdf)
    engine = cli._server(argparse.Namespace(
        p=str(tmp / "p"), storage="backend=lsm", encryption_key_file=None))
    engine.alter(snb.SCHEMA)
    loader = ParallelBulkLoader(engine)
    loader.load_files([rdf])
    engine.kv.sync()
    assert loader.nquads == model.nquads
    srv = HTTPServer(engine, host="127.0.0.1", port=0).start()
    try:
        yield model, DgraphClient(f"http://127.0.0.1:{srv.port}", timeout=60)
    finally:
        srv.stop()
        engine.kv.close()


def _chosen(model, name):
    """Keys that must be among those compared: a person with and without
    messages, a post and a comment, a message with and without replies
    (the most replied-to, too)."""
    msgs = model.messages()
    wrote = np.bincount(msgs.creator, minlength=model.n)
    replied = np.bincount(msgs.parent[msgs.parent >= 0], minlength=len(msgs))
    if name in ("is1", "is2", "is3"):
        return [int(np.argmax(wrote)), int(np.argmin(wrote))]
    if name == "is6":
        return [0, msgs.n_posts - 1]
    return [0, msgs.n_posts, int(np.argmax(replied)),
            int(np.flatnonzero(replied == 0)[0]), len(msgs) - 1]


def _keys(model, name, count=40):
    kind, params = _kind(name), _params(name)
    rng = np.random.default_rng([SEED, KINDS.index(name)])
    drawn = [kind.request({"model": model}, params, rng)[0]
             for _ in range(count)]
    return _chosen(model, name) + drawn


@pytest.mark.parametrize("name", KINDS)
def test_the_reference_is_what_the_served_engine_answers(served, name):
    model, client = served
    kind, params = _kind(name), _params(name)
    keys = _keys(model, name)
    if name == "is2":
        assert min(len(model.messages().by_creator(k)) for k in keys) == 0
    answers = [kind.parse(client.query(kind.text(model, params, k)))
               for k in keys]
    want = kind.reference(model, params, keys)
    assert answers == want
    numbers = kind.check(model, params, keys, answers)
    assert sum(numbers["wrong_answers"]) == 0
    assert sum(numbers[f"compared_{name}"]) == len(keys)
    assert sum(map(len, answers)) > 0  # not a comparison of nothing
    if name == "is2":
        assert sum(numbers["is2_parents_compared"]) >= 1
        assert any(len(a) == params["first"] for a in answers)
    if name == "is7":
        assert sum(numbers["is7_replies_compared"]) >= 2


def test_is7_says_who_knows_the_author(served):
    """Both answers of `knows @filter(uid(c))` are among the compared:
    over every message with a reply, some replier knows the author and
    some does not."""
    model, client = served
    kind, params = _kind("is7"), _params("is7")
    msgs = model.messages()
    keys = np.unique(msgs.parent[msgs.parent >= 0])[:400].tolist()
    want = kind.reference(model, params, keys)
    flags = {row[-1] for a in want for row in a}
    assert flags == {True, False}
    pick = [k for k, a in zip(keys, want) if any(r[-1] for r in a)][:10]
    got = [kind.parse(client.query(kind.text(model, params, k))) for k in pick]
    assert got == kind.reference(model, params, pick)


@pytest.mark.parametrize("name", ["is2", "is7"])
def test_an_order_of_few_ids_lists_no_more_buckets_than_its_budget(served,
                                                                   name):
    """`~hasCreator(orderdesc: creationDate, first: 10)` and
    `~replyOf(orderdesc: creationDate)` order a few dozen ids at most:
    each call lists or reads at most len(candidates) // 8 buckets of
    `creationDate`'s hour index (it has thousands), and none where the
    candidates are fewer than 8."""
    model, client = served
    kind, params = _kind(name), _params(name)
    calls = 0
    for k in _keys(model, name):
        before = [METRICS.value(c) for c in ORDER]
        client.query(kind.text(model, params, k))
        values, walked, over, cands, buckets = (
            int(METRICS.value(c) - b) for c, b in zip(ORDER, before))
        assert values + walked + over <= 1
        calls += values + walked + over
        assert buckets < cands // 8 + 1
        if cands < 8:
            assert (values, buckets) == (min(cands, 1), 0)
    assert calls >= 10


@pytest.mark.parametrize("name", KINDS)
def test_the_control_reads_wrong_where_messages_are_read(served, name):
    """The control is the model without its newest 1% of messages: wrong
    for the reads that order messages (IS2, IS7) and for those that
    fetch one (IS4-IS6) when it is among the missing; persons and
    friendships (IS1, IS3) are older than any message."""
    model, _ = served
    kind, params = _kind(name), _params(name)
    msgs = model.messages()
    newest = np.argsort(msgs.ms)[-len(msgs) // 200:]
    if name in ("is1", "is3"):
        keys = _keys(model, name)
    elif name == "is2":
        keys = msgs.creator[newest].tolist()
    elif name == "is7":
        keys = [int(p) for p in msgs.parent[newest] if p >= 0]
    elif name == "is6":
        keys = np.sort(msgs.ms[:msgs.n_posts].argsort()[-10:]).tolist()
    else:
        keys = newest.tolist()
    answers, captured = kind.control(model, params, keys)
    assert captured is None
    wrong = sum(kind.check(model, params, keys, answers)["wrong_answers"])
    if name in ("is1", "is3"):
        assert wrong == 0
    elif name == "is6":  # the newest messages are replies, seldom posts
        from chipbench.queries import snb_reads

        assert wrong == int((~snb_reads.present(model, True)[keys]).sum())
    else:
        assert wrong == len(keys) > 0


def _sha256(path):
    h = hashlib.sha256()
    with open(path, "rb") as f:
        for block in iter(lambda: f.read(1 << 20), b""):
            h.update(block)
    return h.hexdigest()


@pytest.mark.parametrize("sizes", ["small", "rehearsal"])
def test_a_seeds_nquads_are_snb_sf1s_byte_for_byte(tmp_path, sizes):
    """The two configurations differ in traffic alone: the sizes, the
    cut and every key the maker reads are equal, and so is the file a
    seed writes."""
    a, b = _config("snb-sf1"), _config("snb-sf1-reads")
    for key in ("chips", "sizes", "source_sizes", "reduced",
                "reduced_why", "guarantees", "rehearsal_env"):
        assert a[key] == b[key], key
    # the read mix's maker is `snb`'s behind one question to the program
    assert (a["data"], b["data"]) == ("snb", "snb_reads")
    assert snb_reads_maker.make is snb.make
    assert snb_reads_maker.catalog is snb.catalog
    for key in ("degree_lognormal_sigma", "degree_cap", "degree_sequence",
                "structure_seed", "first_names"):
        assert a["assumed"][key] == b["assumed"][key], key
    at = SMALL if sizes == "small" else b["rehearsal"]
    shas = []
    for config in (a, b):
        path = str(tmp_path / (config["name"] + ".rdf"))
        model = snb.make(dict(config, sizes=dict(config["sizes"], **at)),
                         SEED, path)
        assert model.nquads > 30000
        shas.append(_sha256(path))
    assert shas[0] == shas[1]


def test_the_maker_refuses_a_program_that_lists_the_whole_index(
        monkeypatch, tmp_path):
    """On a program from before `order_single_total` the cell would run
    (a short read in seconds, complex read 1 perhaps never in the
    window): the maker ends the run at once, before anything is built
    or opened, and hands everything else to `snb`."""
    from dgraph_tpu.utils import observe

    assert observe.registered_metric(snb_reads_maker.NEEDS)
    calls = []
    monkeypatch.setattr(snb, "install", lambda *a: calls.append(a) or "ok")
    assert snb_reads_maker.install({"name": "c"}, 1, None, "d") == "ok"
    assert calls == [({"name": "c"}, 1, None, "d")]

    monkeypatch.delitem(observe.METRIC_DEFS, 'order_single_total{path="*"}')
    with pytest.raises(SystemExit) as e:
        snb_reads_maker.install({"name": "snb-sf1-reads"}, 1, None,
                                str(tmp_path / "store"))
    assert "order_single_total" in str(e.value.code)
    assert len(calls) == 1 and not (tmp_path / "store").exists()
    assert not hasattr(snb_reads_maker, "captured")  # as `snb`: hasattr


def test_the_mix_is_ldbcs_shares():
    mix = _mix()
    weights = {k["kind"]: k["weight"] for k in mix["kinds"]}
    share = {k: round(100 * w / sum(weights.values()), 2)
             for k, w in weights.items()}
    assert share == {**{k: 14.12 for k in KINDS}, "ic1_counted": 1.19}
    # IC1's own share of the 7.26% complex reads, by SF1's frequencies
    f = [26, 37, 69, 36, 57, 129, 87, 45, 157, 30, 16, 44, 19, 49]
    assert round(7.26 * (1 / 26) / sum(1 / x for x in f), 3) == 0.766
    assert round(63.82 / 7, 3) == 9.117 == weights["is1"]
    assert mix["clients"] == 16 and mix["compare_sample"] == 0
    assert mix["trace_seconds"] == 20 and mix["fault"] == "order_single"
    ic1 = next(k for k in mix["kinds"] if k["kind"] == "ic1_counted")
    with open(os.path.join(ROOT, "chipbench", "mixes", "ic1.json")) as fh:
        assert ic1["params"] == json.load(fh)["kinds"][0]["params"]
    checks = _config("snb-sf1-reads")["checks"]
    assert set(checks) == {
        "wrong_answers", "answers_compared", "is2_parents_compared",
        "is7_replies_compared", "compared_ic1",
        *(f"compared_{k}" for k in KINDS)}


def _drive(module, *args):
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT)
    env.pop("BENCH_RUN", None)
    proc = subprocess.run(
        [sys.executable, "-m", module, *args, "--workload", "snb.short16",
         "--seed", str(SEED), "--seconds", "3"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def test_the_cell_rehearsed_with_its_fault_and_its_control():
    """As `chipbench/tests` runs every cell: the rehearsal reads
    `correct` true with every kind compared, the planted fault
    (`order_single`: the single-key order drops its newest id) false,
    the control false."""
    out = _drive("chipbench.run", "--trace", "1", "--rehearsal")
    assert out["rehearsal"] is True and out["correct"] is True
    assert out["failed"] == 0 and out["attempted"] > 0
    checks = out["checks"]
    assert checks["answers_compared"][0] == out["attempted"]
    for name in (*KINDS, "ic1"):
        assert checks[f"compared_{name}"][0] >= 1, name
    assert checks["is2_parents_compared"][0] >= 1
    assert checks["is7_replies_compared"][0] >= 1
    assert out["setup"]["compiles_in_window"] == 0
    metrics = out["metrics"]
    # the rehearsal's IC1 took the jitted set ops; the short reads' orders
    # listed next to nothing (hundreds of hour buckets at this size)
    assert metrics["device_ops_per_req"]["value"] > 0
    assert 0 <= metrics["order_buckets_per_req"]["value"] < 10
    assert metrics["order_sorted_ids_per_req"]["value"] > 0

    out = _drive("chipbench.tests.faults", "order_single", "--trace", "0")
    assert out["correct"] is False
    assert out["checks"]["wrong_answers"][0] > 0

    out = _drive("chipbench.control", "--rehearsal")
    assert out["program"]["correct"] is True
    assert out["control"]["correct"] is False
    assert out["control"]["checks"]["wrong_answers"][0] > 0
