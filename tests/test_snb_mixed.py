"""LDBC SNB Interactive's reads with its update stream, as the benchmark
serves them (configuration `snb-sf1-mixed`, cell `snb.mixed16`): each
update IU1-IU8 (`chipbench/queries/iu1.py` .. `iu8.py`) committed
through the served path (`cli._server` on `backend=lsm`, `HTTPServer`,
`DgraphClient`), applied to the maker's growing model
(`chipbench/data/snb_mixed.py`) and read back against it; complex read
9 served between IU6 and IU7 writes from a value column that takes
their rows, against `chipbench/queries/ic9.reference` on the grown
model; the session rule; the data kept equal to `snb-sf1`'s; the mix's
shares; and the cell rehearsed with its fault and its control
(`chipbench/tests` is outside tier-1)."""

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
from chipbench.data import snb_mixed
from dgraph_tpu.query import dispatch
from dgraph_tpu.utils.observe import METRICS

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SEED = 2**31 + 41
UPDATES = [f"iu{i}" for i in range(1, 9)]
COLUMN = ("value_column_builds_total", "value_column_invalidations_total",
          "value_column_patched_rows_total",
          'value_column_fallback_total{why="stale"}',
          'device_dispatch_total{family="column#filter"}',
          'device_dispatch_total{family="column#narrow"}')


def _config(name="snb-sf1-mixed"):
    with open(os.path.join(ROOT, "chipbench", "configs", name + ".json")) as f:
        return json.load(f)


def _mix():
    with open(os.path.join(ROOT, "chipbench", "mixes", "mixed16.json")) as f:
        return json.load(f)


def _kind(name):
    return importlib.import_module(f"chipbench.queries.{name}")


def _params(name):
    """The mix's params of kind `name` (of its session form, for IS2,
    IS3 and IS7)."""
    return next(k["params"] for k in _mix()["kinds"]
                if k["kind"] in (name, name + "_session"))


def _rehearsed():
    config = _config()
    return dict(config, sizes=dict(config["sizes"], **config["rehearsal"]))


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    """(growing model, catalog, DgraphClient) of the rehearsal's store
    behind the served path, with `hasMember` declared as the maker
    declares it."""
    from dgraph_tpu import cli
    from dgraph_tpu.api.http_server import HTTPServer
    from dgraph_tpu.client import DgraphClient
    from dgraph_tpu.loaders.bulk2 import ParallelBulkLoader

    tmp = tmp_path_factory.mktemp("snb_mixed")
    config = _rehearsed()
    rdf = str(tmp / "snb.rdf")
    model = snb_mixed.make(config, SEED, rdf)
    engine = cli._server(argparse.Namespace(
        p=str(tmp / "p"), storage="backend=lsm", encryption_key_file=None))
    engine.alter(snb.SCHEMA)
    loader = ParallelBulkLoader(engine)
    loader.load_files([rdf])
    engine.kv.sync()
    assert loader.nquads == model.nquads
    engine.alter(snb_mixed.SCHEMA_ADDED)
    srv = HTTPServer(engine, host="127.0.0.1", port=0).start()
    try:
        yield (model, {"model": snb.make(config, SEED)},
               DgraphClient(f"http://127.0.0.1:{srv.port}", timeout=60))
    finally:
        srv.stop()
        engine.kv.close()


_SEQ = iter(range(10_000))


def _write(served, name, keep=lambda key: True):
    """One write of kind `name` drawn (a draw `keep` refuses is drawn
    again), committed through the wire and applied to the model: its
    key."""
    model, catalog, client = served
    kind, params = _kind(name), _params(name)
    rng = np.random.default_rng([SEED, 7])
    while True:
        key, write = kind.request(catalog, params, rng, 3, 2 * next(_SEQ) + 1)
        if keep(key):
            break
    data = client.txn().mutate(set_rdf=write["set"], commit_now=True)
    answer = kind.parse(data)
    kind.apply(model, params, key, answer)
    numbers = kind.check(model, params, [key], [answer])
    assert numbers == {"writes_unnamed": [0.0]}
    return key


def _read(served, name, keys, params=None):
    """The served answers of read kind `name` and the grown model's."""
    model, _, client = served
    kind = _kind(name)
    params = _params(name) if params is None else params
    got = [kind.parse(client.query(kind.text(model, params, k))) for k in keys]
    return got, kind.reference(model, params, keys)


def _rows(client, text):
    return client.query(text)["data"]["q"]


def test_iu1_a_person_is_read_back_with_their_place(served):
    model, _, _ = served
    i, row = _write(served, "iu1")
    assert i >= model.n0 and model.person(i)["id"] == row["id"]
    got, want = _read(served, "is1", [i])
    assert got == want and len(want[0]) == 1


@pytest.mark.parametrize("name", ["iu2", "iu3"])
def test_iu2_iu3_a_like_is_read_back(served, name):
    model, _, client = served
    p, i, at = _write(served, name)
    msgs = model.messages()
    assert msgs.is_post(i) == (name == "iu2")
    rows = _rows(client, f'{{ q(func: eq(fqid, "person_{snb.person_sid(p)}"))'
                         " { likes @facets(creationDate) { id } } }")
    liked = {(r["id"], snb.epoch_ms(r["likes|creationDate"].split('"')[1]))
             for r in rows[0]["likes"]}
    assert liked == {(msgs.sid(j), ms) for q, j, ms in msgs.likes if q == p}
    assert (msgs.sid(i), at) in liked


def test_iu4_a_forum_is_read_back(served):
    model, _, client = served
    f, mod, title, at = _write(served, "iu4")
    rows = _rows(client, f'{{ q(func: eq(fqid, "forum_{snb.forum_sid(f)}")) '
                         "{ id title creationDate hasModerator { id } } }")
    new = model.messages().new_forums[f]
    assert [(r["id"], r["title"], snb.epoch_ms(r["creationDate"]),
             r["hasModerator"][0]["id"]) for r in rows] == [
        (snb.forum_sid(f), new["title"], new["ms"],
         snb.person_sid(new["moderator"]))]


def test_iu5_a_membership_is_read_back(served):
    model, catalog, client = served
    f, p, at = _write(served, "iu5")
    msgs = model.messages()
    rows = _rows(client, f"{{ q(func: uid({msgs.forum_uid(f):#x})) "
                         "{ hasMember @facets(joinDate) { id } } }")
    got = {(r["id"], snb.epoch_ms(r["hasMember|joinDate"].split('"')[1]))
           for r in rows[0]["hasMember"]}
    assert got == {(snb.person_sid(q), ms) for g, q, ms in msgs.members
                   if g == f}
    # the maker's declaration: a list with its reverse edge
    rows = _rows(client, f'{{ q(func: eq(fqid, "person_{snb.person_sid(p)}"))'
                         " { ~hasMember { id } } }")
    assert snb.forum_sid(f) in [r["id"] for r in rows[0]["~hasMember"]]


def test_iu6_a_post_is_read_back_by_every_short_read(served):
    model, _, _ = served
    i, creator, f, at, content, image = _write(served, "iu6")
    msgs = model.messages()
    assert msgs.is_post(i) and msgs.forum_of_post(i) == f
    assert msgs.sid(i) == snb.comment_sid(i - msgs.n_posts)
    for name, keys in (("is2", [creator]), ("is4", [i]), ("is5", [i]),
                       ("is6", [i])):
        got, want = _read(served, name, keys)
        assert got == want and want[0], name
    got, _ = _read(served, "is2", [creator])
    assert got[0][0][:4] == (msgs.sid(i), content, image, at)  # the newest


def test_iu7_a_comment_is_read_back_under_its_parent(served):
    model, _, _ = served
    i, creator, parent, at, content = _write(served, "iu7")
    msgs = model.messages()
    assert not msgs.is_post(i) and i in msgs.replies(parent).tolist()
    for name, keys in (("is2", [creator]), ("is7", [parent]), ("is4", [i]),
                       ("is5", [i])):
        got, want = _read(served, name, keys)
        assert got == want and want[0], name
    got, _ = _read(served, "is7", [parent])
    assert got[0][0][:3] == (msgs.sid(i), content, at)


def test_iu8_a_friendship_is_read_back_both_ways(served):
    from chipbench.queries import is3_session

    model, _, _ = served
    a, b, at = _write(served, "iu8")
    assert b in model.friends(a) and a in model.friends(b)
    got, want = _read(served, "is3", [a, b])
    assert got == want
    assert is3_session.reference(model, {}, [a, b]) == want
    assert [d for *_, d in want[0]][0] == at  # the newest friendship


def test_ic9_between_writes_reads_the_column_and_its_delta(served,
                                                          monkeypatch):
    """IC9 from one start person whose circle writes IU6 posts and IU7
    comments between the reads, with a `maxDate` above the new dates:
    every answer equals `ic9.reference` on the model as grown so far,
    the column is built once and takes every write's row, and the new
    messages lead the answer."""
    from chipbench.queries import ic9

    model, catalog, client = served
    monkeypatch.setattr(dispatch, "_DEVICE_MIN_TOTAL", 512)
    params = _params("ic9")
    start = int(ic9.ic1.curated(catalog, params)[0])
    circle = set(np.concatenate(model.hops(start, 2)).tolist())
    bound = (int(model.messages().ms.max()) // ic9.DAY_MS + 10) * ic9.DAY_MS
    key = np.array([start, bound])
    before = {c: METRICS.value(c) for c in COLUMN}
    answers = []
    for name in ("iu6", "iu7", "iu6", "iu7", None):
        got = ic9.parse(client.query(ic9.text(start, bound, params["limit"])))
        assert got == ic9.reference(model, params, [key])[0]
        answers.append(got)
        if name is not None:
            _write(served, name, keep=lambda k: k[1] in circle)
    moved = {c: METRICS.value(c) - before[c] for c in COLUMN}
    assert moved["value_column_builds_total"] <= 1
    assert moved["value_column_invalidations_total"] == 0
    assert moved["value_column_patched_rows_total"] == 4
    assert moved['value_column_fallback_total{why="stale"}'] == 0
    assert moved['device_dispatch_total{family="column#narrow"}'] == 5
    newest = [sid for sid, *_ in answers[-1][:4]]
    msgs = model.messages()
    assert sorted(newest) == sorted(msgs.sid(i) for i in msgs.new
                                    if msgs.creator[i] in circle)[-4:]


def test_the_session_rule_reads_the_last_write(served):
    """On one draw stream: IU7 leaves its creator for the next IS2 and
    its parent for the next IS7, IU8 one of its persons for the next
    IS3; a later write replaces what was pending; other draws are the
    plain kinds'."""
    _, catalog, _ = served
    rng = np.random.default_rng([SEED, 11])
    draw = {n: _kind(n) for n in ("iu6", "iu7", "iu8", "iu2", "is2_session",
                                  "is3_session", "is7_session")}

    def write(name, seq):
        return draw[name].request(catalog, _params(name), rng, 0, seq)[0]

    def read(name):
        return draw[name].request(catalog, _params(name), rng)[0]

    from chipbench.queries import snb_writes

    i, creator, parent, *_ = write("iu7", 1)
    assert (read("is7_session"), read("is2_session")) == (parent, creator)
    a, b, _ = write("iu8", 3)
    assert read("is3_session") == a
    post = write("iu6", 5)
    assert snb_writes.pending(catalog, rng) == {"is2": post[1]}
    write("iu2", 7)  # replaces what the post left
    assert snb_writes.pending(catalog, rng) == {}
    fresh = np.random.default_rng([SEED, 12])
    twin = np.random.default_rng([SEED, 12])
    assert draw["is2_session"].request(catalog, _params("is2_session"),
                                       fresh) == _kind("is2").request(
        catalog, _params("is2_session"), twin)


def test_new_ids_and_dates_are_functions_of_the_draw(served):
    """Two draws of (client, seq) give the same ids and dates; dates
    grow with seq, are after every loaded one, and no two draws share
    an index."""
    _, catalog, _ = served
    params = _params("iu7")
    got = {}
    for client in (0, 15):
        for seq in (1, 2, 301):
            key = _kind("iu7").request(catalog, params,
                                       np.random.default_rng(seq), client,
                                       seq)[0]
            got[client, seq] = key
            assert key == _kind("iu7").request(
                catalog, params, np.random.default_rng(seq), client, seq)[0]
    newest = int(catalog["model"].messages().ms.max())
    assert all(k[3] > newest for k in got.values())
    assert got[0, 1][3] < got[0, 2][3] < got[0, 301][3]
    assert len({k[0] for k in got.values()}) == len(got)


def _sha256(path):
    h = hashlib.sha256()
    with open(path, "rb") as f:
        for block in iter(lambda: f.read(1 << 20), b""):
            h.update(block)
    return h.hexdigest()


def test_the_configuration_is_snb_sf1s_network(tmp_path):
    a, b = _config("snb-sf1"), _config()
    for key in ("chips", "sizes", "source_sizes", "reduced", "reduced_why"):
        assert a[key] == b[key], key
    for key in ("degree_lognormal_sigma", "degree_cap", "degree_sequence",
                "structure_seed", "first_names", "why"):
        assert a["assumed"][key] == b["assumed"][key], key
    assert b["data"] == "snb_mixed" and len(b["source"]) <= 200
    assert "read timestamp" in b["guarantees"]["columns"]
    assert set(b["checks"]) == {
        "wrong_answers", "answers_compared", "compared_ic1",
        "is2_parents_compared", "is7_replies_compared", "writes_committed",
        "reads_changed_by_writes", "order_violations",
        *(f"compared_is{i}" for i in range(1, 8))}
    shas = []
    for make in (snb.make, snb_mixed.make):
        path = str(tmp_path / f"{len(shas)}.rdf")
        make(dict(b, sizes=dict(b["sizes"], **b["rehearsal"])), SEED, path)
        shas.append(_sha256(path))
    assert shas[0] == shas[1]


def test_the_mix_is_ldbcs_shares():
    mix = _mix()
    weights = {k["kind"]: k["weight"] for k in mix["kinds"]}
    total = sum(weights.values())
    share = {k: round(100 * w / total, 3) for k, w in weights.items()}
    assert {k: share[k] for k in ("is1", "is2_session", "ic1_counted",
                                  "ic9")} == {
        "is1": 9.738, "is2_session": 9.738, "ic1_counted": 0.818,
        "ic9": 0.136}
    assert round(sum(weights[k] for k in UPDATES), 3) == 28.911
    assert round(100 * sum(weights[k] for k in UPDATES) / total, 2) == 30.88
    f = [26, 37, 69, 36, 57, 129, 87, 45, 157, 30, 16, 44, 19, 49]
    assert round(7.26 * (1 / 157) / sum(1 / x for x in f), 3) == 0.127
    assert mix["clients"] == 16 and mix["compare_sample"] == 0
    assert (mix["lookahead"], mix["cap_draws"], mix["trace_seconds"]) == (
        1000, 4000, 20)
    assert mix["fault"] == "one_commit_behind"
    assert all(_params(k) == {"clients": 16} for k in UPDATES)
    for name, cell in (("ic1_counted", "ic1"), ("ic9", "ic9")):
        path = os.path.join(ROOT, "chipbench", "mixes", cell + ".json")
        with open(path) as fh:
            assert _params(name) == json.load(fh)["kinds"][0]["params"]


def _drive(module, *args):
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT)
    env.pop("BENCH_RUN", None)
    proc = subprocess.run(
        [sys.executable, "-m", module, *args, "--workload", "snb.mixed16",
         "--seed", str(SEED), "--seconds", "3"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


NEW_METRICS = ("write_cpu_ms_per_write", "commit_wait_ms_per_write",
               "commit_batch_mean", "valcol_builds_in_window")


@pytest.mark.parametrize("trace", [0, 1])
def test_the_cell_rehearsed(trace):
    """As `chipbench/tests` runs every cell: `correct` true with every
    kind, a write and a read it changed compared; traced, the jitted
    paths taken and the write path's metrics read."""
    out = _drive("chipbench.run", "--trace", str(trace), "--rehearsal")
    assert out["rehearsal"] is True
    assert out["correct"] is True, (out["attempted"], out["checks"])
    assert out["failed"] == 0 and out["attempted"] > 0
    checks = out["checks"]
    for name in (*(f"is{i}" for i in range(1, 8)), "ic1"):
        assert checks[f"compared_{name}"][0] >= 1, name
    assert checks["writes_committed"][0] >= 1
    assert checks["reads_changed_by_writes"][0] >= 1
    assert checks["order_violations"][0] == 0
    install = out["setup"]["install"]
    assert "copy_s" in install  # a writing run opens a copy
    metrics = out["metrics"]
    if trace:
        assert metrics["device_ops_per_req"]["value"] > 0
        for name in NEW_METRICS:
            assert isinstance(metrics[name]["value"], float), name
        assert metrics["valcol_builds_in_window"]["value"] == 0.0
        assert metrics["commit_batch_mean"]["value"] >= 1.0
    else:
        assert metrics["qps"]["value"] > 0


def test_the_fault_and_the_control_read_wrong():
    """The planted fault (`one_commit_behind`: each read one commit behind)
    and the control (each read one acknowledged write behind) read
    `correct` false."""
    out = _drive("chipbench.tests.faults", "one_commit_behind",
                 "--trace", "0")
    assert out["correct"] is False
    assert out["checks"]["wrong_answers"][0] > 0
    out = _drive("chipbench.control", "--rehearsal")
    assert out["program"]["correct"] is True
    assert out["control"]["correct"] is False
    assert out["control"]["checks"]["wrong_answers"][0] > 0


def test_the_maker_refuses_a_program_that_drops_its_column(monkeypatch,
                                                            tmp_path):
    """On a program whose value column is dropped by every commit an IC9
    outlasts the window: the maker ends the run at once, before anything
    is built or opened."""
    from dgraph_tpu.utils import observe

    assert observe.registered_metric(snb_mixed.NEEDS)
    monkeypatch.delitem(observe.METRIC_DEFS, snb_mixed.NEEDS)
    calls = []
    monkeypatch.setattr(snb, "install", lambda *a: calls.append(a))
    with pytest.raises(SystemExit) as e:
        snb_mixed.install({"name": "snb-sf1-mixed"}, 1, None,
                          str(tmp_path / "store"))
    assert snb_mixed.NEEDS in str(e.value.code)
    assert calls == [] and not (tmp_path / "store").exists()
