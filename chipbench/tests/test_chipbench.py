"""CPU tests of the benchmark's own code, at tiny sizes (`--rehearsal`).

  JAX_PLATFORMS=cpu python -m pytest chipbench/tests -q

They live under `chipbench/` because the PR that defines the benchmark
may add files only there; a later PR may move them to tests/.
"""

from __future__ import annotations

import importlib
import json
import os
import re
import shutil
import subprocess
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
CHIPBENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(CHIPBENCH)
sys.path.insert(0, ROOT)

with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    BENCH = json.load(_f)
CELLS = [w["name"] for w in BENCH["workloads"]]
NAME = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}")
UNIT = re.compile(r"[A-Za-z0-9_/%.\-]{1,16}")
BIG_SEED = 2**31 + 11
# sha256 of the n-quads `snb.make` writes for snb-sf1 at its rehearsal
# sizes, by seed (the parent of PR 33 wrote the same bytes; at full sizes
# seed 2**31 + 11 gives 1dc8cd9a41f2...: CHANGES.md, PR 33)
NQUADS_SHA256 = {
    5: "8bb02bb81ce8d973239c6b707e8424348f16ce21ea5e1b7e7edc37413a0170ca",
    BIG_SEED:
        "02b845933caae1e3b7da4fab9b10f3461a97c908834b8e58ceab251b8f2da287",
}


def cell(name):
    return next(w for w in BENCH["workloads"] if w["name"] == name)


def config_of(name):
    entry = next(c for c in BENCH["configs"] if c["name"] == cell(name)["config"])
    with open(os.path.join(ROOT, entry["file"])) as f:
        return json.load(f)


def mix_of(name):
    with open(os.path.join(CHIPBENCH, "mixes",
                           cell(name)["traffic"] + ".json")) as f:
        return json.load(f)


def drive(module, *args, cwd=ROOT, extra_path=()):
    """Run `python -m <module> ...` on the CPU. What a configuration's
    rehearsal needs in its environment is in its own file
    (`rehearsal_env`), and `--rehearsal` puts it there."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.pathsep.join([cwd, *extra_path]))
    env.pop("BENCH_RUN", None)
    return subprocess.run(
        [sys.executable, "-m", module, *args], cwd=cwd, env=env,
        capture_output=True, text=True, timeout=600)


def last_json(proc):
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


# -- BENCHMARK.json against the contract ------------------------------------


def test_names_and_units_use_the_allowed_characters():
    names = [c["name"] for c in BENCH["configs"]]
    names += [k for c in BENCH["configs"] for k in c["reduced"]]
    for w in BENCH["workloads"]:
        names += [w["name"], w["config"], w["traffic"]]
    metrics = BENCH["end_to_end"] + BENCH["per_layer"]
    names += [m["name"] for m in metrics]
    assert all(NAME.fullmatch(n) for n in names), names
    assert all(UNIT.fullmatch(m["unit"]) for m in metrics)
    assert len(set(m["name"] for m in metrics)) == len(metrics)
    assert all(m["better"] in ("lower", "higher") for m in metrics)
    for text in ([w["why"] for w in BENCH["workloads"]]
                 + [c["why"] for c in BENCH["configs"]]
                 + [c["source"] for c in BENCH["configs"]]
                 + [m["layer"] for m in BENCH["per_layer"]]
                 + BENCH["command"]):
        assert 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text


def test_benchmark_json_keeps_the_contract():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.25
    assert all(0.01 <= m["bound"] <= 0.25 and
               m["source"] in ("host_clock", "device_trace")
               for m in e2e.values())
    n = 24  # the limit has to fit with the full 24 cells
    assert (2 + 14 * n) * (BENCH["run_seconds"] + 60) + n * 180 + 1200 <= 43200
    assert sum(w["chips"] == 4 for w in BENCH["workloads"]) <= max(
        1, len(BENCH["workloads"]) // 2)

    def reports(metric, name):
        return "workloads" not in metric or name in metric["workloads"]

    for m in BENCH["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert m["moves"] in e2e
        for w in m.get("workloads", CELLS):
            assert reports(e2e[m["moves"]], w), (m["name"], w)
    for w in CELLS:
        assert reports(e2e["setup_s"], w)
        assert sum(reports(m, w) for m in e2e.values()) >= 2
        assert any(reports(m, w) for m in BENCH["per_layer"])
    for c in BENCH["configs"]:
        assert c["file"].startswith(tuple(p + "/" for p in BENCH["paths"]))
        with open(os.path.join(ROOT, c["file"])) as f:
            body = json.load(f)
        assert sorted(body["reduced"]) == sorted(c["reduced"])
        assert set(body["checks"]) and body["guarantees"]


@pytest.mark.parametrize("name", CELLS)
def test_every_piece_of_a_cell_is_found_by_name(name):
    config, mix = config_of(name), mix_of(name)
    maker = importlib.import_module(f"chipbench.data.{config['data']}")
    assert all(hasattr(maker, f) for f in ("make", "install", "catalog"))
    assert all(isinstance(v, str) for v in config["rehearsal_env"].values())
    assert set(config["rehearsal"]) <= set(config["sizes"])
    fault = importlib.import_module(f"chipbench.faults.{mix['fault']}")
    assert callable(fault.plant)
    for k in mix["kinds"]:
        kind = importlib.import_module(f"chipbench.queries.{k['kind']}")
        assert all(hasattr(kind, f)
                   for f in ("request", "parse", "check", "control"))
    for m in BENCH["per_layer"]:
        if "workloads" not in m or name in m["workloads"]:
            reader = importlib.import_module(
                f"chipbench.layer_metrics.{m['name']}")
            assert callable(reader.read)


# -- a whole run, rehearsed ---------------------------------------------------


@pytest.mark.parametrize("name", CELLS)
@pytest.mark.parametrize("trace", [0, 1])
def test_rehearsal_runs_a_cell_end_to_end(name, trace):
    out = last_json(drive("chipbench.run", "--workload", name,
                          "--seed", str(BIG_SEED), "--seconds", "3",
                          "--trace", str(trace), "--rehearsal"))
    assert {"correct", "attempted", "failed", "metrics", "device"} <= set(out)
    assert out["rehearsal"] is True and list(out)[-1] == "checks"
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] > 0
    # a mix that only reads opens the kept store in place: no copy
    assert "copy_s" not in out["setup"]["install"]
    group = "per_layer" if trace else "end_to_end"
    want = {m["name"] for m in BENCH[group]
            if "workloads" not in m or name in m["workloads"]}
    assert set(out["metrics"]) <= want
    for m, v in out["metrics"].items():
        assert isinstance(v["value"], float) and v["unit"]
    if trace:
        assert {"busy_s", "window_s"} <= set(out["device"])
        assert "compiles_in_window" in out["metrics"]
        # the configuration's `rehearsal_env` reached the program: the
        # requests took the jitted paths
        assert out["metrics"]["device_ops_per_req"]["value"] > 0
        assert out["breakdown"]["idle_gaps"] == []  # no device plane here
        # no device plane on the CPU: the device readers return nothing
        assert "device_idle_share" not in out["metrics"]
    else:
        assert set(out["metrics"]) == want
        assert out["metrics"]["qps"]["value"] > 0


def test_without_a_tpu_there_is_no_result():
    proc = drive("chipbench.run", "--workload", CELLS[0], "--seed",
                 "1", "--seconds", "1", "--trace", "0")
    assert proc.returncode != 0 and proc.stdout.strip() == ""


def test_outside_the_repo_there_is_no_result(tmp_path):
    shutil.copytree(CHIPBENCH, tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns(".store", "__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    proc = drive("chipbench.run", "--workload", CELLS[0], "--seed",
                 "1", "--seconds", "1", "--trace", "0", "--rehearsal",
                 cwd=str(tmp_path))
    assert proc.returncode != 0 and proc.stdout.strip() == ""


# -- correct: the faults and the control ---------------------------------------


@pytest.mark.parametrize("name", CELLS)
def test_an_answer_altered_where_it_is_produced_is_not_correct(name):
    out = last_json(drive(
        "chipbench.tests.faults", mix_of(name)["fault"], "--workload", name, "--seed", str(BIG_SEED), "--seconds", "3",
        "--trace", "0"))
    assert out["correct"] is False
    assert any(not _ok(c) for c in out["checks"].values())


def _ok(c):
    value, op, limit = c
    return value <= limit if op == "<=" else value >= limit


def test_a_fault_that_is_no_file_fails_loudly():
    """A mix names its fault; a name with no `chipbench/faults/<name>.py`
    ends the run with no result and says which file is missing."""
    proc = drive("chipbench.tests.faults", "no_such_fault", "--workload",
                 CELLS[0], "--seed", "1", "--seconds", "1", "--trace", "0")
    assert proc.returncode != 0 and proc.stdout.strip() == ""
    assert "chipbench/faults/no_such_fault.py" in proc.stderr


@pytest.mark.parametrize("name", CELLS)
def test_the_control_comes_out_not_correct(name):
    out = last_json(drive("chipbench.control", "--workload", name,
                          "--seed", str(BIG_SEED), "--seconds", "3",
                          "--rehearsal"))
    assert out["program"]["correct"] is True
    assert out["control"]["correct"] is False


def test_compare_counts_a_wrong_answer():
    from chipbench import run
    from chipbench.data import snb
    from chipbench.queries import ic1

    config = config_of("snb.ic1")
    config["sizes"].update(config["rehearsal"])
    model = snb.make(config, 5)
    mix = mix_of("snb.ic1")
    params = mix["kinds"][0]["params"]
    rng = np.random.default_rng(1)
    cat = snb.catalog(config, 5)
    keys = [ic1.request(cat, params, rng)[0] for _ in range(4)]
    recs = [{"kind": 0, "key": k, "answer": a, "error": None}
            for k, a in zip(keys, ic1.reference(model, params, keys))]
    assert all(0 < len(r["answer"]) <= 60 for r in recs)

    def compare(recs, failed_requests):
        return run.judge(config, run.numbers_of(mix, model, recs),
                         failed_requests)

    assert all(c["ok"] for c in compare(recs, 0).values())
    recs[2]["answer"] = recs[2]["answer"][1:]
    got = compare(recs, 0)
    assert got["wrong_answers"]["value"] == 1 and not got["wrong_answers"]["ok"]
    row = list(recs[1]["answer"][0])
    row[2] = "Nobody"  # one field of one row
    recs[1]["answer"] = [tuple(row)] + recs[1]["answer"][1:]
    assert compare(recs, 0)["wrong_answers"]["value"] == 2
    assert not compare(recs[:1], 1)["failed_requests"]["ok"]


def _vector_case(n_keys, vectors=6000):
    from chipbench.data import mog
    from chipbench.queries import similar_to as kind

    vconf = config_of("vec.solo16")
    vconf["sizes"].update(vectors=vectors, dim=32)
    model, params = mog.make(vconf, 3), {"k": 10}
    cat = mog.catalog(vconf, 3)
    rng = np.random.default_rng(2)
    keys = [kind.request(cat, params, rng)[0] for _ in range(n_keys)]
    exact = [np.sort(u) for u, _ in model.topk(np.stack(keys), 10)]
    return vconf, model, params, keys, exact


def _vector_verdict(vconf, model, params, keys, answers):
    """The configuration's own limits over the answers' numbers (the
    probe tap's numbers left out: nothing was tapped here)."""
    from chipbench import run
    from chipbench.queries import similar_to as kind

    checks = run.judge(vconf, kind.check(model, params, keys, answers), 0)
    return {n: c for n, c in checks.items() if not n.startswith("probe")}


def test_planted_vector_faults_read_over_the_limit():
    """A wrong neighbour, as near a miss as can be made, in every answer
    or in every third, comes out not correct; the exact answers read 0."""
    from chipbench.queries import similar_to as kind

    vconf, model, params, keys, exact = _vector_case(48)
    got = kind.check(model, params, keys, exact)
    assert max(got["dist_excess"]) == 0 and max(got["inexact_answers"]) == 0
    assert all(c["ok"] for c in _vector_verdict(
        vconf, model, params, keys, exact).values())
    planted = kind.faults(model, params, keys, exact, 3)
    assert set(planted) == set(kind.FAULTS)
    for name, answers in planted.items():
        got = kind.check(model, params, keys, answers)
        wrong = [i for i, x in enumerate(got["inexact_answers"]) if x]
        assert len(wrong) == (16 if name.endswith("_third") else 48)
        assert min(got["dist_excess"][i] for i in wrong) > 0
        assert max(got["recall_at_k"][i] for i in wrong) == 0.9
        verdict = _vector_verdict(vconf, model, params, keys, answers)
        assert not verdict["inexact_answers"]["ok"], name
        assert verdict["recall_at_k"]["ok"] == name.endswith("_third")


def test_a_lone_miss_of_the_approximate_index_stays_correct():
    """The index is approximate: one answer of a run's 257 that names
    the next nearest row in place of a neighbour is a sound answer (the
    check's refusal of PR 24 was one such). The same answer naming a row
    of another gaussian is not."""
    from chipbench.data import mog
    from chipbench.queries import similar_to as kind

    vconf, model, params, keys, exact = _vector_case(257, vectors=20000)
    miss = kind.faults(model, params, keys, exact, 3)["next_nearest"]
    answers = list(exact)
    answers[100] = miss[100]
    verdict = _vector_verdict(vconf, model, params, keys, answers)
    assert verdict["inexact_answers"]["value"] == 1 / 257
    assert verdict["dist_excess"]["value"] > 0
    assert all(c["ok"] for c in verdict.values())
    far = exact[100].copy()
    own = model.labels[int(far[0]) - mog.UID_BASE]
    far[0] = mog.UID_BASE + int(np.flatnonzero(model.labels != own)[0])
    answers[100] = np.sort(far)
    verdict = _vector_verdict(vconf, model, params, keys, answers)
    assert verdict["inexact_answers"]["ok"]
    assert not verdict["dist_excess"]["ok"]


# -- the judging rule of a mix that writes, on histories made by hand -------------


def _toy():
    """A mix over a model that is a set of names: kind 0 writes (adds
    its key), kind 1 reads (answers the names, sorted); the control
    answers as the reference does."""
    from types import SimpleNamespace

    add = SimpleNamespace(
        WRITES=True,
        apply=lambda model, params, key, answer: model.add(key),
        check=lambda model, params, keys, answers, captured=None: {})
    look = SimpleNamespace(
        check=lambda model, params, keys, answers, captured=None: {
            "wrong_answers": [float(a != sorted(model)) for a in answers]},
        control=lambda model, params, keys: ([sorted(model)] * len(keys),
                                              None))
    return {"kinds": [{"kind": "add", "params": {}},
                      {"kind": "look", "params": {}}]}, [add, look]


def _write(key, sent, done, commit_ts):
    return {"kind": 0, "key": key, "answer": "0x1", "sent": sent,
            "attempt_sent": sent, "done": done, "commit_ts": commit_ts,
            "retries": 0, "error": None}


def _read(sent, done, answer):
    return {"kind": 1, "key": None, "answer": answer, "sent": sent,
            "done": done, "error": None}


# (writes as (key, sent, done, commit ts), the read as (sent, done,
# answer), wrong, changed by writes, order violated)
HISTORIES = {
    "read_after_ack_misses_the_write": (
        [("a", 0, 1, 1)], (2, 3, []), 1, 0, 0),
    "read_after_ack_sees_the_write": (
        [("a", 0, 1, 1)], (2, 3, ["a"]), 0, 1, 0),
    "concurrent_write_missed": (
        [("a", 1, 3, 1)], (0.5, 2.5, []), 0, 0, 0),
    "concurrent_write_seen": (
        [("a", 1, 3, 1)], (0.5, 2.5, ["a"]), 0, 1, 0),
    "read_sees_a_write_sent_after_its_answer": (
        [("a", 2, 3, 1)], (0, 1, ["a"]), 1, 0, 0),
    "read_sees_w2_without_w1": (
        [("a", 0, 5, 1), ("b", 0, 5, 2)], (1, 2, ["b"]), 1, 0, 0),
    "read_sees_a_prefix_of_the_commits": (
        [("a", 0, 5, 1), ("b", 0, 5, 2)], (1, 2, ["a"]), 0, 1, 0),
    "commit_order_against_real_time": (
        [("a", 0, 1, 2), ("b", 3, 4, 1)], (2, 2.5, ["a"]), 1, 0, 1),
}


@pytest.mark.parametrize("case", sorted(HISTORIES))
def test_a_read_is_judged_by_the_commits_it_could_have_seen(case):
    from chipbench import history

    writes, (sent, done, answer), wrong, changed, violated = HISTORIES[case]
    mix, kinds = _toy()
    past = sorted((_write(*w) for w in writes), key=lambda w: w["commit_ts"])
    got = history.numbers(mix, kinds, set(), [_read(sent, done, answer)],
                          past)
    assert got["wrong_answers"] == [float(wrong)]
    assert got["reads_changed_by_writes"] == [float(changed)]
    assert got["order_violations"] == [float(violated)]
    assert got["writes_committed"] == [1.0] * len(writes)


def test_the_control_reads_one_acknowledged_write_behind():
    from chipbench import history

    mix, kinds = _toy()
    reads = [_read(2, 3, ["a"]), _read(0, 0.5, [])]
    past = [_write("a", 0, 1, 1)]
    assert history.numbers(mix, kinds, set(), reads, past)[
        "wrong_answers"] == [0.0, 0.0]
    # the read sent after the ack answers as state 0 does; the read
    # before any write has no state behind it and stays right
    assert history.numbers(mix, kinds, set(), reads, past, stale=True)[
        "wrong_answers"] == [1.0, 0.0]


def test_a_mix_that_only_reads_gets_the_numbers_it_always_got():
    """With no write the rule has one state: the numbers are
    `run.numbers_of`'s, name for name and answer for answer, a wrong
    answer among them, and the harness adds none of its own."""
    from chipbench import history, run
    from chipbench.data import snb

    config = _rehearsal_config("snb.short16")
    model, cat = snb.make(config, 5), snb.catalog(config, 5)
    mix = mix_of("snb.short16")
    kinds = [run.kind_of(k) for k in mix["kinds"]]
    rng = np.random.default_rng(3)
    recs = []
    for i in range(40):
        ki = i % len(kinds)
        params = mix["kinds"][ki]["params"]
        key = kinds[ki].request(cat, params, rng)[0]
        recs.append({"kind": ki, "key": key, "sent": i, "done": i + 0.5,
                     "error": None,
                     "answer": kinds[ki].reference(model, params, [key])[0]})
    recs[3]["answer"] = recs[3]["answer"][1:] or [("nobody",)]
    old = run.numbers_of(mix, model, recs)
    assert history.numbers(mix, kinds, model, recs, []) == old
    assert sum(old["wrong_answers"]) == 1


# -- the trace reduction ----------------------------------------------------------


def test_union_of_busy_intervals():
    from chipbench import trace_reduce as T

    secs, merged = T.union_seconds([(0, 10), (5, 20), (30, 40), (32, 35)])
    assert secs == 30 / 1e9 and merged == [[0, 20], [30, 40]]
    planes = [("/device:TPU:0", [
        ("XLA Modules", [("jit_f(1)", 0, 650), ("jit_g(2)", 790, 200)]),
        ("XLA Ops", [("a", 0, 400), ("b", 300, 200), ("c", 600, 50),
                     ("a", 800, 100)])]),
        ("/host:CPU", [("t", [("py", 0, 5000)])])]
    r = T.reduce_planes(planes)
    assert r["device_planes"] == 1 and r["busy_s"] == 650 / 1e9
    assert r["top_ops"][0] == ["a", 500 / 1e9]
    assert r["top_gaps"] == [["before:jit_g", 150 / 1e9],
                             ["within:jit_f", 100 / 1e9]]
    assert T.reduce_planes([])["device_planes"] == 0


def test_reduction_of_the_recorded_chip_trace():
    """The first 1.2 s of a 64-root friends-of-friends trace from the v5e
    (PR 24, the same `intersect#shared` programs snb.ic1 runs): the
    busy time is known from an independent sweep over its op events."""
    from chipbench import trace_reduce as T

    path = os.path.join(HERE, "recorded_trace.json")
    r = T.reduce_dir(path)
    with open(path) as f:
        ops = dict(dict((p, l) for p, l in json.load(f))["/device:TPU:0"])[
            "XLA Ops"]
    edges = sorted([(s, 1) for _, s, d in ops] + [(s + d, -1) for _, s, d in ops])
    depth = busy = 0
    for (t, step), (t_next, _) in zip(edges, edges[1:]):
        depth += step
        busy += (t_next - t) if depth > 0 else 0
    assert r["device_planes"] == 1
    assert r["busy_s"] == pytest.approx(busy / 1e9, rel=1e-9)
    assert r["busy_s"] == pytest.approx(1.168174364, rel=1e-6)
    idle = importlib.import_module("chipbench.layer_metrics.device_idle_share")
    share = idle.read({"trace": dict(r, window_s=1.2)})
    assert share == pytest.approx(100 * (1 - 1.168174364 / 1.2), rel=1e-6)
    assert idle.read({"trace": dict(T.reduce_planes([]), window_s=1.2)}) is None


def test_roofline_reader_counts_bytes_and_refuses_unknown_devices():
    R = importlib.import_module("chipbench.layer_metrics.vec_probe_roofline")
    assert R.probe_bytes(1000, 768, 10) == 1000 * (768 * 4 + 8) + 10 * (768 * 4 + 4)
    with open(os.path.join(CHIPBENCH, "peaks.json")) as f:
        peaks = json.load(f)
    ctx = {"describe": {"probe_rows": 65536, "dim": 768, "nlist": 2000},
           "trace": {"busy_s": 0.5}, "traced_requests": 500, "requests": 900,
           "fetches": {"vector:ivf_batch": 900}, "device_kind": "TPU v5 lite",
           "peaks": peaks}
    least = R.probe_bytes(65536, 768, 2000) / 819e9
    assert R.read(ctx) == pytest.approx(100 * least / 1e-3)
    assert R.read(dict(ctx, fetches={"vector:brute_batch": 1,
                                     "vector:ivf_batch": 899})) is None
    with pytest.raises(KeyError):
        R.read(dict(ctx, device_kind="TPU v9"))


# -- draws and data are functions of the seed alone -----------------------------


def test_draws_and_data_are_functions_of_the_seed():
    from chipbench import draw
    from chipbench.data import mog, snb

    a, b, c = (draw.stream(BIG_SEED, 1, n).integers(0, 1 << 30, 8)
               for n in (3, 3, 4))
    assert np.array_equal(a, b) and not np.array_equal(a, c)

    config = config_of("snb.ic1")
    config["sizes"].update(config["rehearsal"])
    m1, m2, m3 = (snb.make(config, s) for s in (BIG_SEED, BIG_SEED, 5))
    assert np.array_equal(m1.pairs, m2.pairs)
    assert not np.array_equal(m1.pairs, m3.pairs)
    # every seed has the same degree wanted at every popularity rank
    n = config["sizes"]["persons"]
    assert np.array_equal(
        snb.degree_sequence(n, config["sizes"]["knows_pairs"], 1.1, 700),
        snb.degree_sequence(n, config["sizes"]["knows_pairs"], 1.1, 700))
    # every seed serves the same graph under other names
    assert np.array_equal(m1.degrees()[snb.rank_order(n, BIG_SEED)],
                          m3.degrees()[snb.rank_order(n, 5)])
    # so the curated start persons are the same ranks under every seed,
    # and a client's requests are a function of the seed
    from chipbench.queries import ic1

    params = mix_of("snb.ic1")["kinds"][0]["params"]
    cats = [snb.catalog(config, s) for s in (BIG_SEED, BIG_SEED, 5)]
    ranks = [np.sort(np.argsort(snb.rank_order(n, s))[ic1.curated(c, params)])
             for c, s in zip(cats, (BIG_SEED, BIG_SEED, 5))]
    assert np.array_equal(ranks[0], ranks[2]) and 0 < len(ranks[0]) < n / 5
    texts = [[ic1.request(c, params, draw.stream(s, 1, 0))[1]
              for _ in range(3)] for c, s in zip(cats, (BIG_SEED, BIG_SEED, 5))]
    assert texts[0] == texts[1] and texts[0] != texts[2]
    assert m1.person(7) == m2.person(7) and m1.person(7) != m3.person(7)

    vconf = config_of("vec.solo16")
    vconf["sizes"].update(vconf["rehearsal"])
    assert np.array_equal(mog.corpus(vconf, BIG_SEED), mog.corpus(vconf, BIG_SEED))
    assert not np.array_equal(mog.corpus(vconf, BIG_SEED), mog.corpus(vconf, 5))


# -- the plain model's message side against the n-quads the store is loaded from --


def _rehearsal_config(name="snb.ic1"):
    config = config_of(name)
    return dict(config, sizes=dict(config["sizes"], **config["rehearsal"]))


def _sha256(path):
    import hashlib

    with open(path, "rb") as f:
        return hashlib.sha256(f.read()).hexdigest()


@pytest.mark.parametrize("seed", sorted(NQUADS_SHA256))
def test_the_nquads_of_a_config_and_seed_are_pinned(seed, tmp_path):
    """The bytes the bulk loader reads are what they were before the
    model got its message side: a change to a draw's order or bound
    would serve another graph under `snb.ic1`."""
    from chipbench.data import snb

    path = str(tmp_path / "snb.rdf")
    model = snb.make(_rehearsal_config(), seed, path)
    assert _sha256(path) == NQUADS_SHA256[seed]
    with open(path) as f:
        assert model.nquads == sum(1 for _ in f)


def _epoch_ms(literal):
    """'"2011-..Z"^^<xs:dateTime>' as epoch ms, read without the maker."""
    import datetime

    text = literal.split('"')[1]
    assert literal == f'"{text}"^^<xs:dateTime>'
    t = datetime.datetime.fromisoformat(text.replace("Z", "+00:00"))
    return round(t.timestamp() * 1000)


@pytest.fixture(scope="module")
def written(tmp_path_factory):
    """(model, {predicate: {subject uid: [object text]}}, {(subject,
    object) of a `knows`: facet text}) of one rehearsal-size file."""
    from chipbench.data import snb

    path = str(tmp_path_factory.mktemp("snb") / "snb.rdf")
    model = snb.make(_rehearsal_config(), BIG_SEED, path)
    triples, facets = {}, {}
    with open(path) as f:
        for line in f.read().split("\n"):
            body = line[: -len(" .")]
            assert line == body + " ."
            subject, pred, obj = body.split(" ", 2)
            s = int(subject[len("<0x"):-1], 16)
            if obj.endswith(")"):
                obj, facet = obj[:-1].split(" (", 1)
                facets[s, int(obj[len("<0x"):-1], 16)] = facet
            triples.setdefault(pred[1:-1], {}).setdefault(s, []).append(obj)
    return model, triples, facets


def _ref(uid):
    return f"<0x{int(uid):x}>"


def _lit(text):
    return '"' + text + '"'


def _expected(model, pred):
    """{subject uid: [object text]} of EVERY `pred` triple, from the
    model's columns alone."""
    from chipbench.data import snb

    msgs = model.messages()
    every = range(len(msgs))
    forums = range(msgs.n_forums)
    if pred == "hasCreator":
        return {msgs.uid(i): [_ref(snb.person_uid(msgs.creator[i]))]
                for i in every}
    if pred == "replyOf":
        return {msgs.uid(i): [_ref(msgs.uid(msgs.parent[i]))]
                for i in every if msgs.parent[i] >= 0}
    if pred in ("content", "imageFile"):
        return {msgs.uid(i): [_lit(msgs.text(i)[pred])]
                for i in every if pred in msgs.text(i)}
    if pred == "fqid":
        return {msgs.uid(i): [_lit(msgs.fqid(i))] for i in every}
    if pred == "containerOf":
        out = {}
        for j in range(msgs.n_posts):
            out.setdefault(msgs.forum_uid(msgs.forum_of_post(j)),
                           []).append(_ref(msgs.uid(j)))
        return out
    if pred == "hasModerator":
        return {msgs.forum_uid(f): [_ref(snb.person_uid(msgs.moderator[f]))]
                for f in forums}
    if pred == "title":
        return {msgs.forum_uid(f): [_lit(msgs.forum_title(f))]
                for f in forums}
    raise KeyError(pred)


@pytest.mark.parametrize("pred", ["hasCreator", "replyOf", "content",
                                  "imageFile", "fqid", "containerOf",
                                  "hasModerator", "title"])
def test_the_model_says_every_triple_the_file_holds(written, pred):
    from chipbench.data import snb

    model, triples, _ = written
    got = triples[pred]
    if pred == "fqid":  # the persons' and forums' are not the messages'
        got = {s: o for s, o in got.items()
               if not o[0].startswith(('"person_', '"forum_'))}
    assert got == _expected(model, pred)
    msgs = model.messages()
    assert (len(msgs), msgs.n_posts, msgs.n_forums) == (900, 300, 30)
    if pred == "replyOf":  # a comment answers an EARLIER message, later
        replies = np.flatnonzero(msgs.parent >= 0)
        assert np.array_equal(replies, np.arange(300, 900))
        assert (msgs.parent[replies] < replies).all()
        assert (msgs.ms[replies] > msgs.ms[msgs.parent[replies]]).all()
        assert (msgs.parent[replies] >= 300).any()  # some to a comment


def test_the_model_says_every_date_the_file_holds(written):
    """Every `creationDate` triple (persons and messages) and every
    `knows|creationDate` facet, as epoch milliseconds."""
    from chipbench.data import snb

    model, triples, facets = written
    msgs = model.messages()
    got = {s: [_epoch_ms(o) for o in objs]
           for s, objs in triples["creationDate"].items()}
    want = {msgs.uid(i): [int(msgs.ms[i])] for i in range(len(msgs))}
    want.update({snb.person_uid(i): [_epoch_ms(
        f'"{model.person(i)["creationDate"]}"^^<xs:dateTime>')]
        for i in range(model.n)})
    assert got == want
    assert len(model.knows_ms) == len(model.pairs)
    want = {}
    for (a, b), at in zip(model.pairs.tolist(), model.knows_ms.tolist()):
        ua, ub = snb.person_uid(a), snb.person_uid(b)
        want[ua, ub] = want[ub, ua] = at
    assert {k: _epoch_ms(v[len("creationDate="):])
            for k, v in facets.items()} == want
    assert all(v.startswith("creationDate=") for v in facets.values())
    assert sorted(facets) == sorted(
        (s, int(o[len("<0x"):-1], 16))
        for s, objs in triples["knows"].items() for o in objs)


def test_the_models_views_agree_with_its_columns(written):
    model, _, _ = written
    msgs = model.messages()
    assert msgs is model.messages()  # built once, kept
    mine = [msgs.by_creator(p) for p in range(model.n)]
    assert sum(map(len, mine)) == len(msgs)
    assert all((msgs.creator[m] == p).all() and (np.diff(m) > 0).all()
               for p, m in enumerate(mine))
    answered = [msgs.replies(i) for i in range(len(msgs))]
    assert sum(map(len, answered)) == msgs.n_comments
    assert all((msgs.parent[r] == i).all() and (np.diff(r) > 0).all()
               for i, r in enumerate(answered))
    assert msgs.is_post(299) and not msgs.is_post(300)
    assert (msgs.sid(0), msgs.sid(299), msgs.sid(300)) == (
        3, 3 + 299 * 11, 1099511627777)
    # uids: places, persons, posts, comments, forums, with no hole
    from chipbench.data import snb

    assert msgs.uid(0) == snb.person_uid(model.n - 1) + 1
    assert msgs.forum_uid(0) == msgs.uid(len(msgs) - 1) + 1


def test_messages_are_a_function_of_config_and_seed():
    """The same (configuration, seed) gives the same message side with
    no file written, another seed another, and the sizes in force are
    the model's: a query kind reads them there, not from a file."""
    from chipbench.data import snb
    from chipbench.queries import ic1

    config = _rehearsal_config()
    a, b, c = (snb.make(config, s) for s in (BIG_SEED, BIG_SEED, 5))
    assert a.sizes == config["sizes"] and a._messages is None
    # what `snb.ic1` asks of the model never builds the message side
    params = mix_of("snb.ic1")["kinds"][0]["params"]
    ic1.reference(a, params, [np.array([7, 3])])
    ic1.control(a, params, [np.array([7, 3])])
    assert a._messages is None and a._draws is None
    for col in ("creator", "ms", "parent", "moderator"):
        assert np.array_equal(getattr(a.messages(), col),
                              getattr(b.messages(), col))
        assert not np.array_equal(getattr(a.messages(), col),
                                  getattr(c.messages(), col))
    assert np.array_equal(a.knows_ms, b.knows_ms)
    assert [a.messages().text(i) for i in (0, 1, 2, 300, 899)] == [
        b.messages().text(i) for i in (0, 1, 2, 300, 899)]
    full = snb.make(config_of("snb.ic1"), 5)
    assert full.sizes["posts"] == 100360 and full._messages is None
    with pytest.raises(ValueError):  # a control's cut-down copy
        snb.Model(a.n, a.pairs[:10], a.seed).messages()


def test_exact_topk_reference_is_exact():
    from chipbench.data import mog

    vconf = config_of("vec.solo16")
    vconf["sizes"].update(vectors=3000, dim=32)
    model = mog.make(vconf, 3)
    Q = mog.centers_of(vconf, 3)[:5] + 0.5
    for q, (uids, d) in zip(Q, model.topk(Q, 10)):
        full = ((model.V.astype(np.float64) - q.astype(np.float64)) ** 2).sum(1)
        order = np.argsort(full, kind="stable")[:10]
        assert np.array_equal(uids, order.astype(np.uint64) + mog.UID_BASE)
        assert np.allclose(d, full[order])


# -- a later PR adds files and entries, and edits nothing ---------------------------

NEWEST_KIND = '''"""Query kind of a test: the newest messages of a person, newest first
(LDBC's short read 2 without its reply chain), from the plain model's
message side."""
from chipbench.data import snb


def request(catalog, params, rng):
    msgs = catalog["model"].messages()
    person = int(msgs.creator[rng.integers(len(msgs))])  # one who has written
    return person, (
        '{ q(func: eq(fqid, "person_%d")) { ~hasCreator(orderdesc: '
        "creationDate, first: %d) { id creationDate content imageFile } } }"
        % (snb.person_sid(person), params["first"]))


def parse(body):
    if "errors" in body:
        raise ValueError(str(body["errors"])[:200])
    return [(r["id"], snb.epoch_ms(r["creationDate"]), r.get("content"),
             r.get("imageFile")) for r in body["data"]["q"][0]["~hasCreator"]]


def reference(model, params, keys, stale=0):
    msgs = model.messages()
    out = []
    for person in keys:
        mine = sorted(msgs.by_creator(int(person)).tolist(),
                      key=lambda i: (-msgs.ms[i], i))
        out.append([(msgs.sid(i), int(msgs.ms[i]), msgs.text(i).get("content"),
                     msgs.text(i).get("imageFile"))
                    for i in mine[stale:][:params["first"]]])
    return out


def control(model, params, keys):
    """A store that served before its last write was synced: each
    person's newest message is missing."""
    return reference(model, params, keys, stale=1), None


def check(model, params, keys, answers, captured=None):
    want = reference(model, params, keys)
    return {"wrong_answers": [0.0 if list(a) == w else 1.0
                              for a, w in zip(answers, want)],
            "answers_compared": [1.0] * len(answers),
            "newest_compared": [float(len(a)) for a in answers]}
'''

ORDER_FAULT = '''"""Fault of a test: every ordered block loses its first row."""


def plant():
    from dgraph_tpu.query.subgraph import Executor

    orig = Executor._order_uids

    def broken(self, gq, uids, full=False):
        out = orig(self, gq, uids, full)
        return out[1:] if gq.order else out

    Executor._order_uids = broken
'''


def test_new_pieces_need_only_new_files(tmp_path):
    """In a copy of the benchmark, with no file of the copy edited: a
    configuration with its own rehearsal sizes and `rehearsal_env`, a
    fault file and a mix that names it, a query kind whose reference
    reads the plain model's message side, a second kind in the same
    mix and a layer reader, as new files plus BENCHMARK.json entries.
    On that new cell: the rehearsal, the fault run (`correct` false)
    and the control (`correct` false)."""
    ignore = shutil.ignore_patterns(".store", "__pycache__")
    shutil.copytree(CHIPBENCH, tmp_path / "chipbench", ignore=ignore)
    cb = tmp_path / "chipbench"
    before = {p: p.read_bytes() for p in cb.rglob("*") if p.is_file()}
    config = config_of("snb.ic1")
    config.update(
        name="snb-small",
        rehearsal={"persons": 1500, "knows_pairs": 12000, "posts": 3000,
                   "comments": 6000, "forums": 40},
        # lower than snb-sf1's: at 1,500 persons only this takes IC1's
        # third level to the jitted set ops, and nothing else sets it
        rehearsal_env={"DGRAPH_TPU_DEVICE_MIN_TOTAL": "1024"})
    config["checks"]["newest_compared"] = {"agg": "sum", "op": ">=",
                                           "limit": 1}
    (cb / "configs" / "snb-small.json").write_text(json.dumps(config))
    (cb / "queries" / "ic1_again.py").write_text(
        "from chipbench.queries.ic1 import *  # noqa: F401,F403\n")
    (cb / "queries" / "newest.py").write_text(NEWEST_KIND)
    (cb / "faults" / "order_drops_first.py").write_text(ORDER_FAULT)
    mix = mix_of("snb.ic1")
    mix.update(name="walls", clients=2, fault="order_drops_first",
               kinds=[{"kind": "ic1_again", "weight": 1,
                       "params": {"limit": 5, "band": [0.3, 0.7]}},
                      {"kind": "newest", "weight": 3,
                       "params": {"first": 10}}])
    (cb / "mixes" / "walls.json").write_text(json.dumps(mix))
    (cb / "layer_metrics" / "requests_answered.py").write_text(
        "def read(ctx):\n    return float(ctx['requests'])\n")
    bench = json.loads(json.dumps(BENCH))
    bench["configs"].append({
        "name": "snb-small", "source": "test",
        "file": "chipbench/configs/snb-small.json",
        "reduced": config["reduced"], "why": "test"})
    bench["workloads"].append({"name": "small.walls", "config": "snb-small",
                               "traffic": "walls", "chips": 1, "why": "test"})
    bench["per_layer"].append({
        "name": "requests_answered", "unit": "count", "better": "higher",
        "source": "program_counter", "layer": "wire", "moves": "qps",
        "workloads": ["small.walls"]})
    for m in bench["per_layer"]:
        if m["name"] == "store_open_s":
            m["workloads"].append("small.walls")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    assert "DGRAPH_TPU_DEVICE_MIN_TOTAL" not in os.environ

    def run_it(module, *args):
        return last_json(drive(
            module, *args, "--workload", "small.walls", "--seed", "9",
            "--seconds", "3", cwd=str(tmp_path), extra_path=[ROOT]))

    out = run_it("chipbench.run", "--trace", "1", "--rehearsal")
    assert out["correct"] is True and out["failed"] == 0
    assert out["metrics"]["requests_answered"]["value"] > 0
    assert "store_open_s" in out["metrics"]
    assert out["metrics"]["device_ops_per_req"]["value"] > 0
    # both kinds were sent and compared: rows of `newest`, answers of both
    compared = out["checks"]["answers_compared"][0]
    assert compared == out["attempted"]
    assert out["checks"]["newest_compared"][0] > compared / 2

    out = run_it("chipbench.tests.faults", mix["fault"], "--trace", "0")
    assert out["correct"] is False and out["checks"]["wrong_answers"][0] > 0

    out = run_it("chipbench.control", "--rehearsal")
    assert out["program"]["correct"] is True
    assert out["control"]["correct"] is False

    after = {p: p.read_bytes() for p in cb.rglob("*") if p.is_file()
             and ".store" not in p.parts and "__pycache__" not in p.parts}
    added = {str(p.relative_to(cb)) for p in set(after) - set(before)}
    assert added == {"configs/snb-small.json", "queries/ic1_again.py",
                     "queries/newest.py", "faults/order_drops_first.py",
                     "mixes/walls.json", "layer_metrics/requests_answered.py"}
    assert all(after[p] == data for p, data in before.items())


def test_a_writing_cell_needs_only_new_files(tmp_path, monkeypatch):
    """In a copy of the benchmark, with no file of the copy edited: a
    query kind that writes, a maker whose model takes what it writes, a
    mix that pairs it with `is3`, a fault that serves reads one commit
    behind (`chipbench/tests/write_cell.py`). The rehearsal is correct
    with writes committed and reads that saw them; the fault and the
    control are not; the seed's kept store is not touched by any run
    after the one that built it."""
    from chipbench import run
    from chipbench.tests import write_cell

    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    before = write_cell.build(str(tmp_path))

    def run_it(module, *args):
        return last_json(write_cell.drive(str(tmp_path), module, *args))

    out = run_it("chipbench.run", "--trace", "0", "--rehearsal")
    assert out["correct"] is True and out["failed"] == 0
    assert out["checks"]["writes_committed"][0] >= 1
    assert out["checks"]["reads_changed_by_writes"][0] >= 1
    install = out["setup"]["install"]
    assert install["built"]["loaded"] is True and install["copy_s"] >= 0
    store = tmp_path / "chipbench" / ".store"
    kept = {p: p.read_bytes() for p in store.rglob("*") if p.is_file()}
    assert kept and not any(run.RUN_COPY in p.parts for p in kept)

    out = run_it("chipbench.tests.faults", "reads_behind", "--trace", "0")
    assert out["correct"] is False and out["checks"]["wrong_answers"][0] > 0
    assert "built" not in out["setup"]["install"]

    out = run_it("chipbench.control", "--rehearsal")
    assert out["program"]["correct"] is True
    assert out["control"]["correct"] is False

    assert {p: p.read_bytes() for p in store.rglob("*")
            if p.is_file()} == kept
    after = {str(p): p.read_bytes()
             for p in (tmp_path / "chipbench").rglob("*") if p.is_file()
             and ".store" not in p.parts and "__pycache__" not in p.parts}
    cb = str(tmp_path / "chipbench") + os.sep
    assert {p[len(cb):] for p in set(after) - set(before)} == write_cell.ADDED
    assert all(after[p] == data for p, data in before.items())
