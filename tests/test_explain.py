"""EXPLAIN/ANALYZE query introspection (the telemetry plane's debug
surface).

The golden gate: response `data` bytes must be IDENTICAL with the
debug flag on vs off over the DQL golden corpus (smoke subset tier-1,
full 535-case sweep slow-marked) — plan capture is observation-only.
Every smoke query's plan tree must also be present and schema-valid.
Plus: the CLI renderer snapshot, the HTTP ?debug=true surface, the
capture hooks (plan cache, admission, micro-batch, set-op decisions),
and the ProcCluster entry point.
"""

import json
import os

import pytest

HERE = os.path.join(os.path.dirname(os.path.abspath(__file__)), "ref_golden")
CASES = json.load(open(os.path.join(HERE, "cases.json")))
SMOKE_CASES = CASES[::9]  # same stride as test_stream_encoder's smoke set

_NODE_FIELDS = {
    "attr": str,
    "level": int,
    "uids_in": int,
    "uids_out": int,
    "read": str,
    "wall_ns": int,
    "kernels": dict,
    "children": list,
}


def _validate_node(node, path="nodes"):
    for field, typ in _NODE_FIELDS.items():
        assert field in node, f"{path}: missing {field!r} in {node}"
        assert isinstance(node[field], typ), (path, field, node[field])
    assert node["level"] >= 0
    assert node["uids_in"] >= 0 and node["uids_out"] >= 0
    for i, c in enumerate(node["children"]):
        assert c["level"] > node["level"], (path, node, c)
        _validate_node(c, f"{path}.children[{i}]")


def validate_plan(plan):
    """The extensions.plan schema the CLI renderer and dashboards
    consume — every field the tentpole names."""
    assert isinstance(plan, dict)
    for key, typ in (
        ("nodes", list),
        ("setops", list),
        ("microbatch", dict),
        ("plan_cache", dict),
        ("admission", dict),
        ("cache", dict),
        ("planner", dict),
        ("result_cache", dict),
    ):
        assert key in plan and isinstance(plan[key], typ), key
    for node in plan["nodes"]:
        _validate_node(node)
    for s in plan["setops"]:
        assert s.get("verdict") in ("packed", "decoded", "pushdown"), s
        assert s.get("site") in (
            "pair", "index_intersect", "level_filter"
        ), s
    mb = plan["microbatch"]
    assert set(mb) == {"solo", "coalesced", "members_max"}
    assert {"cost", "degrade", "enabled"} <= set(plan["admission"])
    assert "wall_ns" in plan and plan["wall_ns"] >= 0


@pytest.fixture(scope="module")
def golden_server():
    from dgraph_tpu.api.server import Server

    s = Server()
    s.alter(open(os.path.join(HERE, "schema.txt")).read())
    for rdf in ("triples.rdf", "triples_facets.rdf"):
        t = s.new_txn()
        t.mutate_rdf(
            set_rdf=open(os.path.join(HERE, rdf)).read(), commit_now=True
        )
    return s


def _data_bytes(d):
    """Wire bytes of a response's data: the raw arena shell when the
    streaming path produced one, else a canonical dump (schema blocks
    return plain dicts on the raw path too)."""
    raw = getattr(d, "raw", None)
    if raw is not None:
        return bytes(raw)
    return json.dumps(d, sort_keys=True).encode()


def _two_ways(server, q):
    """(plain data bytes, debug data bytes, plan) — or the matching
    error reprs when the query fails either way."""
    try:
        plain = _data_bytes(server.query(q, want="raw")["data"])
    except Exception as exc:
        plain = f"{type(exc).__name__}: {exc}"
    try:
        res = server.query(q, want="raw", debug=True)
        dbg = _data_bytes(res["data"])
        plan = res["extensions"].get("plan")
    except Exception as exc:
        dbg = f"{type(exc).__name__}: {exc}"
        plan = None
    return plain, dbg, plan


@pytest.mark.parametrize(
    "case", SMOKE_CASES, ids=[c["id"] for c in SMOKE_CASES]
)
def test_golden_debug_byte_equality_smoke(golden_server, case):
    plain, dbg, plan = _two_ways(golden_server, case["query"])
    assert plain == dbg
    if isinstance(plain, bytes):  # executed cleanly both ways
        assert plan is not None
        validate_plan(plan)


@pytest.mark.slow
@pytest.mark.parametrize("case", CASES, ids=[c["id"] for c in CASES])
def test_golden_debug_byte_equality_full(golden_server, case):
    plain, dbg, _plan = _two_ways(golden_server, case["query"])
    assert plain == dbg


# ---------------------------------------------------------------------------
# capture hooks
# ---------------------------------------------------------------------------


@pytest.fixture()
def small_server():
    from dgraph_tpu.api.server import Server

    s = Server()
    s.alter("name: string @index(exact) .\nfriend: [uid] .")
    t = s.new_txn()
    t.mutate_rdf(
        set_rdf=(
            '<0x1> <name> "A" .\n<0x2> <name> "B" .\n<0x3> <name> "C" .\n'
            "<0x1> <friend> <0x2> .\n<0x1> <friend> <0x3> .\n"
            "<0x2> <friend> <0x3> ."
        ),
        commit_now=True,
    )
    return s


def test_plan_tree_shape_and_counts(small_server):
    q = '{ q(func: eq(name, "A")) { name friend { name } } }'
    res = small_server.query(q, debug=True)
    plan = res["extensions"]["plan"]
    validate_plan(plan)
    (root,) = plan["nodes"]
    assert root["read"] == "root" and root["func"] == "eq"
    assert root["uids_out"] == 1
    by_attr = {c["attr"]: c for c in root["children"]}
    assert by_attr["friend"]["uids_in"] == 1
    assert by_attr["friend"]["uids_out"] == 2
    assert by_attr["friend"]["level"] == 1
    (gname,) = by_attr["friend"]["children"]
    assert gname["attr"] == "name" and gname["level"] == 2
    assert gname["uids_in"] == 2 and gname["uids_out"] == 2
    # plan-cache outcome captured with the normalized shape key
    assert plan["plan_cache"]["shape"].startswith("{ q ( func : eq")
    # second run: the same shape must now report a hit
    res2 = small_server.query(q, debug=True)
    assert res2["extensions"]["plan"]["plan_cache"]["hit"] is True
    # cache tiers: the whole query read through the memlayer
    assert res2["extensions"]["plan"]["cache"]["batch_reads"] >= 1


def test_plan_captures_admission_decision(small_server, monkeypatch):
    monkeypatch.setenv("DGRAPH_TPU_ADMISSION", "1")
    res = small_server.query(
        '{ q(func: has(name)) { name } }', debug=True
    )
    adm = res["extensions"]["plan"]["admission"]
    assert adm["enabled"] is True
    assert adm["cost"] >= 1.0
    assert adm["degrade"] is False


def test_plan_captures_setop_decisions(small_server):
    # a root filter routes through _index_src_intersect (the
    # StatsHolder decision site)
    q = '{ q(func: has(name)) @filter(eq(name, "B")) { name } }'
    res = small_server.query(q, debug=True)
    plan = res["extensions"]["plan"]
    sites = {s["site"] for s in plan["setops"]}
    assert "index_intersect" in sites, plan["setops"]
    rec = next(
        s for s in plan["setops"] if s["site"] == "index_intersect"
    )
    assert rec["attr"] == "name"
    assert rec["verdict"] in ("packed", "decoded")
    assert rec["src"] >= 1 and rec["min_ratio"] >= 1


def test_no_plan_without_debug(small_server):
    res = small_server.query('{ q(func: has(name)) { name } }')
    assert "plan" not in res["extensions"]
    # and the capture hooks see no active plan outside a debug query
    from dgraph_tpu.utils.observe import current_plan

    assert current_plan() is None


def test_explain_counter_ticks(small_server):
    from dgraph_tpu.utils.observe import METRICS

    before = METRICS.value("explain_queries_total")
    small_server.query('{ q(func: has(name)) { name } }', debug=True)
    assert METRICS.value("explain_queries_total") == before + 1


# ---------------------------------------------------------------------------
# CLI renderer
# ---------------------------------------------------------------------------


def test_render_plan_snapshot(small_server):
    """The rendered plan is a stable contract: one header, the
    decision lines, one indented line per node."""
    from dgraph_tpu.cli import render_plan

    res = small_server.query(
        '{ q(func: eq(name, "A")) { friend { uid } } }', debug=True
    )
    out = render_plan(res["extensions"]["plan"])
    lines = out.splitlines()
    assert lines[0].startswith("Query plan (wall ")
    assert any(l.startswith("  plan cache: ") for l in lines)
    assert any(l.startswith("  admission: ") for l in lines)
    assert any(l.startswith("  cache: ") for l in lines)
    assert "  q (root func=eq) -> 1 uids" in lines
    (friend_line,) = [
        l for l in lines if l.lstrip().startswith("friend level=")
    ]
    assert friend_line.startswith("    friend level=1 [batched] 1 -> 2 uids")


def test_cli_explain_local(small_server, tmp_path, capsys):
    """dgraph-tpu explain against a data dir renders a plan."""
    from dgraph_tpu.cli import main as cli_main

    d = str(tmp_path / "data")
    from dgraph_tpu.api.server import Server

    s = Server(data_dir=d)
    s.alter("name: string @index(exact) .")
    s.new_txn().mutate_rdf(
        set_rdf='<0x1> <name> "A" .', commit_now=True
    )
    s.kv.sync()
    rc = cli_main(
        ["explain", "-p", d, '{ q(func: has(name)) { name } }']
    )
    out = capsys.readouterr().out
    assert rc == 0
    assert out.startswith("Query plan")
    assert "name level=1" in out


# ---------------------------------------------------------------------------
# transport surfaces
# ---------------------------------------------------------------------------


def test_http_debug_flag(small_server):
    import urllib.request

    from dgraph_tpu.api.http_server import HTTPServer

    srv = HTTPServer(small_server, port=0).start()
    try:
        url = f"http://127.0.0.1:{srv.port}/query"
        q = '{ q(func: has(name)) { name } }'

        def post(u):
            req = urllib.request.Request(
                u, data=q.encode(), method="POST",
                headers={"Content-Type": "application/dql"},
            )
            return json.loads(urllib.request.urlopen(req, timeout=10).read())

        plain = post(url)
        dbg = post(url + "?debug=true")
        assert plain["data"] == dbg["data"]
        assert "plan" not in plain.get("extensions", {})
        validate_plan(dbg["extensions"]["plan"])
        # JSON body spelling too
        req = urllib.request.Request(
            url,
            data=json.dumps({"query": q, "debug": True}).encode(),
            method="POST",
            headers={"Content-Type": "application/json"},
        )
        viajson = json.loads(
            urllib.request.urlopen(req, timeout=10).read()
        )
        assert viajson["data"] == plain["data"]
        assert "plan" in viajson["extensions"]
    finally:
        srv.stop()


def test_proc_cluster_debug_flag():
    from dgraph_tpu.worker.harness import ProcCluster

    c = ProcCluster(n_groups=1, replicas=1)
    try:
        c.alter("name: string @index(exact) .")
        c.new_txn().mutate_rdf(
            set_rdf='<0x1> <name> "A" .\n<0x2> <name> "B" .',
            commit_now=True,
        )
        q = '{ q(func: has(name)) { name } }'
        plain = c.query(q, want="raw")
        dbg = c.query(q, want="raw", debug=True)
        assert plain["data"].raw == dbg["data"].raw
        plan = dbg["extensions"]["plan"]
        validate_plan(plan)
        assert plan["nodes"], plan
        assert "plan" not in plain["extensions"]
    finally:
        c.close()
