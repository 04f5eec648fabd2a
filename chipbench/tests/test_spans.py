"""The readers of the program's request records (`chipbench/spans.py`
and the layer metrics over it) on hand-made records, and the idle
gaps by span (`chipbench/span_reduce.py`) on a small recorded planes
file whose attribution is known."""

import importlib
import json
import os

import pytest

from chipbench import span_reduce, spans

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
NEW = ("wire_cpu_ms_per_req", "parse_plan_cpu_ms_per_req",
       "executor_cpu_ms_per_req", "level_read_cpu_ms_per_req",
       "boundary_cpu_ms_per_req", "encode_cpu_ms_per_req",
       "device_wait_ms_per_req", "host_stall_ms_per_req",
       "dispatches_per_req")


def record(wall, cpu, wait, launches=1):
    """A request record as `Tracer.request_records` gives it: `cpu` is
    {span: self cpu ms}, `wait` {span: wall ms}."""
    counts = {n: 1 for n in cpu}
    counts.update({n: 1 for n in wait})
    counts["setop.launch"] = launches
    return {"name": "http.request", "root_attrs": {"path": "/query"},
            "wall_ms": wall, "threads": 1, "self_cpu_ms": dict(cpu),
            "self_wall_ms": dict(cpu, **wait), "counts": counts, "attrs": {}}


# three requests; each metric is over its own three values
RECORDS = [
    record(100.0, {"http.request": 0.1, "http.read": 0.2, "http.reply": 0.3,
                   "parse": 1.0, "admit": 0.5, "query": 2.0, "process": 3.0,
                   "level_task": 4.0, "setop.pad": 5.0, "setop.upload": 1.0,
                   "setop.launch": 0.5, "setop.split": 0.5, "encode": 6.0,
                   "setop.wait": 0.7},
           {"setop.wait": 60.0}),
    record(50.0, {"http.request": 0.1, "http.read": 0.1, "http.reply": 0.1,
                  "parse": 0.5, "admit": 0.1, "query": 1.0, "process": 1.0,
                  "level_task": 2.0, "vec.plan": 0.2, "vec.launch": 0.1,
                  "vec.post": 0.1, "encode": 3.0},
           {"vec.wait": 10.0}, launches=0),
    record(200.0, {"http.request": 1.0, "http.read": 1.0, "http.reply": 1.0,
                   "parse": 2.0, "admit": 1.0, "query": 4.0, "process": 6.0,
                   "level_task": 8.0, "setop.pad": 9.0, "encode": 12.0},
           {"setop.wait": 100.0, "vec.wait": 20.0}, launches=2),
]
EXPECTED = {  # CPU times are means, wall times and counts medians
    "wire_cpu_ms_per_req": (0.6 + 0.3 + 3.0) / 3,
    "parse_plan_cpu_ms_per_req": (1.5 + 0.6 + 3.0) / 3,
    "executor_cpu_ms_per_req": (5.0 + 2.0 + 10.0) / 3,
    "level_read_cpu_ms_per_req": (4.0 + 2.0 + 8.0) / 3,
    "boundary_cpu_ms_per_req": (7.0 + 0.4 + 9.0) / 3,
    "encode_cpu_ms_per_req": (6.0 + 3.0 + 12.0) / 3,
    "device_wait_ms_per_req": 60.0,
    # 15.9 (100 - 24.1 of cpu - 60: the wait's own 0.7 of cpu is inside
    # its wall), 31.7 (50 - 8.3 - 10) and 35.0 (200 - 45 - 120)
    "host_stall_ms_per_req": (15.9 + 31.7 + 35.0) / 3,
    "dispatches_per_req": 1.0,
}


def reader(name):
    return importlib.import_module(f"chipbench.layer_metrics.{name}")


@pytest.mark.parametrize("name", NEW)
def test_reader_over_the_records(name):
    got = reader(name).read({"span_records": RECORDS})
    assert got == pytest.approx(EXPECTED[name], abs=1e-9)
    assert isinstance(got, float)


@pytest.mark.parametrize("name", NEW)
def test_reader_gives_none_without_records_and_zero_is_a_reading(name):
    assert reader(name).read({"span_records": None}) is None
    assert reader(name).read({"span_records": []}) is None
    # a request that never left the host kernels and burned nothing
    bare = record(1.0, {}, {}, launches=0)
    got = reader(name).read({"span_records": [bare]})
    assert got == (1.0 if name == "host_stall_ms_per_req" else 0.0)


def test_the_value_columns_host_side_is_the_boundarys():
    """`snb.ic9` dispatches through `valcol.*`: its pad, upload and
    launch are the device boundary's host side, its wait is not."""
    rec = record(10.0, {"valcol.pad": 1.0, "valcol.upload": 2.0,
                        "valcol.launch": 3.0, "process": 4.0,
                        "valcol.wait": 0.5}, {"valcol.wait": 5.0})
    got = reader("boundary_cpu_ms_per_req").read({"span_records": [rec]})
    assert got == 6.0


def test_every_new_reader_is_declared_with_its_cells():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    declared = {m["name"]: m for m in bench["per_layer"]}
    for name in NEW:
        m = declared[name]
        # every cell that sends `/query` requests; vec.solo16's read no level
        cells = ["snb.ic1", "snb.short16", "snb.ic9"] + (
            [] if name == "level_read_cpu_ms_per_req" else ["vec.solo16"])
        assert sorted(m["workloads"]) == sorted(cells)
        assert m["better"] == "lower"
        assert m["source"] == ("program_span"  # it counts launch spans
                               if name == "dispatches_per_req"
                               else "host_clock")
        assert m["moves"] == ("latency_p50_ms" if name in (
            "device_wait_ms_per_req", "host_stall_ms_per_req") else "qps")


PHASES = {"ivf_kmeans_s": "ivf.kmeans", "ivf_assign_s": "ivf.assign",
          "ivf_slab_gather_s": "ivf.slab_gather",
          "ivf_upload_s": "ivf.upload"}


@pytest.mark.parametrize("name", sorted(PHASES))
def test_setup_phase_reader_sums_the_spans_of_its_name(name):
    """A set-up phase is read from the histogram every finished span
    feeds: two spans of a name sum; no span of the name reads None."""
    from dgraph_tpu.utils import observe

    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        declared = {m["name"]: m for m in json.load(f)["per_layer"]}
    assert declared[name] == {
        "name": name, "unit": "s", "better": "lower",
        "source": "program_span", "layer": "vector index",
        "moves": "setup_s", "workloads": ["vec.solo16"]}
    hist = f"span_{PHASES[name]}_seconds"
    before = reader(name).read({})
    base, count = observe.METRICS.hist_stats(hist)
    assert before == (base if count else None)
    tr = observe.Tracer()
    with tr.span("query"):
        for _ in range(2):
            with tr.span(PHASES[name], cpu=True) as sp:
                pass
            sp.start, sp.end = 100.0, 101.5  # before the root finishes
    assert reader(name).read({}) == pytest.approx(base + 3.0)
    assert spans.phase_seconds("ivf.no_such_phase") is None


def test_records_are_the_traced_query_requests(monkeypatch):
    """Newest first, `/query` only, of the requests that began under
    the profiler session; a program that keeps no request records gives
    None."""
    from dgraph_tpu.utils import observe

    def rec(i, path="/query", name="http.request"):
        return {"name": name, "root_attrs": {"path": path}, "i": i}

    kept = [rec(0), rec(1, "/mutate"), rec(2), rec(3, name="query"),
            rec(4), rec(5)]
    asked = []

    def request_records(n, profiled=False):
        asked.append((n, profiled))
        return kept[:n]

    monkeypatch.setattr(observe.TRACER, "request_records", request_records,
                        raising=False)
    assert [r["i"] for r in spans.records({"requests": 3})] == [0, 2, 4, 5]
    assert asked == [(1024, True)]
    assert spans.records({"requests": 0}) is None
    monkeypatch.setattr(observe.TRACER, "request_records", None,
                        raising=False)
    assert spans.records({"requests": 3}) is None
    ctx = {"requests": 3}
    assert reader("wire_cpu_ms_per_req").read(ctx) is None
    assert ctx["span_records"] is None  # read once, kept on the context


def test_innermost_flattens_nested_spans():
    got = span_reduce.innermost([
        ("root", 0, 100), ("a", 10, 50), ("a1", 20, 30), ("b", 50, 70),
        ("late", 90, 130),  # a child that outlives its parent ends with it
        ("next", 120, 130)])
    assert got == [(0, 10, "root"), (10, 20, "a"), (20, 30, "a1"),
                   (30, 50, "a"), (50, 70, "b"), (70, 90, "root"),
                   (90, 100, "late"), (120, 130, "next")]


def test_span_reduce_on_the_recorded_planes():
    """Three gaps. [1500, 3000] ns is ended by the program thread B
    launched at 2900: B read its body, parsed, was admitted, planned and
    launched in it. [3500, 6000] by the one thread A launched at 5850:
    A's connection had no request until 5000 (no span), then the same
    stretches. [6150, 6200] lies between two operations of one program.
    A host event that is no span of ours is not counted."""
    r = span_reduce.reduce_planes(span_reduce.read_planes(
        os.path.join(HERE, "recorded_spans.json")))
    assert r["gaps"] == 3
    assert r["idle_s"] == pytest.approx(4050e-9)
    ns = {k: round(v * 1e9) for k, v in r["by_span"].items()}
    assert ns == {"no_span": 1500, "parse": 990 + 490,
                  "vec.plan": 250 + 220, "http.read": 100 + 90,
                  "vec.launch": 90 + 100, "admit": 50 + 20,
                  "vec.wait": 10 + 50, "query": 10 + 10,
                  "http.request": 10, "process": 10, "within_program": 50}
    assert r["attributed_share"] == pytest.approx(2500 / 4050)
    assert r["launch_to_device_us_p50"] == pytest.approx(0.125)
    assert r["host_threads_with_spans"] == 2  # two lines of one name
    assert r["span_events"] == 36
    assert r["span_events_with_trace_id"] == 35
    assert r["device_scope_s"] == {"vec.ivf": pytest.approx(1400e-9)}


def test_a_span_is_what_carries_a_trace_id_and_SPANS_is_the_fallback():
    """With its ids the recorded trace names its own spans; without
    them the frozen list does, and both give the same attribution. A
    span the list has never heard of is named as soon as it carries an
    id, and a host event that carries none stays what it was."""
    path = os.path.join(HERE, "recorded_spans.json")
    planes = span_reduce.read_planes(path)
    names = span_reduce.span_names(planes)
    assert names < span_reduce.SPANS and "vec.wait" in names
    assert "PjitFunction(run)" not in names and "TpuExecute" not in names
    stripped = span_reduce.bare(planes)
    assert span_reduce.span_names(stripped) == span_reduce.SPANS
    with_ids = span_reduce.reduce_planes(planes)
    without = span_reduce.reduce_planes(stripped)
    assert with_ids["by_span"] == without["by_span"]
    assert list(with_ids["by_span"])[:2] == ["no_span", "parse"]
    assert (with_ids["span_events"], without["span_events"]) == (36, 36)
    assert without["span_events_with_trace_id"] == 0

    # `parse` renamed to a span no list knows: found by its id, lost
    # without one (its time then falls to the span around it)
    renamed = [(p, [(ln, [["order.walk", *ev[1:]] if ev[0] == "parse"
                          else ev for ev in evs]) for ln, evs in lines])
               for p, lines in planes]
    found = span_reduce.reduce_planes(renamed)["by_span"]
    assert found["order.walk"] == with_ids["by_span"]["parse"]
    assert {k: v for k, v in found.items() if k != "order.walk"} == {
        k: v for k, v in with_ids["by_span"].items() if k != "parse"}
    lost = span_reduce.reduce_planes(span_reduce.bare(renamed))["by_span"]
    assert "order.walk" not in lost and "parse" not in lost
    assert lost["query"] == pytest.approx(
        with_ids["by_span"]["query"] + with_ids["by_span"]["parse"])


def _two_threads(skew, requests=40):
    """Planes of two handler threads taking turns: a request parses for
    2,000 ns, launches for 600 and waits 2,400; its program starts 300
    after the launch began and runs 1,000, on a device clock that reads
    `skew` early; requests begin 4,000-9,000 apart."""
    import random

    rng = random.Random(3)
    host, programs, t = ([], []), [], 10_000
    for i in range(requests):
        t += rng.randrange(4000, 9000)
        tid = f"{i + 1:032x}"
        host[i % 2].extend([["parse", t, 2000, tid],
                            ["vec.launch", t + 2000, 600, tid],
                            ["vec.wait", t + 2600, 2400, tid]])
        programs.append(["jit_run(1)", t + 2300 - skew, 1000])
    return [["/device:TPU:0", [["XLA Modules", programs],
                               ["XLA Ops", programs]]],
            ["/host:CPU", [["handler", host[0]], ["handler", host[1]]]]]


def test_the_launcher_is_found_by_order_where_the_clocks_differ(monkeypatch):
    """The device's clock a microsecond early puts a program's start
    before its own launch: the last launch before it is then the OTHER
    thread's, which waits. Matched by order, the gap goes to what the
    launching thread did (it parsed), and the clamp takes out all of
    the skew but the dispatch latency itself."""
    true = span_reduce.reduce_planes(_two_threads(0))
    assert span_reduce.order_offset([1, 2], [1, 2]) == (None, 0)  # too few
    assert true["launch_to_device_us_p50"] == pytest.approx(0.3)
    assert true["by_span"]["parse"] > 10 * true["by_span"].get("vec.wait", 0)
    skewed = span_reduce.reduce_planes(_two_threads(1000))
    assert skewed["by_span"]["parse"] > 10 * skewed["by_span"].get(
        "vec.wait", 0)
    assert skewed["by_span"] == span_reduce.reduce_planes(
        _two_threads(400))["by_span"]  # what is left is the 300 of latency
    assert skewed["launch_to_device_us_p50"] == pytest.approx(0.0)
    # programs whose launch the trace did not catch shift the order
    late = _two_threads(0)
    late[1][1][0][1] = late[1][1][0][1][6:]  # thread 0 traced from its 3rd
    late[1][1][1][1] = late[1][1][1][1][6:]  # request on, thread 1 too
    assert span_reduce.reduce_planes(late)["launch_to_device_us_p50"] == (
        pytest.approx(0.3))
    # the rule before PR 33, the last launch that began before the device
    # did, on the same skewed trace: the waiting thread gets the gap
    monkeypatch.setattr(span_reduce, "ORDER_LEAST", 10 ** 6)
    by_time = span_reduce.reduce_planes(_two_threads(1000))["by_span"]
    assert by_time["vec.wait"] > by_time.get("parse", 0)
    assert span_reduce.reduce_planes(_two_threads(0))["by_span"] == (
        true["by_span"])  # with one clock the two rules agree


def test_span_reduce_names_a_gap_no_launch_precedes():
    planes = [
        ["/device:TPU:0", [
            ["XLA Ops", [["%a", 100, 50], ["%a", 400, 50], ["%a", 900, 50]]],
            ["Framework Name Scope", [["jit(f)/setop.union.chain/sort", 100,
                                       50], ["vec.brute", 900, 50]]]]],
        ["/host:CPU", [["t", [["setop.launch", 500, 100],
                              ["setop.pad", 450, 50]]]]],
    ]
    r = span_reduce.reduce_planes(planes)
    ns = {k: round(v * 1e9) for k, v in r["by_span"].items()}
    # [150, 400]: nothing was launched before it; [450, 900]: pad 50,
    # launch 100, and 300 under no span
    assert ns == {"no_launch": 250, "no_span": 300, "setop.launch": 100,
                  "setop.pad": 50}
    assert r["attributed_share"] == pytest.approx(150 / 700)
    assert r["device_scope_s"] == {"setop.union.chain": pytest.approx(50e-9),
                                   "vec.brute": pytest.approx(50e-9)}
    assert span_reduce.scope_of("jit(run)/vec.ivf/top_k") == "vec.ivf"
    assert span_reduce.scope_of("jit(run)/top_k") is None
    empty = span_reduce.reduce_planes([])
    assert empty["gaps"] == 0 and empty["attributed_share"] is None
    assert span_reduce.read_planes(os.path.join(HERE, "no_such_dir")) == []
