"""The device's idle gaps, named for what the host was doing in them.

The program's spans are events on the host plane of the profiler's trace
(`dgraph_tpu.utils.observe.Tracer.span` opens a `TraceAnnotation` beside
each span), nominally on the clock of the device plane's "XLA Ops" line.
For every idle gap on a device plane (the time between two merged busy
intervals, as `trace_reduce` takes them) this reduction finds the host
thread whose `*.launch` span enqueued the program that ended the gap
(the k-th launch enqueued the k-th program, `order_offset`; where a
trace shows no such order, the launch span, on any thread, that started
last before the device did), and splits the gap's time over that
thread's innermost program spans by overlap: `encode`, `http.reply`,
`http.read`, `parse`, `level_task`, `setop.pad`, ... A span is whatever the program emits as one: every
`Tracer.span` event carries its trace id, so the span names of a trace
are the names of its host events that carry an id, and a span a later PR
adds is named here with no edit (`SPANS` is only the fallback for a
recorded trace that kept no ids). What no span covers goes under
`no_span` (between two requests of a connection: the client's own time,
the request line and the headers), a gap no launch span precedes under
`no_launch`, and a gap between two operations of one program under
`within_program`.

  python3 -m chipbench.span_reduce --workload <cell> --seed <n> --seconds <s>

takes one short traced window of a cell with `run.py`'s own pieces,
keeps the trace until both reductions have read it, and prints both,
with the program's request records of the traced stretch, every counter
and gauge of the program that moved over the window, per request, and
the seconds of its set-up phases beside them. It is a builder's tool;
`run.py` takes `reduce_planes`'s `by_span` for its `breakdown.idle_gaps`
and no metric from here.

  python3 -m chipbench.span_reduce <dir, .xplane.pb or planes .json>

reduces a trace that is already there. Planes are `[(plane name, [(line
name, [(event name, start_ns, duration_ns[, trace id])])])]`, which is
`trace_reduce`'s form with the span's trace id as an optional fourth.
`device_scope_s` sums the device plane's name-scope lines by the
programs' `jax.named_scope` names (`setop.<op>.<family>`, `vec.<tier>`);
a trace taken with `run.traced`'s options has no such line (PERF.md).
"""

from __future__ import annotations

import argparse
import bisect
import collections
import glob
import importlib
import json
import os
import shutil
import statistics
import sys
import tempfile
import time

from chipbench import trace_reduce

# the spans `utils/observe.py`'s call sites opened on the served paths at
# PR 32: read only where a trace carries no trace id (a recorded file)
SPANS = frozenset((
    "http.request", "http.read", "http.reply", "query", "parse", "admit",
    "process", "encode", "level_task", "commit",
    "setop.pad", "setop.upload", "setop.launch", "setop.wait", "setop.split",
    "vec.plan", "vec.launch", "vec.wait", "vec.post",
    "ivf.kmeans", "ivf.assign", "ivf.slab_gather", "ivf.upload",
))
SCOPES = ("setop.", "vec.")  # the programs' jax.named_scope names
NOT_SCOPE_LINES = (trace_reduce.OPS_LINE, trace_reduce.MODULES_LINE, "Steps")
UNNAMED = ("no_span", "no_launch", "within_program")


def scope_of(path: str):
    """"vec.ivf" out of "vec.ivf" or of an op name such as
    "jit(run)/vec.ivf/top_k"; None where no part is a scope of ours."""
    for part in path.split("/"):
        if part.startswith(SCOPES):
            return part
    return None


def innermost(events) -> list:
    """One thread's spans [(name, start, end)], which nest, flattened to
    the sorted, disjoint [(start, end, name)] of the innermost span at
    each moment."""
    out = []
    stack = []  # open spans, outermost first: (name, end)
    at = 0  # `out` is written up to here

    def emit(until):
        nonlocal at
        if until > at:
            out.append((at, until, stack[-1][0]))
            at = until

    def close(before):
        while stack and stack[-1][1] <= before:
            emit(stack[-1][1])
            stack.pop()

    for name, start, end in sorted(events, key=lambda e: (e[1], -e[2])):
        close(start)
        if stack:
            emit(start)  # the parent, until this child starts
            end = min(end, stack[-1][1])  # a child ends with its parent
        at = start
        stack.append((name, end))
    close(float("inf"))
    return out


def overlaps(segments, starts, lo, hi):
    """(name, ns) of each of the disjoint `segments` inside [lo, hi]."""
    i = max(0, bisect.bisect_right(starts, lo) - 1)
    while i < len(segments) and segments[i][0] < hi:
        s, e, name = segments[i]
        if min(e, hi) > max(s, lo):
            yield name, min(e, hi) - max(s, lo)
        i += 1


def bare(planes) -> list:
    """The planes in `trace_reduce`'s form: every event without its id."""
    return [(p, [(ln, [ev[:3] for ev in evs]) for ln, evs in lines])
            for p, lines in planes]


def span_names(planes) -> frozenset:
    """The names of the host events that carry a trace id; `SPANS`
    where none does."""
    return frozenset(
        ev[0] for pname, lines in planes
        if not trace_reduce.is_device_plane(pname)
        for _, events in lines for ev in events
        if len(ev) > 3 and ev[3]) or SPANS


ORDER_REACH, ORDER_LEAST = 32, 16  # offsets tried; pairs an offset needs


def order_offset(launch_starts, module_starts):
    """(k, early_ns): the device's program i + k is the one launch i
    enqueued, and the device's clock reads at least `early_ns` before
    the host's; (None, 0) where the trace shows no such k. One stream
    runs its programs in the order they were launched, so the two sorted
    lists are one sequence seen twice, shifted by the programs whose
    launch the trace did not catch; at the right shift the distance from
    launch to program is the dispatch latency, all but constant, and at
    a wrong one it swings with the time between requests. The shift
    whose distances spread least (quartiles) is taken where it wins
    clearly, by half. It needs no common clock: the host's and the
    device's timestamps have been seen a millisecond apart in one trace
    and not in the next (PERF.md), more than a short program's dispatch
    takes. No program starts before its launch began: where the lowest
    tenth of the distances is negative, the clocks differ by that much
    or more."""
    spreads = []
    for k in range(-ORDER_REACH, ORDER_REACH + 1):
        lo = max(0, -k)
        hi = min(len(launch_starts), len(module_starts) - k)
        if hi - lo < ORDER_LEAST:
            continue
        q = statistics.quantiles(
            [module_starts[i + k] - launch_starts[i] for i in range(lo, hi)],
            n=4)
        spreads.append((q[2] - q[0], k))
    spreads.sort()
    if not spreads or (len(spreads) > 1
                       and 2 * spreads[0][0] > spreads[1][0]):
        return None, 0
    k = spreads[0][1]
    lowest = statistics.quantiles(
        [module_starts[i + k] - launch_starts[i]
         for i in range(max(0, -k),
                        min(len(launch_starts), len(module_starts) - k))],
        n=10)[0]
    return k, max(0, -lowest)


def reduce_planes(planes) -> dict:
    threads = {}  # (plane, line) -> [(name, start, end)]
    launches = []  # (start, thread)
    with_id = 0
    names = span_names(planes)
    for pname, lines in planes:
        if trace_reduce.is_device_plane(pname):
            continue
        for li, (lname, events) in enumerate(lines):
            mine = [ev for ev in events if ev[0] in names]
            if not mine:
                continue
            key = (pname, li, lname)  # thread names can repeat
            threads[key] = [(ev[0], ev[1], ev[1] + ev[2]) for ev in mine]
            for ev in mine:
                if len(ev) > 3 and ev[3]:
                    with_id += 1
                if ev[0].endswith(".launch"):
                    launches.append((ev[1], key))
    launches.sort()
    launch_starts = [s for s, _ in launches]
    flat = {key: innermost(evs) for key, evs in threads.items()}
    flat_starts = {key: [s for s, _, _ in segs] for key, segs in flat.items()}

    by_span = collections.Counter()
    scopes = collections.Counter()
    gaps = 0
    lags = []  # launch span's start to the device's first op, ns
    for pname, lines in planes:
        if not trace_reduce.is_device_plane(pname):
            continue
        by_line = dict(lines)
        for lname, events in lines:
            if lname in NOT_SCOPE_LINES:
                continue
            for ev in events:  # a name-scope line, where the trace has one
                scope = scope_of(ev[0])
                if scope:
                    scopes[scope] += ev[2] / 1e9
        _, merged = trace_reduce.union_seconds(
            (ev[1], ev[1] + ev[2])
            for ev in by_line.get(trace_reduce.OPS_LINE, []) if ev[2] > 0)
        modules = sorted((ev[1], ev[1] + ev[2])
                         for ev in by_line.get(trace_reduce.MODULES_LINE, []))
        module_starts = [m[0] for m in modules]
        shift, early = order_offset(launch_starts, module_starts)
        for (_, e0), (s1, _) in zip(merged, merged[1:]):
            gaps += 1
            m = bisect.bisect_right(module_starts, s1) - 1
            if m >= 0 and modules[m][0] < e0 and s1 < modules[m][1]:
                # between two ops of one program: not the host's doing
                by_span["within_program"] += (s1 - e0) / 1e9
                continue
            # the launch that enqueued the program: by order where the
            # trace shows one, else the last that began before the device
            if shift is not None and m >= 0 and s1 < modules[m][1]:
                i = m - shift if m - shift < len(launches) else -1
            else:
                i = bisect.bisect_right(launch_starts, s1) - 1
            if i < 0:
                by_span["no_launch"] += (s1 - e0) / 1e9
                continue
            start, key = launches[i]
            lags.append(s1 + early - start)
            left = s1 - e0
            for name, ns in overlaps(flat[key], flat_starts[key],
                                     e0 + early, s1 + early):
                by_span[name] += ns / 1e9
                left -= ns
            by_span["no_span"] += left / 1e9
    idle = sum(by_span.values())
    named = idle - sum(by_span[n] for n in UNNAMED)
    return {
        "idle_s": idle,
        "gaps": gaps,
        "by_span": dict(by_span.most_common()),
        "attributed_share": named / idle if idle else None,
        "launch_to_device_us_p50": (
            statistics.median(lags) / 1e3 if lags else None),
        "host_threads_with_spans": len(threads),
        "span_events": sum(len(evs) for evs in threads.values()),
        "span_events_with_trace_id": with_id,
        "device_scope_s": dict(scopes.most_common(8)),
    }


def read_xplane(path: str) -> list:
    from jax.profiler import ProfileData

    def bare_event(ev):
        return (ev.name, ev.start_ns, ev.duration_ns)

    def host_event(ev):
        # the converter reads an all-digit hex id as a number
        tid = dict(ev.stats).get("trace_id")
        return bare_event(ev) if tid is None else (*bare_event(ev), str(tid))

    return [(p.name, [(ln.name, [
        (bare_event if trace_reduce.is_device_plane(p.name)
         else host_event)(ev) for ev in ln.events]) for ln in p.lines])
        for p in ProfileData.from_file(path).planes]


def read_planes(path: str) -> list:
    files = ([path] if os.path.isfile(path) else
             glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                       recursive=True))
    if not files:
        return []
    if files[0].endswith(".json"):
        with open(files[0]) as f:
            return json.load(f)
    return read_xplane(sorted(files)[-1])


def records_summary(ctx: dict):
    """The traced request records, for PERF.md's reconciliation: what
    the readers give per layer, and what only the whole list shows."""
    from chipbench import spans

    recs = spans.records(ctx)
    if not recs:
        return None
    names = sorted({n for r in recs for n in r["self_wall_ms"]})
    attrs = sorted({n for r in recs for n in r["attrs"]})
    stall = [r["wall_ms"] - spans.host_cpu(r) - spans.wait_wall(r)
             for r in recs]
    return {
        "records": len(recs),
        "root_wall_ms_p50": statistics.median(r["wall_ms"] for r in recs),
        "host_cpu_ms_mean": statistics.fmean(map(spans.host_cpu, recs)),
        "wait_wall_ms_p50": statistics.median(map(spans.wait_wall, recs)),
        "host_stall_ms_mean": statistics.fmean(stall),
        "host_stall_ms_min": min(stall),
        "launches_p50": statistics.median(map(spans.launches, recs)),
        "threads_max": max(r["threads"] for r in recs),
        # what the tracer itself adds to a traced request (PERF.md)
        "spans_per_req_mean": statistics.fmean(
            sum(r["counts"].values()) for r in recs),
        "cpu_clock_reads_per_req_mean": statistics.fmean(
            2 * sum(r["counts"][n] for n in r["self_cpu_ms"])
            for r in recs),
        "self_cpu_ms_mean": {n: statistics.fmean(
            r["self_cpu_ms"].get(n, 0.0) for r in recs) for n in names},
        "self_wall_ms_p50": {n: statistics.median(
            r["self_wall_ms"].get(n, 0.0) for r in recs) for n in names},
        "attrs_mean": {n: statistics.fmean(
            r["attrs"].get(n, 0) for r in recs) for n in attrs},
    }


def window(args) -> dict:
    """One traced window of a cell, as `run.run` takes it, with the
    trace kept until both reductions have read it."""
    from chipbench import run

    _, _, config, mix = run.load_cell(args)
    import dgraph_tpu  # noqa: F401  (places the compile cache, before jax)
    import jax

    from chipbench import alpha as alpha_mod

    dev = jax.devices()[0]
    if not args.rehearsal and dev.platform != "tpu":
        raise SystemExit(f"chipbench: needs a TPU; jax reports {dev}")
    maker = importlib.import_module(f"chipbench.data.{config['data']}")
    clock = alpha_mod.CompileClock()
    alpha = alpha_mod.Alpha()
    child = trace = None
    tmp = tempfile.mkdtemp(prefix="chipbench_")
    try:
        model, install = maker.install(
            config, args.seed, alpha,
            run.store_dir(config, maker, args.seed))
        child = run.Child({"url": alpha.serve(), "config": config,
                           "mix": mix, "seed": args.seed})
        warm = run.warm_up(child, clock, mix)
        if hasattr(maker, "window_opens"):
            maker.window_opens(model)
        from chipbench import spans
        from dgraph_tpu.utils.observe import METRICS

        c0 = clock.compiles
        m0 = METRICS.snapshot("")
        cpu0 = time.process_time()
        reply, trace = run.traced(child, args.seconds, mix,
                                  os.path.join(tmp, "records.pkl"))
        alpha_cpu_s = time.process_time() - cpu0
        m1 = METRICS.snapshot("")
        planes = read_planes(trace["dir"])
        out = {
            "rehearsal": bool(args.rehearsal),
            "device": {"platform": dev.platform, "kind": dev.device_kind},
            "install": install, "warm": warm, "window": reply,
            "traced_s": trace["stop"] - trace["start"],
            "alpha_cpu_s": alpha_cpu_s,
            "compiles_in_window": clock.compiles - c0,
            "slow_queries_total": METRICS.value("slow_queries_total"),
            # every counter and gauge of the program that moved over the
            # whole window, exact where the fine spans ride in one tree
            # per 50 ms
            "counters_per_req": {
                k: (v - m0.get(k, 0)) / max(1, reply["requests"])
                for k, v in sorted(m1.items()) if v != m0.get(k, 0)},
            "setup_phases_s": {
                n: spans.phase_seconds(n) for n in sorted(SPANS)
                if n.startswith("ivf.")},
            "trace_reduce": trace_reduce.reduce_planes(bare(planes)),
            "span_reduce": reduce_planes(planes),
            "records": records_summary({"requests": reply["requests"]}),
        }
        out["trace_reduce"].pop("planes")
        return out
    finally:
        if child is not None:
            child.close()
        alpha.close()
        shutil.rmtree(tmp, ignore_errors=True)
        if trace is not None:
            shutil.rmtree(trace["dir"], ignore_errors=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("trace", nargs="?",
                    help="a trace that is already there, in place of a "
                    "window")
    ap.add_argument("--workload")
    ap.add_argument("--seed", type=int)
    ap.add_argument("--seconds", type=float)
    ap.add_argument("--rehearsal", action="store_true",
                    help="tiny sizes on whatever platform jax has; never a "
                    "result")
    args = ap.parse_args(argv)
    if args.trace:
        print(json.dumps(reduce_planes(read_planes(args.trace)), indent=1))
        return 0
    if not args.workload or args.seed is None or not args.seconds:
        ap.error("give a trace, or --workload, --seed and --seconds")
    print(json.dumps(window(args)))
    return 0


if __name__ == "__main__":
    sys.exit(main())
