"""The program's own account of the traced requests: the tracer's
request records (`dgraph_tpu.utils.observe.TRACER.request_records`), one
per finished `http.request` span tree, read after the window has closed.
A record holds, per span name, the self wall time, the self CPU time of
the thread it ran on and the count, as `utils/observe.py` computes them.

The records read are those of the `/query` requests that began while the
profiler session of a `--trace 1` run was collecting (the tracer keeps
up to 1,024 of them apart from the newest requests, so that the rest of
the window does not push them out): the same stretch of the window that
`device_busy_ms_per_req` and `device_idle_share` are taken over. On the
chip's host the thread CPU clock is read only in those trees: a read
costs 6 us there and far more under load (PERF.md).

Wall times and counts are exact in every record, and their metrics are
medians. CPU times are MEANS: that clock ticks in steps of 10 ms on the
chip's host (a sandboxed kernel), so one request's reading is 0 or 10 ms
where the truth is 5, and only the sum over many requests reads true
(each tick falls on the span that was running). A program without
`request_records` (an older commit) gives None, and so does every
reader.

A set-up phase that runs once, long before the window (`ivf.*`: the
host IVF build), is read from the `span_<name>_seconds` histogram that
every finished span feeds: its sum is the wall time the alpha has spent
inside spans of that name.

This is the only file of the span readers that imports the program."""

from __future__ import annotations

import statistics

KEPT = 1024


def records(ctx: dict):
    """The traced `/query` request records, newest first; None where
    the program keeps none. Read once and kept on `ctx`."""
    if "span_records" not in ctx:
        ctx["span_records"] = _read() if ctx.get("requests") else None
    return ctx["span_records"]


def _read():
    from dgraph_tpu.utils import observe

    ask = getattr(observe.TRACER, "request_records", None)
    if ask is None:
        return None
    recs = [r for r in ask(KEPT, profiled=True)
            if r["name"] == "http.request"
            and r["root_attrs"].get("path") == "/query"]
    return recs or None


def self_cpu(rec: dict, names) -> float:
    return sum(rec["self_cpu_ms"].get(n, 0.0) for n in names)


def wait_wall(rec: dict) -> float:
    """Wall time inside `setop.wait` and `vec.wait`: queueing behind
    other requests' programs, execution, read-back."""
    return sum(v for n, v in rec["self_wall_ms"].items()
               if n.endswith(".wait"))


def host_cpu(rec: dict) -> float:
    """Self CPU time of every span but the waits (theirs is inside
    their wall time)."""
    return sum(v for n, v in rec["self_cpu_ms"].items()
               if not n.endswith(".wait"))


def launches(rec: dict) -> int:
    return sum(v for n, v in rec["counts"].items() if n.endswith(".launch"))


def median(ctx: dict, of):
    """Median over the window's records of `of(record)`; None where
    there is nothing to read. 0.0 is a reading."""
    recs = records(ctx)
    if not recs:
        return None
    return float(statistics.median(of(r) for r in recs))


def mean(ctx: dict, of):
    """Mean over the window's records of `of(record)`: for what holds a
    CPU time, whose clock ticks too coarsely for one record."""
    recs = records(ctx)
    if not recs:
        return None
    return float(statistics.fmean(of(r) for r in recs))


def mean_self_cpu(ctx: dict, names):
    return mean(ctx, lambda r: self_cpu(r, names))


def phase_seconds(name: str):
    """Wall seconds the alpha has spent inside spans of `name` since it
    started; None where none finished (an older commit, or a tier that
    never builds)."""
    from dgraph_tpu.utils import observe

    total, count = observe.METRICS.hist_stats(f"span_{name}_seconds")
    return float(total) if count else None
