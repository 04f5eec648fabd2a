"""The program's own account of the traced WRITES: the tracer's request
records (`dgraph_tpu.utils.observe.TRACER.request_records`) of the
`/mutate` requests that began while the profiler session of a
`--trace 1` run was collecting, read after the window as
`chipbench/spans.py` reads the `/query` ones (whose helpers serve here
too: a median for wall times, a mean for CPU times). A `/mutate` tree is
`http.request > {http.read, mutate > {mutate.parse, mutate.apply,
commit.wait}, http.reply}`, and the thread that ran a group commit's
batch also holds its `commit` span (`batch`, `oracle_ms`, `propose_ms`,
`apply_ms`) under its `commit.wait`.

Where a counter over the whole window serves better than the newest
traced trees (a rare request), the maker's `describe` hands the
program's counters that moved over the window in
`ctx["describe"]["counters_in_window"]` (`chipbench/data/snb_mixed.py`).

A program without these spans gives None, and so does every reader."""

from __future__ import annotations

import statistics

from chipbench import spans

# the spans of a write's own work: its transaction and its commit
MUTATE = ("mutate", "mutate.parse", "mutate.apply", "commit.wait", "commit",
          "valcol.patch")


def records(ctx: dict):
    """The traced `/mutate` request records that hold a `mutate` span,
    newest first; None where there are none. Read once, kept on
    `ctx`."""
    if "mutate_records" not in ctx:
        ctx["mutate_records"] = _read() if ctx.get("requests") else None
    return ctx["mutate_records"]


def _read():
    from dgraph_tpu.utils import observe

    ask = getattr(observe.TRACER, "request_records", None)
    if ask is None:
        return None
    recs = [r for r in ask(spans.KEPT, profiled=True)
            if r["name"] == "http.request"
            and r["root_attrs"].get("path") == "/mutate"
            and "mutate" in r["counts"]]
    return recs or None


def median(ctx: dict, of):
    recs = records(ctx)
    return float(statistics.median(of(r) for r in recs)) if recs else None


def mean(ctx: dict, of):
    recs = records(ctx)
    return float(statistics.fmean(of(r) for r in recs)) if recs else None


def in_window(ctx: dict, name: str):
    """A program counter's rise over the window, where the maker
    reported the counters; 0.0 for one that did not move."""
    moved = (ctx.get("describe") or {}).get("counters_in_window")
    return None if moved is None else float(moved.get(name, 0.0))
