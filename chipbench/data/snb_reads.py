"""Data maker `snb_reads`: `snb`'s, n-quad for n-quad. Everything is
`chipbench/data/snb.py`'s (the draws, the n-quads, the bulk load, the
plain model and its message side: `snb-sf1-reads` holds the social
network `snb-sf1` holds), behind ONE question put to the program before
any data is built: does it order a few candidates without listing the
order key's whole index?

A program from before the counter `order_single_total` (METRICS.md)
lists every `creationDate` hour bucket, ~22,000 of them, for each IS2
and IS7. Under `short16`'s 16 clients a short read then takes 5-26 s, a
45 s window ends ~270 requests, complex read 1 arrives two or three
times in it or not at all, and a traced stretch may hold no device
program (the driver's check of PR 34 met one: busy_s 0.0 over 20 s).
That is no run of this deployment, and its numbers would be compared
as if it were. `install` therefore refuses such a program at once: exit
code 1, the reason on stderr, no result line, nothing built.

No program import at module level (the load generator imports this
file for `catalog`)."""

from __future__ import annotations

from chipbench.data import snb

NEEDS = 'order_single_total{path="values"}'


def __getattr__(name: str):
    return getattr(snb, name)


def install(config: dict, seed: int, alpha, store_dir: str):
    from dgraph_tpu.utils import observe

    if not observe.registered_metric(NEEDS):
        raise SystemExit(
            f"chipbench: {config['name']} needs a program that orders by "
            f"one key without listing the key's whole index (it declares "
            f"no metric {NEEDS}: METRICS.md); this one would serve a "
            "short read in seconds and the cell would measure nothing")
    return snb.install(config, seed, alpha, store_dir)
