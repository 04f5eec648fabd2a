"""Data maker `snb_feed`: `snb`'s, n-quad for n-quad. Everything is
`chipbench/data/snb.py`'s (the draws, the n-quads, the bulk load, the
plain model and its message side: `snb-sf1-feed` holds the social
network `snb-sf1` and `snb-sf1-reads` hold), behind ONE question put to
the program before any data is built: does it keep a predicate's values
resident on the device?

A program from before the counter `value_column_builds_total`
(METRICS.md) answers complex read 9 by reading ~53,000 values one
posting list at a time for the date filter, then two values for each
of the 24,000-59,000 ids that pass and a Python comparator over them
all: seconds a request with the interpreter's lock held and no device
program anywhere in it. A traced stretch of that holds nothing on the
device, and its numbers would be compared as if they were this
deployment's. `install` therefore refuses such a program at once: exit
code 1, the reason on stderr, no result line, nothing built. (The
column's dispatch counter cannot be the question: the family
`device_dispatch_total{family="*"}` was declared long before any column
program existed.)

No program import at module level (the load generator imports this
file for `catalog`)."""

from __future__ import annotations

from chipbench.data import snb

NEEDS = "value_column_builds_total"


def __getattr__(name: str):
    return getattr(snb, name)


def install(config: dict, seed: int, alpha, store_dir: str):
    from dgraph_tpu.utils import observe

    if not observe.registered_metric(NEEDS):
        raise SystemExit(
            f"chipbench: {config['name']} needs a program that filters and "
            f"orders tens of thousands of candidates from a resident value "
            f"column (it declares no metric {NEEDS}: METRICS.md); this one "
            "would read every candidate's value in Python, seconds a "
            "request, and the cell would measure nothing")
    return snb.install(config, seed, alpha, store_dir)
