"""Query kind `is2_session`: LDBC SNB Interactive short read 2 as `is2`
sends, parses and compares it, under the session rule: after the
client's IU6 or IU7 it reads that write's creator, else a person drawn
as `is2` draws one (`chipbench/queries/snb_writes.py`). The control is
the plain reference, for the harness takes it one acknowledged write
behind."""

from __future__ import annotations

from chipbench.queries import is2
from chipbench.queries import snb_writes as w
from chipbench.queries.is2 import check, parse, reference, text  # noqa: F401


def request(catalog: dict, params: dict, rng):
    p = w.take(catalog, rng, "is2")
    if p is None:
        return is2.request(catalog, params, rng)
    return p, text(catalog["model"], params, p)


def control(model, params: dict, keys: list):
    return reference(model, params, keys), None
