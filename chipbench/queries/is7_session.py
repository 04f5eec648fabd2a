"""Query kind `is7_session`: LDBC SNB Interactive short read 7 as `is7`
sends, parses and compares it, under the session rule: after the
client's IU7 it reads the replies to the message that comment answered,
else a message drawn as `is7` draws one
(`chipbench/queries/snb_writes.py`). The control is the plain
reference, for the harness takes it one acknowledged write behind."""

from __future__ import annotations

from chipbench.queries import is7
from chipbench.queries import snb_writes as w
from chipbench.queries.is7 import check, parse, reference, text  # noqa: F401


def request(catalog: dict, params: dict, rng):
    i = w.take(catalog, rng, "is7")
    if i is None:
        return is7.request(catalog, params, rng)
    return i, text(catalog["model"], params, i)


def control(model, params: dict, keys: list):
    return reference(model, params, keys), None
