"""Query kind `is3_session`: LDBC SNB Interactive short read 3 as `is3`
sends, parses and compares it, under the session rule: after the
client's IU8 it reads one person of the friendship it added, else a
person drawn as `is3` draws one (`chipbench/queries/snb_writes.py`).

Its reference is `is3.reference`'s, row for row, from the growing
model's `knows_of` (the loaded pairs sorted once, the added ones
beside them) instead of `snb_reads.friendships`, which sorts every pair
again after each friendship a write adds. The control is the plain
reference, for the harness takes it one acknowledged write behind."""

from __future__ import annotations

from chipbench.queries import is3
from chipbench.queries import snb_reads as reads
from chipbench.queries import snb_writes as w
from chipbench.queries.is3 import parse, text  # noqa: F401


def request(catalog: dict, params: dict, rng):
    p = w.take(catalog, rng, "is3")
    if p is None:
        return is3.request(catalog, params, rng)
    return p, text(catalog["model"], params, p)


def reference(model, params: dict, keys: list) -> list:
    out = []
    for p in keys:
        rows = sorted(model.knows_of(int(p)), key=lambda fd: (-fd[1], fd[0]))
        out.append([(*reads.name_of(model, f), d) for f, d in rows])
    return out


def control(model, params: dict, keys: list):
    return reference(model, params, keys), None


def check(model, params: dict, keys: list, answers: list,
          captured=None) -> dict:
    return reads.compared("is3", answers, reference(model, params, keys))
