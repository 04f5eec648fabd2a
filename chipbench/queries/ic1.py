"""Query kind `ic1`: LDBC SNB Interactive complex read 1, "transitive
friends with a certain name". From ONE start person: the persons with a
given first name within 3 `knows` steps (the start person excluded),
nearest first, then by last name, then by id, 20 of them, with their
profile. DQL gives each distance a block of its own, each sorted and cut
to 20; the caller keeps the first 20 rows over the three blocks, so all
three are compared as they are served.

Parameters are curated as LDBC's are (Gubichev and Boncz, "Parameter
curation for benchmark queries", which the SNB driver's substitution
parameters come from): start persons are those whose 2-step circle is
nearest the median in size (the mix's `band` of quantiles), so that
every request does about the same work; the run's seed draws uniformly
among them, and the first name uniformly among the generator's."""

from __future__ import annotations

import numpy as np

from chipbench.data import snb

FIELDS = ("id", "lastName", "birthday", "creationDate", "gender",
          "browserUsed", "locationIP")
DATES = ("birthday", "creationDate")
PROFILE = "{ " + " ".join(FIELDS) + " isLocatedIn { name } }"


def _row(d: int, person: dict, place: str) -> tuple:
    return (d, *(snb.epoch_ms(person[f]) if f in DATES else person[f]
                 for f in FIELDS), place)


def curated(catalog: dict, params: dict) -> np.ndarray:
    """Start persons inside the band, in index order (kept on the
    catalog: one sweep over every person's 2-step circle)."""
    key = ("ic1.curated", tuple(params["band"]))
    if key not in catalog:
        model = catalog["model"]
        size = np.array([sum(len(h) for h in model.hops(i, 2))
                         for i in range(model.n)])
        lo, hi = np.quantile(size, params["band"])
        catalog[key] = np.flatnonzero((size >= lo) & (size <= hi))
    return catalog[key]


def request(catalog: dict, params: dict, rng):
    """(key, DQL text): the key is (start person's index, index of the
    first name)."""
    persons = curated(catalog, params)
    start = int(persons[rng.integers(len(persons))])
    name = int(rng.integers(len(snb._FIRST)))
    order = f"orderasc: lastName, orderasc: id, first: {params['limit']}"
    named = f'@filter(eq(firstName, "{snb._FIRST[name]}"))'
    text = (f'{{ me as var(func: eq(fqid, "person_{snb.person_sid(start)}")) '
            "{ f1 as knows } "
            "var(func: uid(f1)) { f2 as knows "
            "@filter(NOT uid(me) AND NOT uid(f1)) } "
            "var(func: uid(f2)) { f3 as knows "
            "@filter(NOT uid(me) AND NOT uid(f1) AND NOT uid(f2)) } "
            + " ".join(f"d{d}(func: uid(f{d}), {order}) {named} {PROFILE}"
                       for d in (1, 2, 3)) + " }")
    return np.array([start, name]), text


def _pow2(n: int) -> int:
    return 1 << (max(1, int(n)) - 1).bit_length()


def shape(catalog: dict, params: dict, key) -> tuple:
    """The request's shape class: the third level's rows (the persons 2
    steps away), its widest row, and the set each row is intersected
    with (the persons 3 steps away), each rounded up to a power of two
    as the dispatcher pads them."""
    model = catalog["model"]
    _, two, three = model.hops(int(key[0]), 3)
    widest = max((len(model.friends(f)) for f in two), default=1)
    return _pow2(len(two)), _pow2(widest), _pow2(len(three))


def parse(body: dict) -> list:
    """[(distance, id, lastName, .., place name)] as served, block by
    block (a row that lacks a field is a failed request)."""
    if "errors" in body:
        raise ValueError(str(body["errors"])[:200])
    return [_row(d, r, r["isLocatedIn"][0]["name"])
            for d in (1, 2, 3) for r in body["data"].get(f"d{d}", [])]


def reference(model, params: dict, keys: list) -> list:
    out = []
    for start, name in keys:
        rows = []
        for d, level in enumerate(model.hops(int(start), 3), 1):
            named = [model.person(i) for i in level
                     if model.columns()[0][i] == name]
            named.sort(key=lambda p: (p["lastName"], p["id"]))
            rows += [_row(d, p, snb._PLACES[p["place"]])
                     for p in named[: params["limit"]]]
        out.append(rows)
    return out


def control(model, params: dict, keys: list):
    """The broken guarantee: answers of a store that served before its
    last writes were synced — the model without the newest 1% of
    `knows` pairs. Returns (answers, captured) as `check` takes them."""
    stale = snb.Model(model.n, model.pairs[: len(model.pairs) * 99 // 100],
                      model.seed)
    return reference(stale, params, keys), None


def check(model, params: dict, keys: list, answers: list,
          captured=None) -> dict:
    """Per-answer numbers: exact comparison with the model, row for row
    and field for field."""
    want = reference(model, params, keys)
    return {
        "wrong_answers": [0.0 if list(a) == w else 1.0
                          for a, w in zip(answers, want)],
        "answers_compared": [1.0] * len(answers),
    }
