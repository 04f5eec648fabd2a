"""Query kind `is1`: LDBC SNB Interactive short read 1, a person's
profile and the city they live in."""

from __future__ import annotations

from chipbench.data import snb
from chipbench.queries import snb_reads as reads

FIELDS = ("firstName", "lastName", "birthday", "locationIP", "browserUsed",
          "gender", "creationDate")
DATES = ("birthday", "creationDate")


def _row(person: dict, place_id: int, place: str) -> tuple:
    return (*(reads.ms(person[f]) if f in DATES else person[f]
              for f in FIELDS), place_id, place)


def text(model, params: dict, p: int) -> str:
    return (f"{{ q(func: {reads.person_root(p)}) {{ {' '.join(FIELDS)} "
            "isLocatedIn { id name } } }")


def request(catalog: dict, params: dict, rng):
    p = reads.draw_person(catalog, rng)
    return p, text(catalog["model"], params, p)


def parse(body: dict) -> list:
    return [_row(r, r["isLocatedIn"][0]["id"], r["isLocatedIn"][0]["name"])
            for r in reads.served(body)]


def reference(model, params: dict, keys: list) -> list:
    out = []
    for p in keys:
        row = model.person(int(p))
        out.append([_row(row, 200 + row["place"], snb._PLACES[row["place"]])])
    return out


def control(model, params: dict, keys: list):
    """Persons are older than any message: the stale store answers as
    the model does."""
    return reference(model, params, keys), None


def check(model, params: dict, keys: list, answers: list,
          captured=None) -> dict:
    return reads.compared("is1", answers, reference(model, params, keys))
