"""Query kind `iu1`, which WRITES: LDBC SNB Interactive update 1, add a
person, with their place (`isLocatedIn`). The person's attributes are
drawn as the loaded persons' are; LDBC's e-mails, languages, interests,
study and work are left out (the data has no tags or organisations:
the configuration's `assumed` says so)."""

from __future__ import annotations

from chipbench.data import snb
from chipbench.queries import snb_writes as w
from chipbench.queries.snb_writes import control, parse  # noqa: F401

WRITES = True


def request(catalog: dict, params: dict, rng, client: int, seq: int):
    """(key, write): the key is (the person's index, their row as
    `snb.Model.person` gives it)."""
    i = w.loaded(catalog)["persons"] + w.slot(params, client, seq)
    fi, la, ge, by, bm, bd, ip1, ip2, br, pl = (
        int(rng.integers(hi)) for hi in (
            len(snb._FIRST), len(snb._LAST), 2, 40, 9, 9, 256, 256,
            len(snb._BROWSERS), snb.N_PLACES))
    row = {"id": snb.person_sid(i), "firstName": snb._FIRST[fi],
           "lastName": snb._LAST[la], "gender": "male" if ge else "female",
           "birthday": f"19{60 + by:02d}-0{1 + bm}-0{1 + bd}T00:00:00Z",
           "creationDate": snb._dt(w.date(catalog, params, client, seq)),
           "locationIP": f"27.54.{ip1}.{ip2}",
           "browserUsed": snb._BROWSERS[br], "place": pl}
    lines = [f'_:p <fqid> "person_{row["id"]}" .',
             f'_:p <id> "{row["id"]}"^^<xs:int> .']
    lines += [f'_:p <{a}> "{row[a]}" .'
              for a in ("firstName", "lastName", "gender")]
    lines += [f'_:p <{a}> "{row[a]}"^^<xs:dateTime> .'
              for a in ("birthday", "creationDate")]
    lines += [f'_:p <{a}> "{row[a]}" .' for a in ("locationIP", "browserUsed")]
    lines += ['_:p <dgraph.type> "person" .',
              f"_:p <isLocatedIn> {w.node(snb.UID0 + 1 + pl)} ."]
    w.pend(catalog, rng)
    return (i, row), {"set": "\n".join(lines)}


def apply(model, params: dict, key, answer: dict) -> None:
    i, row = key
    model.add_person(i, dict(row, uid=int(answer["p"], 16)))


def check(model, params: dict, keys: list, answers: list,
          captured=None) -> dict:
    return w.named(answers, "p")
