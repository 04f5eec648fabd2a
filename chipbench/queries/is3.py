"""Query kind `is3`: LDBC SNB Interactive short read 3, a person's
friends and the date each friendship began, newest first (a facet
order)."""

from __future__ import annotations

from chipbench.queries import snb_reads as reads


def text(model, params: dict, p: int) -> str:
    return (f"{{ q(func: {reads.person_root(p)}) {{ knows "
            f"@facets(orderdesc: creationDate) {{ {reads.PERSON} }} }} }}")


def request(catalog: dict, params: dict, rng):
    p = reads.draw_person(catalog, rng)
    return p, text(catalog["model"], params, p)


def parse(body: dict) -> list:
    return [(*reads.served_name(r), reads.facet_ms(r["knows|creationDate"]))
            for person in reads.served(body)
            for r in person.get("knows", [])]


def reference(model, params: dict, keys: list) -> list:
    friends, dates, starts = reads.friendships(model)
    out = []
    for p in keys:
        mine = slice(starts[int(p)], starts[int(p) + 1])
        rows = sorted(zip(friends[mine].tolist(), dates[mine].tolist()),
                      key=lambda fd: (-fd[1], fd[0]))
        out.append([(*reads.name_of(model, f), d) for f, d in rows])
    return out


def control(model, params: dict, keys: list):
    """Friendships are not messages: the stale store answers as the
    model does."""
    return reference(model, params, keys), None


def check(model, params: dict, keys: list, answers: list,
          captured=None) -> dict:
    return reads.compared("is3", answers, reference(model, params, keys))
