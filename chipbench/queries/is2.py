"""Query kind `is2`: LDBC SNB Interactive short read 2, a person's 10
newest messages, newest first, each with the message it replies to and
that message's creator (LDBC returns the thread's root post and its
author: the configuration's `assumed` says so)."""

from __future__ import annotations

from chipbench.queries import snb_reads as reads


def text(model, params: dict, p: int) -> str:
    return (
        f"{{ q(func: {reads.person_root(p)}) {{ ~hasCreator(orderdesc: "
        f"creationDate, first: {params['first']}) {{ id content imageFile "
        f"creationDate replyOf {{ id hasCreator {{ {reads.PERSON} }} }} "
        "} } }")


def request(catalog: dict, params: dict, rng):
    p = reads.draw_person(catalog, rng)
    return p, text(catalog["model"], params, p)


def parse(body: dict) -> list:
    """[(id, content, imageFile, creationDate, parent)]: parent is None
    for a post, else (its id, its creator's id and name)."""
    out = []
    for person in reads.served(body):
        for r in person.get("~hasCreator", []):
            parent = None
            for up in r.get("replyOf", []):
                parent = (up["id"], *reads.served_name(up["hasCreator"][0]))
            out.append((r["id"], r.get("content"), r.get("imageFile"),
                        reads.ms(r["creationDate"]), parent))
    return out


def reference(model, params: dict, keys: list, stale: bool = False) -> list:
    msgs = model.messages()
    held = reads.present(model, stale)
    out = []
    for p in keys:
        mine = msgs.by_creator(int(p))
        rows = []
        for i in reads.newest_first(msgs, mine[held[mine]])[:params["first"]]:
            text, up = msgs.text(i), int(msgs.parent[i])
            parent = None if up < 0 else (
                msgs.sid(up), *reads.name_of(model, int(msgs.creator[up])))
            rows.append((msgs.sid(i), text.get("content"),
                         text.get("imageFile"), int(msgs.ms[i]), parent))
        out.append(rows)
    return out


def control(model, params: dict, keys: list):
    return reference(model, params, keys, stale=True), None


def check(model, params: dict, keys: list, answers: list,
          captured=None) -> dict:
    return reads.compared(
        "is2", answers, reference(model, params, keys),
        is2_parents_compared=[float(sum(r[4] is not None for r in a))
                              for a in answers])
