"""Query kind `iu7`, which WRITES: LDBC SNB Interactive update 7, add a
comment: its creator (a loaded person), the message it replies to (a
loaded post or comment, `replyOf`), its content and creationDate. The
client's next IS2 reads the creator's messages and its next IS7 the
parent's replies (`chipbench/queries/snb_writes.py`)."""

from __future__ import annotations

from chipbench.data import snb
from chipbench.queries import snb_writes as w
from chipbench.queries.snb_writes import control, parse  # noqa: F401

WRITES = True


def request(catalog: dict, params: dict, rng, client: int, seq: int):
    """(key, write): the key is (the comment's index, creator, parent's
    index, date, content)."""
    info = w.loaded(catalog)
    n = w.slot(params, client, seq)
    i = info["messages"] + n
    sid = snb.comment_sid(i - info["posts"])
    creator = int(rng.integers(info["persons"]))
    parent = int(rng.integers(info["messages"]))
    content = f"reply {n} about {int(rng.integers(100))}"
    at = w.date(catalog, params, client, seq)
    lines = [f'_:m <fqid> "comment_{sid}" .', f'_:m <id> "{sid}"^^<xs:int> .',
             f'_:m <content> "{content}" .',
             f"_:m <creationDate> {w.datetime(at)} .",
             '_:m <dgraph.type> "comment" .',
             f"_:m <hasCreator> {w.person(creator)} .",
             f"_:m <replyOf> {w.message(catalog, parent)} ."]
    w.pend(catalog, rng, is2=creator, is7=parent)
    return (i, creator, parent, at, content), {"set": "\n".join(lines)}


def apply(model, params: dict, key, answer: dict) -> None:
    i, creator, parent, at, content = key
    model.messages().add(i, creator, parent, None, at, {"content": content},
                         int(answer["m"], 16))


def check(model, params: dict, keys: list, answers: list,
          captured=None) -> dict:
    return w.named(answers, "m")
