"""Query kind `iu6`, which WRITES: LDBC SNB Interactive update 6, add a
post: its creator (a loaded person), its forum (a loaded forum's
`containerOf`), its creationDate, and its content or image drawn as the
loaded posts' are (3 in 4 have content, 1 in 3 an image; LDBC's tags
and language are left out). The client's next IS2 reads the creator's
messages (`chipbench/queries/snb_writes.py`)."""

from __future__ import annotations

from chipbench.data import snb
from chipbench.queries import snb_writes as w
from chipbench.queries.snb_writes import control, parse  # noqa: F401

WRITES = True


def request(catalog: dict, params: dict, rng, client: int, seq: int):
    """(key, write): the key is (the post's index, creator, forum's
    index, date, content or None, image file or None)."""
    info = w.loaded(catalog)
    i = info["messages"] + w.slot(params, client, seq)
    sid = snb.comment_sid(i - info["posts"])
    creator = int(rng.integers(info["persons"]))
    f = int(rng.integers(info["forums"]))
    topic, has_content, no_image = (
        int(rng.integers(hi)) for hi in (500, 4, 3))
    content = f"About topic {topic}, opinion {i}" if has_content else None
    image = None if no_image else f"photo{sid}.jpg"
    at = w.date(catalog, params, client, seq)
    lines = [f'_:m <fqid> "post_{sid}" .', f'_:m <id> "{sid}"^^<xs:int> .']
    if content is not None:
        lines.append(f'_:m <content> "{content}" .')
    if image is not None:
        lines.append(f'_:m <imageFile> "{image}" .')
    lines += [f"_:m <creationDate> {w.datetime(at)} .",
              '_:m <dgraph.type> "post" .',
              f"_:m <hasCreator> {w.person(creator)} .",
              f"{w.forum(catalog, f)} <containerOf> _:m ."]
    w.pend(catalog, rng, is2=creator)
    return (i, creator, f, at, content, image), {"set": "\n".join(lines)}


def apply(model, params: dict, key, answer: dict) -> None:
    i, creator, f, at, content, image = key
    text = {k: v for k, v in (("content", content), ("imageFile", image))
            if v is not None}
    model.messages().add(i, creator, -1, f, at, text, int(answer["m"], 16))


def check(model, params: dict, keys: list, answers: list,
          captured=None) -> dict:
    return w.named(answers, "m")
