"""What LDBC SNB Interactive's seven short reads (kinds `is1` .. `is7`)
share: the draws, the DQL fragments, the rows of the plain model
(`chipbench/data/snb.Model` and its `messages()`), and the comparison.

Draws are uniform (LDBC's driver issues a short read on an entity a
complex read returned; that driver is not here): a person over all, a
message over all posts and comments, IS6's over the posts.

The tie rule, written down: the program orders `orderdesc: creationDate`
over the lossy `hour` index by value and breaks equal dates by uid in the
key's direction, DESCENDING (an exact index would keep a bucket's
ascending uids); `@facets(orderdesc: ..)` is a stable sort of the
ascending row, so equal facets stay by uid ASCENDING. The references do
the same. Message uids ascend with the message index, person uids with
the person index.

The control of every kind is the model without its newest 1% of
messages: a store that served before its last writes were synced. Nothing
of the program is imported here."""

from __future__ import annotations

import functools
import re

import numpy as np

from chipbench.data import snb

PERSON = "id firstName lastName"
_FACET = re.compile(r'"([^"]+)"')


def person_root(p: int) -> str:
    return f'eq(fqid, "person_{snb.person_sid(p)}")'


def message_root(model, i: int) -> str:
    return f'eq(fqid, "{model.messages().fqid(i)}")'


def draw_person(catalog: dict, rng) -> int:
    return int(rng.integers(catalog["model"].n))


def draw_message(catalog: dict, rng, posts_only: bool = False) -> int:
    msgs = catalog["model"].messages()
    return int(rng.integers(msgs.n_posts if posts_only else len(msgs)))


def served(body: dict) -> list:
    """The rows of block `q` (a failed query is a failed request)."""
    if "errors" in body:
        raise ValueError(str(body["errors"])[:200])
    return body["data"]["q"]


def ms(text: str) -> int:
    return snb.epoch_ms(text)


def facet_ms(text: str) -> int:
    """A served facet: '"2012-04-29T04:23:29.465Z"^^<xs:dateTime>'."""
    return snb.epoch_ms(_FACET.match(text).group(1))


def name_of(model, p: int) -> tuple:
    """(id, firstName, lastName) of person `p`, as `PERSON` serves it."""
    row = model.person(p)
    return row["id"], row["firstName"], row["lastName"]


def served_name(row: dict) -> tuple:
    return row["id"], row["firstName"], row["lastName"]


def present(model, stale: bool) -> np.ndarray:
    """Which messages the store holds: all, or all but the newest 1%."""
    at = model.messages().ms
    return at <= (np.quantile(at, 0.99) if stale else at.max())


def newest_first(msgs, ids) -> list:
    return sorted((int(i) for i in ids), key=lambda i: (-msgs.ms[i], -i))


@functools.lru_cache(maxsize=2)
def friendships(model):
    """(friends, dates, starts): person p's friends ascending and the
    `knows|creationDate` of each, at [starts[p]:starts[p + 1]]."""
    both = np.concatenate([model.pairs, model.pairs[:, ::-1]])
    at = np.concatenate([model.knows_ms, model.knows_ms])
    order = np.lexsort((both[:, 1], both[:, 0]))
    return (both[order, 1], at[order],
            np.searchsorted(both[order, 0], np.arange(model.n + 1)))


def compared(kind: str, answers: list, want: list, **counts) -> dict:
    """The numbers every short read's `check` returns: exact comparison,
    answer for answer, and how many answers of this kind were held to
    the model (`correct` asks for at least one of each)."""
    return {
        "wrong_answers": [0.0 if a == w else 1.0
                          for a, w in zip(answers, want)],
        "answers_compared": [1.0] * len(answers),
        f"compared_{kind}": [1.0] * len(answers),
        **counts,
    }
