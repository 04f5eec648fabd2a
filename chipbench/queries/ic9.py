"""Query kind `ic9`: LDBC SNB Interactive complex read 9, "recent
messages by friends or friends of friends". From ONE start person: the
messages created by the persons one or two `knows` steps away (the start
person excluded) before a given `maxDate`; the 20 newest, by
`creationDate` descending, then message `id` ascending; each with its
id, content or image file, creation date and its creator's id, first
and last name.

The DQL is ours (upstream's `test_cases.yaml` is not here): LDBC's
"before the given day" is `lt` on that day's midnight instant. Start
persons are curated as `ic1` curates them (the mix's `band` over the
2-step circle's size), so every request orders about as many candidate
messages; `maxDate` is midnight UTC of a day drawn uniformly between
the day of the median message and the day after the newest, so between
half and all of a request's candidates pass the filter.

The plain reference is numpy over `chipbench/data/snb.Model` and its
`messages()`; nothing of the program is imported here."""

from __future__ import annotations

import numpy as np

from chipbench.data import snb
from chipbench.queries import ic1
from chipbench.queries import snb_reads as reads

DAY_MS = 86_400_000
ROW = ("id content imageFile creationDate "
       f"hasCreator {{ {reads.PERSON} }}")


def days(catalog: dict) -> tuple:
    """(first, last) day a `maxDate` is drawn from, as days since the
    epoch: the median message's day and the day after the newest's."""
    if "ic9.days" not in catalog:
        at = catalog["model"].messages().ms
        catalog["ic9.days"] = (int(np.median(at)) // DAY_MS,
                               int(at.max()) // DAY_MS + 1)
    return catalog["ic9.days"]


def text(start: int, bound_ms: int, limit: int) -> str:
    return (
        f'{{ me as var(func: eq(fqid, "person_{snb.person_sid(start)}")) '
        "{ f1 as knows } "
        "var(func: uid(f1)) { f2 as knows "
        "@filter(NOT uid(me) AND NOT uid(f1)) } "
        "var(func: uid(f1, f2)) { m as ~hasCreator "
        f'@filter(lt(creationDate, "{snb._dt(bound_ms)}")) }} '
        "q(func: uid(m), orderdesc: creationDate, orderasc: id, "
        f"first: {limit}) {{ {ROW} }} }}")


def request(catalog: dict, params: dict, rng):
    """(key, DQL text): the key is (start person's index, `maxDate` as
    epoch milliseconds)."""
    persons = ic1.curated(catalog, params)
    start = int(persons[rng.integers(len(persons))])
    first, last = days(catalog)
    bound = int(rng.integers(first, last + 1)) * DAY_MS
    return np.array([start, bound]), text(start, bound, params["limit"])


def _candidates(model, start: int):
    """(message indices the circle wrote, how many of them the persons
    ONE step away wrote): friends' first, then friends of friends'."""
    msgs = model.messages()
    one, two = model.hops(int(start), 2)
    by = [msgs.by_creator(int(p)) for p in (*one, *two)]
    near = sum(len(b) for b in by[: len(one)])
    return (np.concatenate(by) if by else np.empty(0, np.int64)), near


def shape(catalog: dict, params: dict, key) -> tuple:
    """The request's shape class: the candidates its filter is given
    and the candidates its order is given, each rounded up to a power
    of four as the dispatcher pads a flat level."""
    model = catalog["model"]
    cand, _ = _candidates(model, key[0])
    passed = int((model.messages().ms[cand] < int(key[1])).sum())
    return _pow4(len(cand)), _pow4(passed)


def _pow4(n: int) -> int:
    p = max(8, 1 << (max(1, int(n)) - 1).bit_length())
    return p if p.bit_length() % 2 else p * 2


def parse(body: dict) -> list:
    """[(id, content, imageFile, creationDate ms, creator's id,
    firstName, lastName)] as served."""
    return [(r["id"], r.get("content"), r.get("imageFile"),
             reads.ms(r["creationDate"]),
             *reads.served_name(r["hasCreator"][0]))
            for r in reads.served(body)]


def _answer(model, params: dict, key, held) -> tuple:
    """(rows, whether `maxDate` cut a candidate off, rows whose creator
    is two steps away) of one request over the messages `held`."""
    msgs = model.messages()
    start, bound = int(key[0]), int(key[1])
    cand, near = _candidates(model, start)
    far = np.arange(len(cand)) >= near
    live = held[cand]
    cand, far = cand[live], far[live]
    passed = msgs.ms[cand] < bound
    cut = bool((~passed).any())
    cand, far = cand[passed], far[passed]
    sid = np.where(cand < msgs.n_posts, snb.post_sid(cand),
                   snb.comment_sid(cand - msgs.n_posts))
    top = np.lexsort((sid, -msgs.ms[cand]))[: params["limit"]]
    rows = []
    for i, s in zip(cand[top].tolist(), sid[top].tolist()):
        words = msgs.text(i)
        rows.append((s, words.get("content"), words.get("imageFile"),
                     int(msgs.ms[i]),
                     *reads.name_of(model, int(msgs.creator[i]))))
    return rows, cut, int(far[top].sum())


def reference(model, params: dict, keys: list, stale: bool = False) -> list:
    held = reads.present(model, stale)
    return [_answer(model, params, k, held)[0] for k in keys]


def control(model, params: dict, keys: list):
    """The model without its newest 1% of messages (a store that served
    before its last writes were synced)."""
    return reference(model, params, keys, stale=True), None


def check(model, params: dict, keys: list, answers: list,
          captured=None) -> dict:
    """Exact comparison, row for row and field for field; and what keeps
    `correct` from reading true with the filter or the second step
    unexercised: answers whose `maxDate` excluded a candidate (each
    newer than every row served), rows written two steps away."""
    held = reads.present(model, False)
    want = [_answer(model, params, k, held) for k in keys]
    return reads.compared(
        "ic9", answers, [w[0] for w in want],
        ic9_cut_by_bound=[float(w[1]) for w in want],
        ic9_two_step_rows=[float(w[2]) for w in want])
