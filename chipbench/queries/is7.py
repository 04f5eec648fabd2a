"""Query kind `is7`: LDBC SNB Interactive short read 7, the replies to
a message, newest first, each with its creator and whether that creator
knows the message's author (a `var` block, `uid(mid)`, and
`knows @filter(uid(c))`, as tests/test_ldbc.py `test_is07` has it;
LDBC's second order key, the replier's id, is left out: the
configuration's `assumed` says so)."""

from __future__ import annotations

from chipbench.queries import snb_reads as reads


def text(model, params: dict, i: int) -> str:
    return (
        f"{{ mid as var(func: {reads.message_root(model, i)}) "
        "{ c as hasCreator } "
        "q(func: uid(mid)) { ~replyOf(orderdesc: creationDate) { id content "
        f"creationDate hasCreator {{ {reads.PERSON} "
        "knows @filter(uid(c)) { id } } } } }")


def request(catalog: dict, params: dict, rng):
    i = reads.draw_message(catalog, rng)
    return i, text(catalog["model"], params, i)


def parse(body: dict) -> list:
    """[(id, content, creationDate, the replier's id and name, whether
    the replier knows the author)]."""
    out = []
    for message in reads.served(body):
        for r in message.get("~replyOf", []):
            who = r["hasCreator"][0]
            out.append((r["id"], r["content"], reads.ms(r["creationDate"]),
                        *reads.served_name(who), bool(who.get("knows"))))
    return out


def reference(model, params: dict, keys: list, stale: bool = False) -> list:
    msgs = model.messages()
    held = reads.present(model, stale)
    out = []
    for i in keys:
        author = int(msgs.creator[i])
        near = set(model.friends(author).tolist())
        replies = msgs.replies(int(i))
        out.append([
            (msgs.sid(r), msgs.text(r)["content"], int(msgs.ms[r]),
             *reads.name_of(model, int(msgs.creator[r])),
             int(msgs.creator[r]) in near)
            for r in reads.newest_first(msgs, replies[held[replies]])])
    return out


def control(model, params: dict, keys: list):
    return reference(model, params, keys, stale=True), None


def check(model, params: dict, keys: list, answers: list,
          captured=None) -> dict:
    return reads.compared(
        "is7", answers, reference(model, params, keys),
        is7_replies_compared=[float(len(a)) for a in answers])
