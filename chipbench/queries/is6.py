"""Query kind `is6`: LDBC SNB Interactive short read 6, the forum that
holds a message, and its moderator. The DQL reads `~containerOf` of the
message itself, so the draw is over posts (LDBC walks a comment's
`replyOf` chain to its post first: the configuration's `assumed` says
so)."""

from __future__ import annotations

from chipbench.data import snb
from chipbench.queries import snb_reads as reads


def text(model, params: dict, j: int) -> str:
    return (f"{{ q(func: {reads.message_root(model, j)}) {{ ~containerOf "
            f"{{ id title hasModerator {{ {reads.PERSON} }} }} }} }}")


def request(catalog: dict, params: dict, rng):
    j = reads.draw_message(catalog, rng, posts_only=True)
    return j, text(catalog["model"], params, j)


def parse(body: dict) -> list:
    return [(f["id"], f["title"], *reads.served_name(f["hasModerator"][0]))
            for r in reads.served(body) for f in r["~containerOf"]]


def reference(model, params: dict, keys: list, stale: bool = False) -> list:
    msgs = model.messages()
    held = reads.present(model, stale)
    out = []
    for j in keys:
        f = int(msgs.forum_of_post(int(j)))
        out.append([(snb.forum_sid(f), msgs.forum_title(f),
                     *reads.name_of(model, int(msgs.moderator[f])))]
                   if held[j] else [])
    return out


def control(model, params: dict, keys: list):
    return reference(model, params, keys, stale=True), None


def check(model, params: dict, keys: list, answers: list,
          captured=None) -> dict:
    return reads.compared("is6", answers, reference(model, params, keys))
