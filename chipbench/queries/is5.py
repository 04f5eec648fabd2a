"""Query kind `is5`: LDBC SNB Interactive short read 5, a message's
creator."""

from __future__ import annotations

from chipbench.queries import snb_reads as reads


def text(model, params: dict, i: int) -> str:
    return (f"{{ q(func: {reads.message_root(model, i)}) "
            f"{{ hasCreator {{ {reads.PERSON} }} }} }}")


def request(catalog: dict, params: dict, rng):
    i = reads.draw_message(catalog, rng)
    return i, text(catalog["model"], params, i)


def parse(body: dict) -> list:
    return [reads.served_name(c) for r in reads.served(body)
            for c in r["hasCreator"]]


def reference(model, params: dict, keys: list, stale: bool = False) -> list:
    msgs = model.messages()
    held = reads.present(model, stale)
    return [[reads.name_of(model, int(msgs.creator[i]))] if held[i] else []
            for i in keys]


def control(model, params: dict, keys: list):
    return reference(model, params, keys, stale=True), None


def check(model, params: dict, keys: list, answers: list,
          captured=None) -> dict:
    return reads.compared("is5", answers, reference(model, params, keys))
