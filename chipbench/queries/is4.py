"""Query kind `is4`: LDBC SNB Interactive short read 4, a message's
creation date and its content or image."""

from __future__ import annotations

from chipbench.queries import snb_reads as reads


def text(model, params: dict, i: int) -> str:
    return (f"{{ q(func: {reads.message_root(model, i)}) "
            "{ creationDate content imageFile } }")


def request(catalog: dict, params: dict, rng):
    i = reads.draw_message(catalog, rng)
    return i, text(catalog["model"], params, i)


def parse(body: dict) -> list:
    return [(reads.ms(r["creationDate"]), r.get("content"),
             r.get("imageFile")) for r in reads.served(body)]


def reference(model, params: dict, keys: list, stale: bool = False) -> list:
    msgs = model.messages()
    held = reads.present(model, stale)
    out = []
    for i in keys:
        text = msgs.text(int(i))
        out.append([(int(msgs.ms[i]), text.get("content"),
                     text.get("imageFile"))] if held[i] else [])
    return out


def control(model, params: dict, keys: list):
    return reference(model, params, keys, stale=True), None


def check(model, params: dict, keys: list, answers: list,
          captured=None) -> dict:
    return reads.compared("is4", answers, reference(model, params, keys))
