"""What LDBC SNB Interactive's update stream (kinds `iu1` .. `iu8`, which
WRITE) and the reads that follow a write (`is2_session`, `is3_session`,
`is7_session`) share. The configuration's `assumed` states each choice.

The draws (LDBC's update-stream files are not here): a write's keys
are uniform over the LOADED entities (the catalog's model: persons,
posts, comments, forums), and a new friendship is a pair of loaded
persons that is not a loaded pair. A new entity's index is its write's
slot past the loaded ones, `seq * clients + client` (`clients`: the
mix's, in each kind's params; `seq`: the draw's, `chipbench/client.py`),
so its index and ids are known when the request is drawn, unique in a
run and a function of the seed. Its `creationDate` is `DATE_STEP_MS`
times (slot + 1) after the newest loaded date: after every loaded one,
a function of (client, seq) alone, increasing with `seq`.

The session rule: after a write, the same client's next IS2 takes the
write's creator (IU6, IU7), its next IS7 the parent of the comment it
wrote (IU7), its next IS3 one person of the friendship it added (IU8);
each write replaces whatever the previous one left pending. The state
is kept on the catalog, the load generator's one object that every
draw is handed, under the client's draw stream (`rng`: one per client
and phase, drawn from in the order the client sends, lookahead
included). It stands in for the sequences LDBC's workload runner
issues, short reads on what the previous operation touched, and it
makes a read depend on its own client's newest write, so that a read
served a commit behind is wrong.

Nothing of the program is imported here."""

from __future__ import annotations

from chipbench.data import snb

DATE_STEP_MS = 1000


def pend(catalog: dict, rng, **keys) -> None:
    """What this stream's next reads of each kind take. Kept as id(rng)
    -> (the stream, keys): the stream is held, so its id names no
    other stream while anything is pending for it."""
    catalog.setdefault("writes.pending", {})[id(rng)] = (rng, keys)


def pending(catalog: dict, rng) -> dict:
    got = catalog.get("writes.pending", {}).get(id(rng))
    return got[1] if got is not None and got[0] is rng else {}


def take(catalog: dict, rng, kind: str):
    """The key pending for `kind` on this stream, or None."""
    return pending(catalog, rng).pop(kind, None)


def loaded(catalog: dict) -> dict:
    """The loaded entities' counts, the newest loaded date and the
    loaded `knows` pairs (as a * persons + b), kept on the catalog."""
    if "writes.loaded" not in catalog:
        model = catalog["model"]
        msgs = model.messages()
        catalog["writes.loaded"] = {
            "persons": model.n, "messages": len(msgs),
            "posts": msgs.n_posts, "forums": msgs.n_forums,
            "newest_ms": int(msgs.ms.max()),
            "pairs": set((model.pairs[:, 0] * model.n
                          + model.pairs[:, 1]).tolist()),
        }
    return catalog["writes.loaded"]


def slot(params: dict, client: int, seq: int) -> int:
    return seq * params["clients"] + client


def date(catalog: dict, params: dict, client: int, seq: int) -> int:
    return loaded(catalog)["newest_ms"] + DATE_STEP_MS * (
        slot(params, client, seq) + 1)


def datetime(ms: int) -> str:
    return f'"{snb._dt(ms)}"^^<xs:dateTime>'


def facet(name: str, ms: int) -> str:
    return f"({name}={datetime(ms)})"


def node(uid: int) -> str:
    return f"<0x{int(uid):x}>"


def person(p: int) -> str:
    return node(snb.person_uid(p))


def message(catalog: dict, i: int) -> str:
    return node(catalog["model"].messages().uid(i))


def forum(catalog: dict, f: int) -> str:
    return node(catalog["model"].messages().forum_uid(f))


def parse(data: dict) -> dict:
    """The server's uids of the write's blank nodes."""
    return data["uids"]


def control(model, params: dict, keys: list):
    """A write has no stale form: the reads after it are held one
    acknowledged write behind (`chipbench/history.py`)."""
    return [None] * len(keys), None


def named(answers: list, blank) -> dict:
    """`check` of a write: whether the server named its new node."""
    return {"writes_unnamed": [
        0.0 if blank is None or a.get(blank) else 1.0 for a in answers]}
