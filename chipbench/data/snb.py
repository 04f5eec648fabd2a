"""LDBC-SNB data at scale factor 1's person and `knows` counts, with its
plain model: the benchmark's own generator (schema, attribute widths,
creationDate facets, uid and id numbering as upstream dgraph's
systest/ldbc has them; it began as a copy of benchmarks/ldbc_corpus.py,
which tier-1's tests still use, and has since its own degree draw, its
own counts and the model's message side).

Degrees: SF1 has 9,892 persons and 180,623 friendship pairs (mean degree
36.5) with a heavy tail. The `knows` graph here has ONE structure for
every seed: degrees are the quantiles of a lognormal (sigma, cap and the
pair count are in the configuration's file under `assumed`), wired by a
configuration model over popularity ranks from a fixed generator
(self-pairs and repeats dropped). The seed chooses which person holds
rank r, so every seed serves the same graph under other names — the same
sizes in another order — and a run's work does not depend on the seed
beyond which requests its clients happen to draw. Everything else
(attributes, facet dates, messages, forums) is drawn from the seed, by
ONE generator in ONE order: `person_columns`, then `message_draws`. The
n-quads writer and the model both read those two, so what the store
holds and what the model says cannot drift apart; the n-quads of a
(configuration, seed) are pinned byte for byte by a test.

Nothing of the program is imported here: `Model` is the plain reference.
"""

from __future__ import annotations

import datetime
import os
import shutil
import time

import numpy as np

SCHEMA = """
fqid: string @index(exact) @upsert .
id: int @index(int) .
firstName: string @index(exact, term) .
lastName: string @index(exact, term) .
gender: string .
birthday: datetime .
creationDate: datetime @index(hour) .
locationIP: string .
browserUsed: string .
content: string @index(fulltext) .
imageFile: string .
title: string @index(term) .
name: string @index(exact) .
dgraph.type: [string] @index(exact) .
knows: [uid] @reverse .
isLocatedIn: [uid] @reverse .
hasCreator: [uid] @reverse .
replyOf: [uid] @reverse .
containerOf: [uid] @reverse .
hasModerator: [uid] @reverse .
likes: [uid] @reverse .
"""

_FIRST = ["Mahinda", "Karl", "Jose", "Rudolf", "Chutima", "Farhad",
          "Abhishek", "Ouwo", "Abdou", "Jan", "Aisha", "Wei", "Maria",
          "Ivan", "Lena", "Noor"]
_LAST = ["Perera", "Wagner", "Costa", "Engel", "Wattansin", "Qaderi",
         "Roy", "Maazou", "Dia", "Hus", "Khan", "Chen", "Silva",
         "Petrov", "Meyer", "Ali"]
_PLACES = ["Thanjavur", "Leipzig", "Porto", "Vienna", "Bangkok",
           "Kabul", "Kolkata", "Niamey", "Dakar", "Prague"]
_BROWSERS = ["Internet Explorer", "Firefox", "Chrome", "Safari", "Opera"]

UID0 = 0x10000
BASE_MS = 1275850000000  # ~2010-06
N_PLACES = len(_PLACES)


def person_uid(index):
    """uid of person `index` (places take the first uids, as in the
    copied generator)."""
    return UID0 + N_PLACES + 1 + index


def person_sid(index):
    return 933 + index * 7


def message_uid(n: int, index):
    """uid of message `index` among `n` persons: the posts follow the
    persons, the comments the posts (a message index counts posts
    first, then comments in the order written)."""
    return person_uid(n) + index


def post_sid(j):
    return 3 + j * 11


def comment_sid(i):
    """`i` counts comments alone (message index less the posts)."""
    return 1099511627777 + i * 3


def forum_sid(f):
    return f


def _dt(ms_epoch: int) -> str:
    """RFC3339 with millis, the SNB creationDate shape."""
    d = datetime.datetime.fromtimestamp(ms_epoch / 1000.0,
                                        datetime.timezone.utc)
    return d.strftime("%Y-%m-%dT%H:%M:%S.") + f"{ms_epoch % 1000:03d}Z"


def epoch_ms(text: str) -> int:
    """An RFC3339 time as epoch milliseconds, `_dt`'s inverse: the served
    text and the n-quads' differ in how many digits the fraction has."""
    t = datetime.datetime.fromisoformat(text.replace("Z", "+00:00"))
    return round(t.timestamp() * 1000)


def degree_sequence(n: int, pairs: int, sigma: float, cap: int) -> np.ndarray:
    """Degree of popularity rank r, the same under every seed: lognormal
    quantiles, capped, scaled to 2 * pairs stubs, then put on the ranks in
    a fixed order (so that hot ranks are not all hubs)."""
    from statistics import NormalDist

    z = np.array([NormalDist().inv_cdf((i + 0.5) / n) for i in range(n)])
    raw = np.exp(sigma * z)
    lo, hi = 0.0, 2.0 * pairs
    for _ in range(60):  # the scale at which the capped sum is 2 * pairs
        mid = (lo + hi) / 2
        if np.minimum(raw * mid, cap).sum() < 2 * pairs:
            lo = mid
        else:
            hi = mid
    deg = np.maximum(1, np.rint(np.minimum(raw * hi, cap))).astype(np.int64)
    return deg[np.random.default_rng(0).permutation(n)]


def rank_order(n: int, seed: int) -> np.ndarray:
    """rank -> person index, from the seed alone: the names under which
    this seed serves the one `knows` structure."""
    return np.random.default_rng([seed, 11]).permutation(n)


def catalog(config: dict, seed: int) -> dict:
    """What a client needs to write requests: the plain model (the
    requests' parameters are curated from it, and their shape classes
    read off it)."""
    return {"model": make(config, seed)}


def person_columns(n: int, seed: int):
    """The persons' attribute draws, and the generator they came from
    (the n-quads writer goes on drawing from it): indices into _FIRST,
    _LAST, gender, birthday's year, month and day, creation offset, two
    IP bytes, _BROWSERS, _PLACES."""
    rng = np.random.default_rng([seed, 13])
    highs = (len(_FIRST), len(_LAST), 2, 40, 9, 9, 60_000_000_000, 256, 256,
             len(_BROWSERS), N_PLACES)
    return rng, [rng.integers(0, hi, n).tolist() for hi in highs]


def person_row(i: int, cols) -> dict:
    """Person i's attributes as the n-quads carry them."""
    fi, la, ge, by, bm, bd, cr, ip1, ip2, br, pl = (c[i] for c in cols)
    return {"id": person_sid(i), "firstName": _FIRST[fi],
            "lastName": _LAST[la], "gender": "male" if ge else "female",
            "birthday": f"19{60 + by:02d}-0{1 + bm}-0{1 + bd}T00:00:00Z",
            "creationDate": _dt(BASE_MS + cr),
            "locationIP": f"27.54.{ip1}.{ip2}",
            "browserUsed": _BROWSERS[br], "place": pl}


def message_draws(rng, n: int, n_pairs: int, sizes: dict) -> dict:
    """Everything the seed draws after the persons' attributes, in the
    one order the generator is read in (`rng` is `person_columns`'s,
    handed on): friendship dates; the posts' creator, topic, has-content
    (0 of 4: none), has-no-image (0 of 3: an image) and creation offset;
    the comments' parent draw in [0, 1), THEN their creator, subject and
    gap after the parent; the forums' moderators. A change of order or
    of a bound here changes every store: the pinned n-quads say so."""

    def ints(hi, count):
        return rng.integers(0, hi, count)

    n_posts, n_comments = sizes["posts"], sizes["comments"]
    return {
        "knows_ms": BASE_MS + ints(60_000_000_000, n_pairs),
        "post_creator": ints(n, n_posts),
        "post_topic": ints(500, n_posts),
        "post_has_content": ints(4, n_posts),
        "post_no_image": ints(3, n_posts),
        "post_ms": BASE_MS + ints(70_000_000_000, n_posts),
        "comment_u01": rng.random(n_comments),
        "comment_creator": ints(n, n_comments),
        "comment_about": ints(100, n_comments),
        "comment_gap": ints(5_000_000_000, n_comments),
        "moderator": ints(n, sizes["forums"]),
    }


def _csr(owner: np.ndarray, owners: int):
    """(members sorted by owner then index, starts): owner o's members
    are members[starts[o]:starts[o + 1]], ascending."""
    order = np.argsort(owner, kind="stable")
    return order, np.searchsorted(owner[order], np.arange(owners + 1))


class Messages:
    """The message side of the plain model: numpy columns over message
    index (posts first, then comments in the order written) and over
    forum index, as the n-quads carry them."""

    def __init__(self, n: int, draws: dict):
        self.n = n
        self.n_posts = len(draws["post_creator"])
        self.n_comments = len(draws["comment_creator"])
        self.n_forums = len(draws["moderator"])
        self.creator = np.concatenate(
            [draws["post_creator"], draws["comment_creator"]])
        # a comment replies to a post or to an EARLIER comment, drawn
        # uniformly over the messages written before it, and is created
        # 1 s plus its gap after that message
        count = self.n_posts + np.arange(self.n_comments)
        reply_to = (draws["comment_u01"] * count).astype(np.int64)
        ms = draws["post_ms"].tolist()
        for t, gap in zip(reply_to.tolist(), draws["comment_gap"].tolist()):
            ms.append(ms[t] + 1000 + gap)
        self.ms = np.array(ms, np.int64)  # creationDate, epoch ms
        self.parent = np.concatenate(
            [np.full(self.n_posts, -1, np.int64), reply_to])
        self.moderator = draws["moderator"]
        self._draws = draws
        self._by_creator = _csr(self.creator, n)
        self._replies = _csr(self.parent + 1, len(self.creator) + 1)

    def __len__(self) -> int:
        return len(self.creator)

    def is_post(self, i: int) -> bool:
        return i < self.n_posts

    def uid(self, i):
        return message_uid(self.n, i)

    def sid(self, i: int) -> int:
        """The message's `id`; its `fqid` is `post_<id>` or
        `comment_<id>`."""
        return (post_sid(i) if i < self.n_posts
                else comment_sid(i - self.n_posts))

    def fqid(self, i: int) -> str:
        return f"{'post' if i < self.n_posts else 'comment'}_{self.sid(i)}"

    def text(self, i: int) -> dict:
        """The message's text predicates, those it has: a post's
        `content` (3 of 4) and `imageFile` (1 of 3), a comment's
        `content`."""
        if i >= self.n_posts:
            c = i - self.n_posts
            return {"content":
                    f"reply {c} about {self._draws['comment_about'][c]}"}
        out = {}
        if self._draws["post_has_content"][i]:
            out["content"] = (f"About topic {self._draws['post_topic'][i]}, "
                              f"opinion {i}")
        if not self._draws["post_no_image"][i]:
            out["imageFile"] = f"photo{post_sid(i)}.jpg"
        return out

    def by_creator(self, p: int) -> np.ndarray:
        """Message indices person `p` created, ascending."""
        order, starts = self._by_creator
        return order[starts[p]:starts[p + 1]]

    def replies(self, i: int) -> np.ndarray:
        """Indices of the comments that reply to message `i`, ascending."""
        order, starts = self._replies
        return order[starts[i + 1]:starts[i + 2]]

    def forum_uid(self, f):
        return message_uid(self.n, len(self.creator) + f)

    def forum_title(self, f: int) -> str:
        return f"Wall of person_{person_sid(int(self.moderator[f]))}"

    def forum_of_post(self, j):
        """The forum whose `containerOf` holds post `j`."""
        return j % self.n_forums


class Model:
    """The plain reference: adjacency over person indices, and behind
    `messages()` the posts, comments and forums. `sizes` are those in
    force (a rehearsal's, where one runs); a model made without them
    (a control's cut-down copy) has no message side."""

    def __init__(self, n: int, pairs: np.ndarray, seed: int, sizes=None):
        self.n, self.seed, self.sizes = n, seed, sizes
        self.pairs = pairs  # (m, 2) person indices, a < b, unique
        self._cols = self._draws = self._messages = None
        both = np.concatenate([pairs, pairs[:, ::-1]])
        order = np.lexsort((both[:, 1], both[:, 0]))
        both = both[order]
        starts = np.searchsorted(both[:, 0], np.arange(n + 1))
        self._nbr, self._starts = both[:, 1], starts

    def friends(self, i: int) -> np.ndarray:
        return self._nbr[self._starts[i]:self._starts[i + 1]]

    def degrees(self) -> np.ndarray:
        return np.diff(self._starts)

    def columns(self) -> list:
        if self._cols is None:
            self._cols = person_columns(self.n, self.seed)[1]
        return self._cols

    def _drawn(self) -> dict:
        if self._draws is None:
            if self.sizes is None:
                raise ValueError("a model made without sizes has no "
                                 "friendship dates and no messages")
            rng, self._cols = person_columns(self.n, self.seed)
            self._draws = message_draws(rng, self.n, len(self.pairs),
                                        self.sizes)
        return self._draws

    @property
    def knows_ms(self) -> np.ndarray:
        """The `knows|creationDate` facet of each of `pairs`, epoch ms."""
        return self._drawn()["knows_ms"]

    def messages(self) -> Messages:
        """Built on the first call and kept: a cell that never asks
        pays nothing for it."""
        if self._messages is None:
            self._messages = Messages(self.n, self._drawn())
        return self._messages

    def person(self, i: int) -> dict:
        return person_row(int(i), self.columns())

    def hops(self, start: int, count: int) -> list:
        """[persons exactly 1, 2, .. `count` `knows` steps from `start`],
        each sorted."""
        seen = np.array([start], np.int64)
        level, out = seen, []
        for _ in range(count):
            reach = (np.unique(np.concatenate(
                [self.friends(f) for f in level])) if len(level)
                else np.empty(0, np.int64))
            level = np.setdiff1d(reach, seen, assume_unique=True)
            seen = np.union1d(seen, level)
            out.append(level)
        return out


def knows_pairs(n: int, sizes: dict, assumed: dict, seed: int) -> np.ndarray:
    """(m, 2) person indices, a < b, unique: the fixed structure over
    ranks, renamed by the seed's ranking."""
    deg = degree_sequence(n, sizes["knows_pairs"],
                          assumed["degree_lognormal_sigma"],
                          assumed["degree_cap"])
    rng = np.random.default_rng(assumed["structure_seed"])
    stubs = rng.permutation(np.repeat(np.arange(n), deg))
    stubs = rank_order(n, seed)[stubs[: len(stubs) // 2 * 2].reshape(-1, 2)]
    a, b = stubs.min(axis=1), stubs.max(axis=1)
    code = np.unique(a[a != b] * n + b[a != b])
    return np.stack([code // n, code % n], axis=1)


def make(config: dict, seed: int, rdf_path=None) -> Model:
    """The model and, where `rdf_path` is given, the n-quads file the
    bulk loader reads (not needed when a kept store is reopened)."""
    sizes, assumed = config["sizes"], config["assumed"]
    n = sizes["persons"]
    pairs = knows_pairs(n, sizes, assumed, seed)
    model = Model(n, pairs, seed, sizes)
    if rdf_path is not None:
        model.nquads = _write_rdf(rdf_path, model)
    return model


def _write_rdf(path: str, model: Model) -> int:
    n = model.n
    msgs = model.messages()
    out = []

    def emit(s, p, o, facet=None):
        out.append(f"<0x{s:x}> <{p}> {o} " + (f"({facet}) ." if facet else "."))

    def lit(v: str) -> str:
        e = v.replace("\\", "\\\\").replace('"', '\\"')
        return f'"{e}"'

    ms, creator = msgs.ms.tolist(), msgs.creator.tolist()

    def message(i, kind):
        """What a post and a comment share, in the order written."""
        mu = msgs.uid(i)
        emit(mu, "fqid", lit(msgs.fqid(i)))
        emit(mu, "id", f'"{msgs.sid(i)}"^^<xs:int>')
        for attr, text in msgs.text(i).items():
            emit(mu, attr, lit(text))
        emit(mu, "creationDate", f'"{_dt(ms[i])}"^^<xs:dateTime>')
        emit(mu, "dgraph.type", lit(kind))
        emit(mu, "hasCreator", f"<0x{person_uid(creator[i]):x}>")
        return mu

    place_uids = [UID0 + 1 + i for i in range(N_PLACES)]
    for i, name in enumerate(_PLACES):
        emit(place_uids[i], "name", lit(name))
        emit(place_uids[i], "id", f'"{200 + i}"^^<xs:int>')
        emit(place_uids[i], "dgraph.type", lit("place"))

    for i in range(n):
        pu = person_uid(i)
        row = model.person(i)
        emit(pu, "fqid", lit(f"person_{row['id']}"))
        emit(pu, "id", f'"{row["id"]}"^^<xs:int>')
        for attr in ("firstName", "lastName", "gender"):
            emit(pu, attr, lit(row[attr]))
        for attr in ("birthday", "creationDate"):
            emit(pu, attr, f'"{row[attr]}"^^<xs:dateTime>')
        for attr in ("locationIP", "browserUsed"):
            emit(pu, attr, lit(row[attr]))
        emit(pu, "dgraph.type", lit("person"))
        emit(pu, "isLocatedIn", f"<0x{place_uids[row['place']]:x}>")

    for (a, b), at in zip(model.pairs.tolist(), model.knows_ms.tolist()):
        facet = f'creationDate="{_dt(at)}"^^<xs:dateTime>'
        ua, ub = person_uid(a), person_uid(b)
        emit(ua, "knows", f"<0x{ub:x}>", facet)
        emit(ub, "knows", f"<0x{ua:x}>", facet)

    for i in range(msgs.n_posts):
        message(i, "post")
    for i, t in enumerate(msgs.parent[msgs.n_posts:].tolist(), msgs.n_posts):
        mu = message(i, "comment")
        emit(mu, "replyOf", f"<0x{msgs.uid(t):x}>")

    for f, mod in enumerate(msgs.moderator.tolist()):
        fu = msgs.forum_uid(f)
        emit(fu, "fqid", lit(f"forum_{forum_sid(f)}"))
        emit(fu, "id", f'"{forum_sid(f)}"^^<xs:int>')
        emit(fu, "title", lit(msgs.forum_title(f)))
        emit(fu, "dgraph.type", lit("forum"))
        emit(fu, "hasModerator", f"<0x{person_uid(mod):x}>")
    for j in range(msgs.n_posts):
        emit(msgs.forum_uid(msgs.forum_of_post(j)), "containerOf",
             f"<0x{msgs.uid(j):x}>")

    with open(path, "w") as f:
        f.write("\n".join(out))
    return len(out)


def kept(store_dir: str) -> bool:
    """Whether `store_dir` holds a whole store (its load ran to the end)."""
    return os.path.exists(os.path.join(store_dir, "LOADED"))


def install(config: dict, seed: int, alpha, store_dir: str):
    """Build the model; open the kept store of this seed, or bulk-load
    and sync one — what `dgraph-tpu bulk` then `dgraph-tpu alpha` do.
    Returns (model, {"loaded": bool, ...})."""
    p_dir = os.path.join(store_dir, "p")
    done = os.path.join(store_dir, "LOADED")
    if kept(store_dir):
        model = make(config, seed)
        t0 = time.perf_counter()
        alpha.open(p_dir)
        return model, {"loaded": False,
                       "open_s": time.perf_counter() - t0}
    shutil.rmtree(store_dir, ignore_errors=True)  # a load that was cut
    os.makedirs(store_dir)
    rdf_path = os.path.join(store_dir, "snb.rdf")
    model = make(config, seed, rdf_path)
    t0 = time.perf_counter()
    engine = alpha.open(p_dir)
    engine.alter(SCHEMA)
    from dgraph_tpu.loaders.bulk2 import ParallelBulkLoader

    loader = ParallelBulkLoader(engine)
    loader.load_files([rdf_path])
    engine.kv.sync()
    os.remove(rdf_path)
    if loader.nquads != model.nquads:
        raise RuntimeError(
            f"bulk loader took {loader.nquads} of {model.nquads} n-quads")
    with open(done, "w") as f:
        f.write(str(loader.nquads))
    return model, {"loaded": True, "nquads": loader.nquads,
                   "open_s": time.perf_counter() - t0}
