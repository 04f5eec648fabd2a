"""LDBC-SNB data at scale factor 1's person and `knows` counts, with its
plain model. A copy of benchmarks/ldbc_corpus.py (schema, attribute
widths, creationDate facets, uid and id numbering unchanged) with two
changes: the `knows` degree draw and the counts.

Degrees: SF1 has 9,892 persons and 180,623 friendship pairs (mean degree
36.5) with a heavy tail. The `knows` graph here has ONE structure for
every seed: degrees are the quantiles of a lognormal (sigma, cap and the
pair count are in the configuration's file under `assumed`), wired by a
configuration model over popularity ranks from a fixed generator
(self-pairs and repeats dropped). The seed chooses which person holds
rank r, so every seed serves the same graph under other names — the same
sizes in another order — and a run's work does not depend on the seed
beyond which requests its clients happen to draw. Everything else
(attributes, facet dates, messages, forums) is drawn from the seed.

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


def _dt(ms_epoch: int) -> str:
    """RFC3339 with millis, the SNB creationDate shape."""
    d = datetime.datetime.fromtimestamp(ms_epoch / 1000.0,
                                        datetime.timezone.utc)
    return d.strftime("%Y-%m-%dT%H:%M:%S.") + f"{ms_epoch % 1000:03d}Z"


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


class Model:
    """The plain reference: adjacency over person indices."""

    def __init__(self, n: int, pairs: np.ndarray, seed: int):
        self.n, self.seed = n, seed
        self.pairs = pairs  # (m, 2) person indices, a < b, unique
        self._cols = None
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
    model = Model(n, pairs, seed)
    if rdf_path is not None:
        model.nquads = _write_rdf(rdf_path, model, sizes, seed)
    return model


def _write_rdf(path: str, model: Model, sizes: dict, seed: int) -> int:
    rng, cols = person_columns(model.n, seed)
    n = model.n
    out = []
    uid = UID0

    def nu() -> int:
        nonlocal uid
        uid += 1
        return uid

    def emit(s, p, o, facet=None):
        out.append(f"<0x{s:x}> <{p}> {o} " + (f"({facet}) ." if facet else "."))

    def lit(v: str) -> str:
        e = v.replace("\\", "\\\\").replace('"', '\\"')
        return f'"{e}"'

    def ints(hi, count):
        return rng.integers(0, hi, count).tolist()

    place_uids = []
    for i, name in enumerate(_PLACES):
        pu = nu()
        place_uids.append(pu)
        emit(pu, "name", lit(name))
        emit(pu, "id", f'"{200 + i}"^^<xs:int>')
        emit(pu, "dgraph.type", lit("place"))

    for i in range(n):
        pu = nu()
        row = person_row(i, cols)
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
    assert uid == person_uid(n - 1)

    for (a, b), ms in zip(model.pairs.tolist(),
                          ints(60_000_000_000, len(model.pairs))):
        facet = f'creationDate="{_dt(BASE_MS + ms)}"^^<xs:dateTime>'
        ua, ub = person_uid(a), person_uid(b)
        emit(ua, "knows", f"<0x{ub:x}>", facet)
        emit(ub, "knows", f"<0x{ua:x}>", facet)

    n_posts, n_comments = sizes["posts"], sizes["comments"]
    post_uids, post_ms = [], []
    for i, (cr, topic, has_c, has_i, ms) in enumerate(zip(
            ints(n, n_posts), ints(500, n_posts), ints(4, n_posts),
            ints(3, n_posts), ints(70_000_000_000, n_posts))):
        mu = nu()
        sid = 3 + i * 11
        post_uids.append(mu)
        post_ms.append(BASE_MS + ms)
        emit(mu, "fqid", lit(f"post_{sid}"))
        emit(mu, "id", f'"{sid}"^^<xs:int>')
        if has_c:
            emit(mu, "content", lit(f"About topic {topic}, opinion {i}"))
        if not has_i:
            emit(mu, "imageFile", lit(f"photo{sid}.jpg"))
        emit(mu, "creationDate", f'"{_dt(post_ms[-1])}"^^<xs:dateTime>')
        emit(mu, "dgraph.type", lit("post"))
        emit(mu, "hasCreator", f"<0x{person_uid(cr):x}>")

    msg_uids, msg_ms = list(post_uids), list(post_ms)
    u01 = rng.random(n_comments).tolist()
    for i, (cr, about, gap) in enumerate(zip(
            ints(n, n_comments), ints(100, n_comments),
            ints(5_000_000_000, n_comments))):
        mu = nu()
        sid = 1099511627777 + i * 3
        t = int(u01[i] * len(msg_uids))  # a post or an earlier comment
        ms = msg_ms[t] + 1000 + gap
        emit(mu, "fqid", lit(f"comment_{sid}"))
        emit(mu, "id", f'"{sid}"^^<xs:int>')
        emit(mu, "content", lit(f"reply {i} about {about}"))
        emit(mu, "creationDate", f'"{_dt(ms)}"^^<xs:dateTime>')
        emit(mu, "dgraph.type", lit("comment"))
        emit(mu, "hasCreator", f"<0x{person_uid(cr):x}>")
        emit(mu, "replyOf", f"<0x{msg_uids[t]:x}>")
        msg_uids.append(mu)
        msg_ms.append(ms)

    n_forums = sizes["forums"]
    forum_uids = []
    for i, mod in enumerate(ints(n, n_forums)):
        fu = nu()
        forum_uids.append(fu)
        emit(fu, "fqid", lit(f"forum_{i}"))
        emit(fu, "id", f'"{i}"^^<xs:int>')
        emit(fu, "title", lit(f"Wall of person_{person_sid(mod)}"))
        emit(fu, "dgraph.type", lit("forum"))
        emit(fu, "hasModerator", f"<0x{person_uid(mod):x}>")
    for j, mu in enumerate(post_uids):
        emit(forum_uids[j % n_forums], "containerOf", f"<0x{mu:x}>")

    with open(path, "w") as f:
        f.write("\n".join(out))
    return len(out)


def install(config: dict, seed: int, alpha, store_dir: str):
    """Build the model; open the kept store of this seed, or bulk-load
    and sync one — what `dgraph-tpu bulk` then `dgraph-tpu alpha` do.
    Returns (model, {"loaded": bool, ...})."""
    p_dir = os.path.join(store_dir, "p")
    done = os.path.join(store_dir, "LOADED")
    if os.path.exists(done):
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
