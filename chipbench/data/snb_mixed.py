"""Data maker `snb_mixed`: `snb`'s, n-quad for n-quad (the draws, the
n-quads, the bulk load: `snb-sf1-mixed` holds the social network the
other SNB deployments hold), with a plain model that GROWS under LDBC
SNB Interactive's update stream, IU1-IU8 (`chipbench/queries/iu1.py` ..
`iu8.py`): persons with their place, `knows` pairs with their dates,
posts with their forum and creator, comments with their parent and
creator, forums with their moderator, likes and memberships.

Every write is applied in commit order by the judge
(`chipbench/history.py`), so each `apply` costs the size of its write:
the message side keeps its numpy columns with room to grow, and its
per-creator and per-parent indexes as the loaded CSR plus a small list
of what was added; `knows` keeps the loaded adjacency plus the added
pairs. A new entity's index is a function of the write that made it
(`chipbench/queries/snb_writes.py`: (client, seq)), not of the commit
order, so its `id`, which the request carried, is known before the
commit: a new message of index i has comment i's id in `snb`'s
numbering (which `ic9`'s reference computes from the index), a new
person person i's. Indices no write took hold no message and are
nobody's.

`hasMember` (IU5) is not in `snb.SCHEMA`: `install` declares it
(`[uid] @reverse`, as upstream's `systest/ldbc` schema has it) on the
store it opens, which changes no loaded n-quad.

`install` first puts ONE question to the program: do its value columns
take a commit's rows (`value_column_patched_rows_total`, METRICS.md)?
A program from before that drops complex read 9's `creationDate` column
at every commit that writes the predicate, about 28 a second here, and
rescans it (3.6 s of 305,576 records) or reads the candidates value by
value. On the chip such a program ran this cell to a result line, but
its IC9 requests took up to 107 s, most of them longer than the 45 s
window, so the clients that drew one sat out much of it and the cell's
sixteen clients were not offered: its p50 read lower than a program
that keeps all sixteen busy, from the lighter lock the others shared
(PERF.md, section 6, has the runs). Such a program is refused at once,
exit code 1, the reason on stderr, no result line, nothing built, as
`snb_reads` and `snb_feed` refuse theirs.

`window_opens` and `describe` report the program's counters that moved
over the window (the value columns', the commit path's) on stderr and
to the per-layer readers (`ctx["describe"]["counters_in_window"]`).

No program import at module level (the load generator imports this
file for `catalog`)."""

from __future__ import annotations

import bisect
import sys

import numpy as np

from chipbench.data import snb

SCHEMA_ADDED = "hasMember: [uid] @reverse ."
NEEDS = "value_column_patched_rows_total"


def __getattr__(name: str):
    return getattr(snb, name)


class Messages(snb.Messages):
    """`snb.Messages` that takes new posts and comments at any index at
    or past the loaded ones (`n0`): `ms`, `creator`, `parent` are views
    of arrays with room to grow (an index no write took: creator -1,
    parent -1, date 0); `by_creator` and `replies` are the loaded CSR
    and what was added."""

    def grow(self) -> None:
        self.n0 = len(self.creator)
        self.forums0 = self.n_forums
        self._cols = {name: np.array(getattr(self, name))
                      for name in ("ms", "creator", "parent")}
        self.new: dict = {}  # index -> post, forum, text, uid
        self.new_forums: dict = {}  # forum index -> title, moderator, ..
        self._by = {}  # creator -> new indices, ascending
        self._under = {}  # parent -> new indices, ascending
        self.likes: list = []  # (person, message, ms), commit order
        self.members: list = []  # (forum, person, ms), commit order

    def _room(self, i: int) -> None:
        size = max(len(self.creator), i + 1)
        have = len(self._cols["ms"])
        if size > have:
            grown = max(size, 2 * have)
            for name, fill in (("ms", 0), ("creator", -1), ("parent", -1)):
                col = np.full(grown, fill, np.int64)
                col[:have] = self._cols[name]
                self._cols[name] = col
        for name in ("ms", "creator", "parent"):
            setattr(self, name, self._cols[name][:size])

    def add(self, i: int, creator: int, parent: int, forum, ms: int,
            text: dict, uid: int) -> None:
        """Message `i`: a post in `forum` (parent -1) or a comment on
        `parent`."""
        self._room(i)
        self.ms[i], self.creator[i], self.parent[i] = ms, creator, parent
        self.new[i] = {"post": parent < 0, "forum": forum, "text": text,
                       "uid": uid}
        bisect.insort(self._by.setdefault(creator, []), i)
        if parent >= 0:
            bisect.insort(self._under.setdefault(parent, []), i)

    def __len__(self) -> int:
        return len(self.creator)

    def is_post(self, i: int) -> bool:
        return super().is_post(i) if i < self.n0 else self.new[i]["post"]

    def uid(self, i):
        return super().uid(i) if i < self.n0 else self.new[i]["uid"]

    def sid(self, i: int) -> int:
        return super().sid(i) if i < self.n0 else snb.comment_sid(
            i - self.n_posts)

    def fqid(self, i: int) -> str:
        if i < self.n0:
            return super().fqid(i)
        return f"{'post' if self.new[i]['post'] else 'comment'}_{self.sid(i)}"

    def text(self, i: int) -> dict:
        return super().text(i) if i < self.n0 else dict(self.new[i]["text"])

    def by_creator(self, p: int) -> np.ndarray:
        base = super().by_creator(p)
        added = self._by.get(int(p))
        return base if not added else np.concatenate(
            [base, np.array(added, np.int64)])

    def replies(self, i: int) -> np.ndarray:
        base = (super().replies(i) if i < self.n0
                else np.empty(0, np.int64))
        added = self._under.get(int(i))
        return base if not added else np.concatenate(
            [base, np.array(added, np.int64)])

    def forum_of_post(self, j):
        if j < self.n0:
            return super().forum_of_post(j)
        return self.new[j]["forum"]

    def forum_title(self, f: int) -> str:
        return (super().forum_title(f) if f < self.forums0
                else self.new_forums[f]["title"])


class Model(snb.Model):
    """`snb.Model` that grows (module docstring). Persons at or past
    `n0` are IU1's; `n` stays the loaded count (the seed's draws are of
    it), and `pairs` / `knows_ms` take the added friendships at their
    end, a re-added pair its new date in place."""

    def grow(self) -> None:
        self._drawn()  # the seed's draws are of the loaded sizes
        self.messages().__class__ = Messages
        self._messages.grow()
        self.n0 = self.n
        self.m0 = len(self.pairs)
        self.new_persons: dict = {}
        self._knows_ms = np.array(self._drawn()["knows_ms"])
        self._pair_at: dict = {}  # added (a, b), a < b -> index in pairs
        self._added: dict = {}  # person -> added friends, ascending
        self._loaded_knows = None

    @property
    def knows_ms(self) -> np.ndarray:
        return self._knows_ms

    def person(self, i: int) -> dict:
        i = int(i)
        return super().person(i) if i < self.n0 else self.new_persons[i]

    def friends(self, i: int) -> np.ndarray:
        base = super().friends(i)
        added = self._added.get(int(i))
        return base if not added else np.union1d(
            base, np.array(added, np.int64))

    def add_person(self, i: int, row: dict) -> None:
        self.new_persons[int(i)] = row

    def add_knows(self, a: int, b: int, ms: int) -> None:
        """A friendship (LDBC IU8); a pair added twice keeps the later
        date, as the program's facet does."""
        a, b = min(a, b), max(a, b)
        at = self._pair_at.get((a, b))
        if at is not None:
            self._knows_ms[at] = ms
            return
        self._pair_at[(a, b)] = len(self.pairs)
        self.pairs = np.concatenate([self.pairs, [[a, b]]])
        self._knows_ms = np.append(self._knows_ms, ms)
        for p, q in ((a, b), (b, a)):
            bisect.insort(self._added.setdefault(p, []), q)

    def knows_of(self, p: int) -> list:
        """[(friend, the friendship's date)] of person `p`: the loaded
        pairs' (one sort, on the first call) and the added ones."""
        if self._loaded_knows is None:
            pairs, at = self.pairs[: self.m0], self._knows_ms[: self.m0]
            both = np.concatenate([pairs, pairs[:, ::-1]])
            dates = np.concatenate([at, at])
            order = np.lexsort((both[:, 1], both[:, 0]))
            self._loaded_knows = (
                both[order, 1], dates[order],
                np.searchsorted(both[order, 0], np.arange(self.n0 + 1)))
        friends, dates, starts = self._loaded_knows
        mine = slice(starts[p], starts[p + 1])
        rows = list(zip(friends[mine].tolist(), dates[mine].tolist()))
        for q in self._added.get(int(p), ()):
            rows.append((q, int(self._knows_ms[
                self._pair_at[(min(p, q), max(p, q))]])))
        return rows


def growing(model: snb.Model) -> Model:
    model.__class__ = Model
    model.grow()
    return model


def make(config: dict, seed: int, rdf_path=None) -> Model:
    return growing(snb.make(config, seed, rdf_path))


def install(config: dict, seed: int, alpha, store_dir: str):
    """`snb.install` on `store_dir`, then `hasMember` declared; the
    model grows from there. A program that cannot patch a value column
    with a commit's rows is refused first (`NEEDS`)."""
    from dgraph_tpu.utils import observe

    if not observe.registered_metric(NEEDS):
        raise SystemExit(
            f"chipbench: {config['name']} needs a program whose value "
            f"columns take a commit's rows (it declares no metric {NEEDS}: "
            "METRICS.md); this one drops complex read 9's column at every "
            "commit to creationDate and rescans it, or reads value by "
            "value, so an IC9 outlasts the window and the cell's sixteen "
            "clients are not offered")
    model, info = snb.install(config, seed, alpha, store_dir)
    alpha.engine.alter(SCHEMA_ADDED)
    return growing(model), info


def _counters() -> dict:
    from dgraph_tpu.utils.observe import METRICS

    return METRICS.snapshot()


def window_opens(model: Model) -> None:
    model.counters_at_window = _counters()


SHOWN = ("value_column", "commit", "group_commit", "num_commits",
         "mutate", "device_cache", "device_dispatch_total")


def describe(alpha, model: Model) -> dict:
    """The program's counters and gauges that moved over the window
    (those of the columns and the write path on stderr too)."""
    before = getattr(model, "counters_at_window", {})
    moved = {k: v - before.get(k, 0.0) for k, v in _counters().items()
             if v != before.get(k, 0.0)}
    shown = {k: v for k, v in sorted(moved.items()) if k.startswith(SHOWN)}
    print(f"counters in the window: {shown}", file=sys.stderr, flush=True)
    return {"counters_in_window": moved}
