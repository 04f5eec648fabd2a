"""Fault `one_commit_behind`: every read is served one commit behind,
at the snapshot published before the newest. It breaks a mix whose
reads follow their own client's writes (`snb.mixed16`'s session rule):
the read that comes right after a write misses it."""

from __future__ import annotations


def plant() -> None:
    from dgraph_tpu.api.server import Server

    def seen(self):
        published = self.__dict__.setdefault("_published", [0])
        return published[-2] if len(published) > 1 else published[-1]

    def publish(self, ts):
        published = self.__dict__.setdefault("_published", [0])
        if ts != published[-1]:
            published.append(ts)

    Server._snapshot_ts = property(seen, publish)
