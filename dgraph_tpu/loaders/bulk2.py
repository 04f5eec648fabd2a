"""Out-of-core parallel bulk loader: map workers -> sorted spill runs ->
streaming k-way reduce -> direct storage ingest.

Mirrors /root/reference/dgraph/cmd/bulk (loader.go:354 mapStage,
loader.go:554 reduceStage, reduce.go:51): the map phase parses RDF chunks
into packed map entries and spills them to disk as SORTED runs whenever the
in-memory buffer exceeds `spill_entries` (the external sort the in-memory
BulkLoader lacks); the reduce phase k-way-merges
the runs, groups by key, and emits final rollup records in key order.

Storage ingest is backend-aware:
  - LsmKV: the sorted reduce stream writes ONE SSTable directly
    (badger's StreamWriter shape) — no WAL, no memtable, no compaction.
  - MemKV: batched put_batch.

Map workers run in separate processes (fork: schema + xidmap shared
copy-on-write); on a single-core box the loader transparently degrades to
in-process mapping. XIDs are resolved by a cheap regex pre-pass in the
parent so every worker sees one consistent uid assignment
(ref xidmap/xidmap.go shared map).
"""

from __future__ import annotations

import heapq
import os
import re
import struct
import tempfile
from typing import Dict, Iterable, Iterator, List, Optional, Tuple

import numpy as np

from dgraph_tpu.codec import uidpack
from dgraph_tpu.loaders.rdf import parse_rdf
from dgraph_tpu.x import config
from dgraph_tpu.posting.pl import (
    OP_SET,
    Posting,
    decode_posting_bytes,
    encode_posting_bytes,
    encode_rollup,
    lang_uid,
    rollup_writes,
    value_uid,
)
from dgraph_tpu.tok.tok import build_tokens
from dgraph_tpu.types.types import TypeID, Val, convert, to_binary
from dgraph_tpu.x import keys

_K_UID = 0  # payload: 8B target uid (data/reverse uid edge)
_K_VAL = 1  # payload: wire-encoded Posting (pl.encode_posting_bytes)
_K_IDX = 2  # payload: 8B uid (index entry)

_REC = struct.Struct("<HBI")  # klen, kind, plen

_XID_RE = re.compile(r"<([^>]+)>|(_:[\w.\-]+)")


def _pack_entry(key: bytes, kind: int, payload: bytes) -> bytes:
    return _REC.pack(len(key), kind, len(payload)) + key + payload


class _Run:
    """One sorted spill run on disk."""

    def __init__(self, path: str):
        self.path = path

    @staticmethod
    def write(path: str, entries: List[Tuple[bytes, int, bytes]]) -> "_Run":
        entries.sort(key=lambda e: (e[0], e[1], e[2]))
        with open(path, "wb") as f:
            for key, kind, payload in entries:
                f.write(_pack_entry(key, kind, payload))
        return _Run(path)

    def __iter__(self) -> Iterator[Tuple[bytes, int, bytes]]:
        # buffered incremental read: reduce holds every run open at once,
        # so per-run memory must stay O(record), not O(file)
        with open(self.path, "rb", buffering=1 << 20) as f:
            while True:
                hdr = f.read(_REC.size)
                if len(hdr) < _REC.size:
                    return
                klen, kind, plen = _REC.unpack(hdr)
                key = f.read(klen)
                payload = f.read(plen)
                yield key, kind, payload


class _MapState:
    """Per-worker accumulator that spills sorted runs."""

    def __init__(self, workdir: str, wid: int, spill_entries: int):
        self.workdir = workdir
        self.wid = wid
        self.spill_entries = spill_entries
        self.entries: List[Tuple[bytes, int, bytes]] = []
        self.runs: List[str] = []
        self.inferred: Dict[str, int] = {}  # pred -> TypeID value
        self.nquads = 0

    def add(self, key: bytes, kind: int, payload: bytes):
        self.entries.append((key, kind, payload))
        if len(self.entries) >= self.spill_entries:
            self.spill()

    def spill(self):
        if not self.entries:
            return
        path = os.path.join(
            self.workdir, f"run_{self.wid}_{len(self.runs):04d}.map"
        )
        _Run.write(path, self.entries)
        self.runs.append(path)
        self.entries = []


# the overwhelmingly common bulk-corpus line shapes, parsed without the
# general statement splitter: <s> <p> <o> .   |   <s> <p> "literal" .
_FAST_UID = re.compile(r"^<([^>]+)>\s+<([^>]+)>\s+<([^>]+)>\s+\.$")
_FAST_LIT = re.compile(r'^<([^>]+)>\s+<([^>]+)>\s+"([^"\\]*)"\s+\.$')


def _map_chunk(args) -> dict:
    """Worker: parse one RDF text chunk into sorted spill runs."""
    text, wid, workdir, spill_entries, schema, xidmap, ns = args
    st = _MapState(workdir, wid, spill_entries)

    def resolve(ref: str) -> int:
        if ref.startswith("0x"):
            return int(ref, 16)
        if ref.isdigit():
            return int(ref)
        return xidmap[ref]

    def iter_nquads():
        from dgraph_tpu.loaders.rdf import NQuad

        slow_lines: List[str] = []
        for line in text.split("\n"):
            line = line.strip()
            if not line or line.startswith("#"):
                continue
            m = _FAST_UID.match(line)
            if m:
                yield NQuad(
                    subject=m.group(1),
                    predicate=m.group(2),
                    object_id=m.group(3),
                )
                continue
            m = _FAST_LIT.match(line)
            if m:
                yield NQuad(
                    subject=m.group(1),
                    predicate=m.group(2),
                    object_value=Val(TypeID.DEFAULT, m.group(3)),
                )
                continue
            slow_lines.append(line)
        if slow_lines:
            yield from parse_rdf("\n".join(slow_lines))

    for nq in iter_nquads():
        st.nquads += 1
        subj = resolve(nq.subject)
        attr = nq.predicate
        su = schema.get(attr)
        if su is None:
            tid = (
                TypeID.UID
                if nq.object_id
                else (
                    nq.object_value.tid
                    if nq.object_value
                    else TypeID.DEFAULT
                )
            )
            st.inferred.setdefault(attr, int(tid))
            from dgraph_tpu.schema.schema import SchemaUpdate

            su = SchemaUpdate(predicate=attr, value_type=tid)
            if tid == TypeID.UID:
                su.is_list = True
            schema.set(su)

        if nq.object_id:
            obj = resolve(nq.object_id)
            st.add(
                keys.DataKey(attr, subj, ns), _K_UID, struct.pack("<Q", obj)
            )
            if nq.facets:
                # uid-edge facets ride as a value-less Posting next to the
                # pack (posting/pl.py rollup keeps them alongside)
                fb = {k: to_binary(v) for k, v in nq.facets.items()}
                ft = {k: v.tid for k, v in nq.facets.items()}
                st.add(
                    keys.DataKey(attr, subj, ns),
                    _K_VAL,
                    encode_posting_bytes(
                        Posting(
                            uid=obj, op=OP_SET, facets=fb, facet_types=ft
                        )
                    ),
                )
            if su.directive_reverse:
                st.add(
                    keys.ReverseKey(attr, obj, ns),
                    _K_UID,
                    struct.pack("<Q", subj),
                )
                if nq.facets:
                    st.add(
                        keys.ReverseKey(attr, obj, ns),
                        _K_VAL,
                        encode_posting_bytes(
                            Posting(
                                uid=subj, op=OP_SET, facets=fb,
                                facet_types=ft,
                            )
                        ),
                    )
            continue

        stored = (
            convert(nq.object_value, su.value_type)
            if su.value_type != TypeID.DEFAULT
            else nq.object_value
        )
        vbytes = to_binary(stored)
        puid = (
            value_uid(stored)
            if su.is_list
            else lang_uid(nq.lang if su.lang else "")
        )
        fb = {k: to_binary(v) for k, v in nq.facets.items()}
        ft = {k: v.tid for k, v in nq.facets.items()}
        post = Posting(
            uid=puid,
            op=OP_SET,
            value=vbytes,
            value_type=stored.tid,
            lang=nq.lang,
            facets=fb,
            facet_types=ft,
        )
        st.add(
            keys.DataKey(attr, subj, ns),
            _K_VAL,
            encode_posting_bytes(post),
        )
        for tokb in build_tokens(stored, su.tokenizer_objs()):
            st.add(
                keys.IndexKey(attr, tokb, ns),
                _K_IDX,
                struct.pack("<Q", subj),
            )
    st.spill()
    return {
        "runs": st.runs,
        "nquads": st.nquads,
        "inferred": st.inferred,
    }


class ParallelBulkLoader:
    """Map/shuffle/reduce bulk loader with bounded memory."""

    def __init__(
        self,
        server,
        workdir: Optional[str] = None,
        workers: Optional[int] = None,
        spill_entries: int = 1_000_000,
        ns: int = keys.GALAXY_NS,
    ):
        self.server = server
        self.ns = ns
        self.workdir = workdir or tempfile.mkdtemp(prefix="bulk_")
        os.makedirs(self.workdir, exist_ok=True)
        self.workers = workers or (os.cpu_count() or 1)
        self.spill_entries = spill_entries
        self.nquads = 0

    # -- xid pre-pass ---------------------------------------------------------

    def _assign_xids(self, texts: List[str]) -> Dict[str, int]:
        """One consistent xid -> uid map before mapping (ref xidmap)."""
        xids: Dict[str, int] = {}
        need = False
        for text in texts:
            for m in _XID_RE.finditer(text):
                ref = m.group(1) or m.group(2)
                if ref.startswith("_:"):
                    need = True
                    xids.setdefault(ref, 0)
                elif not (ref.startswith("0x") or ref.isdigit()):
                    # predicate IRIs also match this regex; the extra
                    # entries are never resolved, they just reserve a uid
                    # (cheap over-approximation, one pass, no parser)
                    need = True
                    xids.setdefault(ref, 0)
        if not xids:
            return {}
        base = self.server.zero.assign_uids(len(xids))
        for i, x in enumerate(sorted(xids)):
            xids[x] = base + i
        return xids

    # -- driver ---------------------------------------------------------------

    def load_files(self, paths: List[str]) -> int:
        import gzip

        texts = []
        for p in paths:
            opener = gzip.open if p.endswith(".gz") else open
            with opener(p, "rt") as f:
                texts.append(f.read())
        return self.load_texts(texts)

    def load_text(self, text: str) -> int:
        return self.load_texts([text])

    # -- native pipeline ------------------------------------------------------

    # tokenizers the C++ fast path emits itself (tok/tok.py identifier
    # bytes); predicates with any OTHER tokenizer are withheld from the
    # native pred table so their lines take the Python slow path
    _NATIVE_TOKS = {
        "term": 0x1, "exact": 0x2, "year": 0x4, "month": 0x41,
        "day": 0x42, "hour": 0x43, "int": 0x6, "float": 0x7,
        "fulltext": 0x8, "bool": 0x9,
    }
    # (PASSWORD is excluded: conversion bcrypt-hashes the value)
    _NATIVE_TYPES = {
        TypeID.DEFAULT, TypeID.STRING, TypeID.UID, TypeID.INT,
        TypeID.FLOAT, TypeID.BOOL, TypeID.DATETIME,
    }

    def _native_ok(self) -> bool:
        from dgraph_tpu import native

        if not getattr(native, "NATIVE_AVAILABLE", False):
            return False
        if not config.get("BULK_NATIVE"):
            return False
        # vector predicates feed the similarity engine through the
        # Python reduce — keep the whole load on the Python path
        return not any(
            getattr(self.server.schema.get(p), "vector_specs", None)
            for p in self.server.schema.predicates()
        )

    def _native_push_preds(self, lib, ctx):
        import ctypes

        lib.bulk_clear_preds(ctx)
        for pred in self.server.schema.predicates():
            su = self.server.schema.get(pred)
            if su is None or su.value_type not in self._NATIVE_TYPES:
                continue
            if su.lang:
                continue  # @lang values need lang_uid plumbing: slow
            toks = []
            exotic = False
            for t in su.tokenizers or []:
                tid = self._NATIVE_TOKS.get(t)
                if tid is None:
                    exotic = True
                    break
                toks.append(tid)
            if exotic:
                continue
            flags = (
                (1 if su.is_list else 0)
                | (2 if su.directive_reverse else 0)
                | (4 if su.count else 0)
            )
            nb = pred.encode("utf-8")
            arr = (ctypes.c_uint8 * len(toks))(*toks)
            lib.bulk_add_pred(
                ctx, nb, len(nb), int(su.value_type), flags,
                arr, len(toks), self.ns,
            )

    def _load_texts_native(self, texts: List[str]) -> Optional[int]:
        """C++ map+reduce for the common line shapes; unhandled lines
        round-trip through the Python mapper into the same run format.
        Returns the commit ts, or None to fall back entirely (with
        nquads and temp files restored to their pre-call state)."""
        import ctypes

        from dgraph_tpu import native

        lib = native._LIB
        ctx = lib.bulk_new()
        nquads_before = self.nquads
        cleanup: List[str] = []

        def fall_back():
            self.nquads = nquads_before
            for p in cleanup:
                try:
                    os.unlink(p)
                except FileNotFoundError:
                    pass
            return None

        try:
            blob = "\n".join(texts).encode("utf-8")
            n_xids = lib.bulk_scan_xids(ctx, blob, len(blob))
            if n_xids:
                base = self.server.zero.assign_uids(int(n_xids))
                lib.bulk_set_base(ctx, base)
            self._native_push_preds(lib, ctx)
            slow_path = os.path.join(self.workdir, "slow.rdf")
            n = lib.bulk_map(
                ctx, blob, len(blob), self.ns,
                self.workdir.encode(), slow_path.encode(),
                self.spill_entries,
            )
            cleanup.append(slow_path)
            if n < 0:
                return fall_back()
            self.nquads += int(n)
            run_paths = []
            for i in range(lib.bulk_run_count(ctx)):
                buf = ctypes.create_string_buffer(4096)
                if lib.bulk_run_path(ctx, i, buf, 4096) <= 0:
                    # a dropped run would silently lose edges: fall back
                    return fall_back()
                run_paths.append(buf.value.decode())
            cleanup.extend(run_paths)

            # slow lines: Python mapper, same run format
            slow_text = ""
            if os.path.exists(slow_path):
                with open(slow_path) as f:
                    slow_text = f.read()
            if slow_text.strip():
                class _XidView(dict):
                    def __missing__(_s, name):  # noqa: N805
                        nb = name.encode("utf-8")
                        u = lib.bulk_xid_lookup(ctx, nb, len(nb))
                        if not u:
                            u = self.server.zero.assign_uids(1)
                        _s[name] = u
                        return u

                r = _map_chunk(
                    (
                        slow_text, 9999, self.workdir,
                        self.spill_entries, self.server.schema,
                        _XidView(), self.ns,
                    )
                )
                self.nquads += r["nquads"]
                run_paths.extend(r["runs"])
                cleanup.extend(r["runs"])
                for pred, tid in r["inferred"].items():
                    self.server.schema.ensure_default(pred, TypeID(tid))
                # inferred preds may carry count/reverse defaults the
                # reduce needs; refresh the native pred table
                self._native_push_preds(lib, ctx)

            ts = self.server.zero.next_ts()
            out_main = os.path.join(self.workdir, "reduced.main")
            out_extra = os.path.join(self.workdir, "reduced.extra")
            out_stats = os.path.join(self.workdir, "reduced.stats")
            joined = "\n".join(run_paths).encode()
            max_part = int(config.get("MAX_PART_UIDS"))
            kv = self.server.kv
            sst_direct = (
                hasattr(kv, "ingest_native_sst")
                and getattr(kv, "enc_key", None) is None
            )
            cleanup.extend([out_main, out_extra, out_stats])
            if sst_direct:
                # the reduce emits the SSTable itself — no per-record
                # Python loop between merge and disk
                def write_table(path: str, seq_base: int) -> int:
                    n = lib.bulk_reduce(
                        ctx, joined, len(joined), max_part,
                        path.encode(), out_extra.encode(),
                        out_stats.encode(), self.ns,
                        1, ts, seq_base,
                    )
                    if n < 0:
                        raise RuntimeError("native reduce failed")
                    return int(n)

                try:
                    kv.ingest_native_sst(write_table, ts)
                except RuntimeError:
                    return fall_back()
                if os.path.getsize(out_extra) > 0:
                    self._ingest(_iter_reduced(out_extra, ts), ts)
            else:
                nrec = lib.bulk_reduce(
                    ctx, joined, len(joined), max_part,
                    out_main.encode(), out_extra.encode(),
                    out_stats.encode(), self.ns,
                    0, 0, 0,
                )
                if nrec < 0:
                    return fall_back()
                self._ingest(_iter_reduced(out_main, ts), ts)
                if os.path.getsize(out_extra) > 0:
                    self._ingest(_iter_reduced(out_extra, ts), ts)
            self._ingest_stats(out_stats)
            for p in cleanup:
                try:
                    os.unlink(p)
                except FileNotFoundError:
                    pass
            return ts
        finally:
            lib.bulk_free(ctx)

    def load_texts(self, texts: List[str]) -> int:
        if self._native_ok():
            ts = self._load_texts_native(texts)
            if ts is not None:
                self._bump_snapshot()
                return ts
        xidmap = self._assign_xids(texts)
        chunks = self._chunk(texts)
        jobs = [
            (
                chunk,
                i,
                self.workdir,
                self.spill_entries,
                self.server.schema,
                xidmap,
                self.ns,
            )
            for i, chunk in enumerate(chunks)
        ]
        results = self._run_map(jobs)
        runs: List[_Run] = []
        for r in results:
            self.nquads += r["nquads"]
            runs.extend(_Run(p) for p in r["runs"])
            for pred, tid in r["inferred"].items():
                su = self.server.schema.ensure_default(pred, TypeID(tid))
        ts = self._reduce(runs)
        for r in runs:
            try:
                os.unlink(r.path)
            except FileNotFoundError:
                pass
        self._bump_snapshot()
        return ts

    def _bump_snapshot(self):
        # direct-KV writes bypassed the commit path: advance the
        # snapshot watermark so watermark reads see the loaded data
        bump = getattr(self.server, "bump_snapshot", None)
        if bump is not None:
            bump()

    def _chunk(self, texts: List[str]) -> List[str]:
        """Split on line boundaries into ~workers*2 chunks."""
        blob = "\n".join(texts)
        want = max(1, self.workers * 2)
        if want == 1 or len(blob) < 1 << 20:
            return [blob]
        size = len(blob) // want + 1
        chunks = []
        pos = 0
        while pos < len(blob):
            end = min(len(blob), pos + size)
            nl = blob.find("\n", end)
            end = len(blob) if nl < 0 else nl
            chunks.append(blob[pos:end])
            pos = end + 1
        return chunks

    def _run_map(self, jobs) -> List[dict]:
        if self.workers <= 1 or len(jobs) <= 1:
            return [_map_chunk(j) for j in jobs]
        import multiprocessing as mp

        ctx = mp.get_context("fork")
        with ctx.Pool(self.workers) as pool:
            return pool.map(_map_chunk, jobs)

    # -- reduce ---------------------------------------------------------------

    def _reduce(self, runs: List[_Run]) -> int:
        server = self.server
        ts = server.zero.next_ts()
        merged = heapq.merge(*runs, key=lambda e: (e[0], e[1], e[2]))
        counts: Dict[Tuple[str, int, int], List[int]] = {}
        vecs_out: List[Tuple[str, int, np.ndarray]] = []
        stats = getattr(server, "stats", None)

        def groups():
            cur_key: Optional[bytes] = None
            uids: List[int] = []
            posts: List[bytes] = []
            for key, kind, payload in merged:
                if key != cur_key:
                    if cur_key is not None:
                        yield cur_key, uids, posts
                    cur_key, uids, posts = key, [], []
                if kind == _K_VAL:
                    posts.append(payload)
                else:
                    uids.append(struct.unpack("<Q", payload)[0])
            if cur_key is not None:
                yield cur_key, uids, posts

        from dgraph_tpu.types.types import from_binary

        vec_preds = {
            p
            for p in server.schema.predicates()
            if getattr(server.schema.get(p), "vector_specs", None)
        }

        def writes() -> Iterator[Tuple[bytes, int, bytes]]:
            for key, uids, posts in groups():
                if posts:
                    pk = keys.parse_key(key)
                    su = server.schema.get(pk.attr) if pk.is_data else None
                    dedup: Dict[int, Posting] = {}
                    for pb in posts:
                        p: Posting = decode_posting_bytes(pb)
                        if (
                            p.is_value
                            and su is not None
                            and su.value_type not in (TypeID.DEFAULT, p.value_type)
                        ):
                            # workers infer undeclared-predicate types on
                            # their own chunk; the merged schema (chunk-order
                            # first-wins) is authoritative — re-convert here
                            # so stored data is chunking-independent, and
                            # fail loudly on unconvertible values like the
                            # sequential loader does
                            v = convert(
                                from_binary(TypeID(p.value_type), p.value),
                                su.value_type,
                            )
                            p.value = to_binary(v)
                            p.value_type = v.tid
                        dedup[p.uid] = p  # merge order = run order
                    ordered = [dedup[u] for u in sorted(dedup)]
                    if pk.is_data and pk.attr in vec_preds:
                        for p in ordered:
                            if p.is_value:
                                vecs_out.append(
                                    (
                                        pk.attr,
                                        pk.uid,
                                        np.frombuffer(p.value, np.float32),
                                    )
                                )
                    u = (
                        np.unique(np.asarray(uids, np.uint64))
                        if uids
                        else np.zeros((0,), np.uint64)
                    )
                    if len(u) and pk.is_data and su is not None and su.count:
                        counts.setdefault(
                            (pk.attr, len(u), pk.ns), []
                        ).append(pk.uid)
                    yield key, ts, encode_rollup(
                        uidpack.serialize_uids(u), ordered
                    )
                    continue
                u = np.unique(np.asarray(uids, np.uint64))
                pk = keys.parse_key(key)
                if pk.is_data:
                    su = server.schema.get(pk.attr)
                    if su is not None and su.count:
                        counts.setdefault(
                            (pk.attr, len(u), pk.ns), []
                        ).append(pk.uid)
                elif pk.is_index and stats is not None:
                    stats.record(pk.attr, pk.term, len(u))
                for w in rollup_writes(key, u, [], ts):
                    yield w

        self._ingest(writes(), ts)
        # count-index keys sort elsewhere in keyspace: small second batch
        if counts:
            cw = []
            for (attr, cnt, cns), us in sorted(counts.items()):
                pack = uidpack.encode(np.unique(np.asarray(us, np.uint64)))
                cw.append(
                    (
                        keys.CountKey(attr, cnt, False, cns),
                        ts,
                        encode_rollup(pack, []),
                    )
                )
            cw.sort(key=lambda w: w[0])
            self._ingest(iter(cw), ts)
        # vector predicates feed the similarity engine directly (the old
        # in-memory loader's server.vector_indexes path — review finding)
        for attr, subj, vec in vecs_out:
            server._ensure_vector_index(server.schema.get(attr))
            server.vector_indexes[attr].insert(subj, vec)
        return ts

    def _ingest_stats(self, path: str):
        """Feed StatsHolder from the native reduce's index-selectivity
        sidecar ([u16 klen][key][u64 uid_count] per index key) at load
        finish: the C++ fast path once skipped selectivity stats, and eq
        plans fell back to defaults until the first commits."""
        stats = getattr(self.server, "stats", None)
        if stats is None or not os.path.exists(path):
            return
        with open(path, "rb", buffering=1 << 20) as f:
            while True:
                hdr = f.read(2)
                if len(hdr) < 2:
                    break
                (kl,) = struct.unpack("<H", hdr)
                key = f.read(kl)
                cnt = f.read(8)
                if len(key) < kl or len(cnt) < 8:
                    break  # truncated tail — stats are advisory
                try:
                    pk = keys.parse_key(key)
                except Exception:
                    # unparseable key: records are length-framed, so the
                    # stream is still in sync — skip just this one
                    continue
                if pk.is_index:
                    stats.record(
                        pk.attr, pk.term, struct.unpack("<Q", cnt)[0]
                    )

    def _ingest(self, stream: Iterator[Tuple[bytes, int, bytes]], ts: int):
        kv = self.server.kv
        if hasattr(kv, "ingest_sorted"):
            kv.ingest_sorted(stream)  # LsmKV: direct SSTable stream write
            return
        batch = []
        for w in stream:
            batch.append(w)
            if len(batch) >= 100_000:
                kv.put_batch(batch)
                batch = []
        if batch:
            kv.put_batch(batch)


def _iter_reduced(path: str, ts: int):
    """Stream the native reduce output: [u16 klen][key][u32 rlen][rec].
    A short read mid-record means the reduce output was truncated
    (disk full / killed writer) — fail loudly, never ingest a prefix
    silently."""
    with open(path, "rb", buffering=1 << 22) as f:
        while True:
            hdr = f.read(2)
            if not hdr:
                return
            if len(hdr) < 2:
                raise ValueError(f"truncated reduce output: {path}")
            (kl,) = struct.unpack("<H", hdr)
            key = f.read(kl)
            lenb = f.read(4)
            if len(key) < kl or len(lenb) < 4:
                raise ValueError(f"truncated reduce output: {path}")
            (rl,) = struct.unpack("<I", lenb)
            rec = f.read(rl)
            if len(rec) < rl:
                raise ValueError(f"truncated reduce output: {path}")
            yield key, ts, rec


def bulk_load_parallel(
    server,
    rdf_text: str = "",
    paths: Optional[List[str]] = None,
    workers: Optional[int] = None,
    workdir: Optional[str] = None,
) -> int:
    """Load RDF through the out-of-core parallel pipeline. Returns the
    commit ts (same contract as loaders.bulk.bulk_load_rdf)."""
    ld = ParallelBulkLoader(server, workdir=workdir, workers=workers)
    texts = []
    if rdf_text:
        texts.append(rdf_text)
    if paths:
        import gzip

        for p in paths:
            opener = gzip.open if p.endswith(".gz") else open
            with opener(p, "rt") as f:
                texts.append(f.read())
    return ld.load_texts(texts)
