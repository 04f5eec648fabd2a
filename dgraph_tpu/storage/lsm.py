"""LsmKV: log-structured spill-to-disk storage (the Badger equivalent).

The reference keeps everything in BadgerDB (LSM tree + value log,
/root/reference/worker/server_state.go:95); round-1's MemKV held the whole
DB in RAM. LsmKV bounds memory:

  - writes land in a WAL-backed memtable;
  - when the memtable exceeds `memtable_bytes` it flushes to an immutable
    sorted SSTable (sparse-indexed, mmap-read);
  - reads overlay memtable -> newest..oldest SSTables;
  - destructive ops (drop_prefix / delete_below) are sequence-stamped
    markers honored at read time and physically applied at compaction;
  - compaction k-way-merges all tables into one and clears applied
    markers (badger's level merge, flattened to one level — the access
    pattern here is bulk-load-then-read, not write-heavy churn).

Same KV interface as MemKV, so the posting layer, bulk loader, backup and
raft snapshot machinery run unchanged on top.

With `enc_key` every file entry — key AND value, WAL and SSTable — is
AES-CTR sealed (badger's block encryption role, enc/util.go key
plumbing): nothing about the graph, including value-derived index
tokens embedded in keys, reaches disk in plaintext. In-memory structures
and the sparse index (decrypted once at open) stay plaintext for
ordering/seeks.
"""

from __future__ import annotations

import bisect
import json
import mmap
import os
import struct
import threading
import zlib
from array import array
from typing import Dict, Iterator, List, Optional, Tuple

from dgraph_tpu import native as _native
from dgraph_tpu.storage.kv import KV

_ENT = struct.Struct("<IQQI")  # key_len, ts, seq, val_len
_WAL_REC = struct.Struct("<BIQQI")  # op, key_len, ts, seq, val_len
_OP_PUT = 0
_OP_DROP_PREFIX = 1
_OP_DELETE_BELOW = 2

_INDEX_EVERY = 64  # sparse index stride
_FOOTER_MAGIC = 0x4C534D32  # "LSM2": footer with bloom section
_BLOOM_BITS_PER_KEY = 10
_BLOOM_HASHES = 3


_M64 = (1 << 64) - 1


def _bloom_hashes(key: bytes) -> Tuple[int, int]:
    """Two independent hashes; probe bits via double hashing
    (h1 + i*h2 — the Kirsch-Mitzenmacher construction badger's blooms
    use). Base material is C-speed crc32+adler32 (a cryptographic hash
    here halved bulk-load throughput); a splitmix64 finalizer decorrelates
    them — raw crc32 with a different init is a linear transform of
    crc32(key), which would cluster the probe sets."""
    x = zlib.crc32(key) | (zlib.adler32(key) << 32)
    z = (x + 0x9E3779B97F4A7C15) & _M64
    z = ((z ^ (z >> 30)) * 0xBF58476D1CE4E5B9) & _M64
    z = ((z ^ (z >> 27)) * 0x94D049BB133111EB) & _M64
    h1 = z ^ (z >> 31)
    z = (x + 0x3C6EF372FE94F82A) & _M64
    z = ((z ^ (z >> 30)) * 0xBF58476D1CE4E5B9) & _M64
    z = ((z ^ (z >> 27)) * 0x94D049BB133111EB) & _M64
    h2 = (z ^ (z >> 31)) | 1
    return h1, h2


class _Bloom:
    __slots__ = ("bits", "nbits")

    def __init__(self, bits: bytearray):
        self.bits = bits
        self.nbits = len(bits) * 8

    @staticmethod
    def build_from_hashes(h1s: array, h2s: array) -> "_Bloom":
        n = max(1, len(h1s))
        nbits = -(-n * _BLOOM_BITS_PER_KEY // 8) * 8
        bits = bytearray(nbits // 8)
        for h1, h2 in zip(h1s, h2s):
            for i in range(_BLOOM_HASHES):
                b = (h1 + i * h2) % nbits
                bits[b >> 3] |= 1 << (b & 7)
        return _Bloom(bits)

    def may_contain(self, key: bytes) -> bool:
        h1, h2 = _bloom_hashes(key)
        nbits = self.nbits
        bits = self.bits
        for i in range(_BLOOM_HASHES):
            b = (h1 + i * h2) % nbits
            if not bits[b >> 3] & (1 << (b & 7)):
                return False
        return True


def _index_markers(markers: List[tuple]):
    """Index the persisted marker list for O(1)-ish visibility checks:
    drop-prefix markers stay a (short) list, delete_below markers become a
    per-key dict (they arrive one per rollup and would otherwise make
    _visible O(total rollups) per record)."""
    drops: List[Tuple[bytes, int]] = []
    delbelow: Dict[bytes, List[Tuple[int, int]]] = {}
    for m in markers:
        if m[0] == "drop":
            drops.append((m[1], m[2]))
        else:
            delbelow.setdefault(m[1], []).append((m[2], m[3]))
    return drops, delbelow


def _marker_visible(drops, delbelow, key: bytes, ts: int, seq: int) -> bool:
    for pref, mseq in drops:
        if seq < mseq and key.startswith(pref):
            return False
    got = delbelow.get(key)
    if got:
        for mts, mseq in got:
            if ts < mts and seq < mseq:
                return False
    return True


def _resolve_versions(per_ts: Dict[int, Tuple[int, bytes]], key, versions,
                      visible) -> None:
    """Fold (ts, seq, val) records for ONE key into per_ts with the MVCC
    resolution rule — markers applied, newest seq wins per ts. The single
    authority shared by the point-read and batched-read paths (they must
    never diverge: the MemoryLayer caches whichever answered first)."""
    for ts, seq, val in versions:
        if visible(key, ts, seq):
            got = per_ts.get(ts)
            if got is None or seq > got[0]:
                per_ts[ts] = (seq, val)


def _newest_wins(stream, visible):
    """Collapse an ascending (key, ts, seq, val) stream to the highest-seq
    record per (key, ts), dropping marker-hidden records — the shared
    dedup used by both compaction paths (must match the read path)."""
    pending = None
    for k, ts, seq, val in stream:
        if not visible(k, ts, seq):
            continue
        if pending is not None and (pending[0], pending[1]) != (k, ts):
            yield pending
        pending = (k, ts, seq, val)
    if pending is not None:
        yield pending


def _seal(blob: bytes, key: Optional[bytes]) -> bytes:
    if key is None:
        return blob
    from dgraph_tpu.enc.enc import encrypt_stream

    return encrypt_stream(blob, key)


def _unseal(blob: bytes, key: Optional[bytes]) -> bytes:
    if key is None:
        return blob
    from dgraph_tpu.enc.enc import decrypt_stream

    return decrypt_stream(blob, key)


class _SSTable:
    """Immutable sorted run: entries ascending by (key, ts).

    When `enc_key` is set each entry is one sealed blob
    [len u32][AES-CTR(key,ts,seq,val)] and the index is sealed wholesale;
    order still holds because writes happen from sorted plaintext."""

    def __init__(self, path: str, enc_key: Optional[bytes] = None):
        self.path = path
        self.enc_key = enc_key
        self._ref_mu = threading.Lock()
        self._refs = 1  # owner (LsmKV._tables) reference
        self._unlink = False
        self._closed = False
        self._f = open(path, "rb")
        self._mm = mmap.mmap(self._f.fileno(), 0, access=mmap.ACCESS_READ)
        # native scan fast path (plaintext tables only)
        self._native = enc_key is None and _native.sst_available()
        self._buf = (
            __import__("numpy").frombuffer(self._mm, dtype="uint8")
            if self._native
            else None
        )
        self._buf_ptr = (
            _native.buf_ptr(self._buf) if self._native else None
        )
        # footer (v2): [index_off u64][bloom_off u64][n u64][magic u32]
        # footer (v1): [index_off u64][n u64]  — pre-bloom tables
        self.bloom: Optional[_Bloom] = None
        idx_end = len(self._mm) - 16
        if (
            len(self._mm) >= 28
            and struct.unpack("<I", self._mm[-4:])[0] == _FOOTER_MAGIC
        ):
            idx_off, bloom_off, self.n = struct.unpack("<QQQ", self._mm[-28:-4])
            bloom_blob = _unseal(
                bytes(self._mm[bloom_off : len(self._mm) - 28]), enc_key
            )
            self.bloom = _Bloom(bytearray(bloom_blob))
            idx_end = bloom_off
        else:
            idx_off, self.n = struct.unpack("<QQ", self._mm[-16:])
        self._index: List[Tuple[bytes, int]] = []  # (key, file_offset)
        idx_blob = _unseal(bytes(self._mm[idx_off:idx_end]), enc_key)
        pos = 0
        end = len(idx_blob)
        while pos < end:
            (klen,) = struct.unpack_from("<I", idx_blob, pos)
            pos += 4
            k = idx_blob[pos : pos + klen]
            pos += klen
            (off,) = struct.unpack_from("<Q", idx_blob, pos)
            pos += 8
            self._index.append((k, off))
        # key-range bounds for table pruning (badger table min/max keys)
        self.min_key = self._index[0][0] if self._index else b""
        self.max_key = None  # lazily: last entry's key
        self._data_end = idx_off
        # what sst_versions_multi needs of this table, built ONCE: the
        # bloom's bits and the sparse index as flat arrays, so the range
        # test, the bloom test and the index seek of a batched probe run
        # in native code (the arrays live as long as the table)
        self._probe_addr, self._probe_keep = (
            _native.sst_probe_table(
                self._buf, self._data_end,
                self.bloom.bits if self.bloom is not None else None,
                self._index, self._max_key(),
            )
            if self._native
            else (None, None)
        )

    @staticmethod
    def write(
        path: str,
        entries: Iterator[Tuple[bytes, int, int, bytes]],
        enc_key: Optional[bytes] = None,
    ):
        """entries must be sorted ascending by (key, ts, seq)."""
        tmp = path + ".tmp"
        index: List[Tuple[bytes, int]] = []
        # bloom material as fixed-width hash pairs, not key bytes —
        # a multi-GB ingest would otherwise hold every key in memory
        bh1, bh2 = array("Q"), array("Q")
        last_key = None
        n = 0
        with open(tmp, "wb") as f:
            for key, ts, seq, val in entries:
                if n % _INDEX_EVERY == 0:
                    index.append((key, f.tell()))
                if key != last_key:
                    h1, h2 = _bloom_hashes(key)
                    bh1.append(h1)
                    bh2.append(h2)
                    last_key = key
                if enc_key is None:
                    f.write(_ENT.pack(len(key), ts, seq, len(val)))
                    f.write(key)
                    f.write(val)
                else:
                    blob = _seal(
                        _ENT.pack(len(key), ts, seq, len(val)) + key + val,
                        enc_key,
                    )
                    f.write(struct.pack("<I", len(blob)))
                    f.write(blob)
                n += 1
            idx_off = f.tell()
            import io as _io

            ib = _io.BytesIO()
            for k, off in index:
                ib.write(struct.pack("<I", len(k)))
                ib.write(k)
                ib.write(struct.pack("<Q", off))
            f.write(_seal(ib.getvalue(), enc_key))
            bloom_off = f.tell()
            f.write(
                _seal(bytes(_Bloom.build_from_hashes(bh1, bh2).bits), enc_key)
            )
            f.write(struct.pack("<QQQI", idx_off, bloom_off, n, _FOOTER_MAGIC))
            f.flush()
            os.fsync(f.fileno())
        os.replace(tmp, path)

    def _entry_at(self, pos: int):
        if self.enc_key is None:
            klen, ts, seq, vlen = _ENT.unpack_from(self._mm, pos)
            pos += _ENT.size
            key = bytes(self._mm[pos : pos + klen])
            pos += klen
            val = bytes(self._mm[pos : pos + vlen])
            pos += vlen
            return key, ts, seq, val, pos
        (blen,) = struct.unpack_from("<I", self._mm, pos)
        pos += 4
        blob = _unseal(bytes(self._mm[pos : pos + blen]), self.enc_key)
        pos += blen
        klen, ts, seq, vlen = _ENT.unpack_from(blob, 0)
        key = blob[_ENT.size : _ENT.size + klen]
        val = blob[_ENT.size + klen : _ENT.size + klen + vlen]
        return key, ts, seq, val, pos

    def _seek(self, key: bytes) -> int:
        """File offset of the first entry with entry_key >= key."""
        i = bisect.bisect_right(self._index, (key, -1)) - 1
        # start one stride earlier (sparse index points at stride heads)
        pos = self._index[i][1] if i >= 0 else (self._index[0][1] if self._index else 0)
        end = self._end()
        while pos < end:
            k, ts, seq, val, nxt = self._entry_at(pos)
            if k >= key:
                return pos
            pos = nxt
        return end

    def _end(self) -> int:
        return self._data_end

    def _max_key(self) -> bytes:
        if self.max_key is None:
            last = b""
            # scan the final index stride only
            pos = self._index[-1][1] if self._index else 0
            end = self._end()
            while pos < end:
                k, ts, seq, val, pos = self._entry_at(pos)
                last = k
            self.max_key = last
        return self.max_key

    def may_contain(self, key: bytes) -> bool:
        if not (self.min_key <= key <= self._max_key()):
            return False
        if self.bloom is not None and not self.bloom.may_contain(key):
            return False
        return True

    def versions_of(self, key: bytes) -> List[Tuple[int, int, bytes]]:
        """(ts, seq, val) ascending ts for one key."""
        if not self.may_contain(key):
            return []
        if self._native:
            start = self._index_start(key)
            tss, seqs, voffs, vlens = _native.sst_versions(
                self._buf, self._data_end, start, key, bptr=self._buf_ptr
            )
            return [
                (int(t), int(q), self._mm[vo : vo + vl])
                for t, q, vo, vl in zip(tss, seqs, voffs, vlens)
            ]
        out = []
        pos = self._seek(key)
        end = self._end()
        while pos < end:
            k, ts, seq, val, pos = self._entry_at(pos)
            if k != key:
                break
            out.append((ts, seq, val))
        return out

    def _index_start(self, key: bytes) -> int:
        i = bisect.bisect_right(self._index, (key, -1)) - 1
        if i >= 0:
            return self._index[i][1]
        return self._index[0][1] if self._index else 0

    def versions_of_many(self, keys_sorted: List[bytes]):
        """Batched versions_of over SORTED distinct keys: ONE native call
        prunes (key range, bloom), seeks the sparse index and walks the
        table monotonically (badger MultiGet shape), with no Python per
        key before it. Returns {key: [(ts, seq, val)]} for present keys
        only. Falls back to per-key probes without the native library
        and on encrypted tables. The table is immutable: a caller that
        holds a reference (`retain`) needs no lock."""
        out = {}
        if not self._native:
            for k in keys_sorted:
                got = self.versions_of(k)
                if got:
                    out[k] = got
            return out
        if not keys_sorted:
            return out
        flat = _native.sst_versions_multi(self._probe_addr, keys_sorted)
        mm = self._mm
        j = len(keys_sorted)  # the records follow the counts
        for k, n in zip(keys_sorted, flat):
            if n == 1:  # the bulk-loaded and the compacted shape
                vo = flat[j + 2]
                out[k] = [(flat[j], flat[j + 1], mm[vo : vo + flat[j + 3]])]
                j += 4
            elif n:
                end = j + 4 * n
                out[k] = [
                    (flat[i], flat[i + 1],
                     mm[flat[i + 2] : flat[i + 2] + flat[i + 3]])
                    for i in range(j, end, 4)
                ]
                j = end
        return out

    def scan(self, prefix: bytes = b""):
        """Yield (key, ts, seq, val) ascending from the first prefixed key."""
        if self._native:
            start = self._index_start(prefix) if prefix else 0
            if prefix:
                start = _native.sst_seek(
                    self._buf, self._end(), start, prefix
                )
            for ko, kl, ts, seq, vo, vl in _native.sst_scan(
                self._buf, self._end(), start, prefix
            ):
                yield (
                    self._mm[ko : ko + kl], ts, seq, self._mm[vo : vo + vl]
                )
            return
        pos = self._seek(prefix) if prefix else 0
        end = self._end()
        while pos < end:
            k, ts, seq, val, pos = self._entry_at(pos)
            if prefix and not k.startswith(prefix):
                break
            yield k, ts, seq, val

    def retain(self):
        with self._ref_mu:
            self._refs += 1

    def release(self):
        with self._ref_mu:
            self._refs -= 1
            if self._refs > 0 or self._closed:
                return
            self._closed = True
            unlink = self._unlink
        self._buf = None  # release the numpy buffer export before close
        self._buf_ptr = None
        self._mm.close()
        self._f.close()
        if unlink:
            try:
                os.unlink(self.path)
            except FileNotFoundError:
                pass

    def close(self, unlink: bool = False):
        """Drop the owner reference. Resources are freed (and the file
        unlinked, if requested) once in-flight iterators release theirs —
        compaction must not yank an mmap out from under a live scan."""
        with self._ref_mu:
            self._unlink = self._unlink or unlink
        self.release()


class LsmKV(KV):
    def __init__(self, dirpath: str, memtable_bytes: int = 8 << 20,
                 compact_at: int = 6, enc_key: Optional[bytes] = None):
        os.makedirs(dirpath, exist_ok=True)
        self.dir = dirpath
        self.memtable_bytes = memtable_bytes
        self.compact_at = compact_at
        self.enc_key = enc_key
        # a plaintext store probed by the native library: what the level
        # reads' one-pass decoder is for (posting/memlayer.py); an
        # encrypted store, and a process without the library, keep to
        # the general decoder
        self.native_probe = enc_key is None and _native.sst_available()
        self._mu = threading.RLock()
        # key -> [(ts, seq, val)] ascending ts
        self._mem: Dict[bytes, List[Tuple[int, int, bytes]]] = {}
        self._mem_size = 0
        self._seq = 0
        self._max_ts = 0  # highest version ts ever written (manifest-kept)
        # markers: ("drop", prefix, seq) | ("delbelow", key, ts, seq)
        self._markers: List[tuple] = []
        self._tables: List[_SSTable] = []  # newest first
        self._manifest_path = os.path.join(dirpath, "MANIFEST")
        self._wal_path = os.path.join(dirpath, "wal.log")
        self._wal = None
        self._open()

    # -- startup --------------------------------------------------------------

    def _open(self):
        names: List[str] = []
        if os.path.exists(self._manifest_path):
            with open(self._manifest_path) as f:
                man = json.load(f)
            self._seq = man.get("seq", 0)
            self._max_ts = man.get("max_ts", 0)
            self._markers = [tuple(m) for m in man.get("markers", [])]
            names = man.get("tables", [])
        # markers persisted as lists; key/prefix fields are latin-1 strings
        self._markers = [
            (m[0], m[1].encode("latin-1"), *m[2:]) if isinstance(m[1], str) else m
            for m in self._markers
        ]
        for name in names:  # manifest order: newest first
            self._tables.append(
                _SSTable(os.path.join(self.dir, name), self.enc_key)
            )
        if os.path.exists(self._wal_path):
            self._replay_wal()
        self._drops, self._delbelow = _index_markers(self._markers)
        self._wal = open(self._wal_path, "ab")

    def _save_manifest(self):
        man = {
            "seq": self._seq,
            "max_ts": self._max_ts,
            "tables": [os.path.basename(t.path) for t in self._tables],
            "markers": [
                (m[0], m[1].decode("latin-1"), *m[2:]) for m in self._markers
            ],
        }
        tmp = self._manifest_path + ".tmp"
        with open(tmp, "w") as f:
            json.dump(man, f)
            f.flush()
            os.fsync(f.fileno())
        os.replace(tmp, self._manifest_path)

    def _replay_wal(self):
        with open(self._wal_path, "rb") as f:
            data = f.read()
        pos, n = 0, len(data)
        while True:
            if self.enc_key is None:
                if pos + _WAL_REC.size > n:
                    break
                op, klen, ts, seq, vlen = _WAL_REC.unpack_from(data, pos)
                if (
                    pos + _WAL_REC.size + klen + vlen > n
                    or op > _OP_DELETE_BELOW
                ):
                    break
                pos += _WAL_REC.size
                key = data[pos : pos + klen]
                pos += klen
                val = data[pos : pos + vlen]
                pos += vlen
            else:
                if pos + 4 > n:
                    break
                (blen,) = struct.unpack_from("<I", data, pos)
                if pos + 4 + blen > n:
                    break
                try:
                    blob = _unseal(data[pos + 4 : pos + 4 + blen], self.enc_key)
                    op, klen, ts, seq, vlen = _WAL_REC.unpack_from(blob, 0)
                except Exception:
                    break
                if op > _OP_DELETE_BELOW:
                    break
                key = blob[_WAL_REC.size : _WAL_REC.size + klen]
                val = blob[_WAL_REC.size + klen : _WAL_REC.size + klen + vlen]
                pos += 4 + blen
            self._seq = max(self._seq, seq)
            if op == _OP_PUT:
                self._mem_put(key, ts, seq, val)
            elif op == _OP_DROP_PREFIX:
                self._markers.append(("drop", key, seq))
            else:
                self._markers.append(("delbelow", key, ts, seq))
        if pos < n:
            with open(self._wal_path, "r+b") as f:
                f.truncate(pos)

    # -- write path -----------------------------------------------------------

    def _wal_append(self, op, key, ts, seq, val=b""):
        if self.enc_key is None:
            self._wal.write(_WAL_REC.pack(op, len(key), ts, seq, len(val)))
            self._wal.write(key)
            self._wal.write(val)
        else:
            blob = _seal(
                _WAL_REC.pack(op, len(key), ts, seq, len(val)) + key + val,
                self.enc_key,
            )
            self._wal.write(struct.pack("<I", len(blob)))
            self._wal.write(blob)
        self._wal.flush()

    def _mem_put(self, key, ts, seq, val):
        if ts > self._max_ts:
            self._max_ts = ts
        vers = self._mem.get(key)
        if vers is None:
            vers = self._mem[key] = []
        # ascending ts; same-ts overwrite (idempotent replay)
        i = bisect.bisect_right(vers, ts, key=lambda x: x[0])
        if i > 0 and vers[i - 1][0] == ts:
            self._mem_size -= len(vers[i - 1][2])
            vers[i - 1] = (ts, seq, val)
        else:
            vers.insert(i, (ts, seq, val))
        self._mem_size += len(key) + len(val) + 24

    def put(self, key: bytes, ts: int, value: bytes) -> None:
        with self._mu:
            self._seq += 1
            self._mem_put(key, ts, self._seq, value)
            self._wal_append(_OP_PUT, key, ts, self._seq, value)
            if self._mem_size >= self.memtable_bytes:
                self._flush_locked()

    def put_batch(self, items) -> None:
        with self._mu:
            for k, ts, v in items:
                self._seq += 1
                self._mem_put(k, ts, self._seq, v)
                self._wal_append(_OP_PUT, k, ts, self._seq, v)
            if self._mem_size >= self.memtable_bytes:
                self._flush_locked()

    def drop_prefix(self, prefix: bytes) -> None:
        with self._mu:
            self._seq += 1
            self._markers.append(("drop", prefix, self._seq))
            self._drops.append((prefix, self._seq))
            self._wal_append(_OP_DROP_PREFIX, prefix, 0, self._seq)
            # memtable entries can be dropped eagerly
            for k in [k for k in self._mem if k.startswith(prefix)]:
                del self._mem[k]

    def delete_below(self, key: bytes, ts: int) -> None:
        with self._mu:
            self._seq += 1
            self._markers.append(("delbelow", key, ts, self._seq))
            self._delbelow.setdefault(key, []).append((ts, self._seq))
            self._wal_append(_OP_DELETE_BELOW, key, ts, self._seq)
            vers = self._mem.get(key)
            if vers:
                self._mem[key] = [v for v in vers if v[0] >= ts]

    # -- flush / compaction ---------------------------------------------------

    def _flush_locked(self):
        if not self._mem:
            return
        name = f"sst_{self._seq:016x}.tbl"
        path = os.path.join(self.dir, name)

        def entries():
            for k in sorted(self._mem):
                for ts, seq, val in self._mem[k]:
                    yield k, ts, seq, val

        _SSTable.write(path, entries(), self.enc_key)
        self._tables.insert(0, _SSTable(path, self.enc_key))
        self._mem.clear()
        self._mem_size = 0
        self._save_manifest()
        # restart the WAL: memtable is durable in the table now
        self._wal.close()
        self._wal = open(self._wal_path, "wb")
        if len(self._tables) >= self.compact_at:
            # size-tiered: fold the small tables together without
            # rewriting a dominant (bulk-ingested) table on every flush;
            # full merge when sizes are uniform (badger level merge) or
            # when the marker list has grown enough that clearing it
            # (only a full merge can) pays for the rewrite
            if len(self._markers) > 10_000 or not self._compact_partial_locked():
                self._compact_locked()

    def flush(self):
        with self._mu:
            self._flush_locked()

    def _visible(self, key: bytes, ts: int, seq: int) -> bool:
        return _marker_visible(self._drops, self._delbelow, key, ts, seq)

    def _compact_partial_locked(self) -> bool:
        """Size-tiered partial merge: when one table dominates (the bulk
        ingest case), fold every OTHER table into one and leave the giant
        alone. Markers stay (they span all layers); same-(key,ts) dupes
        resolve newest-seq-wins, matching the read path. Returns False
        when sizes are uniform and a full merge is the right move."""
        import heapq

        sizes = [os.path.getsize(t.path) for t in self._tables]
        biggest = max(sizes)
        if biggest < 4 * max(1, sorted(sizes)[-2] if len(sizes) > 1 else 0):
            return False
        keep = sizes.index(biggest)
        merge = [t for i, t in enumerate(self._tables) if i != keep]
        if len(merge) < 2:
            return False
        merged = heapq.merge(
            *(t.scan() for t in merge), key=lambda e: (e[0], e[1], e[2])
        )
        name = f"sst_{self._seq:016x}p.tbl"
        path = os.path.join(self.dir, name)
        _SSTable.write(
            path, _newest_wins(merged, self._visible), self.enc_key
        )
        giant = self._tables[keep]
        self._tables = [_SSTable(path, self.enc_key), giant]
        self._save_manifest()
        for t in merge:
            t.close(unlink=True)
        return True

    def _compact_locked(self):
        """Merge every table (and memtable) into one, applying markers."""
        import heapq

        streams = [t.scan() for t in self._tables]

        def memstream():
            for k in sorted(self._mem):
                for ts, seq, val in self._mem[k]:
                    yield k, ts, seq, val

        streams.insert(0, memstream())
        merged = heapq.merge(*streams, key=lambda e: (e[0], e[1], e[2]))
        # Same (key, ts) may appear in several layers (e.g. rollup_key
        # rewrites at the latest version's ts); _newest_wins applies the
        # read path's resolution.
        name = f"sst_{self._seq:016x}c.tbl"
        path = os.path.join(self.dir, name)
        _SSTable.write(
            path, _newest_wins(merged, self._visible), self.enc_key
        )
        old = self._tables
        self._tables = [_SSTable(path, self.enc_key)]
        self._mem.clear()
        self._mem_size = 0
        self._markers = []  # applied physically
        self._drops, self._delbelow = [], {}
        self._save_manifest()
        self._wal.close()
        self._wal = open(self._wal_path, "wb")
        for t in old:
            t.close(unlink=True)

    def compact(self):
        with self._mu:
            self._compact_locked()

    # -- read path ------------------------------------------------------------

    def _all_versions(self, key: bytes) -> List[Tuple[int, int, bytes]]:
        """(ts, seq, val) ascending ts, markers applied, newest-seq wins
        per ts (table order is irrelevant — partial compaction may reorder
        tables, seq is the authority)."""
        per_ts: Dict[int, Tuple[int, bytes]] = {}
        for t in self._tables:
            _resolve_versions(per_ts, key, t.versions_of(key), self._visible)
        _resolve_versions(
            per_ts, key, self._mem.get(key, []), self._visible
        )
        return [(ts, *per_ts[ts]) for ts in sorted(per_ts)]

    def get(self, key: bytes, read_ts: int) -> Optional[Tuple[int, bytes]]:
        with self._mu:
            vers = self._all_versions(key)
            best = None
            for ts, _, val in vers:
                if ts <= read_ts:
                    best = (ts, val)
            return best

    def versions(self, key: bytes, read_ts: int) -> List[Tuple[int, bytes]]:
        with self._mu:
            return [
                (ts, val)
                for ts, _, val in reversed(self._all_versions(key))
                if ts <= read_ts
            ]

    def versions_batch(
        self, keys_in: List[bytes], read_ts: int
    ) -> Dict[bytes, List[Tuple[int, bytes]]]:
        """versions() for many keys with one monotone probe pass per table
        — the read path for level-batched query fan-out (badger MultiGet
        analog; kills the per-key re-seek that dominated 2-hop queries on
        this backend).

        Under the store's lock only what must be consistent is taken: the
        list of tables (each retained, so a flush or a compaction that
        runs meanwhile leaves this probe on the tables it started with),
        the memtable's entries for the asked keys and the markers that
        can touch them. The tables are immutable, so the probe itself
        runs unlocked: sixteen readers no longer take turns at it."""
        ks = sorted(set(keys_in))
        with self._mu:
            tables = list(self._tables)
            for t in tables:
                t.retain()
            mem = self._mem
            mem_vers = (
                [(k, list(mem[k])) for k in ks if k in mem] if mem else ()
            )
            drops = list(self._drops)
            delbelow = (
                {k: list(self._delbelow[k]) for k in ks
                 if k in self._delbelow}
                if self._delbelow else None
            )
        try:
            found = [t.versions_of_many(ks) for t in tables]
        finally:
            for t in tables:
                t.release()
        if mem_vers:
            found.append(dict(mem_vers))
        recs = found[0] if found else {}
        for src in found[1:]:
            for k, vers in src.items():
                got = recs.get(k)
                recs[k] = vers if got is None else got + vers

        def visible(key, ts, seq):
            return _marker_visible(drops, delbelow or {}, key, ts, seq)

        out: Dict[bytes, List[Tuple[int, bytes]]] = {}
        for k, vers in recs.items():
            if len(vers) == 1 and not drops and not (
                delbelow and k in delbelow
            ):
                # one record and no marker that can hide it: nothing to
                # resolve (the bulk-loaded and the compacted shape)
                ts, _, val = vers[0]
                out[k] = [(ts, val)] if ts <= read_ts else []
                continue
            d: Dict[int, Tuple[int, bytes]] = {}
            _resolve_versions(d, k, vers, visible)
            out[k] = [
                (ts, d[ts][1])
                for ts in sorted(d, reverse=True)
                if ts <= read_ts
            ]
        return out

    def _merged_keys(self, prefix: bytes) -> Iterator[bytes]:
        import heapq

        streams = []
        for t in self._tables:
            streams.append((k for k, _, _, _ in t.scan(prefix)))
        streams.append(
            iter(sorted(k for k in self._mem if k.startswith(prefix)))
        )
        last = None
        for k in heapq.merge(*streams):
            if k != last:
                last = k
                yield k

    def _merged_stream(self, prefix: bytes):
        """ONE streaming k-way merge over every table + memtable snapshot,
        grouped by key: yields (key, {ts: (seq, val)}) with markers applied.
        Replaces the per-key re-probe pattern (O(keys*tables) seeks) that
        made multi-table iteration 10-100x slower than a single table."""
        import heapq

        with self._mu:
            tables = list(self._tables)
            for t in tables:
                t.retain()
            mem_snap = sorted(
                (k, list(vs))
                for k, vs in self._mem.items()
                if k.startswith(prefix)
            )
            drops = list(self._drops)
            delbelow = {k: list(v) for k, v in self._delbelow.items()}

        def visible(key, ts, seq):
            return _marker_visible(drops, delbelow, key, ts, seq)

        def memstream():
            for k, vs in mem_snap:
                for ts, seq, val in vs:
                    yield k, ts, seq, val

        try:
            streams = [t.scan(prefix) for t in tables]
            if mem_snap:
                streams.append(memstream())
            if len(streams) == 1:
                merged = streams[0]  # single sorted source: skip the heap
            else:
                merged = heapq.merge(
                    *streams, key=lambda e: (e[0], e[1], e[2])
                )
            cur_key = None
            per_ts: Dict[int, Tuple[int, bytes]] = {}
            for k, ts, seq, val in merged:
                if k != cur_key:
                    if cur_key is not None and per_ts:
                        yield cur_key, per_ts
                    cur_key = k
                    per_ts = {}
                if not visible(k, ts, seq):
                    continue
                got = per_ts.get(ts)
                if got is None or seq > got[0]:
                    per_ts[ts] = (seq, val)
            if cur_key is not None and per_ts:
                yield cur_key, per_ts
        finally:
            for t in tables:
                t.release()

    def iterate(self, prefix: bytes, read_ts: int):
        for k, per_ts in self._merged_stream(prefix):
            best = None
            for ts in per_ts:
                if ts <= read_ts and (best is None or ts > best):
                    best = ts
            if best is not None:
                yield (k, best, per_ts[best][1])

    def iterate_versions(self, prefix: bytes, read_ts: int):
        for k, per_ts in self._merged_stream(prefix):
            vs = [
                (ts, per_ts[ts][1])
                for ts in sorted(per_ts, reverse=True)
                if ts <= read_ts
            ]
            if vs:
                yield (k, vs)

    # -- snapshot interop (raft) ----------------------------------------------

    def dump_bytes(self) -> bytes:
        import io

        from dgraph_tpu.storage.kv import _WAL_REC as _MREC, _OP_PUT as _MPUT

        with self._mu:
            out = io.BytesIO()
            for k in self._merged_keys(b""):
                for ts, _, v in self._all_versions(k):
                    out.write(_MREC.pack(_MPUT, len(k), ts, len(v)))
                    out.write(k)
                    out.write(v)
            return out.getvalue()

    def load_bytes(self, blob: bytes):
        from dgraph_tpu.storage.kv import _WAL_REC as _MREC

        with self._mu:
            for t in self._tables:
                t.close(unlink=True)
            self._tables = []
            self._mem.clear()
            self._mem_size = 0
            self._markers = []
            self._drops, self._delbelow = [], {}
            self._wal.close()
            self._wal = open(self._wal_path, "wb")
            pos, n = 0, len(blob)
            while pos + _MREC.size <= n:
                op, klen, ts, vlen = _MREC.unpack_from(blob, pos)
                pos += _MREC.size
                key = blob[pos : pos + klen]
                pos += klen
                val = blob[pos : pos + vlen]
                pos += vlen
                self._seq += 1
                self._mem_put(key, ts, self._seq, val)
                self._wal_append(_OP_PUT, key, ts, self._seq, val)
            self._save_manifest()

    def ingest_sorted(self, entries):
        """Stream key-sorted (key, ts, value) records straight into ONE new
        SSTable — no WAL, no memtable, no compaction (badger StreamWriter,
        the bulk loader's reduce output path). Records must arrive in
        ascending key order."""
        with self._mu:
            self._seq += 1
            base = self._seq
            name = f"sst_{base:016x}i.tbl"
            path = os.path.join(self.dir, name)

            def with_seq():
                n = 0
                for key, ts, val in entries:
                    n += 1
                    if ts > self._max_ts:
                        self._max_ts = ts
                    yield key, ts, base + n, val
                self._seq = base + n

            _SSTable.write(path, with_seq(), self.enc_key)
            if self._seq == base:
                # empty stream: an entry-less table would satisfy no
                # lookup yet shadow older tables in get() — drop it
                os.unlink(path)
                return
            self._tables.insert(0, _SSTable(path, self.enc_key))
            self._save_manifest()

    def ingest_native_sst(self, write_table, ts: int) -> int:
        """Bulk-ingest seam for the native reduce (native/bulkload.cpp):
        `write_table(path, seq_base) -> n` writes a complete SSTable in
        the _SSTable layout directly; we allocate the seq range and
        register the finished table. Unencrypted stores only — callers
        gate on enc_key."""
        if self.enc_key is not None:
            raise ValueError("native SSTable ingest requires no enc_key")
        with self._mu:
            self._seq += 1
            base = self._seq
            name = f"sst_{base:016x}i.tbl"
            path = os.path.join(self.dir, name)
            try:
                n = write_table(path, base)
            except Exception:
                self._seq = base - 1  # roll back the seq reservation
                try:
                    os.unlink(path)
                except FileNotFoundError:
                    pass
                raise
            if n <= 0:
                # same empty-stream rule as ingest_sorted: an entry-less
                # table would shadow older tables in get()
                self._seq = base - 1
                try:
                    os.unlink(path)
                except FileNotFoundError:
                    pass
                return 0
            self._seq = base + n
            if ts > self._max_ts:
                self._max_ts = ts
            self._tables.insert(0, _SSTable(path, self.enc_key))
            self._save_manifest()
            return n

    def mut_seq(self) -> int:
        """Global mutation counter: bumps on every write (put/markers/
        ingest/load). Readers use it to skip per-key cache revalidation
        when the store hasn't changed at all (posting/memlayer.py)."""
        return self._seq

    def max_write_ts(self) -> int:
        """Highest version ts ever written. A cache entry built at
        read_ts >= max_write_ts is a complete view for EVERY later
        read_ts as long as mut_seq hasn't moved (posting/memlayer.py
        fast path)."""
        return self._max_ts

    def sync(self):
        with self._mu:
            if self._wal is not None:
                self._wal.flush()
                os.fsync(self._wal.fileno())

    def close(self):
        with self._mu:
            if self._wal is not None:
                self._wal.flush()
                self._wal.close()
                self._wal = None
            for t in self._tables:
                t.close()
            self._tables = []
