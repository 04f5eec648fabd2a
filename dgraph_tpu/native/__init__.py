"""Native host kernel loader: compiles codec.cpp once, binds via ctypes.

The C++ layer covers the host-side hot paths (SURVEY.md §2.7): the
bit-pack codec used by UID pack (de)serialization and the scalar sorted-set
ops used by the dispatcher's small-op fallback. Python/numpy mirrors keep
the library importable where no compiler exists (`NATIVE_AVAILABLE` tells
you which you got, `BUILD_ERROR` why); a process that serves calls
`require()` at start-up, so a failed build is an error there and never a
quiet switch to the mirrors.
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import tempfile
from array import array
from itertools import accumulate
from typing import Optional

import numpy as np

from dgraph_tpu.x import config

_LIB: Optional[ctypes.CDLL] = None
# The same kernels, called WITHOUT giving up the interpreter's lock. A
# call through _LIB releases it, which is right for milliseconds of native
# work and wrong for microseconds: with other threads waiting, the release
# hands the lock over, and the caller queues for it again. On this
# sandbox's CPU that turns a 1.4 us call into 18-29 us of wall time and
# 23-40 us of CPU (futex, wake-up, a cold cache) at 4-16 threads, while
# the held call stays at 1.4-1.6 us (PERF.md, PR 35). A wrapper picks
# _HELD where the work is bounded by a few microseconds (the level
# reads' probe of a handful of keys).
_HELD: Optional[ctypes.PyDLL] = None
NATIVE_AVAILABLE = False
BUILD_ERROR: Optional[str] = None  # why the build/load failed, if it did

# ---------------------------------------------------------------------------
# ctypes ABI declarations
#
# ONE declarative table, consumed by BOTH the binder below and the static
# ABI cross-checker (dgraph_tpu/analysis/check_ctypes_abi.py), which parses
# the extern "C" signatures in codec.cpp / bulkload.cpp and verifies arity,
# widths and signedness against this table. Every exported function must be
# listed with an EXPLICIT restype: a missing restype on an int64_t-returning
# function silently truncates through ctypes' c_int default — on results
# >= 2**31 (flat decode counts, file offsets) that is a memory-corruption
# class bug, not a style nit. restype None == C void.
# ---------------------------------------------------------------------------

_u8p = ctypes.POINTER(ctypes.c_uint8)
_u32p = ctypes.POINTER(ctypes.c_uint32)
_u64p = ctypes.POINTER(ctypes.c_uint64)
_i8p = ctypes.POINTER(ctypes.c_int8)
_i32 = ctypes.c_int32
_i32p = ctypes.POINTER(ctypes.c_int32)
_i64 = ctypes.c_int64
_i64p = ctypes.POINTER(ctypes.c_int64)
_u64 = ctypes.c_uint64
_int = ctypes.c_int
_f32 = ctypes.c_float
_f32p = ctypes.POINTER(ctypes.c_float)
_vp = ctypes.c_void_p
_cp = ctypes.c_char_p

DECLS = {
    # codec.cpp — bit-pack codec + sorted-set kernels
    "bitpack": (None, [_u32p, _i64, _int, _u8p]),
    "bitunpack": (None, [_u8p, _i64, _i64, _int, _u32p]),
    "pack_decode_blocks": (_i64, [_u64p, _i32p, _u32p, _i64, _i64p, _i64, _u64p]),
    "packs_decode_many": (
        _i64,
        [
            ctypes.POINTER(_u64p), ctypes.POINTER(_i32p),
            ctypes.POINTER(_u32p), _i64p, _i64, _i64, _u64p, _i64p,
        ],
    ),
    "pack_intersect_small": (
        _i64,
        [_u64p, _i32p, _u32p, _i64, _i64, _u64p, _u64p, _i64, _u64p, _i64p],
    ),
    # codec.cpp — adaptive bitmap/packed block engine
    "pack_build_bitmaps": (
        None,
        [_i32p, _u32p, _i64, _i64, _i32p, _i64, _u64p],
    ),
    "pack_pair_setop": (
        _i64,
        [
            _int,
            _u64p, _i32p, _u32p, _i64, _i64, _u64p, _u64p, _i32p,
            _u64p, _i32p, _u32p, _i64, _i64, _u64p, _u64p, _i32p,
            _i64, _u64p, _i64p,
        ],
    ),
    "pack_stream_setop": (
        _i64,
        [
            _int, _u64p, _i64,
            _u64p, _i32p, _u32p, _i64, _i64, _u64p, _u64p, _i32p,
            _i64, _u64p, _i64p,
        ],
    ),
    # codec.cpp — streaming arena result encoder
    "enc_uid_objs": (_i64, [_u64p, _i64, _u8p, _i64, _u8p, _i64, _u8p]),
    "enc_int_objs": (_i64, [_i64p, _i64, _u8p, _i64, _u8p, _i64, _u8p]),
    # codec.cpp — mutation write-path kernels (group commit)
    "enc_delta_records": (
        _i64,
        [_i64p, _i64, _u8p, _u64p, _u8p, _i64p, _u8p, _u8p, _i64p],
    ),
    "tok_terms_ascii": (
        _i64,
        [_u8p, _i64p, _i64, _int, _u8p, _i64p, _i64p],
    ),
    # codec.cpp — columnar batch apply (posting/colwrite.py). void*
    # params by design: the wrapper passes raw buffer addresses
    # (array.array buffer_info / bytes), skipping the per-argument
    # ctypes pointer casts that dominate small-batch commit cost
    "batch_apply": (
        _i64,
        [
            _vp, _i64, _vp, _vp, _vp, _vp, _vp, _vp, _vp,
            _vp, _vp, _vp, _vp, _i64,
            _vp, _vp, _vp, _vp, _vp, _vp, _vp, _vp, _i64,
        ],
    ),
    "batch_apply_caps": (
        _i64,
        [_vp, _i64, _vp, _vp, _vp, _vp, _vp, _i64, _vp],
    ),
    # codec.cpp — quantized vector scoring (models/vector.py)
    "vec_qi8_topk": (
        _i64,
        [
            _i8p, _i64, _i64, _f32p, _f32p, _i32p, _f32p, _u8p,
            _i8p, _f32p, _f32p, _i32p, _f32p,
            _i64, _int, _i64, _i64p, _f32p,
        ],
    ),
    "vec_qi8_topk_idx": (
        _i64,
        [
            _i8p, _i64, _f32p, _f32p, _i32p, _f32p, _u8p,
            _i32p, _i64, _i8p, _f32, _f32, _i32, _f32,
            _int, _i64, _i64p, _f32p,
        ],
    ),
    "vec_qi8_topk_lists": (
        _i64,
        [
            _i8p, _i64, _f32p, _f32p, _i32p, _f32p, _u8p,
            _i32p, _i64p, _i64p,
            _i8p, _f32p, _f32p, _i32p, _f32p,
            _i64, _int, _i64, _i64, _i64p, _f32p,
        ],
    ),
    "vec_qi8_quantize": (
        _i64,
        [_f32p, _i64, _i64, _i64, _i8p, _f32p, _f32p, _i32p, _f32p],
    ),
    "intersect_u64": (_i64, [_u64p, _i64, _u64p, _i64, _u64p]),
    "union_u64": (_i64, [_u64p, _i64, _u64p, _i64, _u64p]),
    "difference_u64": (_i64, [_u64p, _i64, _u64p, _i64, _u64p]),
    "merge_sorted_u64": (_i64, [_u64p, _i64p, _i64, _u64p, _u64p]),
    # codec.cpp — SSTable entry scans
    "sst_seek": (_i64, [_u8p, _i64, _i64, _u8p, _i64]),
    "sst_versions": (
        _i64,
        [_u8p, _i64, _i64, _u8p, _i64, _i64, _u64p, _u64p, _i64p, _i64p],
    ),
    # void* params by design, like batch_apply: the table's words and the
    # thread's scratch by address, the keys as the bytes they are
    "sst_versions_multi": (_i64, [_vp, _i64, _vp, _vp, _i64, _vp]),
    "sst_scan": (
        _i64,
        [
            _u8p, _i64, _i64, _u8p, _i64, _i64,
            _i64p, _i64p, _u64p, _u64p, _i64p, _i64p, _i64p,
        ],
    ),
    # bulkload.cpp — offline bulk-load pipeline
    "bulk_new": (_vp, []),
    "bulk_free": (None, [_vp]),
    "bulk_scan_xids": (_i64, [_vp, _cp, _i64]),
    "bulk_set_base": (None, [_vp, _u64]),
    "bulk_xid_lookup": (_u64, [_vp, _cp, _i64]),
    "bulk_clear_preds": (None, [_vp]),
    "bulk_add_pred": (_int, [_vp, _cp, _i64, _int, _int, _u8p, _i64, _u64]),
    "bulk_map": (_i64, [_vp, _cp, _i64, _u64, _cp, _cp, _i64]),
    "bulk_run_count": (_i64, [_vp]),
    "bulk_run_path": (_i64, [_vp, _i64, _cp, _i64]),
    "bulk_reduce": (
        _i64,
        [_vp, _cp, _i64, _u64, _cp, _cp, _cp, _u64, _i64, _u64, _u64],
    ),
}

# sanitizer build modes: flags + a cache-key suffix so instrumented and
# plain builds never collide in the shared /tmp cache dir
_SAN_FLAGS = {
    "": [],
    # UBSan aborts on the first finding (no silent recovery) — the
    # randomized packed-setops corpus runs under this in the slow suite
    "ubsan": ["-fsanitize=undefined", "-fno-sanitize-recover=all"],
    # ASan .so needs the asan runtime loaded FIRST: run python under
    # LD_PRELOAD=$(g++ -print-file-name=libasan.so) (see README)
    "asan": ["-fsanitize=address"],
    # TSan is the only tool that sees races inside the std::thread
    # fan-outs (vec_qi8_topk_lists, vec_qi8_quantize, batch_apply
    # under concurrent group-commit batches); same LD_PRELOAD story
    # with libtsan.so — tests/test_native_san.py drives the matrix
    "tsan": ["-fsanitize=thread"],
}


def _build_and_load():
    here = os.path.dirname(__file__)
    srcs = [
        os.path.join(here, "codec.cpp"),
        os.path.join(here, "bulkload.cpp"),
    ]
    h = hashlib.sha256()
    for s in srcs + [os.path.join(here, "sst_bloom.h")]:
        with open(s, "rb") as f:
            h.update(f.read())
    tag = h.hexdigest()[:16]
    san = config.get("NATIVE_SAN").strip().lower()
    san_flags = _SAN_FLAGS.get(san)
    if san_flags is None:
        # unknown sanitizer name: fail the build, don't guess
        raise ValueError(f"unknown DGRAPH_TPU_NATIVE_SAN={san!r}")
    if san:
        tag = f"{tag}-{san}"
    cache_dir = config.get("NATIVE_CACHE") or os.path.join(
        tempfile.gettempdir(), "dgraph_tpu_native"
    )
    os.makedirs(cache_dir, exist_ok=True)
    so_path = os.path.join(cache_dir, f"codec-{tag}.so")
    if not os.path.exists(so_path):
        tmp = so_path + f".tmp{os.getpid()}"
        cmd = [
            "g++", "-O3", "-shared", "-fPIC", "-std=c++17", "-pthread",
            *san_flags, "-o", tmp, *srcs,
        ]
        # -march=native unlocks SIMD; retry without it if unsupported
        try:
            subprocess.run(
                cmd[:2] + ["-march=native"] + cmd[2:],
                check=True, capture_output=True, timeout=120,
            )
        except (subprocess.CalledProcessError, FileNotFoundError,
                subprocess.TimeoutExpired):
            subprocess.run(cmd, check=True, capture_output=True, timeout=120)
        os.replace(tmp, so_path)
    # the same library twice: CDLL releases the interpreter's lock
    # around a call, PyDLL holds it (_HELD, below)
    libs = ctypes.CDLL(so_path), ctypes.PyDLL(so_path)
    for lib in libs:
        for name, (restype, argtypes) in DECLS.items():
            fn = getattr(lib, name)
            fn.restype = restype
            fn.argtypes = argtypes
    return libs


def _ptr(arr: np.ndarray, ctype):
    return arr.ctypes.data_as(ctypes.POINTER(ctype))


try:
    _LIB, _HELD = _build_and_load()
    NATIVE_AVAILABLE = True
except Exception as _e:  # no compiler, failed compile, unloadable .so
    # CalledProcessError carries the compiler's stderr
    BUILD_ERROR = "{!r} {}".format(
        _e,
        (getattr(_e, "stderr", None) or b"")[-2000:].decode(errors="replace"),
    ).strip()


def require() -> None:
    """Raise unless the compiled kernels are loaded — the start-up check
    of every serving entry point (cli alpha/bulk, chip_smoke.py)."""
    if not NATIVE_AVAILABLE:
        raise RuntimeError(
            "native host kernels failed to build or load "
            f"(dgraph_tpu/native): {BUILD_ERROR}"
        )


# ---------------------------------------------------------------------------
# numpy-facing wrappers (with pure-Python fallbacks)
# ---------------------------------------------------------------------------


def bitpack(vals: np.ndarray, width: int) -> bytes:
    vals = np.ascontiguousarray(vals, dtype=np.uint32)
    n = vals.size
    if width == 0 or n == 0:
        return b""
    nbytes = (n * width + 7) // 8
    if n <= 32:
        # tiny arrays: ctypes marshaling costs more than packing — build
        # one big int and slice its bytes (bulk loads are dominated by
        # small per-key lists)
        acc = 0
        shift = 0
        for v in vals.tolist():
            acc |= int(v) << shift
            shift += width
        return acc.to_bytes(nbytes, "little")
    if _LIB is not None:
        out = np.zeros((nbytes + 8,), np.uint8)  # slack for the 5-byte write
        _LIB.bitpack(
            _ptr(vals, ctypes.c_uint32), n, width, _ptr(out, ctypes.c_uint8)
        )
        return out[:nbytes].tobytes()
    from dgraph_tpu.codec.uidpack import _bitpack_py

    return _bitpack_py(vals, width)


def bitunpack(data: bytes, count: int, width: int) -> np.ndarray:
    if width == 0 or count == 0:
        return np.zeros((count,), np.uint32)
    if count <= 32:
        acc = int.from_bytes(data[: (count * width + 7) // 8], "little")
        mask = (1 << width) - 1
        return np.fromiter(
            ((acc >> (i * width)) & mask for i in range(count)),
            dtype=np.uint32,
            count=count,
        )
    if _HELD is not None:
        # _HELD: one block, at most 256 lanes. The general record decoder
        # calls this once a record, and with the level reads off the
        # store's lock several threads decode side by side: giving the
        # interpreter's lock away here cost a cold `knows` level on the
        # chip's host 2.4 times its CPU (PERF.md, PR 35)
        buf = np.frombuffer(data, dtype=np.uint8)
        out = np.empty((count,), np.uint32)
        _HELD.bitunpack(
            _ptr(buf, ctypes.c_uint8),
            buf.size,
            count,
            width,
            _ptr(out, ctypes.c_uint32),
        )
        return out
    from dgraph_tpu.codec.uidpack import _bitunpack_py

    return _bitunpack_py(data, count, width)


def pack_decode_blocks(bases, counts, offsets, idxs):
    """Partial UidPack decode (codec/uidpack.decode_blocks fast path).
    Returns the decoded sorted u64 array, or None when the native lib is
    unavailable (caller falls back to the numpy masked broadcast)."""
    if _LIB is None:
        return None
    idxs = np.ascontiguousarray(idxs, np.int64)
    total = int(counts[idxs].sum())
    out = np.empty((total,), np.uint64)
    if total == 0:
        return out
    # bind conversions to locals so any converted temporaries outlive the
    # native call (inline _ptr(ascontiguousarray(...)) would free them
    # before the call runs)
    bases = np.ascontiguousarray(bases, np.uint64)
    counts = np.ascontiguousarray(counts, np.int32)
    offsets = np.ascontiguousarray(offsets, np.uint32)
    n = _LIB.pack_decode_blocks(
        _ptr(bases, ctypes.c_uint64),
        _ptr(counts, ctypes.c_int32),
        _ptr(offsets, ctypes.c_uint32),
        offsets.shape[1],
        idxs.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
        idxs.size,
        _ptr(out, ctypes.c_uint64),
    )
    return out[:n]


def packs_decode_many(packs):
    """Decode N UidPacks into (flat u64 buffer, int64[n+1] prefix offsets)
    in ONE native call — the level-batched fan-out read path (N parents'
    posting lists materialized together). Returns None when the native lib
    is unavailable (caller falls back to per-pack decode)."""
    if _LIB is None:
        return None
    n = len(packs)
    offs = np.zeros((n + 1,), np.int64)
    total = sum(p.num_uids for p in packs)
    out = np.empty((total,), np.uint64)
    if n == 0 or total == 0:
        return out, offs
    u64p = ctypes.POINTER(ctypes.c_uint64)
    i32p = ctypes.POINTER(ctypes.c_int32)
    u32p = ctypes.POINTER(ctypes.c_uint32)
    bases_pp = (u64p * n)()
    counts_pp = (i32p * n)()
    offsets_pp = (u32p * n)()
    nblocks = np.empty((n,), np.int64)
    block_size = 0
    # keep converted temporaries alive past the native call
    keep = []
    for i, p in enumerate(packs):
        b = np.ascontiguousarray(p.bases, np.uint64)
        c = np.ascontiguousarray(p.counts, np.int32)
        o = np.ascontiguousarray(p.offsets, np.uint32)
        keep.append((b, c, o))
        bases_pp[i] = _ptr(b, ctypes.c_uint64)
        counts_pp[i] = _ptr(c, ctypes.c_int32)
        offsets_pp[i] = _ptr(o, ctypes.c_uint32)
        nblocks[i] = b.size
        if o.ndim == 2 and o.shape[1]:
            block_size = o.shape[1]
    _LIB.packs_decode_many(
        bases_pp,
        counts_pp,
        offsets_pp,
        nblocks.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
        block_size,
        n,
        _ptr(out, ctypes.c_uint64),
        offs.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
    )
    return out, offs


def pack_ptrs(bases, counts, offsets, maxes):
    """Pre-built ctypes pointers for a long-lived pack's block arrays —
    callers cache the tuple on the pack so per-op calls skip the
    numpy->ctypes marshaling that dominates tiny-frontier latency (same
    trick as buf_ptr for SSTable mmaps)."""
    return (
        _ptr(bases, ctypes.c_uint64),
        _ptr(counts, ctypes.c_int32),
        _ptr(offsets, ctypes.c_uint32),
        _ptr(maxes, ctypes.c_uint64),
    )


def pack_intersect_small(bases, counts, offsets, maxes, a, ptrs=None):
    """Tiny-frontier compressed-domain intersect: one native call, zero
    decode. Returns (hits u64 array, touched_uids) or None when the native
    lib is unavailable."""
    if _LIB is None:
        return None
    if ptrs is None:
        ptrs = pack_ptrs(bases, counts, offsets, maxes)
    a = np.ascontiguousarray(a, np.uint64)
    out = np.empty((a.size,), np.uint64)
    touched = ctypes.c_int64(0)
    n = _LIB.pack_intersect_small(
        ptrs[0],
        ptrs[1],
        ptrs[2],
        offsets.shape[1],
        bases.size,
        ptrs[3],
        _ptr(a, ctypes.c_uint64),
        a.size,
        _ptr(out, ctypes.c_uint64),
        ctypes.byref(touched),
    )
    return out[:n], int(touched.value)


def pack_build_bitmaps(counts, offsets, rows, bm_bits, out_words) -> bool:
    """Scatter eligible blocks' offsets into the zeroed COMPACT bitset
    matrix; `rows` maps block index -> words row (or -1)
    (codec/uidpack.block_bitmaps fast path). Returns False when the
    native lib is unavailable (caller falls back to the numpy scatter)."""
    if _LIB is None:
        return False
    counts = np.ascontiguousarray(counts, np.int32)
    offsets = np.ascontiguousarray(offsets, np.uint32)
    rows = np.ascontiguousarray(rows, np.int32)
    _LIB.pack_build_bitmaps(
        _ptr(counts, ctypes.c_int32),
        _ptr(offsets, ctypes.c_uint32),
        offsets.shape[1],
        counts.size,
        _ptr(rows, ctypes.c_int32),
        bm_bits,
        _ptr(out_words, ctypes.c_uint64),
    )
    return True


def _bm_arrays(words, rows, ok):
    """(words, rows) contiguous arrays for a compact bitmap sidecar, or
    (None, None) when no block is eligible (the kernels take the packed
    arms only). Callers MUST bind the returns to locals so the converted
    temporaries outlive the native call."""
    if words is None:
        return None, None
    return (
        np.ascontiguousarray(words, np.uint64),
        np.ascontiguousarray(rows, np.int32),
    )


def pack_pair_setop(op, pa, pb, a_bm, b_bm, bm_bits):
    """Compressed-domain pack x pack set op (0=intersect, 1=difference)
    via the adaptive per-block-pair engine. `a_bm`/`b_bm` are the
    compact (words, rows, ok) bitmap sidecars from
    codec/uidpack.block_bitmaps.
    Returns (result u64 array, kernel_counts int64[4]) or None when the
    native lib is unavailable."""
    if _LIB is None:
        return None
    cap = min(pa.num_uids, pb.num_uids) if op == 0 else pa.num_uids
    out = np.empty((cap,), np.uint64)
    kc = np.zeros((4,), np.int64)
    if cap == 0:
        return out, kc
    a_b = np.ascontiguousarray(pa.bases, np.uint64)
    a_c = np.ascontiguousarray(pa.counts, np.int32)
    a_o = np.ascontiguousarray(pa.offsets, np.uint32)
    b_b = np.ascontiguousarray(pb.bases, np.uint64)
    b_c = np.ascontiguousarray(pb.counts, np.int32)
    b_o = np.ascontiguousarray(pb.offsets, np.uint32)
    # keep sidecar conversions alive past the call
    a_wa, a_ra = _bm_arrays(*a_bm)
    b_wa, b_ra = _bm_arrays(*b_bm)
    a_words = _ptr(a_wa, ctypes.c_uint64) if a_wa is not None else None
    a_rowsp = _ptr(a_ra, ctypes.c_int32) if a_ra is not None else None
    b_words = _ptr(b_wa, ctypes.c_uint64) if b_wa is not None else None
    b_rowsp = _ptr(b_ra, ctypes.c_int32) if b_ra is not None else None
    from dgraph_tpu.codec.uidpack import block_maxes

    a_m = block_maxes(pa)
    b_m = block_maxes(pb)
    n = _LIB.pack_pair_setop(
        op,
        _ptr(a_b, ctypes.c_uint64), _ptr(a_c, ctypes.c_int32),
        _ptr(a_o, ctypes.c_uint32), a_o.shape[1], a_b.size,
        _ptr(a_m, ctypes.c_uint64), a_words, a_rowsp,
        _ptr(b_b, ctypes.c_uint64), _ptr(b_c, ctypes.c_int32),
        _ptr(b_o, ctypes.c_uint32), b_o.shape[1], b_b.size,
        _ptr(b_m, ctypes.c_uint64), b_words, b_rowsp,
        bm_bits,
        _ptr(out, ctypes.c_uint64),
        _ptr(kc, ctypes.c_int64),
    )
    return out[:n], kc


def pack_stream_setop(op, a, pack, bm, bm_bits):
    """Compressed-domain sorted-array x pack set op (0=intersect,
    1=difference): stream `a` against the pack's blocks, probing bitmap
    containers where present. Returns (result, kernel_counts int64[4])
    or None when the native lib is unavailable."""
    if _LIB is None:
        return None
    a = np.ascontiguousarray(a, np.uint64)
    out = np.empty((a.size,), np.uint64)
    kc = np.zeros((4,), np.int64)
    if a.size == 0:
        return out, kc
    bases = np.ascontiguousarray(pack.bases, np.uint64)
    counts = np.ascontiguousarray(pack.counts, np.int32)
    offsets = np.ascontiguousarray(pack.offsets, np.uint32)
    wa, ra = _bm_arrays(*bm)
    words = _ptr(wa, ctypes.c_uint64) if wa is not None else None
    rowsp = _ptr(ra, ctypes.c_int32) if ra is not None else None
    from dgraph_tpu.codec.uidpack import block_maxes

    maxes = block_maxes(pack)
    n = _LIB.pack_stream_setop(
        op,
        _ptr(a, ctypes.c_uint64), a.size,
        _ptr(bases, ctypes.c_uint64), _ptr(counts, ctypes.c_int32),
        _ptr(offsets, ctypes.c_uint32), offsets.shape[1], bases.size,
        _ptr(maxes, ctypes.c_uint64), words, rowsp,
        bm_bits,
        _ptr(out, ctypes.c_uint64),
        _ptr(kc, ctypes.c_int64),
    )
    return out[:n], kc


def _enc_objs(fn_name, vals, ctype, per_item, pre: bytes, post: bytes):
    """Shared driver for the arena encoder kernels: one native call
    emits the whole run into a fresh scratch buffer; the returned
    uint8 view is appended to the arena zero-copy (the final join is
    the only copy). Returns None when the native lib is unavailable
    (caller takes the byte-identical Python fallback)."""
    if _LIB is None:
        return None
    n = vals.size
    if n == 0:
        return np.zeros((0,), np.uint8)
    cap = n * (len(pre) + len(post) + per_item + 1)
    out = np.empty((cap,), np.uint8)
    preb = np.frombuffer(pre, np.uint8) if pre else np.zeros(1, np.uint8)
    postb = np.frombuffer(post, np.uint8) if post else np.zeros(1, np.uint8)
    got = getattr(_LIB, fn_name)(
        _ptr(vals, ctype), n,
        _ptr(preb, ctypes.c_uint8), len(pre),
        _ptr(postb, ctypes.c_uint8), len(post),
        _ptr(out, ctypes.c_uint8),
    )
    return out[:got]


def enc_uid_objs(uids: np.ndarray, pre: bytes, post: bytes):
    """`pre + hex(uid) + post` per uid, comma-joined — the
    `{"uid":"0x1"},{"uid":"0x2"}` bulk emitter (query/streamjson.py).
    Returns a uint8 array view or None without the native lib."""
    uids = np.ascontiguousarray(uids, np.uint64)
    return _enc_objs("enc_uid_objs", uids, ctypes.c_uint64, 16, pre, post)


def enc_int_objs(vals: np.ndarray, pre: bytes, post: bytes):
    """`pre + str(val) + post` per int64, comma-joined — the
    `{"c":5},{"c":3}` count-object bulk emitter."""
    vals = np.ascontiguousarray(vals, np.int64)
    return _enc_objs("enc_int_objs", vals, ctypes.c_int64, 20, pre, post)


def enc_delta_records(counts, flags, uids, tids, vlens, vblob: bytes):
    """Batched posting-delta record encode (posting/pl.encode_deltas):
    ONE native call serializes every fast-shape posting (no lang, no
    facets) of a whole txn's write set, byte-identical to the per-key
    Python encoder. Returns a list of per-key record bytes (aligned
    with `counts`), or None when the native lib is unavailable."""
    if _LIB is None:
        return None
    counts = np.ascontiguousarray(counts, np.int64)
    flags = np.ascontiguousarray(flags, np.uint8)
    uids = np.ascontiguousarray(uids, np.uint64)
    tids = np.ascontiguousarray(tids, np.uint8)
    vlens = np.ascontiguousarray(vlens, np.int64)
    n_keys = counts.size
    total = int(5 * n_keys + 17 * flags.size + vlens.sum())
    out = np.empty((total,), np.uint8)
    offs = np.empty((n_keys + 1,), np.int64)
    vb = (
        np.frombuffer(vblob, np.uint8) if vblob else np.zeros(1, np.uint8)
    )
    wrote = _LIB.enc_delta_records(
        _ptr(counts, ctypes.c_int64), n_keys,
        _ptr(flags, ctypes.c_uint8), _ptr(uids, ctypes.c_uint64),
        _ptr(tids, ctypes.c_uint8), _ptr(vlens, ctypes.c_int64),
        _ptr(vb, ctypes.c_uint8),
        _ptr(out, ctypes.c_uint8), _ptr(offs, ctypes.c_int64),
    )
    assert wrote == total, (wrote, total)
    ob = out.tobytes()
    ol = offs.tolist()  # python ints: numpy-scalar slicing is slow
    return [ob[ol[i]:ol[i + 1]] for i in range(n_keys)]


def tok_terms_ascii(values, prefix: int):
    """Bulk ASCII term tokenization (tok/tok.py TermTokenizer fast
    path): `values` is a list of pure-ASCII byte strings; returns a
    list of per-value sorted-unique token lists (each token prefixed
    with the tokenizer identifier byte), byte-identical to the Python
    tokenizer over ASCII input — or None when the native lib is
    unavailable."""
    if _LIB is None:
        return None
    n = len(values)
    blob = b"".join(values)
    offs = np.zeros((n + 1,), np.int64)
    np.cumsum(
        np.fromiter((len(v) for v in values), np.int64, n), out=offs[1:]
    )
    total = len(blob)
    max_toks = total // 2 + n + 1
    bb = np.frombuffer(blob, np.uint8) if blob else np.zeros(1, np.uint8)
    out = np.empty((total + max_toks,), np.uint8)
    tok_offs = np.empty((max_toks + 1,), np.int64)
    tok_counts = np.empty((n,), np.int64)
    ntok = _LIB.tok_terms_ascii(
        _ptr(bb, ctypes.c_uint8), _ptr(offs, ctypes.c_int64), n,
        prefix,
        _ptr(out, ctypes.c_uint8), _ptr(tok_offs, ctypes.c_int64),
        _ptr(tok_counts, ctypes.c_int64),
    )
    ob = out.tobytes()
    to = tok_offs[: ntok + 1].tolist()
    tc = tok_counts.tolist()
    result = []
    t = 0
    for i in range(n):
        cnt = tc[i]
        result.append([ob[to[j]:to[j + 1]] for j in range(t, t + cnt)])
        t += cnt
    assert t == ntok
    return result


def _ba_addr(buf) -> int:
    """Raw address of a writable buffer (bytearray) for the void*
    batch-apply params; empty buffers pass 0 (never dereferenced —
    every span over them is zero-length)."""
    if not len(buf):
        return 0
    return ctypes.addressof(ctypes.c_char.from_buffer(buf))


def batch_apply(
    m_offs, shapes, entities, pred_ids, objects, vtypes, voffs,
    vblob, pp_blob: bytes, pp_offs, pflags: bytes, pidents: bytes,
):
    """Columnar batch apply (posting/colwrite.py): ONE GIL-released
    call turns a whole group-commit batch's collected edge columns
    into ready-to-put (key, delta-record) pairs — key construction,
    exact/int/bool/term tokenization and record encoding fused.

    Columns arrive as the cheap typed buffers colwrite collects into —
    array.array('q'/'Q'/'i') for the int columns and CSR offsets,
    bytearray/bytes for the byte columns — and are passed by raw
    address (no numpy conversion, no per-arg ctypes casts: this entry
    runs once per commit batch and its Python-side fixed cost is what
    the columnar path exists to delete). Returns (n_pairs, keys_blob,
    key_offs, recs_blob, rec_offs, member, pred, kinds, counts) with
    CSR blobs as bytes and the per-pair annotations as indexable
    typed-array sequences, or None when the native lib is unavailable
    (caller materializes to the Python path)."""
    from array import array

    if _LIB is None:
        return None
    n_members = len(m_offs) - 1
    n_preds = len(pp_offs) - 1
    if n_members <= 0 or m_offs[-1] == 0:
        empty = array("q", (0,))
        return (0, b"", empty, b"", empty, b"", b"", b"", b"")
    return batch_apply_addrs(
        m_offs.buffer_info()[0], n_members,
        _ba_addr(shapes), entities.buffer_info()[0],
        pred_ids.buffer_info()[0], objects.buffer_info()[0],
        _ba_addr(vtypes), voffs.buffer_info()[0],
        _ba_addr(vblob) if isinstance(vblob, bytearray) else vblob,
        pp_blob, pp_offs.buffer_info()[0], pflags, pidents, n_preds,
    )


def batch_apply_addrs(
    a_m_offs: int, n_members: int, a_shapes: int, a_entities: int,
    a_pred_ids: int, a_objects: int, a_vtypes: int, a_voffs: int,
    a_vblob, pp_blob: bytes, a_pp_offs: int, pflags: bytes,
    pidents: bytes, n_preds: int,
):
    """Address-level core of `batch_apply`: every big input column
    arrives as a raw address, so the apply-shard worker processes
    (worker/applyshard.py) can point the kernel straight into their
    shared-memory ring — zero input copies on the worker side. Same
    return tuple as `batch_apply`; None when the lib is unavailable.
    Callers own the empty-batch short-circuit (a zero-row call here
    would dereference nothing but still pays the caps exchange)."""
    from array import array

    if _LIB is None:
        return None
    caps = array("q", (0, 0, 0))
    _LIB.batch_apply_caps(
        a_m_offs, n_members, a_shapes, a_pred_ids, a_voffs, a_pp_offs,
        pflags, n_preds, caps.buffer_info()[0],
    )
    max_pairs, key_cap, rec_cap = caps
    out_keys = bytearray(key_cap)
    out_key_offs = array("q", bytes(8 * (max_pairs + 1)))
    out_recs = bytearray(rec_cap)
    out_rec_offs = array("q", bytes(8 * (max_pairs + 1)))
    out_member = array("i", bytes(4 * max_pairs))
    out_pred = array("i", bytes(4 * max_pairs))
    out_kinds = bytearray(max_pairs)
    out_counts = array("i", bytes(4 * max_pairs))
    n_pairs = _LIB.batch_apply(
        a_m_offs, n_members, a_shapes, a_entities, a_pred_ids,
        a_objects, a_vtypes, a_voffs, a_vblob,
        pp_blob, a_pp_offs, pflags, pidents, n_preds,
        _ba_addr(out_keys), out_key_offs.buffer_info()[0],
        _ba_addr(out_recs), out_rec_offs.buffer_info()[0],
        out_member.buffer_info()[0], out_pred.buffer_info()[0],
        _ba_addr(out_kinds), out_counts.buffer_info()[0],
        max_pairs,
    )
    assert n_pairs >= 0, "batch_apply output caps overflowed"
    n_pairs = int(n_pairs)
    return (
        n_pairs,
        bytes(memoryview(out_keys)[: out_key_offs[n_pairs]]),
        out_key_offs,
        bytes(memoryview(out_recs)[: out_rec_offs[n_pairs]]),
        out_rec_offs,
        out_member,
        out_pred,
        out_kinds,
        out_counts,
    )


def vec_qi8_topk(
    codes, scales, offsets, csums, sqnorms, valid,
    qcodes, qscales, qoffsets, qcsums, qstats, metric: int, k: int,
):
    """Batched quantized full-corpus top-k (models/vector.py brute
    tier): nq queries scored against every valid row in one corpus
    pass, per-query fused top-k heaps, ascending (dist, row) with
    deterministic low-index tie-break. Returns (idx (nq, k) int64 with
    -1 padding, dist (nq, k) float32, n_valid) or None when the native
    lib is unavailable (caller takes the numpy fallback)."""
    if _LIB is None:
        return None
    codes = np.ascontiguousarray(codes, np.int8)
    qcodes = np.ascontiguousarray(qcodes, np.int8)
    nq = qcodes.shape[0]
    n, d = codes.shape
    # bind conversions to locals so temporaries outlive the call
    scales = np.ascontiguousarray(scales, np.float32)
    offsets = np.ascontiguousarray(offsets, np.float32)
    csums = np.ascontiguousarray(csums, np.int32)
    sqnorms = np.ascontiguousarray(sqnorms, np.float32)
    valid = np.ascontiguousarray(valid, np.uint8)
    qscales = np.ascontiguousarray(qscales, np.float32)
    qoffsets = np.ascontiguousarray(qoffsets, np.float32)
    qcsums = np.ascontiguousarray(qcsums, np.int32)
    qstats = np.ascontiguousarray(qstats, np.float32)
    out_idx = np.empty((nq, k), np.int64)
    out_dist = np.empty((nq, k), np.float32)
    nvalid = _LIB.vec_qi8_topk(
        _ptr(codes, ctypes.c_int8), n, d,
        _ptr(scales, ctypes.c_float), _ptr(offsets, ctypes.c_float),
        _ptr(csums, ctypes.c_int32), _ptr(sqnorms, ctypes.c_float),
        _ptr(valid, ctypes.c_uint8),
        _ptr(qcodes, ctypes.c_int8),
        _ptr(qscales, ctypes.c_float), _ptr(qoffsets, ctypes.c_float),
        _ptr(qcsums, ctypes.c_int32), _ptr(qstats, ctypes.c_float),
        nq, metric, k,
        _ptr(out_idx, ctypes.c_int64), _ptr(out_dist, ctypes.c_float),
    )
    return out_idx, out_dist, int(nvalid)


def vec_qi8_topk_idx(
    codes, scales, offsets, csums, sqnorms, valid, rows,
    qc, qscale, qoffset, qcsum, qstat, metric: int, k: int,
):
    """Quantized candidate-list top-k (the IVF probe): one query
    against the probed cells' concatenated row ids. Returns
    (idx (k,) int64 with -1 padding, dist (k,) float32, written) or
    None when the native lib is unavailable."""
    if _LIB is None:
        return None
    codes = np.ascontiguousarray(codes, np.int8)
    d = codes.shape[1]
    scales = np.ascontiguousarray(scales, np.float32)
    offsets = np.ascontiguousarray(offsets, np.float32)
    csums = np.ascontiguousarray(csums, np.int32)
    sqnorms = np.ascontiguousarray(sqnorms, np.float32)
    valid = np.ascontiguousarray(valid, np.uint8)
    rows = np.ascontiguousarray(rows, np.int32)
    qc = np.ascontiguousarray(qc, np.int8)
    out_idx = np.empty((k,), np.int64)
    out_dist = np.empty((k,), np.float32)
    wrote = _LIB.vec_qi8_topk_idx(
        _ptr(codes, ctypes.c_int8), d,
        _ptr(scales, ctypes.c_float), _ptr(offsets, ctypes.c_float),
        _ptr(csums, ctypes.c_int32), _ptr(sqnorms, ctypes.c_float),
        _ptr(valid, ctypes.c_uint8),
        _ptr(rows, ctypes.c_int32), rows.size,
        _ptr(qc, ctypes.c_int8),
        ctypes.c_float(float(qscale)), ctypes.c_float(float(qoffset)),
        int(qcsum), ctypes.c_float(float(qstat)),
        metric, k,
        _ptr(out_idx, ctypes.c_int64), _ptr(out_dist, ctypes.c_float),
    )
    return out_idx, out_dist, int(wrote)


def vec_qi8_topk_lists(
    codes, scales, offsets, csums, sqnorms, valid,
    rows, begs, ends,
    qcodes, qscales, qoffsets, qcsums, qstats,
    metric: int, k: int, nthreads: int = 1,
):
    """Batched quantized candidate-list top-k (the IVF probe batch and
    the top-2 cell-assignment fan): query q scores rows[begs[q]:ends[q]]
    of a shared candidate array — slices may alias. Scoring and
    tie-break identical to vec_qi8_topk_idx (a batch row is byte-equal
    to the solo call); threaded over queries. Returns (idx (nq, k)
    int64 with -1 padding, dist (nq, k) float32, candidates scanned)
    or None when the native lib is unavailable."""
    if _LIB is None:
        return None
    codes = np.ascontiguousarray(codes, np.int8)
    qcodes = np.ascontiguousarray(qcodes, np.int8)
    nq = qcodes.shape[0]
    d = codes.shape[1]
    scales = np.ascontiguousarray(scales, np.float32)
    offsets = np.ascontiguousarray(offsets, np.float32)
    csums = np.ascontiguousarray(csums, np.int32)
    sqnorms = np.ascontiguousarray(sqnorms, np.float32)
    valid = np.ascontiguousarray(valid, np.uint8)
    rows = np.ascontiguousarray(rows, np.int32)
    begs = np.ascontiguousarray(begs, np.int64)
    ends = np.ascontiguousarray(ends, np.int64)
    qscales = np.ascontiguousarray(qscales, np.float32)
    qoffsets = np.ascontiguousarray(qoffsets, np.float32)
    qcsums = np.ascontiguousarray(qcsums, np.int32)
    qstats = np.ascontiguousarray(qstats, np.float32)
    out_idx = np.empty((nq, k), np.int64)
    out_dist = np.empty((nq, k), np.float32)
    scanned = _LIB.vec_qi8_topk_lists(
        _ptr(codes, ctypes.c_int8), d,
        _ptr(scales, ctypes.c_float), _ptr(offsets, ctypes.c_float),
        _ptr(csums, ctypes.c_int32), _ptr(sqnorms, ctypes.c_float),
        _ptr(valid, ctypes.c_uint8),
        _ptr(rows, ctypes.c_int32),
        _ptr(begs, ctypes.c_int64), _ptr(ends, ctypes.c_int64),
        _ptr(qcodes, ctypes.c_int8),
        _ptr(qscales, ctypes.c_float), _ptr(qoffsets, ctypes.c_float),
        _ptr(qcsums, ctypes.c_int32), _ptr(qstats, ctypes.c_float),
        nq, metric, k, max(1, int(nthreads)),
        _ptr(out_idx, ctypes.c_int64), _ptr(out_dist, ctypes.c_float),
    )
    return out_idx, out_dist, int(scanned)


def vec_qi8_quantize(V, nthreads: int = 1):
    """Threaded int8 row quantizer (models/vector.py sidecar store):
    returns (codes i8, scales f32, offsets f32, csums i32, sqnorms f32)
    or None when the native lib is unavailable. Codes and sidecars are
    bit-identical to the numpy mirror; sqnorms agree to float32
    accumulation order."""
    if _LIB is None:
        return None
    V = np.ascontiguousarray(V, np.float32)
    n, d = V.shape
    codes = np.empty((n, d), np.int8)
    scales = np.empty((n,), np.float32)
    offsets = np.empty((n,), np.float32)
    csums = np.empty((n,), np.int32)
    sqnorms = np.empty((n,), np.float32)
    _LIB.vec_qi8_quantize(
        _ptr(V, ctypes.c_float), n, d, max(1, int(nthreads)),
        _ptr(codes, ctypes.c_int8), _ptr(scales, ctypes.c_float),
        _ptr(offsets, ctypes.c_float), _ptr(csums, ctypes.c_int32),
        _ptr(sqnorms, ctypes.c_float),
    )
    return codes, scales, offsets, csums, sqnorms


def _setop(name: str, a: np.ndarray, b: np.ndarray, out_size: int) -> np.ndarray:
    a = np.ascontiguousarray(a, np.uint64)
    b = np.ascontiguousarray(b, np.uint64)
    out = np.empty((out_size,), np.uint64)
    n = getattr(_LIB, name)(
        _ptr(a, ctypes.c_uint64),
        a.size,
        _ptr(b, ctypes.c_uint64),
        b.size,
        _ptr(out, ctypes.c_uint64),
    )
    return out[:n]


def intersect(a: np.ndarray, b: np.ndarray) -> np.ndarray:
    if _LIB is None:
        return np.intersect1d(a, b, assume_unique=True)
    return _setop("intersect_u64", a, b, min(a.size, b.size))


def union(a: np.ndarray, b: np.ndarray) -> np.ndarray:
    if _LIB is None:
        return np.union1d(a, b)
    return _setop("union_u64", a, b, a.size + b.size)


def difference(a: np.ndarray, b: np.ndarray) -> np.ndarray:
    if _LIB is None:
        return np.setdiff1d(a, b, assume_unique=True)
    return _setop("difference_u64", a, b, a.size)


def merge_sorted(lists) -> np.ndarray:
    """K-way sorted union (ref algo/uidlist.go:448 MergeSorted)."""
    lists = [np.ascontiguousarray(x, np.uint64) for x in lists if len(x)]
    if not lists:
        return np.zeros((0,), np.uint64)
    if _LIB is None:
        return np.unique(np.concatenate(lists))
    flat = np.concatenate(lists)
    lens = np.asarray([x.size for x in lists], np.int64)
    total = int(flat.size)
    out = np.empty((total,), np.uint64)
    scratch = np.empty((total,), np.uint64)
    n = _LIB.merge_sorted_u64(
        _ptr(flat, ctypes.c_uint64),
        lens.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
        lens.size,
        _ptr(out, ctypes.c_uint64),
        _ptr(scratch, ctypes.c_uint64),
    )
    return out[:n]


def merge_sorted_flat(flat: np.ndarray, lens: np.ndarray) -> np.ndarray:
    """K-way sorted union over an ALREADY-FLAT ragged buffer (row i is
    flat[sum(lens[:i]) : sum(lens[:i+1])], each row sorted) — the
    level-batched read form, skipping the per-row concatenate that
    merge_sorted() does. Falls back to numpy unique without the lib."""
    flat = np.ascontiguousarray(flat, np.uint64)
    lens = np.ascontiguousarray(lens, np.int64)
    if flat.size == 0:
        return np.zeros((0,), np.uint64)
    if _LIB is None:
        return np.unique(flat)
    # empty rows don't move flat but each would still cost two O(acc)
    # copies in merge_sorted_u64's fold — sparse wide levels are mostly
    # empty rows, so drop them first
    lens = lens[lens != 0]
    total = int(flat.size)
    out = np.empty((total,), np.uint64)
    scratch = np.empty((total,), np.uint64)
    n = _LIB.merge_sorted_u64(
        _ptr(flat, ctypes.c_uint64),
        lens.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
        lens.size,
        _ptr(out, ctypes.c_uint64),
        _ptr(scratch, ctypes.c_uint64),
    )
    return out[:n]


def sst_available() -> bool:
    return _LIB is not None


def sst_seek(buf: np.ndarray, end: int, off: int, key: bytes) -> int:
    kb = np.frombuffer(key, dtype=np.uint8)
    return int(
        _LIB.sst_seek(
            _ptr(buf, ctypes.c_uint8), end, off,
            _ptr(kb, ctypes.c_uint8), len(key),
        )
    )


def buf_ptr(arr: np.ndarray):
    """Stable uint8 pointer for a long-lived buffer (an SSTable mmap) —
    callers cache it so per-probe calls skip the numpy/ctypes marshaling
    that dominated the point-get profile."""
    return _ptr(arr, ctypes.c_uint8)


class _VerScratch(__import__("threading").local):
    """Reusable output arrays + cached pointers for sst_versions."""

    def __init__(self):
        self.cap = 0

    def ensure(self, cap: int):
        if cap <= self.cap:
            return
        self.cap = cap
        self.tss = np.empty(cap, np.uint64)
        self.seqs = np.empty(cap, np.uint64)
        self.voffs = np.empty(cap, np.int64)
        self.vlens = np.empty(cap, np.int64)
        self.ptrs = (
            _ptr(self.tss, ctypes.c_uint64),
            _ptr(self.seqs, ctypes.c_uint64),
            _ptr(self.voffs, ctypes.c_int64),
            _ptr(self.vlens, ctypes.c_int64),
        )


_VSCRATCH = _VerScratch()
_U8P = ctypes.POINTER(ctypes.c_uint8)


def sst_versions(
    buf: np.ndarray,
    end: int,
    off: int,
    key: bytes,
    cap: int = 64,
    bptr=None,
):
    """(tss, seqs, val_offs, val_lens) arrays for entries == key.
    Returned arrays are views into thread-local scratch — consume before
    the next call on this thread."""
    if bptr is None:
        bptr = _ptr(buf, ctypes.c_uint8)
    kp = ctypes.cast(ctypes.c_char_p(key), _U8P)
    s = _VSCRATCH
    while True:
        s.ensure(cap)
        # _HELD: one key's few records, under the store's lock (LsmKV.
        # versions / get): giving the interpreter's lock away here would
        # park the next reader on the store's
        n = int(
            _HELD.sst_versions(
                bptr, end, off, kp, len(key), s.cap, *s.ptrs
            )
        )
        if n < s.cap:
            return s.tss[:n], s.seqs[:n], s.voffs[:n], s.vlens[:n]
        cap = s.cap * 4


class _ProbeScratch(__import__("threading").local):
    """A thread's reusable output buffer for sst_versions_multi."""

    def __init__(self):
        self.words = 0

    def ensure(self, words: int):
        if words > self.words:
            self.words = words
            self.buf = np.empty(words, np.uint64)
            self.addr = self.buf.ctypes.data


_PSCRATCH = _ProbeScratch()
# a probe of at most this many keys keeps the interpreter's lock (_HELD):
# under a microsecond a key, so at most about as long as the hand-over it
# saves; a wider level (a cold multi-hop expansion) releases it as before
_HELD_PROBE_KEYS = 64


def sst_probe_table(buf, data_end, bloom_bits, index, max_key):
    """What sst_versions_multi needs of one table, as ten words built
    ONCE (`_SSTable` keeps the result for its lifetime): returns (address
    of the words, everything the addresses point into)."""
    idx_keys = np.frombuffer(b"".join(k for k, _ in index) or b"\0", np.uint8)
    koffs = np.zeros(len(index) + 1, np.int64)
    np.cumsum([len(k) for k, _ in index], out=koffs[1:])
    foffs = np.array([off for _, off in index], np.int64)
    bits = None if not bloom_bits else np.frombuffer(bloom_bits, np.uint8)
    mk = np.frombuffer(max_key or b"\0", np.uint8)
    words = np.array(
        [
            buf.ctypes.data, data_end,
            0 if bits is None else bits.ctypes.data,
            0 if bits is None else 8 * bits.size,
            idx_keys.ctypes.data, koffs.ctypes.data, foffs.ctypes.data,
            len(index), mk.ctypes.data, len(max_key),
        ],
        np.int64,
    )
    return words.ctypes.data, (words, idx_keys, koffs, foffs, bits, mk)


def sst_versions_multi(table_addr: int, keys: list) -> list:
    """Batched version probe over SORTED distinct keys in one native
    call: range test, bloom test, index seek and scan all happen there.
    Returns one flat list: len(keys) counts, then (ts, seq, value offset,
    value length) per version in key order."""
    nk = len(keys)
    ends = array("q", accumulate(map(len, keys)))
    blob = b"".join(keys)
    s = _PSCRATCH
    fn = (_HELD if nk <= _HELD_PROBE_KEYS else _LIB).sst_versions_multi
    cap = max(64, 2 * nk)
    while True:
        s.ensure(nk + 4 * cap)
        cap = (s.words - nk) // 4
        got = fn(table_addr, nk, blob, ends.buffer_info()[0], cap, s.addr)
        if got >= 0:
            return s.buf[: nk + 4 * got].tolist()
        cap = max(cap * 2, -got + 64)


def sst_scan(buf: np.ndarray, end: int, off: int, prefix: bytes, batch: int = 1024):
    """Yield (key_off, key_len, ts, seq, val_off, val_len) per entry while
    keys match `prefix`, scanning from `off`."""
    pb = np.frombuffer(prefix, dtype=np.uint8) if prefix else np.zeros(1, np.uint8)
    pos = off
    nxt = np.zeros(1, np.int64)
    while pos < end:
        koffs = np.empty(batch, np.int64)
        klens = np.empty(batch, np.int64)
        tss = np.empty(batch, np.uint64)
        seqs = np.empty(batch, np.uint64)
        voffs = np.empty(batch, np.int64)
        vlens = np.empty(batch, np.int64)
        n = int(
            _LIB.sst_scan(
                _ptr(buf, ctypes.c_uint8), end, pos,
                _ptr(pb, ctypes.c_uint8), len(prefix), batch,
                _ptr(koffs, ctypes.c_int64), _ptr(klens, ctypes.c_int64),
                _ptr(tss, ctypes.c_uint64), _ptr(seqs, ctypes.c_uint64),
                _ptr(voffs, ctypes.c_int64), _ptr(vlens, ctypes.c_int64),
                _ptr(nxt, ctypes.c_int64),
            )
        )
        for i in range(n):
            yield (
                int(koffs[i]), int(klens[i]), int(tss[i]), int(seqs[i]),
                int(voffs[i]), int(vlens[i]),
            )
        if n < batch:
            break
        pos = int(nxt[0])
