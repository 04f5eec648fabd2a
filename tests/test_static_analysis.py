"""Tier-1 gate + self-tests for the project-invariant analyzer suite.

Two layers:

  1. The GATE: `analysis.run()` over the real package must come back
     clean — zero unallowlisted violations AND zero stale allowlist
     entries (every deliberate exception keeps matching something).

  2. SELF-TESTS: each checker is run against fixture sources seeding
     exactly the defect class it exists to catch (bad lock nesting,
     raw env read, truncated restype, naked retry sleep, np-in-jit),
     plus a clean fixture asserting no false positives. A checker that
     silently stops detecting its class fails here, not in production.

Also covers the x/config registry itself (types, defaults, precedence)
and the generated CONFIG.md sync.
"""

import ctypes
import os
import textwrap

import pytest

from dgraph_tpu import analysis
from dgraph_tpu.analysis import check_ctypes_abi
from dgraph_tpu.analysis.allowlist import ALLOWLIST
from dgraph_tpu.analysis.core import Allow
from dgraph_tpu.x import config

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------------------
# the gate
# ---------------------------------------------------------------------------


def test_package_is_clean():
    rep = analysis.run()
    assert not rep.violations, "\n" + "\n".join(
        v.render() for v in rep.violations
    )
    assert not rep.unused_allows, (
        "stale allowlist entries (remove them): "
        + ", ".join(f"({a.checker}, {a.path})" for a in rep.unused_allows)
    )


def test_every_allowlist_entry_has_a_reason():
    for a in ALLOWLIST:
        assert a.reason and len(a.reason.split()) >= 5, (
            f"allowlist entry ({a.checker}, {a.path}, {a.match!r}) needs "
            f"a real reason, not a token"
        )


def test_cli_lint_contract():
    from dgraph_tpu import cli

    class Args:
        json = False
        checker = None

    assert cli.cmd_lint(Args()) == 0
    Args.checker = ["no-such-checker"]
    assert cli.cmd_lint(Args()) == 2


# ---------------------------------------------------------------------------
# fixtures
# ---------------------------------------------------------------------------


def _run_fixture(tmp_path, rel, source, checkers):
    p = tmp_path / rel
    p.parent.mkdir(parents=True, exist_ok=True)
    p.write_text(textwrap.dedent(source))
    return analysis.run(
        root=str(tmp_path), checkers=checkers, allows=[]
    )


CLEAN_FIXTURE = """
    import threading
    import time

    from dgraph_tpu.x import config

    _LOCK = threading.Lock()


    def good(counter):
        workers = config.get("EXEC_WORKERS")
        with _LOCK:
            counter += workers
        time.sleep(0.01)  # not in a loop, no lock held
        return counter
"""


def test_clean_fixture_no_false_positives(tmp_path):
    rep = _run_fixture(
        tmp_path, "conn/clean.py", CLEAN_FIXTURE, list(analysis.CHECKERS)
    )
    assert rep.violations == []


def test_config_checker_catches_raw_env_read(tmp_path):
    rep = _run_fixture(
        tmp_path,
        "worker/bad_env.py",
        """
        import os
        import os as _os
        from os import environ, getenv

        A = os.environ.get("DGRAPH_TPU_EXEC_WORKERS", "0")
        B = os.getenv("DGRAPH_TPU_LEVEL_BATCH")
        C = _os.environ["DGRAPH_TPU_STORAGE"]
        os.environ["DGRAPH_TPU_STORAGE"] = "lsm"
        D = environ.get("SOME_OTHER_VAR")
        E = dict(os.environ)
        F = environ["DGRAPH_TPU_SHARD_MIN_B"] # from-import bypass
        G = getenv("DGRAPH_TPU_SHARD_MIN_B")  # bare getenv bypass
        """,
        ["config-registry"],
    )
    codes = [v.code for v in rep.violations]
    # A, B, C, the write, F, G — from-imported access must still
    # classify as the DGRAPH hard-violation class, not generic
    assert codes.count("raw-dgraph-env") == 6
    assert codes.count("raw-env-read") == 2  # D + dict(os.environ)


def test_config_checker_exempts_registry_itself(tmp_path):
    rep = _run_fixture(
        tmp_path,
        "x/config.py",
        """
        import os

        V = os.environ.get("DGRAPH_TPU_ANYTHING")
        """,
        ["config-registry"],
    )
    assert rep.violations == []


LOCK_FIXTURE = """
    import threading
    import threading as th
    import time
    import subprocess

    from dgraph_tpu.native import packs_decode_many

    A = th.Lock()  # aliased module import must still register
    B = threading.Lock()


    class Layer:
        def __init__(self):
            self._lock = threading.Lock()
            self._cv = threading.Condition(self._lock)

        def bad_sleep(self):
            with self._lock:
                time.sleep(0.5)

        def bad_native(self, packs):
            with self._lock:
                return packs_decode_many(packs)

        def good_wait(self):
            with self._cv:
                self._cv.wait(1.0)  # releases its own lock: fine

        def bad_wait(self):
            with A:
                with self._cv:
                    self._cv.wait(1.0)  # A stays held for the wait

        def bad_subprocess(self):
            with B:
                subprocess.run(["true"])


    def order_ab():
        with A:
            with B:
                pass


    def order_ba():
        with B:
            with A:
                pass
"""


def test_lock_checker_catches_seeded_violations(tmp_path):
    rep = _run_fixture(
        tmp_path, "posting/bad_locks.py", LOCK_FIXTURE, ["lock-discipline"]
    )
    codes = sorted(v.code for v in rep.violations)
    msgs = "\n".join(v.render() for v in rep.violations)
    assert codes.count("blocking-under-lock") == 2, msgs  # sleep + subprocess
    assert codes.count("native-call-under-lock") == 1, msgs
    assert codes.count("cv-wait-under-other-lock") == 1, msgs
    assert codes.count("lock-order-cycle") == 1, msgs
    # the good condition wait produced nothing
    assert "good_wait" not in msgs


def test_deadline_checker_catches_naked_sleep_and_settimeout(tmp_path):
    src = """
        import time
        from time import sleep


        def naked_retry(sock):
            sock.settimeout(5)
            while True:
                try:
                    return sock.recv(1)
                except OSError:
                    time.sleep(0.05)


        def also_naked():
            for _ in range(3):
                sleep(0.1)


        def fine_outside_loop():
            time.sleep(0.01)
    """
    rep = _run_fixture(
        tmp_path / "in_scope", "conn/bad_retry.py", src,
        ["deadline-hygiene"],
    )
    codes = sorted(v.code for v in rep.violations)
    assert codes.count("naked-sleep-in-loop") == 2
    assert codes.count("raw-settimeout-constant") == 1
    # same file OUTSIDE the cluster dirs: out of scope
    rep2 = _run_fixture(
        tmp_path / "out_of_scope", "query/bad_retry.py", src,
        ["deadline-hygiene"],
    )
    assert rep2.violations == []


def test_jax_checker_catches_np_in_jit(tmp_path):
    src = """
        import functools

        import jax
        import jax.numpy as jnp
        import numpy as np


        @jax.jit
        def bad(a):
            return np.sum(a)  # host numpy inside jit


        @functools.partial(jax.jit, static_argnames=("k",))
        def bad2(a, k):
            b = jnp.take(a, 0)
            return b.item()  # forced device->host sync


        def helper(a):
            return np.sum(a)  # NOT jitted: numpy is fine


        def wrapped(a):
            return np.asarray(a)


        wrapped = jax.jit(wrapped)
    """
    rep = _run_fixture(tmp_path, "ops/bad_jit.py", src, ["jax-hygiene"])
    codes = sorted(v.code for v in rep.violations)
    msgs = "\n".join(v.render() for v in rep.violations)
    assert codes.count("np-in-jit") == 1, msgs
    assert codes.count("host-sync-in-jit") == 2, msgs  # .item + np.asarray
    assert "helper" not in msgs


# ---------------------------------------------------------------------------
# ctypes ABI checker self-tests (synthetic C++ + synthetic DECLS)
# ---------------------------------------------------------------------------

_SYN_CPP = """
using i64 = int64_t;
using u64 = uint64_t;

extern "C" {

static i64 helper(i64 x) { return x; }

i64 truncated(const u64* a, i64 n) { return n; }

void takes_three(i64 a, i64 b, int c) {}

u64* returns_ptr(void* h) { return 0; }

int undeclared_fn(int x) { return x; }

}  // extern "C"
"""


def _syn_decls(**overrides):
    i64 = ctypes.c_int64
    u64p = ctypes.POINTER(ctypes.c_uint64)
    decls = {
        "truncated": (i64, [u64p, i64]),
        "takes_three": (None, [i64, i64, ctypes.c_int]),
        "returns_ptr": (u64p, [ctypes.c_void_p]),
        "undeclared_fn": (ctypes.c_int, [ctypes.c_int]),
    }
    decls.update(overrides)
    return decls


def _abi(decls):
    return check_ctypes_abi.check_abi(
        {"native/syn.cpp": _SYN_CPP}, decls, "native/__init__.py"
    )


def test_abi_clean_baseline():
    assert _abi(_syn_decls()) == []


def test_abi_catches_truncated_restype():
    # the headline defect class: int64_t return bound with default c_int
    decls = _syn_decls(
        truncated=(None, [ctypes.POINTER(ctypes.c_uint64), ctypes.c_int64])
    )
    out = _abi(decls)
    assert [v.code for v in out] == ["restype-mismatch"]
    assert "truncated" in out[0].message


def test_abi_catches_arity_and_width():
    i64 = ctypes.c_int64
    out = _abi(_syn_decls(takes_three=(None, [i64, i64])))
    assert [v.code for v in out] == ["arity-mismatch"]
    # int32 param declared as int64: width mismatch
    out = _abi(_syn_decls(takes_three=(None, [i64, i64, i64])))
    assert [v.code for v in out] == ["arg-type-mismatch"]
    # unsigned vs signed pointee
    out = _abi(_syn_decls(
        truncated=(i64, [ctypes.POINTER(ctypes.c_int64), i64])
    ))
    assert [v.code for v in out] == ["arg-type-mismatch"]


def test_abi_catches_undeclared_and_stale():
    decls = _syn_decls()
    del decls["undeclared_fn"]
    decls["ghost"] = (ctypes.c_int64, [])
    codes = sorted(v.code for v in _abi(decls))
    assert codes == ["stale-decl", "undeclared-export"]
    # static helper must NOT demand a declaration
    assert all("helper" not in v.message for v in _abi(decls))


_SYN_BITMAP_CPP = """
extern "C" {

int64_t bitmap_and_block(const uint64_t* a_words, const uint64_t* b_words,
                         int64_t nwords, int64_t bm_bits, uint64_t* out) {
    return 0;
}

}  // extern "C"
"""


def test_abi_catches_bitmap_kernel_width_mismatch():
    """Seeded violation for the adaptive-engine kernel class: a bitmap
    kernel whose word-count parameter is declared c_int32 against the
    C++ int64_t must be flagged (on a >2^31-bit operand the truncated
    width silently corrupts the word loop's bounds)."""
    i64 = ctypes.c_int64
    u64p = ctypes.POINTER(ctypes.c_uint64)
    good = {"bitmap_and_block": (i64, [u64p, u64p, i64, i64, u64p])}
    assert (
        check_ctypes_abi.check_abi(
            {"native/syn_bitmap.cpp": _SYN_BITMAP_CPP},
            good,
            "native/__init__.py",
        )
        == []
    )
    bad = {
        "bitmap_and_block": (
            i64,
            [u64p, u64p, ctypes.c_int32, i64, u64p],
        )
    }
    out = check_ctypes_abi.check_abi(
        {"native/syn_bitmap.cpp": _SYN_BITMAP_CPP},
        bad,
        "native/__init__.py",
    )
    assert [v.code for v in out] == ["arg-type-mismatch"]
    assert "bitmap_and_block" in out[0].message and "arg 2" in out[0].message


_SYN_ENCODER_CPP = """
extern "C" {

int64_t enc_uid_objs(const uint64_t* uids, int64_t n, const uint8_t* pre,
                     int64_t pre_len, const uint8_t* post, int64_t post_len,
                     uint8_t* out) {
    return 0;
}

}  // extern "C"
"""


def test_abi_catches_encoder_width_mismatch():
    """Seeded violation for the arena-encoder kernel class: the uid
    pointer declared c_uint32* against the C++ uint64_t* must be
    flagged (the kernel would read half-width uids and emit garbage
    hex — silently, since the call still 'works')."""
    i64 = ctypes.c_int64
    u64p = ctypes.POINTER(ctypes.c_uint64)
    u8p = ctypes.POINTER(ctypes.c_uint8)
    good = {
        "enc_uid_objs": (i64, [u64p, i64, u8p, i64, u8p, i64, u8p])
    }
    assert (
        check_ctypes_abi.check_abi(
            {"native/syn_enc.cpp": _SYN_ENCODER_CPP},
            good,
            "native/__init__.py",
        )
        == []
    )
    bad = {
        "enc_uid_objs": (
            i64,
            [
                ctypes.POINTER(ctypes.c_uint32),
                i64, u8p, i64, u8p, i64, u8p,
            ],
        )
    }
    out = check_ctypes_abi.check_abi(
        {"native/syn_enc.cpp": _SYN_ENCODER_CPP},
        bad,
        "native/__init__.py",
    )
    assert [v.code for v in out] == ["arg-type-mismatch"]
    assert "enc_uid_objs" in out[0].message and "arg 0" in out[0].message
    # the length parameter truncated to c_int32 is the other silent
    # corruption class (a >2^31-row run would wrap negative)
    bad_n = {
        "enc_uid_objs": (
            i64,
            [u64p, ctypes.c_int32, u8p, i64, u8p, i64, u8p],
        )
    }
    out = check_ctypes_abi.check_abi(
        {"native/syn_enc.cpp": _SYN_ENCODER_CPP},
        bad_n,
        "native/__init__.py",
    )
    assert [v.code for v in out] == ["arg-type-mismatch"]


def test_abi_covers_encoder_exports():
    """The real arena-encoder entry points are parsed from codec.cpp and
    covered by DECLS (the ctypes-abi analyzer then enforces full
    width/signedness equality on every run)."""
    from dgraph_tpu import native

    with open(
        os.path.join(REPO, "dgraph_tpu", "native", "codec.cpp")
    ) as f:
        exports = check_ctypes_abi.parse_cpp_exports(f.read())
    for name in ("enc_uid_objs", "enc_int_objs"):
        assert name in exports, name
        assert name in native.DECLS, name
        assert len(exports[name][1]) == len(native.DECLS[name][1]), name


def test_abi_covers_mutation_kernel_exports():
    """The write-path mutation kernels are parsed from codec.cpp and
    covered by DECLS (regression guard: a missing restype on the
    int64-returning encoders is the memory-corruption class)."""
    from dgraph_tpu import native

    with open(
        os.path.join(REPO, "dgraph_tpu", "native", "codec.cpp")
    ) as f:
        exports = check_ctypes_abi.parse_cpp_exports(f.read())
    for name in (
        "enc_delta_records",
        "tok_terms_ascii",
        "batch_apply",
        "batch_apply_caps",
    ):
        assert name in exports, name
        assert name in native.DECLS, name
        assert len(exports[name][1]) == len(native.DECLS[name][1]), name


def test_abi_covers_adaptive_engine_exports():
    """The real adaptive-engine entry points are parsed from codec.cpp
    and covered by DECLS (regression guard for the new kernels)."""
    from dgraph_tpu import native

    with open(
        os.path.join(REPO, "dgraph_tpu", "native", "codec.cpp")
    ) as f:
        exports = check_ctypes_abi.parse_cpp_exports(f.read())
    for name in (
        "pack_build_bitmaps",
        "pack_pair_setop",
        "pack_stream_setop",
    ):
        assert name in exports, name
        assert name in native.DECLS, name
        # arity agrees (full width/signedness equality is the analyzer's
        # job — test_abi_real_package_is_clean keeps it at zero findings)
        assert len(exports[name][1]) == len(native.DECLS[name][1]), name


_SYN_VEC_CPP = """
extern "C" {

int64_t vec_qi8_topk_idx(const int8_t* codes, int64_t d,
                         const float* scales, const int32_t* rows,
                         int64_t nrows, float qscale, int metric,
                         int64_t k, int64_t* out_idx, float* out_dist) {
    return 0;
}

}  // extern "C"
"""

_SYN_VEC_LISTS_CPP = """
extern "C" {

int64_t vec_qi8_topk_lists(const int8_t* codes, int64_t d,
                           const int32_t* rows, const int64_t* begs,
                           const int64_t* ends, int64_t nq, int64_t k,
                           int64_t* out_idx, float* out_dist) {
    return 0;
}

}  // extern "C"
"""


def test_abi_catches_vector_kernel_width_mismatch():
    """Seeded violations for the quantized-vector kernel class: (a) the
    candidate row-id pointer declared c_int64* against the C++ int32_t*
    (the probe would stride double-width through the cell lists and
    score garbage rows — silently); (b) the code-matrix pointer widened
    to c_int16* (every dot product reads interleaved halves of two
    rows)."""
    i64 = ctypes.c_int64
    i8p = ctypes.POINTER(ctypes.c_int8)
    i32p = ctypes.POINTER(ctypes.c_int32)
    i64p = ctypes.POINTER(ctypes.c_int64)
    f32 = ctypes.c_float
    f32p = ctypes.POINTER(ctypes.c_float)
    good = {
        "vec_qi8_topk_idx": (
            i64, [i8p, i64, f32p, i32p, i64, f32, ctypes.c_int, i64,
                  i64p, f32p],
        )
    }
    assert (
        check_ctypes_abi.check_abi(
            {"native/syn_vec.cpp": _SYN_VEC_CPP},
            good,
            "native/__init__.py",
        )
        == []
    )
    bad_rows = {
        "vec_qi8_topk_idx": (
            i64, [i8p, i64, f32p, i64p, i64, f32, ctypes.c_int, i64,
                  i64p, f32p],
        )
    }
    out = check_ctypes_abi.check_abi(
        {"native/syn_vec.cpp": _SYN_VEC_CPP}, bad_rows,
        "native/__init__.py",
    )
    assert [v.code for v in out] == ["arg-type-mismatch"]
    assert "vec_qi8_topk_idx" in out[0].message and "arg 3" in out[0].message
    bad_codes = {
        "vec_qi8_topk_idx": (
            i64, [ctypes.POINTER(ctypes.c_int16), i64, f32p, i32p, i64,
                  f32, ctypes.c_int, i64, i64p, f32p],
        )
    }
    out = check_ctypes_abi.check_abi(
        {"native/syn_vec.cpp": _SYN_VEC_CPP}, bad_codes,
        "native/__init__.py",
    )
    assert [v.code for v in out] == ["arg-type-mismatch"]
    assert "arg 0" in out[0].message


def test_abi_catches_lists_kernel_csr_width_mismatch():
    """Seeded violation for the batched CSR scan kernel: the begs/ends
    slice-bound pointers declared c_int32* against the C++ int64_t* —
    every query after the first would read garbage slice bounds and
    scan (or skip) the wrong candidates, silently."""
    i64 = ctypes.c_int64
    i8p = ctypes.POINTER(ctypes.c_int8)
    i32p = ctypes.POINTER(ctypes.c_int32)
    i64p = ctypes.POINTER(ctypes.c_int64)
    f32p = ctypes.POINTER(ctypes.c_float)
    good = {
        "vec_qi8_topk_lists": (
            i64, [i8p, i64, i32p, i64p, i64p, i64, i64, i64p, f32p],
        )
    }
    assert (
        check_ctypes_abi.check_abi(
            {"native/syn_vec.cpp": _SYN_VEC_LISTS_CPP}, good,
            "native/__init__.py",
        )
        == []
    )
    bad_begs = {
        "vec_qi8_topk_lists": (
            i64, [i8p, i64, i32p, i32p, i64p, i64, i64, i64p, f32p],
        )
    }
    out = check_ctypes_abi.check_abi(
        {"native/syn_vec.cpp": _SYN_VEC_LISTS_CPP}, bad_begs,
        "native/__init__.py",
    )
    assert [v.code for v in out] == ["arg-type-mismatch"]
    assert (
        "vec_qi8_topk_lists" in out[0].message and "arg 3" in out[0].message
    )


def test_abi_covers_vector_exports():
    """The real quantized-vector entry points are parsed from codec.cpp
    and covered by DECLS (the analyzer then enforces full width and
    signedness equality on every run)."""
    from dgraph_tpu import native

    with open(
        os.path.join(REPO, "dgraph_tpu", "native", "codec.cpp")
    ) as f:
        exports = check_ctypes_abi.parse_cpp_exports(f.read())
    for name in (
        "vec_qi8_topk", "vec_qi8_topk_idx",
        "vec_qi8_topk_lists", "vec_qi8_quantize",
    ):
        assert name in exports, name
        assert name in native.DECLS, name
        assert len(exports[name][1]) == len(native.DECLS[name][1]), name


def test_abi_real_package_is_clean():
    # re-derive from the real sources; independent of the full gate so a
    # regression pinpoints here
    rep = analysis.run(checkers=["ctypes-abi"], allows=[])
    assert rep.violations == [], "\n".join(
        v.render() for v in rep.violations
    )
    # and the parser actually saw the real exports (not a silent no-op)
    from dgraph_tpu import native

    with open(
        os.path.join(REPO, "dgraph_tpu", "native", "codec.cpp")
    ) as f:
        exports = check_ctypes_abi.parse_cpp_exports(f.read())
    assert "merge_sorted_u64" in exports and "sst_scan" in exports
    assert set(exports) <= set(native.DECLS)


# ---------------------------------------------------------------------------
# x/config registry
# ---------------------------------------------------------------------------


def test_config_types_and_defaults(monkeypatch):
    monkeypatch.delenv("DGRAPH_TPU_EXEC_WORKERS", raising=False)
    assert config.get("EXEC_WORKERS") == 0
    monkeypatch.setenv("DGRAPH_TPU_EXEC_WORKERS", "4")
    assert config.get("EXEC_WORKERS") == 4
    # malformed values fall back instead of crashing server startup
    monkeypatch.setenv("DGRAPH_TPU_EXEC_WORKERS", "banana")
    assert config.get("EXEC_WORKERS") == 0
    monkeypatch.setenv("DGRAPH_TPU_LEVEL_BATCH", "0")
    assert config.get("LEVEL_BATCH") is False
    monkeypatch.setenv("DGRAPH_TPU_LEVEL_BATCH", "true")
    assert config.get("LEVEL_BATCH") is True
    monkeypatch.delenv("DGRAPH_TPU_DEVICE_MIN_TOTAL", raising=False)
    assert config.get("DEVICE_MIN_TOTAL") is None


def test_config_set_env_roundtrip(monkeypatch):
    monkeypatch.delenv("DGRAPH_TPU_STORAGE", raising=False)
    config.set_env("STORAGE", "lsm")
    assert os.environ["DGRAPH_TPU_STORAGE"] == "lsm"
    assert config.get("STORAGE") == "lsm"
    config.unset_env("STORAGE")
    assert config.get("STORAGE") == "mem"
    config.set_env("WIRE_COMPRESS", True)
    assert os.environ["DGRAPH_TPU_WIRE_COMPRESS"] == "1"
    config.unset_env("WIRE_COMPRESS")


def test_max_part_uids_single_default(monkeypatch):
    """Regression for the duplicated-default hazard: posting/pl.py and
    loaders/bulk2.py both size multi-part splits off MAX_PART_UIDS. The
    registry is now the one place the 1<<20 default lives; both call
    sites must agree with it."""
    monkeypatch.delenv("DGRAPH_TPU_MAX_PART_UIDS", raising=False)
    assert config.knob("MAX_PART_UIDS").default == 1 << 20
    assert config.get("MAX_PART_UIDS") == 1 << 20
    from dgraph_tpu.posting import pl

    # pl reads at import: its module constant equals the registry default
    assert pl.MAX_PART_UIDS == config.knob("MAX_PART_UIDS").default


def test_every_registered_knob_documented():
    for name, k in config.REGISTRY.items():
        assert k.doc and len(k.doc.split()) >= 5, name
        assert k.type in ("str", "int", "float", "bool"), name
        if k.default is not None and k.type == "bool":
            assert isinstance(k.default, bool), name


def test_config_md_in_sync():
    with open(os.path.join(REPO, "CONFIG.md")) as f:
        on_disk = f.read()
    assert on_disk == config.reference_table(), (
        "CONFIG.md is stale — regenerate with "
        "`python -m dgraph_tpu.cli config-ref -o CONFIG.md`"
    )


def test_no_unregistered_dgraph_env_vars_in_package():
    """Every DGRAPH_TPU_* string literal in the package must be a
    registered knob (catches a knob added ad hoc via config-checker
    bypass like indirection through a constant)."""
    import re

    known = {k.env for k in config.REGISTRY.values()}
    pkg = os.path.join(REPO, "dgraph_tpu")
    offenders = []
    for dirpath, _, files in os.walk(pkg):
        for fn in files:
            if not fn.endswith(".py"):
                continue
            path = os.path.join(dirpath, fn)
            with open(path, encoding="utf-8") as f:
                for i, line in enumerate(f, 1):
                    for m in re.finditer(r"DGRAPH_TPU_[A-Z0-9_]+", line):
                        if m.group(0) not in known and m.group(0) != \
                                config.PREFIX.rstrip("_"):
                            offenders.append(
                                f"{path}:{i}: {m.group(0)}"
                            )
    assert not offenders, "\n".join(offenders)


# ---------------------------------------------------------------------------
# metrics-registry checker (PR 5): every METRICS name is declared
# ---------------------------------------------------------------------------


def test_metrics_registry_checker_flags_undeclared(tmp_path):
    rep = _run_fixture(
        tmp_path,
        "mod.py",
        """
        from dgraph_tpu.utils.observe import METRICS

        def f(x, name):
            METRICS.inc("tootally_bogus_counter")       # typo'd name
            METRICS.observe(f"span_{x}_oops", 1.0)      # unknown family
            METRICS.inc(name)                           # unresolvable
        """,
        ["metrics-registry"],
    )
    codes = sorted(v.code for v in rep.violations)
    assert codes == [
        "dynamic-metric-name",
        "dynamic-metric-name",
        "unregistered-metric",
    ], [v.render() for v in rep.violations]


def test_metrics_registry_checker_clean_fixture(tmp_path):
    rep = _run_fixture(
        tmp_path,
        "mod.py",
        """
        from dgraph_tpu.utils.observe import METRICS, Metrics

        def f(name):
            METRICS.inc("rpc_retries_total")
            METRICS.inc("level_task_uids", 5)
            METRICS.observe(f"span_{name}_seconds", 0.1)  # declared family
            METRICS.set_gauge("cache_point_reads", 1.0)
            with METRICS.timer("query_latency_seconds"):
                pass
            local = Metrics(prefix="t")
            local.inc("anything_goes")  # local registries are exempt
        """,
        ["metrics-registry"],
    )
    assert not rep.violations, [v.render() for v in rep.violations]


def test_metrics_md_in_sync():
    from dgraph_tpu.utils import observe

    with open(os.path.join(REPO, "METRICS.md")) as f:
        on_disk = f.read()
    assert on_disk == observe.metrics_reference(), (
        "METRICS.md is stale — regenerate with "
        "`python -m dgraph_tpu.cli metrics-ref -o METRICS.md`"
    )


# a name a document gives in backticks that is a file the program
# writes at run time, not a path of the tree
_RUNTIME_FILES = {"MANIFEST.json"}  # a debug bundle's member


@pytest.mark.parametrize(
    "doc",
    [
        "tools/check.sh",
        "README.md",
        "ARCHITECTURE.md",
        "benchmarks/README.md",
        "CONFIG.md",
    ],
)
def test_documents_name_only_files_that_exist(doc):
    """Every script tools/check.sh runs, and every .py/.json/.md/.sh
    path a document names in backticks, is in the tree: a document
    that sends its reader to a deleted harness or capture fails here."""
    import re

    with open(os.path.join(REPO, doc)) as f:
        text = f.read()
    path = r"[\w./-]+\.(?:py|json|md|sh)"
    if doc.endswith(".sh"):
        named = set(re.findall(path, text))
    else:
        named = {
            m.group(0)
            for tok in re.findall(r"`([^`\n]+)`", text)
            for word in tok.split()
            for m in [re.match(path + r"(?=$|:)", word)]
            if m
        }
    # a path is given from the document's directory, the repo root, the
    # package or the tests; a bare name may be any file of those trees
    roots = [os.path.dirname(doc), "", "dgraph_tpu", "tests"]
    trees = ["dgraph_tpu", "tests", "tools", "benchmarks", "chipbench"]
    basenames = set(os.listdir(REPO)) | _RUNTIME_FILES
    for tree in trees:
        for _, _, files in os.walk(os.path.join(REPO, tree)):
            basenames.update(files)
    missing = sorted(
        p for p in named
        if not any(
            os.path.isfile(os.path.join(REPO, root, p)) for root in roots
        )
        and ("/" in p or p not in basenames)
    )
    assert named and not missing, missing


def test_metric_declarations_are_documented():
    from dgraph_tpu.utils.observe import METRIC_DEFS

    for d in METRIC_DEFS.values():
        assert d.kind in ("counter", "gauge", "histogram"), d
        assert len(d.doc.split()) >= 4, f"{d.name} needs a real doc line"


# ---------------------------------------------------------------------------
# lock-order: cross-module acquisition graph + cycle detection
# ---------------------------------------------------------------------------

# an inversion neither half of which is visible intra-file: EngineX
# holds its instance lock while calling into the coalescer module,
# which elsewhere holds its queue lock while calling back into a
# (unique-name-resolved) EngineX method that takes the instance lock
_LO_ENGINE = """
    import threading

    from dgraph_tpu.worker import coalx


    class EngineX:
        def __init__(self):
            self._lock = threading.Lock()

        def flush_batches(self):
            with self._lock:
                coalx.drain_all()

        def apply_one_delta(self):
            with self._lock:
                return 1
"""

_LO_COAL = """
    import threading

    _QLOCK = threading.Lock()


    def drain_all():
        with _QLOCK:
            return []


    def requeue(engine):
        with _QLOCK:
            engine.apply_one_delta()
"""


def _write_fixture(tmp_path, rel, source):
    p = tmp_path / rel
    p.parent.mkdir(parents=True, exist_ok=True)
    p.write_text(textwrap.dedent(source))


def test_lockorder_catches_cross_module_inversion(tmp_path):
    _write_fixture(tmp_path, "worker/enginex.py", _LO_ENGINE)
    _write_fixture(tmp_path, "worker/coalx.py", _LO_COAL)
    rep = analysis.run(
        root=str(tmp_path), checkers=["lock-order"], allows=[]
    )
    assert [v.code for v in rep.violations] == ["lock-order-cycle"], [
        v.render() for v in rep.violations
    ]
    msg = rep.violations[0].message
    assert "worker/enginex.py:EngineX._lock" in msg
    assert "worker/coalx.py:_QLOCK" in msg
    # each hop carries a concrete code location
    assert "worker/enginex.py:" in msg and "worker/coalx.py:" in msg


def test_lockorder_clean_when_callback_runs_unlocked(tmp_path):
    # same modules, but the coalescer calls back AFTER releasing its
    # queue lock — the classic fix — so the edge (and cycle) vanishes
    fixed = _LO_COAL.replace(
        """
    def requeue(engine):
        with _QLOCK:
            engine.apply_one_delta()
""",
        """
    def requeue(engine):
        with _QLOCK:
            pass
        engine.apply_one_delta()
""",
    )
    assert fixed != _LO_COAL  # the replace actually happened
    _write_fixture(tmp_path, "worker/enginex.py", _LO_ENGINE)
    _write_fixture(tmp_path, "worker/coalx.py", fixed)
    rep = analysis.run(
        root=str(tmp_path), checkers=["lock-order"], allows=[]
    )
    assert rep.violations == [], [v.render() for v in rep.violations]


_LO_NEST = """
    import threading

    A = threading.Lock()
    B = threading.Lock()
    C = threading.Lock()


    def ab():
        with A:
            with B:
                pass


    def bc():
        with B:
            with C:
                pass


    def ca():
        with C:
            with A:
                pass
"""


def test_lockorder_catches_three_lock_nest_cycle(tmp_path):
    # arbitrary-length cycles via lexical nesting alone — beyond the
    # pairwise inversion the lock-discipline checker already catches
    _write_fixture(tmp_path, "worker/ringlocks.py", _LO_NEST)
    rep = analysis.run(
        root=str(tmp_path), checkers=["lock-order"], allows=[]
    )
    assert [v.code for v in rep.violations] == ["lock-order-cycle"]
    msg = rep.violations[0].message
    for lock in ("ringlocks.py:A", "ringlocks.py:B", "ringlocks.py:C"):
        assert lock in msg, msg


def test_lockorder_real_graph_is_populated():
    # guard against the checker silently extracting nothing: the real
    # package must yield a non-trivial graph containing the known
    # commit-plane orderings (and, per the gate above, zero cycles)
    from dgraph_tpu.analysis import check_lockorder
    from dgraph_tpu.analysis.core import load_sources

    g = check_lockorder.lock_graph(load_sources(analysis.package_root()))
    nodes = {n for e in g for n in e}
    assert len(g) >= 12, sorted(g)
    for expected in (
        "worker/groupcommit.py:GroupCommit._lock",
        "worker/harness.py:ProcCluster._commit_lock",
        "worker/groups.py:DistributedCluster._commit_lock",
        "utils/observe.py:Metrics._lock",
        "models/vector.py:VectorIndex._lock",
    ):
        assert expected in nodes, sorted(nodes)
    # the commit lock is held across GroupCommit bookkeeping — the
    # ordering TSan/chaos runs exercise dynamically
    assert (
        "worker/harness.py:ProcCluster._commit_lock",
        "worker/groupcommit.py:GroupCommit._lock",
    ) in g


# ---------------------------------------------------------------------------
# shared-state: unguarded writes from thread-context functions
# ---------------------------------------------------------------------------

_SS_FIXTURE = """
    import threading

    _REGISTRY = {}
    _TOTAL = 0


    class Daemon:
        def __init__(self):
            self._lock = threading.Lock()
            self.count = 0
            self.ok_count = 0
            self.noted = 0
            self._thread = threading.Thread(target=self._loop, daemon=True)

        def _loop(self):
            self.count += 1
            _REGISTRY["d"] = self
            with self._lock:
                self.ok_count += 1
            self.noted = 1  # race-ok: single-writer monotonic flag
            self.bare = 2  # race-ok


    def kick(pool):
        return pool.submit(_work)


    def _work():
        global _TOTAL
        _TOTAL += 1
"""


def test_shared_state_catches_seeded_races(tmp_path):
    rep = _run_fixture(
        tmp_path, "worker/daemon.py", _SS_FIXTURE, ["shared-state"]
    )
    codes = sorted(v.code for v in rep.violations)
    msgs = "\n".join(v.render() for v in rep.violations)
    # self.count, _REGISTRY["d"], and the pool-submitted global
    assert codes.count("unguarded-shared-write") == 3, msgs
    # bare `# race-ok` without an ownership reason still fails
    assert codes.count("race-ok-missing-reason") == 1, msgs
    # the lock-guarded write and the annotated write produced nothing
    assert "ok_count" not in msgs and "noted" not in msgs, msgs


def test_shared_state_accepts_preceding_comment_annotation(tmp_path):
    rep = _run_fixture(
        tmp_path,
        "worker/annotated.py",
        """
        import threading


        class D:
            def __init__(self):
                self.beat = 0
                threading.Thread(target=self._loop, daemon=True).start()

            def _loop(self):
                # race-ok: heartbeat counter, this thread is the only
                # writer and readers tolerate staleness
                self.beat += 1
        """,
        ["shared-state"],
    )
    assert rep.violations == [], [v.render() for v in rep.violations]


def test_shared_state_def_level_annotation_covers_body(tmp_path):
    rep = _run_fixture(
        tmp_path,
        "worker/owned.py",
        """
        import threading


        class D:
            def __init__(self):
                self.a = 0
                self.b = 0
                threading.Thread(target=self._loop, daemon=True).start()

            def _loop(self):  # race-ok: sole owner of a and b
                self.a += 1
                self.b += 1
        """,
        ["shared-state"],
    )
    assert rep.violations == [], [v.render() for v in rep.violations]


def test_shared_state_ignores_locals_and_main_thread_writes(tmp_path):
    rep = _run_fixture(
        tmp_path,
        "worker/clean.py",
        """
        import threading

        _STATE = {}


        class D:
            def __init__(self):
                self.total = 0  # main-thread write: not thread context

            def run_inline(self):
                self.total += 1  # never a thread target

            def spawn(self):
                threading.Thread(target=self._loop, daemon=True).start()

            def _loop(self):
                local = 0
                local += 1
                items = [x for x in range(3)]
                for x in items:
                    local = x
        """,
        ["shared-state"],
    )
    assert rep.violations == [], [v.render() for v in rep.violations]


def test_shared_state_sees_lambda_and_ctx_run_entries(tmp_path):
    rep = _run_fixture(
        tmp_path,
        "worker/wrapped.py",
        """
        import contextvars
        import threading

        _SINK = {}


        class H:
            def fire(self, pool):
                threading.Thread(
                    target=lambda: _SINK.update(a=1), daemon=True
                ).start()
                pool.submit(
                    contextvars.copy_context().run, self._timed, 1
                )

            def _timed(self, x):
                self.last = x
        """,
        ["shared-state"],
    )
    codes = [v.code for v in rep.violations]
    msgs = "\n".join(v.render() for v in rep.violations)
    # the ctx.run-wrapped method's self.last write is found; the
    # lambda's .update() method call is a documented limitation
    assert codes == ["unguarded-shared-write"], msgs
    assert "self.last" in msgs


def test_shared_state_real_package_is_clean():
    rep = analysis.run(checkers=["shared-state"], allows=[])
    assert rep.violations == [], "\n".join(
        v.render() for v in rep.violations
    )
    # and entry discovery actually saw the real daemons (not a no-op)
    from dgraph_tpu.analysis import check_shared_state
    from dgraph_tpu.analysis.core import load_sources

    entries = 0
    per_file = {}
    for src in load_sources(analysis.package_root()):
        if src.tree is None:
            continue
        found = check_shared_state._find_entries(src)
        entries += len(found)
        if found:
            per_file[src.rel] = len(found)
    assert entries >= 10, per_file
    for rel in (
        "posting/rollup.py", "worker/groups.py", "worker/remote.py",
        "utils/observe.py",
    ):
        assert rel in per_file, per_file


# ---------------------------------------------------------------------------
# DECLS drift: extern "C" prototypes vs ctypes decls, both directions
# ---------------------------------------------------------------------------


def _real_cpp_texts():
    out = {}
    native_dir = os.path.join(REPO, "dgraph_tpu", "native")
    for fn in sorted(os.listdir(native_dir)):
        if fn.endswith(".cpp"):
            with open(os.path.join(native_dir, fn)) as f:
                out[f"native/{fn}"] = f.read()
    return out


def test_decls_drift_name_and_arity_set_equality():
    # the drift invariant, asserted directly: the union of extern "C"
    # exports across every native .cpp equals DECLS exactly, name AND
    # arity — not just the subset direction the width checker implies
    from dgraph_tpu import native

    exports = {}
    for text in _real_cpp_texts().values():
        exports.update(check_ctypes_abi.parse_cpp_exports(text))
    assert set(exports) == set(native.DECLS), (
        sorted(set(exports) ^ set(native.DECLS))
    )
    for name, (_ret, params, _line) in exports.items():
        assert len(params) == len(native.DECLS[name][1]), (
            f"{name}: .cpp takes {len(params)} args, "
            f"DECLS declares {len(native.DECLS[name][1])}"
        )


def test_decls_drift_detected_on_mutated_real_source():
    # seed drift into the REAL codec.cpp text (proving the parser
    # handles the production file, not just synthetic fixtures):
    # 1. an extra parameter on a live kernel -> arity-mismatch
    from dgraph_tpu import native

    texts = _real_cpp_texts()
    cpp = texts["native/codec.cpp"]
    needle = "int64_t sst_scan("
    assert needle in cpp, "sst_scan prototype moved; update this test"
    mutated = dict(texts)
    mutated["native/codec.cpp"] = cpp.replace(
        needle, "int64_t sst_scan(int32_t extra_flag, ", 1
    )
    out = check_ctypes_abi.check_abi(
        mutated, native.DECLS, "native/__init__.py"
    )
    assert any(
        v.code in ("arity-mismatch", "arg-type-mismatch")
        and "sst_scan" in v.message
        for v in out
    ), [v.render() for v in out]

    # 2. a renamed export -> stale-decl (old name) + undeclared-export
    mutated["native/codec.cpp"] = cpp.replace(
        "int64_t sst_scan(", "int64_t sst_scan_v2(", 1
    )
    codes = sorted(
        v.code for v in check_ctypes_abi.check_abi(
            mutated, native.DECLS, "native/__init__.py"
        )
    )
    assert "stale-decl" in codes and "undeclared-export" in codes, codes
