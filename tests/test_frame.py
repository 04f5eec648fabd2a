"""conn/frame.py binary multipart codec (the snappy-framing analog)."""

import json

import numpy as np
import pytest

from dgraph_tpu.conn.frame import MAGIC, pack_body, unpack_body


def test_small_message_stays_json():
    obj = {"id": 1, "m": "ping", "a": {"x": [1, 2, 3], "s": "hi"}}
    body = pack_body(obj)
    assert body[0] != MAGIC
    assert json.loads(body) == obj
    assert unpack_body(body) == obj


def test_small_bytes_inline_b64():
    obj = {"a": {"key": b"shortkey", "n": 7}}
    body = pack_body(obj)
    assert body[0] != MAGIC  # no blobs extracted
    assert unpack_body(body) == obj


def test_large_bytes_ride_as_blobs():
    rng = np.random.default_rng(0)
    big = rng.integers(0, 256, 100_000, dtype=np.uint8).tobytes()
    obj = {"r": [[b"k1", 5, big], [b"k2", 6, big[: 50_000]]]}
    body = pack_body(obj)
    assert body[0] == MAGIC
    got = unpack_body(body)
    assert got == {"r": [["k1".encode(), 5, big], [b"k2", 6, big[:50_000]]]}


def test_compressible_blob_shrinks_when_enabled(monkeypatch):
    from dgraph_tpu.conn import frame

    monkeypatch.setattr(frame, "_COMPRESS", True)
    big = b"abcdefgh" * 200_000  # 1.6MB, highly compressible
    body = pack_body({"d": big})
    assert body[0] == MAGIC
    assert len(body) < len(big) // 10
    assert unpack_body(body)["d"] == big


def test_default_mode_stores_raw():
    big = b"abcdefgh" * 200_000
    body = pack_body({"d": big})
    assert len(body) >= len(big)  # raw blob, no b64 inflation either
    assert unpack_body(body)["d"] == big


def test_incompressible_blob_stored_raw():
    rng = np.random.default_rng(1)
    big = rng.integers(0, 256, 1 << 20, dtype=np.uint8).tobytes()
    body = pack_body({"d": big})
    # raw + headers: no inflation beyond a few dozen bytes
    assert len(body) < len(big) + 128
    assert unpack_body(body)["d"] == big


def test_nested_structures_and_tuples():
    obj = {"p": ("delta", [(b"x" * 500, 1)], {"deep": [b"y" * 300]})}
    got = unpack_body(pack_body(obj))
    # tuples become lists on the wire (JSON), like the old codec
    assert got["p"][0] == "delta"
    assert got["p"][1][0][0] == b"x" * 500
    assert got["p"][2]["deep"][0] == b"y" * 300


def test_rpc_roundtrip_with_bulk_payload():
    from dgraph_tpu.conn.rpc import RpcClient, RpcServer

    srv = RpcServer().start()
    payload = [bytes([i % 251] * 2000) for i in range(50)]
    srv.register("bulk", lambda a: {"vals": payload, "n": len(a["keys"])})
    try:
        c = RpcClient(srv.addr)
        got = c.call("bulk", {"keys": [b"a" * 400, b"b" * 400]})
        assert got["n"] == 2
        assert got["vals"] == payload
        c.close_conn()
    finally:
        srv.close()


def test_sentinel_collision_dicts_roundtrip():
    # user payloads that look exactly like codec sentinels must survive
    big = b"x" * 1000
    obj = {
        "a": {"__blob__": 3},
        "b": {"__b64__": "not base64!"},
        "c": {"__esc__": {"__blob__": 0}},
        "d": {"__blob__": big},  # value itself is blob-sized bytes
        "e": big,  # a real blob alongside, indices must not collide
    }
    got = unpack_body(pack_body(obj))
    assert got == obj


def test_sentinel_collision_without_blobs_stays_consistent():
    obj = {"only": {"__b64__": 42}}
    assert unpack_body(pack_body(obj)) == obj


def test_decompression_bomb_rejected():
    import struct
    import zlib

    from dgraph_tpu.conn.frame import FrameError

    # hand-build a frame whose blob declares 100 bytes but inflates to 10MB
    bomb = zlib.compress(b"\x00" * (10 << 20), 1)
    payload = struct.pack(">I", 100) + bomb
    jb = json.dumps({"d": {"__blob__": 0}}).encode()
    body = (
        bytes([MAGIC])
        + struct.pack(">I", len(jb))
        + jb
        + struct.pack(">I", len(payload))
        + b"\x02"
        + payload
    )
    with pytest.raises(FrameError):
        unpack_body(body)


def test_compressed_roundtrip_with_rawlen_header(monkeypatch):
    from dgraph_tpu.conn import frame

    monkeypatch.setattr(frame, "_COMPRESS", True)
    big = b"pattern!" * 100_000
    body = pack_body({"d": big, "meta": {"__blob__": "user-key"}})
    got = unpack_body(body)
    assert got == {"d": big, "meta": {"__blob__": "user-key"}}


def test_declared_huge_rawlen_rejected():
    import struct
    import zlib

    from dgraph_tpu.conn import frame
    from dgraph_tpu.conn.frame import FrameError

    # blob declares 1GB (over the 256MB cap) — rejected before inflating
    comp = zlib.compress(b"\x00" * 1024, 1)
    payload = struct.pack(">I", 1 << 30) + comp
    jb = json.dumps({"d": {"__blob__": 0}}).encode()
    body = (
        bytes([MAGIC])
        + struct.pack(">I", len(jb))
        + jb
        + struct.pack(">I", len(payload))
        + b"\x02"
        + payload
    )
    with pytest.raises(FrameError):
        unpack_body(body)
    assert frame._MAX_INFLATE == 256 << 20


def test_truncated_zlib_trailer_rejected():
    import struct
    import zlib

    from dgraph_tpu.conn.frame import FrameError

    raw = b"checksum-me" * 100
    comp = zlib.compress(raw, 1)[:-4]  # cut the adler32 trailer
    payload = struct.pack(">I", len(raw)) + comp
    jb = json.dumps({"d": {"__blob__": 0}}).encode()
    body = (
        bytes([MAGIC])
        + struct.pack(">I", len(jb))
        + jb
        + struct.pack(">I", len(payload))
        + b"\x02"
        + payload
    )
    with pytest.raises(FrameError):
        unpack_body(body)


def test_malformed_esc_payload_raises_frameerror():
    from dgraph_tpu.conn.frame import FrameError

    with pytest.raises(FrameError):
        unpack_body(json.dumps({"x": {"__esc__": 5}}).encode())


def test_aggregate_inflation_budget_enforced():
    import struct
    import zlib

    from dgraph_tpu.conn import frame
    from dgraph_tpu.conn.frame import FrameError

    # three blobs each declaring 100MB (each under the 256MB cap, but
    # 300MB aggregate) — the frame budget must reject the third
    comp = zlib.compress(b"\x00" * (100 << 20), 1)
    payload = struct.pack(">I", 100 << 20) + comp
    jb = json.dumps({"d": [{"__blob__": i} for i in range(3)]}).encode()
    body = bytes([MAGIC]) + struct.pack(">I", len(jb)) + jb
    for _ in range(3):
        body += struct.pack(">I", len(payload)) + b"\x02" + payload
    with pytest.raises(FrameError):
        unpack_body(body)


def test_legacy_flag1_blob_still_decodes():
    import struct
    import zlib

    raw = b"legacy-data" * 1000
    comp = zlib.compress(raw, 1)
    jb = json.dumps({"d": {"__blob__": 0}}).encode()
    body = (
        bytes([MAGIC])
        + struct.pack(">I", len(jb))
        + jb
        + struct.pack(">I", len(comp))
        + b"\x01"
        + comp
    )
    assert unpack_body(body) == {"d": raw}


def test_bad_blob_ref_types_raise_frameerror():
    from dgraph_tpu.conn.frame import FrameError

    for payload in (
        {"x": {"__blob__": "0"}},  # string index
        {"x": {"__blob__": 0}},  # dangling (no blobs in plain JSON)
        {"x": {"__blob__": True}},  # bool index
        {"x": {"__b64__": 7}},  # non-string b64
    ):
        with pytest.raises(FrameError):
            unpack_body(json.dumps(payload).encode())


def test_trailing_bytes_after_stream_rejected():
    import struct
    import zlib

    from dgraph_tpu.conn.frame import FrameError

    raw = b"payload" * 100
    comp = zlib.compress(raw, 1) + b"JUNKJUNK"
    payload = struct.pack(">I", len(raw)) + comp
    jb = json.dumps({"d": {"__blob__": 0}}).encode()
    body = (
        bytes([MAGIC])
        + struct.pack(">I", len(jb))
        + jb
        + struct.pack(">I", len(payload))
        + b"\x02"
        + payload
    )
    with pytest.raises(FrameError):
        unpack_body(body)


def test_typed_messages_roundtrip():
    """conn/messages.py: pb-wire-format codec roundtrips every schema
    (the typed control plane)."""
    from dgraph_tpu.conn import messages as M

    kvl = M.KVList(
        kv=[
            M.KV(key=b"\x00k1", ts=7, value=b"\xff" * 300),
            M.KV(key=b"k2", ts=1 << 40, value=b""),
        ]
    )
    back = M.KVList.decode(kvl.encode())
    assert back == kvl
    h = M.HealthInfo(ok=True, node=3, group=1, is_leader=True, term=9,
                     applied=12345)
    assert M.HealthInfo.decode(h.encode()) == h
    g = M.GetResponse(found=True, ts=5, value=b"v")
    assert M.GetResponse.decode(g.encode()) == g
    p = M.ProposalResponse(ok=False, error="not leader", leader_hint=2)
    assert M.ProposalResponse.decode(p.encode()) == p
    env = M.RaftEnvelope(kind="append_req", frm=1, to=2, term=3,
                         payload=b"\x01\x02\x00raw")
    assert M.RaftEnvelope.decode(env.encode()) == env
    # unknown fields are skipped (forward compat): append an extra field
    extra = h.encode() + bytes([15 << 3 | 0, 42])
    assert M.HealthInfo.decode(extra) == h


def test_typed_message_over_rpc():
    """A typed request/response crosses the socket as a typed message."""
    from dgraph_tpu.conn import messages as M
    from dgraph_tpu.conn.rpc import RpcClient, RpcServer

    srv = RpcServer()
    srv.register(
        "echo.kv",
        lambda a: M.KVList(kv=[M.KV(key=a.key, ts=a.ts, value=b"hit")]),
    )
    srv.start()
    try:
        c = RpcClient(srv.addr)
        out = c.call("echo.kv", M.GetRequest(key=b"K", ts=7))
        assert isinstance(out, M.KVList)
        assert out.kv[0].key == b"K" and out.kv[0].ts == 7
        assert out.kv[0].value == b"hit"
    finally:
        srv.close()
