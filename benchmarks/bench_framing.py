"""Inter-node data-plane framing: JSON+b64 (old) vs binary multipart (new).

Measures the two costs of bulk transfers
(raft snapshot install, predicate-move streams): encode+decode CPU time
and bytes on the wire, on a realistic tablet payload (posting-list
records: binary keys + pack bytes). Then times a real cross-process
predicate move in a ProcCluster with the live codec.

Usage: python benchmarks/bench_framing.py [--json out] [--move-edges N]
"""

import os as _os
import sys as _sys

_REPO = _os.path.dirname(_os.path.dirname(_os.path.abspath(__file__)))
if _REPO not in _sys.path:  # `python benchmarks/x.py` puts only benchmarks/ there
    _sys.path.insert(0, _REPO)

# a host-plane measurement (codec + ProcCluster transport): CPU by request
_os.environ["JAX_PLATFORMS"] = "cpu"

import dgraph_tpu  # noqa: E402,F401 — places the compile cache before jax loads

import argparse
import base64
import json
import time

import numpy as np

from dgraph_tpu.conn.frame import pack_body, unpack_body


def _old_jsonize(obj):
    if isinstance(obj, bytes):
        return {"__b64__": base64.b64encode(obj).decode()}
    if isinstance(obj, (list, tuple)):
        return [_old_jsonize(x) for x in obj]
    if isinstance(obj, dict):
        return {k: _old_jsonize(v) for k, v in obj.items()}
    return obj


def _old_unjsonize(obj):
    if isinstance(obj, dict):
        if set(obj.keys()) == {"__b64__"}:
            return base64.b64decode(obj["__b64__"])
        return {k: _old_unjsonize(v) for k, v in obj.items()}
    if isinstance(obj, list):
        return [_old_unjsonize(x) for x in obj]
    return obj


def tablet_payload(n_keys: int, val_bytes: int) -> dict:
    """A predicate-move stream chunk: [key, ts, record] triples with
    pack-like values (bit-packed uid blocks: structured, compressible)."""
    rng = np.random.default_rng(0)
    rows = []
    for i in range(n_keys):
        key = b"\x00\x00dgraph.movie.film" + i.to_bytes(8, "big")
        uids = np.sort(
            rng.choice(1 << 24, val_bytes // 4, replace=False)
        ).astype(np.uint32)
        rows.append([key, 7, np.diff(uids, prepend=uids[:1]).tobytes()])
    return {"rows": rows}


def bench_codec(payload: dict) -> dict:
    t0 = time.perf_counter()
    old_body = json.dumps(_old_jsonize(payload)).encode()
    t_old_enc = time.perf_counter() - t0
    t0 = time.perf_counter()
    _old_unjsonize(json.loads(old_body))
    t_old_dec = time.perf_counter() - t0

    t0 = time.perf_counter()
    new_body = pack_body(payload)
    t_new_enc = time.perf_counter() - t0
    t0 = time.perf_counter()
    unpack_body(new_body)
    t_new_dec = time.perf_counter() - t0

    return {
        "payload_mb": round(
            sum(len(r[0]) + len(r[2]) for r in payload["rows"]) / 1e6, 1
        ),
        "old_wire_mb": round(len(old_body) / 1e6, 2),
        "new_wire_mb": round(len(new_body) / 1e6, 2),
        "old_enc_s": round(t_old_enc, 3),
        "old_dec_s": round(t_old_dec, 3),
        "new_enc_s": round(t_new_enc, 3),
        "new_dec_s": round(t_new_dec, 3),
        "wire_ratio": round(len(old_body) / len(new_body), 2),
        "cpu_speedup": round(
            (t_old_enc + t_old_dec) / (t_new_enc + t_new_dec), 2
        ),
    }


def bench_typed(payload: dict) -> dict:
    """Typed KVList (conn/messages.py, pb wire format) vs the legacy
    JSON+b64 body for the same record batch:
    small-record wire_ratio must exceed 1.0 (typed bytes < JSON bytes)."""
    from dgraph_tpu.conn.messages import KV, KVList

    t0 = time.perf_counter()
    old_body = json.dumps(_old_jsonize(payload)).encode()
    t_old_enc = time.perf_counter() - t0
    t0 = time.perf_counter()
    _old_unjsonize(json.loads(old_body))
    t_old_dec = time.perf_counter() - t0

    t0 = time.perf_counter()
    msg = KVList(
        kv=[KV(key=k, ts=ts, value=v) for k, ts, v in payload["rows"]]
    )
    typed_body = msg.encode()
    t_new_enc = time.perf_counter() - t0
    t0 = time.perf_counter()
    back = KVList.decode(typed_body)
    t_new_dec = time.perf_counter() - t0
    assert len(back.kv) == len(payload["rows"])

    return {
        "payload_mb": round(
            sum(len(r[0]) + len(r[2]) for r in payload["rows"]) / 1e6, 1
        ),
        "old_wire_mb": round(len(old_body) / 1e6, 2),
        "typed_wire_mb": round(len(typed_body) / 1e6, 2),
        "old_enc_s": round(t_old_enc, 3),
        "old_dec_s": round(t_old_dec, 3),
        "typed_enc_s": round(t_new_enc, 3),
        "typed_dec_s": round(t_new_dec, 3),
        "wire_ratio": round(len(old_body) / len(typed_body), 2),
        "cpu_speedup": round(
            (t_old_enc + t_old_dec) / (t_new_enc + t_new_dec), 2
        ),
    }


def bench_proc_move(n_edges: int) -> dict:
    """A real cross-process predicate move over the live RPC framing."""
    import tempfile

    from dgraph_tpu.worker.harness import ProcCluster

    with tempfile.TemporaryDirectory(prefix="framing_move_") as td:
        pc = ProcCluster(n_groups=2, replicas=1, data_dir=td)
        try:
            pc.alter("name: string .\nfollow: [uid] .")
            rng = np.random.default_rng(3)
            batch = []
            t0 = time.time()
            for i in range(1, n_edges + 1):
                s, o = int(rng.integers(1, 5000)), int(rng.integers(1, 5000))
                batch.append(f"<0x{s:x}> <follow> <0x{o:x}> .")
                if len(batch) >= 2000:
                    t = pc.new_txn()
                    t.mutate_rdf(set_rdf="\n".join(batch), commit_now=True)
                    batch = []
            if batch:
                t = pc.new_txn()
                t.mutate_rdf(set_rdf="\n".join(batch), commit_now=True)
            load_s = time.time() - t0

            src = pc.zero.belongs_to("follow")
            dst = 2 if src == 1 else 1
            t0 = time.time()
            pc.move_tablet("follow", dst)
            move_s = time.time() - t0
            return {
                "edges": n_edges,
                "load_s": round(load_s, 2),
                "move_s": round(move_s, 2),
                "from_group": src,
                "to_group": dst,
            }
        finally:
            pc.close()


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--json", default=None)
    ap.add_argument("--move-edges", type=int, default=30_000)
    args = ap.parse_args()

    from dgraph_tpu.conn import frame

    big = tablet_payload(200, 1 << 18)
    frame._COMPRESS = True
    compressed = bench_codec(big)
    frame._COMPRESS = False
    out = {
        # ~50MB tablet stream: 200 keys x 256KB packs (default raw mode)
        "codec_50mb_raw": bench_codec(big),
        # same payload with DGRAPH_TPU_WIRE_COMPRESS=1 (DCN-class links)
        "codec_50mb_zlib": compressed,
        # many-small-records shape (index keys)
        "codec_small_records": bench_codec(tablet_payload(20_000, 64)),
        # typed control-plane messages (conn/messages.py): the shape
        # RemoteKV/tablet-move streams actually use now
        "typed_small_records": bench_typed(tablet_payload(20_000, 64)),
        "typed_large_records": bench_typed(tablet_payload(2_000, 4096)),
    }
    print(json.dumps(out, indent=1), flush=True)
    if args.move_edges:
        out["proc_move"] = bench_proc_move(args.move_edges)
    blob = json.dumps(out, indent=1)
    print(blob)
    if args.json:
        with open(args.json, "w") as f:
            f.write(blob)


if __name__ == "__main__":
    main()
