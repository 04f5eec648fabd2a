"""alpha CLI smoke tests: single-node and --cluster serving modes."""

import json
import os
import signal
import socket
import subprocess
import sys
import time
import urllib.request

import pytest


def _free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    p = s.getsockname()[1]
    s.close()
    return p


def _wait_http(port, timeout=60):
    deadline = time.time() + timeout
    while time.time() < deadline:
        try:
            with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/health", timeout=1
            ) as r:
                return json.loads(r.read())
        except Exception:
            time.sleep(0.3)
    raise TimeoutError("alpha never became healthy")


def _spawn_alpha(*extra):
    port = _free_port()
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env["PYTHONPATH"] = repo + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.Popen(
        [
            sys.executable, "-m", "dgraph_tpu", "alpha",
            "--port", str(port), "--grpc_port", "0", *extra,
        ],
        env=env,
        stdout=subprocess.DEVNULL,
        stderr=subprocess.DEVNULL,
    )
    return proc, port


def _post(port, path, body, ctype="application/rdf"):
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}{path}",
        data=body.encode(),
        headers={"Content-Type": ctype},
        method="POST",
    )
    with urllib.request.urlopen(req, timeout=10) as r:
        return json.loads(r.read())


@pytest.mark.parametrize(
    "extra",
    [
        (),
        ("--cluster", "groups=2; replicas=3"),
    ],
    ids=["single-node", "cluster"],
)
def test_alpha_cli_serves(extra):
    proc, port = _spawn_alpha(*extra)
    try:
        health = _wait_http(port)
        assert health[0]["status"] == "healthy"
        out = _post(port, "/alter", "name: string @index(exact) .")
        assert out["data"]["code"] == "Success"
        out = _post(
            port, "/mutate?commitNow=true",
            '{ set { _:x <name> "cli-alice" . } }',
        )
        assert out["data"]["code"] == "Success"
        res = _post(port, "/query", '{ q(func: eq(name, "cli-alice")) { name } }')
        assert res["data"]["q"] == [{"name": "cli-alice"}]
    finally:
        proc.send_signal(signal.SIGKILL)
        proc.wait(timeout=5)
