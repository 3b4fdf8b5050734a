"""Subprocess harness for the tier-2 integration scenarios.

The reference's integration suite drives the real ``pio`` CLI and real
HTTP servers from a Python runner (reference: [U] tests/pio_tests/
{tests.py,integration.py,utils.py} — unverified, SURVEY.md §4 Tier 2).
This is the same shape without Docker: every scenario gets a throwaway
``PIO_HOME`` (SQLite meta + events, LocalFS models), runs ``bin/pio``
verbs as real subprocesses, and talks to the spawned servers over HTTP.

JAX in the subprocesses runs on the CPU backend (``JAX_PLATFORMS=cpu``),
like the rest of the tests.
"""

from __future__ import annotations

import json
import os
import socket
import subprocess
import sys
import time
import urllib.error
import urllib.request
from typing import Any, Dict, List, Optional, Sequence, Tuple

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
PIO = os.path.join(REPO, "bin", "pio")


def scenario_env(pio_home: str) -> Dict[str, str]:
    env = dict(os.environ)
    env["PIO_HOME"] = pio_home
    env["JAX_PLATFORMS"] = "cpu"
    env["PIO_MESH_PLATFORM"] = "cpu"
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env["PIO_PYTHON"] = sys.executable
    return env


def pio(args: Sequence[str], env: Dict[str, str], check: bool = True,
        timeout: float = 300.0) -> subprocess.CompletedProcess:
    """Run one pio verb to completion; returns the CompletedProcess."""
    proc = subprocess.run(
        [PIO, *args], env=env, capture_output=True, text=True, timeout=timeout)
    if check and proc.returncode != 0:
        raise AssertionError(
            f"pio {' '.join(args)} failed (rc={proc.returncode})\n"
            f"stdout:\n{proc.stdout}\nstderr:\n{proc.stderr}")
    return proc


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


class Server:
    """A pio server subprocess (eventserver / deploy) with readiness wait."""

    def __init__(self, args: Sequence[str], env: Dict[str, str], port: int,
                 ready_path: str = "/", ready_timeout: float = 240.0):
        self.port = port
        self.proc = subprocess.Popen(
            [PIO, *args], env=env,
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True)
        deadline = time.time() + ready_timeout
        last_err: Optional[BaseException] = None
        while time.time() < deadline:
            if self.proc.poll() is not None:
                out = self.proc.stdout.read() if self.proc.stdout else ""
                raise AssertionError(
                    f"server exited early (rc={self.proc.returncode}):\n{out}")
            try:
                self.get(ready_path)
                return
            except (urllib.error.URLError, ConnectionError, OSError) as e:
                last_err = e
                time.sleep(0.3)
        self.stop()
        raise AssertionError(f"server on :{port} never became ready: {last_err}")

    # -- HTTP helpers ---------------------------------------------------------

    def _url(self, path: str) -> str:
        return f"http://127.0.0.1:{self.port}{path}"

    def get(self, path: str, timeout: float = 10.0) -> Tuple[int, Any]:
        return self.request("GET", path, None, timeout)

    def post(self, path: str, body: Any, timeout: float = 30.0) -> Tuple[int, Any]:
        return self.request("POST", path, body, timeout)

    def delete(self, path: str, timeout: float = 10.0) -> Tuple[int, Any]:
        return self.request("DELETE", path, None, timeout)

    def request(self, method: str, path: str, body: Any,
                timeout: float = 30.0) -> Tuple[int, Any]:
        data = None if body is None else json.dumps(body).encode()
        req = urllib.request.Request(
            self._url(path), data=data, method=method,
            headers={"Content-Type": "application/json"})
        try:
            with urllib.request.urlopen(req, timeout=timeout) as r:
                raw = r.read().decode()
                if "json" in (r.headers.get("Content-Type") or ""):
                    return r.status, json.loads(raw or "null")
                return r.status, raw
        except urllib.error.HTTPError as e:
            payload = e.read().decode()
            try:
                payload = json.loads(payload)
            except (json.JSONDecodeError, ValueError):
                pass
            return e.code, payload

    # -- lifecycle ------------------------------------------------------------

    def stop(self, grace: float = 10.0) -> None:
        if self.proc.poll() is None:
            self.proc.terminate()
            try:
                self.proc.wait(timeout=grace)
            except subprocess.TimeoutExpired:
                self.proc.kill()
                self.proc.wait(timeout=grace)

    def __enter__(self) -> "Server":
        return self

    def __exit__(self, *exc: Any) -> None:
        self.stop()


def new_app(env: Dict[str, str], name: str) -> str:
    """`pio app new`; returns the generated access key."""
    out = pio(["app", "new", name], env).stdout
    for line in out.splitlines():
        if "Access Key:" in line:
            return line.split("Access Key:")[1].strip()
    raise AssertionError(f"no access key in output:\n{out}")


def rating_events(n_users: int = 8, n_items: int = 12) -> List[Dict[str, Any]]:
    """Two disjoint taste cliques — same fixture logic as the in-process
    quickstart test: even users rate even items high, odd users odd."""
    events = []
    for u in range(n_users):
        for i in range(n_items):
            if (u + i) % 2 == 0:
                events.append({
                    "event": "rate",
                    "entityType": "user", "entityId": str(u),
                    "targetEntityType": "item", "targetEntityId": str(i),
                    "properties": {"rating": 4.5},
                })
    return events


def write_engine_variant(engine_dir: str, app_name: str,
                         rank: int = 8, iters: int = 5) -> str:
    """Materialize an engine dir holding an engine.json that points at the
    in-package recommendation template with the scenario's app."""
    os.makedirs(engine_dir, exist_ok=True)
    variant = {
        "id": "default",
        "description": "scenario recommendation engine",
        "engineFactory":
            "predictionio_tpu.templates.recommendation.engine:engine_factory",
        "datasource": {"params": {"appName": app_name,
                                  "eventNames": ["rate", "buy"]}},
        "preparator": {"params": {}},
        "algorithms": [{"name": "als",
                        "params": {"rank": rank, "numIterations": iters,
                                   "lambda": 0.01, "seed": 3}}],
        "serving": {"params": {}},
    }
    path = os.path.join(engine_dir, "engine.json")
    with open(path, "w", encoding="utf-8") as f:
        json.dump(variant, f, indent=2)
    return path
