"""Aux subsystem tests: admin server, batch views, fake workflow, SSL,
new CLI verbs (build/run), bin scripts presence."""

import datetime as dt
import json
import os
import subprocess
import sys

import pytest

from tests.test_servers import ServerThread, free_port, http

from predictionio_tpu.data.event import Event
from predictionio_tpu.data.view import BatchView
from predictionio_tpu.core.fake_workflow import fake_run
from predictionio_tpu.tools.admin import AdminServer

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def ev(name, eid, target=None, props=None, etype="user"):
    return Event(event=name, entity_type=etype, entity_id=eid,
                 target_entity_type="item" if target else None,
                 target_entity_id=target, properties=props or {})


class TestAdminServer:
    def test_crud_over_http(self, storage):
        port = free_port()
        with ServerThread(AdminServer(storage=storage, host="127.0.0.1",
                                      port=port)):
            base = f"http://127.0.0.1:{port}"
            st, body = http("GET", f"{base}/")
            assert (st, body["status"]) == (200, "alive")

            st, body = http("POST", f"{base}/cmd/app", {"name": "adm"})
            assert st == 201 and body["name"] == "adm" and body["accessKey"]

            st, body = http("POST", f"{base}/cmd/app", {"name": "adm"})
            assert st == 409

            st, body = http("GET", f"{base}/cmd/app")
            assert st == 200 and [a["name"] for a in body["apps"]] == ["adm"]

            app = storage.meta.get_app_by_name("adm")
            storage.events.insert(ev("buy", "u1", target="i1"), app.id)
            st, _ = http("DELETE", f"{base}/cmd/app/adm/data")
            assert st == 200
            assert list(storage.events.find(app.id)) == []

            st, _ = http("DELETE", f"{base}/cmd/app/adm")
            assert st == 200
            assert storage.meta.get_app_by_name("adm") is None
            st, _ = http("GET", f"{base}/cmd/app/adm")
            assert st == 404


class TestBatchView:
    def test_views(self, storage):
        app = storage.meta.create_app("viewapp")
        storage.events.insert(ev("$set", "u1", props={"a": 1}), app.id)
        storage.events.insert(ev("$set", "u1", props={"b": 2}), app.id)
        storage.events.insert(ev("buy", "u1", target="i1"), app.id)
        storage.events.insert(ev("buy", "u2", target="i2"), app.id)
        storage.events.insert(ev("rate", "u2", target="i1"), app.id)

        view = BatchView("viewapp", storage=storage)
        agg = view.aggregate_properties("user")
        assert agg["u1"].properties == {"a": 1, "b": 2}
        grouped = view.group_by_entity("user", event_names=["buy"])
        assert sorted(grouped) == ["u1", "u2"]
        assert view.count_by_event() == {"$set": 2, "buy": 2, "rate": 1}
        assert ("u2", "i1") in view.pairs(["rate"])
        assert view.pairs(["buy"]) == [("u1", "i1"), ("u2", "i2")]


class TestFakeWorkflow:
    def test_completed_instance(self, storage):
        out = fake_run(lambda ctx: 41 + 1, storage=storage, label="t")
        assert out == 42
        eis = storage.meta.list_engine_instances()
        assert len(eis) == 1 and eis[0].status == "COMPLETED"
        assert eis[0].engine_factory == "fake:t"

    def test_failure_recorded(self, storage):
        def boom(ctx):
            raise RuntimeError("nope")

        with pytest.raises(RuntimeError):
            fake_run(boom, storage=storage)
        assert storage.meta.list_engine_instances()[0].status == "FAILED"


class TestSSL:
    def test_no_env_no_context(self, monkeypatch):
        from predictionio_tpu.server.ssl_config import ssl_context_from_env

        monkeypatch.delenv("PIO_SSL_CERT_PATH", raising=False)
        monkeypatch.delenv("PIO_SSL_KEY_PATH", raising=False)
        assert ssl_context_from_env() is None

    def test_half_config_rejected(self, monkeypatch):
        from predictionio_tpu.server.ssl_config import ssl_context_from_env

        monkeypatch.setenv("PIO_SSL_CERT_PATH", "/tmp/x.pem")
        monkeypatch.delenv("PIO_SSL_KEY_PATH", raising=False)
        with pytest.raises(ValueError):
            ssl_context_from_env()

    def test_https_end_to_end(self, storage, tmp_path):
        ssl_mod = pytest.importorskip("ssl")
        # self-signed cert via cryptography is unavailable; use openssl CLI
        cert, key = str(tmp_path / "c.pem"), str(tmp_path / "k.pem")
        r = subprocess.run(
            ["openssl", "req", "-x509", "-newkey", "rsa:2048", "-nodes",
             "-keyout", key, "-out", cert, "-days", "1", "-subj",
             "/CN=localhost"], capture_output=True)
        if r.returncode != 0:
            pytest.skip("openssl unavailable")
        from predictionio_tpu.server.ssl_config import ssl_context_from_env

        ctx = ssl_context_from_env(cert_path=cert, key_path=key)
        port = free_port()
        srv = AdminServer(storage=storage, host="127.0.0.1", port=port)
        srv.http.ssl_context = ctx
        with ServerThread(srv):
            import urllib.request

            client = ssl_mod.create_default_context()
            client.check_hostname = False
            client.verify_mode = ssl_mod.CERT_NONE
            with urllib.request.urlopen(
                    f"https://127.0.0.1:{port}/", context=client,
                    timeout=10) as resp:
                assert json.loads(resp.read())["status"] == "alive"


class TestCLIVerbs:
    def test_build_validates_template(self, tmp_path):
        variant_path = tmp_path / "engine.json"
        v = json.load(open(os.path.join(
            REPO, "predictionio_tpu/templates/recommendation/engine.json")))
        json.dump(v, open(variant_path, "w"))
        r = subprocess.run(
            [sys.executable, "-m", "predictionio_tpu.tools.cli", "build",
             "-e", str(variant_path)],
            capture_output=True, text=True, cwd=REPO,
            env={**os.environ, "PYTHONPATH": REPO})
        assert r.returncode == 0, r.stderr
        assert "is valid" in r.stdout

    def test_build_rejects_bad_factory(self, tmp_path):
        variant_path = tmp_path / "engine.json"
        json.dump({"engineFactory": "nope.nope:missing"}, open(variant_path, "w"))
        r = subprocess.run(
            [sys.executable, "-m", "predictionio_tpu.tools.cli", "build",
             "-e", str(variant_path)],
            capture_output=True, text=True, cwd=REPO,
            env={**os.environ, "PYTHONPATH": REPO})
        assert r.returncode != 0

    def test_run_verb(self, tmp_path):
        mod = tmp_path / "job.py"
        mod.write_text("def main(*args):\n    return 'ran:' + ','.join(args)\n")
        r = subprocess.run(
            [sys.executable, "-m", "predictionio_tpu.tools.cli", "run",
             "job:main", "a", "b", "--engine-dir", str(tmp_path)],
            capture_output=True, text=True, cwd=REPO,
            env={**os.environ, "PYTHONPATH": REPO})
        assert r.returncode == 0, r.stderr
        assert "ran:a,b" in r.stdout


class TestStatusVerb:
    """`pio status` answers for the devices too: "all systems go" is
    only printed when jax has them."""

    def _status(self, tmp_path, platforms):
        return subprocess.run(
            [sys.executable, "-m", "predictionio_tpu.tools.cli", "status"],
            capture_output=True, text=True, cwd=REPO,
            env={**os.environ, "PYTHONPATH": REPO,
                 "PIO_HOME": str(tmp_path / "home"),
                 "JAX_PLATFORMS": platforms})

    def test_ok_with_devices(self, tmp_path):
        r = self._status(tmp_path, "cpu")
        assert r.returncode == 0, r.stderr
        assert "jax devices" in r.stdout and "all systems go" in r.stdout

    def test_fails_without_devices(self, tmp_path):
        r = self._status(tmp_path, "no_such_platform")
        assert r.returncode != 0
        assert "all systems go" not in r.stdout
        assert "jax devices unavailable" in r.stderr


class TestBinScripts:
    def test_present_and_executable(self):
        for name in ("pio", "pio-daemon", "pio-start-all", "pio-stop-all",
                     "pio-shell"):
            path = os.path.join(REPO, "bin", name)
            assert os.path.isfile(path) and os.access(path, os.X_OK)

    def test_pio_launcher_dispatches(self, tmp_path):
        r = subprocess.run(
            [os.path.join(REPO, "bin", "pio"), "version"],
            capture_output=True, text=True,
            env={**os.environ, "PIO_HOME": str(tmp_path)})
        assert r.returncode == 0, r.stderr
