"""The daemon's two entries as real processes: `python -m
gubernator_tpu.cmd.daemon` (configuration from the environment) and
`gubernator_tpu.cmd.daemon.serve(conf)`, which an embedder calls with a
configuration whose `store` it has set, as the reference's embedders set
`Config.Store` (no environment variable names a Store)."""

import json
import os
import signal
import socket
import subprocess
import sys
import time
import urllib.request

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# An embedder: the environment's configuration, a Store of its own that
# also logs what it is handed, and the program's serve().
EMBEDDER = """
import json, sys
from gubernator_tpu.cmd.daemon import serve
from gubernator_tpu.service.envconfig import setup_daemon_config
from gubernator_tpu.store import MemoryStore

class Logged(MemoryStore):
    def on_change(self, items):
        super().on_change(items)
        with open(sys.argv[1], "a") as f:
            for it in items:
                f.write(json.dumps([it.key, it.remaining]) + "\\n")

conf = setup_daemon_config(None)
conf.store = Logged()
serve(conf)
"""


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def start(argv, tmp_path):
    http = f"127.0.0.1:{free_port()}"
    env = {k: v for k, v in os.environ.items() if not k.startswith("GUBER_")}
    env.update(
        JAX_PLATFORMS="cpu", PYTHONPATH=ROOT,
        GUBER_GRPC_ADDRESS=f"127.0.0.1:{free_port()}", GUBER_HTTP_ADDRESS=http,
        GUBER_CACHE_SIZE="4096", TMPDIR=str(tmp_path),
    )
    log = open(tmp_path / "daemon.log", "wb")
    p = subprocess.Popen([sys.executable, *argv], cwd=ROOT, env=env,
                         stdout=log, stderr=subprocess.STDOUT)
    deadline = time.monotonic() + 90
    while True:
        assert p.poll() is None, (tmp_path / "daemon.log").read_text()[-3000:]
        try:
            with urllib.request.urlopen(f"http://{http}/v1/HealthCheck", timeout=2) as r:
                if json.loads(r.read())["status"] == "healthy":
                    return p, http
        except OSError:
            pass
        assert time.monotonic() < deadline, "daemon never became healthy"
        time.sleep(0.2)


def hit(http: str, key: str, hits: int) -> dict:
    body = json.dumps({"requests": [{
        "name": "serve", "unique_key": key, "duration": 60000, "limit": 10,
        "hits": hits}]}).encode()
    req = urllib.request.Request(
        f"http://{http}/v1/GetRateLimits", data=body,
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=30) as r:
        return json.loads(r.read())["responses"][0]


def stop(p, tmp_path) -> str:
    p.send_signal(signal.SIGTERM)
    try:
        rc = p.wait(timeout=60)
    finally:
        if p.poll() is None:
            p.kill()
    text = (tmp_path / "daemon.log").read_text()
    assert rc == 0, text[-3000:]
    return text


@pytest.mark.deadline(180)
def test_serve_with_a_store_starts_answers_persists_and_drains(tmp_path):
    changes = tmp_path / "changes.jsonl"
    p, http = start(["-c", EMBEDDER, str(changes)], tmp_path)
    try:
        assert int(hit(http, "a", 4)["remaining"]) == 6
        # write-behind runs before the answer: the Store has the change
        rows = [json.loads(ln) for ln in changes.read_text().splitlines()]
        assert ["serve_a", 6] in rows
        with urllib.request.urlopen(f"http://{http}/metrics", timeout=10) as r:
            text = r.read().decode()
        assert "gubernator_store_on_change_items 1.0" in text
        assert 'gubernator_engine_wave_programs{program="gather_rows"} 1.0' in text
    finally:
        log = stop(p, tmp_path)
    assert "gubernator-tpu listening" in log
    assert "signal received: draining" in log and "drain complete" in log


@pytest.mark.deadline(180)
def test_main_serves_from_the_environment_as_before(tmp_path):
    p, http = start(["-m", "gubernator_tpu.cmd.daemon"], tmp_path)
    try:
        assert int(hit(http, "b", 3)["remaining"]) == 7
        with urllib.request.urlopen(f"http://{http}/metrics", timeout=10) as r:
            text = r.read().decode()
        # no Store: its counters exist and stay at 0
        assert "gubernator_store_on_change_items 0.0" in text
        assert 'gubernator_engine_wave_programs{program="probe"} 0.0' in text
    finally:
        log = stop(p, tmp_path)
    assert "gubernator-tpu listening" in log and "drain complete" in log
    assert " DEBUG " not in log


def test_main_hands_its_arguments_to_serve(tmp_path, monkeypatch):
    from gubernator_tpu.cmd import daemon

    conf_file = tmp_path / "d.conf"
    conf_file.write_text("GUBER_CACHE_SIZE=8192\n")
    seen = {}
    monkeypatch.setattr(daemon, "serve", lambda conf, debug=False: seen.update(
        conf=conf, debug=debug))
    monkeypatch.setattr(sys, "argv", ["daemon", "--config", str(conf_file), "--debug"])
    # the file's keys land in the environment: set first, so that the
    # patch's undo takes the injected value away again
    monkeypatch.setenv("GUBER_CACHE_SIZE", "")
    monkeypatch.delenv("GUBER_CACHE_SIZE")
    daemon.main()
    assert seen["debug"] is True and seen["conf"].cache_size == 8192
    assert seen["conf"].store is None
