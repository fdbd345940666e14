"""The system under test as child processes: start through the normal
entry point, wait for health, read ``/metrics`` and ``/debug/*``, stop
with SIGTERM. A copy of chip_smoke.py's child handling (PR 21), kept here
so that the yardstick imports nothing of the program.

This module never imports JAX: a parent that touched JAX would hold the
chip its child needs.
"""

from __future__ import annotations

import json
import os
import signal
import socket
import subprocess
import sys
import time
import urllib.error
import urllib.request

START_TIMEOUT_S = 1100  # exec to healthy, cold compile included
# SIGTERM to exit: a Loader's Save of 1M rows took 61 s where the program that
# reads the table back compiled, 12-14 s after (my chip runs, PR 48)
STOP_TIMEOUT_S = 300
BAD_LOG_LINES = (
    "Traceback",
    "native library",
    "bucket warm-up failed",
    "sync tick failed",
)


class BenchFailure(Exception):
    """The run cannot produce a result: non-zero exit, no result line."""


def require(cond, msg: str) -> None:
    if not cond:
        raise BenchFailure(msg)


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def child_env(platform: str, n_devices: int, extra: dict) -> dict:
    """The caller's environment minus every GUBER_* setting, plus the
    configuration's. JAX_PLATFORMS is left alone unless --platform cpu
    asked for the rehearsal: on the chip host nobody sets it, and if JAX
    then quietly initialises the CPU the platform check fails the run."""
    env = {k: v for k, v in os.environ.items() if not k.startswith("GUBER_")}
    if platform == "cpu":
        env["JAX_PLATFORMS"] = "cpu"
        env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n_devices}"
    env.update(extra)
    return env


class Child:
    """One process with its output in a log file."""

    def __init__(self, label: str, argv: list, env: dict, cwd: str,
                 log_dir: str, stdin=None, stdout_pipe: bool = False):
        os.makedirs(log_dir, exist_ok=True)
        self.label = label
        self.log_path = os.path.join(log_dir, f"{label}.log")
        self.t_exec = time.monotonic()
        self._log = open(self.log_path, "wb")
        self.proc = subprocess.Popen(
            argv, cwd=cwd, env=env, stdin=stdin,
            stdout=subprocess.PIPE if stdout_pipe else self._log,
            stderr=self._log if stdout_pipe else subprocess.STDOUT,
        )

    def log_text(self) -> str:
        with open(self.log_path, encoding="utf-8", errors="replace") as f:
            return f.read()

    def require_running(self) -> None:
        rc = self.proc.poll()
        require(rc is None,
                f"{self.label} exited rc={rc}; log tail:\n{self.log_text()[-3000:]}")

    def require_clean_log(self) -> None:
        text = self.log_text()
        for bad in BAD_LOG_LINES:
            require(bad not in text,
                    f"{self.label} log holds {bad!r}:\n{text[-3000:]}")

    def terminate(self, timeout_s: float = 120.0) -> int:
        self.proc.send_signal(signal.SIGTERM)
        return self.proc.wait(timeout=timeout_s)

    def kill(self) -> None:
        if self.proc.poll() is None:
            self.proc.kill()
        self.proc.wait()
        self._log.close()


def http_json(addr: str, path: str, timeout: float = 60.0):
    with urllib.request.urlopen(f"http://{addr}{path}", timeout=timeout) as r:
        return json.loads(r.read())


def scrape(addr: str) -> dict:
    """``/metrics`` as {series with its labels: value}."""
    with urllib.request.urlopen(f"http://{addr}/metrics", timeout=60) as r:
        text = r.read().decode()
    out = {}
    for line in text.splitlines():
        if line and not line.startswith("#"):
            name, _, value = line.rpartition(" ")
            try:
                out[name] = float(value)
            except ValueError:
                pass
    return out


def wait_until(what: str, cond, timeout_s: float, poll_s: float = 0.25) -> None:
    deadline = time.monotonic() + timeout_s
    while not cond():
        require(time.monotonic() < deadline, f"timed out waiting for {what}")
        time.sleep(poll_s)


class Daemon:
    """One ``python -m gubernator_tpu.cmd.daemon`` under the
    configuration's environment."""

    def __init__(self, label: str, conf: dict, platform: str, chips: int,
                 root: str, work_dir: str, extra_env: dict = None):
        self.grpc_addr = f"127.0.0.1:{free_port()}"
        self.http_addr = f"127.0.0.1:{free_port()}"
        tmp = os.path.join(work_dir, "tmp")
        os.makedirs(tmp, exist_ok=True)
        env = {
            "GUBER_GRPC_ADDRESS": self.grpc_addr,
            "GUBER_HTTP_ADDRESS": self.http_addr,
            # profiler captures land under TMPDIR: keep them in the checkout
            "TMPDIR": tmp,
            **{k: str(v) for k, v in conf.get("env", {}).items()},
            **(extra_env or {}),  # the harness's own, e.g. BENCH_SNAPSHOT_IN
        }
        if platform != "cpu":
            # only the first run of a cell in a checkout compiles
            env["JAX_COMPILATION_CACHE_DIR"] = os.environ.get(
                "JAX_COMPILATION_CACHE_DIR") or os.path.join(root, ".jax_cache")
        self.child = Child(
            label, [sys.executable, *conf["command"]],
            child_env(platform, chips, env), root, work_dir,
        )

    def wait_healthy(self) -> float:
        """Seconds from exec until /v1/HealthCheck answers healthy."""

        def healthy() -> bool:
            self.child.require_running()
            try:
                return http_json(self.http_addr, "/v1/HealthCheck",
                                 timeout=5)["status"] == "healthy"
            except (urllib.error.URLError, OSError, ValueError):
                return False

        wait_until(f"{self.child.label} to be healthy", healthy,
                   START_TIMEOUT_S, poll_s=0.1)
        return time.monotonic() - self.child.t_exec

    def stop(self) -> float:
        """SIGTERM, the drain, the exit; returns the seconds that took."""
        t_term = time.monotonic()
        rc = self.child.terminate(STOP_TIMEOUT_S)
        stop_s = time.monotonic() - t_term
        require(rc == 0, f"{self.child.label} exited rc={rc} after SIGTERM")
        require("drain complete" in self.child.log_text(),
                f"{self.child.label} log never reached 'drain complete'")
        self.child.require_clean_log()
        return stop_s
