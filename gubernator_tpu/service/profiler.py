"""Bounded jax.profiler capture + continuous background profiling.

Two consumers share this module (and its one-at-a-time guard —
jax.profiler state is process-global, so exactly one capture may run
at a time regardless of how many daemons/listeners share the process):

- /debug/profile (service/gateway.py): on-demand captures. Earlier
  revisions mkdtemp'd a fresh directory per capture and never deleted
  it — a debug-poller leaked a trace dir per request. Captures now
  land under ONE rotating parent (capture-<ns> children, newest
  `keep` retained).
- ContinuousProfiler: the opt-in sampler (GUBER_PROFILE_INTERVAL >
  0): a daemon thread that wakes on the configured cadence, takes a
  short capture, and relies on the same rotation bound — a week of
  unattended soak holds `keep` traces, not 10k. It acquires the guard
  non-blocking: an operator's /debug/profile always wins, the sampler
  just skips that cycle.

Trace directories are plain jax.profiler trace dumps (TensorBoard /
xprof readable); capture() reports the path, file count, and byte
footprint so the debug JSON tells the operator where to point the
viewer and how much disk the trace took.
"""

from __future__ import annotations

import logging
import os
import shutil
import tempfile
import threading
import time

from gubernator_tpu.utils import lockorder
from gubernator_tpu.utils import tracing

log = logging.getLogger("gubernator_tpu.profiler")

# Keep the historical lock name: the guard moved here from gateway.py
# and the lockorder graph keys by name.
PROFILE_GUARD = lockorder.make_lock("gateway.profile_guard")
PROFILE_MAX_SECONDS = 30.0
DEFAULT_KEEP = 8


def trace_root() -> str:
    """Parent directory all captures rotate under."""
    return os.path.join(tempfile.gettempdir(), "gubernator_profiles")


def _dir_stats(path: str) -> tuple:
    files = 0
    nbytes = 0
    for r, _, fs in os.walk(path):
        for f in fs:
            files += 1
            try:
                nbytes += os.path.getsize(os.path.join(r, f))
            except OSError:
                pass
    return files, nbytes


def rotate(keep: int, root: str | None = None) -> int:
    """Delete all but the newest `keep` capture dirs. Returns how many
    were removed. Never raises (a half-deleted trace dir is fine)."""
    root = root or trace_root()
    try:
        entries = sorted(
            e for e in os.listdir(root) if e.startswith("capture-")
        )
    except OSError:
        return 0
    removed = 0
    for name in entries[: max(len(entries) - max(keep, 1), 0)]:
        shutil.rmtree(os.path.join(root, name), ignore_errors=True)
        removed += 1
    return removed


def capture(
    seconds: float, keep: int = DEFAULT_KEEP, root: str | None = None,
    python: bool = False,
) -> dict:
    """Blocking profiler capture (callers run it in an executor or the
    sampler thread) into a fresh dir under the rotating parent.
    Caller must hold PROFILE_GUARD.

    The Python tracer is off unless `python`: the profiler's default
    hooks every Python call on every thread, and this server's host path
    is Python, so the default capture slows what it measures. The host
    tracer stays on: tracing.stage()'s spans and the runtime's own
    events land in plane /host:CPU either way."""
    import jax

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 1 if python else 0

    root = root or trace_root()
    os.makedirs(root, exist_ok=True)
    # Monotonic-clock suffix: unique per process without a tempfile
    # handle the rotation would then have to special-case.
    trace_dir = os.path.join(root, f"capture-{time.time_ns():020d}")
    os.makedirs(trace_dir, exist_ok=True)
    t0 = time.perf_counter()
    jax.profiler.start_trace(trace_dir, profiler_options=options)
    # From here tracing.stage() puts its spans on this timeline.
    tracing.set_capturing(True)
    t_started = time.perf_counter()
    try:
        time.sleep(seconds)
    finally:
        tracing.set_capturing(False)
        t_stop = time.perf_counter()
        jax.profiler.stop_trace()
    t_stopped = time.perf_counter()
    files, nbytes = _dir_stats(trace_dir)
    rotated = rotate(keep, root)
    log.info(
        "profile capture: %.2f s window, stop_trace %.1f s, %d file(s), "
        "%.1f MB in %s", seconds, t_stopped - t_stop, files, nbytes / 1e6,
        trace_dir,
    )
    return {
        "trace_dir": trace_dir,
        "seconds": seconds,
        "files": files,
        "bytes": nbytes,
        "python": python,
        "start_s": t_started - t0,
        "stop_s": t_stopped - t_stop,
        "rotated_out": rotated,
        "keep": keep,
    }


class ContinuousProfiler:
    """Background sampler: one short capture every `interval_s`,
    bounded on disk by `keep`. Off unless interval_s > 0 (the
    GUBER_PROFILE_INTERVAL default is off — captures cost real device
    time and trace bytes, an explicit operator opt-in)."""

    def __init__(
        self,
        interval_s: float,
        seconds: float = 0.5,
        keep: int = DEFAULT_KEEP,
        root: str | None = None,
    ):
        self.interval_s = float(interval_s)
        self.seconds = min(max(float(seconds), 0.05), PROFILE_MAX_SECONDS)
        self.keep = max(int(keep), 1)
        self.root = root or trace_root()
        self.captures = 0
        self.skipped = 0
        self.errors = 0
        self.last = None  # most recent capture() result
        self._stop = threading.Event()
        self._thread = None
        # Self-watchdog heartbeat seam, injected by the daemon (None
        # keeps the sampler usable standalone in tests).
        self.watchdog = None

    def start(self) -> bool:
        if self.interval_s <= 0 or self._thread is not None:
            return False
        self._thread = threading.Thread(
            target=self._loop, name="gubernator-profiler", daemon=True
        )
        self._thread.start()
        return True

    def stop(self) -> None:
        self._stop.set()
        t = self._thread
        if t is not None:
            # A cycle is at most seconds + rotation; don't hang close().
            t.join(timeout=self.seconds + 5.0)
        self._thread = None

    def _loop(self) -> None:
        while not self._stop.wait(self.interval_s):
            wd = self.watchdog
            if wd is not None:
                # A capture blocks for up to `seconds`; fold it into the
                # deadline so a slow trace isn't flagged as a stall.
                wd.beat(
                    "profiler", period_s=self.interval_s + self.seconds
                )
            # Non-blocking: an in-flight /debug/profile capture wins and
            # this cycle is skipped, never queued behind it.
            if not PROFILE_GUARD.acquire(blocking=False):
                self.skipped += 1
                continue
            try:
                self.last = capture(self.seconds, self.keep, self.root)
                self.captures += 1
            except Exception:
                self.errors += 1
                if self.errors in (1, 10) or self.errors % 100 == 0:
                    log.exception(
                        "continuous profile capture failed (%d total)",
                        self.errors,
                    )
            finally:
                PROFILE_GUARD.release()

    def stats(self) -> dict:
        return {
            "interval_s": self.interval_s,
            "seconds": self.seconds,
            "keep": self.keep,
            "captures": self.captures,
            "skipped": self.skipped,
            "errors": self.errors,
            "last": self.last,
        }
