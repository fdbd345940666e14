"""chip_smoke.py rehearsed on CPU, and the pieces it leans on: the
compile cache's one placement rule, native libraries keyed by their
source, and one table per device in the in-process cluster."""

import json
import logging
import os
import shutil
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = [sys.executable, os.path.join(REPO, "chip_smoke.py"), "--keys", "2000"]


# ---- chip_smoke.py ----------------------------------------------------------


@pytest.mark.deadline(300)
def test_chip_smoke_cpu_rehearsal():
    p = subprocess.run(
        SMOKE + ["--platform", "cpu"], cwd=REPO, capture_output=True,
        text=True, timeout=280,
    )
    assert p.returncode == 0, p.stdout[-2000:] + p.stderr[-2000:]
    last = json.loads(p.stdout.strip().splitlines()[-1])
    assert last == {
        "ok": True,
        "device": {"platform": "cpu", "kind": "cpu", "count": 1},
    }
    assert "keys_loaded=2000" in p.stdout and "mismatches=0" in p.stdout


@pytest.mark.deadline(300)
def test_chip_smoke_refuses_without_a_chip():
    """The same command without --platform cpu: JAX here initialises the
    CPU backend, and the smoke must say no rather than pass on it."""
    p = subprocess.run(
        SMOKE, cwd=REPO, capture_output=True, text=True, timeout=280
    )
    assert p.returncode != 0
    assert '"ok"' not in p.stdout
    assert "expected 'tpu'" in p.stderr


# ---- utils/compilecache.py --------------------------------------------------

_CACHE_PROBE = """
import json, jax
set_keys = []
real = jax.config.update
jax.config.update = lambda k, v: (set_keys.append(k), real(k, v))[1]
from gubernator_tpu.utils import compilecache
got = compilecache.enable_compile_cache()
print(json.dumps({
    "returned": got, "set_keys": set_keys,
    "config": jax.config.jax_compilation_cache_dir,
    "stats_path": compilecache.cache_stats()["path"],
}))
"""


def _probe_cache(**env_changes) -> dict:
    env = {
        k: v for k, v in os.environ.items()
        if k not in ("JAX_PLATFORMS", "JAX_COMPILATION_CACHE_DIR")
    }
    env.update(env_changes)
    p = subprocess.run(
        [sys.executable, "-c", _CACHE_PROBE], cwd=REPO, env=env,
        capture_output=True, text=True, timeout=120,
    )
    assert p.returncode == 0, p.stderr[-2000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


def test_compile_cache_env_dir_is_left_to_jax(tmp_path):
    want = str(tmp_path / "xla")
    got = _probe_cache(JAX_COMPILATION_CACHE_DIR=want, JAX_PLATFORMS="cpu")
    assert "jax_compilation_cache_dir" not in got["set_keys"]
    assert got["returned"] == got["config"] == got["stats_path"] == want


def test_compile_cache_default_dir_is_the_checkout():
    # Platform unpinned — the chip host's case — from two processes.
    a, b = _probe_cache(), _probe_cache()
    want = os.path.join(REPO, ".jax_cache")
    assert a["returned"] == b["returned"] == want
    assert a["config"] == a["stats_path"] == want


def test_compile_cache_cpu_pinned_stays_uncached():
    got = _probe_cache(JAX_PLATFORMS="cpu")
    assert got["returned"] is None and got["stats_path"] is None
    assert not [k for k in got["set_keys"] if "cache" in k]


# ---- native libraries keyed by their source ---------------------------------


def _reset(monkeypatch, mod, src):
    monkeypatch.setattr(mod, "_SRC", str(src))
    monkeypatch.setattr(mod, "_lib", None)
    monkeypatch.setattr(mod, "_tried", False)
    monkeypatch.setattr(mod, "unavailable_reason", "")


def test_native_library_follows_its_source(tmp_path, monkeypatch):
    from gubernator_tpu import native
    from gubernator_tpu.utils import nativebuild

    src = tmp_path / "guberhash.cc"
    shutil.copy(native._SRC, src)
    first, why = nativebuild.build_library(str(src))
    assert first and os.path.exists(first), why
    # a stale library beside the source is never loaded: wrong name
    stale = tmp_path / "_guberhash.so"
    stale.write_bytes(b"not a library")
    with open(src, "a") as f:
        f.write("\n// edited\n")
    second, why = nativebuild.build_library(str(src))
    assert second and second != first, why
    assert not [n for n in os.listdir(tmp_path) if n.endswith(".tmp")]
    _reset(monkeypatch, native, src)
    assert native.load()._name == second


def test_unbuildable_native_source_warns_and_hashes_in_python(
    tmp_path, monkeypatch, caplog
):
    import xxhash

    from gubernator_tpu import native
    from gubernator_tpu.api import keys
    from gubernator_tpu.service.daemon import _warn_missing_native

    src = tmp_path / "guberhash.cc"
    src.write_text("this is not C++\n")
    _reset(monkeypatch, native, src)
    keys._reset_native_for_tests()
    try:
        with caplog.at_level(logging.WARNING, logger="gubernator.daemon"):
            _warn_missing_native()
        warned = [r for r in caplog.records if "guberhash" in r.getMessage()]
        assert len(warned) == 1 and "g++ failed" in warned[0].getMessage()
        assert not keys.native_enabled()
        d = xxhash.xxh3_128_intdigest(b"a_b")
        hi, lo = keys.key_hash128("a_b")
        assert (hi & (2**64 - 1), lo & (2**64 - 1)) == (
            d >> 64, d & (2**64 - 1)
        )
    finally:
        keys._reset_native_for_tests()


# ---- one table per device ---------------------------------------------------


@pytest.mark.deadline(120)
def test_cluster_puts_each_table_on_its_own_device(loop_thread):
    import jax

    from gubernator_tpu.api.types import RateLimitReq
    from gubernator_tpu.cluster import Cluster

    c = loop_thread.run(Cluster.start(4), timeout=100)
    try:
        for d in c.daemons:  # serve once: dispatch must not move the table
            d.engine.check_batch([RateLimitReq(
                name="n", unique_key="k", hits=1, limit=5, duration=60_000
            )])
        placed = [d.engine.table.data.devices() for d in c.daemons]
        assert placed == [{dev} for dev in jax.devices()[:4]]
        assert all(d.engine.table.data.committed for d in c.daemons)
        assert [d.engine.devices for d in c.daemons] == [
            [dev] for dev in jax.devices()[:4]
        ]
        rows = [
            d.svc.device_debug_info()["memory"]["devices"] for d in c.daemons
        ]
        assert [r[0]["id"] for r in rows] == [0, 1, 2, 3]
    finally:
        loop_thread.run(c.stop(), timeout=60)
