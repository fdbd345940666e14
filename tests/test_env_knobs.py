"""GL004 satellite regression tests: env knobs that used to latch at
import time must honor variables set AFTER import (the daemon's
--config file is injected into os.environ long after these modules
load)."""

from types import SimpleNamespace

import pytest

from gubernator_tpu.api import keys
from gubernator_tpu.service import fastpath


class _ColumnarEngine:
    def check_columns(self, *a, **k):  # pragma: no cover - eligibility only
        raise NotImplementedError


@pytest.fixture
def fast_svc(monkeypatch):
    monkeypatch.setattr(fastpath.wire, "available", lambda: True)
    return SimpleNamespace(fast_edge=True, engine=_ColumnarEngine())


def test_fast_edge_disable_set_after_import(fast_svc, monkeypatch):
    monkeypatch.delenv("GUBER_DISABLE_FAST_EDGE", raising=False)
    assert fastpath.enabled(fast_svc)
    # the regression: with the old import-time _DISABLED global this
    # set would have been invisible
    monkeypatch.setenv("GUBER_DISABLE_FAST_EDGE", "1")
    assert not fastpath.enabled(fast_svc)
    monkeypatch.setenv("GUBER_DISABLE_FAST_EDGE", "true")
    assert not fastpath.enabled(fast_svc)
    # and it is flippable live (per-call read), e.g. for triage
    monkeypatch.setenv("GUBER_DISABLE_FAST_EDGE", "0")
    assert fastpath.enabled(fast_svc)


def test_native_hash_disable_set_after_import(monkeypatch):
    keys._reset_native_for_tests()
    try:
        monkeypatch.setenv("GUBER_DISABLE_NATIVE_HASH", "1")
        # decided on first use — the post-import set is honored
        assert keys.native_enabled() is False
        h = keys.key_hash128("latch-test-key")
        assert h != (0, 0)
    finally:
        keys._reset_native_for_tests()


def test_native_hash_decision_latches_until_reset(monkeypatch):
    keys._reset_native_for_tests()
    try:
        monkeypatch.setenv("GUBER_DISABLE_NATIVE_HASH", "1")
        assert keys.native_enabled() is False
        # flipping the env mid-process must NOT flip the hasher: Murmur
        # and xxh3 digests differ, so live keys' table identities would
        # split. The first-use decision is latched.
        monkeypatch.delenv("GUBER_DISABLE_NATIVE_HASH")
        assert keys.native_enabled() is False
    finally:
        keys._reset_native_for_tests()


def test_hashing_consistent_within_a_latch(monkeypatch):
    keys._reset_native_for_tests()
    try:
        monkeypatch.setenv("GUBER_DISABLE_NATIVE_HASH", "1")
        one = keys.key_hash128("stable-key")
        two = keys.key_hash128("stable-key")
        assert one == two
        hi, lo, grp = keys.key_hash128_batch(["stable-key"], 8)
        assert (int(hi[0]), int(lo[0])) == one
        assert int(grp[0]) == keys.group_of(one[1], 8)
    finally:
        keys._reset_native_for_tests()


# ---------------------------------------------------------------------------
# One serving layout, one backend: the knobs that chose among the others
# are retired, and a start that still sets one to something else is
# refused by name rather than served from a different program.

import pathlib  # noqa: E402
import re  # noqa: E402

from gubernator_tpu import metrics as metrics_mod  # noqa: E402
from gubernator_tpu.service import envconfig  # noqa: E402

PACKAGE = pathlib.Path(envconfig.__file__).resolve().parents[1]

# name -> (its only value left, something an operator may still have set)
RETIRED_KNOBS = {
    "GUBER_KERNEL": ("xla", "pallas"),
    "GUBER_TABLE_LAYOUT": ("fused", "narrow"),
    "GUBER_ICI_LAYOUT": ("fused", "wide"),
    "GUBER_PALLAS_BLOCK": ("", "256"),
    "GUBER_PALLAS_INTERPRET": ("", "1"),
    "GUBER_PALLAS_TUNE": ("", "0"),
    "GUBER_PALLAS_TUNE_CACHE": ("", "/tmp/pallas_tune.json"),
}
RETIRED_FAMILIES = {
    "gubernator_kernel_backend",
    "gubernator_pallas_block_lanes",
    "gubernator_pallas_tune_cache_hits",
}


def test_layouts_are_wide_and_fused():
    from gubernator_tpu.ops.kernels import BYTES_PER_SLOT, LAYOUTS

    assert LAYOUTS == ("wide", "fused")
    assert set(BYTES_PER_SLOT) == set(LAYOUTS)


def test_envconfig_retires_exactly_the_seven():
    assert {
        "GUBER_" + name: only for name, only in envconfig._RETIRED
    } == {name: only for name, (only, _) in RETIRED_KNOBS.items()}


@pytest.mark.parametrize("name", sorted(RETIRED_KNOBS))
def test_retired_knob_refused_off_its_only_value(name, monkeypatch):
    only, stale = RETIRED_KNOBS[name]
    for other in RETIRED_KNOBS:
        monkeypatch.delenv(other, raising=False)
    envconfig.setup_daemon_config()  # unset: starts
    monkeypatch.setenv(name, only.upper())  # on its only value: starts
    envconfig.setup_daemon_config()
    monkeypatch.setenv(name, stale)
    with pytest.raises(ValueError, match=re.escape(f"{name}={stale}")):
        envconfig.setup_daemon_config()


def test_metrics_expose_no_retired_family():
    assert not RETIRED_FAMILIES & metrics_mod.catalog_names()
    text = metrics_mod.Metrics().render().decode()
    assert "pallas" not in text and "kernel_backend" not in text


def test_package_reads_no_retired_name_and_imports_no_pallas():
    """No file of the package spells a retired knob (envconfig composes
    the names it refuses from their suffixes) or imports Pallas."""
    retired = re.compile(
        "|".join(sorted(RETIRED_KNOBS)) + r"|jax\.experimental\.pallas"
        r"|from jax\.experimental import pallas"
    )
    hits = [
        f"{path.relative_to(PACKAGE)}:{i}: {line.strip()}"
        for path in sorted(PACKAGE.rglob("*.py"))
        for i, line in enumerate(path.read_text().splitlines(), 1)
        if retired.search(line)
    ]
    assert hits == []
