"""The capped sync tick's two selectors flag the same groups.

parallel/ici.py make_sync_step merges, on a capped tick, only the groups
its selector finds active: content that differs between replicas, hits
pending, or an entry that has expired. The fused layout fingerprints its
groups itself, from its lines as they lie (ops/fused.py group_signals);
the wide layout, the reference, is walked leaf by leaf
(ici._leaf_signals). Both must find what was planted and nothing else,
at every way count and at geometries where a line of the fused table
holds a fraction of a group.
"""

import numpy as np
import pytest

import jax.numpy as jnp

from gubernator_tpu.ops import fused
from gubernator_tpu.ops.layout import SlotTable
from gubernator_tpu.parallel import ici

NOW = 1_753_700_000_000
NDEV = 4


def _random_wide(rng, n: int) -> dict:
    """A table of `n` live slots as numpy columns."""
    i64 = lambda hi: rng.integers(0, hi, n, dtype=np.int64)  # noqa: E731
    return dict(
        key_hi=rng.integers(-(1 << 62), 1 << 62, n, dtype=np.int64),
        key_lo=rng.integers(-(1 << 62), 1 << 62, n, dtype=np.int64),
        used=rng.random(n) < 0.7,
        algo=rng.integers(0, 2, n).astype(np.int8),
        status=rng.integers(0, 2, n).astype(np.int8),
        limit=i64(1 << 40),
        duration=i64(1 << 40),
        remaining=i64(1 << 50),
        stamp=NOW - i64(10_000),
        expire_at=NOW + 1 + i64(1 << 33),  # live; a few beyond 2**32 ms
        invalid_at=np.zeros(n, np.int64),
        burst=i64(1 << 30),
        lru=NOW - i64(10_000),
    )


def _flags(signals, tables, pendings, ways):
    """Groups the tick would take, as `make_sync_step` decides it on
    each chip: a psum of the fingerprints against the chip's own."""
    got = [signals(t, p, jnp.int64(NOW), ways) for t, p in zip(tables, pendings)]
    fps = np.stack([np.asarray(f) for f, _, _ in got])
    total = fps.sum(axis=0, dtype=fps.dtype)
    diverged = [(total != fps[d] * NDEV).any(axis=0) for d in range(NDEV)]
    for d in diverged[1:]:
        np.testing.assert_array_equal(d, diverged[0])
    has_pend = np.stack([np.asarray(h) for _, h, _ in got]).any(axis=0)
    expired = np.stack([np.asarray(e) for _, _, e in got])
    return diverged[0], has_pend, expired


# ways 16 and 3: a line of eight slots holds half a group, or two and
# two thirds. 25 groups: a line holds 1, 2, 4 or 8 slots as the way
# count goes; 32: a table of whole 128-slot rows, as a daemon's is.
@pytest.mark.parametrize(
    "ways,groups",
    [(w, 25) for w in (1, 2, 4, 8, 16, 3)] + [(1, 128), (4, 32), (16, 32)],
)
def test_fused_and_leaf_selectors_flag_the_same_groups(ways, groups):
    rng = np.random.default_rng(ways)
    n = groups * ways
    base = _random_wide(rng, n)
    base["used"][2 * ways] = True  # the planted slots are live ones
    replicas = [{k: v.copy() for k, v in base.items()} for _ in range(NDEV)]
    pendings = [np.zeros((2, n), np.uint32) for _ in range(NDEV)]

    def slot(group, way=0):
        return group * ways + way

    # 1: one replica has counted a hit the others have not
    replicas[1]["remaining"][slot(1)] -= 1
    # 2: the same content everywhere, expired (used, so it would be erased)
    for r in replicas:
        r["expire_at"][slot(2)] = NOW - 1
    # 3: hits pending on one replica, content the same; the high word alone
    pendings[2][0, slot(3, ways - 1)] = 5
    pendings[3][1, slot(4)] = 1
    # 5: the same keys at other ways on one replica
    if ways > 1:
        for col in replicas[3].values():
            col[slot(5):slot(6)] = np.roll(col[slot(5):slot(6)], 1)
    # 6: only the used bit differs; 7: only a high word differs
    replicas[0]["used"][slot(6)] ^= True
    replicas[2]["limit"][slot(7)] ^= 1 << 40
    # 8: an unused slot with an old expiry is not an expired entry
    for r in replicas:
        r["used"][slot(8):slot(9)] = False
        r["expire_at"][slot(8)] = NOW - 5
    # 9: expired by the high word alone, low word above now's
    for r in replicas:
        r["used"][slot(9)] = True
        r["expire_at"][slot(9)] = ((NOW >> 32) - 1 << 32) | 0xFFFFFFFF
    want_diverged = {1, 6, 7} | ({5} if ways > 1 else set())
    want_pend = {3, 4}
    want_expired = {2, 9}

    wide = [SlotTable(**{k: jnp.asarray(v) for k, v in r.items()}) for r in replicas]
    pend = [jnp.asarray(p) for p in pendings]
    packed = [fused.pack_table(t) for t in wide]
    for name, signals, tables in (
        ("fused", fused.group_signals, packed),
        ("leaf", ici._leaf_signals, wide),
    ):
        diverged, has_pend, expired = _flags(signals, tables, pend, ways)
        # The leaf walk fingerprints `pending` too; the fused one leaves that
        # to the flag, which holds wherever any of it is set.
        assert set(np.flatnonzero(diverged & ~has_pend)) == want_diverged, name
        assert set(np.flatnonzero(has_pend)) == want_pend, name
        for d in range(NDEV):
            assert set(np.flatnonzero(expired[d])) == want_expired, (name, d)


def test_block_widths_follow_the_cap():
    assert ici._block_widths(65536) == (1024, 8192, 65536)
    assert ici._block_widths(64) == (1, 8, 64)
    assert ici._block_widths(2) == (1, 2)
    assert ici._block_widths(1) == (1,)
