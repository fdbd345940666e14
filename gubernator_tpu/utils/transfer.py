"""Host<->device transfer ledger: the accounted wrapper every
device_put / materialize / snapshot-inject site in runtime/ and
parallel/ rides (guberlint GL010 pins raw jax.device_put calls there
to this module).

Each accounted transfer records (bytes, wall seconds) into the owning
engine's `gubernator_transfer_*` Log2Histograms, labeled by direction
("h2d" | "d2h") and purpose ("serve" | "snapshot" | "inject" |
"warmup" | "census" | "demote" | "promote") — demote/promote are the
paged table's page-migration moves (runtime/pager.py): demote = d2h
page evacuation to the host-DRAM tier, promote = h2d page fill on a
probe against a demoted page.

The serving upload is an accounted record too: a flush uploads its
waves' operands, ONE int64 array a wave (ops/layout.py WaveOperand),
through device_put(..., purpose="serve") before it asks for the engine
lock, one "h2d"/"serve" record a flush. (Until PR 25 the jit call
transferred fourteen host arrays a wave implicitly, under the lock, and
the ledger never saw them.) Its "d2h"/"serve" twin is the flush's
readback: one output vector a wave.

Honesty note on timing: d2h materializations (np.asarray of device
arrays) block until the copy lands, so their latency is the real
transfer + any pending compute it waits on. h2d device_put is ASYNC on
TPU/GPU — its recorded latency is the dispatch cost; the copy itself
overlaps. Bytes are exact either way (buffer nbytes).

Import-light: jax loads lazily inside device_put(); nbytes() walks
numpy/jax arrays and containers without importing either.
"""

from __future__ import annotations

import time

DIRECTIONS = ("h2d", "d2h")
PURPOSES = (
    "serve", "snapshot", "inject", "warmup", "census", "demote", "promote",
)


def nbytes(obj) -> int:
    """Total buffer bytes in a (possibly nested) structure: anything
    with .nbytes counts directly; dicts/lists/tuples (incl. NamedTuple
    pytrees) recurse; scalars and None count 0."""
    nb = getattr(obj, "nbytes", None)
    if nb is not None:
        try:
            return int(nb)
        except TypeError:
            pass  # a property object / lazy proxy: fall through
    if isinstance(obj, dict):
        return sum(nbytes(v) for v in obj.values())
    if isinstance(obj, (list, tuple)):
        return sum(nbytes(v) for v in obj)
    return 0


def record(metrics, direction: str, purpose: str,
           n_bytes: int, seconds: float) -> None:
    """Record one completed transfer against `metrics` (an
    EngineMetrics). A None metrics or one without the transfer families
    (bare stubs in tests) is a silent no-op — accounting must never
    break the transfer it observes."""
    if metrics is None:
        return
    obs = getattr(metrics, "observe_transfer", None)
    if obs is not None:
        obs(direction, purpose, n_bytes, seconds)


class account:
    """Timed accounting scope:

        with transfer.account(metrics, "d2h", "serve") as tx:
            host = materialize(...)
            tx.add(host)

    Records the added bytes + the scope's wall time on clean exit; an
    exceptional exit records nothing (a failed transfer's timing would
    pollute the ledger)."""

    __slots__ = ("_metrics", "_direction", "_purpose", "bytes", "_t0")

    def __init__(self, metrics, direction: str, purpose: str):
        self._metrics = metrics
        self._direction = direction
        self._purpose = purpose
        self.bytes = 0

    def add(self, obj) -> None:
        """Add an int byte count or any nbytes()-measurable structure."""
        self.bytes += obj if isinstance(obj, int) else nbytes(obj)

    def __enter__(self) -> "account":
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, et, ev, tb) -> bool:
        if et is None:
            record(
                self._metrics, self._direction, self._purpose,
                self.bytes, time.perf_counter() - self._t0,
            )
        return False


def device_put(x, sharding=None, *, metrics=None, purpose="warmup"):
    """Accounted jax.device_put — the sanctioned h2d entry point for
    runtime/ and parallel/ (guberlint GL010)."""
    import jax

    t0 = time.perf_counter()
    out = (
        jax.device_put(x, sharding) if sharding is not None
        else jax.device_put(x)
    )
    record(metrics, "h2d", purpose, nbytes(x), time.perf_counter() - t0)
    return out


def put_tree(tree, sharding=None, *, metrics=None, purpose="warmup"):
    """Accounted per-leaf device_put over a pytree: one ledger
    observation for the whole logical transfer (a sharded table is one
    promote-shaped move, not num_fields separate ones)."""
    import jax

    t0 = time.perf_counter()
    if sharding is not None:
        out = jax.tree.map(lambda a: jax.device_put(a, sharding), tree)
    else:
        out = jax.tree.map(jax.device_put, tree)
    record(
        metrics, "h2d", purpose,
        sum(nbytes(leaf) for leaf in jax.tree.leaves(tree)),
        time.perf_counter() - t0,
    )
    return out
