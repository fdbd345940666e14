"""Layout-agnostic kernel facade.

The engine selects a table layout by name (EngineConfig.layout):

- "wide": one int64 column per field (ops/layout.py + ops/decide.py) —
  the reference-shaped baseline.
- "packed": narrowed/packed columns with a 3-gather probe (ops/packed.py).
- "fused": ONE tensor of 32-bit words, one gather + one scatter of the
  lanes' slots (ops/fused.py) — the fastest at scale (a program's cost
  follows its lanes, not the table; see ops/fused.py's module docstring)
  and the flagship default.
- "narrow": fused v2 — a split-word (N, 9) tensor (ops/narrow.py)
  ordered so way selection reads only a 5-column row PREFIX (40 B/way,
  half of fused's probe DMA) and the int32-clamped counters bit-pack
  into one word; still exactly one gather + one scatter.

All are bit-exact against the oracle (tests/test_kernel_fuzz.py runs the
whole differential suite per layout). Snapshots are ALWAYS exchanged in
the wide format (to_wide/from_wide), so Loader files are portable across
layouts.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

# The registry every layout-selection surface validates against
# (EngineConfig.layout, GUBER_TABLE_LAYOUT / GUBER_ICI_LAYOUT, bench.py
# --layout, the kernel fuzz suite).
LAYOUTS = ("wide", "packed", "fused", "narrow")

# Resident bytes per table slot, by layout (engine table-size gates,
# e.g. the bucket-warmer's scratch-copy budget; see each layout module
# for the field-by-field accounting).
BYTES_PER_SLOT = {"wide": 83, "packed": 72, "fused": 80, "narrow": 72}

import os

from gubernator_tpu.ops.decide import (
    decide as _wd,
    decide_scan as _wds,
    gather_rows as _wgr,
    probe_exists as _wpe,
)
from gubernator_tpu.ops.inject import inject as _wi
from gubernator_tpu.ops.layout import (
    SlotTable,
    pack_output,
    unpack_operand,
)

# Decide-program backends (GUBER_KERNEL). "xla" is the grown fleet of
# per-layout XLA programs; "pallas" routes the narrow/fused decide hot
# path through the hand-written one-HBM-pass kernel
# (ops/pallas_decide.py) with the XLA path kept as the fallback and the
# bit-exactness oracle. Layouts pallas does not lower (wide/packed — the
# diagnostic layouts) and all non-decide entry points stay on XLA.
KERNEL_BACKENDS = ("xla", "pallas")


def kernel_backend() -> str:
    """Decide-program backend, read from GUBER_KERNEL at registry-build
    time (engine/topology startup — NOT per decide call), so a built
    `Kernels` facade is pinned to one backend and the warmed programs
    are exactly the served programs."""
    v = os.environ.get("GUBER_KERNEL", "xla").strip().lower() or "xla"
    if v not in KERNEL_BACKENDS:
        raise ValueError(
            f"GUBER_KERNEL={v!r}: expected one of {KERNEL_BACKENDS}"
        )
    return v


class Kernels(NamedTuple):
    layout: str
    create: object  # (num_groups, ways) -> table
    decide: object  # (table, batch, now, ways, with_store) -> (table, out)
    decide_scan: object  # (table, batches, nows, ways, with_store)
    inject: object  # (table, items, now, ways) -> (table, ehi, elo)
    probe_exists: object  # (table, hi, lo, group, now, ways) -> bool[B]
    gather_rows: object  # (table, slots) -> SlotTable rows (wide view)
    to_wide: object  # table -> SlotTable
    from_wide: object  # SlotTable -> table
    bytes_per_slot: int = 83  # resident table bytes per slot
    # What an engine launches: (table, operand, ways, with_store) ->
    # (table, output vector). One uploaded operand in, one array out
    # (ops/layout.py WaveOperand / split_output).
    decide_packed: object = None


def _wide_decide(table, batch, now, ways, with_store=False):
    return _wd(table, batch, now, ways=ways)


def _wide_scan(table, batches, nows, ways, with_store=False):
    return _wds(table, batches, nows, ways=ways)


_WIDE = Kernels(
    layout="wide",
    create=SlotTable.create,
    decide=_wide_decide,
    decide_scan=_wide_scan,
    inject=lambda table, items, now, ways: _wi(table, items, now, ways=ways),
    probe_exists=lambda table, hi, lo, group, now, ways: _wpe(
        table, hi, lo, group, now, ways=ways
    ),
    gather_rows=_wgr,
    to_wide=lambda t: t,
    from_wide=lambda t: t,
    bytes_per_slot=BYTES_PER_SLOT["wide"],
)


def _packed():
    from gubernator_tpu.ops import packed as _p

    return Kernels(
        layout="packed",
        create=_p.PackedTable.create,
        decide=lambda table, batch, now, ways, with_store=False: _p.decide_packed(
            table, batch, now, ways=ways
        ),
        decide_scan=lambda table, batches, nows, ways, with_store=False: (
            _p.decide_scan_packed(table, batches, nows, ways=ways)
        ),
        inject=lambda table, items, now, ways: _p.inject_packed(
            table, items, now, ways=ways
        ),
        probe_exists=lambda table, hi, lo, group, now, ways: (
            _p.probe_exists_packed(table, hi, lo, group, now, ways=ways)
        ),
        gather_rows=_p.gather_rows_packed,
        to_wide=_p.unpack_table,
        from_wide=_p.pack_table,
        bytes_per_slot=BYTES_PER_SLOT["packed"],
    )


def _fused():
    from gubernator_tpu.ops import fused as _f

    return Kernels(
        layout="fused",
        create=_f.FusedTable.create,
        decide=lambda table, batch, now, ways, with_store=False: _f.decide_fused(
            table, batch, now, ways=ways
        ),
        decide_scan=lambda table, batches, nows, ways, with_store=False: (
            _f.decide_scan_fused(table, batches, nows, ways=ways)
        ),
        inject=lambda table, items, now, ways: _f.inject_fused(
            table, items, now, ways=ways
        ),
        probe_exists=lambda table, hi, lo, group, now, ways: (
            _f.probe_exists_fused(table, hi, lo, group, now, ways=ways)
        ),
        gather_rows=_f.gather_rows_fused,
        to_wide=_f.unpack_table,
        from_wide=_f.pack_table,
        bytes_per_slot=BYTES_PER_SLOT["fused"],
    )


def _narrow():
    from gubernator_tpu.ops import narrow as _n

    return Kernels(
        layout="narrow",
        create=_n.NarrowTable.create,
        decide=lambda table, batch, now, ways, with_store=False: _n.decide_narrow(
            table, batch, now, ways=ways
        ),
        decide_scan=lambda table, batches, nows, ways, with_store=False: (
            _n.decide_scan_narrow(table, batches, nows, ways=ways)
        ),
        inject=lambda table, items, now, ways: _n.inject_narrow(
            table, items, now, ways=ways
        ),
        probe_exists=lambda table, hi, lo, group, now, ways: (
            _n.probe_exists_narrow(table, hi, lo, group, now, ways=ways)
        ),
        gather_rows=_n.gather_rows_narrow,
        to_wide=_n.unpack_table,
        from_wide=_n.pack_table,
        bytes_per_slot=BYTES_PER_SLOT["narrow"],
    )


def _pallas(layout: str, base: Kernels) -> Kernels:
    """Reroute the decide hot path of `base` through the fused Pallas
    program; every other entry point (inject, probes, snapshots) keeps
    the XLA impls — they are not wave-rate paths."""
    from gubernator_tpu.ops import pallas_decide as _pd

    return base._replace(
        decide=lambda table, batch, now, ways, with_store=False: (
            _pd.decide_flat(table, batch, now, layout=layout, ways=ways)
        ),
        decide_scan=lambda table, batches, nows, ways, with_store=False: (
            _pd.decide_scan_flat(
                table, batches, nows, layout=layout, ways=ways
            )
        ),
    )


def get_kernels(layout: str) -> Kernels:
    if layout == "wide":
        base = _WIDE
    elif layout == "packed":
        base = _packed()
    elif layout == "fused":
        base = _fused()
    elif layout == "narrow":
        base = _narrow()
    else:
        raise ValueError(f"unknown table layout: {layout!r}")
    if layout in ("fused", "narrow") and kernel_backend() == "pallas":
        base = _pallas(layout, base)
    return base._replace(decide_packed=packed_decide(layout))


def program_variant(layout: str, lanes: int, paged: bool = False):
    """What besides shapes selects the decide program: "xla", or the
    Pallas route's lowering and lane tile, which it resolves when it is
    traced. A static argument of the packed entries, so that a change
    of either traces a program of its own."""
    if layout not in ("fused", "narrow") or kernel_backend() != "pallas":
        return "xla"
    from gubernator_tpu.ops import pallas_decide as _pd

    return ("pallas", _pd.pallas_mode(), _pd.choose_block(layout, paged, lanes))


@functools.lru_cache(maxsize=None)
def _packed_program(layout: str):
    """The jitted packed entry of `layout`, one per process (the jit
    cache lives on it): unpack the operand, run the layout's raw decide,
    pack the output. Named after the layout so a profile shows the
    program under the name it always had (`jit_decide_fused`)."""

    def entry(table, operand, ways, with_store, variant):
        batch, _home, now = unpack_operand(operand)
        table, out = get_raw_kernels(layout).decide(table, batch, now, ways)
        return table, pack_output(out, with_store)

    entry.__name__ = entry.__qualname__ = f"decide_{layout}"
    return jax.jit(
        entry,
        static_argnames=("ways", "with_store", "variant"),
        donate_argnums=(0,),
    )


def packed_decide(layout: str):
    """(table, operand, ways, with_store=False) -> (table, output
    vector): the launch of one wave whose only operand beside the table
    is the uploaded (OPERAND_ROWS, B) int64 array."""
    program = _packed_program(layout)

    def decide_packed(table, operand, ways, with_store=False):
        return program(
            table, operand, ways=ways, with_store=bool(with_store),
            variant=program_variant(layout, operand.shape[-1]),
        )

    return decide_packed


def _group_slots(gids, ways: int):
    """Every slot of groups `gids` (C,), group by group: (C * ways,)."""
    return (gids[:, None] * ways + jnp.arange(ways, dtype=gids.dtype)).reshape(-1)


class RawKernels(NamedTuple):
    """UNJITTED impls for composition inside shard_map/pjit (the
    multi-device tier, parallel/mesh.py + parallel/ici.py). The jitted
    `Kernels` wrappers donate buffers and can't be nested inside a
    shard_map body; these are the raw traceable functions.

    `to_wide`/`from_wide` are traceable table<->SlotTable converters the
    sync tick uses so its merge logic stays layout-agnostic while decide
    runs layout-native (VERDICT r4 item 2: the hot path must be fused on
    the multi-device tier too — wide measured 137x slower on TPU)."""

    layout: str
    create: object  # (num_groups, ways) -> table
    decide: object  # (table, batch, now, ways) -> (table, DecideOutput)
    inject: object  # (table, items, now, ways) -> (table, ehi, elo)
    to_wide: object  # table -> SlotTable (traceable)
    from_wide: object  # SlotTable -> table (traceable)
    # The sync tick's compaction and fingerprints (parallel/ici.py),
    # layout-native: the table of groups `gids` (C,) alone, `ways` slots
    # each (an index past the end reads the last slots); `table` with
    # such a table written back at those groups (an index past the end
    # writes nothing); a pytree of per-slot (N, ...) arrays holding the
    # state.
    take_groups: object = lambda t, gids, ways: jax.tree.map(
        lambda a: jnp.take(a, _group_slots(gids, ways), axis=0, mode="clip"),
        t,
    )
    put_groups: object = lambda t, gids, ways, part: jax.tree.map(
        lambda full, p: full.at[_group_slots(gids, ways)].set(p, mode="drop"),
        t, part,
    )
    slot_leaves: object = lambda t: t


def get_census(layout: str, ways: int, **kwargs):
    """Census program for `layout` (ops/census.py): one jitted,
    NON-donating scan per (layout, geometry) returning O(buckets)
    device scalars — the table-observatory entry point, registered
    here alongside the kernel registry so every layout-selection
    surface resolves both from one place. Lazy import: census is a
    scrape-cadence diagnostic, not a serving dependency."""
    if layout not in LAYOUTS:
        raise ValueError(f"unknown table layout: {layout!r}")
    from gubernator_tpu.ops.census import make_census

    return make_census(layout, ways, **kwargs)


def get_admission(layout: str, ways: int, **kwargs):
    """Admission-accounting program for `layout` (ops/admission.py):
    one jitted, NON-donating scan per (layout, geometry) reducing
    per-key admitted-this-window vs. configured limit to O(buckets)
    device scalars — the enforcement-error SLI's ground truth,
    registered here alongside the kernel registry so every
    layout-selection surface resolves both from one place. Lazy
    import: admission accounting is a scrape-cadence diagnostic, not
    a serving dependency."""
    if layout not in LAYOUTS:
        raise ValueError(f"unknown table layout: {layout!r}")
    from gubernator_tpu.ops.admission import make_admission

    return make_admission(layout, ways, **kwargs)


def get_paged_kernels(
    layout: str,
    num_groups: int,
    ways: int,
    groups_per_page: int,
    num_phys_pages: int,
):
    """Paged addressing layer over `layout` (ops/paged.py): the physical
    table shrinks to a resident-page budget and every kernel consults a
    device page map (one extra gather) to translate logical groups.
    Registered here so layout selection and paging compose at the same
    seam the engine already resolves kernels from. Lazy import: flat
    tables never pay for the paged module."""
    if layout not in LAYOUTS:
        raise ValueError(f"unknown table layout: {layout!r}")
    from gubernator_tpu.ops.paged import make_paged_kernels

    return make_paged_kernels(
        layout, num_groups, ways, groups_per_page, num_phys_pages
    )


def get_raw_kernels(layout: str) -> RawKernels:
    if layout == "wide":
        from gubernator_tpu.ops.decide import _decide_impl
        from gubernator_tpu.ops.inject import _inject_impl

        return RawKernels(
            layout="wide",
            create=SlotTable.create,
            decide=lambda t, b, now, ways: _decide_impl(t, b, now, ways=ways),
            inject=lambda t, i, now, ways: _inject_impl(t, i, now, ways=ways),
            to_wide=lambda t: t,
            from_wide=lambda t: t,
        )
    if layout == "packed":
        from gubernator_tpu.ops import packed as _p

        return RawKernels(
            layout="packed",
            create=_p.PackedTable.create,
            decide=lambda t, b, now, ways: _p._decide_packed_impl(
                t, b, now, ways=ways
            ),
            inject=lambda t, i, now, ways: _p._inject_packed_impl(
                t, i, now, ways
            ),
            to_wide=_p.unpack_table,
            from_wide=_p.pack_table,
        )
    if layout == "fused":
        from gubernator_tpu.ops import fused as _f

        raw = RawKernels(
            layout="fused",
            create=_f.FusedTable.create,
            decide=lambda t, b, now, ways: _f._decide_fused_impl(
                t, b, now, ways=ways
            ),
            inject=lambda t, i, now, ways: _f._inject_fused_impl(
                t, i, now, ways
            ),
            to_wide=_f.unpack_table,
            from_wide=_f.pack_table,
            take_groups=_f.take_groups,
            put_groups=_f.put_groups,
            slot_leaves=_f.FusedTable.cols,
        )
    elif layout == "narrow":
        from gubernator_tpu.ops import narrow as _n

        raw = RawKernels(
            layout="narrow",
            create=_n.NarrowTable.create,
            decide=lambda t, b, now, ways: _n._decide_narrow_impl(
                t, b, now, ways=ways
            ),
            inject=lambda t, i, now, ways: _n._inject_narrow_impl(
                t, i, now, ways
            ),
            to_wide=_n.unpack_table,
            from_wide=_n.pack_table,
        )
    else:
        raise ValueError(f"unknown table layout: {layout!r}")
    if kernel_backend() == "pallas":
        # The mesh tier composes RawKernels.decide inside shard_map
        # (parallel/mesh.py local_decide), so routing the raw decide here
        # is what makes IciMeshTopology dispatch the Pallas program PER
        # SHARD: each shard's slice traces its own pallas_call.
        from gubernator_tpu.ops import pallas_decide as _pd

        raw = raw._replace(
            decide=lambda t, b, now, ways: _pd.raw_decide_flat(
                t, b, now, layout=layout, ways=ways
            )
        )
    return raw
