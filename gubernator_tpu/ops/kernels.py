"""Kernel facade over the two table layouts (EngineConfig.layout).

- "fused": ONE tensor of 32-bit words, one gather + one scatter of the
  lanes' slots (ops/fused.py): a program's cost follows its lanes, not
  the table. What every daemon serves from; no option chooses it.
- "wide": one int64 column per field (ops/layout.py + ops/decide.py),
  the plain reference. Tests build wide engines to compare against, and
  snapshots are ALWAYS exchanged in the wide format (to_wide/from_wide).

Both are bit-exact against the oracle (tests/test_kernel_fuzz.py runs
the whole differential suite on each).
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

# The registry every layout-selection surface validates against
# (EngineConfig.layout, IciEngineConfig.layout, the kernel fuzz suite).
LAYOUTS = ("wide", "fused")

# Resident bytes per table slot, by layout (engine table-size gates,
# e.g. the bucket-warmer's scratch-copy budget; see each layout module
# for the field-by-field accounting).
BYTES_PER_SLOT = {"wide": 83, "fused": 80}

from gubernator_tpu.ops.decide import (
    gather_rows as _wgr,
    probe_exists as _wpe,
)
from gubernator_tpu.ops.inject import inject as _wi
from gubernator_tpu.ops.layout import SlotTable, packed_waves


class Kernels(NamedTuple):
    layout: str
    create: object  # (num_groups, ways) -> table
    inject: object  # (table, items, now, ways) -> (table, ehi, elo)
    # The Store's two other programs exchange packed device arrays as the
    # decide does. probe_exists reads the wave's uploaded operand;
    # gather_rows takes (B,) slots, or with from_output the vector the
    # `with_store` decide just produced (its OUT_SLOT row, on the
    # device), and returns ONE (NCOLS, B) int64 array that
    # ops/layout.py wide_rows views as the wide struct on the host.
    probe_exists: object  # (table, operand, ways) -> bool[B]
    gather_rows: object  # (table, slots, from_output=False) -> packed rows
    to_wide: object  # table -> SlotTable
    from_wide: object  # SlotTable -> table
    bytes_per_slot: int = 83  # resident table bytes per slot
    # What an engine launches: (table, operand, ways, with_store) ->
    # (table, output). One uploaded operand in, one array out, for one
    # wave or a stacked run of them (ops/layout.py WaveOperand /
    # packed_waves / split_output).
    decide_packed: object = None


_WIDE = Kernels(
    layout="wide",
    create=SlotTable.create,
    inject=lambda table, items, now, ways: _wi(table, items, now, ways=ways),
    probe_exists=lambda table, operand, ways: _wpe(table, operand, ways=ways),
    gather_rows=_wgr,
    to_wide=lambda t: t,
    from_wide=lambda t: t,
    bytes_per_slot=BYTES_PER_SLOT["wide"],
)


def _fused():
    from gubernator_tpu.ops import fused as _f

    return Kernels(
        layout="fused",
        create=_f.FusedTable.create,
        inject=lambda table, items, now, ways: _f.inject_fused(
            table, items, now, ways=ways
        ),
        probe_exists=lambda table, operand, ways: (
            _f.probe_exists_fused(table, operand, ways=ways)
        ),
        gather_rows=_f.gather_rows_fused,
        to_wide=_f.unpack_table,
        from_wide=_f.pack_table,
        bytes_per_slot=BYTES_PER_SLOT["fused"],
    )


def get_kernels(layout: str) -> Kernels:
    if layout == "wide":
        base = _WIDE
    elif layout == "fused":
        base = _fused()
    else:
        raise ValueError(f"unknown table layout: {layout!r}")
    return base._replace(decide_packed=packed_decide(layout))


@functools.lru_cache(maxsize=None)
def _packed_program(layout: str):
    """The jitted packed entry of `layout`, one per process (the jit
    cache lives on it): unpack the operand, run the layout's raw decide
    (once a wave of a stacked operand: ops/layout.py packed_waves), pack
    the output. Named after the layout so a profile shows the program
    under the name it always had (`jit_decide_fused`), one wave or a
    run of them: the operand's rank picks the variant."""

    def entry(table, operand, ways, with_store):
        decide = get_raw_kernels(layout).decide
        return packed_waves(
            lambda t, batch, now: decide(t, batch, now, ways),
            table, operand, with_store,
        )

    entry.__name__ = entry.__qualname__ = f"decide_{layout}"
    return jax.jit(
        entry,
        static_argnames=("ways", "with_store"),
        donate_argnums=(0,),
    )


def packed_decide(layout: str):
    """(table, operand, ways, with_store=False) -> (table, output): the
    launch of one wave, or of a run of waves, whose only operand beside
    the table is the uploaded (OPERAND_ROWS, B) or (W, OPERAND_ROWS, B)
    int64 array; the output is one vector, or one a wave."""
    program = _packed_program(layout)

    def decide_packed(table, operand, ways, with_store=False):
        return program(
            table, operand, ways=ways, with_store=bool(with_store)
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
    probe_exists: object  # (table, batch, now, ways) -> bool[B]
    # (table, slots) -> the (NCOLS, B) packed columns of in-range slots
    # (ops/layout.py gathered_rows is the jitted kernel sets' use of it;
    # parallel/mesh.py runs it on a shard's slice)
    gather_cols: object
    to_wide: object  # table -> SlotTable (traceable)
    from_wide: object  # SlotTable -> table (traceable)
    # The sync tick's compaction and selection (parallel/ici.py),
    # layout-native: the table of groups `gids` (C,) alone, `ways` slots
    # each (an index past the end reads the last slots); `table` with
    # such a table written back at those groups (an index past the end
    # writes nothing); (table, pending words, now, ways) -> the groups'
    # content fingerprints, pending flags and expiry flags
    # (ops/fused.py group_signals). The defaults index per-slot leaves
    # and are wide's (no selector: the tick walks its leaves); fused has
    # its own, over lines. These are the only two.
    take_groups: object = lambda t, gids, ways: jax.tree.map(
        lambda a: jnp.take(a, _group_slots(gids, ways), axis=0, mode="clip"),
        t,
    )
    put_groups: object = lambda t, gids, ways, part: jax.tree.map(
        lambda full, p: full.at[_group_slots(gids, ways)].set(p, mode="drop"),
        t, part,
    )
    group_signals: object = None


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
        from gubernator_tpu.ops.decide import (
            _decide_impl,
            _gather_cols,
            _probe_exists_impl,
        )
        from gubernator_tpu.ops.inject import _inject_impl

        return RawKernels(
            layout="wide",
            create=SlotTable.create,
            decide=lambda t, b, now, ways: _decide_impl(t, b, now, ways=ways),
            inject=lambda t, i, now, ways: _inject_impl(t, i, now, ways=ways),
            probe_exists=_probe_exists_impl,
            gather_cols=_gather_cols,
            to_wide=lambda t: t,
            from_wide=lambda t: t,
        )
    if layout == "fused":
        from gubernator_tpu.ops import fused as _f

        return RawKernels(
            layout="fused",
            create=_f.FusedTable.create,
            decide=lambda t, b, now, ways: _f._decide_fused_impl(
                t, b, now, ways=ways
            ),
            inject=lambda t, i, now, ways: _f._inject_fused_impl(
                t, i, now, ways
            ),
            probe_exists=_f._probe_exists_fused_impl,
            gather_cols=_f._gather_cols,
            to_wide=_f.unpack_table,
            from_wide=_f.pack_table,
            take_groups=_f.take_groups,
            put_groups=_f.put_groups,
            group_signals=_f.group_signals,
        )
    raise ValueError(f"unknown table layout: {layout!r}")
