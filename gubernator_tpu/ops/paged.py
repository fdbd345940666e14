"""Paged slot-table addressing: device-resident indirection over any layout.

The flat table makes capacity a boot-time bet — every logical group owns
HBM whether or not its keys are warm. This module carves the PHYSICAL
table into fixed-size pages of `groups_per_page` contiguous groups and
routes every kernel through a device-resident page map:

    logical group g
      -> logical page   lp = g // groups_per_page
      -> physical page  pp = page_map[lp]        (ONE extra gather)
      -> physical group pp * groups_per_page + (g % groups_per_page)

Everything downstream of the translation is the UNMODIFIED layout kernel
(ops/kernels.py registry): every decide/inject/probe impl derives slot
indices exclusively from the batch's `group` field (`grp_base = group *
ways`), so translating the batch — not the kernel — keeps the paged path
bit-exact with the flat table for resident pages on both layouts
(pinned by tests/test_kernel_fuzz.py's paged differential suite).

Non-resident pages map to -1; translation sends those lanes to the
sentinel physical group `num_phys_pages * groups_per_page`, one past the
end of the physical table. That is safe by construction:

- gathers clamp to the last physical slot, and a clamped row can never
  spuriously match the probed key: a key's group is a pure function of
  its hash, so an equal (key_hi, key_lo) would live on the SAME
  (non-resident) logical page, never in a resident slot;
- scatters use the layouts' `idx = where(active, slot, n)` +
  `.at[idx].set(..., mode="drop")` discipline, so sentinel lanes write
  nothing.

The runtime pager (runtime/pager.py) promotes touched pages BEFORE
dispatching a wave, so sentinel lanes never carry live traffic; the
sentinel exists so a race or bug degrades to a dropped write, not
corruption of an unrelated page.

Page migration is POSITIONAL, not probe-based: `extract_page` gathers
the page's slot range as wide (SlotTable) rows and `write_page` packs
them back with `lax.dynamic_update_slice` at the new physical offset.
Way order and LRU stamps survive byte-for-byte, so demote -> promote is
an identity on table state (acceptance: zero-loss round trip). Every
layout keeps slot order along axis 0 of every pytree leaf, a whole
number of slots a row (one, or the fused layout's line of eight), which
is what lets the page ops be one generic `jax.tree.map` over the native
table (`zero_region`, `write_region`).
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from gubernator_tpu.ops.kernels import (
    BYTES_PER_SLOT,
    get_kernels,
    get_raw_kernels,
)
from gubernator_tpu.ops.layout import (
    SlotTable,
    packed_waves,
    probed_waves,
    wide_rows,
)


class PagedTable(NamedTuple):
    """The paged table pytree the engine holds in place of a flat table.

    data:     the inner layout's native table, sized to the PHYSICAL
              group count (num_phys_pages * groups_per_page groups).
    page_map: (num_logical_pages,) int32 — physical page index per
              logical page, -1 when the page is demoted/never-bound.
    """

    data: object
    page_map: jnp.ndarray

    # Wide-compatible host views over the PHYSICAL table, mirroring the
    # layout tables' own properties (live_count, key pruning, recovery
    # probes) — engine host-side sites read `table.used`/`key_hi`
    # without knowing whether the table is paged.
    @property
    def used(self) -> jnp.ndarray:
        return self.data.used

    @property
    def key_hi(self) -> jnp.ndarray:
        return self.data.key_hi

    @property
    def key_lo(self) -> jnp.ndarray:
        return self.data.key_lo

    @property
    def num_slots(self) -> int:
        return self.data.num_slots


class PagedKernels(NamedTuple):
    """Kernels-compatible facade (same field names/signatures as
    ops.kernels.Kernels where they overlap, so engine call sites don't
    fork) plus the page-management ops and geometry the runtime pager
    needs. `from_wide` intentionally raises: a paged table cannot be
    rebuilt from one flat wide image without placement decisions — the
    engine's paged restore path goes through `write_page`."""

    layout: str
    create: object  # () -> PagedTable (empty map, zeroed physical table)
    # (pt, operand, ways, with_store) -> (pt, output vector): what an
    # engine launches (ops/kernels.py Kernels.decide_packed)
    decide_packed: object
    inject: object  # (pt, items, now, ways) -> (pt, ehi, elo)
    probe_exists: object  # (pt, operand, ways) -> bool[B]
    # (pt, PHYSICAL slots, from_output=False) -> packed rows (the paged
    # decide's slot output is physical already)
    gather_rows: object
    to_wide: object  # pt -> SlotTable view of the PHYSICAL table
    from_wide: object  # raises NotImplementedError
    bytes_per_slot: int
    # --- page ops (all donate the PagedTable) ---
    bind_page: object  # (pt, lp, pp) -> pt: zero phys page, map lp->pp
    unbind_page: object  # (pt, lp, pp) -> pt: zero phys page, map lp->-1
    extract_page: object  # (pt, pp) -> SlotTable rows (page_slots,)
    write_page: object  # (pt, lp, pp, wide_rows) -> pt (positional)
    # --- geometry ---
    ways: int
    groups_per_page: int
    page_slots: int  # groups_per_page * ways
    num_phys_pages: int
    num_logical_pages: int
    num_logical_groups: int


def _leaf_start(leaf, num_slots: int, start, slots: int):
    """Start indices, along every axis of `leaf`, of the `slots` slots
    from slot `start`. A leaf keeps slot order along its first axis, a
    whole number of slots a row: one, or the fused layout's line of
    eight."""
    per = num_slots // leaf.shape[0]
    if slots % per:
        raise ValueError(
            f"a page of {slots} slots does not divide into rows of {per} slots"
        )
    z = jnp.asarray(0, dtype=jnp.int32)
    return (jnp.asarray(start // per, dtype=jnp.int32),) + (z,) * (
        leaf.ndim - 1
    )


def zero_region(data, start, slots: int):
    """`data` (a layout-native table) with the `slots` slots from slot
    `start` zeroed, leaf by leaf."""
    n = data.num_slots

    def z(leaf):
        rows = slots * leaf.shape[0] // n
        blk = jnp.zeros((rows,) + leaf.shape[1:], dtype=leaf.dtype)
        return jax.lax.dynamic_update_slice(
            leaf, blk, _leaf_start(leaf, n, start, slots)
        )

    return jax.tree.map(z, data)


def write_region(data, rows, start):
    """`data` with the layout-native table `rows` written over the slots
    from slot `start` (positional: way order and LRU stamps survive)."""
    n = data.num_slots

    def upd(leaf, r):
        r = r.astype(leaf.dtype).reshape((-1,) + leaf.shape[1:])
        return jax.lax.dynamic_update_slice(
            leaf, r, _leaf_start(leaf, n, start, rows.num_slots)
        )

    return jax.tree.map(upd, data, rows)


def logical_page_of(group: int, groups_per_page: int) -> int:
    """Host-side logical-page index for one group (pager bookkeeping)."""
    return group // groups_per_page


def make_paged_kernels(
    layout: str,
    num_groups: int,
    ways: int,
    groups_per_page: int,
    num_phys_pages: int,
) -> PagedKernels:
    """Build the paged kernel set for `layout` with a fixed geometry.

    num_groups:      LOGICAL group count (the keyspace the engine hashes
                     into — unchanged from the flat table).
    groups_per_page: page granularity; the last logical page may be
                     partially used when num_groups isn't a multiple.
    num_phys_pages:  resident-page budget — the HBM footprint is
                     num_phys_pages * groups_per_page * ways slots.
    """
    if groups_per_page <= 0:
        raise ValueError(f"groups_per_page must be > 0: {groups_per_page}")
    if num_phys_pages <= 0:
        raise ValueError(f"num_phys_pages must be > 0: {num_phys_pages}")
    base = get_kernels(layout)
    raw = get_raw_kernels(layout)
    gpp = groups_per_page
    page_slots = gpp * ways
    num_logical_pages = -(-num_groups // gpp)  # ceil
    num_phys_groups = num_phys_pages * gpp
    sentinel = jnp.int32(num_phys_groups)

    def _xlate(page_map, group):
        """Logical -> physical group: the one extra gather of the paged
        probe path. Non-resident lanes -> sentinel (out of range)."""
        g = group.astype(jnp.int32)
        pp = page_map[g // gpp]
        phys = jnp.where(pp >= 0, pp * gpp + g % gpp, sentinel)
        return phys.astype(group.dtype)

    def _raw_decide(pt, batch, now):
        b = batch._replace(group=_xlate(pt.page_map, batch.group))
        data, out = raw.decide(pt.data, b, now, ways)
        return PagedTable(data, pt.page_map), out

    @functools.partial(
        jax.jit, static_argnames=("with_store",), donate_argnums=(0,)
    )
    def _decide_packed(pt, operand, with_store):
        return packed_waves(_raw_decide, pt, operand, with_store)

    @functools.partial(jax.jit, donate_argnums=(0,))
    def _inject(pt, items, now):
        i = items._replace(group=_xlate(pt.page_map, items.group))
        data, ehi, elo = raw.inject(pt.data, i, now, ways)
        return PagedTable(data, pt.page_map), ehi, elo

    @jax.jit
    def _probe_exists(pt, operand):
        def probe(batch, now):
            b = batch._replace(group=_xlate(pt.page_map, batch.group))
            return raw.probe_exists(pt.data, b, now, ways)

        return probed_waves(probe, operand)

    @functools.partial(jax.jit, donate_argnums=(0,))
    def _bind_page(pt, lp, pp):
        data = zero_region(pt.data, pp * page_slots, page_slots)
        return PagedTable(data, pt.page_map.at[lp].set(pp))

    @functools.partial(jax.jit, donate_argnums=(0,))
    def _unbind_page(pt, lp, pp):
        # Zero the evacuated frame too: census and key-string pruning
        # scan the PHYSICAL table and must not see ghost rows.
        data = zero_region(pt.data, pp * page_slots, page_slots)
        return PagedTable(data, pt.page_map.at[lp].set(jnp.int32(-1)))

    @jax.jit
    def _extract_page(pt, pp):
        slots = pp * page_slots + jnp.arange(page_slots, dtype=jnp.int64)
        return wide_rows(base.gather_rows(pt.data, slots))

    @functools.partial(jax.jit, donate_argnums=(0,))
    def _write_page(pt, lp, pp, rows_wide):
        rows = raw.from_wide(SlotTable(*rows_wide))
        data = write_region(pt.data, rows, pp * page_slots)
        return PagedTable(data, pt.page_map.at[lp].set(pp))

    def _create(*_a, **_k) -> PagedTable:
        return PagedTable(
            data=base.create(num_phys_groups, ways),
            page_map=jnp.full((num_logical_pages,), -1, dtype=jnp.int32),
        )

    def _from_wide(_t):
        raise NotImplementedError(
            "paged tables restore page-by-page (write_page), not from one "
            "flat wide image — see DeviceEngine.restore's paged path"
        )

    return PagedKernels(
        layout=layout,
        create=_create,
        decide_packed=lambda t, op, ways_=ways, with_store=False: (
            _decide_packed(t, op, with_store=bool(with_store))
        ),
        inject=lambda t, i, now, ways_=ways: _inject(t, i, now),
        probe_exists=lambda t, operand, ways_=ways: _probe_exists(t, operand),
        gather_rows=lambda t, slots, from_output=False: base.gather_rows(
            t.data, slots, from_output
        ),
        to_wide=lambda t: base.to_wide(t.data),
        from_wide=_from_wide,
        bytes_per_slot=BYTES_PER_SLOT[layout],
        bind_page=_bind_page,
        unbind_page=_unbind_page,
        extract_page=_extract_page,
        write_page=_write_page,
        ways=ways,
        groups_per_page=gpp,
        page_slots=page_slots,
        num_phys_pages=num_phys_pages,
        num_logical_pages=num_logical_pages,
        num_logical_groups=num_groups,
    )
