"""Owner-sharded decide over a device mesh.

The TPU-native replacement for peer forwarding (SURVEY.md §2.3 row 1):
instead of hashing keys to *hosts* and relaying batches over gRPC, the
slot table is sharded across the devices of a jax.sharding.Mesh — each
device owns a contiguous range of slot groups — and ONE jitted SPMD call
decides the whole batch: every device masks the batch lanes whose group
falls in its shard, runs the same decide kernel on its local table shard,
and lane results are combined with a psum over the mesh axis (each lane
is answered by exactly one owner device, so the sum is the answer).

"Forwarding" therefore costs one replicated batch broadcast plus one
(B,)-sized psum over ICI — no per-peer RPCs, no retries, no batching
timers — while ownership semantics (exactly one authoritative counter
per key) are identical to the reference's hash ring.
"""

from __future__ import annotations

import functools
import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from gubernator_tpu.ops.kernels import (
    BYTES_PER_SLOT,
    Kernels,
    get_kernels,
    get_raw_kernels,
)
from gubernator_tpu.ops.layout import (
    SlotTable,
    output_slots,
    packed_waves,
    probed_waves,
    rows_of_slots,
    wide_rows,
)
from gubernator_tpu.utils import lockorder, transfer

AXIS = "owners"

# Process-wide multi-device ENQUEUE guard. Two engines in one process
# (two pods in a test, serving + background demoter, a sync tick racing
# a warmup) each dispatch multi-device programs onto the SAME devices;
# nothing orders the per-device enqueues of two concurrent dispatches
# against each other, so device 0 can start program A while device 1
# starts program B — both collectives then wait on the other's
# rendezvous forever (the test_two_tier_global ~25% hang). Holding this
# lock across the *dispatch call* (not the async execution) makes the
# enqueue order identical on every device; each device then drains its
# queue in order and no cross-program rendezvous can interleave.
# Reentrant: composite operations (snapshot -> extract_page per page)
# may take it around an outer section and again around inner dispatches.
_COLLECTIVES = lockorder.make_rlock("mesh.collectives")


def collective_guard():
    """The process-wide mesh dispatch lock (see _COLLECTIVES). Engines
    acquire it INSIDE their own table lock (consistent order:
    engine.table -> mesh.collectives), or alone during init/warmup."""
    return _COLLECTIVES


def _mask_to_local(groups_per: int, batch):
    """Shared ownership discipline for every sharded kernel: deactivate
    lanes whose group falls outside this shard's contiguous range
    [dev*groups_per, (dev+1)*groups_per), rebase the rest to shard-local
    group indices. Inactive lanes produce zeros in every layout kernel
    (drop-scatter + masked outputs), so a psum over the mesh axis
    recovers each lane's single authoritative answer."""
    dev = jax.lax.axis_index(AXIS)
    g0 = dev.astype(jnp.int64) * groups_per
    local_grp = batch.group.astype(jnp.int64) - g0
    mine = (local_grp >= 0) & (local_grp < groups_per) & batch.active
    return batch._replace(
        group=jnp.where(mine, local_grp, 0).astype(batch.group.dtype),
        active=mine,
    )

# The multi-device tier defaults to the fused layout like the single-chip
# engine (VERDICT r4 item 2: one hot path everywhere — wide measured 137x
# slower on TPU at 1M keys).
DEFAULT_LAYOUT = "fused"


def make_mesh(devices=None, axis: str = AXIS) -> Mesh:
    devices = devices if devices is not None else jax.devices()
    return Mesh(np.array(devices).reshape(-1), (axis,))


def create_sharded_table(
    mesh: Mesh, num_groups: int, ways: int = 8, layout: str = DEFAULT_LAYOUT,
    metrics=None,
):
    """Layout-native table sharded along the slot axis; contiguous groups
    per device (num_groups must divide evenly by mesh size). The shard
    placement rides the accounted transfer wrapper (utils/transfer.py,
    GL010): one h2d "warmup" ledger entry for the whole table."""
    n_dev = mesh.devices.size
    assert num_groups % n_dev == 0, "num_groups must be divisible by mesh size"
    sharding = NamedSharding(mesh, P(AXIS))
    table = get_raw_kernels(layout).create(num_groups, ways)
    return transfer.put_tree(table, sharding, metrics=metrics)


def _sharded_packed_decide(
    mesh: Mesh, groups_per: int, ways: int, decide, xlate=None
):
    """The packed launch over a sharded table: (table, operand,
    with_store) -> (table', output), operand and output replicated. The
    one operand, a wave or a stacked run of them, is unpacked and looped
    over INSIDE the shard_map body (ops/layout.py packed_waves): for
    each wave a shard masks the batch to the lanes it owns and runs
    `decide(table, batch, now)` on its slice; inactive lanes and foreign
    totals are zeros, so ONE psum of the packed output, after the last
    wave, gives every lane its single authoritative answer.
    `xlate(page_map, group)` (paged) maps logical to physical groups,
    replicated, before the ownership mask; `table` is then the
    PagedTable and only its data is sharded.

    `with_store` (a Store is attached): the output's OUT_SLOT row has to
    name the row of the WHOLE table that the lane's owner wrote, because
    the row gather that follows (_sharded_gather_rows) finds the owner
    by it. A shard's decide reports a slot of its own slice, and its
    slice's length for a lane it does not own; here the owner rebases
    its slot to the whole table's index less the table's length, every
    other shard gives 0, and the first shard adds the table's length
    once: the same one psum then yields the global slot, and the
    table's length (reads zeros) for a lane nobody owns."""
    slots_per = groups_per * ways
    num_slots = mesh.devices.size * slots_per

    def local(data, operand, *page_map, with_store):
        # named scopes are profile metadata only: they name a trace's
        # operations by phase and change nothing that is computed
        def wave(data, batch, now):
            with jax.named_scope("owner_mask"):
                if xlate is not None:
                    batch = batch._replace(
                        group=xlate(page_map[0], batch.group)
                    )
                mine = _mask_to_local(groups_per, batch)
            with jax.named_scope("decide"):
                data, out = decide(data, mine, now)
            if with_store:
                with jax.named_scope("global_slot"):
                    dev = jax.lax.axis_index(AXIS).astype(jnp.int64)
                    out = out._replace(slot=jnp.where(
                        mine.active,
                        out.slot + (dev * slots_per - num_slots),
                        0,
                    ) + jnp.where(dev == 0, num_slots, 0))
            return data, out

        data, out = packed_waves(wave, data, operand, with_store)
        with jax.named_scope("psum_merge"):
            return data, jax.lax.psum(out, AXIS)

    @functools.partial(
        jax.jit, static_argnames=("with_store",), donate_argnums=(0,)
    )
    def decide_fn(table, operand, with_store=False):
        paged = xlate is not None
        sharded = jax.shard_map(
            functools.partial(local, with_store=with_store),
            mesh=mesh,
            in_specs=(P(AXIS), P()) + ((P(),) if paged else ()),
            out_specs=(P(AXIS), P()),
        )
        if not paged:
            return sharded(table, operand)
        data, out = sharded(table.data, operand, table.page_map)
        return type(table)(data, table.page_map), out

    return decide_fn


def _sharded_gather_rows(mesh: Mesh, slots_per: int, gather_cols):
    """The Store's row gather over a sharded table, an owner program in
    the decide's image: gather_rows(table, slots, from_output=False) ->
    the (NCOLS, B) packed rows, replicated. `slots` index the WHOLE
    table (replicated; with `from_output` the `with_store` decide's
    output vector, whose OUT_SLOT row is taken inside the program). A
    shard reads the lanes whose slot lies in its slice out of that
    slice, `gather_cols(slice, local slots)`, and gives zeros for the
    rest; one psum hands every device every lane's row. A slot past the
    table (a padding lane's) is in nobody's slice and reads zeros. A
    stacked run's (W, L) output gives (W, NCOLS, B): every wave's lanes
    in the one gather and the one psum (ops/layout.py rows_of_slots)."""

    def local(data, slots):
        with jax.named_scope("owner_mask"):
            at = slots - jax.lax.axis_index(AXIS).astype(jnp.int64) * slots_per
            mine = (at >= 0) & (at < slots_per)
        with jax.named_scope("store_rows_local"):
            rows = gather_cols(data, jnp.where(mine, at, 0))
            rows = jnp.where(mine[None, :], rows, 0)
        with jax.named_scope("psum_rows"):
            return jax.lax.psum(rows, AXIS)

    sharded = jax.shard_map(
        local, mesh=mesh, in_specs=(P(AXIS), P()), out_specs=P()
    )

    @functools.partial(jax.jit, static_argnames=("from_output",))
    def gather_rows_fn(table, slots, from_output=False):
        return rows_of_slots(
            functools.partial(sharded, table),
            output_slots(slots) if from_output else slots,
        )

    return gather_rows_fn


def _sharded_probe_exists(
    mesh: Mesh, groups_per: int, ways: int, probe, xlate=None
):
    """The Store's residency probe over a sharded table, an owner
    program: probe_exists(table, operand, *page_map) -> bool[B],
    replicated. Each shard probes the lanes it owns against its slice
    (`probe(slice, batch, now, ways)`, the decide's ownership mask, the
    paged `xlate` before it) and answers False for the rest; one psum of
    the (B,) answers gives every device every lane's. A stacked run's
    operand is probed whole, (W, B), in the one probe and the one psum
    (ops/layout.py probed_waves)."""

    def local(data, operand, *page_map):
        def probe_owned(batch, now):
            with jax.named_scope("owner_mask"):
                if xlate is not None:
                    batch = batch._replace(
                        group=xlate(page_map[0], batch.group)
                    )
                mine = _mask_to_local(groups_per, batch)
            with jax.named_scope("probe_local"):
                return probe(data, mine, now, ways)

        found = probed_waves(probe_owned, operand)
        with jax.named_scope("psum_probe"):
            return jax.lax.psum(found.astype(jnp.int32), AXIS) != 0

    paged = xlate is not None
    sharded = jax.shard_map(
        local,
        mesh=mesh,
        in_specs=(P(AXIS), P()) + ((P(),) if paged else ()),
        out_specs=P(),
    )

    @jax.jit
    def probe_exists_fn(table, operand):
        if not paged:
            return sharded(table, operand)
        return sharded(table.data, operand, table.page_map)

    return probe_exists_fn


def make_sharded_decide(
    mesh: Mesh, num_groups: int, ways: int = 8, layout: str = DEFAULT_LAYOUT
):
    """Builds decide(table, operand, with_store=False) -> (table', output
    vector) where the table is sharded over `mesh` and the one operand
    (ops/layout.py WaveOperand) is replicated."""
    RK = get_raw_kernels(layout)
    return _sharded_packed_decide(
        mesh, num_groups // mesh.devices.size, ways,
        lambda t, b, now: RK.decide(t, b, now, ways),
    )


def make_sharded_inject(
    mesh: Mesh, num_groups: int, ways: int = 8, layout: str = DEFAULT_LAYOUT
):
    """Builds inject(table, items, now) -> (table', evicted_hi, evicted_lo)
    over a sharded table: the decide ownership mask applied to the inject
    batch. Displaced-occupant key columns are psum-merged exactly like
    DecideOutput (a lane lands on exactly one owner; inactive lanes
    scatter nothing and report (0, 0))."""
    n_dev = mesh.devices.size
    groups_per = num_groups // n_dev
    RK = get_raw_kernels(layout)

    def local_inject(table, items, now):
        table, ehi, elo = RK.inject(
            table, _mask_to_local(groups_per, items), now, ways
        )
        return table, jax.lax.psum(ehi, AXIS), jax.lax.psum(elo, AXIS)

    sharded = jax.shard_map(
        local_inject,
        mesh=mesh,
        in_specs=(P(AXIS), P(), P()),
        out_specs=(P(AXIS), P(), P()),
    )

    @functools.partial(jax.jit, donate_argnums=(0,))
    def inject_fn(table, items, now):
        now = jnp.asarray(now, dtype=jnp.int64)
        return sharded(table, items, now)

    return inject_fn


def make_mesh_kernels(
    mesh: Mesh,
    layout: str,
    num_groups: int,
    ways: int = 8,
    *,
    page_groups: int = 0,
    page_budget: int = 0,
    metrics=None,
):
    """Kernels-compatible facade over a mesh-sharded table, so the engine
    core binds one kernel set and never learns the topology.

    Flat (page_groups == 0): returns an ops.kernels.Kernels whose
    decide, inject and the Store's two other programs (probe_exists,
    gather_rows) are the shard_map ownership programs above: every
    program of a Store's per-wave sequence reads or writes a lane at the
    shard that owns it and ends in one psum, so a Store on a sharded
    table sees what a Store on one device sees. The whole-table reads
    (to_wide, census input) are the plain layout jits — GSPMD partitions
    them over the sharded table automatically.

    Paged (page_groups > 0): returns an ops.paged.PagedKernels-shaped
    facade where the PHYSICAL table is sharded along the slot axis and
    the page map is replicated: translation (logical -> physical group)
    runs replicated *before* the shard_map, then the ownership mask
    applies in PHYSICAL group space with groups_per = num_phys_groups /
    n_dev. Sentinel (non-resident) lanes rebase out of every shard's
    range, go inactive everywhere, and psum to zeros — same degrade-to-
    dropped-write guarantee as the single-chip paged table. Page frames
    are placed by the MeshPager (runtime/pager.py) so each shard keeps
    its own frame pool and host-DRAM cold tier."""
    n_dev = mesh.devices.size
    if num_groups % n_dev:
        raise ValueError(
            f"num_groups {num_groups} must divide by mesh size {n_dev}"
        )
    if page_groups <= 0:
        base = get_kernels(layout)
        raw = get_raw_kernels(layout)
        decide_fn = make_sharded_decide(mesh, num_groups, ways, layout)
        inject_fn = make_sharded_inject(mesh, num_groups, ways, layout)
        groups_per = num_groups // n_dev
        probe_fn = _sharded_probe_exists(
            mesh, groups_per, ways, raw.probe_exists
        )
        gather_fn = _sharded_gather_rows(
            mesh, groups_per * ways, raw.gather_cols
        )
        sharding = NamedSharding(mesh, P(AXIS))

        def _create(*_a, **_k):
            return create_sharded_table(
                mesh, num_groups, ways, layout, metrics=metrics
            )

        def _from_wide(wide):
            return jax.device_put(raw.from_wide(wide), sharding)  # guberlint: allow-unaccounted-transfer -- restore path: the engine's snapshot/restore tx accounts the upload around this call

        return Kernels(
            layout=layout,
            create=_create,
            decide_packed=lambda t, op, ways_=ways, with_store=False: (
                decide_fn(t, op, with_store=bool(with_store))
            ),
            inject=lambda t, i, now, ways_=ways: inject_fn(t, i, now),
            probe_exists=lambda t, operand, ways_=ways: probe_fn(t, operand),
            gather_rows=gather_fn,
            to_wide=base.to_wide,
            from_wide=_from_wide,
            bytes_per_slot=BYTES_PER_SLOT[layout],
        )
    return _make_mesh_paged_kernels(
        mesh, layout, num_groups, ways, page_groups, page_budget, metrics
    )


def _make_mesh_paged_kernels(
    mesh: Mesh,
    layout: str,
    num_groups: int,
    ways: int,
    groups_per_page: int,
    num_phys_pages: int,
    metrics=None,
):
    # Lazy import mirrors ops/kernels.get_paged_kernels: flat mesh tables
    # never pay for the paged module.
    from gubernator_tpu.ops.paged import (
        PagedKernels,
        PagedTable,
        write_region,
        zero_region,
    )

    n_dev = mesh.devices.size
    if groups_per_page <= 0:
        raise ValueError(f"groups_per_page must be > 0: {groups_per_page}")
    if num_phys_pages <= 0 or num_phys_pages % n_dev:
        raise ValueError(
            f"page budget {num_phys_pages} must be a positive multiple of "
            f"mesh size {n_dev} (each shard owns an equal frame pool)"
        )
    gpp = groups_per_page
    page_slots = gpp * ways
    num_logical_pages = -(-num_groups // gpp)  # ceil
    num_phys_groups = num_phys_pages * gpp
    groups_per = num_phys_groups // n_dev
    base = get_kernels(layout)
    raw = get_raw_kernels(layout)
    sentinel = jnp.int32(num_phys_groups)
    data_sharding = NamedSharding(mesh, P(AXIS))
    repl = NamedSharding(mesh, P())
    pt_sharding = PagedTable(data=data_sharding, page_map=repl)

    def _xlate(page_map, group):
        """Logical -> PHYSICAL group, replicated (the page map is small
        and replicated; one gather before the shard_map)."""
        g = group.astype(jnp.int32)
        pp = page_map[g // gpp]
        phys = jnp.where(pp >= 0, pp * gpp + g % gpp, sentinel)
        return phys.astype(group.dtype)

    _decide_packed = _sharded_packed_decide(
        mesh, groups_per, ways,
        lambda d, b, now: raw.decide(d, b, now, ways),
        xlate=_xlate,
    )
    # the Store's two other programs, over the PHYSICAL frames: slots
    # index the physical table, as the paged decide reports them
    _probe_exists = _sharded_probe_exists(
        mesh, groups_per, ways, raw.probe_exists, xlate=_xlate
    )
    _gather_rows = _sharded_gather_rows(
        mesh, groups_per * ways, raw.gather_cols
    )

    def _local_inject(data, items, now):
        data, ehi, elo = raw.inject(
            data, _mask_to_local(groups_per, items), now, ways
        )
        return data, jax.lax.psum(ehi, AXIS), jax.lax.psum(elo, AXIS)

    _sharded_inject = jax.shard_map(
        _local_inject,
        mesh=mesh,
        in_specs=(P(AXIS), P(), P()),
        out_specs=(P(AXIS), P(), P()),
    )

    @functools.partial(jax.jit, donate_argnums=(0,))
    def _inject(pt, items, now):
        now = jnp.asarray(now, dtype=jnp.int64)
        i = items._replace(group=_xlate(pt.page_map, items.group))
        data, ehi, elo = _sharded_inject(pt.data, i, now)
        return PagedTable(data, pt.page_map), ehi, elo

    # Page moves are the single-chip programs with output shardings
    # pinned: the physical table stays sharded along the slot axis and
    # the page map stays replicated, regardless of what GSPMD would
    # infer from the replicated update operands.
    @functools.partial(
        jax.jit, donate_argnums=(0,), out_shardings=pt_sharding
    )
    def _bind_page(pt, lp, pp):
        data = zero_region(pt.data, pp * page_slots, page_slots)
        return PagedTable(data, pt.page_map.at[lp].set(pp))

    @functools.partial(
        jax.jit, donate_argnums=(0,), out_shardings=pt_sharding
    )
    def _unbind_page(pt, lp, pp):
        # Zero the evacuated frame: census and key-string pruning scan
        # the PHYSICAL table and must not see ghost rows.
        data = zero_region(pt.data, pp * page_slots, page_slots)
        return PagedTable(data, pt.page_map.at[lp].set(jnp.int32(-1)))

    @functools.partial(jax.jit, out_shardings=repl)
    def _extract_page(pt, pp):
        slots = pp * page_slots + jnp.arange(page_slots, dtype=jnp.int64)
        return wide_rows(base.gather_rows(pt.data, slots))

    @functools.partial(
        jax.jit, donate_argnums=(0,), out_shardings=pt_sharding
    )
    def _write_page(pt, lp, pp, rows_wide):
        rows = raw.from_wide(SlotTable(*rows_wide))
        data = write_region(pt.data, rows, pp * page_slots)
        return PagedTable(data, pt.page_map.at[lp].set(pp))

    def _create(*_a, **_k):
        data = create_sharded_table(
            mesh, num_phys_groups, ways, layout, metrics=metrics
        )
        page_map = jax.device_put(  # guberlint: allow-unaccounted-transfer -- one-time empty-map constant at table creation, not a serving-path upload
            jnp.full((num_logical_pages,), -1, dtype=jnp.int32), repl
        )
        return PagedTable(data=data, page_map=page_map)

    def _from_wide(_t):
        raise NotImplementedError(
            "paged tables restore page-by-page (write_page), not from one "
            "flat wide image — see the engine's paged restore path"
        )

    return PagedKernels(
        layout=layout,
        create=_create,
        decide_packed=lambda t, op, ways_=ways, with_store=False: (
            _decide_packed(t, op, with_store=bool(with_store))
        ),
        inject=lambda t, i, now, ways_=ways: _inject(t, i, now),
        probe_exists=lambda t, operand, ways_=ways: _probe_exists(t, operand),
        gather_rows=lambda t, slots, from_output=False: _gather_rows(
            t.data, slots, from_output=from_output
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
