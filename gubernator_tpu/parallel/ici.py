"""GLOBAL behavior over ICI collectives: per-chip replicas + psum'd deltas.

The TPU-native replacement for the reference globalManager's two gRPC
legs (reference global.go:91-283; SURVEY.md §2.3 row 4). Within one pod,
the "peers" are mesh devices:

- Every device holds a full REPLICA of the GLOBAL counter table and
  answers its share of requests locally (the reference's
  getGlobalRateLimit replica path, gubernator.go:395-421), accumulating
  each non-owned hit into a per-device `pending` delta table.
- Each sync tick (GlobalSyncWait cadence, 100ms default) ONE jitted
  collective step replaces both network legs: hit deltas flow to owner
  shards via psum (the async-hits leg), owners apply them with drain
  semantics (the GetPeerRateLimits apply), and the authoritative state
  is rebroadcast to every replica via a second masked psum (the
  UpdatePeerGlobals leg).

Geometry: replica tables are W-way set-associative (same policy as the
local table, ops/decide.py _choose_slot), so a key may sit in DIFFERENT
ways on different devices — each device's LRU/eviction history differs.
The sync merge therefore key-matches deltas ACROSS the ways of a group:
for each slot of the owner's layout, every replica contributes the
pending of whichever of its own ways holds that key. ways=1 (slot ==
group on every device, merge is pure per-slot arithmetic) remains
available and is the degenerate case of the same code path. W-way
placement removes the direct-mapped collision cliff: colliding keys
spread over W ways instead of evicting each other between syncs.
Cross-device safety holds at any W: every merge is key-checked, so a
slot whose replicas hold different keys never mixes their counters.

Consistency contract preserved (validated in tests/test_mesh.py and the
differential fuzz tests/test_ici_fuzz.py): hits on a replica appear on
every other replica after one sync; owner hits need no delta leg;
over-limit relays drain.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from gubernator_tpu.api.types import Behavior, Status
from gubernator_tpu.models.bucket import FIXED_SHIFT
from gubernator_tpu.ops.fused import join, split
from gubernator_tpu.ops.kernels import get_raw_kernels
from gubernator_tpu.ops.layout import (
    SlotTable,
    pack_output,
    unpack_operand,
)
from gubernator_tpu.utils import transfer

AXIS = "owners"
I64 = jnp.int64

# Same flagship default as the single-chip engine and the sharded tier
# (VERDICT r4 item 2): the replica decide runs layout-native; only the
# sync tick's merge goes through the wide view (to_wide/from_wide).
DEFAULT_LAYOUT = "fused"


class IciState(NamedTuple):
    """Per-device replica tables + pending hit deltas.

    Every table leaf is stacked (D, ...) and sharded on the device
    axis; `pending` is the hit deltas awaiting the next sync, one int64
    a slot, recorded at the slot where the key resides on THAT device,
    and stored as (D, 2, N) uint32: the low words, then the high words
    (ops/fused.py `join` / `split`: a TPU converts a whole int64
    parameter and result on every dispatch, words it indexes in place). `tick`
    is a (D,) sync-tick counter (identical on every device) — the
    capped sync's scan rotation mixes it with `now` so back-to-back
    ticks at a coarse timestamp still rotate over a backlog.
    """

    table: object  # layout-native table, leaves stacked (D, ...)
    pending: jnp.ndarray
    tick: jnp.ndarray


def create_ici_state(
    mesh: Mesh, num_slots: int, ways: int = 1, layout: str = DEFAULT_LAYOUT,
    metrics=None,
) -> IciState:
    n_dev = mesh.devices.size
    assert num_slots % ways == 0, "num_slots must divide by ways"
    num_groups = num_slots // ways
    assert num_groups % n_dev == 0, (
        "num_slots/ways (group count) must divide by mesh size"
    )
    sharding = NamedSharding(mesh, P(AXIS))
    table = get_raw_kernels(layout).create(num_groups, ways)
    # The replica-tier placement rides the accounted transfer wrapper
    # (utils/transfer.py, GL010): one h2d "warmup" ledger entry for the
    # stacked replicas, one for each delta buffer.
    stacked = transfer.put_tree(
        jax.tree.map(
            lambda x: jnp.broadcast_to(x[None], (n_dev,) + x.shape), table
        ),
        sharding, metrics=metrics,
    )
    pending = transfer.device_put(
        jnp.zeros((n_dev, 2, num_slots), dtype=jnp.uint32), sharding,
        metrics=metrics,
    )
    tick = transfer.device_put(
        jnp.zeros((n_dev,), dtype=I64), sharding, metrics=metrics
    )
    return IciState(table=stacked, pending=pending, tick=tick)


def pending_hits(state: IciState) -> jnp.ndarray:
    """`state.pending` as what it spells: (D, N) int64 hit deltas (a
    whole-array conversion, for tools and tests)."""
    return join(state.pending[:, 0], state.pending[:, 1])


def _squeeze(tree):
    return jax.tree.map(lambda x: x[0], tree)


def _unsqueeze(tree):
    return jax.tree.map(lambda x: x[None], tree)


def _replica_step(RK, ways, groups_per, num_slots, dev, tbl, pending,
                  batch, home, now):
    """One device-local replica decide: answer my lanes, maintain pending
    deltas."""
    mine = batch.active & (home == dev)
    local_batch = batch._replace(active=mine)

    tbl, out = RK.decide(tbl, local_batch, now, ways)

    # If this request replaced a DIFFERENT key at its landing slot
    # (W-way eviction), the old key's un-synced pending hits must not
    # be credited to the new key — drop them. A freed slot (token
    # RESET_REMAINING) likewise clears its pending: the reset erased
    # the entry the delta belonged to.
    drop = mine & (
        (out.evicted_hi != 0) | (out.evicted_lo != 0) | out.freed
    )
    evict_idx = jnp.where(drop, out.slot, num_slots)
    pending = pending.at[:, evict_idx].set(0, mode="drop")

    # Accumulate deltas for lanes I answered but do not own
    # (reference globalManager.QueueHit, global.go:74-78). Only what
    # this replica took is queued: the hits of an OVER_LIMIT answer
    # consumed nothing here and must consume nothing at the owner (the
    # reference queues them too, so a refused request drains its owner;
    # the tick's over-admission count would read every refusal as an
    # admission). A DRAIN_OVER_LIMIT request drained this copy, and is
    # relayed to drain the owner's.
    owned = (batch.group.astype(I64) // groups_per) == dev
    is_global = (batch.behavior & int(Behavior.GLOBAL)) != 0
    took = (out.status == int(Status.UNDER_LIMIT)) | (
        (batch.behavior & int(Behavior.DRAIN_OVER_LIMIT)) != 0
    )
    pend_mask = mine & ~owned & is_global & (batch.hits != 0) & took
    idx = jnp.where(pend_mask, out.slot, num_slots)
    # A 64-bit add on the two words: read, add, write back. A wave's
    # lanes lie in distinct groups, hence distinct slots (the table's
    # own scatter needs that already), so no delta is written twice.
    held = pending[:, jnp.minimum(idx, num_slots - 1)]
    added = jnp.stack(split(join(held[0], held[1]) + batch.hits))
    pending = pending.at[:, idx].set(added, mode="drop")
    return tbl, pending, out


def make_replica_decide(
    mesh: Mesh, num_slots: int, ways: int = 1, layout: str = DEFAULT_LAYOUT
):
    """decide(state, operand): lane i is answered by device home[i]'s
    replica (the node the request arrived at); non-owned GLOBAL hits are
    accumulated into that device's pending deltas at the slot decide()
    placed the key in (way choice is per-device). `operand` is the one
    uploaded wave array (ops/layout.py WaveOperand: the batch, its
    `home` row and `now`); the answer is one output vector, psum-merged
    (ops/layout.py split_output)."""
    n_dev = mesh.devices.size
    num_groups = num_slots // ways
    groups_per = num_groups // n_dev
    RK = get_raw_kernels(layout)

    def local(state: IciState, operand):
        batch, home, now = unpack_operand(operand)
        dev = jax.lax.axis_index(AXIS).astype(I64)
        tbl, pending, out = _replica_step(
            RK, ways, groups_per, num_slots, dev,
            _squeeze(state.table), state.pending[0], batch, home, now,
        )
        return (
            IciState(
                table=_unsqueeze(tbl), pending=pending[None],
                tick=state.tick,
            ),
            jax.lax.psum(pack_output(out, False), AXIS),
        )

    sharded = jax.shard_map(
        local,
        mesh=mesh,
        in_specs=(P(AXIS), P()),
        out_specs=(P(AXIS), P()),
    )

    @functools.partial(jax.jit, donate_argnums=(0,))
    def decide_fn(state: IciState, operand):
        return sharded(state, operand)

    return decide_fn


def make_inject_replicas(
    mesh: Mesh, num_slots: int, ways: int = 1, layout: str = DEFAULT_LAYOUT
):
    """Apply authoritative state rows to EVERY device's replica — the
    landing side of a cross-pod UpdatePeerGlobals push (the intra-pod
    sync uses make_sync_step's rebroadcast instead)."""
    RK = get_raw_kernels(layout)

    def local(state: IciState, items, now):
        tbl = _squeeze(state.table)
        pending = state.pending[0]
        tbl, _ehi, _elo = RK.inject(tbl, items, now, ways)
        # The authoritative push supersedes this pod's un-synced local
        # deltas for these keys (the host tier already carried them to
        # the owner); leaving them would re-apply the same hits at the
        # next sync tick and double-count. The injected key now occupies
        # exactly one way of its group — clear that slot's pending (this
        # also drops a displaced occupant's orphaned delta).
        grp_base = items.group.astype(I64) * ways
        way_ix = grp_base[:, None] + jnp.arange(ways, dtype=I64)[None, :]
        landed = (
            items.active[:, None]
            & (tbl.key_hi[way_ix] == items.key_hi[:, None])
            & (tbl.key_lo[way_ix] == items.key_lo[:, None])
        )
        idx = jnp.where(landed, way_ix, num_slots).reshape(-1)
        pending = pending.at[:, idx].set(0, mode="drop")
        return IciState(
            table=_unsqueeze(tbl), pending=pending[None], tick=state.tick
        )

    sharded = jax.shard_map(
        local, mesh=mesh, in_specs=(P(AXIS), P(), P()), out_specs=P(AXIS)
    )

    @functools.partial(jax.jit, donate_argnums=(0,))
    def inject_fn(state: IciState, items, now):
        return sharded(state, items, jnp.asarray(now, I64))

    return inject_fn


def _mix64(x):
    """splitmix64 finalizer (elementwise, uint64): deterministic
    avalanche for the sync tick's content fingerprints."""
    x = (x ^ (x >> jnp.uint64(30))) * jnp.uint64(0xBF58476D1CE4E5B9)
    x = (x ^ (x >> jnp.uint64(27))) * jnp.uint64(0x94D049BB133111EB)
    return x ^ (x >> jnp.uint64(31))


def _leaf_signals(table, pending, now, ways: int):
    """The capped tick's selector over a table that is a pytree of
    per-slot (N, ...) leaves (wide, the reference), in the form of
    ops/kernels.py `RawKernels.group_signals`; the serving layout brings
    its own over its lines (ops/fused.py `group_signals`). TWO
    independently-salted per-group uint64 content fingerprints over the
    leaves + pending, accumulated in a single traversal. Way position is
    salted in, so the same keys at different ways on different devices
    still diverge. Returned as int64 (same bits, same wrap-around sums):
    XLA:TPU lowers a 64-bit Sum all-reduce for s64 only and refuses the
    u64 one ("UNIMPLEMENTED: Supported lowering only of Sum all
    reduce", libtpu 0.0.34)."""
    num_slots = pending.shape[-1]
    G, W = num_slots // ways, ways
    accs = [jnp.zeros(num_slots, jnp.uint64) for _ in range(2)]
    col = 0
    for leaf in jax.tree_util.tree_leaves(table):
        x = leaf.reshape(num_slots, -1).astype(jnp.uint64)
        for s in range(2):
            salts = (
                jnp.arange(x.shape[1], dtype=jnp.uint64)
                + jnp.uint64(col + s + 1)
            ) * jnp.uint64(0x9E3779B97F4A7C15)
            accs[s] = accs[s] + _mix64(x + salts[None, :]).sum(
                axis=1, dtype=jnp.uint64
            )
        col += x.shape[1]
    wsalt = jnp.arange(W, dtype=jnp.uint64) * jnp.uint64(0xD6E8FEB86659FD93)
    p64 = join(pending[0], pending[1])
    fps = [
        _mix64(
            (accs[s] + _mix64(p64.astype(jnp.uint64) + jnp.uint64(col + s + 1)))
            .reshape(G, W)
            + wsalt[None, :]
        ).sum(axis=1, dtype=jnp.uint64)
        for s in range(2)
    ]
    return (
        jnp.stack(fps).astype(I64),
        (p64 != 0).reshape(G, W).any(axis=1),
        (table.used & (table.expire_at < now)).reshape(G, W).any(axis=1),
    )


def _block_widths(cap: int) -> tuple:
    """The widths, in groups, a capped tick merges at, ascending: it
    takes the least that holds the groups it found active. Three at
    most and `cap` the widest: each is one more copy of the merge to
    compile, and a narrower block than a sixty-fourth of the cap saves
    nothing the fixed work of a tick would show."""
    return tuple(sorted({max(1, cap // 64), max(1, cap // 8), cap}))


def make_sync_step(
    mesh: Mesh,
    num_slots: int,
    ways: int = 1,
    layout: str = DEFAULT_LAYOUT,
    max_sync_groups: "int | None" = None,
):
    """One collective sync tick: deltas -> owners -> authoritative apply ->
    replica rebroadcast. Replaces both gRPC legs of the reference's
    globalManager with ~20 psums over ICI.

    With W>1 the merge key-matches across the ways of each group (a key
    sits in different ways on different devices); adoption stays
    per-slot-position and is deduplicated within the group afterwards so
    the rebroadcast layout never holds the same key twice.

    The merge itself is layout-agnostic: a non-wide replica table is
    unpacked to the wide column view at tick entry and repacked at exit
    (the decide hot path stays layout-native; only this 10Hz tick pays
    the conversion).

    `max_sync_groups` bounds per-tick work (VERDICT r4 item 3: the full
    (G,W,W) merge + ~20 full-table psums scale with TABLE size and blow
    the 100ms cadence at 10M keys). When set, the tick first finds
    groups needing sync — any device's group content fingerprint
    diverges, pending deltas exist, or an entry has expired (one
    group-sized psum, the only full-size collective; the fingerprints are
    the layout's own, `RawKernels.group_signals`, one read of the table
    as it lies) — then gathers up to C=max_sync_groups of them
    compactly and runs the identical merge on the compact view, at the
    least of `_block_widths(C)` that holds what it found: every chip
    knows the count, so the choice is made inside the one program.
    Overflow beyond C stays dirty and is picked up next tick (diag[2]
    reports the backlog); the scan start rotates with `now` so a
    persistent over-budget load cannot starve any group. None =
    unbounded (exact single-pass semantics; the two paths are
    differentially tested)."""
    n_dev = mesh.devices.size
    num_groups = num_slots // ways
    groups_per = num_groups // n_dev
    G, W = num_groups, ways
    RK = get_raw_kernels(layout)
    C = G if max_sync_groups is None else max(1, min(int(max_sync_groups), G))
    capped = C < G
    signals = RK.group_signals or _leaf_signals

    def merge_block(dev, t, pending, gids, valid, now, psum):
        """The sync merge over a block of groups. `t` is a wide SlotTable
        whose leaves are (C*W,), `pending` (C*W,), `gids` (C,) original
        group ids (sentinel G for padding lanes, valid False). Returns
        (new wide table, new pending, totals) for the block, `totals`
        this device's [kept, dropped, merged hits, over-admitted hits];
        padded lanes produce empty rows."""
        nslots = gids.shape[0] * W
        own = jnp.broadcast_to(
            ((gids // groups_per) == dev)[:, None], (gids.shape[0], W)
        ).reshape(nslots)
        vmask = jnp.broadcast_to(
            valid[:, None], (gids.shape[0], W)
        ).reshape(nslots)
        live = t.used & (t.expire_at >= now) & vmask

        # Phase A: owner identity per slot (replicated after psum). The
        # owner's layout is authoritative: rebroadcast reproduces it on
        # every replica.
        owner_live = psum((own & live).astype(I64)) > 0
        owner_key_hi = psum(jnp.where(own & live, t.key_hi, 0))
        owner_key_lo = psum(jnp.where(own & live, t.key_lo, 0))

        resh = lambda x: x.reshape(-1, W)  # noqa: E731
        lv, pnd = resh(live), resh(pending)
        lk_hi, lk_lo = resh(t.key_hi), resh(t.key_lo)

        def crossway_inc(dst_hi, dst_lo, dst_ok):
            """Per destination slot (g, w): psum over devices of the
            pending sitting at whichever way of group g holds dst's key
            on that device (key-checked, so colliding entries never
            pollute another key's counter)."""
            eq = (
                lv[:, :, None]
                & dst_ok[:, None, :]
                & (lk_hi[:, :, None] == dst_hi[:, None, :])
                & (lk_lo[:, :, None] == dst_lo[:, None, :])
            )
            inc = jnp.sum(jnp.where(eq, pnd[:, :, None], 0), axis=1)
            return psum(inc.reshape(nslots))

        ow_hi, ow_lo, ow_lv = (
            resh(owner_key_hi), resh(owner_key_lo), resh(owner_live),
        )
        inc_match = crossway_inc(ow_hi, ow_lo, ow_lv)

        # Adoption: a replica holds a live entry whose key is absent from
        # the owner's layout (the relayed request would have created the
        # entry at the owner in the reference — including zero-hit reads:
        # gating on pending!=0 left read-created buckets replica-local
        # FOREVER, permanently inflating the overflow-kept gauge).
        # Candidacy pre-filters keys already in the owner layout for the
        # group, so a rebroadcast copy never shadows a genuinely-missing
        # key at the same way position. Candidates are selected per slot
        # position (lowest device index wins), deduplicated, then packed
        # into the owner group's EMPTY ways in rank order — a candidate
        # is not tied to its own way position, so an owner group with
        # free space always absorbs overflow keys regardless of where
        # replicas placed them.
        in_own_src = (
            ow_lv[:, None, :]
            & (lk_hi[:, :, None] == ow_hi[:, None, :])
            & (lk_lo[:, :, None] == ow_lo[:, None, :])
        ).any(axis=2)  # [g, w_src]: my key at (g, w_src) is owner-known
        cand = live & ~in_own_src.reshape(nslots)
        # On int32: XLA:TPU lowers 64-bit all-reduces for Sum only
        # ("UNIMPLEMENTED: Supported lowering only of Sum all reduce"
        # for an s64 minimum, libtpu 0.0.34); device indices fit.
        sel = jax.lax.pmin(
            jnp.where(cand, dev, n_dev).astype(jnp.int32), AXIS
        )
        is_sel = cand & (dev == sel)
        adopted_key_hi = psum(jnp.where(is_sel, t.key_hi, 0))
        adopted_key_lo = psum(jnp.where(is_sel, t.key_lo, 0))
        adopt_ok = sel < n_dev
        ad_hi, ad_lo, ad_ok = (
            resh(adopted_key_hi), resh(adopted_key_lo), resh(adopt_ok),
        )
        inc_adopt = crossway_inc(ad_hi, ad_lo, ad_ok)
        pending_sel = psum(jnp.where(is_sel, pending, 0))

        def adopt(field):
            return psum(jnp.where(is_sel, field.astype(I64), 0))

        # Owner-layout keys were already excluded at candidacy
        # (in_own_src), so only same-key dedup against lower-way
        # candidates remains (two devices may hold the same key at
        # different way positions). Vacuous at W=1.
        ua1 = ad_ok
        same = (ad_hi[:, :, None] == ad_hi[:, None, :]) & (
            ad_lo[:, :, None] == ad_lo[:, None, :]
        )
        earlier = jnp.tril(jnp.ones((W, W), dtype=bool), -1)  # [w, w']: w' < w
        dup_prev = (same & ua1[:, None, :] & earlier[None]).any(axis=2)
        ua_src = ua1 & ~dup_prev  # surviving candidates, at source ways

        # Pack candidates into empty owner ways: rank r candidate lands
        # in the rank r empty way. src_onehot[g, w_dst, w_src].
        empty = ~ow_lv
        c_rank = jnp.cumsum(ua_src.astype(I64), axis=1) - 1
        e_rank = jnp.cumsum(empty.astype(I64), axis=1) - 1
        src_onehot = (
            empty[:, :, None]
            & ua_src[:, None, :]
            & (e_rank[:, :, None] == c_rank[:, None, :])
        )
        use_adopt = src_onehot.any(axis=2).reshape(nslots)

        def permute(per_slot):
            """Move a per-slot quantity from candidate source ways to
            their destination (adopted) ways."""
            q = per_slot.reshape(-1, W).astype(I64)
            return jnp.sum(
                jnp.where(src_onehot, q[:, None, :], 0), axis=2
            ).reshape(nslots)

        # Merge my owned region: authoritative base + incoming deltas.
        use_mine = owner_live

        def merged(field_mine, adopted_i64):
            return jnp.where(
                use_mine,
                field_mine,
                jnp.where(use_adopt, permute(adopted_i64), 0).astype(
                    field_mine.dtype
                ),
            )

        inc = jnp.where(
            use_mine,
            inc_match,
            jnp.where(use_adopt, permute(inc_adopt) - permute(pending_sel), 0),
        )

        base = {f: merged(getattr(t, f), adopt(getattr(t, f))) for f in t._fields}
        base_used = jnp.where(use_mine, live, use_adopt)

        # Apply deltas with drain semantics (relayed GLOBAL hits force
        # DRAIN_OVER_LIMIT at the owner, reference gubernator.go:510-512).
        is_leaky = base["algo"] == 1
        rem = base["remaining"]
        rem_tok = jnp.maximum(rem - inc, 0)
        rem_lky = jnp.maximum(rem - (inc << FIXED_SHIFT), 0)
        new_rem = jnp.where(base_used & (inc != 0), jnp.where(is_leaky, rem_lky, rem_tok), rem)
        # What the deltas did to the buckets the owner held, counted by
        # the owner: the hits other replicas took, and those of them its
        # bucket could no longer take (whole hits, token and leaky
        # alike): between two ticks the replicas together may admit more
        # than is left, by no more than they admit in between.
        mine_inc = jnp.where(own & use_mine, inc, 0)
        room = jnp.where(is_leaky, rem >> FIXED_SHIFT, rem)
        merged_hits = jnp.sum(mine_inc)
        over_hits = jnp.sum(jnp.maximum(mine_inc - room, 0))

        # Rebroadcast: each device contributes only its owned region; the
        # psum IS the UpdatePeerGlobals fan-out.
        def bcast(val):
            out = psum(jnp.where(own & base_used, val.astype(I64), 0))
            return out.astype(val.dtype)

        merged_used = psum(jnp.where(own & base_used, 1, 0)) > 0
        mk_hi = bcast(base["key_hi"])
        mk_lo = bcast(base["key_lo"])

        # Replica-local retention: a live local entry whose key did not
        # make the merged layout (its group is full at the owner) is
        # RELOCATED into one of the group's merged-free ways instead of
        # being erased — the key degrades to per-replica counting under
        # capacity pressure rather than losing all state, and its pending
        # survives so the delta reconciles the moment the owner group
        # frees a way. (The reference's owner cache is unbounded, so
        # relayed hits never face this; a fixed-capacity table needs an
        # overflow story.) Relocation (same rank-packing as adoption, but
        # per device) means a survivor is only dropped when the group has
        # no free way left on THIS device — not merely because an adopted
        # key landed on its position. A local copy of a key the merged
        # layout DOES hold somewhere in the group is dropped — keeping it
        # would duplicate the key on this device.
        mfree = ~merged_used.reshape(-1, W)
        in_merged = (
            (lk_hi[:, :, None] == mk_hi.reshape(-1, W)[:, None, :])
            & (lk_lo[:, :, None] == mk_lo.reshape(-1, W)[:, None, :])
            & ~mfree[:, None, :]
        ).any(axis=2)
        surv = lv & ~in_merged
        s_rank = jnp.cumsum(surv.astype(I64), axis=1) - 1
        f_rank = jnp.cumsum(mfree.astype(I64), axis=1) - 1
        move_onehot = (  # [g, w_dst, w_src]
            mfree[:, :, None]
            & surv[:, None, :]
            & (f_rank[:, :, None] == s_rank[:, None, :])
        )
        kept = move_onehot.any(axis=2).reshape(nslots)

        def relocate(per_slot):
            q = per_slot.reshape(-1, W).astype(I64)
            return jnp.sum(
                jnp.where(move_onehot, q[:, None, :], 0), axis=2
            ).reshape(nslots)

        def take(merged_val, local_val):
            moved = relocate(local_val).astype(local_val.dtype)
            return jnp.where(
                merged_used,
                merged_val,
                jnp.where(kept, moved, jnp.zeros_like(local_val)),
            )

        new_table = SlotTable(
            key_hi=take(mk_hi, t.key_hi),
            key_lo=take(mk_lo, t.key_lo),
            used=merged_used | kept,
            algo=take(bcast(base["algo"]), t.algo),
            status=take(bcast(base["status"]), t.status),
            limit=take(bcast(base["limit"]), t.limit),
            duration=take(bcast(base["duration"]), t.duration),
            remaining=take(bcast(jnp.where(base_used, new_rem, 0)), t.remaining),
            stamp=take(bcast(base["stamp"]), t.stamp),
            expire_at=take(bcast(base["expire_at"]), t.expire_at),
            invalid_at=take(bcast(base["invalid_at"]), t.invalid_at),
            burst=take(bcast(base["burst"]), t.burst),
            lru=take(bcast(base["lru"]), t.lru),
        )
        # Pending rides along with relocated survivors (same key,
        # un-applied deltas). Everything else was either applied via inc
        # or belongs to a key the merged layout now covers.
        new_pending = jnp.where(kept, relocate(pending), 0)

        # Overflow diagnostics (VERDICT r3 item 5): how many entries on
        # THIS device are degraded to per-replica counting (kept
        # survivors), and how many survivors were dropped this tick
        # because their group had no free way (their local counter and
        # un-synced pending are lost — the capacity-exhausted regime, the
        # analog of the reference LRU cache evicting an unexpired bucket
        # under pressure). Exposed as gauges so operators can see the
        # degraded regime the reference cannot surface.
        surv_total = jnp.sum(surv.astype(I64))
        kept_total = jnp.sum(kept.astype(I64))
        return new_table, new_pending, jnp.stack(
            [kept_total, surv_total - kept_total, merged_hits, over_hits]
        )

    def local(state: IciState, now):
        dev = jax.lax.axis_index(AXIS).astype(I64)
        native = _squeeze(state.table)
        words = state.pending[0]
        psum = lambda x: jax.lax.psum(x, AXIS)  # noqa: E731

        if not capped:
            gids = jnp.arange(G, dtype=I64)
            valid = jnp.ones(G, dtype=bool)
            new_t, new_p, totals = merge_block(
                dev, RK.to_wide(native), join(words[0], words[1]),
                gids, valid, now, psum,
            )
            diag = jnp.concatenate([
                totals[:2],
                jnp.stack([jnp.zeros((), I64), jnp.full((), G, I64),
                           jnp.full((), G, I64)]),
                totals[2:],
            ])[None, :]
            return (
                IciState(
                    table=_unsqueeze(RK.from_wide(new_t)),
                    pending=jnp.stack(split(new_p))[None],
                    tick=state.tick + 1,
                ),
                diag,
            )

        # Delta compaction: find groups needing sync (content diverges
        # across devices, or pending deltas exist anywhere), then merge
        # up to C of them on a compact gather. Independently salted
        # fingerprints make a cross-device hash collision (a diverged
        # group reading as clean) astronomically unlikely;
        # identical-content groups are exactly the ones the full merge
        # would leave unchanged. Expired-but-identical groups fool the
        # fingerprint (content equal everywhere) yet the full merge would
        # ERASE them; they are active too, so capped and unbounded sync
        # stay bit-identical. That flag is local-only: identical content
        # expires identically on every device, no collective needed.
        fps, has_pend, expired_any = signals(native, words, now, W)
        total = psum(
            jnp.concatenate([fps, has_pend.astype(fps.dtype)[None]])
        )
        diverged = (total[:-1] != fps * n_dev).any(axis=0)
        g_act = diverged | (total[-1] > 0) | expired_any
        # The most any chip counts: what every chip then chooses by.
        n_act = jax.lax.pmax(jnp.sum(g_act, dtype=jnp.int32), AXIS)

        # Rotate the scan start with `now` AND the tick counter so a
        # sustained backlog can't starve any group, even when `now` is
        # coarse enough to repeat across ticks.
        start = (
            _mix64(
                jnp.asarray(now, I64).astype(jnp.uint64)
                ^ (state.tick[0].astype(jnp.uint64) * jnp.uint64(
                    0x9E3779B97F4A7C15
                ))
            ).astype(I64)
            % G
        )
        act_rot = jnp.roll(g_act, -start)
        # The rank of each active group in scan order. An
        # associative_scan — not jnp.cumsum + jnp.nonzero(size=C), which
        # are three cumulative sums inside: on TPU those lower to
        # reduce-windows whose compile time explodes with length
        # (ops/census.py measured 205 s for one over 262,144 elements on
        # a v5e).
        rank = jax.lax.associative_scan(jnp.add, act_rot.astype(jnp.int32))

        def merge_at(width):
            """The tick's merge on a block of `width` groups: the first
            `width` active ones in scan order, compacted."""

            def merge(native, words):
                # Where the k-th active group lies: a binary search of
                # the ranks for each k (log2 G gathers of `width`), or
                # one scatter of every group's index to its rank (G
                # updates), whichever moves fewer elements: on a v5e, G
                # 262,144, the search takes 0.14 / 1.1 / 8.9 ms at 1,024
                # / 8,192 / 65,536 and the scatter 1.3 at any (PERF.md
                # §6, PR 31).
                if width * G.bit_length() < G:
                    idx_rot = jnp.searchsorted(
                        rank, jnp.arange(1, width + 1, dtype=jnp.int32)
                    ).astype(jnp.int32)
                    valid = idx_rot < G
                else:
                    in_block = act_rot & (rank <= width)
                    idx_rot = (
                        jnp.full((width,), -1, dtype=jnp.int32)
                        .at[jnp.where(in_block, rank - 1, width)]
                        .set(jnp.arange(G, dtype=jnp.int32), mode="drop")
                    )
                    valid = idx_rot >= 0
                gid = idx_rot.astype(I64) + start
                gids = jnp.where(  # G = sentinel
                    valid, jnp.where(gid >= G, gid - G, gid), G
                )
                slots = (
                    gids[:, None] * W + jnp.arange(W, dtype=I64)[None, :]
                ).reshape(width * W)

                native_c = RK.take_groups(native, gids, W)
                held = jnp.take(words, slots, axis=1, mode="clip")
                new_tc, new_pc, totals = merge_block(
                    dev, RK.to_wide(native_c), join(held[0], held[1]),
                    gids, valid, now, psum,
                )
                # Sentinel groups scatter to slot >= num_slots -> dropped.
                return (
                    RK.put_groups(native, gids, W, RK.from_wide(new_tc)),
                    words.at[:, slots].set(
                        jnp.stack(split(new_pc)), mode="drop"
                    ),
                    jnp.concatenate([
                        totals[:2],
                        jnp.stack([jnp.sum(valid.astype(I64)),
                                   jnp.full((), width, I64)]),
                        totals[2:],
                    ]),
                )

            return merge

        widths = _block_widths(C)
        rung = jnp.sum(
            n_act > jnp.asarray(widths[:-1], dtype=jnp.int32), dtype=jnp.int32
        )
        # Each width's merge as a loop of one turn or none, not a branch
        # of a `lax.switch`: a loop's state stays in its buffers, where
        # XLA:TPU copies the table into a conditional and out of it
        # (two to four copies of 134 MB a tick here, compiled for a
        # v5e; none this way).
        merged_state = (
            native, words,
            jax.lax.pcast(jnp.zeros(6, I64), AXIS, to="varying"),
        )
        for k, w in enumerate(widths):
            merged_state = jax.lax.fori_loop(
                0, (rung == k).astype(jnp.int32),
                lambda _, s, merge=merge_at(w): merge(s[0], s[1]),
                merged_state,
            )
        new_native, new_words, done = merged_state
        merged = done[2]

        # kept/dropped counters from UNSELECTED overflow groups carry
        # over from the previous tick's table unchanged; the gauges
        # reflect blocks actually merged this tick, plus the backlog of
        # active groups the cap pushed to the next tick.
        backlog = jnp.sum(g_act.astype(I64)) - merged
        diag = jnp.concatenate(
            [done[:2], backlog[None], done[2:]]
        )[None, :]
        return (
            IciState(
                table=_unsqueeze(new_native),
                pending=new_words[None],
                tick=state.tick + 1,
            ),
            diag,
        )

    sharded = jax.shard_map(
        local, mesh=mesh, in_specs=(P(AXIS), P()),
        out_specs=(P(AXIS), P(AXIS)),
    )

    @functools.partial(jax.jit, donate_argnums=(0,))
    def sync_fn(state: IciState, now):
        """Returns (new_state, diag) where diag is (n_dev, 7) int64:
        diag[d] = [overflow entries kept replica-local on device d (among
                   groups merged this tick), overflow survivors dropped
                   on device d this tick, active groups beyond the cap
                   left for the next tick (identical on every device; 0
                   when unbounded), groups merged this tick (identical
                   on every device; G when unbounded), the width in
                   groups of the block they were merged in (likewise),
                   hits of other replicas applied this tick to buckets
                   device d owns and held, those of them its buckets
                   could no longer take]."""
        with jax.named_scope("ici.tick"):  # profile metadata only
            return sharded(state, jnp.asarray(now, I64))

    return sync_fn
