"""Pallas fused decide kernel: one HBM pass for probe, paging, and update.

The XLA decide path is a chain of separately-materialized HBM ops —
narrow-slice gather -> way-select -> (paged: page-map gather) -> chosen-row
gather -> scatter — and each link is a full HBM round trip for the rows it
touches. This module collapses the chain into ONE Pallas program per wave
("Ragged Paged Attention" shape, PAPERS.md): the kernel

- folds the `ops/paged.py` page-map lookup INSIDE the kernel (a scalar
  SMEM read per lane while computing the DMA offset), so the PR 12
  "one extra gather" disappears from the paged hot path;
- DMAs each lane's contiguous (W, C) group block into VMEM once and keeps
  it resident across way-selection AND token/leaky arithmetic — each slot
  row crosses HBM exactly once (the XLA narrow path re-gathers the chosen
  row after the prefix probe; here it is already on-chip);
- writes exactly one row per active resident lane back via a guarded DMA
  (sentinel/non-resident lanes and padding lanes write NOTHING — the
  paged scatter-drop contract holds by construction, not by clamping);
- emits the admission/census scalars the PR 10/14 observatories consume
  (`ops/admission.py` / `ops/census.py` input conventions) as a fused
  side-output over the rows the wave wrote, for free.

Branch semantics are bit-exact with the XLA layouts: the kernel body
reuses the SHARED policy/arithmetic verbatim — `probe_ways` from
ops/fused.py and `_token_paths`/`_leaky_paths` from ops/decide.py — on
the VMEM-resident block, so the pallas path can never drift from the
oracle-fuzzed XLA path (tests/test_kernel_fuzz.py runs the differential
suite pallas-vs-XLA, flat and paged).

Three lowerings, resolved at dispatch time (`pallas_mode()`):

- "mosaic":    real `pl.pallas_call` on TPU backends.
- "interpret": the same `pl.pallas_call` with `interpret=True` — tier-1
  CPU tests exercise the kernel logic (DMA sequencing, SMEM page-map
  reads, guarded stores, grid accumulation) without a TPU.
- "reference": a plain-XLA lowering of the identical fused program (one
  block gather + shared compute + one scatter + fused side-outputs) for
  non-TPU backends where interpret-mode's per-lane emulation would be
  benchmark noise. All three share `_wave_compute`, so they are
  bit-exact with each other by construction.

Deliberate divergences from the XLA path, confined to SENTINEL
(non-resident-page) lanes — where the XLA kernels compute way selection
over clamped out-of-range gathers and can report garbage-derived
`evicted_hi/lo` / `unexpired_evictions`:

- the kernel treats a sentinel lane's group as EMPTY (zeroed block), so
  its way-choice metadata is deterministic: no spurious displaced-key
  report, no spurious unexpired-eviction count, `slot == num_slots`
  exactly. Response fields (status/remaining/reset_time) are unaffected
  in either path (state is zero-masked on `~exists` everywhere), and the
  dropped-write guarantee is identical.

Block size (`block_b`, the per-grid-step lane tile) is the autotuned
parameter — see runtime/kerneltune.py; `GUBER_PALLAS_BLOCK` pins it by
hand. TPU-side Mosaic lowering of the int64 policy arithmetic is staged
behind the tools/jobs/42_pallas_ab.py device job; tier-1 correctness
evidence runs interpret-mode.
"""

from __future__ import annotations

import functools
import os
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from gubernator_tpu.api.types import Algorithm, Behavior, Status
from gubernator_tpu.ops import fused as _f
from gubernator_tpu.ops import narrow as _n
from gubernator_tpu.ops.admission import ADMISSION_SHIFT
from gubernator_tpu.ops.decide import _leaky_paths, _token_paths
from gubernator_tpu.ops.fused import probe_ways
from gubernator_tpu.ops.layout import DecideOutput, RequestBatch
from gubernator_tpu.ops.packed import (
    META_ALGO_SHIFT,
    META_STATUS_SHIFT,
    META_USED,
    _pack_meta,
)

I64 = jnp.int64
I32 = jnp.int32

# Layouts this module lowers; everything else stays on the XLA path
# (ops/kernels.py silently keeps wide/packed on XLA under
# GUBER_KERNEL=pallas — they are diagnostic layouts, not serving ones).
PALLAS_LAYOUTS = ("narrow", "fused")

# Lane-tile bounds for the batch grid dimension. The default is the
# safe-everywhere fallback used when no autotuned choice is registered
# (runtime/kerneltune.py) and no GUBER_PALLAS_BLOCK override is set.
DEFAULT_BLOCK = 256
MIN_BLOCK = 8
MAX_BLOCK = 1024

# Fused side-output scalar slots (one (1, N_SCAL) accumulated output).
_S_HITS, _S_MISSES, _S_EVICTS, _S_OVER = 0, 1, 2, 3
_S_ADM_KEYS, _S_ADM_ADMITTED, _S_ADM_LIMIT = 4, 5, 6
_S_CENSUS_LIVE, _S_CENSUS_WASTE = 7, 8
N_SCAL = 9


class WaveScan(NamedTuple):
    """Admission/census side-output for ONE wave, over the rows the wave
    actually wrote (post-update state at the wave's `now`). These are the
    per-wave contributions the observatories accumulate; bit-exactness
    against the standalone scans is pinned by running
    `admission_oracle`/`census_oracle` over the written rows
    (tests/test_kernel_fuzz.py pallas section)."""

    adm_keys: jnp.ndarray  # () int64 written rows active for admission
    adm_admitted: jnp.ndarray  # () int64 sum clamp(limit - tokens, >=0)
    adm_limit: jnp.ndarray  # () int64 sum limit over admission-active rows
    census_live: jnp.ndarray  # () int64 written rows left used
    census_waste: jnp.ndarray  # () int64 written used rows already expired


# ---------------------------------------------------------------------------
# dispatch-time knobs (env reads at call time — GL004)

_block_choice: dict = {}  # (layout, paged) -> autotuned block_b


def register_block(layout: str, paged: bool, block: int) -> None:
    """Record the autotuned lane tile for (layout, paged) — called by
    runtime/kerneltune.py BEFORE the engine warms the decide program, so
    the warmed executable and the serving executable share one static
    configuration (the cold-compile invariant)."""
    _block_choice[(layout, bool(paged))] = _clamp_block(block)


def registered_block(layout: str, paged: bool) -> Optional[int]:
    return _block_choice.get((layout, bool(paged)))


def _clamp_block(block: int) -> int:
    b = max(MIN_BLOCK, min(int(block), MAX_BLOCK))
    # power-of-two tiles only: keeps the padded batch small and the
    # autotuner's candidate space aligned with the warm-bucket widths
    p = MIN_BLOCK
    while p * 2 <= b:
        p *= 2
    return p


def _pow2_at_least(n: int) -> int:
    p = MIN_BLOCK
    while p < n:
        p *= 2
    return p


def choose_block(layout: str, paged: bool, batch_size: int) -> int:
    """Lane tile for this dispatch: GUBER_PALLAS_BLOCK override, else the
    autotuned registration, else DEFAULT_BLOCK; never larger than the
    padded batch needs."""
    env = os.environ.get("GUBER_PALLAS_BLOCK", "").strip()
    if env:
        blk = _clamp_block(int(env))
    else:
        blk = _block_choice.get(
            (layout, bool(paged)), _clamp_block(DEFAULT_BLOCK)
        )
    return min(blk, _pow2_at_least(max(batch_size, 1)))


def pallas_mode() -> str:
    """Lowering for this dispatch: forced interpret, else mosaic on TPU,
    else the XLA reference lowering (bit-exact; see module docstring)."""
    v = os.environ.get("GUBER_PALLAS_INTERPRET", "auto").strip().lower()
    if v in ("1", "true", "yes", "on", "interpret"):
        return "interpret"
    if jax.default_backend() == "tpu":
        return "mosaic"
    return "reference"


# ---------------------------------------------------------------------------
# shared wave computation (bit-exactness seam: every lowering calls this)


def _pick_way(vals: jnp.ndarray, way: jnp.ndarray) -> jnp.ndarray:
    """Select vals[b, way[b]] via a one-hot reduce — the Mosaic-friendly
    spelling of the XLA kernels' vmap'd row indexing; bit-exact for
    integer selection (single non-zero term per lane)."""
    oh = (
        lax.broadcasted_iota(I64, vals.shape[:2], 1)
        == way.astype(I64)[:, None]
    )
    if vals.ndim == 3:
        oh = oh[:, :, None]
    return jnp.sum(jnp.where(oh, vals, 0), axis=1)


def _wave_compute(
    layout, rows, batch, now, n, resident, phys_grp, ways,
    *, probe=None, st_row=None,
):
    """One wave over a VMEM/registers-resident (B, W, C) block.

    rows      : the gathered group blocks, ZEROED for non-resident lanes.
    phys_grp  : (B,) physical group per lane (valid only where resident).
    probe     : optional pre-staged way-selection columns ({col: (B, W)})
                — the reference lowering gathers ONLY these off HBM.
    st_row    : optional pre-gathered selected row (B, C). When both
                overrides are given `rows` is never read (pass None);
                the mosaic/interpret kernels keep the VMEM-block path.
    Returns (new_row (B, C), out: DecideOutput, scan: WaveScan). Every
    value is computed with the exact arithmetic of the XLA layout impls
    (ops/narrow.py / ops/fused.py) — this function is shared by the
    mosaic, interpret, and reference lowerings.
    """
    if layout == "narrow":
        KHI, KLO, META, EXPC, INVC = _n.KHI, _n.KLO, _n.META, _n.EXP, _n.INV
        ncols = _n.NCOLS
    elif layout == "fused":
        KHI, KLO, META, EXPC, INVC = _f.KHI, _f.KLO, _f.META, _f.EXP, _f.INV
        ncols = _f.NCOLS
    else:  # pragma: no cover - guarded by PALLAS_LAYOUTS at the facade
        raise ValueError(f"pallas decide does not lower layout {layout!r}")

    if probe is None:
        probe = {
            KHI: rows[..., KHI], KLO: rows[..., KLO],
            META: rows[..., META], EXPC: rows[..., EXPC],
            INVC: rows[..., INVC],
        }
    exists, matched_way, insert_way, cat = probe_ways(
        probe[KHI], probe[KLO], probe[META], probe[EXPC], probe[INVC],
        batch, now,
    )
    way = jnp.where(exists, matched_way, insert_way)
    if st_row is None:
        st_row = _pick_way(rows, way)  # (B, C) — on-chip, no re-gather

    sel = _pick_way(cat, insert_way)
    evicts_live = (~exists) & (sel == 3) & batch.active

    old_used = (st_row[:, META] & META_USED) != 0
    displaced = (
        batch.active
        & ~exists
        & old_used
        & (
            (st_row[:, KHI] != batch.key_hi)
            | (st_row[:, KLO] != batch.key_lo)
        )
    )
    evicted_hi = jnp.where(displaced, st_row[:, KHI], 0)
    evicted_lo = jnp.where(displaced, st_row[:, KLO], 0)

    meta_sel = st_row[:, META]
    if layout == "narrow":
        limit_sel, burst_sel = _n._unpack_limbur(st_row[:, _n.LIMBUR])
        st = dict(
            algo=((meta_sel >> META_ALGO_SHIFT) & 1).astype(jnp.int8),
            status=((meta_sel >> META_STATUS_SHIFT) & 3).astype(jnp.int8),
            limit=limit_sel,
            duration=st_row[:, _n.DUR],
            remaining=st_row[:, _n.REM],
            stamp=st_row[:, _n.STM],
            expire_at=st_row[:, _n.EXP],
            burst=burst_sel,
            invalid_at=st_row[:, _n.INV],
        )
    else:
        st = dict(
            algo=((meta_sel >> META_ALGO_SHIFT) & 1).astype(jnp.int8),
            status=((meta_sel >> META_STATUS_SHIFT) & 3).astype(jnp.int8),
            limit=st_row[:, _f.LIM],
            duration=st_row[:, _f.DUR],
            remaining=st_row[:, _f.REM],
            stamp=st_row[:, _f.STM],
            expire_at=st_row[:, _f.EXP],
            burst=st_row[:, _f.BUR],
            invalid_at=st_row[:, _f.INV],
        )
    for k in st:
        st[k] = jnp.where(exists, st[k], jnp.zeros_like(st[k]))

    bhv = batch.behavior
    b_greg = (bhv & int(Behavior.DURATION_IS_GREGORIAN)) != 0
    b_reset = (bhv & int(Behavior.RESET_REMAINING)) != 0
    b_drain = (bhv & int(Behavior.DRAIN_OVER_LIMIT)) != 0

    tok_state, tok_resp = _token_paths(
        batch, st, b_greg, b_reset, b_drain, exists, now
    )
    lky_state, lky_resp = _leaky_paths(
        batch, st, b_greg, b_reset, b_drain, exists, now
    )

    is_leaky = batch.algo == jnp.int8(Algorithm.LEAKY_BUCKET)

    def both(t, l):
        return jnp.where(is_leaky, l, t)

    new_state = {k: both(tok_state[k], lky_state[k]) for k in tok_state}
    resp = {k: both(tok_resp[k], lky_resp[k]) for k in tok_resp}

    freed = ~new_state["used"]
    cols = [None] * ncols
    cols[KHI] = jnp.where(freed, 0, batch.key_hi)
    cols[KLO] = jnp.where(freed, 0, batch.key_lo)
    cols[META] = jnp.where(
        freed,
        0,
        _pack_meta(
            jnp.ones_like(freed),
            batch.algo,
            new_state["status"],
            jnp.broadcast_to(now, freed.shape),
        ),
    )
    cols[EXPC] = new_state["expire_at"]
    cols[INVC] = jnp.where(exists & ~freed, st["invalid_at"], 0)
    if layout == "narrow":
        cols[_n.LIMBUR] = _n._pack_limbur(
            new_state["limit"], new_state["burst"]
        )
        cols[_n.DUR] = new_state["duration"]
        cols[_n.REM] = new_state["remaining"]
        cols[_n.STM] = new_state["stamp"]
    else:
        cols[_f.LIM] = new_state["limit"]
        cols[_f.DUR] = new_state["duration"]
        cols[_f.REM] = new_state["remaining"]
        cols[_f.STM] = new_state["stamp"]
        cols[_f.BUR] = new_state["burst"]
    new_row = jnp.stack([c.astype(I64) for c in cols], axis=-1)  # (B, C)

    # Sentinel lanes land exactly on n (the drop index); resident lanes
    # on their physical slot. Inactive lanes are n, as in the XLA path.
    slot = jnp.where(
        resident, phys_grp.astype(I64) * ways + way, jnp.int64(n)
    )
    idx = jnp.where(batch.active, slot, n)

    act = batch.active
    out = DecideOutput(
        status=jnp.where(act, resp["status"], jnp.int8(0)),
        limit=jnp.where(act, batch.limit, 0),
        remaining=jnp.where(act, resp["remaining"], 0),
        reset_time=jnp.where(act, resp["reset_time"], 0),
        slot=idx,
        evicted_hi=evicted_hi,
        evicted_lo=evicted_lo,
        freed=act & freed,
        hits=jnp.sum(act & exists),
        misses=jnp.sum(act & ~exists),
        unexpired_evictions=jnp.sum(evicts_live),
        over_limit=jnp.sum(act & resp["over"]),
    )

    # Fused admission/census side-output over the rows this wave WROTE,
    # with the standalone scans' exact conventions (ops/admission.py
    # `_admission_wide`, ops/census.py `_census_wide`) applied to the
    # post-update state at this wave's `now`.
    written = act & resident
    row_used = written & ~freed
    lim_new = new_state["limit"]
    exp_new = new_state["expire_at"]
    adm_active = row_used & (lim_new > 0) & (exp_new > now)
    tokens = jnp.where(
        is_leaky, new_state["remaining"] >> ADMISSION_SHIFT,
        new_state["remaining"],
    )
    admitted = jnp.where(
        adm_active, jnp.maximum(lim_new - tokens, jnp.int64(0)), jnp.int64(0)
    )
    scan = WaveScan(
        adm_keys=jnp.sum(adm_active, dtype=I64),
        adm_admitted=jnp.sum(admitted, dtype=I64),
        adm_limit=jnp.sum(
            jnp.where(adm_active, lim_new, jnp.int64(0)), dtype=I64
        ),
        census_live=jnp.sum(row_used, dtype=I64),
        census_waste=jnp.sum(row_used & (exp_new <= now), dtype=I64),
    )
    return new_row, out, scan


def _scalars_vector(out: DecideOutput, scan: WaveScan) -> jnp.ndarray:
    v = [jnp.int64(0)] * N_SCAL
    v[_S_HITS] = out.hits.astype(I64)
    v[_S_MISSES] = out.misses.astype(I64)
    v[_S_EVICTS] = out.unexpired_evictions.astype(I64)
    v[_S_OVER] = out.over_limit.astype(I64)
    v[_S_ADM_KEYS] = scan.adm_keys
    v[_S_ADM_ADMITTED] = scan.adm_admitted
    v[_S_ADM_LIMIT] = scan.adm_limit
    v[_S_CENSUS_LIVE] = scan.census_live
    v[_S_CENSUS_WASTE] = scan.census_waste
    return jnp.stack(v)


# ---------------------------------------------------------------------------
# reference lowering (plain XLA, same fused structure, bit-exact)


def _reference_wave(layout, data, page_map, batch, now, *, ways, gpp):
    """Plain-XLA lowering with the mosaic kernel's read discipline
    translated to gather shapes; for narrow: a probe gather of ONLY the
    way-selection columns plus ONE full-row gather at the selected slot —
    never a full (B, W, C) block off HBM. The gathered pieces are
    reassembled into the (B, W, C) layout `_wave_compute` expects (true
    probe columns everywhere, selected-row state one-hot-placed at its
    way, zeros elsewhere); since the shared compute body reads state
    columns only through `_pick_way`'s one-hot reduce, the assembly is
    bit-exact with a full gather while moving ~half the bytes."""
    fused = layout == "fused"
    n = _f.FusedTable(data).num_slots if fused else data.shape[0]
    if page_map is not None:
        g32 = batch.group.astype(I32)
        pp = page_map[g32 // gpp]
        resident = pp >= 0
        phys_grp = jnp.where(resident, pp * gpp + g32 % gpp, 0)
    else:
        resident = jnp.ones_like(batch.active)
        phys_grp = batch.group.astype(I32)
    way_ix = (
        phys_grp.astype(I64)[:, None] * ways
        + jnp.arange(ways, dtype=I64)[None, :]
    )
    res_bw = resident[:, None]
    if fused:
        # A fused slot is one window of words (ops/fused.py): the probe
        # reads whole slots, which is the block the kernels hold in VMEM.
        rows = jnp.where(
            res_bw[..., None], _f._gather_groups(data, phys_grp, ways), 0
        )
        new_row, out, scan = _wave_compute(
            layout, rows, batch, now, n, resident, phys_grp, ways
        )
        return _f._scatter(data, out.slot, new_row), out, scan
    # narrow: the probe columns ARE the row prefix (the layout's design)
    hot = jnp.where(
        res_bw[..., None], _n._gather_cols(data, way_ix, _n.N_HOT), 0
    )
    probe = {
        _n.KHI: hot[..., _n.KHI], _n.KLO: hot[..., _n.KLO],
        _n.META: hot[..., _n.META], _n.EXP: hot[..., _n.EXP],
        _n.INV: hot[..., _n.INV],
    }
    KHI, KLO, META, EXPC, INVC = _n.KHI, _n.KLO, _n.META, _n.EXP, _n.INV
    # Same way selection _wave_compute re-derives from the same probe
    # dict (same function, same inputs — XLA CSEs the duplicate); the
    # selected-row gather this slot feeds is therefore bit-identical to
    # the VMEM-block path's `_pick_way(rows, way)`.
    exists, matched_way, insert_way, _cat = probe_ways(
        probe[KHI], probe[KLO], probe[META], probe[EXPC], probe[INVC],
        batch, now,
    )
    way = jnp.where(exists, matched_way, insert_way)
    sel_slot = phys_grp.astype(I64) * ways + way
    sel_row = jnp.where(res_bw, data[sel_slot], 0)  # (B, C)
    new_row, out, scan = _wave_compute(
        layout, None, batch, now, n, resident, phys_grp, ways,
        probe=probe, st_row=sel_row,
    )
    new_data = data.at[out.slot].set(new_row, mode="drop")
    return new_data, out, scan


# ---------------------------------------------------------------------------
# pallas lowering (mosaic on TPU, interpret on CPU)

# Batch columns fed to the kernel as (block_b,) VMEM blocks, in order.
_VMEM_COLS = (
    "key_hi", "key_lo", "hits", "limit", "duration", "rate_num",
    "eff_duration", "greg_expire", "burst", "created_at",
)


def _make_kernel(layout, ways, block_b, n, paged, gpp, per_line):
    """Build the kernel body for one static configuration. A fused table
    is lines of `per_line` slots of uint32 words (ops/fused.py): its
    slots are loaded and stored one window each and widened to int64 in
    VMEM; a narrow table is (n, NCOLS) int64 rows (`per_line` 0)."""
    words = _f.SLOT_WORDS

    def slot_window(ref, slot):
        """The window of `ref` (a fused table) that is slot `slot`."""
        return ref.at[
            pl.ds(slot // per_line, 1), pl.ds((slot % per_line) * words, words)
        ]

    def kernel(*refs):
        it = iter(refs)
        group_ref = next(it)  # SMEM (block_b,) i32
        active_ref = next(it)  # SMEM (block_b,) i32
        algo_ref = next(it)  # SMEM (block_b,) i32
        behavior_ref = next(it)  # SMEM (block_b,) i32
        now_ref = next(it)  # SMEM (1,) i64
        pmap_ref = next(it) if paged else None  # SMEM (n_log_pages,) i32
        vmem_cols = [next(it) for _ in _VMEM_COLS]  # VMEM (block_b,) i64
        data_ref = next(it)  # ANY, the table's own shape — aliased input
        out_data_ref = next(it)  # ANY — aliased output (same buffer)
        status_ref = next(it)  # VMEM (block_b,) i32
        limit_ref = next(it)
        remaining_ref = next(it)
        reset_ref = next(it)
        slot_ref = next(it)
        ehi_ref = next(it)
        elo_ref = next(it)
        freed_ref = next(it)  # VMEM (block_b,) i32
        scal_ref = next(it)  # VMEM (1, N_SCAL) i64, accumulated
        rows = next(it)  # VMEM scratch (block_b, W, C) i64 / (.., words) u32
        newrow = next(it)  # VMEM scratch (block_b, C) i64 / (.., words) u32
        physg = next(it)  # SMEM scratch (block_b,) i32
        res = next(it)  # SMEM scratch (block_b,) i32
        slotg = next(it)  # SMEM scratch (block_b,) i32
        lsem = next(it)  # DMA sems (block_b,)
        ssem = next(it)  # DMA sems (block_b,)

        @pl.when(pl.program_id(0) == 0)
        def _init():
            scal_ref[...] = jnp.zeros_like(scal_ref)

        now = now_ref[0]

        def _load_copies(j):
            start = physg[j] * ways
            if not per_line:
                return [pltpu.make_async_copy(
                    data_ref.at[pl.ds(start, ways), :], rows.at[j], lsem.at[j]
                )]
            return [
                pltpu.make_async_copy(
                    slot_window(data_ref, start + k),
                    rows.at[j, pl.ds(k, 1), :], lsem.at[j],
                )
                for k in range(ways)
            ]

        # Phase 1: translate + start one DMA per lane. The page-map
        # lookup happens HERE, as a scalar SMEM read folded into the DMA
        # offset computation — the paged path's former standalone gather.
        def load(j, _):
            g = group_ref[j]
            if paged:
                pp = pmap_ref[g // gpp]
                r = pp >= 0
                physg[j] = jnp.where(r, pp * gpp + g % gpp, 0)
                res[j] = r.astype(I32)
            else:
                physg[j] = g
                res[j] = jnp.int32(1)

            @pl.when(res[j] != 0)
            def _go():
                for c in _load_copies(j):
                    c.start()

            @pl.when(res[j] == 0)
            def _zero():
                # Sentinel lane: treat the group as empty (deterministic
                # way-choice metadata; see module docstring).
                rows[j] = jnp.zeros(rows.shape[1:], dtype=rows.dtype)

            return 0

        lax.fori_loop(0, block_b, load, 0)

        def wait(j, _):
            @pl.when(res[j] != 0)
            def _w():
                for c in _load_copies(j):
                    c.wait()

            return 0

        lax.fori_loop(0, block_b, wait, 0)

        # Phase 2: the whole wave's policy + token arithmetic on the
        # VMEM-resident block — the shared bit-exact compute.
        act = active_ref[...] != 0
        batch = RequestBatch(
            key_hi=vmem_cols[0][...],
            key_lo=vmem_cols[1][...],
            group=group_ref[...],
            algo=algo_ref[...].astype(jnp.int8),
            behavior=behavior_ref[...],
            hits=vmem_cols[2][...],
            limit=vmem_cols[3][...],
            duration=vmem_cols[4][...],
            rate_num=vmem_cols[5][...],
            eff_duration=vmem_cols[6][...],
            greg_expire=vmem_cols[7][...],
            burst=vmem_cols[8][...],
            created_at=vmem_cols[9][...],
            active=act,
        )
        resident = res[...] != 0
        block = _f.join_words(rows[...]) if per_line else rows[...]
        new_row, out, scan = _wave_compute(
            layout, block, batch, now, n, resident, physg[...], ways
        )
        newrow[...] = _f.split_words(new_row) if per_line else new_row
        status_ref[...] = out.status.astype(I32)
        limit_ref[...] = out.limit
        remaining_ref[...] = out.remaining
        reset_ref[...] = out.reset_time
        slot_ref[...] = out.slot
        ehi_ref[...] = out.evicted_hi
        elo_ref[...] = out.evicted_lo
        freed_ref[...] = out.freed.astype(I32)
        scal_ref[...] += _scalars_vector(out, scan)[None, :]
        # Physical row index for the store loop's scalar reads (row
        # indices fit i32: tables cap far below 2^31 slots).
        slotg[...] = jnp.where(
            act & resident, out.slot, jnp.int64(n)
        ).astype(I32)

        def _store_copy(j):
            return pltpu.make_async_copy(
                newrow.at[pl.ds(j, 1), :],
                slot_window(out_data_ref, slotg[j]) if per_line
                else out_data_ref.at[pl.ds(slotg[j], 1), :],
                ssem.at[j],
            )

        # Phase 3: one guarded row store per active resident lane.
        # Sentinel and padding lanes start no DMA at all — scatter-drop
        # by omission. Distinct-group batches (the assembler invariant)
        # make the unsynchronized per-lane stores race-free.
        def store(j, _):
            @pl.when(slotg[j] < n)
            def _go():
                _store_copy(j).start()

            return 0

        lax.fori_loop(0, block_b, store, 0)

        def drain(j, _):
            @pl.when(slotg[j] < n)
            def _w():
                _store_copy(j).wait()

            return 0

        lax.fori_loop(0, block_b, drain, 0)

    return kernel


@functools.lru_cache(maxsize=None)
def _build_pallas_call(
    layout, ways, data_shape, bp, block_b, paged, gpp, n_log_pages, interpret
):
    if layout == "fused":
        per_line = data_shape[-1] // _f.SLOT_WORDS
        n = data_shape[0] * per_line
        row, dtype = (_f.SLOT_WORDS,), jnp.uint32
    else:
        per_line = 0
        n = data_shape[0]
        row, dtype = data_shape[1:], I64
    nb = bp // block_b
    grid = (nb,)

    def blk(space=None):
        if space is None:
            return pl.BlockSpec((block_b,), lambda i: (i,))
        return pl.BlockSpec((block_b,), lambda i: (i,), memory_space=space)

    in_specs = [
        blk(pltpu.SMEM),  # group
        blk(pltpu.SMEM),  # active
        blk(pltpu.SMEM),  # algo
        blk(pltpu.SMEM),  # behavior
        pl.BlockSpec(memory_space=pltpu.SMEM),  # now
    ]
    if paged:
        in_specs.append(pl.BlockSpec(memory_space=pltpu.SMEM))  # page_map
    in_specs.extend(blk() for _ in _VMEM_COLS)
    in_specs.append(pl.BlockSpec(memory_space=pl.ANY))  # data
    data_index = len(in_specs) - 1

    out_specs = [
        pl.BlockSpec(memory_space=pl.ANY),  # data (aliased)
        blk(),  # status (i32)
        blk(),  # limit
        blk(),  # remaining
        blk(),  # reset_time
        blk(),  # slot
        blk(),  # evicted_hi
        blk(),  # evicted_lo
        blk(),  # freed (i32)
        pl.BlockSpec((1, N_SCAL), lambda i: (0, 0)),  # scalars, accumulated
    ]
    out_shape = [
        jax.ShapeDtypeStruct(data_shape, dtype),
        jax.ShapeDtypeStruct((bp,), I32),
        jax.ShapeDtypeStruct((bp,), I64),
        jax.ShapeDtypeStruct((bp,), I64),
        jax.ShapeDtypeStruct((bp,), I64),
        jax.ShapeDtypeStruct((bp,), I64),
        jax.ShapeDtypeStruct((bp,), I64),
        jax.ShapeDtypeStruct((bp,), I64),
        jax.ShapeDtypeStruct((bp,), I32),
        jax.ShapeDtypeStruct((1, N_SCAL), I64),
    ]
    scratch_shapes = [
        pltpu.VMEM((block_b, ways) + row, dtype),
        pltpu.VMEM((block_b,) + row, dtype),
        pltpu.SMEM((block_b,), I32),
        pltpu.SMEM((block_b,), I32),
        pltpu.SMEM((block_b,), I32),
        pltpu.SemaphoreType.DMA((block_b,)),
        pltpu.SemaphoreType.DMA((block_b,)),
    ]
    kernel = _make_kernel(layout, ways, block_b, n, paged, gpp, per_line)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=scratch_shapes,
        input_output_aliases={data_index: 0},
        interpret=bool(interpret),
    )


def _pad_to(x, bp):
    b = x.shape[0]
    if b == bp:
        return x
    pad = [(0, bp - b)] + [(0, 0)] * (x.ndim - 1)
    return jnp.pad(x, pad)


def _pallas_wave(
    layout, data, page_map, batch, now, *, ways, gpp, block_b, interpret
):
    b = batch.key_hi.shape[0]
    bp = -(-b // block_b) * block_b
    paged = page_map is not None
    call = _build_pallas_call(
        layout, ways, data.shape, bp, block_b, paged, gpp,
        page_map.shape[0] if paged else 0, interpret,
    )
    pb = jax.tree.map(lambda x: _pad_to(jnp.asarray(x, x.dtype), bp), batch)
    args = [
        pb.group.astype(I32),
        pb.active.astype(I32),
        pb.algo.astype(I32),
        pb.behavior.astype(I32),
        jnp.asarray(now, dtype=I64).reshape((1,)),
    ]
    if paged:
        args.append(page_map.astype(I32))
    args.extend(getattr(pb, c).astype(I64) for c in _VMEM_COLS)
    args.append(data)
    (
        new_data, status, limit, remaining, reset_time, slot,
        ehi, elo, freed, scal,
    ) = call(*args)
    sv = scal[0]
    out = DecideOutput(
        status=status[:b].astype(jnp.int8),
        limit=limit[:b],
        remaining=remaining[:b],
        reset_time=reset_time[:b],
        slot=slot[:b],
        evicted_hi=ehi[:b],
        evicted_lo=elo[:b],
        freed=freed[:b] != 0,
        hits=sv[_S_HITS],
        misses=sv[_S_MISSES],
        unexpired_evictions=sv[_S_EVICTS],
        over_limit=sv[_S_OVER],
    )
    scan = WaveScan(
        adm_keys=sv[_S_ADM_KEYS],
        adm_admitted=sv[_S_ADM_ADMITTED],
        adm_limit=sv[_S_ADM_LIMIT],
        census_live=sv[_S_CENSUS_LIVE],
        census_waste=sv[_S_CENSUS_WASTE],
    )
    return new_data, out, scan


def _wave(layout, data, page_map, batch, now, *, ways, gpp, block_b, mode):
    """One decide wave through the selected lowering; the traceable core
    every public entry point (and the shard_map raw path) goes through."""
    now = jnp.asarray(now, dtype=I64)
    if mode == "reference":
        return _reference_wave(
            layout, data, page_map, batch, now, ways=ways, gpp=gpp
        )
    return _pallas_wave(
        layout, data, page_map, batch, now,
        ways=ways, gpp=gpp, block_b=block_b,
        interpret=(mode == "interpret"),
    )


# ---------------------------------------------------------------------------
# public entry points (flat + paged, single wave + scan, raw for shard_map)


@functools.partial(
    jax.jit,
    static_argnames=("layout", "ways", "block_b", "mode"),
    donate_argnums=(0,),
)
def _flat_jit(data, batch, now, *, layout, ways, block_b, mode):
    return _wave(
        layout, data, None, batch, now,
        ways=ways, gpp=0, block_b=block_b, mode=mode,
    )


@functools.partial(
    jax.jit,
    static_argnames=("layout", "ways", "block_b", "mode"),
    donate_argnums=(0,),
)
def _flat_scan_jit(data, batches, nows, *, layout, ways, block_b, mode):
    def step(d, xs):
        b, t = xs
        d, out, _scan = _wave(
            layout, d, None, b, t,
            ways=ways, gpp=0, block_b=block_b, mode=mode,
        )
        return d, out

    return lax.scan(step, data, (batches, nows))


@functools.partial(
    jax.jit,
    static_argnames=("layout", "ways", "gpp", "block_b", "mode"),
    donate_argnums=(0,),
)
def _paged_jit(data, page_map, batch, now, *, layout, ways, gpp, block_b, mode):
    return _wave(
        layout, data, page_map, batch, now,
        ways=ways, gpp=gpp, block_b=block_b, mode=mode,
    )


@functools.partial(
    jax.jit,
    static_argnames=("layout", "ways", "gpp", "block_b", "mode"),
    donate_argnums=(0,),
)
def _paged_scan_jit(
    data, page_map, batches, nows, *, layout, ways, gpp, block_b, mode
):
    def step(d, xs):
        b, t = xs
        d, out, _scan = _wave(
            layout, d, page_map, b, t,
            ways=ways, gpp=gpp, block_b=block_b, mode=mode,
        )
        return d, out

    return lax.scan(step, data, (batches, nows))


def _check_layout(layout: str) -> None:
    if layout not in PALLAS_LAYOUTS:
        raise ValueError(
            f"pallas decide lowers {PALLAS_LAYOUTS}, not {layout!r}"
        )


def decide_flat(table, batch, now, *, layout: str, ways: int):
    """Registry-facing flat decide: (table, batch, now) -> (table', out).
    Resolves lowering + lane tile at dispatch time, then runs one cached
    jitted program per static configuration."""
    _check_layout(layout)
    mode = pallas_mode()
    blk = choose_block(layout, False, batch.key_hi.shape[0])
    data, out, _scan = _flat_jit(
        table.data, batch, now,
        layout=layout, ways=ways, block_b=blk, mode=mode,
    )
    return type(table)(data), out


def decide_flat_with_scan(table, batch, now, *, layout: str, ways: int):
    """decide_flat plus the fused WaveScan side-output (the observatory
    seam; also the bit-exactness surface the fuzz suite pins)."""
    _check_layout(layout)
    mode = pallas_mode()
    blk = choose_block(layout, False, batch.key_hi.shape[0])
    data, out, scan = _flat_jit(
        table.data, batch, now,
        layout=layout, ways=ways, block_b=blk, mode=mode,
    )
    return type(table)(data), out, scan


def decide_scan_flat(table, batches, nows, *, layout: str, ways: int):
    _check_layout(layout)
    mode = pallas_mode()
    blk = choose_block(layout, False, batches.key_hi.shape[1])
    data, outs = _flat_scan_jit(
        table.data, batches, nows,
        layout=layout, ways=ways, block_b=blk, mode=mode,
    )
    return type(table)(data), outs


def decide_paged(pt, batch, now, *, layout: str, ways: int, gpp: int):
    """Paged decide with the page-map translation folded into the kernel
    (no standalone translation gather). pt is an ops.paged.PagedTable."""
    _check_layout(layout)
    mode = pallas_mode()
    blk = choose_block(layout, True, batch.key_hi.shape[0])
    data, out, _scan = _paged_jit(
        pt.data.data, pt.page_map, batch, now,
        layout=layout, ways=ways, gpp=gpp, block_b=blk, mode=mode,
    )
    inner = type(pt.data)(data)
    return type(pt)(inner, pt.page_map), out


def decide_paged_with_scan(pt, batch, now, *, layout: str, ways: int, gpp: int):
    _check_layout(layout)
    mode = pallas_mode()
    blk = choose_block(layout, True, batch.key_hi.shape[0])
    data, out, scan = _paged_jit(
        pt.data.data, pt.page_map, batch, now,
        layout=layout, ways=ways, gpp=gpp, block_b=blk, mode=mode,
    )
    inner = type(pt.data)(data)
    return type(pt)(inner, pt.page_map), out, scan


def decide_scan_paged(pt, batches, nows, *, layout: str, ways: int, gpp: int):
    _check_layout(layout)
    mode = pallas_mode()
    blk = choose_block(layout, True, batches.key_hi.shape[1])
    data, outs = _paged_scan_jit(
        pt.data.data, pt.page_map, batches, nows,
        layout=layout, ways=ways, gpp=gpp, block_b=blk, mode=mode,
    )
    inner = type(pt.data)(data)
    return type(pt)(inner, pt.page_map), outs


def raw_decide_flat(table, batch, now, *, layout: str, ways: int):
    """UNJITTED flat decide for composition inside shard_map (the
    parallel/mesh.py ownership programs) — same contract as the XLA
    RawKernels.decide. Lowering/tile resolve at trace time."""
    _check_layout(layout)
    mode = pallas_mode()
    blk = choose_block(layout, False, batch.key_hi.shape[0])
    data, out, _scan = _wave(
        layout, table.data, None, batch, now,
        ways=ways, gpp=0, block_b=blk, mode=mode,
    )
    return type(table)(data), out


def raw_decide_paged(pt, batch, now, *, layout: str, ways: int, gpp: int):
    """UNJITTED paged decide, the page-map translation folded into the
    kernel: what the paged packed entry (ops/paged.py) traces."""
    _check_layout(layout)
    mode = pallas_mode()
    blk = choose_block(layout, True, batch.key_hi.shape[0])
    data, out, _scan = _wave(
        layout, pt.data.data, pt.page_map, batch, now,
        ways=ways, gpp=gpp, block_b=blk, mode=mode,
    )
    inner = type(pt.data)(data)
    return type(pt)(inner, pt.page_map), out
