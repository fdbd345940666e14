"""Columnar serving edge: bytes -> columns -> kernel -> bytes.

The object path (protobuf message -> dataclass -> pump -> demux) costs
~10-20µs of Python per request item; this path serves an entire
GetRateLimits/GetPeerRateLimits call with no per-item Python at all
(native wire parse, vectorized wave assembly, one jitted decide per
wave, native response build). It is an OPTIMIZATION, not a semantic
fork: every batch it cannot serve byte-identically falls back to the
object path (equivalence is fuzz-tested in tests/test_fastpath.py).

Fallback triggers:
- native library unavailable, malformed/empty/oversized batch;
- any item carrying metadata (trace context) or failing validation
  (those need per-item error strings);
- DURATION_IS_GREGORIAN items on a peer call or an all-Gregorian batch
  (V1 mixed batches keep the columnar lanes and splice the Gregorian
  items through the object path, like GLOBAL's round-5 lane split);
- a key this node does not own (peer forwarding), checked with the
  vectorized ring mask — GetPeerRateLimits skips this check because
  forwarded items are owned by construction;
- engine not eligible (wave/lane overflow); a daemon with a Loader but
  no Store keeps the object path so the key-string dictionary stays
  complete for snapshots without columnar string-decode overhead.

A Store does NOT fall back: check_columns runs the object path's exact
per-wave sequence (probe -> read-through -> decide -> write-behind,
reference algorithms.go:45-51, 149-153) with request objects built only
for actual miss lanes.
"""

from __future__ import annotations

import time
from typing import Optional

import numpy as np

from gubernator_tpu import wire
from gubernator_tpu.api.types import Behavior
from gubernator_tpu.parallel import hash_ring
from gubernator_tpu.utils import tracing

MAX_BATCH_SIZE = 1000


def _committed_error():
    from gubernator_tpu.runtime.engine import TableCommittedError

    return TableCommittedError

# Gregorian durations need host-side calendar math the columnar decide
# doesn't carry — those ITEMS are pinned to the object path (via the
# mixed splice on V1 calls; whole-batch fallback on peer calls).
_SLOW_BEHAVIOR = int(Behavior.DURATION_IS_GREGORIAN)
_GLOBAL = int(Behavior.GLOBAL)
_DRAIN = int(Behavior.DRAIN_OVER_LIMIT)
_MULTI_REGION = int(Behavior.MULTI_REGION)
_RESET = int(Behavior.RESET_REMAINING)

_RING_VARIANT = {
    hash_ring.fnv1_64: "fnv1",
    hash_ring.fnv1a_64: "fnv1a",
    hash_ring.fnv1a_mix_64: "fnv1a-mix",
}


import os


def _disabled() -> bool:
    # Read per call, NOT at import: the daemon's --config file is
    # injected into os.environ after this module may already have been
    # imported (guberlint GL004).
    return os.environ.get("GUBER_DISABLE_FAST_EDGE", "") in ("1", "true")


def enabled(svc) -> bool:
    """Static eligibility for this service instance."""
    return (
        not _disabled()
        and getattr(svc, "fast_edge", False)
        and wire.available()
        and hasattr(svc.engine, "check_columns")
        # GUBER_STAGE_METADATA promises per-response diagnostics
        # (stage_breakdown_us, global_staleness_ms) that only the object
        # path attaches — a diagnostics mode, so it trades the fast edge
        # for the richer responses rather than silently dropping them.
        and not getattr(
            getattr(svc.engine, "cfg", None), "stage_metadata", False
        )
        # GUBER_RETRY_AFTER promises retry_after_ms on OVER_LIMIT
        # responses, which only the object path attaches — same
        # trade as stage_metadata above.
        and not getattr(svc, "retry_after", False)
    )


class _Phases:
    """try_serve's stages of the call's timeline, one open at a time:
    call.parse -> call.engine -> call.build (docs/monitoring.md
    "Tracing the pipeline")."""

    __slots__ = ("call", "open")

    def __init__(self, call):
        self.call = call
        self.open = None

    def enter(self, name: str) -> None:
        self.close()
        self.open = tracing.stage(name, self.call, self.call.ids)
        self.open.__enter__()

    def close(self) -> None:
        if self.open is not None:
            self.open.__exit__(None, None, None)
            self.open = None


def try_serve(svc, data: bytes, peer_call: bool, call=tracing.NO_CALL):
    """Serve one call's raw request bytes columnar-fast, on a serving
    executor thread. `call` (tracing.CallRecord) takes the stages and,
    where the call leaves the columnar path, the reason.

    Returns:
    - bytes — the complete response (all items served columnar);
    - ("mixed", n, local_pos, local_arrays, nonlocal_reqs, md) — locally
      served items (owned, plus ALL GLOBAL items) already DECIDED
      columnar; the async caller forwards `nonlocal_reqs` through the
      object path and splices with merge_mixed() (V1 only; peer calls
      are all-local by construction). `md` carries the GLOBAL non-owner
      owner-metadata spans, or None;
    - None — fall back to the object path entirely.

    GLOBAL items: V1 calls are answered from the local table whether
    owned or not (reference gubernator.go:395-421), with the
    replication legs queued through the GlobalManager after the decide
    commits — queue_update for owned items, queue_hit plus
    metadata={"owner": ...} for non-owned. Peer relays apply drain
    semantics at the owner (DRAIN_OVER_LIMIT forced) and queue the
    broadcast. Engines that route GLOBAL internally (ici mode) receive
    the flag unstripped and decide through their replica tier; items
    carrying trace metadata keep the object path.
    """
    call.mark("executor_wait")
    # One call in sixteen, by its sequence number, reads this thread's
    # CPU clock at both ends (a 7 us system call each on the TPU
    # host): CPU against wall is the call's own queueing for the
    # interpreter lock (docs/monitoring.md "Tracing the pipeline").
    sampled = call.seq & 15 == 0 and call.seq > 0
    if sampled:
        wall0 = time.perf_counter_ns()
        cpu0 = time.thread_time_ns()
    # Until _try_serve says which path serves the call: it raised.
    call.served("object", "error")
    phases = _Phases(call)
    # The request span's context does not follow the call onto this
    # thread by itself (run_in_executor copies no context).
    ctx = tracing.attached(call.otel_ctx) if call.otel_ctx else None
    if ctx is not None:
        ctx.__enter__()
    phases.enter("call.parse")
    try:
        return _try_serve(svc, data, peer_call, call, phases)
    finally:
        phases.close()
        if ctx is not None:
            ctx.__exit__(None, None, None)
        if sampled:
            cpu_ns = time.thread_time_ns() - cpu0
            wall_ns = time.perf_counter_ns() - wall0
            cpu, wall = svc.metrics.call_cpu_children[call.kind + call.path]
            cpu.observe(cpu_ns * 1e-9)
            wall.observe(wall_ns * 1e-9)


def _try_serve(svc, data: bytes, peer_call: bool, call, phases):
    """try_serve's body; `phases` moves the call's timeline on."""
    cols = wire.parse_requests(data)
    if cols is None or cols.n == 0 or cols.n > MAX_BATCH_SIZE:
        call.served("object", "error")
        return None
    if cols.slow.any():
        call.served("object", "slow_item")
        return None
    # DURATION_IS_GREGORIAN needs host-side calendar math the columnar
    # decide doesn't carry — but those ITEMS ride the mixed return's
    # object-path lane (the same split GLOBAL lanes got in round 5)
    # instead of demoting the whole batch. Peer calls cannot return
    # "mixed", and an all-Gregorian batch has no columnar work left.
    greg = (cols.behavior & _SLOW_BEHAVIOR) != 0
    has_greg = bool(greg.any())
    if has_greg and (peer_call or bool(greg.all())):
        call.served("object", "gregorian")
        return None
    if not peer_call and getattr(svc, "force_global", False):
        # GUBER_FORCE_GLOBAL: every V1 item becomes GLOBAL (the same OR
        # the object path applies per item, server.py).
        cols.behavior = cols.behavior | np.int64(_GLOBAL)
    g_mask = (cols.behavior & _GLOBAL) != 0
    has_global = bool(g_mask.any())
    # ici-mode engines route GLOBAL internally (replica tier): the
    # GLOBAL bit must reach the engine unstripped; the daemon-level
    # replication legs + owner metadata are identical.
    strip_global = not getattr(svc.engine, "routes_global_internally", False)
    if peer_call and has_global:
        # Owner applying relayed GLOBAL hits always drains (reference
        # gubernator.go:510-512) and queues a broadcast; items with
        # trace metadata took the object path already (cols.slow).
        cols.behavior = np.where(
            g_mask, cols.behavior | np.int64(_DRAIN), cols.behavior
        )
    # Validation needs per-item error strings -> object path.
    key_lens = np.diff(cols.key_offsets)
    if np.any(cols.name_lens == 0) or np.any(
        key_lens - cols.name_lens - 1 == 0
    ):
        call.served("object", "slow_item")
        return None
    local = None
    forwards = False  # some items go to the peer that owns them
    g_owned = g_mask  # standalone daemon: owner of everything
    owner_addrs = None
    ring_mask = None
    if not peer_call:
        picker = svc.picker
        if picker is not None and picker.peers():
            variant = _RING_VARIANT.get(getattr(picker, "hash_fn", None))
            if variant is None:
                call.served("object", "ring")
                return None
            ring_h = wire.fnv1_batch(cols.key_data, cols.key_offsets, variant)
            mask = np.asarray(picker.local_mask(ring_h), dtype=bool)
            ring_mask = mask
            if has_global:
                # GLOBAL items are answered from the LOCAL table whether
                # owned or not (reference gubernator.go:395-421); only
                # non-GLOBAL peer-owned items forward.
                if not hasattr(picker, "owner_spans"):
                    call.served("object", "ring")
                    return None
                g_owned = g_mask & mask
                owner_addrs = (picker, ring_h)  # spans built post-decide
                serve = mask | g_mask
            else:
                serve = mask
            if not serve.all():
                local = serve
                forwards = True
    if has_greg:
        # Gregorian lanes leave the columnar set and come back spliced
        # through merge_mixed, decided by the object path.
        base = local if local is not None else np.ones(cols.n, dtype=bool)
        local = base & ~greg
    # MULTI_REGION: the in-region owner's apply queues the cross-region
    # leg (server.py observe call sites). V1 owned items qualify (the
    # non-owned forward and observe at their in-region owner); peer-call
    # applies are owner applies by definition. Reqs are built BEFORE the
    # GLOBAL strip so combined-flag items replicate with both bits.
    mr_mask = (cols.behavior & _MULTI_REGION) != 0
    mr_queue = []
    if bool(mr_mask.any()) and svc.region_mgr is not None:
        mr_owned = mr_mask if ring_mask is None else (mr_mask & ring_mask)
        if has_greg:
            # Gregorian lanes decide through svc.get_rate_limits, which
            # observes its own cross-region leg (server.py) — queueing
            # here too would double-replicate.
            mr_owned = mr_owned & ~greg
        q = mr_owned & (
            (cols.hits != 0) | ((cols.behavior & _RESET) != 0)
        )
        mr_queue = [
            _req_from_columns(cols, int(i)) for i in np.nonzero(q)[0]
        ]

    now = None
    if has_global or mr_queue:
        # One timestamp for BOTH the local decide and the replicated
        # legs — the object path stamps created_at before the engine
        # call and replicates that same value (server.py); a later
        # re-stamp could land the owner's apply in the next window.
        now = svc.engine.now_fn()
        for req in mr_queue:
            if req.created_at is None:
                req.created_at = now
    if has_global:
        # Queue the replication legs ONLY for items the decide applies
        # (built from the pre-strip behavior; zero-hit items queue
        # nothing, matching GlobalManager's own gate). Objects are built
        # up front so a failed construction falls back BEFORE any table
        # commit.
        # Gregorian GLOBAL lanes replicate through the object path they
        # decide on (svc.get_rate_limits queues their legs) — queueing
        # them here too would double-count the hit at the owner.
        g_queue = [
            (bool(g_owned[i]), _req_from_columns(cols, int(i)))
            for i in np.nonzero(g_mask & ~greg & (cols.hits != 0))[0]
        ]
        for _, req in g_queue:
            if req.created_at is None:
                req.created_at = now
        # The standard engine expects GLOBAL stripped (the daemon's
        # global manager owns replication) — same conditional strip the
        # object path does (server.py). Gregorian lanes keep the bit:
        # they never reach the columnar engine, and their object-path
        # request must still carry it.
        if strip_global:
            stripped = cols.behavior & ~np.int64(_GLOBAL)
            cols.behavior = (
                np.where(greg, cols.behavior, stripped) if has_greg else stripped
            )

    def queue_legs():
        # try_serve runs on the serving executor; the managers' queues
        # are loop-affine — hop each batch over in one callback.
        if has_global and svc.global_mgr is not None and g_queue:
            svc.global_mgr.queue_from_thread(g_queue)
        if mr_queue:
            svc.region_mgr.observe_from_thread(mr_queue)

    def count_metrics(served_mask):
        # Label parity with the object path: owned GLOBAL items count
        # as "local" (server.py checks is_owner before the GLOBAL
        # branch); only non-owner GLOBAL answers count as "global".
        n_glob = (
            int((g_mask & ~g_owned & served_mask).sum()) if has_global else 0
        )
        m = getattr(svc, "_m_global", None)
        if n_glob and m is not None:
            m.inc(n_glob)
        m = getattr(svc, "_m_local", None)
        if m is not None:
            m.inc(int(served_mask.sum()) - n_glob)

    def record_provenance(out, positions):
        # Decision provenance (docs/monitoring.md "Admission"), with the
        # same replica/local split as the labels above: GLOBAL non-owner
        # lanes answered from the local table are path=replica, the rest
        # path=fastpath. Peer-call batches are NOT recorded — the object
        # path counts forwarded answers at the forwarding node only, and
        # the columnar edge must match it decision-for-decision. Staleness
        # bounds stay 0: the per-key bound lives in the object path's
        # metadata, and GUBER_STAGE_METADATA disables this edge entirely.
        rec = getattr(svc, "recorder", None)
        if rec is None or peer_call:
            return
        status, _limit, remaining, _reset = out

        def sample_key(j):
            return _req_from_columns(cols, int(positions[j])).hash_key()

        rest = None
        if has_global:
            rep = (g_mask & ~g_owned)[positions]
            if bool(rep.any()):
                rec.record_columnar(
                    "replica", status, remaining,
                    mask=rep, sample_key=sample_key,
                )
                rest = ~rep
        rec.record_columnar(
            "fastpath", status, remaining,
            mask=rest, sample_key=sample_key,
        )

    def owner_spans(positions):
        """(owner_data, owner_offsets) for build_responses_md: non-owned
        GLOBAL items report their authoritative owner; everything else
        gets an empty span (no metadata). Fully vectorized in the ring."""
        pick, rh = owner_addrs
        need = (g_mask & ~g_owned)[positions]
        return pick.owner_spans(rh[positions], need)

    if local is None:
        # NOTE: a failure BEFORE the table commits falls back safely;
        # a failure AFTER waves committed to a surviving table raises
        # TableCommittedError, which must propagate (a silent fallback
        # would re-apply every committed hit).
        phases.enter("call.engine")
        try:
            out = svc.engine.check_columns(cols, now=now, call=call)
        except _committed_error():
            raise
        # guberlint: allow-swallow -- fallback to the object path IS the handling (byte-equivalence fuzzed); TableCommittedError re-raised above
        except Exception:
            call.served("object", "error")
            return None
        if out is None:
            call.served("object", "waves")
            return None
        phases.enter("call.build")
        call.served("columnar")
        count_metrics(np.ones(cols.n, dtype=bool))
        record_provenance(out, np.arange(cols.n))
        if has_global or mr_queue:
            queue_legs()
        if has_global and owner_addrs is not None and bool(
            (g_mask & ~g_owned).any()
        ):
            odata, ooffs = owner_spans(np.arange(cols.n))
            return wire.build_responses_md(*out, odata, ooffs)
        return wire.build_responses(*out)
    if not local.any():
        # nothing local to decide: pure forwarding batch
        call.served("object", "forward_only")
        return None
    # Mixed ownership: decide the local subset columnar now (with the
    # identity hashes computed once over the full batch); hand the
    # peer-owned subset back as objects for the forwarding path. The
    # request objects build BEFORE the decide so a construction failure
    # cannot strand already-committed hits.
    from gubernator_tpu import native as _native

    local_pos = np.nonzero(local)[0]
    nonlocal_pos = np.nonzero(~local)[0]
    nonlocal_reqs = [_req_from_columns(cols, int(i)) for i in nonlocal_pos]
    hashes = _native.hash128_batch_raw(
        cols.key_data.tobytes(), cols.key_offsets,
        svc.engine.cfg.num_groups,
    )
    phases.enter("call.engine")
    try:
        out = svc.engine.check_columns(
            cols, now=now, select=local_pos, hashes=hashes, call=call
        )
    except _committed_error():
        raise
    # guberlint: allow-swallow -- fallback to the object path IS the handling (byte-equivalence fuzzed); TableCommittedError re-raised above
    except Exception:
        call.served("object", "error")
        return None
    if out is None:
        call.served("object", "waves")
        return None
    phases.enter("call.build")
    # Mixed: some items forward to their owner ("ring"), or Gregorian
    # lanes splice through the object path.
    call.served("mixed", "ring" if forwards else "gregorian")
    count_metrics(local)
    record_provenance(out, local_pos)
    md = None
    if has_global or mr_queue:
        queue_legs()
    if has_global and owner_addrs is not None and bool(
        (g_mask & ~g_owned).any()
    ):
        md = owner_spans(local_pos)
    return ("mixed", cols.n, local_pos, out, nonlocal_reqs, md)


def _req_from_columns(cols, i: int):
    """RateLimitReq object for one (peer-owned) lane — the forwarding
    path needs objects; only the non-local fraction pays this cost."""
    return wire.req_from_columns(cols, i)


def _varint(v: int) -> bytes:
    out = bytearray()
    while v >= 0x80:
        out.append((v & 0x7F) | 0x80)
        v >>= 7
    out.append(v)
    return bytes(out)


def merge_mixed(n: int, local_pos, local_out, nonlocal_resps, md=None) -> bytes:
    """Splice columnar-decided local items with forwarded object-path
    responses, preserving request order. Repeated message items frame
    independently, so native-built runs and protobuf-serialized items
    concatenate into one valid GetRateLimitsResp. `md` (owner_data,
    owner_offsets aligned with local_out order) adds the GLOBAL
    non-owner metadata={"owner": ...} entries."""
    from gubernator_tpu.service import pb

    status, limit, remaining, reset_time = local_out
    local_set = set(int(i) for i in local_pos)
    chunks = []
    li = 0  # pointer into local arrays
    ni = 0  # pointer into nonlocal responses

    def flush_run(count):
        nonlocal li
        if count:
            s = slice(li - count, li)
            if md is not None:
                odata, ooffs = md
                sub = ooffs[li - count: li + 1]
                chunks.append(
                    wire.build_responses_md(
                        status[s], limit[s], remaining[s], reset_time[s],
                        odata[int(sub[0]): int(sub[-1])],
                        (sub - sub[0]).astype("int64"),
                    )
                )
                return
            chunks.append(
                wire.build_responses(
                    status[s], limit[s], remaining[s], reset_time[s]
                )
            )

    run = 0
    for i in range(n):
        if i in local_set:
            li += 1
            run += 1
        else:
            flush_run(run)
            run = 0
            body = pb.resp_to_pb(nonlocal_resps[ni]).SerializeToString()
            ni += 1
            chunks.append(b"\x0a" + _varint(len(body)) + body)
    flush_run(run)
    return b"".join(chunks)
