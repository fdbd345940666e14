"""Topology strategies for the unified engine core (runtime/engine.py).

MeshEngine owns everything topology-independent exactly once — the pump,
the pipeline ring, ticket lifecycle, failure recovery, drain, snapshots,
census/admission caching, and flush telemetry. The per-topology delta
lives HERE, reduced to a small strategy object with three duties:

- **kernel binding** (`build_kernels`): which Kernels facade the core
  dispatches through, and whether a Pager manages page residency behind
  it. Single chip binds the plain per-layout jits (ops/kernels.py);
  the mesh binds the shard_map ownership programs (parallel/mesh.py)
  whose psum over the mesh axis replaces peer forwarding. The paged
  indirection layer rides the SAME seam on both: the core only ever
  sees a Kernels-shaped object plus an optional Pager, so per-shard
  page maps and per-shard host-DRAM cold tiers come for free on the
  multi-chip tier.
- **table residency** (mesh geometry): `n_dev` / `mesh_shape` size the
  per-shard pools; mesh shape ``(1,)`` reproduces the single-chip
  engine bit-exactly, ``(chips,)`` runs the sharded tier. The axis is
  one-dimensional on purpose — a later DCN x ICI build extends the
  mesh to ``(hosts, chips)`` and the strategy, not the core, absorbs it.
- **collective step** (`dispatch_guard` + `build_replica`): multi-device
  programs rendezvous in collectives, so every dispatch site in the
  core runs under the strategy's guard (the process-wide enqueue lock,
  parallel/mesh.collective_guard — a nullcontext on one chip), and the
  GLOBAL replica tier (parallel/ici.py) is built only where a mesh
  exists to replicate over.

Import discipline: this module imports ops/, parallel/, and
runtime/pager — NEVER runtime/engine (the engine imports us).
"""

from __future__ import annotations

import contextlib

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

from gubernator_tpu.ops.kernels import (
    get_admission,
    get_census,
    get_kernels,
    get_paged_kernels,
)
from gubernator_tpu.parallel import ici
from gubernator_tpu.parallel import mesh as pmesh


class ReplicaTier:
    """The GLOBAL replica tier, bundled for the engine core: per-device
    replica tables with pending deltas (parallel/ici.py), the decide /
    sync / inject programs over them, and the stacked census/admission
    scans. The core treats it as opaque state + callables; the sync
    CADENCE (thread + tick bookkeeping) stays in IciEngine — it is
    policy, not topology."""

    def __init__(self, mesh, cfg, metrics, census_thresholds):
        self.mesh = mesh
        self._metrics = metrics
        self._layout = cfg.layout
        self.num_slots = int(cfg.num_slots)
        self.replica_ways = int(cfg.replica_ways)
        self.num_rgroups = self.num_slots // self.replica_ways
        self.state = ici.create_ici_state(
            mesh, self.num_slots, self.replica_ways, layout=cfg.layout,
            metrics=metrics,
        )
        self.decide = ici.make_replica_decide(
            mesh, self.num_slots, self.replica_ways, layout=cfg.layout
        )
        self.sync = ici.make_sync_step(
            mesh, self.num_slots, self.replica_ways, layout=cfg.layout,
            max_sync_groups=cfg.max_sync_groups,
        )
        # Collision backstop: a second, unbounded sync program selected
        # every `full_tick_every`-th tick. Only built when the regular
        # tick is actually capped (an uncapped tick IS the full tick;
        # a cap >= group count compiles to the uncapped program too).
        self.sync_full = None
        if (
            cfg.max_sync_groups is not None
            and cfg.max_sync_groups < self.num_rgroups
            and cfg.full_tick_every > 0
        ):
            self.sync_full = ici.make_sync_step(
                mesh, self.num_slots, self.replica_ways,
                layout=cfg.layout, max_sync_groups=None,
            )
        self.inject = ici.make_inject_replicas(
            mesh, self.num_slots, self.replica_ways, layout=cfg.layout
        )
        # Replica-tier observatory programs: the tier's leaves carry a
        # leading device axis, so both use the stacked variants
        # (replica 0; post-sync replicas mirror each other).
        self.census = get_census(
            cfg.layout, self.replica_ways,
            heatmap_width=int(cfg.census_heatmap_width),
            thresholds=census_thresholds,
            stacked=True,
        )
        self.admission = get_admission(
            cfg.layout, self.replica_ways, stacked=True
        )

    def recreate_state(self):
        """Fresh empty replica state after a failed donated dispatch
        (counter loss on failure matches the accepted cache-loss-on-
        restart semantics)."""
        return ici.create_ici_state(
            self.mesh, self.num_slots, self.replica_ways,
            layout=self._layout, metrics=self._metrics,
        )


class SingleChipTopology:
    """Mesh shape ``(1,)``: one chip, the plain per-layout kernels, no
    replica tier, no collective guard. Binding THIS strategy into
    MeshEngine reproduces the pre-unification DeviceEngine bit-exactly
    (pinned by tests/test_pipeline.py + tests/test_kernel_fuzz.py)."""

    n_dev = 1
    mesh_shape = (1,)
    primary_tier = "device"
    thread_name = "gubernator-tpu-engine"

    def build_kernels(self, cfg, metrics):
        """(Kernels, Pager|None) for one chip — the pre-unification
        DeviceEngine binding: paged facade + Pager when page_groups is
        set, the flat layout jits otherwise."""
        pg = int(getattr(cfg, "page_groups", 0) or 0)
        if pg > 0:
            budget = int(getattr(cfg, "page_budget", 0) or 0)
            if budget <= 0:
                raise ValueError(
                    "page_budget must be > 0 when page_groups > 0"
                )
            if pg > cfg.num_groups:
                raise ValueError(
                    f"page_groups ({pg}) exceeds num_groups "
                    f"({cfg.num_groups})"
                )
            from gubernator_tpu.runtime.pager import Pager

            K = get_paged_kernels(
                cfg.layout, cfg.num_groups, cfg.ways, pg, budget
            )
            return K, Pager(K, metrics=metrics)
        return get_kernels(cfg.layout), None

    def build_replica(self, cfg, metrics):
        return None  # no mesh to replicate over

    def operand_sharding(self, cfg):
        """Where a wave's operand is uploaded: the table's own device
        (None = the process default, where an unpinned table lives)."""
        return getattr(cfg, "device", None)

    def dispatch_guard(self):
        """Single-device programs cannot rendezvous: no guard."""
        return contextlib.nullcontext()


class IciMeshTopology:
    """Mesh shape ``(chips,)``: the slot table shards across the mesh
    (owner-sharded decide, parallel/mesh.py), GLOBAL traffic runs on
    per-device replicas (parallel/ici.py), and every dispatch runs
    under the process-wide collective enqueue guard. Paging composes:
    the paged mesh facade keeps the physical frames sharded and the
    page map replicated, and the Pager runs one frame pool + host-DRAM
    cold tier PER SHARD (n_shards = mesh size)."""

    primary_tier = "sharded"
    thread_name = "ici-engine"

    def __init__(self, devices=None):
        self.devices = list(devices) if devices else jax.devices()
        self.mesh = pmesh.make_mesh(self.devices)
        self.n_dev = int(self.mesh.devices.size)
        self.mesh_shape = (self.n_dev,)

    def build_kernels(self, cfg, metrics):
        """(Kernels, Pager|None) over the mesh: shard_map ownership
        programs, with the paged indirection layer (replicated map,
        sharded frames, per-shard pools) when page_groups is set."""
        pg = int(getattr(cfg, "page_groups", 0) or 0)
        budget = int(getattr(cfg, "page_budget", 0) or 0)
        if pg > 0:
            if budget <= 0:
                raise ValueError(
                    "page_budget must be > 0 when page_groups > 0"
                )
            if pg > cfg.num_groups:
                raise ValueError(
                    f"page_groups ({pg}) exceeds num_groups "
                    f"({cfg.num_groups})"
                )
        K = pmesh.make_mesh_kernels(
            self.mesh, cfg.layout, cfg.num_groups, cfg.ways,
            page_groups=pg, page_budget=budget, metrics=metrics,
        )
        if pg <= 0:
            return K, None
        from gubernator_tpu.runtime.pager import Pager

        return K, Pager(K, metrics=metrics, n_shards=self.n_dev)

    def build_replica(self, cfg, metrics):
        return ReplicaTier(
            self.mesh, cfg, metrics,
            tuple(int(k) for k in cfg.census_thresholds),
        )

    def operand_sharding(self, cfg):
        """A wave's operand is replicated over the mesh: every shard
        unpacks the same array and masks it to the lanes it owns."""
        return NamedSharding(self.mesh, P())

    def dispatch_guard(self):
        """Process-wide multi-device enqueue lock (parallel/mesh.py):
        taken INSIDE the engine table lock at every dispatch site, so
        two engines' collectives can never interleave their per-device
        enqueues (the cross-program rendezvous deadlock)."""
        return pmesh.collective_guard()
