"""In-process multi-daemon cluster harness.

The reference's central test fixture boots N full daemons (real gRPC +
HTTP listeners on loopback) inside one process and wires peers statically
— no discovery backend (reference cluster/cluster.go:123-189). Same trick
here: each daemon gets its own DeviceEngine/table/registry, listeners
bind port 0, and the assembled PeerInfo list is pushed through the real
SetPeers path. Helpers locate key owners through the real hash ring
(reference cluster/cluster.go:40-110).
"""

from __future__ import annotations

import random
from typing import List, Optional, Sequence

import jax

from gubernator_tpu.api.types import PeerInfo
from gubernator_tpu.service.config import BehaviorConfig, DaemonConfig
from gubernator_tpu.service.daemon import Daemon

DATACENTER_NONE = ""


class Cluster:
    def __init__(self):
        self.daemons: List[Daemon] = []

    # -- lifecycle -----------------------------------------------------------

    @classmethod
    async def start(
        cls,
        count: int,
        datacenters: Optional[Sequence[str]] = None,
        behaviors: Optional[BehaviorConfig] = None,
        cache_size: int = 8192,
        **daemon_conf,
    ) -> "Cluster":
        """Daemon i's table lives on ``jax.devices()[i % n]``: on a
        four-chip host four daemons hold four chips. Extra keyword args
        pass through to every DaemonConfig — e.g. ``overload=True,
        intake_limit=64`` arms the overload control plane mesh-wide."""
        c = cls()
        dcs = list(datacenters) if datacenters else [DATACENTER_NONE] * count
        devices = jax.devices()
        for i, dc in enumerate(dcs):
            conf = DaemonConfig(
                data_center=dc,
                cache_size=cache_size,
                behaviors=behaviors or BehaviorConfig(),
                device=devices[i % len(devices)],
                **daemon_conf,
            )
            c.daemons.append(await Daemon.spawn(conf))
        c.rewire()
        return c

    def rewire(self) -> None:
        """Push the full membership to every daemon (SetPeers path)."""
        peers = [
            PeerInfo(
                grpc_address=d.grpc_address,
                http_address=d.http_address,
                data_center=d.conf.data_center,
            )
            for d in self.daemons
        ]
        for d in self.daemons:
            d.set_peers(peers)

    async def stop(self) -> None:
        for d in self.daemons:
            await d.close()
        self.daemons.clear()

    # -- lookup helpers (reference cluster/cluster.go:40-110) ----------------

    def peer_at(self, i: int) -> Daemon:
        return self.daemons[i]

    def get_random_peer(self, dc: str = DATACENTER_NONE) -> Daemon:
        options = [d for d in self.daemons if d.conf.data_center == dc]
        return random.choice(options)

    def find_owning_daemon(self, name: str, unique_key: str) -> Daemon:
        key = name + "_" + unique_key
        peer = self.daemons[0].svc.picker.get(key)
        for d in self.daemons:
            if d.grpc_address == peer.info.grpc_address:
                return d
        raise RuntimeError("owning daemon not found")

    def list_non_owning_daemons(self, name: str, unique_key: str) -> List[Daemon]:
        owner = self.find_owning_daemon(name, unique_key)
        return [d for d in self.daemons if d is not owner]

    def num_of_daemons(self) -> int:
        return len(self.daemons)
