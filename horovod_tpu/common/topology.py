"""Process/device topology bookkeeping.

Maps Horovod's rank trichotomy onto the TPU world
(reference: horovod/common/mpi/mpi_context.cc — global/local/cross
communicator split):

  rank        — index of this *process* in the job (one process per host
                in multi-controller JAX; the launcher sets HOROVOD_RANK).
  local_rank  — index of this process among processes on the same host.
  cross_rank  — index of this process's host (slice) among hosts.

Devices are a separate axis: a process owns jax.local_devices() chips
(4 on a v5p host). The classic eager API reduces across *processes*; the
jit path shards across *all chips* via horovod_tpu.parallel meshes.
"""

from __future__ import annotations

import dataclasses
import socket
from typing import List, Optional

import jax


@dataclasses.dataclass
class Topology:
    rank: int
    size: int
    local_rank: int
    local_size: int
    cross_rank: int
    cross_size: int
    hostname: str

    @property
    def is_homogeneous(self) -> bool:
        # With launcher-provided env this is exact for this host; a
        # truly heterogeneous job would need a cross-host exchange, which
        # the launcher performs and reflects into the env.
        return self.size % max(self.local_size, 1) == 0


def detect(cfg) -> Topology:
    """Derive topology from launcher env, falling back to JAX runtime."""
    hostname = socket.gethostname()
    if cfg.size > 0:
        rank = max(cfg.rank, 0)
        size = cfg.size
        local_rank = cfg.local_rank if cfg.local_rank >= 0 else 0
        local_size = cfg.local_size if cfg.local_size >= 0 else 1
        cross_rank = (cfg.cross_rank if cfg.cross_rank >= 0
                      else rank // max(local_size, 1))
        cross_size = cfg.cross_size if cfg.cross_size >= 0 else (
            size + local_size - 1) // max(local_size, 1)
    else:
        # No launcher: single process (possibly already-initialized
        # jax.distributed from the user's own bootstrap).
        rank = jax.process_index()
        size = jax.process_count()
        local_rank = 0
        local_size = 1
        cross_rank = rank
        cross_size = size
    return Topology(rank=rank, size=size, local_rank=local_rank,
                    local_size=local_size, cross_rank=cross_rank,
                    cross_size=cross_size, hostname=hostname)


# hvd rank -> jax process index. The CPU backend numbers processes by
# the process_id handed to jax.distributed.initialize, so there the
# map is the identity (None). The TPU runtime numbers them by the
# coordinates of the chips each process was given, whatever task id
# the launcher asked for (first seen under `--per-chip` on a v5e 2x2:
# ranks 0..3 came up as processes 0, 2, 3, 1).
_process_of_rank: Optional[List[int]] = None


def exchange_process_indices(rank: int, size: int,
                             timeout_s: float) -> None:
    """Publish this rank's jax.process_index() through the
    coordination service's key-value store and read every rank's.
    Called by init() right after jax.distributed.initialize()."""
    global _process_of_rank
    from jax._src import distributed
    client = distributed.global_state.client
    if client is None or size <= 1:
        _process_of_rank = None
        return
    client.key_value_set(f"hvd/process_index/{rank}",
                         str(jax.process_index()), allow_overwrite=True)
    _process_of_rank = [
        int(client.blocking_key_value_get(f"hvd/process_index/{r}",
                                          int(timeout_s * 1000)))
        for r in range(size)]


def reset_process_indices() -> None:
    global _process_of_rank
    _process_of_rank = None


def _devices_of_rank(rank: int) -> List[jax.Device]:
    pidx = rank if _process_of_rank is None else _process_of_rank[rank]
    devs = [d for d in jax.devices() if d.process_index == pidx]
    if not devs:
        raise RuntimeError(f"no devices for rank {rank} "
                           f"(jax process {pidx})")
    return sorted(devs, key=lambda d: d.id)


def process_device(rank: int) -> jax.Device:
    """The representative device of a rank's process, used for the
    eager process-level mesh (one device per rank)."""
    return _devices_of_rank(rank)[0]


def process_local_devices(rank: int) -> List[jax.Device]:
    """ALL devices owned by a rank's process, in id order. Row
    material for the device-spanning eager mesh (see
    ProcessSet.device_mesh)."""
    return _devices_of_rank(rank)


def device_matrix(ranks: List[int]):
    """(len(ranks), D) grid of EVERY device of every member process
    (row r = process ranks[r]'s devices in id order), or None when
    members own differing device counts (a device-spanning mesh needs
    a rectangle). numpy object array, ready for jax.sharding.Mesh."""
    import numpy as np
    rows = [process_local_devices(r) for r in ranks]
    width = len(rows[0])
    if any(len(r) != width for r in rows):
        return None
    return np.array(rows)


def process_mesh_devices(ranks: Optional[List[int]] = None
                         ) -> List[jax.Device]:
    """One device per process, in rank order (optionally a subset)."""
    n = jax.process_count()
    ranks = list(range(n)) if ranks is None else ranks
    return [process_device(r) for r in ranks]
