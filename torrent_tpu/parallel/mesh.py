"""Device mesh + sharding helpers for the hash plane.

The reference's only parallelism is async concurrency on one event loop
(SURVEY §2); the TPU build's parallelism is SPMD over a
``jax.sharding.Mesh``:

- axis ``"dp"`` — pieces (data parallel; the batch axis of every kernel)
- axis ``"hosts"`` — multi-host fan-out over DCN for pod-scale bulk
  verification (BASELINE config 5); piece batches shard over
  ``hosts × dp`` so collectives ride ICI within a host and only the final
  few-byte bitfield reductions cross DCN. On a real multi-process
  cluster (``jax.distributed``) the host rows are process-aligned and
  inputs enter as per-process local shards — see
  ``parallel/distributed.py``; the live 2-process path is exercised by
  ``tests/test_distributed.py``.

SHA1's block chain is inherently serial *within* a piece, so there is no
tensor/sequence-parallel axis to shard — all scale-out is across pieces,
which is exactly what ICI is worst-case-free at: the verify step is
embarrassingly parallel until the final ``psum`` of match counts.
"""

from __future__ import annotations

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

DP_AXIS = "dp"
HOST_AXIS = "hosts"


def make_mesh(devices=None, n_hosts: int | None = None) -> Mesh:
    """Build a ``(hosts, dp)`` mesh over ``devices`` (default: all).

    ``n_hosts`` defaults to ``jax.process_count()`` so a single-host run
    gets a ``(1, n_chips)`` mesh and a pod run gets ``(n_hosts, chips)`` —
    the per-host sub-batches never need cross-DCN data movement.
    """
    if devices is None:
        devices = jax.devices()
    if n_hosts is None:
        n_hosts = jax.process_count()
    if jax.process_count() > 1 and n_hosts == jax.process_count():
        # Real multi-process mesh (parallel/distributed.py): row p MUST
        # be process p's local devices, so the batch rows a process
        # feeds via make_array_from_process_local_data are the rows its
        # own devices hold — piece bytes stay on-host, only bitfield /
        # stats reductions cross DCN. jax.devices() order is not a
        # contract; group explicitly.
        rows = [
            [d for d in devices if d.process_index == p]
            for p in range(n_hosts)
        ]
        width = len(rows[0])
        if width == 0 or any(len(r) != width for r in rows):
            raise ValueError(
                "devices are not evenly spread over processes: "
                + str([len(r) for r in rows])
            )
        return Mesh(np.array(rows, dtype=object), (HOST_AXIS, DP_AXIS))
    devices = np.asarray(devices)
    if devices.size % n_hosts != 0:
        raise ValueError(f"{devices.size} devices not divisible by {n_hosts} hosts")
    grid = devices.reshape(n_hosts, devices.size // n_hosts)
    return Mesh(grid, (HOST_AXIS, DP_AXIS))


def batch_sharding(mesh: Mesh) -> NamedSharding:
    """Shard the leading (piece-batch) axis over every mesh axis."""
    return NamedSharding(mesh, P((HOST_AXIS, DP_AXIS),))


def replicated_sharding(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def round_up_to_multiple(n: int, k: int) -> int:
    return ((n + k - 1) // k) * k
