"""Multi-host orchestration.

The reference's "distributed backend" is Kubernetes pod scheduling — no
NCCL/MPI anywhere (SURVEY.md §2.3). The TPU-native equivalent:
``jax.distributed.initialize`` brings N hosts into one JAX runtime over
DCN; inside the runtime, ``global_fleet_mesh`` spans every chip of every
host and the fleet programs' collectives ride ICI within a slice (DCN only
carries the runtime's control plane and cross-slice collectives).

Restart/elasticity parity: the reference leans on k8s pod restarts + the
config-hash cache for idempotent retries. The same holds here — a restarted
multi-host job re-runs ``build_fleet``, which skips every machine already
registered (per-machine resume), so host failure costs at most the
in-flight bucket.
"""

from __future__ import annotations

import logging
from typing import Optional

import jax
import numpy as np
from jax.sharding import Mesh

from .mesh import FLEET_AXIS

logger = logging.getLogger(__name__)


def initialize_multihost(
    coordinator_address: Optional[str] = None,
    num_processes: Optional[int] = None,
    process_id: Optional[int] = None,
) -> None:
    """Join this host to the distributed JAX runtime.

    With no arguments, cluster-environment autodetection is used (TPU pod
    metadata / k8s JobSet env vars) — the normal path on Cloud TPU.
    Explicit args support bare-metal setups. No-op if already initialized.

    Must run before anything touches the XLA backend (do NOT query
    ``jax.devices()``/``process_count()`` first — that would pin a
    single-process runtime).
    """
    if jax.distributed.is_initialized():
        logger.info("jax.distributed already initialized")
        return
    explicit = coordinator_address is not None
    try:
        jax.distributed.initialize(
            coordinator_address=coordinator_address,
            num_processes=num_processes,
            process_id=process_id,
        )
    except (ValueError, RuntimeError) as exc:
        if explicit:
            # the caller named a coordinator: failing to join it is an
            # error, not a single-host fallback
            raise
        # autodetection found no cluster (tests, one-host dev) — fine
        logger.info("jax.distributed.initialize skipped: %s", exc)
    logger.info(
        "Distributed runtime: process %d/%d, %d local / %d global devices",
        jax.process_index(),
        jax.process_count(),
        jax.local_device_count(),
        jax.device_count(),
    )


def global_fleet_mesh(axis_name: str = FLEET_AXIS) -> Mesh:
    """1-D mesh over every device of every host. With
    ``jax.distributed`` initialized, ``jax.devices()`` already spans hosts;
    the fleet axis shards machines across the full pod and XLA keeps each
    machine's collectives on-chip (no cross-machine communication exists in
    the fleet program, so DCN carries nothing in steady state)."""
    return Mesh(np.array(jax.devices()), (axis_name,))
