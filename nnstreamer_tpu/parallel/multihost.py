"""Multi-host (multi-process) runtime bootstrap: the DCN-scale half of the
distributed communication backend.

The reference scales among devices with nnstreamer-edge transports (TCP /
MQTT / AITT — SURVEY §2.3) and leaves intra-model collectives to
NCCL-style out-of-repo stacks.  The TPU-native equivalent is the JAX
multi-process runtime: **one process per host**, every process sees the
global device list, XLA inserts collectives that ride ICI within a slice
and DCN across slices (SURVEY §5.8 "inter-slice/inter-host = DCN via JAX
multi-process runtime").

This module owns three things:

1. ``initialize()`` — env-driven ``jax.distributed`` bring-up that works
   both on real TPU pods (where the coordinator is auto-discovered) and in
   CPU-simulated multi-host tests (N processes × M virtual devices on
   localhost, gloo collectives).
2. ``hybrid_mesh()`` — a Mesh whose DCN-crossing axes are outermost (one
   mesh row per process) and whose ICI axes stay within a host, following
   the scaling-book rule: put the slowest links on the axes with the
   least-frequent/most-overlappable collectives (dp gradient psum), keep
   tp/sp activation collectives on ICI.
3. Cross-process utilities — barrier, broadcast-from-primary,
   per-process data → global sharded array — small wrappers with a stable
   framework-level API so elements/trainers never import jax internals.

Elasticity: the JAX runtime is gang-scheduled (a lost process fails the
job); elastic behavior is restart-from-checkpoint — see
``trainer/jax_trainer.py`` periodic Orbax checkpoints + the
``resume`` property, and ``Documentation/examples.md`` (elastic resume).
"""

from __future__ import annotations

import os
from typing import Dict, Optional

import numpy as np

from ..core.log import get_logger

log = get_logger("parallel.multihost")

_ENV_COORD = "NNS_TPU_COORDINATOR"
_ENV_NPROC = "NNS_TPU_NUM_PROCS"
_ENV_PROC = "NNS_TPU_PROC_ID"
_ENV_LOCAL = "NNS_TPU_LOCAL_DEVICES"

_initialized = False


def is_initialized() -> bool:
    return _initialized


def initialize(
    coordinator: Optional[str] = None,
    num_processes: Optional[int] = None,
    process_id: Optional[int] = None,
    local_device_count: Optional[int] = None,
    platform: Optional[str] = None,
) -> None:
    """Bring up the multi-process runtime (idempotent).

    On a real TPU pod all arguments are auto-discovered by JAX (metadata
    server) — call with no arguments.  For CPU-simulated multi-host (tests,
    laptops) pass/export the coordinator address and process ids:

        NNS_TPU_COORDINATOR=127.0.0.1:29400 NNS_TPU_NUM_PROCS=2 \
        NNS_TPU_PROC_ID=0 NNS_TPU_LOCAL_DEVICES=4 python worker.py

    ``local_device_count``/``platform`` must be applied BEFORE the backend
    initializes, so call this before any other jax API touches devices.
    """
    global _initialized
    if _initialized:
        return
    import jax

    coordinator = coordinator or os.environ.get(_ENV_COORD)
    if num_processes is None and os.environ.get(_ENV_NPROC):
        num_processes = int(os.environ[_ENV_NPROC])
    if process_id is None and os.environ.get(_ENV_PROC):
        process_id = int(os.environ[_ENV_PROC])
    if local_device_count is None and os.environ.get(_ENV_LOCAL):
        local_device_count = int(os.environ[_ENV_LOCAL])

    if platform:
        os.environ["JAX_PLATFORMS"] = platform
        jax.config.update("jax_platforms", platform)
    if platform == "cpu" and (coordinator or num_processes):
        # CPU-simulated multi-host: cross-process collectives on the CPU
        # backend need an explicit implementation (default "none" fails
        # any multiprocess computation with INVALID_ARGUMENT); gloo is
        # the one jaxlib ships
        try:
            jax.config.update("jax_cpu_collectives_implementation", "gloo")
        except (AttributeError, ValueError) as e:
            log.warning("cpu collectives unavailable (%s): cross-process "
                        "computations may fail", e)
    if local_device_count:
        jax.config.update("jax_num_cpu_devices", local_device_count)

    if coordinator is None and num_processes is None:
        # real pod: everything comes from the cluster environment
        jax.distributed.initialize()
    else:
        jax.distributed.initialize(
            coordinator_address=coordinator,
            num_processes=num_processes,
            process_id=process_id,
        )
    _initialized = True
    log.info(
        "multihost up: process %d/%d, %d local / %d global devices",
        jax.process_index(), jax.process_count(),
        jax.local_device_count(), jax.device_count(),
    )


def shutdown() -> None:
    global _initialized
    if not _initialized:
        return
    import jax

    jax.distributed.shutdown()
    _initialized = False


def process_index() -> int:
    import jax

    return jax.process_index()


def process_count() -> int:
    import jax

    return jax.process_count()


def is_primary() -> bool:
    """True on the process that should own singleton side effects
    (checkpoint writes, bus logging, serving endpoints)."""
    return process_index() == 0


# ---------------------------------------------------------------------------
# Hybrid DCN×ICI meshes
# ---------------------------------------------------------------------------

def hybrid_mesh(
    ici_axes: Dict[str, int],
    dcn_axes: Optional[Dict[str, int]] = None,
):
    """Mesh spanning every process: ``dcn_axes`` cross hosts (outermost,
    default ``{"dp": process_count()}``), ``ici_axes`` stay within a host.

    ``hybrid_mesh({"tp": 4}, {"dp": 2})`` on 2 hosts × 4 chips gives a
    (dp=2, tp=4) mesh where tp collectives never touch DCN.  Axis sizes
    must multiply to the per-host / host counts respectively; ``-1``
    wildcards are resolved like ``make_mesh``.
    """
    import jax
    from jax.experimental import mesh_utils
    from jax.sharding import Mesh

    nproc = jax.process_count()
    nlocal = jax.local_device_count()
    if dcn_axes is None:
        dcn_axes = {"dp": nproc}

    ici = _resolve(dict(ici_axes), nlocal, "ici")
    dcn = _resolve(dict(dcn_axes), nproc, "dcn")

    if nproc == 1:
        # single-process: collapse to an ordinary mesh over local devices
        from .mesh import make_mesh

        merged = {**dcn, **ici}
        return make_mesh(merged, devices=jax.devices()[: nproc * nlocal])

    # per-axis shape vectors: every mesh axis appears in both vectors, as 1
    # on the side it does not span
    names = tuple(dcn.keys()) + tuple(ici.keys())
    ici_shape = [1] * len(dcn) + [ici[k] for k in ici]
    dcn_shape = [dcn[k] for k in dcn] + [1] * len(ici)
    devs = mesh_utils.create_hybrid_device_mesh(
        ici_shape, dcn_shape, devices=jax.devices(),
        process_is_granule=True,
    )
    return Mesh(devs, names)


def _resolve(sizes: Dict[str, int], total: int, kind: str) -> Dict[str, int]:
    import math

    wild = [k for k, v in sizes.items() if v == -1]
    if len(wild) > 1:
        raise ValueError(f"at most one {kind} axis may be -1")
    fixed = math.prod(v for v in sizes.values() if v != -1)
    if wild:
        if total % fixed:
            raise ValueError(f"{total} {kind} devices not divisible by {fixed}")
        sizes[wild[0]] = total // fixed
    elif math.prod(sizes.values()) != total:
        raise ValueError(
            f"{kind} axes {sizes} must multiply to {total}"
        )
    return sizes


# ---------------------------------------------------------------------------
# Cross-process data movement
# ---------------------------------------------------------------------------

def barrier(name: str = "nns_tpu_barrier") -> None:
    """Block until every process reaches this point (control plane)."""
    from jax.experimental import multihost_utils

    multihost_utils.sync_global_devices(name)


def broadcast_from_primary(tree):
    """Replicate host-local data from process 0 to all processes
    (config blobs, model-selection decisions, shuffled index orders)."""
    from jax.experimental import multihost_utils

    return multihost_utils.broadcast_one_to_all(tree)


def all_processes_agree(value) -> bool:
    """True iff every process passed an identical value (guardrail before
    collective compilation: mismatched shapes deadlock a gang-scheduled
    job with no diagnostics)."""
    from jax.experimental import multihost_utils

    try:
        multihost_utils.assert_equal(value, fail_message="mismatch")
        return True
    except AssertionError:
        return False


def global_array(mesh, pspec, local_data: np.ndarray):
    """Assemble per-process host data into ONE global jax.Array sharded by
    ``pspec`` over ``mesh`` — the data-loader handoff for multi-host
    training (each host reads its own datarepo shard; XLA sees a single
    logical batch).
    """
    import jax
    from jax.sharding import NamedSharding

    return jax.make_array_from_process_local_data(
        NamedSharding(mesh, pspec), np.asarray(local_data)
    )


def gather_to_host(arr) -> np.ndarray:
    """Fetch a (possibly multi-host sharded) jax.Array to every host as
    numpy — the sink-side boundary (metrics, decoders that must run on
    host).  Uses an all-gather under the hood; cheap for the small
    decoded outputs it is meant for."""
    from jax.experimental import multihost_utils

    return np.asarray(multihost_utils.process_allgather(arr, tiled=True))
