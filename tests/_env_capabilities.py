"""Environment-capability probes for explicit skipif guards.

The tier-1 suite must report REAL regressions only: tests whose failure
is a property of the environment (jax version capabilities, the
reference checkout, real devices) carry explicit ``skipif`` guards built
from these probes instead of failing forever.  Every probe is cheap,
cached, and names the genuine capability the test needs — a newer jax /
a mounted reference tree flips the guard off with no code change.
"""

import functools
import os

#: the reference NNStreamer checkout (prop-parity audit, reference
#: .tflite test models) — absent on CI boxes without the mount
REFERENCE_TREE = "/root/reference"


@functools.lru_cache(maxsize=None)
def has_reference_tree() -> bool:
    return os.path.isdir(REFERENCE_TREE)


@functools.lru_cache(maxsize=None)
def spmd_stack_ok() -> bool:
    """PROBE-AND-RUN: True when a tiny shard_map program actually runs
    on this process's multi-device CPU mesh — the capability the
    manual-SPMD suite needs is the EXECUTION, so the suite runs wherever
    >= 2 devices exist."""
    import jax

    try:
        if len(jax.devices()) < 2:
            return False  # a mesh program needs a mesh
        import jax.numpy as jnp
        from jax.sharding import PartitionSpec as P

        from nnstreamer_tpu.parallel.mesh import make_mesh

        mesh = make_mesh({"sp": 2}, devices=jax.devices()[:2])

        def body(x):
            acc = jax.lax.pcast(
                jnp.zeros(x.shape, x.dtype), ("sp",), to="varying")
            rolled = jax.lax.ppermute(x, "sp", [(0, 1), (1, 0)])
            return acc + x + rolled

        fn = jax.shard_map(
            body, mesh=mesh, in_specs=(P("sp"),), out_specs=P("sp"))
        out = fn(jnp.arange(4, dtype=jnp.float32))
        return float(out.sum()) == 12.0
    except Exception:
        return False


@functools.lru_cache(maxsize=None)
def multihost_cpu_ok() -> bool:
    """PROBE-AND-RUN: True when this box can actually host a localhost
    multi-process "multi-host" gang.  What gates these tests is the
    HARDWARE: a 2-4 process gang, each with 4 virtual devices, starves
    gloo barriers into timeouts on a single-core box under tier-1 load
    — probed as cores >= 2."""
    import jax

    try:
        return hasattr(jax, "distributed") and (os.cpu_count() or 1) >= 2
    except Exception:
        return False
