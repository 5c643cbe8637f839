"""Worker process for test_multihost.py: one simulated "host".

Initializes the multi-process runtime from NNS_TPU_* env vars, builds a
hybrid DCN×ICI mesh, runs a dp-across-hosts / tp-within-host sharded
train-ish step, and exercises the cross-process utilities.  Prints
RESULT <json> on success; any mismatch raises (nonzero exit)."""

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np  # noqa: E402

from nnstreamer_tpu.parallel import multihost  # noqa: E402


def main() -> None:
    # platform="cpu": a localhost gang never claims the chip; local device
    # count comes from env
    multihost.initialize(platform="cpu")

    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    nproc = multihost.process_count()
    pid = multihost.process_index()
    nlocal = jax.local_device_count()

    mesh = multihost.hybrid_mesh({"tp": 2, "sp": -1}, {"dp": nproc})
    assert mesh.shape["dp"] == nproc
    assert mesh.shape["tp"] == 2
    assert mesh.shape["sp"] == nlocal // 2

    # every process contributes its own slice of the global batch
    # (dp-sharded over hosts); weights are tp-sharded within a host
    d = 8
    local_batch = np.full((4, d), float(pid + 1), np.float32)
    x = multihost.global_array(mesh, P("dp", None), local_batch)
    w = jax.device_put(
        np.eye(d, dtype=np.float32),
        NamedSharding(mesh, P(None, "tp")),
    )

    @jax.jit
    def step(w, x):
        y = x @ w  # tp-sharded matmul: all-gather rides ICI
        return jnp.mean(y**2)  # mean over the global batch: psum over DCN

    loss = float(step(w, x))
    # oracle: mean over all processes' slices of value (pid+1)^2
    want = float(np.mean([(p + 1) ** 2 for p in range(nproc)]))
    assert abs(loss - want) < 1e-5, (loss, want)

    multihost.barrier("phase1")

    # broadcast: non-primary must observe primary's value
    blob = multihost.broadcast_from_primary(
        np.asarray([42.0 if pid == 0 else -1.0], np.float32)
    )
    assert float(np.asarray(blob)[0]) == 42.0

    assert multihost.all_processes_agree(np.asarray([d], np.int32))

    # gather: every host sees the full dp-sharded array
    full = multihost.gather_to_host(x)
    assert full.shape == (4 * nproc, d)
    for p in range(nproc):
        assert np.all(full[4 * p : 4 * (p + 1)] == p + 1)

    print(
        "RESULT "
        + json.dumps({
            "pid": pid,
            "nproc": nproc,
            "global_devices": jax.device_count(),
            "loss": loss,
            "primary": multihost.is_primary(),
        }),
        flush=True,
    )


if __name__ == "__main__":
    main()
