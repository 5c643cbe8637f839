"""Record ``data/tiny.xplane.pb`` on the chip (run by hand through the chip
tool; writes ``chiprun_out/tiny.xplane.pb``, which is then copied here)."""

import glob
import os
import shutil
import tempfile
import time

import jax
import jax.numpy as jnp


def main():
    x = jnp.ones((1024, 1024), jnp.bfloat16)
    f = jax.jit(lambda a: (a @ a).sum())
    f(x).block_until_ready()
    d = tempfile.mkdtemp()
    options = jax.profiler.ProfileOptions()     # as harness.TraceWindow sets them
    options.python_tracer_level = 0
    options.enable_hlo_proto = False
    jax.profiler.start_trace(d, profiler_options=options)
    for i in range(6):
        with jax.profiler.TraceAnnotation("bench:step", i=i):
            f(x).block_until_ready()
        with jax.profiler.TraceAnnotation("bench:sleep"):
            time.sleep(0.005)
    jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(d, "**", "*.xplane.pb"), recursive=True)
    os.makedirs("chiprun_out", exist_ok=True)
    shutil.copy(path, "chiprun_out/tiny.xplane.pb")
    print(os.path.getsize(path), "bytes")


if __name__ == "__main__":
    main()
