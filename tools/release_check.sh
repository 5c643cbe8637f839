#!/usr/bin/env bash
# Release gate, executed locally (≙ the reference's
# .github/workflows/ubuntu_clean_meson_build.yml clean-room build):
# build the wheel, install it into a FRESH venv, and prove the installed
# artifact works — import from the package boundary, console scripts,
# a real pipeline run, native-core build from packaged sources.
#
# Offline-friendly: the venv uses --system-site-packages for the baked-in
# heavy deps (jax, numpy, grpc); the wheel itself installs with --no-deps
# so what's proven is OUR artifact, not the dependency resolver.
#
# Usage: bash tools/release_check.sh [workdir]
# Writes a full transcript to RELEASE_CHECK.log next to this repo's root.

set -euo pipefail

ROOT="$(cd "$(dirname "$0")/.." && pwd)"
WORK="${1:-$(mktemp -d /tmp/nns_release.XXXXXX)}"
LOG="$ROOT/RELEASE_CHECK.log"
: > "$LOG"

say() { echo "[release_check] $*" | tee -a "$LOG"; }
run() { say "+ $*"; "$@" >> "$LOG" 2>&1; }

say "workdir: $WORK"
say "python: $(python --version 2>&1)"

# 1. build the wheel from a clean dist dir
rm -rf "$WORK/dist"
run python -m pip wheel "$ROOT" --no-deps --no-build-isolation -w "$WORK/dist"
WHEEL="$(ls "$WORK"/dist/nnstreamer_tpu-*.whl)"
say "wheel: $(basename "$WHEEL") ($(stat -c%s "$WHEEL") bytes)"

# 2. fresh venv.  The baked-in deps live in the *parent* environment's
# site-packages (which is itself a venv here, so --system-site-packages
# would skip it); expose exactly that directory via a .pth instead.
run python -m venv "$WORK/venv"
VPY="$WORK/venv/bin/python"
DEPS_DIR="$(python -c 'import numpy, os; print(os.path.dirname(os.path.dirname(numpy.__file__)))')"
say "parent deps dir: $DEPS_DIR"
VSITE="$("$VPY" -c 'import sysconfig; print(sysconfig.get_paths()["purelib"])')"
echo "$DEPS_DIR" > "$VSITE/baked_deps.pth"
run "$VPY" -m pip install --no-deps --force-reinstall "$WHEEL"

# 3. the installed package imports from OUTSIDE the repo (no cwd tricks)
say "import check (cwd=/tmp, repo not on sys.path)"
(cd /tmp && run "$VPY" -c "
import sys
assert not any(p.rstrip('/').endswith('repo') for p in sys.path if p), sys.path
import nnstreamer_tpu
from nnstreamer_tpu.core.types import StreamSpec, TensorSpec
from nnstreamer_tpu.pipeline import parse_pipeline
print('import OK from', nnstreamer_tpu.__file__)
assert 'site-packages' in nnstreamer_tpu.__file__
")

# 4. console scripts, as installed by the wheel entry points
say "console scripts"
run "$WORK/venv/bin/nns-tpu-inspect" queue
run "$WORK/venv/bin/nns-tpu-check" --help
JAX_PLATFORMS=cpu run "$WORK/venv/bin/nns-tpu-launch" \
  "videotestsrc num-buffers=4 ! tensor_converter ! tensor_transform mode=arithmetic option=typecast:float32,div:255 ! tensor_sink"
# offline model conversion (importer -> .jaxexport), when the reference
# test models are around to convert (override with NNS_REF_TFLITE)
REF_TFLITE="${NNS_REF_TFLITE:-/root/reference/tests/test_models/models/add.tflite}"
if [ ! -f "$REF_TFLITE" ]; then
  say "convert->serve gate SKIPPED (no reference model at $REF_TFLITE)"
fi
if [ -f "$REF_TFLITE" ]; then
  (cd /tmp && JAX_PLATFORMS=cpu run "$VPY" -c "
import jax; jax.config.update('jax_platforms', 'cpu')
from nnstreamer_tpu.cli.convert import main
import numpy as np
assert main(['$REF_TFLITE', '$WORK/add.jaxexport']) == 0
from nnstreamer_tpu import SingleShot
with SingleShot('jax-xla', '$WORK/add.jaxexport') as m:
    (out,) = m.invoke([np.float32([1.5])])
    assert float(np.asarray(out)[0]) == 3.5, out
print('convert->serve OK')
")
fi

# 5. a real pipeline through the installed package (filter + decoder)
say "smoke pipeline (jax filter + decoder, CPU)"
(cd /tmp && JAX_PLATFORMS=cpu run "$VPY" -c "
import jax; jax.config.update('jax_platforms', 'cpu')
import numpy as np
from nnstreamer_tpu.backends.jax_xla import register_jax_model
from nnstreamer_tpu.pipeline import parse_pipeline
register_jax_model('rc_scale', lambda p, xs: [xs[0] * 2.0], {})
pipe = parse_pipeline('appsrc name=src ! tensor_filter framework=jax-xla model=rc_scale ! tensor_sink name=out')
pipe.start()
for i in range(3):
    pipe['src'].push(np.full((4,), float(i), np.float32))
pipe['src'].end_of_stream()
pipe.wait(timeout=60)
frames = pipe['out'].frames
pipe.stop()
assert len(frames) == 3, frames
np.testing.assert_allclose(frames[2].tensors[0], np.full((4,), 4.0))
print('pipeline OK:', [f.tensors[0][0] for f in frames])
")

# 6. native core builds from the wheel's packaged sources
say "native core build from installed package data"
(cd /tmp && run "$VPY" -c "
from nnstreamer_tpu.native import runtime
assert runtime.available(), 'native core failed to build'
pool = runtime.BufferPool(block_size=1024, prealloc=2)
ptr, mv = pool.acquire(); mv[:4] = b'test'; pool.release(ptr)
assert pool.outstanding == 0
pool.destroy()
print('native OK:', runtime._load()._name)
")

# 7. CI-parity quick test slice against the installed wheel (the full
#    suite runs in CI / the dev tree; this proves the artifact is testable)
say "test slice against the installed wheel"
(cd "$WORK" && cp -r "$ROOT/tests" . && JAX_PLATFORMS=cpu run "$VPY" -m pytest \
  tests/test_core_types.py tests/test_pipeline.py tests/test_wire_interop.py -q)

say "ALL CHECKS PASSED"
