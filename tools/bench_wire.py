#!/usr/bin/env python
"""Measure the wire-integrity tax: checksum-on vs checksum-off overhead.

Emits one row per payload size comparing the three envelope modes:

* ``v1``        — legacy frames, no checksum (encode + decode);
* ``v2``        — checksummed frames, verify ON at decode (the default
                  data plane after ISSUE 4);
* ``v2_noverify`` — checksummed encode, verification skipped at decode
                  (the ``verify-checksum=false`` element property).

Reported as round trips/s plus the derived integrity tax (percent
throughput lost v1 -> v2) and the effective CRC bandwidth, so the cost
is measured, not guessed (Documentation/wire-protocol.md "Cost").
BENCH_WIRE_FRAMES / BENCH_WIRE_SIZES override the defaults; --out
writes the rows as JSON (BENCH_WIRE.json convention).

The decode path is zero-copy, so the checksum pass dominates at large
payloads — the honest framing of this number is GB/s of CRC, not a
relative slowdown of an otherwise-nearly-free decode.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from nnstreamer_tpu.core.buffer import TensorFrame  # noqa: E402
from nnstreamer_tpu.distributed import wire  # noqa: E402


def _roundtrip_rate(frame, version: int, verify: bool, n: int) -> float:
    buf = wire.encode_frame(frame, version=version)
    # warm-up (allocator, caches)
    for _ in range(3):
        wire.decode_frame(wire.encode_frame(frame, version=version),
                          verify=verify)
    t0 = time.perf_counter()
    for _ in range(n):
        buf = wire.encode_frame(frame, version=version)
        wire.decode_frame(buf, verify=verify)
    dt = time.perf_counter() - t0
    return n / dt, len(buf)


def run(sizes, n_frames) -> list:
    rows = []
    for size in sizes:
        elems = max(1, size // 4)
        frame = TensorFrame(
            [np.arange(elems, dtype=np.float32)], pts=0.5, meta={"b": 1})
        n = max(20, min(n_frames, int(4e8 // max(size, 1))))
        v1_fps, nbytes = _roundtrip_rate(frame, 1, True, n)
        v2_fps, _ = _roundtrip_rate(frame, 2, True, n)
        v2nv_fps, _ = _roundtrip_rate(frame, 2, False, n)
        # two CRC passes per round trip (encode + verify)
        crc_s = (1.0 / v2_fps) - (1.0 / v2nv_fps)  # verify pass alone
        rows.append({
            "payload_bytes": nbytes,
            "iters": n,
            "v1_rps": round(v1_fps, 1),
            "v2_rps": round(v2_fps, 1),
            "v2_noverify_rps": round(v2nv_fps, 1),
            "integrity_tax_pct": round(100.0 * (1.0 - v2_fps / v1_fps), 2),
            "verify_crc_mb_s": (
                round(nbytes / crc_s / 1e6, 1) if crc_s > 1e-9 else None),
        })
    return rows


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--out", default="", help="write rows as JSON here")
    args = ap.parse_args(argv)
    sizes = [int(s) for s in os.environ.get(
        "BENCH_WIRE_SIZES", "4096,153600,1048576").split(",")]
    n_frames = int(os.environ.get("BENCH_WIRE_FRAMES", "2000"))
    rows = run(sizes, n_frames)
    for r in rows:
        print(json.dumps(r))
    if args.out:
        with open(args.out, "w") as f:
            json.dump({"bench": "wire_checksum_overhead", "rows": rows}, f,
                      indent=2)
    return 0


if __name__ == "__main__":
    sys.exit(main())
