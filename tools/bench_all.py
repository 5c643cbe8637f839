#!/usr/bin/env python
"""Run every BASELINE.md bench row (plus the host-sourced headline variant)
and collect the JSON lines into one artifact.

Usage: python tools/bench_all.py [out.json]
Honors the same env knobs as bench.py (BENCH_DEADLINE etc.).  Exits
non-zero when any row came back without a value.
"""

import json
import os
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# (BENCH_MODEL, extra env) — mobilenet runs device- AND host-sourced so the
# headline number is published alongside its transfer-inclusive variant
ROWS = [
    ("mobilenet", {"BENCH_RAW": "1"}),  # headline + same-window raw ref
    # block ingest (frames-per-tensor batching): per-frame Python ingest
    # amortized across the micro-batch — the pipeline_vs_raw >= 0.9
    # configuration on a host whose per-frame dispatch can't keep up
    ("mobilenet", {"BENCH_RAW": "1", "BENCH_INGEST": "block"}),
    # + whole-block delivery (sink/decoder keep blocks intact): removes
    # the per-frame fan-out on the output side too — the peak streaming
    # configuration for hosts far slower than the chip
    ("mobilenet", {"BENCH_RAW": "1", "BENCH_INGEST": "block",
                   "BENCH_SINK_SPLIT": "0"}),
    # depth ablation: same window, synchronous dispatch — quantifies what
    # the depth-4 in-flight window buys on the chip
    ("mobilenet", {"BENCH_RAW": "1", "BENCH_DEPTH": "1"}),
    # int8 rows are MXU-targeted: XLA-CPU has no vectorized int8 conv
    # (scalar codegen, ~1000x slower), so these time out in a
    # JAX_PLATFORMS=cpu dry run — expected, not a defect; correctness is
    # proven small-scale by tests/test_quantize.py
    ("mobilenet", {"BENCH_QUANT": "1"}),  # int8 MXU path
    ("mobilenet", {"BENCH_BATCH": "256"}),  # amortizes per-batch costs
    # cheapest per-frame device time + fewest per-batch round trips
    ("mobilenet", {"BENCH_QUANT": "1", "BENCH_BATCH": "256"}),
    # every lever at once: block ingest + whole-block delivery + int8 MXU
    # + batch 256 — the "don't stop at parity" configuration
    ("mobilenet", {"BENCH_RAW": "1", "BENCH_INGEST": "block",
                   "BENCH_SINK_SPLIT": "0", "BENCH_QUANT": "1",
                   "BENCH_BATCH": "256"}),
    ("ssd", {}),
    ("ssd", {"BENCH_QUANT": "1"}),  # int8 backbone
    ("yolov5", {}),
    ("yolov5", {"BENCH_QUANT": "1"}),  # int8 backbone/neck
    ("posenet", {}),
    ("vit", {}),
    # latency-optimized serving config (BASELINE.md tracks p50 per-frame
    # latency): small batch, synchronous dispatch — the fps column is NOT
    # the headline, the e2e_latency fields are
    ("mobilenet", {"BENCH_BATCH": "8", "BENCH_DEPTH": "1",
                   "BENCH_FRAMES": "1024", "BENCH_BATCH_TIMEOUT": "2"}),
    ("mnist_trainer", {}),
    # host-sourced frames: how real streams arrive (the other mobilenet
    # rows isolate the dataplane with device-resident input)
    ("mobilenet", {"BENCH_HOST": "1"}),
]


def _write_rows(out_path, results):
    """Atomic write after every row: a kill mid-sweep keeps the rows
    already measured and never truncates the artifact."""
    tmp = out_path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(results, f, indent=2)
    os.replace(tmp, out_path)


def main() -> int:
    out_path = sys.argv[1] if len(sys.argv) > 1 else "bench_rows.json"
    results = []
    for model, extra in ROWS:
        env = {**os.environ, "BENCH_MODEL": model, **extra}
        print(f"[bench_all] {model} {extra or ''}...", flush=True)
        # one bench.py process per row, one after another: this parent
        # never imports jax, so each child has the chip to itself
        r = subprocess.run(
            [sys.executable, os.path.join(ROOT, "bench.py")],
            capture_output=True, text=True, env=env,
        )
        row = None
        for line in reversed(r.stdout.splitlines()):
            line = line.strip()
            if line.startswith("{"):
                try:
                    row = json.loads(line)
                    break
                except json.JSONDecodeError:
                    continue
        if row is None:
            row = {
                "metric": model, "value": None, "unit": None,
                "vs_baseline": None,
                "error": f"no JSON line (rc={r.returncode})",
            }
        print(f"[bench_all]   -> {json.dumps(row)}", flush=True)
        row["_env"] = {"BENCH_MODEL": model, **extra}
        results.append(row)
        _write_rows(out_path, results)
    failed = sum(1 for row in results if row.get("value") is None)
    print(f"[bench_all] wrote {out_path} ({failed} of {len(results)} "
          "row(s) without a value)")
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
