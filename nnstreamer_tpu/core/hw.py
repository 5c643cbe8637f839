"""Hardware capability probe.

Reference: ``gst/nnstreamer/hw_accel.c`` (runtime NEON/SIMD detection via
hwcap, 64 LoC) — used to pick accelerated code paths.  The TPU analog
asks the XLA backend of THIS process: platform, device kind/count, and
whether a real accelerator (vs host CPU) is attached; backends use it to
choose dtypes (bfloat16 on TPU) and batching defaults.

The probe runs in-process.  A chip belongs to one process at a time, so a
probe from a child process would either fail or come up on CPU once the
parent holds the chip — and report the wrong hardware.
"""

from __future__ import annotations

import threading
from typing import Dict, Optional

_cache: Optional[Dict[str, object]] = None
_cache_lock = threading.Lock()


def probe() -> Dict[str, object]:
    """Device facts of this process's default backend, cached:
    {'platform', 'device_kind', 'num_devices', 'accelerated', 'devices'}.
    A backend that fails to initialize raises (jax's own RuntimeError)."""
    global _cache
    with _cache_lock:
        if _cache is None:
            import jax

            devs = jax.devices()
            _cache = {
                "platform": devs[0].platform,
                "device_kind": devs[0].device_kind,
                "num_devices": len(devs),
                "accelerated": devs[0].platform != "cpu",
                "devices": [str(d) for d in devs],
            }
        return dict(_cache)


def default_device():
    """The device jax places uncommitted work on: whatever
    ``jax.default_device(...)`` names, else the first device of the
    default backend."""
    import jax

    dev = jax.config.jax_default_device
    if dev is None:
        return jax.devices()[0]
    if isinstance(dev, str):  # a platform name
        return jax.devices(dev)[0]
    return dev


def reset() -> None:
    """Drop the cached probe (tests / after backend reconfiguration)."""
    global _cache
    with _cache_lock:
        _cache = None


def has_accelerator() -> bool:
    return bool(probe()["accelerated"])


def preferred_dtype() -> str:
    """bfloat16 on accelerators (MXU-native), float32 on host CPU."""
    return "bfloat16" if has_accelerator() else "float32"
