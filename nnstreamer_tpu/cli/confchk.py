"""nns-tpu-check: dump installed elements, subplugins, and configuration.

≙ the reference's ``nnstreamer-check`` / confchk CLI
(``tools/development/confchk/confchk.c``): prints what is registered per
subplugin kind and where the active configuration came from.

CLI: ``python -m nnstreamer_tpu.cli.confchk``
"""

from __future__ import annotations

import sys
from typing import List, Optional

from ..core import config, registry


def report() -> str:
    # importing the element/subplugin packages triggers self-registration
    from .. import backends as _b  # noqa: F401
    from .. import converters as _c  # noqa: F401
    from .. import decoders as _d  # noqa: F401
    from .. import elements as _e  # noqa: F401
    from ..pipeline.element import ELEMENT_TYPES

    lines: List[str] = []
    lines.append("nnstreamer_tpu configuration check")
    lines.append("=" * 40)
    from ..core import compile_cache, hw
    from ..native import runtime as native_runtime

    # the facts chip_smoke.py asserts, from the same in-process probe
    hw_info = hw.probe()
    lines.append(f"jax platform        : {hw_info['platform']}")
    lines.append(f"device kind         : {hw_info['device_kind']}")
    lines.append(f"device count        : {hw_info['num_devices']}")
    lines.append(f"jax backend devices : {hw_info['devices']}")
    lines.append(
        f"compile cache dir   : {compile_cache.enable() or '(none: cpu)'}")
    lines.append(f"mailbox             : {native_runtime.mailbox_impl()}")
    lines.append(f"config loaded from  : {config.loaded_from() or '(defaults)'}")
    lines.append("")
    factories = sorted(set(ELEMENT_TYPES))
    lines.append(f"pipeline elements ({len(factories)}):")
    for n in factories:
        cls = ELEMENT_TYPES[n]
        alias = "" if cls.FACTORY_NAME == n else f"  (alias of {cls.FACTORY_NAME})"
        lines.append(f"  {n}{alias}")
    for kind in registry.KINDS:
        names = sorted(registry.get_all(kind))
        lines.append("")
        lines.append(f"{kind} subplugins ({len(names)}):")
        if kind == registry.KIND_CUSTOM and not names:
            # the custom kind holds RUNTIME registrations (tensor_if
            # custom conditions via register_if_condition, ≙ the
            # reference's nnstreamer_if_custom_register) — empty at
            # import time by design, not a missing subplugin class
            lines.append(
                "  (runtime-registered tensor_if conditions; none "
                "registered in this process)"
            )
        for n in names:
            desc = registry.get_custom_property_desc(kind, n)
            if desc:  # Dict[str, str] -> readable "key: help" list
                desc_text = ", ".join(f"{k}: {v}" for k, v in desc.items())
                lines.append(f"  {n}  [{desc_text}]")
            else:
                lines.append(f"  {n}")
    return "\n".join(lines) + "\n"


def main(argv: Optional[List[str]] = None) -> int:
    sys.stdout.write(report())
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
