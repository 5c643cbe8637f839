"""Device-mesh construction helpers.

The reference scales *among devices* with nnstreamer-edge transports
(SURVEY §2.3); intra-model sharding does not exist there (§2.3 "NOT
present").  The TPU build's answer is a first-class `jax.sharding.Mesh`
layer: every parallel subsystem (data/tensor/sequence parallel filters,
ring attention, the trainer) takes a mesh + axis names.

Axis vocabulary (the scaling-book convention):
  * ``dp`` — data parallel (batch split; gradient psum)
  * ``fsdp`` — fully-sharded data parallel (params sharded over dp too)
  * ``tp`` — tensor parallel (heads / hidden split; activation collectives)
  * ``sp`` — sequence/context parallel (ring attention over this axis)
  * ``pp`` — pipeline stages  * ``ep`` — expert parallel
"""

from __future__ import annotations

import math
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh

DP, FSDP, TP, SP, PP, EP = "dp", "fsdp", "tp", "sp", "pp", "ep"

#: the axis vocabulary serving configs may name (typo guard for the
#: ``mesh=`` element-prop grammar; make_mesh itself accepts any names)
KNOWN_AXES = (DP, FSDP, TP, SP, PP, EP)


def parse_mesh_spec(text: str) -> Dict[str, int]:
    """Parse the serving-config mesh grammar: ``"tp:4"`` /
    ``"dp:2,tp:2"`` / ``"dp:-1"`` (-1 = remaining devices, at most one
    axis) into ``{axis: size}``.  Empty/``"0"``/``"off"`` -> ``{}``
    (unsharded).  The one grammar shared by the tensor_filter /
    tensor_generator ``mesh=`` props and the jax-xla backend — config
    surfaces cannot drift."""
    text = (text or "").strip()
    if text in ("", "0", "off", "none"):
        return {}
    axes: Dict[str, int] = {}
    for part in text.split(","):
        part = part.strip()
        if not part:
            continue
        name, sep, size = part.partition(":")
        name = name.strip().lower()
        if not sep:
            raise ValueError(
                f"mesh spec {text!r}: expected axis:size, got {part!r}")
        if name not in KNOWN_AXES:
            raise ValueError(
                f"mesh spec {text!r}: unknown axis {name!r} "
                f"(want one of {', '.join(KNOWN_AXES)})")
        if name in axes:
            raise ValueError(f"mesh spec {text!r}: duplicate axis {name!r}")
        try:
            n = int(size)
        except ValueError:
            raise ValueError(
                f"mesh spec {text!r}: axis {name} size {size!r} is not "
                "an integer") from None
        if n == 0 or n < -1:
            raise ValueError(
                f"mesh spec {text!r}: axis {name} size must be >= 1 "
                "(or -1 = remaining devices)")
        axes[name] = n
    if sum(1 for v in axes.values() if v == -1) > 1:
        raise ValueError(f"mesh spec {text!r}: at most one axis may be -1")
    return axes


def claim_devices(axes: Dict[str, int], devices: Optional[Sequence] = None,
                  exclude: Sequence[int] = ()):
    """THE device-claiming rule for a parsed serving mesh spec (shared
    by the jax-xla backend and the slotted generator): a ``-1`` wildcard
    claims every device, explicit sizes claim a sub-mesh of the first
    N.  ``exclude`` removes device ORDINALS from the claimable pool
    first — the degraded re-shard path claims the survivors of a lost
    mesh member this way, so a rebuilt backend can never land back on
    the dead chip."""
    import math

    import jax

    devices = list(devices if devices is not None else jax.devices())
    if exclude:
        dead = {int(i) for i in exclude}
        devices = [d for d in devices if int(d.id) not in dead]
    if any(v == -1 for v in axes.values()):
        return devices
    return devices[: math.prod(axes.values())]


def shrink_axes(axes: Dict[str, int], n_avail: int) -> Dict[str, int]:
    """THE degraded-mesh shrink ladder: the largest mesh config that
    fits ``n_avail`` surviving devices, derived from the serving mesh
    ``axes``.  Data parallelism gives way first (``dp:2,tp:2`` on 3
    survivors -> ``dp:1,tp:2`` — dp only changes batch scatter, never
    the math); when even the non-dp product no longer fits, ``tp``
    halves down pow2-style (params re-shard by the same rules); an
    empty dict means "serve unsharded on one survivor".  Shared by the
    jax-xla filter backend and the slotted generator so both re-shard
    identically."""
    if n_avail <= 1:
        return {}
    out = {k: int(v) for k, v in axes.items() if k != DP}
    other = math.prod(out.values()) if out else 1
    if other <= n_avail:
        if DP in axes:
            out[DP] = n_avail // other
        return out
    # non-dp axes alone no longer fit: halve tp until they do
    tp = out.get(TP, 1)
    rest = other // max(1, tp)
    while tp > 1 and rest * tp > n_avail:
        tp //= 2
    if rest * max(1, tp) > n_avail:
        return {}
    if TP in out:
        if tp > 1:
            out[TP] = tp
        else:
            out.pop(TP)
    return out


def remesh_after_loss(current_ids: Sequence[int], axes: Dict[str, int],
                      lost_ids: Sequence[int] = (), probe=None):
    """THE survivors/shrink computation after a device loss, shared by
    the jax-xla backend and the slotted generator so both re-shard
    identically.  Identify the dead members — the runtime's reported
    ordinals when it names them, else ``probe(current_ids)`` (a
    per-device liveness probe; real XLA status strings usually do NOT
    carry the ordinal), else conservatively the LAST mesh member — and
    shrink ``axes`` to the survivors via :func:`shrink_axes`.

    Returns ``(dead_ids, new_axes, spec)`` with ``spec`` the
    :func:`mesh_spec_str` string of ``new_axes`` (``""`` = rebuild
    unsharded).  The probe distinguishes CANNOT-PROBE (``None`` —
    enumeration itself failed; fall back to the conservative
    last-member guess) from ALL-ALIVE (``()`` — every member answered,
    the loss did not reproduce): in the latter case ``dead_ids`` comes
    back EMPTY with ``axes`` unchanged, and callers must escalate to
    supervision (a plain retry may cure a transient) instead of
    condemning a healthy chip.  Whenever ``dead_ids`` is non-empty,
    every rebuild path EXCLUDES them from its device claim, so a
    replacement backend can never land back on the chip that just
    died."""
    current = [int(i) for i in current_ids]
    dead = {int(i) for i in (lost_ids or ())}
    if not dead:
        probed = probe(current) if probe is not None else None
        if probed is None:
            # no probe / probe unavailable: conservative last-member guess
            dead = {current[-1]}
        else:
            dead = {int(i) for i in probed}
    if not dead:
        # every member answered the probe: nothing provably dead,
        # nothing to shrink — the caller escalates to supervision
        return (), dict(axes), mesh_spec_str(axes)
    survivors = [i for i in current if i not in dead]
    new_axes = shrink_axes(axes, len(survivors))
    spec = mesh_spec_str(new_axes) if new_axes else ""
    return tuple(sorted(dead)), new_axes, spec


def mesh_spec_str(axes: Dict[str, int]) -> str:
    """Canonical string form of a parsed mesh spec (health/evidence
    labels): ``{}`` -> ``"0"``, else ``"dp:2,tp:2"`` in KNOWN_AXES
    order."""
    if not axes:
        return "0"
    known = [a for a in KNOWN_AXES if a in axes]
    rest = [a for a in axes if a not in KNOWN_AXES]
    return ",".join(f"{a}:{axes[a]}" for a in known + rest)


def mesh_health_info(mesh: Mesh, axes: Dict[str, int]) -> Dict[str, object]:
    """THE serving-mesh health/metrics dict (``mesh_devices``/``mesh_dp``/
    ``mesh_tp``/``mesh_axes``), shared by every element that serves on a
    mesh (jax-xla filter backend, slotted generator) so the exported
    ``nns.mesh.*`` surface cannot drift between them."""
    return {
        "mesh_devices": int(mesh.size),
        "mesh_dp": int(mesh.shape.get(DP, 1)),
        "mesh_tp": int(mesh.shape.get(TP, 1)),
        "mesh_axes": mesh_spec_str(axes),
    }


def make_mesh(
    axes: Dict[str, int], devices: Optional[Sequence] = None
) -> Mesh:
    """Build a Mesh with named axes, e.g. ``make_mesh({"dp": 2, "tp": 4})``.

    Axis sizes must multiply to the device count. ``-1`` for at most one
    axis means "whatever is left".
    """
    devices = list(devices if devices is not None else jax.devices())
    n = len(devices)
    sizes = dict(axes)
    wild = [k for k, v in sizes.items() if v == -1]
    if len(wild) > 1:
        raise ValueError("at most one axis may be -1")
    fixed = math.prod(v for v in sizes.values() if v != -1)
    if wild:
        if n % fixed:
            raise ValueError(f"{n} devices not divisible by {fixed}")
        sizes[wild[0]] = n // fixed
    if math.prod(sizes.values()) != n:
        raise ValueError(
            f"mesh axes {sizes} multiply to {math.prod(sizes.values())}, "
            f"but {n} devices are available"
        )
    arr = np.asarray(devices).reshape(tuple(sizes.values()))
    return Mesh(arr, tuple(sizes.keys()))


def single_device_mesh(axis: str = DP) -> Mesh:
    return make_mesh({axis: 1}, devices=jax.devices()[:1])


def default_mesh(n: Optional[int] = None) -> Mesh:
    """A sensible mesh for n devices: prefer dp×tp close to square
    (dp outermost → gradient psum rides the slower links, tp innermost →
    activation collectives ride the fastest ICI neighbors)."""
    devices = jax.devices() if n is None else jax.devices()[:n]
    n = len(devices)
    tp = 1
    for cand in (8, 4, 2, 1):
        if n % cand == 0 and cand <= n:
            tp = cand
            break
    return make_mesh({DP: n // tp, TP: tp}, devices=devices)


def mesh_axis_size(mesh: Mesh, axis: str) -> int:
    return mesh.shape[axis] if axis in mesh.shape else 1
