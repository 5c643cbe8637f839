"""jax-xla: the flagship filter backend — JIT-compiles models to XLA TPU
executables.

This is the TPU-native answer to the reference's backend zoo
(``ext/nnstreamer/tensor_filter/``, e.g. ``tensor_filter_tensorflow_lite.cc``
TFLiteCore open/invoke, ``tensor_filter_edgetpu.cc`` device binding): one
backend, any JAX-expressible model, compiled once per shape bucket and
dispatched as a single XLA call per micro-batch.

Model resolution (the ``model=`` property):

* a name registered in-process via :func:`register_jax_model`
  (≙ custom-easy, but jit-compiled);
* a ``.py`` file defining ``get_model() -> (fn, params)`` where
  ``fn(params, inputs: list[Array]) -> list[Array]``
  (≙ the python3 subplugin, but the function is traced, not interpreted);
* a ``.msgpack`` flax-serialized params file with custom prop
  ``arch:<zoo-name>`` naming a model family from ``nnstreamer_tpu.models``;
* an Orbax checkpoint directory with the same ``arch:`` prop.

TPU-first design:

* **shape-bucketed compilation** — XLA needs static shapes; batches are
  padded up to the next power of two and sliced back, so a steady stream
  compiles exactly once per bucket (the "flexible tensors vs static XLA"
  policy from SURVEY §7 hard-part (b)).
* **native invoke_batch** — one XLA call per micro-batch (dispatch
  amortization; the ≥1000 fps lever).
* **donation** — input device buffers are donated to the executable where
  safe, letting XLA reuse HBM (≙ allocate-in-invoke).
* **device residency** — outputs stay on device (jax.Array); chained
  jax-xla filters never bounce through host (≙ zero-copy GstMemory).
* optional ``dtype:bfloat16`` custom prop casts params/compute to bf16
  (MXU-native).
* **sharded serving** — the ``mesh=`` prop (``mesh=tp:4`` /
  ``mesh=dp:2,tp:2``; legacy custom props ``mesh_dp:2,mesh_tp:4`` still
  accepted) runs ONE logical filter across a device mesh: params sharded
  by the parallel layer's rules (``parallel/sharding.py``) and staged
  across the WHOLE mesh before serving, ``invoke``/``invoke_batch``/
  ``invoke_batch_donated`` compiled under explicit ``NamedSharding``
  in/out specs (batch scattered over ``dp``, replicated over ``tp``),
  host-staged batches placed directly in the sharded layout by the
  ingest lane, XLA SPMD inserts the collectives.  The reference's only
  multi-device story is stream fan-out over nnstreamer-edge transports
  (SURVEY §2.3); intra-model sharding of a *serving* pipeline is
  TPU-native net-new.
"""

from __future__ import annotations

import importlib.util
import os
import threading
from typing import Any, Callable, Dict, List, Optional, Tuple

import numpy as np

from ..core.config import EXPORTED_MODEL_EXTS
from ..core.resilience import DeviceLostError, device_call
from ..core.tracer import note
from ..core.types import FORMAT_STATIC, StreamSpec, TensorSpec
from .base import FilterBackend, register_backend

_registry_lock = threading.Lock()
_model_registry: Dict[str, Tuple[Callable, Any, Optional[StreamSpec], Optional[StreamSpec]]] = {}


def register_jax_model(
    name: str,
    fn: Callable[[Any, List[Any]], List[Any]],
    params: Any = None,
    in_spec: Optional[StreamSpec] = None,
    out_spec: Optional[StreamSpec] = None,
) -> None:
    """Register an in-process JAX model under `name`.

    ``fn(params, inputs) -> outputs`` must be jit-traceable. Single-array
    models may return a bare array.
    """
    with _registry_lock:
        _model_registry[name] = (fn, params, in_spec, out_spec)


def unregister_jax_model(name: str) -> bool:
    with _registry_lock:
        return _model_registry.pop(name, None) is not None


def export_model(fn, params, frame_specs, path: str,
                 batch_polymorphic: bool = True) -> None:
    """Serialize ``fn(params, inputs) -> outputs`` as a ``.jaxexport``
    artifact (params baked in as StableHLO constants).

    ``frame_specs``: one ``(shape, dtype)`` pair per input tensor, for a
    SINGLE frame (no batch dim).  With ``batch_polymorphic`` (default) a
    symbolic leading batch dim is prepended, so the artifact serves both
    per-frame and micro-batched invokes natively — export this way unless
    the model genuinely cannot be batched.
    """
    import jax
    from jax import export as jax_export

    def call(*xs):
        out = fn(params, list(xs))
        return tuple(out) if isinstance(out, (list, tuple)) else (out,)

    specs = []
    batch = jax_export.symbolic_shape("b")[0] if batch_polymorphic else None
    for shape, dtype in frame_specs:
        full = ((batch,) + tuple(shape)) if batch_polymorphic else tuple(shape)
        specs.append(jax.ShapeDtypeStruct(full, np.dtype(dtype)))
    exported = jax_export.export(jax.jit(call))(*specs)
    with open(path, "wb") as f:
        f.write(exported.serialize())


def _next_pow2(n: int) -> int:
    p = 1
    while p < n:
        p <<= 1
    return p


# accelerator wish name -> candidate jax platform names, in probe order
# ("npu" wishes — reference edgetpu/srnpu parlance — map to the TPU);
# None = the process's default device.
_WISH_PLATFORMS = {
    "auto": (None,),
    "default": (None,),
    "tpu": ("tpu",),
    "npu": ("tpu",),
    "npu.edgetpu": ("tpu",),
    "gpu": ("gpu", "cuda", "rocm"),
    "cpu": ("cpu",),
    "cpu.simd": ("cpu",),
}

# the wish vocabulary is owned by base.KNOWN_ACCELERATORS (the parse
# side); this mapping must cover it so parse/placement cannot drift.
# Explicit raise (not assert): must survive python -O
from .base import KNOWN_ACCELERATORS as _KNOWN

if set(_WISH_PLATFORMS) != set(_KNOWN):
    raise ImportError(
        "accelerator vocabulary drift between base.KNOWN_ACCELERATORS and "
        f"jax_xla._WISH_PLATFORMS: {sorted(set(_WISH_PLATFORMS) ^ set(_KNOWN))}"
    )
del _KNOWN


def pick_device(wishes):
    """Resolve an accelerator wish list to a concrete jax.Device.

    Honors the reference's ordered-wish semantics
    (``tensor_filter_common.c:2719-2878``: first available hardware in
    the list wins) plus a TPU-native extension: a ``.N`` suffix pins a
    specific device ordinal — ``accelerator=true:tpu.1,cpu`` means
    "second TPU chip, else CPU".  ``auto``/``default`` take the process
    default device.  A wish that is unknown, names a platform this
    process does not have, or an ordinal past its last device falls
    through to the next; a list that matches NO device raises — serving
    from some other device than every one asked for is never done
    quietly.
    """
    import jax

    from ..core.hw import default_device

    for wish in wishes:
        name = wish.strip().lower()
        idx = 0
        # trailing .N = device ordinal (distinct from variant suffixes
        # like cpu.simd / npu.edgetpu, which are non-numeric)
        head, _, tail = name.rpartition(".")
        if tail.isdigit() and head:
            name, idx = head, int(tail)
        for plat in _WISH_PLATFORMS.get(name, ()):
            if plat is None:
                return default_device()
            try:
                devs = jax.devices(plat)
            except RuntimeError:  # this process has no such backend
                continue
            if idx < len(devs):
                return devs[idx]
    raise RuntimeError(
        f"accelerator wish list {list(wishes)} matches no device of this "
        f"process; jax.devices() = {jax.devices()}")


def surviving_device(device, dead_ids):
    """``device`` itself unless its ordinal is in ``dead_ids`` (devices
    lost earlier in this process's life); then a survivor, same platform
    preferred — a degraded restart must not land back on the dead chip.
    Raises :class:`DeviceLostError` when nothing survives."""
    import jax

    dead = {int(i) for i in dead_ids or ()}
    if int(device.id) not in dead:
        return device
    alive = [d for d in jax.devices()
             if d.platform == device.platform
             and int(d.id) not in dead] or [
        d for d in jax.devices() if int(d.id) not in dead]
    if not alive:
        raise DeviceLostError(
            "no surviving device to place on",
            device_ids=tuple(sorted(dead)))
    return alive[0]


def probe_device_ids(ids):
    """Per-device liveness probe: a tiny transfer+sync against each of
    the given ordinals, returning the ids that FAILED (the dead set).
    The re-mesh ladder calls this when a :class:`DeviceLostError`
    carries no ordinals — real XLA status strings usually name no chip,
    and guessing wrong would re-place the rebuilt backend on the dead
    one.  A probe that cannot even enumerate devices returns ``None``
    (the caller falls back to its conservative guess); ``()`` means
    every probed member ANSWERED — the loss did not reproduce, and the
    caller must not condemn a healthy chip."""
    import jax

    from ..core.log import get_logger

    try:
        by_id = {int(d.id): d for d in jax.devices()}
    except Exception as e:  # noqa: BLE001 — runtime may be wedged
        get_logger("jax-xla").warning("device probe: enumeration failed (%s)", e)
        return None
    dead = []
    for i in ids:
        d = by_id.get(int(i))
        try:
            if d is None:
                raise RuntimeError("no longer enumerated")
            jax.device_put(np.zeros((1,), np.float32), d).block_until_ready()
        except (KeyboardInterrupt, SystemExit):
            raise
        except Exception as e:  # noqa: BLE001 — dead chip detection
            get_logger("jax-xla").warning("device probe: id %d dead (%s)", i, e)
            dead.append(int(i))
    return tuple(dead)


class JaxXla(FilterBackend):
    NAME = "jax-xla"

    #: host-staged batches are really copied to device (device_put), so
    #: the filter's staging lane may reuse its host buffers after emission
    SUPPORTS_STAGING = True

    #: honors the ``mesh=`` prop (sharded serving across a device mesh)
    SUPPORTS_MESH = True

    def __init__(self):
        super().__init__()
        self._fn: Optional[Callable] = None
        self._params: Any = None
        self._in_spec: Optional[StreamSpec] = None
        self._out_spec: Optional[StreamSpec] = None
        self._device = None
        # compile cache, LRU-bounded (core/slots.lru_bucket — the shared
        # compile-bucket discipline): a mesh-shape / flexible-shape sweep
        # mints a fresh (donate, nargs, shapes) key per configuration and
        # each entry pins a compiled XLA program, so unbounded growth is
        # a slow leak on long-lived servers (evicted keys just retrace)
        from collections import OrderedDict

        self._jit_cache: "OrderedDict[Tuple, Any]" = OrderedDict()
        self._cache_lock = threading.Lock()
        self._reload_lock = threading.Lock()  # double-buffered hot reload
        self._posts: List[Callable[[List[Any]], List[Any]]] = []
        # sharded serving (mesh= prop / legacy mesh_* custom props)
        self._mesh = None
        self._mesh_axes: Dict[str, int] = {}
        self._dp = 1
        self._batch_sharding = None
        self._replicated = None
        self.mesh_scatters = 0  # host batches scattered onto the mesh

    # -- framework info -----------------------------------------------------
    def framework_info(self):
        info = super().framework_info()
        info.verify_model_path = False  # may be a registry key
        info.hw_list = ("tpu", "cpu")
        return info

    # -- model loading ------------------------------------------------------
    def _resolve_model(self, model_path: Optional[str]):
        if not model_path:
            raise ValueError("jax-xla requires model= (registry key or file)")
        with _registry_lock:
            entry = _model_registry.get(model_path)
        if entry is not None:
            return entry
        if model_path.endswith(EXPORTED_MODEL_EXTS):
            if not os.path.isfile(model_path):
                raise FileNotFoundError(
                    f"exported-model file not found: {model_path}")
            return self._load_exported(model_path)
        if model_path.endswith(".py") and os.path.isfile(model_path):
            spec = importlib.util.spec_from_file_location(
                f"_nns_jax_model_{abs(hash(model_path))}", model_path
            )
            mod = importlib.util.module_from_spec(spec)
            spec.loader.exec_module(mod)
            if not hasattr(mod, "get_model"):
                raise ValueError(f"{model_path}: must define get_model()")
            got = mod.get_model()
            fn, params = got[0], got[1]
            return (fn, params) + tuple(got[2:4]) + (None,) * (2 - len(got[2:4]))
        arch = self.custom_props.get("arch")
        if arch:
            from .. import models as zoo

            fn, params, in_spec, out_spec = zoo.build(arch, self.custom_props)
            if os.path.isfile(model_path):  # msgpack flax params
                from flax import serialization

                with open(model_path, "rb") as f:
                    params = serialization.from_bytes(params, f.read())
            elif os.path.isdir(model_path):  # orbax checkpoint
                import orbax.checkpoint as ocp

                ckptr = ocp.StandardCheckpointer()
                params = ckptr.restore(os.path.abspath(model_path), params)
            return fn, params, in_spec, out_spec
        raise FileNotFoundError(
            f"jax-xla cannot resolve model {model_path!r} "
            "(not registered; for files pass custom=arch:<zoo-name>)"
        )

    @staticmethod
    def _load_exported(model_path: str):
        """Load a serialized ``jax.export`` artifact (StableHLO): the
        TPU-native model interchange format.  Any jitted JAX function
        ``jax.export.export(jit_fn)(specs).serialize()``-d to a file runs
        here with schemas derived from the embedded avals — the XLA
        answer to the reference's "drop a model file in" flow (its
        subplugins each embed a vendor interpreter;
        ``tensor_filter_tensorflow_lite.cc:158``).  Constants live inside
        the StableHLO module, so there is no separate params pytree.

        Batch handling: artifacts from :func:`export_model` carry a
        symbolic leading batch dim, so per-frame invokes add/strip a
        length-1 axis and micro-batches run natively (one XLA call).
        Fixed-shape artifacts invoke per-frame exactly; a batched call
        against one unrolls inside the trace (correct, but export
        batch-polymorphic for speed — ``call_exported`` has no batching
        rule, so vmap is not an option)."""
        import jax
        from jax import export as jax_export

        with open(model_path, "rb") as f:
            blob = f.read()
        try:
            exported = jax_export.deserialize(blob)
        except Exception as e:  # noqa: BLE001 — loader boundary
            raise ValueError(
                f"{model_path}: not a jax.export artifact (produce one "
                "with nnstreamer_tpu.backends.jax_xla.export_model, or "
                "jax.export.export(jit_fn)(specs).serialize()); raw "
                f"StableHLO text/bytecode is not loadable directly: {e}"
            ) from e

        in_ranks = [len(a.shape) for a in exported.in_avals]
        symbolic = any(
            not isinstance(d, int)
            for a in exported.in_avals for d in a.shape
        )

        normalize = JaxXla._normalize_out

        def fn(params, xs: List[Any]) -> List[Any]:
            if symbolic:
                if all(x.ndim == r - 1 for x, r in zip(xs, in_ranks)):
                    # per-frame invoke of a batch-polymorphic artifact
                    out = normalize(exported.call(*[x[None] for x in xs]))
                    return [o[0] for o in out]
                return normalize(exported.call(*xs))
            if all(x.ndim == r + 1 for x, r in zip(xs, in_ranks)):
                # micro-batch against a fixed-shape artifact: lax.map
                # traces the body ONCE (vmap has no call_exported
                # batching rule; a python unroll would inline the whole
                # module per bucket row)
                from jax import lax

                outs = lax.map(
                    lambda row: tuple(normalize(exported.call(*row))),
                    tuple(xs))
                return list(outs)
            return normalize(exported.call(*xs))

        def spec_of(avals) -> Optional[StreamSpec]:
            dims = [d for a in avals for d in a.shape]
            if any(not isinstance(d, int) for d in dims):
                return None  # symbolic: schema derives from the stream
            return StreamSpec(
                tuple(TensorSpec(tuple(a.shape), np.dtype(a.dtype))
                      for a in avals),
                FORMAT_STATIC,
            )

        return (fn, None, spec_of(exported.in_avals),
                spec_of(exported.out_avals))

    def _mesh_axes_from_props(self, props: Dict[str, Any]) -> Dict[str, int]:
        """The serving mesh config: the first-class ``mesh=`` prop
        (``mesh=tp:4`` / ``mesh=dp:2,tp:2`` — parallel/mesh.py grammar)
        merged over legacy ``mesh_<axis>:<size>`` custom props.  Empty
        dict = unsharded.  A degraded re-shard's survivor spec
        (``mesh_remesh_override``) REPLACES the configured mesh
        entirely — legacy ``mesh_*`` custom props included: a shrunk
        config must never re-merge axes the survivors can no longer
        satisfy."""
        from ..parallel.mesh import parse_mesh_spec

        spec = str(props.get("mesh") or "")
        if props.get("mesh_remesh_override"):
            return dict(parse_mesh_spec(spec)) if spec else {}
        axes = {}
        for k, v in self.custom_props.items():
            if k.startswith("mesh_"):
                axes[k[len("mesh_"):]] = int(v)
        if spec:
            axes.update(parse_mesh_spec(spec))
        return axes

    def open(self, model_path, props):
        super().open(model_path, props)
        import jax

        from ..core.compile_cache import enable as enable_compile_cache

        enable_compile_cache()  # before the first compile (zoo init)
        self._fn, self._params, self._in_spec, self._out_spec = self._resolve_model(
            model_path
        )
        # degraded re-shard bottomed out at unsharded: the pick may be the
        # very chip that died — place on a survivor instead of
        # crash-looping on it
        self._device = surviving_device(
            pick_device(props.get("accelerators") or ["auto"]),
            props.get("mesh_exclude_ids"))
        dtype = self.custom_props.get("dtype")
        if dtype in ("bfloat16", "float16", "float32"):
            import jax.numpy as jnp

            target = jnp.dtype(dtype)
            self._params = jax.tree.map(
                lambda a: a.astype(target)
                if hasattr(a, "dtype") and np.issubdtype(a.dtype, np.floating)
                else a,
                self._params,
            )
        mesh_axes = self._mesh_axes_from_props(props)
        if mesh_axes:
            from ..parallel.mesh import claim_devices, make_mesh
            from ..parallel.sharding import (
                batch_sharding,
                replicated,
                shard_params,
                transformer_rules,
            )

            # degraded re-shard (element recovery ladder): lost device
            # ordinals are excluded from the claimable pool, so a
            # rebuilt backend lands only on survivors
            self._mesh = make_mesh(
                mesh_axes,
                devices=claim_devices(
                    mesh_axes,
                    exclude=props.get("mesh_exclude_ids") or ()))
            self._mesh_axes = {k: self._mesh.shape[k] for k in mesh_axes}
            self._dp = self._mesh.shape.get("dp", 1)
            if self._params is not None:
                # rule misses fall back to replicated — safe for any family
                self._params = shard_params(
                    self._params, self._mesh, transformer_rules(tp_axis="tp")
                )
                # every shard LANDED on its device before this backend is
                # declared open: a hot swap's pointer exchange must never
                # activate a half-staged mesh (the staging thread pays
                # this wait, not the serving thread)
                jax.block_until_ready(self._params)
            self._batch_sharding = batch_sharding(self._mesh, "dp")
            self._replicated = replicated(self._mesh)
        elif self._params is not None:
            self._params = jax.device_put(self._params, self._device)

    def close(self):
        self._jit_cache.clear()
        self._fn = None
        self._params = None

    def reload(self, model_path):
        """Hot reload: build the new params fully, then swap under the lock
        (≙ double-buffered interpreter reload,
        tensor_filter_tensorflow_lite.cc:274)."""
        import jax

        fn, params, in_spec, out_spec = self._resolve_model(model_path)
        if params is not None:
            if self._mesh is not None:
                from ..parallel.sharding import shard_params, transformer_rules

                params = shard_params(
                    params, self._mesh, transformer_rules(tp_axis="tp")
                )
                # fully staged across the mesh BEFORE the pointer swap
                # below — the serving thread never sees a torn half-mesh
                jax.block_until_ready(params)
            else:
                params = jax.device_put(params, self._device)
        with self._reload_lock:
            self._fn, self._params = fn, params
            self._in_spec = in_spec or self._in_spec
            self._out_spec = out_spec or self._out_spec
            self._jit_cache.clear()
            self.model_path = model_path

    # -- model info ---------------------------------------------------------
    def get_model_info(self):
        return self._in_spec, self._out_spec

    @staticmethod
    def _normalize_out(out) -> List[Any]:
        if isinstance(out, (list, tuple)):
            return list(out)
        return [out]

    # -- device-fused postprocess -------------------------------------------
    def append_postprocess(self, fn: Callable[[List[Any]], List[Any]]) -> None:
        """Fold a jit-traceable postprocess (e.g. a decoder's device half)
        into the compiled program: outputs = fn(model outputs).

        The TPU-native replacement for the reference's host-side decoder
        hop (tensordec-*.c operate on mapped CPU memory after invoke): XLA
        fuses the postprocess into the same program, so only its (usually
        tiny) result ever crosses PCIe.  Used by the pipeline's device-
        fusion pass; survives hot reload (applied outside the model fn).

        Postprocess fns that take a ``single_device`` keyword learn
        whether THIS backend compiles for one device: a Mosaic kernel
        (e.g. the Pallas top-1) cannot be auto-partitioned over a mesh,
        so such a post keeps to jnp when it is False.
        """
        self._posts.append(self._tell_single_device(fn))
        with self._cache_lock:
            self._jit_cache.clear()

    def _tell_single_device(self, fn):
        """``fn`` with ``single_device=`` bound to whether this backend
        compiles for one device, where ``fn`` takes that keyword (a model
        or a post that holds a Mosaic kernel); ``fn`` itself otherwise."""
        from ..models import takes_single_device

        if not takes_single_device(fn):
            return fn
        return lambda *args, _fn=fn: _fn(
            *args, single_device=self._mesh is None)

    def _apply_posts(self, outs: List[Any]) -> List[Any]:
        for post in self._posts:
            outs = self._normalize_out(post(outs))
        return outs

    def set_input_info(self, in_spec: StreamSpec) -> StreamSpec:
        import jax

        if not in_spec.is_static:
            raise ValueError("jax-xla needs a static input schema to trace")
        dummies = [
            jax.ShapeDtypeStruct(t.shape, t.dtype) for t in in_spec.tensors
        ]
        model = self._tell_single_device(self._fn)
        outs = jax.eval_shape(
            lambda p, xs: self._apply_posts(self._normalize_out(model(p, xs))),
            self._params, dummies,
        )
        spec = StreamSpec(
            tuple(TensorSpec(tuple(o.shape), np.dtype(o.dtype)) for o in outs),
            FORMAT_STATIC,
            in_spec.framerate,
        )
        self._out_spec = spec
        return spec

    # -- compilation --------------------------------------------------------
    def _donation_forced(self) -> Optional[bool]:
        """The legacy custom prop "donate:true|false" pins donation for
        EVERY invoke (the caller takes responsibility for input privacy);
        None = decide per call path."""
        forced = self.custom_props.get("donate", "").lower()
        if forced in ("1", "true"):
            return True
        if forced in ("0", "false"):
            return False
        return None

    def _donation_ok(self) -> bool:
        """Donation for a caller-private batch (invoke_batch_donated):
        on by default except on CPU, where XLA ignores donation and warns
        per compile — custom prop donate: overrides either way."""
        forced = self._donation_forced()
        if forced is not None:
            return forced
        return self._device is not None and self._device.platform != "cpu"

    #: live compiled programs kept per backend (LRU; evicted keys retrace)
    JIT_CACHE_MAX = 64

    def _compiled(self, key: Tuple, donate: bool = False,
                  batched: bool = False):
        from ..core.slots import lru_bucket

        cache_key = (donate, batched) + key

        def build(_key):
            import jax

            model = self._tell_single_device(self._fn)
            out_sharding = None
            if self._mesh is not None:
                # mesh mode: outputs carry explicit NamedSharding specs —
                # batch-carrying leaves stay scattered on dp, everything
                # else replicated — so a chained consumer (pool, window,
                # next filter) sees a committed placement, not whatever
                # GSPMD happened to infer
                bucket = key[1][0][0] if batched else None

                def out_sharding(o):  # noqa: F811 — trace-time closure
                    if (batched and getattr(o, "ndim", 0) >= 1
                            and o.shape[0] == bucket):
                        return self._batch_sharding
                    return self._replicated

            def call(params, *xs):
                outs = self._normalize_out(model(params, list(xs)))
                outs = self._apply_posts(outs)
                if out_sharding is not None:
                    outs = [
                        jax.lax.with_sharding_constraint(o, out_sharding(o))
                        for o in outs
                    ]
                return tuple(outs)

            # donation: XLA reuses the input arrays' HBM for outputs
            # (zero per-batch device allocations in steady state).
            # Only ever set for inputs the CALLER declared private —
            # the filter's freshly stacked/staged batches — or when
            # the "donate:true" custom prop pins it; upstream-shared
            # arrays (tee fan-out, pre-batched blocks) never donate.
            donate_nums = tuple(range(1, 1 + key[0])) if donate else ()
            if self._mesh is None:
                return jax.jit(call, donate_argnums=donate_nums)
            # mesh mode: inputs compiled under explicit NamedSharding in
            # specs — params at their rule-derived placements, the data
            # args scattered on dp (batch) or replicated (per-frame)
            in_sh = self._batch_sharding if batched else self._replicated
            param_sh = (
                jax.tree.map(lambda a: a.sharding, self._params)
                if self._params is not None else None
            )
            return jax.jit(
                call, donate_argnums=donate_nums,
                in_shardings=(param_sh,) + (in_sh,) * key[0],
            )

        with self._cache_lock:
            return lru_bucket(
                self._jit_cache, cache_key, build, self.JIT_CACHE_MAX)

    def _device_call(self, fn, *args, inject=True):
        """Every compiled-program execution funnels through the shared
        classification boundary (``core/resilience.device_call``: the
        deterministic ``device.oom`` / ``device.lost`` fault sites plus
        raw-runtime-error typing) so the element-side recovery ladders —
        shrink-retry, slot shed, degraded re-mesh — key on types, never
        on XLA status strings.  Transfer/staging paths pass
        ``inject=False``: they still get the typed classification (a
        transfer-time ``RESOURCE_EXHAUSTED`` engages the same OOM
        ladder) but armed fault counters keep firing at compiled-call
        boundaries only.  A lost device marks this backend degraded
        until it is replaced."""
        try:
            return device_call(fn, *args, inject=inject)
        except DeviceLostError:
            self.degraded = True
            raise

    def trim_caches(self) -> int:
        """Memory-pressure relief: drop the OLDEST half of the live
        compiled programs (they retrace on next use; the hot bucket —
        most recently used — survives, so the steady-state stream pays
        nothing).  Called by the filter's OOM recovery and the
        watermark monitor."""
        with self._cache_lock:
            drop = len(self._jit_cache) // 2
            for _ in range(drop):
                self._jit_cache.popitem(last=False)
        return drop

    def mesh_device_ids(self) -> Tuple[int, ...]:
        """Ordinals of the devices this backend serves on (empty when
        unsharded) — the survivors calculation of the re-mesh ladder."""
        if self._mesh is None:
            return ()
        return tuple(int(d.id) for d in self._mesh.devices.flat)

    def remesh_spec_after_loss(self, lost_ids):
        """``(spec, dead_ids)`` to rebuild with after a device loss
        (``parallel/mesh.remesh_after_loss``: dp gives way first, then
        tp halves, then unsharded).  When the runtime did not name the
        lost ordinals (real XLA status strings usually don't),
        :func:`probe_device_ids` finds them with a per-device liveness
        probe; only if the probe is UNAVAILABLE is the LAST member
        conservatively assumed dead.  A probe that reaches every member
        (the loss did not reproduce) yields ``None`` just like an
        unsharded backend: no re-mesh story — the caller escalates to
        supervision, whose plain retry may cure a transient, rather
        than condemning a healthy chip.  ``dead_ids`` is never empty
        when a pair IS returned — the caller excludes them from every
        future claim, so the rebuilt backend cannot land back on the
        dead chip."""
        if self._mesh is None:
            return None
        from ..parallel.mesh import remesh_after_loss

        dead, _axes, spec = remesh_after_loss(
            self.mesh_device_ids(), self._mesh_axes, lost_ids,
            probe=probe_device_ids)
        if not dead:
            return None
        return spec, dead

    def dead_ordinals_after_loss(self, lost_ids):
        """Exclusion ordinals when there is no re-mesh story: reported
        ids win; an UNSHARDED backend probes its own serving device —
        the only chip the loss could implicate — so the supervision
        restart places on a survivor instead of crash-looping on the
        dead ordinal.  A probe that answers "alive" yields ``()`` (a
        spurious loss condemns nobody); a probe that cannot even
        enumerate condemns the lone chip conservatively."""
        ids = tuple(int(i) for i in (lost_ids or ()))
        if ids or self._mesh is not None or self._device is None:
            return ids
        own = int(self._device.id)
        probed = probe_device_ids((own,))
        if probed is None:
            return (own,)
        return tuple(int(i) for i in probed)

    def _put(self, a, sharding=None) -> Any:
        # classification-only boundary (inject=False): a transfer-time
        # RESOURCE_EXHAUSTED surfaces typed so the element-side OOM
        # ladder (shrink-retry, trim) engages, without the armed fault
        # sites firing mid-staging
        return self._device_call(self._put_raw, a, sharding, inject=False)

    def _put_raw(self, a, sharding=None) -> Any:
        import jax

        if self._mesh is not None:
            # mesh placement: a bare put means "replicate" (per-frame
            # invoke), never a single-device gather.  Resharding an
            # already-placed array is a device-side scatter/collective,
            # not a host bounce; an array already carrying the target
            # sharding passes through untouched.
            target = sharding if sharding is not None else self._replicated
            if isinstance(a, jax.Array) and a.sharding == target:
                return a
            return jax.device_put(
                a if isinstance(a, jax.Array) else np.asarray(a), target)
        if sharding is not None:
            return jax.device_put(a, sharding)
        if isinstance(a, jax.Array):
            # zero-copy pass-through only when the array already lives on
            # THIS filter's device; a chained upstream filter pinned to a
            # different chip hands us its residents — move them (device-
            # to-device, no host bounce) or jit would raise incompatible-
            # devices / silently ignore the pin
            if a.devices() == {self._device}:
                return a
            return jax.device_put(a, self._device)
        return jax.device_put(np.asarray(a), self._device)

    def _bucket(self, n: int) -> int:
        """Compile-bucket size for a batch of ``n``: next power of two,
        rounded up to a dp multiple so the mesh scatter is always even."""
        bucket = _next_pow2(n)
        if bucket % self._dp:
            bucket = ((bucket + self._dp - 1) // self._dp) * self._dp
        return bucket

    @staticmethod
    def _pad_rows(arr, bucket: int, xp=np):
        """THE pad-to-bucket rule (edge-repeat rows on dim 0), shared by
        every staging/dispatch site; ``xp`` picks host np or device
        jnp.  Identity when already at the bucket."""
        n = int(arr.shape[0])
        if bucket == n:
            return arr
        return xp.pad(
            arr, [(0, bucket - n)] + [(0, 0)] * (arr.ndim - 1),
            mode="edge")

    @property
    def _mesh_on_cpu(self) -> bool:
        return (self._mesh is not None
                and next(iter(self._mesh.devices.flat)).platform == "cpu")

    # -- placement identity / mesh observability ----------------------------
    def staging_placement(self):
        """Hashable placement-domain token for the staging-buffer pool:
        buffers staged for one mesh/device must never be pooled into
        another's ring (core.buffer.DeviceBufferPool keys on it)."""
        if self._mesh is not None:
            from ..parallel.mesh import mesh_spec_str

            return ("mesh", mesh_spec_str(self._mesh_axes),
                    tuple(d.id for d in self._mesh.devices.flat))
        if self._device is not None:
            return ("dev", self._device.platform, self._device.id)
        return None

    def mesh_info(self) -> Dict[str, Any]:
        """Serving-mesh facts for health()/the metrics registry
        (``nns.mesh.*``): empty when unsharded."""
        if self._mesh is None:
            return {}
        from ..parallel.mesh import mesh_health_info

        info = mesh_health_info(self._mesh, self._mesh_axes)
        info["mesh_scatters"] = int(self.mesh_scatters)
        return info

    # -- execution ----------------------------------------------------------
    def invoke(self, inputs: List[Any]) -> List[Any]:
        with self._reload_lock:
            # single frame has no batch dim to scatter: replicate on a mesh
            xs = [self._put(a, self._replicated) for a in inputs]
            key = (len(xs),) + tuple((tuple(x.shape), str(x.dtype)) for x in xs)
            out = self._device_call(
                self._compiled(key, donate=bool(self._donation_forced())),
                self._params, *xs)
        return list(out)

    def _stage_sharded(self, arrays: List[Any]) -> List[Any]:
        """Lane-thread hook body for a mesh backend: pad each host batch
        to the dp-divisible compile bucket and scatter it STRAIGHT into
        the batch NamedSharding — each dp shard lands on its owning
        device from here, so the transfer overlaps the previous batch's
        compute exactly like the single-device lane path (the scatter
        never re-runs on the dispatch thread)."""
        return self._device_call(
            self._stage_sharded_raw, arrays, inject=False)

    def _stage_sharded_raw(self, arrays: List[Any]) -> List[Any]:
        import jax

        n = int(arrays[0].shape[0])
        bucket = self._bucket(n)
        staged = []
        for a in arrays:
            arr = np.asarray(a)
            if bucket != n:
                arr = self._pad_rows(arr, bucket)  # pad copies
            elif self._mesh_on_cpu:
                # XLA's CPU client zero-copies aligned host arrays into
                # device_put shards: hand it a private copy or the staged
                # jax.Array aliases the pooled staging buffer the lane is
                # about to overwrite (same bug class as the single-device
                # path below; regression-pinned there)
                arr = np.array(arr)
            staged.append(jax.device_put(arr, self._batch_sharding))
        jax.block_until_ready(staged)
        self.mesh_scatters += 1
        return staged

    def to_device(self, arrays: List[Any]) -> List[Any]:
        """Staging-lane hook: place host-staged batches on this filter's
        device.  Runs ON THE LANE THREAD, so the ``block_until_ready``
        below IS the overlapped transfer — it orders the copy strictly
        before return, which is the lane's buffer-reuse contract (the
        staging buffers go back to the pool the moment this returns).
        On a mesh the lane stages straight to the sharded layout
        (:meth:`_stage_sharded`): dp shards land on their owning devices
        from the lane thread, so the scatter overlaps compute too."""
        if self._batch_sharding is not None:
            # mesh backend: the lane thread scatters straight to the
            # sharded layout (overlap preserved; dispatch never re-puts)
            return self._stage_sharded(arrays)
        return self._device_call(self._to_device_raw, arrays, inject=False)

    def _to_device_raw(self, arrays: List[Any]) -> List[Any]:
        import jax

        if self._device is None or self._device.platform == "cpu":
            # XLA's CPU client ZERO-COPIES suitably-aligned host arrays:
            # device_put returns a jax.Array that ALIASES the staging
            # buffer, and the lane overwrites that buffer with the next
            # batch the moment this returns.  Hand jax a private copy —
            # the memcpy is this platform's "transfer", still paid on
            # the lane thread, and jax owns the copy outright.
            arrays = [np.array(a) for a in arrays]
        out = [jax.device_put(a, self._device) for a in arrays]
        jax.block_until_ready(out)
        return out

    def invoke_batch_donated(self, inputs: List[Any]) -> List[Any]:
        """Caller-private micro-batch: donate the input buffers to the
        executable so XLA reuses their HBM for outputs — zero per-batch
        device allocations in steady state (skipped on CPU, where XLA
        ignores donation and would warn per compile)."""
        donate = self._donation_ok()
        if donate:
            self.stats.record_donation_applied()
        return self._invoke_batch_impl(inputs, donate)

    def invoke_batch(self, inputs: List[Any]) -> List[Any]:
        return self._invoke_batch_impl(
            inputs, bool(self._donation_forced()))

    def _invoke_batch_impl(self, inputs: List[Any], donate: bool) -> List[Any]:
        """One XLA call for the whole micro-batch, bucket-padded so each
        bucket size compiles exactly once (and, on a mesh, stays divisible
        by the dp axis so the scatter is even)."""
        n = int(inputs[0].shape[0])
        bucket = self._bucket(n)
        note(bucket=bucket)  # on the caller's invoke span, if one is open
        with self._reload_lock:
            import jax

            xs = []
            scattered = False
            for a in inputs:
                if self._batch_sharding is not None and not isinstance(
                    a, jax.Array
                ):
                    # host batch onto a mesh: pad host-side, then scatter
                    # each dp shard straight to its owning device (no
                    # whole-batch bounce through device 0)
                    arr = self._pad_rows(np.asarray(a), bucket)
                    arr = self._put(arr, self._batch_sharding)
                    scattered = True
                    xs.append(arr)
                    continue
                if self._batch_sharding is not None:
                    # device-resident batch on a mesh (chained filter /
                    # lane-staged): pad on device, commit the batch
                    # sharding (no-op when the lane already placed it)
                    import jax.numpy as jnp

                    arr = self._pad_rows(a, bucket, xp=jnp)
                    xs.append(self._put(arr, self._batch_sharding))
                    continue
                import jax.numpy as jnp

                xs.append(self._pad_rows(self._put(a), bucket, xp=jnp))
            if scattered:
                self.mesh_scatters += 1
            key = (len(xs),) + tuple((tuple(x.shape), str(x.dtype)) for x in xs)
            out = self._device_call(
                self._compiled(key, donate=donate, batched=True),
                self._params, *xs)
        if bucket != n:
            out = [o[:n] for o in out]
        return list(out)


register_backend(JaxXla)
