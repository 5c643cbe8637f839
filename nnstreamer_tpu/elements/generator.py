"""tensor_generator: streaming autoregressive generation (net-new).

The serving shape of interactive LLM inference, which the reference has no
analog for (its closest relative is recurrence emulation through
tensor_repo loops, ``tests/nnstreamer_repo_lstm``): ONE prompt frame in,
token CHUNKS streamed out as they decode.  Downstream elements
(detokenizer → sink / query serversink) run CONCURRENTLY with the next
chunk's decode — the pipeline's per-element threads are the streaming
transport, no extra machinery.

TPU-first structure: the zoo transformer's KV cache (device-resident
pytree) is carried across jitted calls — prefill is one causal pass, each
chunk is one ``lax.scan`` segment (compile buckets: one per distinct
chunk length, i.e. the chunk size + one tail, bounded by an LRU).  Python
dispatch cost is per CHUNK, not per token.  Sampling (greedy/temperature/
top-k, per-step key folding) is bit-identical to one-shot ``generate:<N>``
serving (``models/transformer.py make_stream_generate``).

Continuous batching (``slots=N``, core/slots.py): the element multiplexes
MANY concurrent prompt streams into one fixed-width slot batch — live
requests occupy slots, new prompts join at token boundaries via chunked
prefill interleaved with decode, finished/cancelled/deadline-evicted
streams free their slot immediately, and the idle-slot mask keeps the
jitted decode step shape-stable (zero retracing as streams churn).  A
single occupant's output stays bit-identical to the seed per-request
path.  The engine decodes on its own pump thread; chunks are EMITTED on
the element's dispatch thread (``handle_frame``/``handle_idle`` drain
``pop_ready``), so supervision attribution is unchanged — the PR-6
CompletionWindow discipline.

Emission contract: ``handle_frame`` returns frames/generators; the
scheduler pushes each yielded frame downstream as it is produced (frames
stream, they do not wait for the full completion).  Each chunk frame
carries tokens (B, n) int32 plus meta ``stream_seq`` (source frame seq),
``chunk_index``, ``tokens_done`` and ``final`` (evicted streams add
``evicted``/``deadline_expired`` — the typed expiry).
"""

from __future__ import annotations

import time
from collections import OrderedDict
from typing import Any, Dict, Optional

import numpy as np

from ..core.buffer import BatchFrame
from ..core.continuity import (
    RESUME_REJECT_META,
    RESUME_REQ_META,
    prompt_digest,
    resume_signature,
)
from ..core.liveness import (
    DEADLINE_META,
    PRIORITY_MAX,
    PRIORITY_META,
    TENANT_META,
    clamp_priority,
    thread_census,
)
from ..core.telemetry import TL_RX_META
from ..core.tracer import armed, record
from ..core.types import ANY, FORMAT_FLEXIBLE, StreamSpec
from ..pipeline.element import Element, ElementError, Property, element

#: bound on live decode-chunk jit buckets (LRU — the discipline of the
#: filter's _stack_jit_cache, PR-3): distinct chunk lengths churn (tail
#: chunks, reconfigured clients) but live executables stay bounded
_JIT_BUCKET_MAX = 16


@element("tensor_generator")
class TensorGenerator(Element):
    # a block of prompts streams each logical prompt in order (lazy chain)
    BATCH_AWARE = True

    PROPERTIES = {
        "custom": Property(
            str, "",
            "zoo-transformer dialect: vocab:N,d_model:N,heads:N,layers:N,"
            "d_ff:N,seq:N,seed:N[,temperature:F,top_k:N,gen_seed:N]; "
            "arch:nemotron_h, arch:cohere2_moe or arch:lfm2_moe selects the "
            "hybrid family (layers:<pattern of M, E, *, W, C, D, parallel "
            "blocks in parentheses> and its widths: "
            "Documentation/examples.md)",
        ),
        "max-new": Property(int, 32, "tokens to generate per prompt"),
        "chunk": Property(int, 8, "tokens per streamed chunk frame"),
        "max-buffers": Property(int, 0, "mailbox depth override"),
        # continuous batching (core/slots.py): 0 = per-request streaming
        # (seed path), N>0 = N-wide slot batch shared by concurrent
        # streams (compile-once per width; requests join/leave at token
        # boundaries)
        "slots": Property(
            int, 0,
            "continuous-batching slot width: concurrent prompt streams "
            "share one fixed decode batch (0 = serve requests one at a "
            "time, the pre-slot path)"),
        "prefill-chunk": Property(
            int, 32,
            "prompt tokens prefilled per engine iteration when joining a "
            "slot (chunked prefill interleaves with decode so a long "
            "prompt never stalls live streams)"),
        "prefill-priority": Property(
            int, 1,
            "prefill chunks interleaved per decode step (0 = joining "
            "prompts prefill only while nothing is decoding — decode "
            "throughput over join latency)"),
        "token-budget-s": Property(
            float, 0.0,
            "per-token pace budget: a slotted stream that takes longer "
            "than this between tokens is evicted with the typed expiry "
            "(0 = off; the request's own deadline-s budget is always "
            "honored)"),
        # per-stream SLO accounting (core/telemetry.py SloTracker,
        # engine side): declarative objectives; burn-rate gauges are
        # computed at scrape time from the log2 histograms and exported
        # per tenant as nns.slo.* (0 = objective not armed)
        "slo-ttft-p95": Property(
            float, 0.0,
            "TTFT objective: 95% of fresh streams must emit their first "
            "token within this many seconds (0 = off)"),
        "slo-token-p99": Property(
            float, 0.0,
            "per-token objective: 99% of token inter-arrivals must be "
            "under this many seconds (0 = off)"),
        "slo-availability": Property(
            float, 0.0,
            "goodput objective, e.g. 0.999: completed streams / "
            "classified streams (shed+evicted+expired+errors are the "
            "error budget; 0 = off)"),
        # mesh-sharded decode (parallel/mesh.py grammar, tp only): the
        # slot batch's transformer runs tensor-parallel across a device
        # mesh — params tp-sharded, per-slot KV pages sharded on heads
        # along tp.  Token sequences are unchanged (the resume signature
        # deliberately excludes the mesh), so sharded and unsharded
        # servers can serve the same durable streams.
        "mesh": Property(
            str, "",
            "decode the slot batch tensor-parallel across a device mesh: "
            "'tp:N' (slots >= 1 required; empty = unsharded)"),
        # shared-prefix KV cache (core/slots.py PrefixCache): prompts
        # sharing a long common prefix (system prompt / few-shot header)
        # attach refcounted published pages instead of re-prefilling
        # them — the TTFT collapse for the dominant traffic shape.
        # OFF by default: zero behavior change until armed.
        "prefix-cache": Property(
            str, "off",
            "shared-prefix KV page pool: 'on' publishes each prompt's "
            "prefix pages at grain boundaries and attaches them to later "
            "prompts sharing the prefix, skipping their prefill entirely "
            "(slots >= 1; warm streams stay bit-identical to cold "
            "prefill; 'off' = the pre-cache path, byte-identical "
            "behavior)"),
        "prefix-grain": Property(
            int, 0,
            "prefix chunk grain in tokens (0 = the wire default, 64); "
            "rounded UP to a prefill-chunk multiple so warm and cold "
            "runs share the exact prefill chunk grid (bit-exactness)"),
        "prefix-cap": Property(
            int, 256,
            "max cached prefix entries (LRU among unreferenced entries "
            "past the cap; pinned entries are never reclaimed)"),
    }

    def __init__(self, name=None):
        super().__init__(name)
        self._prefill = None
        self._decode = None
        self._params = None
        self._max_seq = 0
        self._jit_chunks: "OrderedDict[int, Any]" = OrderedDict()
        self._engine = None
        self._mesh = None         # tp decode mesh (mesh= prop, slotted)
        self._mesh_axes = {}
        self._resume_sig = None   # token-sequence signature (slotted)
        self._resume_rejects = 0  # RESUME requests refused (mismatch)
        # device-loss resilience: lifetime degraded state + the device
        # ordinals excluded from any future mesh claim (the dead stay
        # dead across restarts of this element)
        self._degraded = False
        self._mesh_exclude = ()
        self._mesh_override = None  # survivor spec a re-shard leaves behind
        self._zoo_props = {}      # parsed custom dialect (rebuild hook)
        self._slots = 0
        self._sim = False
        self._prefix_pool = None  # PrefixCache (prefix-cache=on, slotted)
        self._slo = None          # SloTracker (slo-* props; slotted only)
        # autoscale resize actuation (core/autoscale.py): the requested
        # slot width, applied on the DISPATCH thread at the next idle
        # boundary after every live stream handed off resumably
        self._resize_target = 0
        self._resizes = 0
        # fenced actuation (core/autoscale.py LeaderLease): a resize
        # carrying a stale lease epoch is REFUSED — a deposed
        # controller's in-flight commands must not race the new leader
        from ..core.autoscale import FencingToken
        self._fence = FencingToken()

    def start(self):
        import jax

        from ..core.compile_cache import enable as enable_compile_cache
        from ..models.transformer import build_stream

        enable_compile_cache()  # before this element's first compile
        props = {}
        for part in self.props["custom"].split(","):
            if ":" in part:
                k, _, v = part.partition(":")
                props[k.strip()] = v.strip()
        # arch: names the model family (_zoo_family); absent or unknown,
        # the dense transformer is built exactly as before
        self._resize_target = 0
        slots = int(self.props["slots"])
        if slots < 0:
            raise ElementError(f"{self.name}: slots must be >= 0")
        mesh = None
        self._mesh_axes = {}
        mesh_spec = self.props["mesh"]
        if self._mesh_override is not None:
            # a degraded re-shard left a survivor config behind: any
            # later restart keeps serving the shrunk mesh ("" =
            # unsharded) — the original spec no longer fits once the
            # dead ordinals are excluded from the claim
            mesh_spec = self._mesh_override
        if mesh_spec:
            from ..parallel.mesh import (
                claim_devices,
                make_mesh,
                parse_mesh_spec,
            )

            try:
                axes = parse_mesh_spec(mesh_spec)
            except ValueError as e:
                raise ElementError(f"{self.name}: {e}") from None
            if axes and set(axes) != {"tp"}:
                # the slot batch IS the data axis: scattering it over dp
                # would break the per-slot page/index layout, and sp/pp
                # have no decode-step story here — refuse loudly
                raise ElementError(
                    f"{self.name}: mesh={self.props['mesh']!r} — the "
                    "slotted decode path shards on tp only")
            if axes and slots < 1:
                raise ElementError(
                    f"{self.name}: mesh= requires slots >= 1 (the mesh "
                    "serves the slot batch)")
            if axes:
                mesh = make_mesh(
                    axes,
                    devices=claim_devices(
                        axes, exclude=self._mesh_exclude))
                self._mesh_axes = {k: mesh.shape[k] for k in axes}
        self._mesh = mesh
        # slotted mode needs its OWN mailbox + dispatch thread: the
        # scheduler's idle hook (handle_idle) and pending_frames fast-poll
        # only run for chain heads, and they are how engine-completed
        # chunks reach the wire between input frames.  Checked by the
        # fusion partition, which runs after start().
        self.THREAD_BOUNDARY = slots > 0
        if slots > 0:
            from ..core.slots import SimSlotModel, SlotEngine

            sim = props.get("sim", "") not in ("", "0", "false")
            # stream continuity: the signature covers everything that
            # determines the TOKEN sequence — two servers may serve the
            # same stream iff it matches (chunk size and sim timing
            # knobs deliberately excluded: they shape latency, not
            # tokens)
            max_new = int(self.props["max-new"])
            if sim:
                self._resume_sig = resume_signature(
                    "sim", vocab=int(props.get("vocab", "997")),
                    max_new=max_new)
            else:
                # the family's name and EVERY field of its config: a
                # stream never resumes across families or expert shares
                family, module = self._zoo_family(props)
                try:
                    fields = module.resume_fields(props)
                except (KeyError, ValueError) as e:
                    raise ElementError(
                        f"{self.name}: custom={self.props['custom']!r}: "
                        f"{e}") from None
                self._resume_sig = resume_signature(
                    family, max_new=max_new, **fields)
                if family != "zoo" and mesh is not None:
                    raise ElementError(
                        f"{self.name}: mesh= is not served for arch:"
                        f"{family} (the experts' ep axis and its exchange "
                        "do not exist yet: one chip holds one share)")
                if family != "zoo" and self.props["prefix-cache"] == "on":
                    raise ElementError(
                        f"{self.name}: prefix-cache=on is not served for "
                        f"arch:{family}: a recurrent state cannot be cut "
                        "by position, nor a window leaf written round")
            if sim and mesh is not None:
                raise ElementError(
                    f"{self.name}: mesh= needs the real transformer "
                    "(custom sim: has no device placement)")
            if sim:
                # async-sim proxy (PR-6 discipline): deterministic token
                # recurrence + TPU-shaped step costs — drives the slot
                # SCHEDULER through the full pipeline without a model
                # (perf floors + chaos harness).  sim_oom_step /
                # sim_lost_step are the device-resource chaos twins:
                # decode attempt N raises the typed OOM / device-loss
                # error exactly once (core/resilience.py taxonomy).
                model = SimSlotModel(
                    slots,
                    vocab=int(props.get("vocab", "997")),
                    step_base_ms=float(props.get("sim_step_ms", "1.0")),
                    step_per_slot_ms=float(
                        props.get("sim_per_slot_ms", "0.05")),
                    prefill_ms_per_token=float(
                        props.get("sim_prefill_ms", "0.02")),
                    oom_at_step=(int(props["sim_oom_step"])
                                 if "sim_oom_step" in props else None),
                    lost_at_step=(int(props["sim_lost_step"])
                                  if "sim_lost_step" in props else None),
                )
                params = None
                self._max_seq = int(props.get("seq", str(1 << 30)))
            else:
                model, params, self._max_seq = self._build_zoo_slot_model(
                    props, slots, mesh)
            self._params = params
            self._zoo_props = dict(props)
            self._slots = slots
            self._sim = sim
            self._slo = self._build_slo()
            self._prefix_pool = self._build_prefix_pool()
            self._engine = SlotEngine(
                model, params,
                max_seq=self._max_seq,
                chunk=max(1, int(self.props["chunk"])),
                prefill_chunk=int(self.props["prefill-chunk"]),
                prefill_priority=int(self.props["prefill-priority"]),
                token_budget_s=float(self.props["token-budget-s"]),
                name=self.name,
                resume_sig=self._resume_sig,
                on_device_lost=self._rebuild_on_device_loss,
                slo=self._slo,
                prefix_cache=self._prefix_pool,
                on_ready=self.wake_dispatch,
            )
            self._engine.start()
            return
        if self.props["prefix-cache"] == "on":
            raise ElementError(
                f"{self.name}: prefix-cache=on needs slots >= 1 (the "
                "pool lives in the slot engine)")
        if props.get("sim", "") not in ("", "0", "false"):
            raise ElementError(
                f"{self.name}: custom sim: needs slots >= 1 (the sim "
                "proxy drives the slot engine; slots=1 is the "
                "request-serial baseline)")
        if self._zoo_family(props)[0] != "zoo":
            raise ElementError(
                f"{self.name}: arch:{props['arch']} needs slots >= 1 (its "
                "state lives in the slot engine)")
        prefill, decode_chunk, params, self._max_seq = build_stream(
            props, device=self._serving_device())
        self._prefill = jax.jit(prefill)
        self._decode = decode_chunk
        self._params = params
        self._jit_chunks = OrderedDict()

    def stop(self):
        if self._engine is not None:
            self._engine.stop()
            self._engine = None
        self._prefix_pool = None  # restart is deliberately cache-cold
        self._prefill = self._decode = self._params = None
        self._jit_chunks.clear()

    def _decode_n(self, n: int):
        import jax

        from ..core.slots import lru_bucket

        def build(k):
            return jax.jit(
                lambda p, cache, tok, t0: self._decode(p, cache, tok, t0, k)
            )

        return lru_bucket(self._jit_chunks, n, build, _JIT_BUCKET_MAX)

    # -- negotiation --------------------------------------------------------
    def accept_spec(self, pad, spec):
        return spec

    def derive_spec(self, pad=0):
        # chunk length varies (tail chunk): flexible stream
        return StreamSpec((), FORMAT_FLEXIBLE)

    def _build_slo(self):
        """SloTracker from the slo-* props (None when no objective is
        armed — the engine's record paths then cost nothing)."""
        from ..core.telemetry import SloTracker

        try:
            tracker = SloTracker(
                ttft_p95_s=float(self.props["slo-ttft-p95"]),
                token_p99_s=float(self.props["slo-token-p99"]),
                availability=float(self.props["slo-availability"]),
            )
        except ValueError as e:
            raise ElementError(f"{self.name}: {e}") from None
        return tracker if tracker.armed else None

    def _build_prefix_pool(self):
        """PrefixCache from the prefix-* props (None = off: the engine
        takes the byte-identical pre-cache path).  The grain rounds UP
        to a prefill-chunk multiple — warm and cold runs must share the
        exact prefill chunk grid or bit-exactness breaks.  A fresh pool
        per start(): a supervision restart is deliberately CACHE-COLD
        (streams migrated here still resume bit-exactly; they just pay
        one cold prefill)."""
        mode = self.props["prefix-cache"]
        if mode not in ("off", "on"):
            raise ElementError(
                f"{self.name}: prefix-cache={mode!r} — want off|on")
        if mode != "on":
            return None
        from ..core.continuity import PREFIX_GRAIN
        from ..core.slots import PrefixCache

        pchunk = max(1, int(self.props["prefill-chunk"]))
        grain = int(self.props["prefix-grain"]) or PREFIX_GRAIN
        grain = ((max(1, grain) + pchunk - 1) // pchunk) * pchunk
        cap = int(self.props["prefix-cap"])
        if cap < 1:
            raise ElementError(
                f"{self.name}: prefix-cap must be >= 1, got {cap}")
        return PrefixCache(grain=grain, cap_entries=cap)

    def trim_prefix_cache(self) -> int:
        """Memory-pressure trim hook (``Pipeline.enable_memory_monitor``
        runs it FIRST in the ladder): drop every unreferenced cached
        prefix — recomputable capacity is the cheapest relief on the
        chip.  Returns entries freed."""
        pool = self._prefix_pool
        return pool.trim() if pool is not None else 0

    def prefix_digest_info(self) -> Optional[Dict[str, Any]]:
        """Bounded cached-prefix advertisement for the discovery digest
        (core/fleet.py): exact hit/miss counters for the observatory's
        fleet rollup plus the hottest entry digests, so routing
        dashboards can see WHICH prefixes this server holds.  None when
        the cache is off (the digest then carries no prefix block)."""
        pool = self._prefix_pool
        if pool is None:
            return None
        snap = pool.snapshot()
        return {
            "hits": snap["prefix_hits"],
            "misses": snap["prefix_misses"],
            "entries": snap["prefix_entries"],
            "hot": pool.hot_digests(),
        }

    # -- observability ------------------------------------------------------
    def health_info(self) -> Dict[str, Any]:
        """Slot occupancy / join / evict / tokens-per-step counters —
        merged into ``Pipeline.health()`` AND exported to the PR-7
        registry as ``nns.gen.*`` via the health collector's key map
        (ONE export path; metrics_info here would double-emit the same
        series).  ``gen_jit_buckets`` counts live decode-chunk compile
        buckets on BOTH paths, so retrace churn is visible."""
        info: Dict[str, Any] = {
            "gen_jit_buckets": len(self._jit_chunks),
            # both paths refuse resumes they cannot validate (the
            # pre-slot path refuses ALL of them)
            "gen_resume_rejects": self._resume_rejects,
            # zero-loss slot-width rebuilds (autoscale resize actuation)
            "gen_resizes": self._resizes,
            # device-loss resilience: 1 while serving in a reduced
            # configuration (mirrored on the discovery plane)
            "degraded": 1 if self._degraded else 0,
            # fenced actuation: stale-epoch resize refusals + the
            # highest lease epoch this generator has obeyed
            "gen_stale_epoch_rejects": self._fence.rejects,
            "gen_fence_epoch": self._fence.epoch,
        }
        if self._engine is not None:
            info.update(self._engine.snapshot())
            info["gen_jit_buckets"] += len(self._jit_chunks)
            if self._mesh is not None:
                from ..parallel.mesh import mesh_health_info

                info.update(mesh_health_info(self._mesh, self._mesh_axes))
            # named-thread census: the pump's liveness is part of the
            # health story (a wedged pump fires an incident from
            # handle_idle; the census makes it visible between polls)
            info["threads"] = thread_census(self._engine.heartbeat)
        if self._slo is not None:
            # per-tenant SLO rows (burn rates computed at read time);
            # the collector's `slo` branch exports them as nns.slo.*
            info["slo"] = self._slo.snapshot()
        return info

    def histograms_info(self):
        """Per-tenant TTFT / inter-token log2 bucket series (scrape-time
        export; empty histograms emit nothing)."""
        return self._slo.hist_rows() if self._slo is not None else []

    # -- continuous-batching hooks ------------------------------------------
    def pending_frames(self) -> int:
        """Streams parked in the slot engine plus undelivered ready
        chunks (scheduler fast-poll + drain/stop accounting)."""
        return self._engine.pending() if self._engine is not None else 0

    def handle_idle(self):
        """Drain chunks the engine completed since the last call —
        emission happens HERE, on the dispatch thread.  Doubling as the
        pump's liveness check: a pump that holds work but stopped
        beating is WEDGED (stuck inside a device call) — surface it as
        a flight-recorder incident NOW instead of waiting for a sticky
        error that a hung thread can never raise."""
        eng = self._engine
        if eng is None:
            return []
        if eng.pending() > 0 and eng.heartbeat.check_stall(busy=True):
            self.log.warning(
                "slot pump %s wedged: no heartbeat for %.1fs with %d "
                "stream(s)/chunk(s) pending", eng.heartbeat.name,
                eng.heartbeat.age_s(), eng.pending(),
            )
            p = self._pipeline
            if p is not None:
                p.incident(
                    "thread_stall", self.name,
                    f"{eng.heartbeat.name} wedged "
                    f"({eng.heartbeat.age_s():.1f}s, "
                    f"pending={eng.pending()})")
        chunks = eng.pop_ready()
        if self._resize_target and eng.idle():
            # the idle boundary: every live stream handed off resumably
            # (begin_goaway in request_resize) and every ready chunk
            # drained — safe to rebuild at the new width, and doing it
            # HERE (dispatch thread) means no frame can race the swap
            self._apply_resize()
        return chunks

    # -- autoscale resize actuation (core/autoscale.py) ---------------------
    def request_resize(self, slots: int, epoch: Optional[int] = None) -> None:
        """Arm a ZERO-LOSS slot-width resize (any thread): live streams
        are flushed as resumable GOAWAY chunks (clients migrate or
        resume them here — remaining tokens bit-identical, the resume
        signature deliberately excludes the slot width), then the slot
        model + engine rebuild at the new width on the dispatch thread's
        next idle boundary.  Poll :attr:`resize_pending` / the
        ``gen_resizes`` health counter for completion.

        ``epoch`` is the commanding controller's lease epoch; a stale
        epoch raises :class:`~..core.autoscale.StaleEpochError` BEFORE
        any stream is touched (``None`` = unfenced operator command)."""
        self._fence.check(epoch)
        slots = int(slots)
        if slots < 1:
            raise ElementError(f"{self.name}: resize slots must be >= 1")
        if self._engine is None:
            raise ElementError(
                f"{self.name}: resize needs the slotted path (slots >= 1)")
        if slots == self._slots:
            return
        self._resize_target = slots
        self._engine.begin_goaway()

    @property
    def resize_pending(self) -> bool:
        """True while a requested resize has not been applied yet."""
        return bool(self._resize_target)

    def _build_slot_model(self, slots: int):
        """(model, params, max_seq) at the requested width from the
        stored knobs — the resize twin of the ``start()`` build.  The
        one-shot chaos triggers (``sim_oom_step`` / ``sim_lost_step``)
        are deliberately NOT re-armed: they script a single synthetic
        fault, and a resize must not replay it."""
        props = self._zoo_props
        if self._sim:
            from ..core.slots import SimSlotModel

            model = SimSlotModel(
                slots,
                vocab=int(props.get("vocab", "997")),
                step_base_ms=float(props.get("sim_step_ms", "1.0")),
                step_per_slot_ms=float(
                    props.get("sim_per_slot_ms", "0.05")),
                prefill_ms_per_token=float(
                    props.get("sim_prefill_ms", "0.02")),
            )
            return model, None, self._max_seq
        return self._build_zoo_slot_model(props, slots, self._mesh)

    def _apply_resize(self) -> None:
        """Runs on the DISPATCH thread with the engine idle: build the
        replacement first (a failed build rolls back to serving at the
        old width), then swap engines.  The resume signature is width-
        independent, so streams handed off around the rebuild resume
        bit-identically at either width."""
        from ..core.slots import SlotEngine

        # NOTE: _resize_target stays set until the swap lands (or the
        # rollback commits) — resize_pending is the actuation-complete
        # signal controllers poll, so clearing it before the rebuild
        # would let a poller read the OLD width as the settled result
        target = self._resize_target
        old = self._engine
        try:
            model, params, max_seq = self._build_slot_model(target)
        except Exception:  # noqa: BLE001 — roll back to the old width
            self.log.exception(
                "resize to %d slots failed building the model; keeping "
                "%d slots", target, self._slots)
            old.end_goaway()
            p = self._pipeline
            if p is not None:
                p.incident(
                    "resize_failed", self.name,
                    f"slot resize {self._slots}->{target} model build "
                    "failed; serving at the old width")
            if self._resize_target == target:
                self._resize_target = 0
            return
        old.stop()
        self._params = params
        self._max_seq = max_seq
        new = SlotEngine(
            model, params,
            max_seq=max_seq,
            chunk=max(1, int(self.props["chunk"])),
            prefill_chunk=int(self.props["prefill-chunk"]),
            prefill_priority=int(self.props["prefill-priority"]),
            token_budget_s=float(self.props["token-budget-s"]),
            name=self.name,
            resume_sig=self._resume_sig,
            on_device_lost=self._rebuild_on_device_loss,
            slo=self._slo,
            # the pool survives a width resize: published pages are
            # (1, n, ...) slot-width-independent blobs from the SAME
            # params, and its counters must stay monotonic for the
            # observatory's exact fleet totals
            prefix_cache=self._prefix_pool,
            on_ready=self.wake_dispatch,
        )
        # the server's lifetime ledger survives the rebuild — digests
        # and the observatory's exact fleet totals must stay monotonic
        new.adopt_ledger(old)
        new.start()
        self._engine = new
        self.log.info("slot width resized %d -> %d (zero-loss: live "
                      "streams handed off resumably)", self._slots, target)
        self._slots = target
        # keep the prop in sync so a supervision restart rebuilds at
        # the actuated width, not the parse-time one
        self.props["slots"] = target
        self._resizes += 1
        # a request_resize racing the swap may have armed a NEWER
        # target — only clear our own
        if self._resize_target == target:
            self._resize_target = 0

    # -- device-loss resilience (degrade, don't die) -------------------------
    def _serving_device(self):
        """The ONE device an unsharded model of this element lives on:
        the process default device — or, once past losses excluded
        ordinals, a survivor (the default pick would hand the dead chip
        back)."""
        from ..backends.jax_xla import pick_device, surviving_device

        return surviving_device(pick_device(["auto"]), self._mesh_exclude)

    @staticmethod
    def _zoo_family(props):
        """(family name, module) of the model the ``custom=`` dialect
        names: ``arch:nemotron_h``, ``arch:cohere2_moe`` and ``arch:lfm2_moe`` are the hybrid
        family (models/hybrid_lm.py, ``FAMILIES``); anything else is the
        dense transformer.  The ONE place a family is chosen; a module
        gives ``build_slot_stream`` and ``resume_fields``."""
        from ..models import hybrid_lm, transformer

        if props.get("arch") in hybrid_lm.FAMILIES:
            return props["arch"], hybrid_lm
        return "zoo", transformer

    def _build_zoo_slot_model(self, props, slots: int, mesh):
        """(model, params, max_seq) for a real model — the one build
        every path shares (start, resize, device-loss rebuild): params
        AND slot state land on the mesh, or unsharded on
        :meth:`_serving_device`, before the first step."""
        return self._zoo_family(props)[1].build_slot_stream(
            props, slots, mesh=mesh,
            device=None if mesh is not None else self._serving_device())

    def _rebuild_on_device_loss(self, err):
        """SlotEngine ``on_device_lost`` hook (runs on the PUMP thread,
        after every live stream was handed off with resume state):
        rebuild the slotted model on the surviving devices — the
        ``parallel/mesh.shrink_axes`` ladder, tp halving down to
        unsharded — and mark this server degraded on the discovery
        plane.  Token sequences are untouched (the resume signature
        deliberately excludes the mesh), so streams that resume HERE
        stay bit-exact.  The sim twin recovers in place (no devices to
        lose for real); a real UNSHARDED model has no survivor to
        rebuild on — the loss re-raises into supervision, whose element
        restart re-picks devices."""
        if not self._sim and self._mesh is None:
            self.log.error(
                "device lost (%s): unsharded model has no survivors to "
                "re-mesh onto — escalating to supervision", err)
            raise err
        was_degraded = self._degraded
        self._degraded = True
        replacement = None
        detail = "sim"
        if not self._sim:
            from ..backends.jax_xla import probe_device_ids
            from ..parallel.mesh import (
                claim_devices,
                make_mesh,
                remesh_after_loss,
            )

            current = [int(d.id) for d in self._mesh.devices.flat]
            dead, axes, spec = remesh_after_loss(
                current, self._mesh_axes,
                getattr(err, "device_ids", ()) or (),
                probe=probe_device_ids)
            if not dead:
                # the probe reached every mesh member — the loss did
                # not reproduce: escalate to supervision (the restart
                # re-picks devices; streams already handed off resume
                # anywhere) instead of condemning a healthy chip
                self._degraded = was_degraded
                self.log.error(
                    "device lost (%s): probe found all mesh members "
                    "alive — escalating to supervision", err)
                raise err
            self._mesh_exclude = tuple(
                set(self._mesh_exclude) | set(dead))
            # later restarts must claim the SHRUNK config: the original
            # spec no longer fits once the dead ordinals are excluded
            self._mesh_override = spec
            mesh = None
            if axes:
                mesh = make_mesh(
                    axes,
                    devices=claim_devices(axes, exclude=self._mesh_exclude))
            detail = spec or "unsharded"
            self.log.error(
                "device lost (%s): rebuilding slot model on survivors "
                "as mesh=%s", err, detail)
            model, params, self._max_seq = self._build_zoo_slot_model(
                self._zoo_props, self._slots, mesh)
            self._mesh = mesh
            self._mesh_axes = axes if mesh is not None else {}
            self._params = params
            replacement = (model, params)
        p = self._pipeline
        if p is not None:
            p.incident("device_lost", self.name, {"remesh": detail})
            p.degraded_feedback(
                self.name, f"device lost; decoding on mesh={detail}")
        return replacement

    def note_stream_drain(self) -> None:
        """The query serversrc of this pipeline entered its drain
        (rolling restart): hand live generation streams off as
        resumable GOAWAY chunks so clients migrate them instead of the
        drain racing its deadline against whole generations."""
        if self._engine is not None:
            self._engine.begin_goaway()

    def note_stream_cancel(self, meta: Dict[str, Any]) -> None:
        """Downstream feedback (serversink): the consumer of this stream
        is GONE — free its slot immediately instead of decoding tokens
        nobody will read."""
        if self._engine is None:
            return
        cid = meta.get("client_id")
        if cid is not None:
            self._engine.cancel(client_id=cid)

    def handle_eos(self, pad):
        """Slotted mode: the stream only ends once every live generation
        completed — flush the engine through the dispatch thread."""
        eng = self._engine
        if eng is None:
            return []

        def flush():
            while True:
                for out in eng.pop_ready():
                    yield out
                if eng.idle():
                    return
                if self.interrupted:
                    return  # watchdog escalation: stop flushing
                eng.wait_progress(0.05)

        return flush()

    # -- processing ---------------------------------------------------------
    def handle_frame(self, pad, frame):
        if self._engine is not None:
            return self._handle_slotted(frame)
        assert self._prefill is not None, f"{self.name} not started"
        if isinstance(frame, BatchFrame):
            # lazily chain one stream per logical prompt: chunk frames of
            # prompt j still leave BEFORE prompt j+1 starts decoding
            logical = frame.split()

            def multi():
                for lf in logical:
                    rej = self._refuse_unslotted_resume(lf)
                    if rej is not None:
                        yield rej
                    else:
                        yield from self._stream_one(lf)

            return multi()
        rej = self._refuse_unslotted_resume(frame)
        if rej is not None:
            return [rej]
        return self._stream_one(frame)

    def _refuse_unslotted_resume(self, lf):
        """A RESUME request landing on a pre-slot (slots=0) generator
        must be REFUSED with the typed reject, never served: this path
        has no checkpoint validation, so silently replaying the prompt
        from token 0 under a possibly-different config would corrupt
        the client's exactly-once ledger without any error (durable
        streams require slots >= 1)."""
        if lf.meta.get(RESUME_REQ_META) is None:
            return None
        return self._resume_reject(
            lf, "resume requires a slotted generator (slots >= 1)")

    def _validated_prompt(self, frame, max_new: int) -> np.ndarray:
        prompt = np.asarray(frame.tensors[0])
        if prompt.ndim == 1:
            prompt = prompt[None]
        if prompt.ndim != 2 or prompt.dtype.kind not in "iu":
            raise ElementError(
                f"{self.name}: prompt must be int tokens (B, Tp) or (Tp,), "
                f"got {prompt.shape} {prompt.dtype}"
            )
        if prompt.shape[1] + max_new > self._max_seq:
            # the cache ring would wrap and pos_embed would index past
            # max_seq — fail loud instead of streaming corrupt tokens
            raise ElementError(
                f"{self.name}: prompt {prompt.shape[1]} + max-new "
                f"{max_new} exceeds the model's seq {self._max_seq}"
            )
        return prompt

    def _handle_slotted(self, frame):
        """Submit the prompt(s) to the slot engine and drain whatever
        chunks are already ready — new prompts JOIN live decoding at the
        next token boundary instead of queueing behind it.  A frame
        carrying :data:`RESUME_REQ_META` re-joins a checkpointed stream
        (validated below) instead of starting a fresh one."""
        max_new = int(self.props["max-new"])
        chunk = max(1, int(self.props["chunk"]))
        logical = frame.split() if isinstance(frame, BatchFrame) else [frame]
        rejects = []
        for lf in logical:
            prompt = self._validated_prompt(lf, max_new)
            if prompt.shape[0] != 1:
                # one stream per slot: split multi-row prompts upstream
                # (appsrc push_block) or serve them on the pre-slot path
                raise ElementError(
                    f"{self.name}: slots>0 serves one prompt per stream; "
                    f"got a (B={prompt.shape[0]}) prompt batch — push a "
                    "block of single prompts instead"
                )
            if max_new <= 0:
                continue
            meta = lf.meta
            resume = None
            rs = meta.get(RESUME_REQ_META)
            if rs is not None:
                resume, reason = self._check_resume(
                    lf, prompt, max_new, rs)
                if resume is None:
                    rejects.append(self._resume_reject(lf, reason))
                    continue
            stream = self._engine.submit(
                lf, prompt.astype(np.int32), max_new, chunk,
                tenant=str(meta.get(TENANT_META, "") or ""),
                priority=clamp_priority(
                    meta.get(PRIORITY_META, PRIORITY_MAX)),
                deadline_ts=meta.get(DEADLINE_META),
                resume=resume,
            )
            rx = meta.get(TL_RX_META)
            if rx is not None and armed():
                # routing: the query server's receive stamp to the
                # engine's queue (crosses the transport, source and
                # generator threads, so it is a ring record)
                record("nns.query.route", rx, time.perf_counter(),
                       request=stream.sid)
        return rejects + self._engine.pop_ready()

    def _check_resume(self, lf, prompt, max_new: int, rs):
        """Validate one RESUME request against THIS server's token
        signature and the prompt it arrived with.  Returns
        ``(engine_resume_dict, None)`` or ``(None, reason)`` — a
        mismatch is a per-stream typed refusal, never a pipeline
        error."""
        try:
            sig = str(rs["sig"])
            r = int(rs["tokens_done"])
        except (KeyError, TypeError, ValueError):
            return None, "malformed resume state"
        if sig != self._resume_sig:
            return None, "model/sampling signature mismatch"
        if str(rs.get("digest", "")) != prompt_digest(
                prompt.astype(np.int32)):
            return None, "prompt digest mismatch"
        if not 0 <= r < max_new:
            return None, f"tokens_done {r} outside [0, {max_new})"
        if r == 0:
            return {"tokens_done": 0}, None
        if len(lf.tensors) < 2:
            return None, "resume request lacks the prefix tensor"
        prefix = np.asarray(lf.tensors[1])
        if prefix.ndim == 1:
            prefix = prefix[None]
        if (prefix.ndim != 2 or prefix.shape != (1, r)
                or prefix.dtype.kind not in "iu"):
            return None, (
                f"prefix {prefix.shape} {prefix.dtype} != (1, {r}) int")
        return {"tokens_done": r,
                "prefix": prefix.astype(np.int32)}, None

    def _resume_reject(self, lf, reason: str):
        """Typed terminal refusal of one RESUME request: the stream gets
        a tensor-less final chunk naming the reason (the client counts
        a resume failure and tries another server); the server pipeline
        — and the other streams it is decoding — survive."""
        self._resume_rejects += 1
        self.log.warning("resume refused: %s", reason)
        out = lf.with_tensors([])
        out.meta.update(
            stream_seq=lf.seq, chunk_index=0, tokens_done=0, final=True,
        )
        out.meta[RESUME_REJECT_META] = reason
        return (0, out)

    def _stream_one(self, frame):
        prompt = self._validated_prompt(frame, int(self.props["max-new"]))
        max_new = int(self.props["max-new"])
        chunk = max(1, int(self.props["chunk"]))
        if max_new <= 0:
            return []

        def stream():
            cache, tok = self._prefill(self._params, prompt.astype(np.int32))
            done = 0
            idx = 0
            pending = [np.asarray(tok)[:, None]]  # token 1 (from prefill)
            pending_n = 1
            t = 1
            while True:
                emit_now = pending_n >= chunk or (t >= max_new)
                if emit_now and pending_n:
                    toks = (
                        pending[0] if len(pending) == 1
                        else np.concatenate(pending, axis=1)
                    )
                    done += toks.shape[1]
                    out = frame.with_tensors([toks.astype(np.int32)])
                    out.meta.update(
                        stream_seq=frame.seq, chunk_index=idx,
                        tokens_done=done, final=bool(t >= max_new),
                    )
                    idx += 1
                    pending.clear()
                    pending_n = 0
                    yield (0, out)
                if t >= max_new:
                    return
                n = min(chunk - pending_n, max_new - t)
                cache2, tok2, toks = self._decode_n(n)(
                    self._params, cache, tok, t
                )
                # materialize BEFORE yielding: emission must mean "these
                # tokens exist", not "their computation was dispatched"
                pending.append(np.asarray(toks))
                pending_n += toks.shape[1]
                cache, tok = cache2, tok2
                t += n

        return stream()
