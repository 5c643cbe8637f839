"""Continuous batching for the generation path: the slot scheduler.

The serving shape of "millions of users" LLM inference: MANY concurrent
autoregressive streams share ONE fixed-width decode batch.  Each live
request occupies a *slot*; the jitted transformer decode scan runs over
the whole slot batch every iteration (``k = min(chunk, min remaining)``
tokens per active slot — per-token dispatch amortized exactly like the
unslotted path), so aggregate token throughput is bound by the token
batch, not by the request count — the roofline view the perf evidence
reports (Documentation/performance.md "Continuous batching").

Mechanics (model halves: ``models/transformer.SlotModel``):

* **join at token boundaries** — a new prompt claims a free slot, its
  pages are reset (only ITS slot is touched), then its prompt is
  prefilled in ``prefill_chunk``-sized pieces INTERLEAVED with the decode
  loop (``prefill_priority`` chunks per decode step), so one long prompt
  never stalls the tokens other streams are owed;
* **leave immediately** — finished, cancelled and deadline-evicted
  streams free their slot at the next token boundary; the idle-slot
  mask keeps the decode step shape-stable, so churn causes ZERO
  retracing (``SlotModel.decode_compiles`` stays at the fixed bucket
  count);
* **per-token deadline QoS** — a stream whose request deadline
  (PR-2 ``DEADLINE_META`` budget, crossed the wire) or per-token pace
  budget (``token_budget_s``) is blown is EVICTED from its slot and
  answered with a typed-expiry final chunk (partial tokens preserved,
  ``evicted="deadline"`` meta) instead of rotting in the batch;
* **priority joins** — free slots go to the highest PR-8 priority class
  first (FIFO within a class), so tenant QoS extends to slot admission;
* **one scan in flight ahead of its read-back** — the token and count
  vectors a scan takes stay where the model's programs leave them (on the
  device), a prompt's first token is seated into them there, and what the
  next scan runs is known on the host when it is dispatched: so the pump
  dispatches scan n+1 before it reads scan n back, and the copies,
  emission and admission run while the chip works.  Whenever nothing is
  left to dispatch behind the scan in flight it is read back at once.

Threading: the engine runs its own decode pump thread (the PR-6
CompletionWindow reaper discipline) so decode never waits on the
element's mailbox poll; the ELEMENT drains ready chunks on its dispatch
thread via :meth:`pop_ready` (emission and supervision attribution stay
on the pipeline thread), and engine errors re-raise there too.
"""

from __future__ import annotations

import functools
import threading
import time
from collections import OrderedDict
from contextlib import contextmanager
from typing import (
    Any, Callable, Dict, List, Optional, Protocol, Tuple, runtime_checkable,
)

from .continuity import (
    GOAWAY_META, PREFIX_GRAIN, RESUME_META, prefix_digests, prompt_digest,
)
from .liveness import ThreadBeat
from .log import get_logger
from .resilience import DeviceLostError, DeviceOomError, device_call
from .telemetry import TL_ANNOUNCE_META, TL_EMIT_META, TL_POP_META
from .tracer import armed, name_os_thread, record, span

log = get_logger("slots")

#: terminal stream states
DONE_STATES = ("done", "evicted", "cancelled", "failed")


def _block(x) -> None:
    """Wait for a device array to be computed, without copying it to the
    host (a host array is ready as it is)."""
    wait = getattr(x, "block_until_ready", None)
    if wait is not None:
        wait()


@functools.cache
def _seat_program():
    """The one device program that writes a slot's next decode input:
    ``(tok, gen, slot, first (1,), n) -> (tok, gen)`` with ``tok[slot] =
    first[0]`` and ``gen[slot] = n``, both vectors donated."""
    import jax

    def seat(tok, gen, slot, first, n):
        return tok.at[slot].set(first[0]), gen.at[slot].set(n)

    return jax.jit(seat, donate_argnums=(0, 1))


class _Scan:
    """What the pump dispatched and has not read back yet: one decode
    scan of ``k`` steps over ``streams`` (``toks`` and the model's
    ``counts`` as the program returned them), and the streams whose first
    token was picked before it (``firsts``, seated into ``tok_in``, the
    token vector the scan took).  ``toks`` is None for first tokens that
    no scan followed."""

    __slots__ = ("k", "streams", "toks", "counts", "tok_in", "firsts")

    def __init__(self, k, streams, toks, counts, tok_in, firsts):
        self.k = k
        self.streams = streams
        self.toks = toks
        self.counts = counts
        self.tok_in = tok_in
        self.firsts = firsts


def lru_bucket(lru: "OrderedDict", key, build, cap: int):
    """THE bounded compile-bucket discipline (filter _stack_jit_cache,
    PR-3), shared by every chunk-length jit cache — the slot engine's
    prefill/decode buckets AND the unslotted generator's decode chunks
    — so the eviction rule cannot drift between paths.  Returns the
    cached (or freshly built) entry; evicts least-recently-used past
    ``cap`` (evicted lengths simply retrace on next use)."""
    fn = lru.get(key)
    if fn is not None:
        lru.move_to_end(key)
        return fn
    fn = build(key)
    lru[key] = fn
    while len(lru) > cap:
        lru.popitem(last=False)
    return fn


class PrefixEntry:
    """One published grain chunk of a shared prefix: the immutable page
    blob (a COPY — never a view into a live slot) for prompt positions
    ``[index*grain, (index+1)*grain)``, keyed by its chain digest, plus
    the refcount that fences reclamation."""

    __slots__ = (
        "digest", "index", "pages", "tokens", "nbytes", "refs",
        "last_used",
    )

    def __init__(self, digest: str, index: int, pages, tokens: int,
                 nbytes: int, now: float):
        self.digest = digest
        self.index = int(index)
        self.pages = pages          # model-opaque blob (attach interprets)
        self.tokens = int(tokens)
        self.nbytes = int(nbytes)
        self.refs = 0
        self.last_used = now


class PrefixCache:
    """Refcounted shared-prefix page pool (ROADMAP item 4): the KV bytes
    the dominant traffic shape (long shared system prompt + short user
    suffix) keeps recomputing, published ONCE and attached by every
    later stream.

    * keyed by chunk-grain CHAIN digests
      (:func:`~.continuity.prefix_digests`): entry *i* is valid only
      under the exact prefix that produced chunks ``0..i-1``, so pages
      from different prefixes can never alias;
    * **publish** stores copies exported at the grain boundary by the
      prefilling stream (the slot keeps its private pages — eviction of
      a published entry never touches a live slot);
    * **acquire** pins (``refs += 1``) the longest run of consecutive
      cached chunks from index 0; the engine holds the pins for the
      stream's whole slot occupancy and releases them with the slot, so
      *a cached page is never reclaimed under a live reader* — eviction
      (LRU past ``cap_entries``/``cap_bytes``) and :meth:`trim` only
      ever take ``refs == 0`` entries;
    * :meth:`trim` is the FIRST rung of the PR-14 ``nns.mem.*``
      pressure ladder (``Pipeline.enable_memory_monitor``): cached
      prefixes are pure recomputable capacity — the most reclaimable
      bytes on the chip.

    Accounting is exact (the fleet observatory cross-checks integer
    totals): one hit or one miss per ELIGIBLE lookup (a prompt with at
    least one full grain chunk), one publish per entry stored, one
    eviction per entry reclaimed, however it left."""

    def __init__(self, grain: int = PREFIX_GRAIN, cap_entries: int = 256,
                 cap_bytes: int = 0,
                 clock: Callable[[], float] = time.monotonic):
        self.grain = max(1, int(grain))
        self.cap_entries = max(1, int(cap_entries))
        self.cap_bytes = max(0, int(cap_bytes))
        self.clock = clock
        self._lock = threading.Lock()
        self._entries: "OrderedDict[str, PrefixEntry]" = OrderedDict()
        self.bytes = 0
        # exact counters (lock-held writes, GIL-atomic reads)
        self.hits = 0
        self.misses = 0
        self.publishes = 0
        self.evictions = 0
        self.hit_tokens = 0   # prefill tokens skipped via attach

    @staticmethod
    def _nbytes(pages) -> int:
        """Byte accounting over a model-opaque page blob (dict/list
        nesting of array-likes; non-arrays count a nominal 8)."""
        n = 0
        stack = [pages]
        while stack:
            x = stack.pop()
            if isinstance(x, dict):
                stack.extend(x.values())
            elif isinstance(x, (list, tuple)):
                stack.extend(x)
            else:
                n += int(getattr(x, "nbytes", 8))
        return n

    def acquire(self, digests: List[str]) -> List[PrefixEntry]:
        """Pin the longest run of consecutive cached chunks from index
        0 for the given chain digests.  Counts ONE hit (+`hit_tokens`)
        when the run is non-empty, else ONE miss.  Callers MUST balance
        with :meth:`release` exactly once."""
        with self._lock:
            run: List[PrefixEntry] = []
            for i, d in enumerate(digests):
                e = self._entries.get(d)
                if e is None or e.index != i:
                    break
                run.append(e)
            if run:
                now = self.clock()
                for e in run:
                    e.refs += 1
                    e.last_used = now
                    self._entries.move_to_end(e.digest)
                self.hits += 1
                self.hit_tokens += sum(e.tokens for e in run)
            else:
                self.misses += 1
            return run

    def release(self, entries: List[PrefixEntry]) -> None:
        with self._lock:
            for e in entries:
                e.refs = max(0, e.refs - 1)

    def contains(self, digest: str) -> bool:
        with self._lock:
            return digest in self._entries

    def publish(self, digest: str, index: int, pages,
                tokens: int) -> bool:
        """Store one exported grain chunk.  False (not stored) when the
        digest is already present or every evictable entry is pinned and
        the caps leave no room — the publisher loses nothing either way
        (its slot keeps its private pages)."""
        nbytes = self._nbytes(pages)
        with self._lock:
            if digest in self._entries:
                return False
            if not self._make_room_locked(nbytes):
                return False
            e = PrefixEntry(
                digest, index, pages, tokens, nbytes, self.clock())
            self._entries[digest] = e
            self.bytes += nbytes
            self.publishes += 1
            return True

    def _make_room_locked(self, incoming: int) -> bool:
        def over() -> bool:
            return (len(self._entries) + 1 > self.cap_entries
                    or (self.cap_bytes > 0
                        and self.bytes + incoming > self.cap_bytes))

        while over():
            victim = next(
                (e for e in self._entries.values() if e.refs == 0), None)
            if victim is None:
                return False  # everything pinned: refuse, never reclaim
            self._evict_locked(victim)
        return True

    def _evict_locked(self, e: PrefixEntry) -> None:
        del self._entries[e.digest]
        self.bytes -= e.nbytes
        self.evictions += 1

    def trim(self) -> int:
        """Reclaim every COLD (``refs == 0``) entry — the memory
        pressure ladder's first rung.  Pinned entries survive by
        construction.  Returns entries freed (the monitor's unit)."""
        with self._lock:
            cold = [e for e in self._entries.values() if e.refs == 0]
            for e in cold:
                self._evict_locked(e)
            return len(cold)

    def clear(self) -> int:
        """Drop EVERYTHING (device-loss remesh: the pages' placements
        died with the mesh).  Only called after every reader was handed
        off — any stale pin is force-released with its entry."""
        with self._lock:
            n = len(self._entries)
            self.evictions += n
            self._entries.clear()
            self.bytes = 0
            return n

    def hot_digests(self, k: int = 8) -> List[str]:
        """Most-recently-used entry digests, truncated for the bounded
        discovery digest (core/fleet.py advertises them so operators
        can see WHICH prefixes a server holds)."""
        with self._lock:
            es = sorted(
                self._entries.values(), key=lambda e: -e.last_used)[:k]
            return [e.digest[:12] for e in es]

    def snapshot(self) -> Dict[str, Any]:
        with self._lock:
            return {
                "prefix_hits": self.hits,
                "prefix_misses": self.misses,
                "prefix_publishes": self.publishes,
                "prefix_evictions": self.evictions,
                "prefix_entries": len(self._entries),
                "prefix_refs": sum(
                    e.refs for e in self._entries.values()),
                "prefix_bytes": self.bytes,
                "prefix_hit_tokens": self.hit_tokens,
            }


class GenStream:
    """One generation stream: a prompt waiting for / occupying a slot.

    ``frame`` is the source TensorFrame (kept alive so emitted chunks
    inherit its meta — client_id, trace id, tenant — via
    ``with_tensors``); tokens accumulate in ``pending`` until a chunk
    boundary or a terminal event flushes them.
    """

    __slots__ = (
        "sid", "frame", "prompt", "max_new", "chunk", "tenant", "priority",
        "deadline_ts", "token_budget_s", "state", "slot", "prefill_pos",
        "gen", "dispatched", "tok", "pending", "pending_n", "chunk_index",
        "tokens_out",
        "evict_reason", "submitted_ts", "last_token_ts", "joined_ts",
        # seconds the pump spent in THIS stream's own reset and prefill
        # steps (what lane wait leaves out)
        "own_s",
        # stream continuity (core/continuity.py): what the chunked
        # prefill actually runs over (prompt, or prompt + generated
        # prefix on a RESUME), the checkpoint to restart decode from,
        # and the per-chunk resume state stamped into emitted meta
        "prefill_src", "resume_tok", "resume_gen", "resume_info",
        # shared-prefix cache (PrefixCache): the chain digests of this
        # stream's eligible prefix chunks, the pinned entries it
        # attached (released with the slot), and the next chunk index
        # to consider publishing as prefill crosses grain boundaries
        "prefix_digests", "prefix_entries", "prefix_pub_i",
    )

    def __init__(self, sid: int, frame, prompt, max_new: int, chunk: int,
                 tenant: str = "", priority: int = 3,
                 deadline_ts: Optional[float] = None,
                 token_budget_s: float = 0.0, now: float = 0.0):
        self.sid = sid
        self.frame = frame
        self.prompt = prompt              # np.int32 (1, Tp)
        self.max_new = int(max_new)
        self.chunk = max(1, int(chunk))
        self.tenant = tenant
        self.priority = int(priority)
        self.deadline_ts = deadline_ts    # absolute monotonic or None
        self.token_budget_s = float(token_budget_s)
        self.state = "waiting"            # waiting|prefill|decoding|<DONE>
        self.slot: Optional[int] = None
        self.prefill_pos = 0
        self.gen = 0                      # tokens generated so far
        self.dispatched = 0               # tokens whose step is dispatched
        self.tok = 0                      # last token (host int)
        self.pending: List[Any] = []      # np arrays (1, k) awaiting a chunk
        self.pending_n = 0
        self.chunk_index = 0
        self.tokens_out = 0               # tokens actually emitted
        self.evict_reason: Optional[str] = None
        self.submitted_ts = now
        self.last_token_ts = now
        self.joined_ts: Optional[float] = None
        self.own_s = 0.0
        self.prefill_src = prompt         # prompt (+ prefix[:-1] on resume)
        self.resume_tok = 0               # last prefix token (resume only)
        self.resume_gen = 0               # tokens already delivered (resume)
        self.resume_info: Optional[Dict[str, Any]] = None
        self.prefix_digests: List[str] = []
        self.prefix_entries: List[Any] = []
        self.prefix_pub_i = 0

    @property
    def finished(self) -> bool:
        return self.state in DONE_STATES


@runtime_checkable
class SlotModelProtocol(Protocol):
    """What :class:`SlotEngine` calls on a slot model, written down once.
    ``models.transformer.SlotModel`` (dense decoder), ``models.hybrid_lm.
    HybridSlotModel`` (Mamba-2 / attention / routed experts) and
    :class:`SimSlotModel` satisfy it; the engine never learns which family
    it drives.

    * ``slots`` — the fixed width of the slot batch;
    * ``init_cache()`` — the zeroed per-slot state, OPAQUE to the engine
      (K/V rows by position, recurrent state without a position axis, a
      counter: the model's own business);
    * ``reset_slot(cache, slot) -> cache`` — zero ONE slot's state;
    * ``prefill_fn(n)(params, cache, toks (1, n), slot) -> (cache, logits
      (1, V))`` — one chunk of one slot's prompt, from the state the
      slot's previous chunk left;
    * ``pick_first(logits) -> (1,)`` — token 1;
    * ``decode_fn(k)(params, cache, tok, gen, active) -> (cache, tok,
      gen, toks (slots, k)[, counts])`` — ``k`` tokens for every active
      slot; a row with ``active == 0`` keeps its token, its count and its
      state.  The engine hands the ``tok`` and ``gen`` a dispatch returned
      to the next one as they are, and reads ``toks`` back a turn later.
      ``counts``, where ``counter_names`` is not empty, is one
      integer per name, summed since the last dispatch took them: the
      engine adds them to always-on counters of those names in
      :meth:`SlotEngine.snapshot`, on the read-back it makes anyway;
    * ``prefill_counts(pos, n) -> {name: int}`` — what a chunk of ``n``
      tokens at position ``pos`` adds to those of ``counter_names`` that
      follow from the position alone (Python integers, no device read:
      such a count grows with the square of the context); ``{}`` where
      there is none;
    * ``decode_compiles`` / ``prefill_compiles`` — trace counts (the
      shape-stability contract is observable);
    * ``place_params(params)`` — stage a parameter tree where the model
      runs;
    * ``supports_prefix`` with ``export_prefix`` / ``attach_prefix`` —
      whether a prefix of a slot's state can be cut out by position (the
      shared-prefix pool); a model that says no is refused the pool.
    """

    slots: int
    decode_compiles: int
    prefill_compiles: int
    counter_names: Tuple[str, ...]
    supports_prefix: bool

    def init_cache(self) -> Any: ...

    def reset_slot(self, cache, slot) -> Any: ...

    def prefill_fn(self, n: int) -> Callable[..., Any]: ...

    def prefill_counts(self, pos: int, n: int) -> Dict[str, int]: ...

    def pick_first(self, logits) -> Any: ...

    def decode_fn(self, k: int) -> Callable[..., Any]: ...

    def place_params(self, params) -> Any: ...

    def export_prefix(self, cache, slot, start: int, stop: int) -> Any: ...

    def attach_prefix(self, cache, slot, pages_list, n: int) -> Any: ...


class SimSlotModel:
    """Deterministic SIMULATED slot model (the async-sim discipline,
    PR-6): duck-types ``models.transformer.SlotModel`` but replaces the
    transformer with a token recurrence plus TPU-SHAPED step costs —
    every decode step pays a B-INDEPENDENT base (weight streaming +
    dispatch, the memory-bound LLM-decode regime batching amortizes)
    plus a small per-active-slot increment.

    This is what the slot engine's dispatch-ledger tests and the chaos
    harness drive: the object under test is the SLOT
    SCHEDULER (join/evict correctness, multiplexing win, emission-path
    overhead), not XLA-CPU GEMM scaling, which inverts the real
    accelerator's batch economics at zoo-model sizes.

    Token oracle: token 1 = ``sum(prompt) % vocab``; token j+1 =
    ``(31 * t_j + 17) % vocab`` — exact per-stream accounting is
    checkable without running a model.  The per-slot "pages" are a
    position counter that asserts slot isolation (a write to slot i can
    never touch slot j by construction, and tests pin the counters).
    """

    counter_names = ()
    supports_prefix = True

    def __init__(self, slots: int, vocab: int = 997,
                 step_base_ms: float = 1.0, step_per_slot_ms: float = 0.05,
                 prefill_ms_per_token: float = 0.02,
                 sleep=time.sleep,
                 oom_at_step: Optional[int] = None,
                 lost_at_step: Optional[int] = None):
        import numpy as np

        self._np = np
        self.slots = int(slots)
        self.vocab = int(vocab)
        self.step_base_s = step_base_ms * 1e-3
        self.step_per_slot_s = step_per_slot_ms * 1e-3
        self.prefill_s_per_token = prefill_ms_per_token * 1e-3
        self._sleep = sleep
        self.decode_compiles = 0
        self.prefill_compiles = 0
        # deterministic device-resource chaos (the AsyncSim twin knobs):
        # decode ATTEMPT index N raises the typed error exactly once —
        # the attempt counter advances on faulted attempts, so the
        # engine's retry (a fresh attempt) proceeds.  Token sequences
        # are unaffected: the fault fires before any state mutation.
        self.oom_at_step = oom_at_step
        self.lost_at_step = lost_at_step
        self._attempts = 0
        self._pending_fault: Optional[str] = None
        #: simulated device-busy seconds (occupancy evidence)
        self.busy_s = 0.0
        # running prompt-sum per slot: chunked prefill accumulates into
        # it so token 1 covers the WHOLE prompt across chunk boundaries
        self._prefill_carry: Dict[int, int] = {}

    def fail_next(self, kind: str) -> None:
        """Arm the NEXT decode attempt to raise the typed device error
        (``"oom"`` | ``"lost"``), race-free against a running pump —
        the chaos harness's scripted injection point."""
        if kind not in ("oom", "lost"):
            raise ValueError(f"fail_next({kind!r}): want oom|lost")
        self._pending_fault = kind

    def place_params(self, params):
        return params  # no device: the oracle holds no parameters

    def init_cache(self):
        np = self._np
        return {"pos": np.zeros((self.slots,), np.int64)}

    def reset_slot(self, cache, slot):
        cache = {"pos": cache["pos"].copy()}
        cache["pos"][int(slot)] = 0
        self._prefill_carry[int(slot)] = 0
        return cache

    def export_prefix(self, cache, slot, start: int, stop: int):
        """Sim twin of ``SlotModel.export_prefix``: the oracle's only
        per-prefix state is the running prompt sum, so a chunk's "pages"
        are the CUMULATIVE carry at ``stop`` (the engine exports exactly
        at the grain-boundary moment ``prefill_pos == stop``, where the
        live carry covers precisely positions ``[0, stop)``)."""
        del cache, start
        return {"carry": int(self._prefill_carry.get(int(slot), 0)),
                "n": int(stop)}

    def attach_prefix(self, cache, slot, pages_list, n: int):
        """Sim twin of ``SlotModel.attach_prefix``: restore the carry
        from the LAST chunk (cumulative encoding) and set the slot's
        position to ``n`` — indistinguishable from a cold prefill paused
        at ``prefill_pos == n``, so token 1 still covers the whole
        prompt."""
        np = self._np
        cache = {"pos": cache["pos"].copy()}
        cache["pos"][int(slot)] = np.int64(n)
        self._prefill_carry[int(slot)] = int(pages_list[-1]["carry"])
        return cache

    @staticmethod
    def prefill_counts(pos: int, n: int):
        return {}

    def prefill_fn(self, n: int):
        np = self._np
        self.prefill_compiles += 1

        def fn(params, cache, toks, slot):
            dt = self.prefill_s_per_token * toks.shape[1]
            self._sleep(dt)
            self.busy_s += dt
            cache = {"pos": cache["pos"].copy()}
            cache["pos"][int(slot)] += toks.shape[1]
            tot = (self._prefill_carry.get(int(slot), 0)
                   + int(toks.sum())) % self.vocab
            self._prefill_carry[int(slot)] = tot
            # "logits": one-hot at the oracle's token 1 so pick_first
            # recovers it
            logits = np.zeros((1, self.vocab), np.float32)
            logits[0, tot] = 1.0
            return cache, logits

        return fn

    def pick_first(self, logits):
        np = self._np
        return np.argmax(np.asarray(logits), axis=-1).astype(np.int32)

    def step_token(self, t: int) -> int:
        return (31 * int(t) + 17) % self.vocab

    def decode_fn(self, k: int):
        np = self._np
        self.decode_compiles += 1

        def fn(params, cache, tok, gen, active):
            idx = self._attempts
            self._attempts += 1
            pending, self._pending_fault = self._pending_fault, None
            if pending == "lost" or (
                    self.lost_at_step is not None
                    and idx == self.lost_at_step):
                raise DeviceLostError(
                    "sim: simulated mesh-member death", device_ids=(0,))
            if pending == "oom" or (
                    self.oom_at_step is not None
                    and idx == self.oom_at_step):
                raise DeviceOomError("sim: simulated HBM exhaustion")
            n_active = int(active.sum())
            dt = k * (self.step_base_s
                      + self.step_per_slot_s * n_active)
            self._sleep(dt)
            self.busy_s += dt
            tok = np.asarray(tok).copy()
            gen = np.asarray(gen).copy()
            cache = {"pos": cache["pos"].copy()}
            toks = np.zeros((self.slots, k), np.int32)
            for step in range(k):
                for slot in range(self.slots):
                    if active[slot]:
                        tok[slot] = self.step_token(tok[slot])
                        toks[slot, step] = tok[slot]
                gen = gen + active
            cache["pos"] = cache["pos"] + k * active.astype(np.int64)
            return cache, tok, gen, toks

        return fn


class SlotEngine:
    """Fixed-width continuous-batching scheduler over a
    :class:`~nnstreamer_tpu.models.transformer.SlotModel`.

    Public API (thread-safe): :meth:`submit`, :meth:`cancel`,
    :meth:`pop_ready`, :meth:`pending`, :meth:`wait_progress`,
    :meth:`snapshot`.  ``start``/``stop`` bound the pump thread's life
    to the owning element's.
    """

    #: bound on live prefill jit buckets (chunk-length LRU — same
    #: discipline as the filter's _stack_jit_cache, PR-3)
    JIT_BUCKET_MAX = 16
    #: deadline evictions fire this far BEFORE the request deadline: the
    #: typed-expiry answer must still reach a client whose own timeout
    #: fires exactly AT the deadline (one reply's worth of headroom)
    EVICT_MARGIN_S = 0.05

    def __init__(self, model, params, *, max_seq: int, chunk: int = 8,
                 prefill_chunk: int = 32, prefill_priority: int = 1,
                 token_budget_s: float = 0.0,
                 jit_bucket_max: Optional[int] = None,
                 clock: Callable[[], float] = time.monotonic,
                 name: str = "slots",
                 resume_sig: Optional[str] = None,
                 on_device_lost: Optional[Callable[..., Any]] = None,
                 slo=None,
                 prefix_cache: Optional[PrefixCache] = None,
                 on_ready: Optional[Callable[[], None]] = None):
        import numpy as np

        self._np = np
        self.model = model
        self.params = params
        self.slots = int(model.slots)
        self.max_seq = int(max_seq)
        self.chunk = max(1, int(chunk))
        self.prefill_chunk = max(1, int(prefill_chunk))
        self.prefill_priority = max(0, int(prefill_priority))
        self.token_budget_s = float(token_budget_s)
        self.jit_bucket_max = int(jit_bucket_max or self.JIT_BUCKET_MAX)
        self.clock = clock
        self.name = name
        # shared-prefix page pool (None = off: ZERO behavior change —
        # no digesting, no attach, no publish, no snapshot keys).  The
        # grain must land on the chunked-prefill grid, or warm and cold
        # runs would see different chunk boundaries (different XLA
        # programs / float reduction orders) and bit-exactness breaks.
        self.prefix = prefix_cache
        if prefix_cache is not None and not model.supports_prefix:
            raise ValueError(
                "prefix cache: this model's slot state cannot be cut by "
                "position (a recurrent state has no position axis)")
        if prefix_cache is not None and (
                prefix_cache.grain % self.prefill_chunk != 0):
            raise ValueError(
                f"prefix grain {prefix_cache.grain} must be a multiple "
                f"of prefill_chunk {self.prefill_chunk} (bit-exactness "
                "requires identical prefill chunk boundaries)")
        # stream continuity (core/continuity.py): with a signature armed,
        # every chunk carries resume state in meta, and a drain hands
        # live streams off as resumable GOAWAY final chunks instead of
        # waiting them out; None = legacy engine (no stamping, drains
        # let streams finish)
        self.resume_sig = resume_sig
        self._goaway = False
        # degrade-don't-die (core/resilience.py device taxonomy): the
        # element-supplied recovery hook for a lost mesh member —
        # ``on_device_lost(err) -> (model, params) | None`` rebuilds the
        # model on the surviving devices (None = the model recovered in
        # place, e.g. the sim twin).  Without a hook a lost device is a
        # sticky engine error (supervision restart rebuilds the element).
        self.on_device_lost = on_device_lost
        # the consumer's wake-up (pump thread; must not block): called
        # once per batch of ready outputs, so that a turn's frames leave
        # when they exist and not at the consumer's next poll.  A batch
        # is DUE when an output lands in an empty ready list and is
        # announced right after the pump's next device dispatch (or as
        # the pump goes idle): the consumer's burst of deliveries then
        # shares the interpreter with a pump that waits for the device,
        # not with the dispatch the device waits for
        self.on_ready = on_ready
        self._wake_due = False
        # how many outputs at the head of the ready list a wake has
        # announced (while a profiler session is live, their meta holds
        # the announce instant: the hop the dispatch thread waits for)
        self._announced = 0
        # per-stream SLO accounting (telemetry.SloTracker, engine side):
        # one TTFT stamp at the first-token pick, one record_n per
        # decode scan, one counter per terminal outcome — all on the
        # pump thread (the tracker's single-writer contract); None =
        # zero cost everywhere
        self.slo = slo
        # background-thread liveness: the pump beats once per loop —
        # a pump with pending work and a stale beat is WEDGED (stuck in
        # a device call), which the sticky pop_ready error can never
        # surface because the thread never returns
        self.heartbeat = ThreadBeat(f"{name}-slots", clock=clock)

        self._lock = threading.Lock()
        self._work = threading.Condition(self._lock)       # pump wakeups
        self._progress = threading.Condition(self._lock)   # consumer waits
        self._waiting: List[GenStream] = []
        self._occupants: List[Optional[GenStream]] = [None] * self.slots
        self._ready: List[Tuple[int, Any]] = []  # (pad, TensorFrame) outs
        self._streams: Dict[int, GenStream] = {}  # live (non-terminal)
        self._sid = 0
        self._error: Optional[BaseException] = None
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None

        # device state (pump-thread-private after start): the cache, the
        # token and count vectors the next scan takes, the scan dispatched
        # and not yet read back, and the streams whose first token has
        # been seated since the last scan was dispatched
        self._cache = None
        self._tok_vec = None
        self._gen_vec = None
        self._inflight: Optional[_Scan] = None
        self._picked: List[GenStream] = []
        # chunk-length jit buckets, LRU-bounded (filter _stack_jit_cache
        # discipline): one per distinct prefill piece / decode scan length
        self._prefill_lru: "OrderedDict[int, Any]" = OrderedDict()
        self._decode_lru: "OrderedDict[int, Any]" = OrderedDict()

        # exact accounting (lock-held writes, GIL-atomic reads)
        self.joins = 0
        self.completions = 0
        self.evictions = 0
        self.cancellations = 0
        self.decode_steps = 0
        self.decode_ahead = 0       # scans dispatched before the last read back
        self.prefill_chunks = 0
        self.prefill_tokens = 0     # prompt tokens those chunks held
        self.tokens_total = 0
        self.tokens_per_step = 0.0  # EWMA of active slots per decode step
        self.resumes = 0            # streams joined via a RESUME request
        self.goaway_evicted = 0     # live streams handed off on drain
        # device-resource resilience accounting (exact; the chaos e2e
        # and the registry read these)
        self.oom_retries = 0        # device steps retried after an OOM
        self.oom_sheds = 0          # slots shed (resumably) to relieve HBM
        self.device_lost = 0        # lost-device events survived
        self.device_lost_evicted = 0  # live streams handed off on loss
        self.remeshes = 0           # models rebuilt on surviving devices
        # where requests and the pump wait (seconds on ``clock``; the
        # pump thread is the only writer): submit -> join summed over
        # joins; join -> the first token's pick minus the stream's own
        # reset and prefill steps, summed over picks; pump seconds inside a
        # turn that are neither a device step or read-back nor the idle
        # wait for a request
        self.admit_wait_s = 0.0
        self.first_tokens = 0
        self.lane_wait_s = 0.0
        self.pump_host_s = 0.0
        # the model's own counters (``counter_names``), as its decode
        # dispatches hand them over
        self.model_counts: Dict[str, int] = dict.fromkeys(
            model.counter_names, 0)
        # this turn's device and idle seconds (pump-thread-private)
        self._dev_s = 0.0
        self._idle_s = 0.0

    # -- lifecycle ----------------------------------------------------------
    def start(self) -> None:
        np = self._np
        self._stop.clear()
        self._error = None
        self._goaway = False
        self._cache = self.model.init_cache()
        # the state vectors start as HOST numpy (model-agnostic: a jax
        # model converts them at the jit boundary and hands back its own,
        # which the next dispatch takes as they are; sim models stay numpy)
        self._tok_vec = np.zeros((self.slots,), np.int32)
        self._gen_vec = np.zeros((self.slots,), np.int32)
        self._inflight, self._picked = None, []
        self._thread = threading.Thread(
            target=self._pump, name=f"{self.name}-slots", daemon=True)
        self.heartbeat.bind(self._thread)
        self.heartbeat.beat()
        self._thread.start()

    def stop(self) -> None:
        self._stop.set()
        with self._work:
            self._work.notify_all()
            self._progress.notify_all()
        t = self._thread
        if t is not None:
            t.join(timeout=30.0)
            self._thread = None
        with self._lock:
            abandoned = len(self._streams)  # waiting ones are members too
            if abandoned:
                log.warning(
                    "%s: engine stopped with %d stream(s) abandoned",
                    self.name, abandoned)
            if self.prefix is not None:
                for s in self._streams.values():
                    self._release_prefix(s)
            self._waiting.clear()
            self._streams.clear()
            self._occupants = [None] * self.slots
            self._ready.clear()
        self._cache = None
        self._prefill_lru.clear()
        self._decode_lru.clear()

    # -- submission / cancellation -----------------------------------------
    def submit(self, frame, prompt, max_new: int, chunk: int,
               tenant: str = "", priority: int = 3,
               deadline_ts: Optional[float] = None,
               resume: Optional[Dict[str, Any]] = None) -> GenStream:
        """Queue one prompt for a slot.  ``prompt`` is host int32
        (1, Tp), already validated against ``max_seq`` by the caller.

        ``resume`` = ``{"prefix": (1, R) int32, "tokens_done": R}``
        joins a CHECKPOINTED stream instead of a fresh one: the chunked
        prefill runs over prompt + prefix[:-1], decode restarts from the
        prefix's last token at absolute step R (the per-step sampling
        key folds at the absolute index, so the remaining tokens are
        bit-identical to an uninterrupted run), and emitted
        ``tokens_done`` / ``chunk_index`` continue from R.  The caller
        validated signature/digest/shape; R == 0 degrades to a fresh
        join (full replay, client-side dedupe owns the overlap)."""
        np = self._np
        with self._lock:
            if self._error is not None:
                raise self._error
            self._sid += 1
            s = GenStream(
                self._sid, frame, prompt, max_new, chunk,
                tenant=tenant, priority=priority, deadline_ts=deadline_ts,
                token_budget_s=self.token_budget_s, now=self.clock(),
            )
            if self.resume_sig is not None:
                s.resume_info = {
                    "v": 1, "sig": self.resume_sig,
                    "digest": prompt_digest(prompt), "chunk": int(s.chunk),
                }
            if resume is not None:
                self.resumes += 1
                r = int(resume.get("tokens_done", 0))
                if r > 0:
                    prefix = np.asarray(resume["prefix"], dtype=np.int32)
                    s.prefill_src = (
                        np.concatenate([prompt, prefix[:, :r - 1]], axis=1)
                        .astype(np.int32) if r > 1 else prompt)
                    s.resume_tok = int(prefix[0, r - 1])
                    s.resume_gen = r
                    s.tokens_out = r
                    s.chunk_index = r // s.chunk
            self._streams[s.sid] = s
            self._waiting.append(s)
            self._work.notify_all()
            return s

    def begin_goaway(self) -> None:
        """Drain handoff (rolling restart): from the next token boundary
        on, every live stream — decoding, prefilling, or still waiting —
        is flushed with a RESUMABLE final chunk (partial tokens +
        resume state + the ``goaway`` marker) and its slot freed, so the
        client migrates it to a healthy server and the serversrc's
        drain completes as soon as the handoffs are delivered.  No-op on
        a legacy engine without a resume signature: a handoff chunk the
        client cannot resume would silently truncate the stream."""
        if self.resume_sig is None:
            log.warning(
                "%s: drain without resume state armed — live streams "
                "will finish in place instead of migrating", self.name)
            return
        with self._work:
            self._goaway = True
            self._work.notify_all()

    def end_goaway(self) -> None:
        """Rescind a drain handoff (the resize rollback path: the
        replacement model failed to build, so this engine keeps
        serving).  Streams already flushed stay handed off — their
        clients resume them here or elsewhere; new joins stop being
        swept from the next boundary on."""
        with self._work:
            self._goaway = False

    #: cumulative ledger counters that survive an in-place engine
    #: rebuild (autoscale resize): the server's lifetime accounting —
    #: digests and the fleet observatory's exactness ride on these
    #: never moving backwards
    _LEDGER_ATTRS = (
        "joins", "completions", "evictions", "cancellations",
        "decode_steps", "decode_ahead", "prefill_chunks", "prefill_tokens",
        "tokens_total",
        "resumes",
        "goaway_evicted", "oom_retries", "oom_sheds", "device_lost",
        "device_lost_evicted", "remeshes", "admit_wait_s", "first_tokens",
        "lane_wait_s", "pump_host_s",
    )

    def adopt_ledger(self, other: "SlotEngine") -> None:
        """Carry ``other``'s cumulative counters into this engine (call
        before :meth:`start`).  A slot-width resize replaces the engine
        but not the SERVER — its digest counters must stay monotonic or
        the observatory's exact fleet totals would lose the pre-resize
        history."""
        for attr in self._LEDGER_ATTRS:
            setattr(self, attr, getattr(other, attr))
        self.tokens_per_step = other.tokens_per_step

    def cancel(self, sid: Optional[int] = None,
               client_id: Optional[int] = None) -> bool:
        """Cancel by stream id or by the source frame's client_id meta
        (the serversink's client-gone feedback).  The slot frees at the
        next token boundary; no further chunks are emitted."""
        with self._lock:
            for s in list(self._streams.values()):
                if s.finished:
                    continue  # reaped at the next boundary; never recount
                if (sid is not None and s.sid == sid) or (
                        client_id is not None
                        and s.frame.meta.get("client_id") == client_id):
                    s.state = "cancelled"
                    self.cancellations += 1
                    self._work.notify_all()
                    return True
        return False

    # -- consumer side (element dispatch thread) ----------------------------
    def pop_ready(self) -> List[Tuple[int, Any]]:
        """Drain ready chunk frames (FIFO).  Re-raises any pump-thread
        error HERE, so supervision attributes it to the element call.
        The error is STICKY: a dead pump must keep failing loudly (and
        keep refusing submits) — a restart re-opens the element and
        builds a fresh engine."""
        with self._lock:
            if self._error is not None and not self._ready:
                raise self._error
            out, self._ready = self._ready, []
            self._announced = 0
        if out and armed():
            t = time.perf_counter()
            for _, f in out:
                f.meta[TL_POP_META] = t
        return out

    def pending(self) -> int:
        """Logical frames parked in the engine (``pending_frames`` hook:
        scheduler fast-poll + drain/stop accounting): live streams
        (``_streams`` already includes the waiting ones) plus
        undelivered ready chunks."""
        with self._lock:
            return len(self._streams) + len(self._ready)

    def idle(self) -> bool:
        with self._lock:
            return not self._streams and not self._ready

    def wait_progress(self, timeout: float = 0.1) -> None:
        """Block the caller until the pump makes progress (EOS flush)."""
        with self._progress:
            if self._ready or self._error is not None:
                return
            self._progress.wait(timeout)

    # -- accounting ---------------------------------------------------------
    def snapshot(self) -> Dict[str, Any]:
        with self._lock:
            occupied = sum(1 for s in self._occupants if s is not None)
            snap = {
                "gen_slots": self.slots,
                "gen_occupied": occupied,
                "gen_waiting": len(self._waiting),
                "gen_joins": self.joins,
                "gen_completed": self.completions,
                "gen_evicted": self.evictions,
                "gen_cancelled": self.cancellations,
                "gen_tokens": self.tokens_total,
                "gen_decode_steps": self.decode_steps,
                "gen_decode_ahead": self.decode_ahead,
                "gen_prefill_chunks": self.prefill_chunks,
                "gen_prefill_tokens": self.prefill_tokens,
                "gen_tokens_per_step": round(self.tokens_per_step, 3),
                "gen_jit_buckets": (
                    len(self._prefill_lru) + len(self._decode_lru)),
                "gen_decode_compiles": self.model.decode_compiles,
                "gen_resumes": self.resumes,
                "gen_goaway_evicted": self.goaway_evicted,
                "gen_oom_retries": self.oom_retries,
                "gen_oom_sheds": self.oom_sheds,
                "gen_device_lost": self.device_lost,
                "gen_device_lost_evicted": self.device_lost_evicted,
                "gen_remeshes": self.remeshes,
                "gen_admit_wait_s": self.admit_wait_s,
                "gen_first_tokens": self.first_tokens,
                "gen_lane_wait_s": self.lane_wait_s,
                "gen_pump_host_s": self.pump_host_s,
                **self.model_counts,
            }
        # armed cache only: with the cache off the snapshot is
        # byte-identical to the pre-prefix engine (zero behavior change)
        if self.prefix is not None:
            snap.update(self.prefix.snapshot())
        return snap

    # -- pump internals -----------------------------------------------------
    def _prefill_fn(self, n: int):
        return lru_bucket(
            self._prefill_lru, n, self.model.prefill_fn,
            self.jit_bucket_max)

    def _decode_fn(self, k: int):
        return lru_bucket(
            self._decode_lru, k, self.model.decode_fn,
            self.jit_bucket_max)

    def _take(self, s: GenStream, n: int):
        """Slice the first ``n`` pending tokens off the stream's buffer
        (lock held)."""
        np = self._np
        buf = (s.pending[0] if len(s.pending) == 1
               else np.concatenate(s.pending, axis=1))
        piece = buf[:, :n]
        rest = buf[:, n:]
        s.pending = [rest] if rest.shape[1] else []
        s.pending_n = buf.shape[1] - n
        return piece

    def _emit_frame(self, s: GenStream, toks, final: bool,
                    extra_meta: Optional[Dict[str, Any]] = None) -> None:
        """Emit one chunk frame (lock held).  ``toks`` may be None for
        a terminal answer with nothing pending (eviction at a chunk
        boundary / never-joined stream): the stream still gets its
        FINAL answer as a tensor-LESS frame — the wire carries
        zero-tensor frames, while a (1, 0) tensor it would refuse."""
        np = self._np
        if toks is not None:
            s.tokens_out += toks.shape[1]
            tensors = [toks.astype(np.int32)]
        else:
            tensors = []
        out = s.frame.with_tensors(tensors)
        out.meta.update(
            stream_seq=s.frame.seq, chunk_index=s.chunk_index,
            tokens_done=s.tokens_out, final=bool(final),
        )
        if s.resume_info is not None:
            # stream continuity: every chunk is a checkpoint — the
            # client can rebuild the stream from its accumulated tokens
            # plus this state on ANY server with a matching signature
            out.meta[RESUME_META] = s.resume_info
        if extra_meta:
            out.meta.update(extra_meta)
        if armed():
            # the frame's first hop on its way to the client: emission
            out.meta[TL_EMIT_META] = (
                time.perf_counter(), s.sid,
                0 if toks is None else int(toks.shape[1]))
        s.chunk_index += 1
        self._ready.append((0, out))
        self._progress.notify_all()
        if len(self._ready) == 1:
            self._wake_due = True

    def _emit_boundary(self, s: GenStream) -> None:
        """Emit EXACTLY chunk-sized pieces (lock held) — identical
        chunking to the unslotted path, whatever the scan length was."""
        while s.pending_n >= s.chunk:
            self._emit_frame(s, self._take(s, s.chunk), final=False)

    def _emit_terminal(self, s: GenStream,
                       extra_meta: Optional[Dict[str, Any]] = None
                       ) -> None:
        """Terminal flush (lock held): full chunks first, then the tail
        as the FINAL frame (exactly the unslotted tail semantics)."""
        while s.pending_n > s.chunk:
            self._emit_frame(s, self._take(s, s.chunk), final=False)
        self._emit_frame(
            s, self._take(s, s.pending_n) if s.pending_n else None,
            final=True, extra_meta=extra_meta)

    def _release_prefix(self, s: GenStream) -> None:
        """Unpin the stream's attached prefix entries (exactly once:
        the list empties).  The pin spans the WHOLE slot occupancy —
        that is the refcount contract ("never reclaimed under a live
        reader"), not merely the attach moment."""
        if self.prefix is not None and s.prefix_entries:
            self.prefix.release(s.prefix_entries)
            s.prefix_entries = []

    def _free_slot(self, s: GenStream) -> None:
        """Release the stream's slot (lock held): pages become reusable
        without touching neighbors; the idle mask clears outside."""
        self._release_prefix(s)
        if s.slot is not None:
            self._occupants[s.slot] = None
        self._streams.pop(s.sid, None)

    def _finish(self, s: GenStream, state: str,
                extra_meta: Optional[Dict[str, Any]] = None) -> None:
        s.state = state
        if state == "done":
            self.completions += 1
            self._slo_stream(s, "good")
            self._emit_terminal(s)
        elif state == "evicted":
            self.evictions += 1
            # typed expiry (deadline/pace): the SLO ledger classifies
            # it as expired, never goodput
            self._slo_stream(s, "expired")
            self._emit_terminal(s, extra_meta=extra_meta or {})
        # cancelled: the consumer is gone — nothing to emit
        self._free_slot(s)

    def _slo_stream(self, s: GenStream, outcome: str) -> None:
        if self.slo is not None:
            self.slo.note_stream(s.tenant, outcome)

    def _sweep_deadlines(self, now: float) -> None:
        """Evict streams whose request deadline or per-token budget is
        blown; expire waiting streams that died in the queue (lock
        held).  The typed-expiry chunk preserves partial tokens."""
        for s in list(self._streams.values()):
            if s.finished:
                continue
            if not (s.deadline_ts is not None
                    and now >= s.deadline_ts - self.EVICT_MARGIN_S):
                continue
            if s.state == "waiting":
                try:
                    self._waiting.remove(s)
                except ValueError:
                    pass
            self._evict(s, "deadline")

    def _evict(self, s: GenStream, reason: str) -> None:
        """Typed-expiry eviction (lock held): partial tokens flush with
        the eviction meta, the slot frees at this boundary."""
        s.evict_reason = reason
        self._finish(s, "evicted", extra_meta={
            "evicted": reason, "deadline_expired": True,
        })
        log.warning(
            "%s: stream %d evicted (%s) after %d token(s)",
            self.name, s.sid, reason, s.tokens_out)

    def _handoff_one(self, s: GenStream, reason: str) -> None:
        """Flush ONE live stream as a resumable handoff final chunk and
        free its slot (lock held).  A MIGRATION, not a failure: no
        ``deadline_expired`` marker (the client must not count a blown
        budget), partial tokens ride the final chunk, and the resume
        state on it lets the client continue bit-identically elsewhere.
        On a legacy engine (no resume signature) the chunk still closes
        the stream typed — truncation is loud, never a poisoned frame."""
        if s.state == "waiting":
            try:
                self._waiting.remove(s)
            except ValueError:
                pass
        s.state = "evicted"
        s.evict_reason = reason
        extra = {"evicted": reason}
        if self.resume_sig is not None:
            extra[GOAWAY_META] = True  # client migrates; tokens survive
        self._emit_terminal(s, extra_meta=extra)
        self._free_slot(s)

    def _sweep_goaway(self) -> None:
        """Drain handoff (lock held): flush EVERY live stream with a
        resumable GOAWAY final chunk and free its slot.  Runs every
        boundary while draining, so streams admitted just before the
        drain hand off too."""
        for s in list(self._streams.values()):
            if s.finished:
                continue
            self._handoff_one(s, "goaway")
            self.goaway_evicted += 1
            log.info(
                "%s: stream %d handed off on drain after %d token(s)",
                self.name, s.sid, s.tokens_out)

    # -- device-resource resilience (degrade, don't die) ---------------------
    def _device_step(self, fn, *args):
        """Every model call of the pump funnels through the shared
        classification boundary (``resilience.device_call``: the
        deterministic ``device.oom`` / ``device.lost`` sites plus
        raw-runtime-error typing) — the pump's recovery ladder keys on
        types, never on XLA status strings."""
        with self._on_device():
            out = device_call(fn, *args)
        self._announce_ready()
        return out

    def _announce_ready(self) -> None:
        """Tell the consumer that a batch of outputs is ready, if one is
        due (``on_ready``; the caller does not hold the lock)."""
        if self._wake_due and armed():
            with self._lock:
                self._stamp_announced()
        self._wake()

    def _wake(self) -> None:
        if self._wake_due:
            self._wake_due = False
            if self.on_ready is not None:
                self.on_ready()

    def _stamp_announced(self) -> None:
        """Stamp the announce instant on the ready outputs no wake has
        announced yet (lock held; only while a profiler session is
        live)."""
        t = time.perf_counter()
        for _, f in self._ready[self._announced:]:
            f.meta[TL_ANNOUNCE_META] = t
        self._announced = len(self._ready)

    @contextmanager
    def _on_device(self):
        """Seconds the pump spends in a device step or a read-back (this
        turn's ``_dev_s``): what ``gen_pump_host_s`` leaves out and a
        stream's ``own_s`` is made of."""
        t0 = self.clock()
        try:
            yield
        finally:
            self._dev_s += self.clock() - t0

    def _handle_oom(self) -> None:
        """HBM exhaustion mid-step: shed the LOWEST-priority occupant as
        a resumable continuity chunk (its tokens survive — the client
        migrates the stream), freeing its slot's KV pages, then let the
        failed step retry on the smaller active set.  Never a
        restart-budget burn, never a poisoned frame."""
        with self._lock:
            self.oom_retries += 1
            live = [
                s for s in self._occupants
                if s is not None and not s.finished
            ]
            if not live:
                return  # nothing held; the bare retry is the relief
            victim = min(
                live,
                key=lambda s: (s.priority, -(s.joined_ts or 0.0)),
            )
            self.oom_sheds += 1
            self._handoff_one(victim, "oom")
            log.warning(
                "%s: device OOM — shed stream %d (priority %d, %d "
                "token(s) safe) and retrying the step",
                self.name, victim.sid, victim.priority, victim.tokens_out)

    def _recover_donated_cache(self) -> None:
        """Donation invalidates at DISPATCH, not at success: on a real
        (non-CPU) backend the decode/prefill jits donate the KV cache,
        so the step that just OOMed may have consumed it — retrying
        with deleted buffers would raise an UNTYPED "Array has been
        deleted" and kill the pump with every remaining stream.  When
        the cache died with the step, every occupant's device context
        is gone: hand ALL live streams off as resumable continuity
        chunks (resume re-prefills from prompt+tokens — bit-exact) and
        re-init device state clean.  No-op on the sim twin and CPU,
        where nothing donates."""
        try:
            import jax

            leaves = jax.tree_util.tree_leaves(self._cache)
        except Exception:  # noqa: BLE001 — sim twin / no jax
            return
        if not any(
                getattr(leaf, "is_deleted", lambda: False)()
                for leaf in leaves):
            return
        shed = 0
        with self._lock:
            for s in list(self._streams.values()):
                if s.finished:
                    continue
                self._handoff_one(s, "oom")
                self.oom_sheds += 1
                shed += 1
        self._reset_device_state()
        log.warning(
            "%s: donated KV cache died with the OOMed step — %d "
            "stream(s) handed off resumable, cache re-initialized",
            self.name, shed)

    def _reset_device_state(self, clear_jit_lrus: bool = False) -> None:
        """Re-init the engine's per-device decode state clean (fresh KV
        cache, zeroed token/progress vectors) after every occupant was
        handed off — shared by the donated-cache OOM recovery and the
        device-loss rebuild so the two paths cannot drift.
        ``clear_jit_lrus`` additionally drops the compiled prefill/
        decode programs (a REPLACEMENT model invalidates them; a cache
        re-init on the same model does not)."""
        np = self._np
        self._cache = self.model.init_cache()
        self._tok_vec = np.zeros((self.slots,), np.int32)
        self._gen_vec = np.zeros((self.slots,), np.int32)
        self._inflight, self._picked = None, []
        if clear_jit_lrus:
            self._prefill_lru.clear()
            self._decode_lru.clear()
            # a REPLACEMENT model invalidates published pages too (their
            # device placements died with the mesh); every reader was
            # handed off above, so nothing is pinned
            if self.prefix is not None:
                dropped = self.prefix.clear()
                if dropped:
                    log.warning(
                        "%s: dropped %d cached prefix entr(ies) with "
                        "the replaced model", self.name, dropped)

    def _handle_device_lost(self, err: DeviceLostError) -> None:
        """A mesh member died under the batch: hand EVERY live stream
        off with resume state (exactly the drain contract — clients
        migrate them), then rebuild the model on the surviving devices
        via the element's ``on_device_lost`` hook and keep serving
        degraded.  Without a hook the loss is sticky (supervision
        restart rebuilds the element)."""
        handed = 0
        with self._lock:
            self.device_lost += 1
            for s in list(self._streams.values()):
                if s.finished:
                    continue
                self._handoff_one(s, "device_lost")
                self.device_lost_evicted += 1
                handed += 1
        hook = self.on_device_lost
        if hook is None:
            raise err
        replacement = hook(err)  # raises = unrecoverable -> sticky error
        with self._lock:
            if replacement is not None:
                self.model, self.params = replacement
            self.remeshes += 1
        # every slot was freed above: device state re-inits clean on
        # the replacement model (compile buckets retrace on demand)
        self._reset_device_state(clear_jit_lrus=True)
        log.warning(
            "%s: device lost (%s) — %d stream(s) handed off, model "
            "rebuilt on survivors (remesh #%d)",
            self.name, err, handed, self.remeshes)

    def _reap_cancelled(self) -> None:
        """Free slots of streams cancelled since the last boundary and
        drop cancelled entries still waiting (lock held).  SLO
        classification happens HERE (pump thread — the tracker's
        single-writer contract), exactly once per cancelled stream
        (``_free_slot`` removes it from ``_streams``)."""
        self._waiting = [w for w in self._waiting if w.state != "cancelled"]
        for s in list(self._streams.values()):
            if s.state == "cancelled":
                self._slo_stream(s, "evicted")
                self._free_slot(s)

    def _join_waiting(self, now: float) -> List[GenStream]:
        """Assign free slots to waiting streams — highest PR-8 priority
        class first, FIFO within a class (lock held).  Returns the
        joined streams (their pages reset OUTSIDE the lock)."""
        joined = []
        free = [i for i, oc in enumerate(self._occupants) if oc is None]
        if not free or not self._waiting:
            return joined
        order = sorted(
            range(len(self._waiting)),
            key=lambda i: (-self._waiting[i].priority, i),
        )
        winners = sorted(order[: len(free)])  # FIFO among the admitted
        for slot, wi in zip(free, winners):
            s = self._waiting[wi]
            s.slot = slot
            s.state = "prefill"
            s.joined_ts = now
            s.last_token_ts = now
            self._occupants[slot] = s
            self.joins += 1
            self.admit_wait_s += now - s.submitted_ts
            if armed():
                t1 = time.perf_counter()
                record("nns.gen.admit_wait", t1 - (now - s.submitted_ts),
                       t1, request=s.sid)
            joined.append(s)
        taken = set(winners)
        self._waiting = [
            w for i, w in enumerate(self._waiting) if i not in taken
        ]
        return joined

    def _pump(self) -> None:
        name_os_thread()
        try:
            self._pump_loop()
        except BaseException as e:  # noqa: BLE001 — thread boundary
            with self._lock:
                self._error = e
                self._progress.notify_all()
            if not self._stop.is_set():
                log.exception("%s: slot pump failed", self.name)

    def _pump_loop(self) -> None:
        while not self._stop.is_set():
            self.heartbeat.beat()
            t0 = self.clock()
            self._dev_s = self._idle_s = 0.0
            # one span per turn, parent of every phase below: while a
            # profiler session is live the pump thread always has a
            # span open, so a device gap is named by the phase it fell in
            with span("nns.slots.turn"):
                self._turn()
            self.pump_host_s += max(
                0.0, self.clock() - t0 - self._dev_s - self._idle_s)
        # stopping: what the chip was still computing is read back
        self._drain()

    def _decodes(self, s: Optional[GenStream]) -> bool:
        """Whether a scan dispatched now would run ``s`` (lock held): it
        decodes and some of its tokens are not dispatched yet."""
        return (s is not None and s.state == "decoding" and not s.finished
                and s.dispatched < s.max_new)

    def _left_to_dispatch(self) -> bool:
        """Whether a later turn has anything to dispatch (lock held): a
        request waiting, a prompt prefilling, a stream with tokens to go."""
        return bool(self._waiting) or any(
            s is not None and not s.finished
            and (s.state == "prefill" or self._decodes(s))
            for s in self._occupants)

    def _turn(self) -> None:
        """One iteration of the pump: admit; dispatch at most the lane's
        share of prefill chunks and the next decode scan; then wait for
        the scan dispatched a turn earlier, read it back and emit it.  The
        chip has the new scan to run while the host copies, emits and
        admits."""
        np = self._np

        if self._goaway:
            # a handoff carries every token the chip has made
            self._drain()
        with span("nns.slots.admit"), self._work:
            self._reap_cancelled()
            if self._goaway:
                self._sweep_goaway()
            self._sweep_deadlines(self.clock())
            self._join_waiting(self.clock())
            prefilling = [
                s for s in self._occupants
                if s is not None and s.state == "prefill"
                and not s.finished
            ]
            have_decode = any(self._decodes(s) for s in self._occupants)
            idle = not (prefilling or have_decode)
            if idle and self._inflight is None and not self._picked:
                # the one sleep of the thread that feeds the device:
                # named as a wait, so an idle chip reads "no request"
                t0 = self.clock()
                # no dispatch is coming to announce the ready outputs
                if self._wake_due and armed():
                    self._stamp_announced()
                self._wake()
                with span("nns.slots.wait_request"):
                    self._work.wait(0.05)
                self._idle_s += self.clock() - t0
                return
        if idle:
            # nothing to dispatch behind what is in flight: read it back
            # now, so that a stream's last frame never waits for a request
            self._drain()
            return

        # ---- prefill phase: while decoding, up to prefill_priority
        # chunks interleave per scan (a long prompt never stalls
        # live streams for more than that); with the decode batch
        # EMPTY there is nothing to protect — run every pending
        # joiner's next chunk so the batch fills immediately
        budget = (self.prefill_priority if have_decode
                  else max(1, len(prefilling)))
        try:
            for s in prefilling:
                if budget <= 0:
                    break
                budget -= 1
                self._prefill_one(s)
        except DeviceOomError:
            # prefill state is re-entrant (prefill_pos advanced only
            # on success): read back what is in flight, shed a slot and
            # re-run next iteration
            self._drain()
            self._handle_oom()
            self._recover_donated_cache()
            return
        except DeviceLostError as e:
            self._drain(lost=True)
            self._handle_device_lost(e)
            return

        # ---- decode phase: k tokens for every active slot in ONE
        # lax.scan dispatch (k = min(chunk, min remaining), so every
        # stream completes exactly at a scan boundary and joins/
        # leaves happen at token boundaries); what a stream has left is
        # counted from its dispatched tokens, so the scan is composed
        # before the one in flight is read back
        with self._lock:
            decoding = [s for s in self._occupants if self._decodes(s)]
            left = self._left_to_dispatch()
        if not decoding and self._inflight is None:
            if self._picked and not left:
                self._drain()  # first tokens no scan will follow
            return
        k = max(1, min([self.chunk]
                       + [s.max_new - s.dispatched for s in decoding]))
        with span("nns.slots.decode", k=k if decoding else 0,
                  active=len(decoding)):
            scan = None
            if decoding:
                active = np.zeros((self.slots,), np.int32)
                for s in decoding:
                    active[s.slot] = 1
                try:
                    with span("nns.slots.decode.dispatch"):
                        # a model with counters of its own hands them over
                        # as a fifth result
                        cache, tok, gen, toks, *counts = self._device_step(
                            self._decode_fn(k),
                            self.params, self._cache, self._tok_vec,
                            self._gen_vec, active,
                        )
                except DeviceOomError:
                    # the step raised before any state assignment: read
                    # back what is in flight, shed the lowest-priority slot
                    # (its tokens survive as a resumable chunk) and retry
                    # on the smaller batch
                    self._drain()
                    self._handle_oom()
                    self._recover_donated_cache()
                    return
                except DeviceLostError as e:
                    self._drain(lost=True)
                    self._handle_device_lost(e)
                    return
                scan = _Scan(k, decoding, toks, counts, self._tok_vec,
                             self._picked)
                self._cache, self._tok_vec, self._gen_vec = cache, tok, gen
                self._picked = []
                for s in decoding:
                    s.dispatched += k
                if self._inflight is not None:
                    self.decode_ahead += 1
            done = [] if self._inflight is None else [self._inflight]
            self._inflight = scan
            with self._lock:
                left = self._left_to_dispatch()
            if not left:
                # nothing to dispatch behind this scan: read it back too
                done += self._take_outstanding()
            got = [self._read(rec) for rec in done]
        with span("nns.slots.emit"), self._lock:
            for rec, host in zip(done, got):
                self._apply(rec, *host)

    def _take_outstanding(self) -> List[_Scan]:
        """Everything dispatched and not read back, in dispatch order: the
        scan in flight and first tokens no scan has followed yet."""
        out = [] if self._inflight is None else [self._inflight]
        if self._picked:
            out.append(_Scan(0, [], None, (), self._tok_vec, self._picked))
        self._inflight, self._picked = None, []
        return out

    def _drain(self, lost: bool = False) -> None:
        """Read back and emit everything dispatched and not yet read, so
        that an idle wait, a GOAWAY sweep, a recovery and a stop each find
        every token the chip has made on the host.  ``lost``: the device
        may have taken what was in flight with it; what cannot be read is
        dropped (the streams are handed off with what they hold)."""
        recs = self._take_outstanding()
        if not recs:
            return
        with span("nns.slots.decode", drain=True):
            try:
                got = [self._read(rec) for rec in recs]
            except Exception:  # noqa: BLE001 — the lost device's results
                if not lost:
                    raise
                log.warning("%s: the work in flight was lost with the "
                            "device; handing off from what was read back",
                            self.name, exc_info=True)
                return
        with span("nns.slots.emit"), self._lock:
            for rec, host in zip(recs, got):
                self._apply(rec, *host)

    def _read(self, rec: _Scan):
        """Wait for one dispatched record and copy what it made to the
        host: the scan's tokens and the model's counters, and the token
        vector its first tokens were seated in.  Returns ``(toks, counts,
        firsts, now)``."""
        np = self._np
        scan = rec.toks is not None
        with span("nns.slots.decode.sync") as sp, self._on_device():
            if sp.live:
                # traced, the wait for the scan alone; the copies below
                # are then the read-back (untraced, the first np.asarray
                # waits as it always did)
                _block(rec.toks if scan else rec.tok_in)
            # materialize BEFORE emission: a yielded token must EXIST, not
            # merely be dispatched (generator element contract)
            with span("nns.slots.readback",
                      what="scan" if scan else "first_token",
                      arrays=scan + bool(rec.counts) + bool(rec.firsts)):
                toks = np.asarray(rec.toks) if scan else None  # (slots, k)
                counts = (np.asarray(rec.counts[0]).tolist() if rec.counts
                          else ())
                firsts = np.asarray(rec.tok_in) if rec.firsts else None
        return toks, counts, firsts, self.clock()

    def _apply(self, rec: _Scan, toks_host, counts, firsts,
               now: float) -> None:
        """Hand one read-back to its streams (lock held), in dispatch
        order: first tokens, then the scan's ``k`` tokens a stream.  A
        stream that finished while they were computed (cancelled,
        evicted, handed off) has them discarded."""
        np = self._np
        for s in rec.firsts:
            if not s.finished:
                self._first_token(s, int(firsts[s.slot]), now)
        if toks_host is None:
            return
        k, decoding = rec.k, rec.streams
        self.decode_steps += 1
        self.tokens_total += k * len(decoding)
        for name, n in zip(self.model.counter_names, counts):
            self.model_counts[name] += n
        a = 0.2  # EWMA horizon ~ last 5 scans
        self.tokens_per_step = (
            len(decoding) if self.decode_steps == 1
            else (1 - a) * self.tokens_per_step + a * len(decoding)
        )
        for s in decoding:
            if s.finished:  # cancelled mid-scan: tokens discarded
                continue
            row = toks_host[s.slot:s.slot + 1, :]  # (1, k)
            s.tok = int(row[0, -1])
            s.gen += k
            # per-token pace QoS: the scan's OWN per-token rate
            # against the stream's budget — a stream decoding
            # slower than its pace is evicted (tokens from this
            # scan are preserved in the typed-expiry flush)
            pace_blown = (
                s.token_budget_s > 0.0
                and (now - s.last_token_ts) / k > s.token_budget_s
            )
            # SLO per-token inter-arrival: the scan's k tokens
            # as k observations of the same pace — one bucket
            # increment, reusing the pace sweep's clock reads
            if self.slo is not None:
                self.slo.note_tokens(
                    s.tenant, max(0.0, now - s.last_token_ts), k)
            s.last_token_ts = now
            s.pending.append(row.astype(np.int32))
            s.pending_n += k
            if s.gen >= s.max_new:
                self._finish(s, "done")
            elif pace_blown:
                self._evict(s, "token_budget")
            else:
                self._emit_boundary(s)

    def _first_token(self, s: GenStream, t: int, now: float) -> None:
        """Token 1 of a stream, read back (lock held)."""
        np = self._np
        s.tok = t
        s.gen = 1
        self.tokens_total += 1  # token 1 comes from the prefill pick
        # SLO TTFT: the promised one-stamp-per-first-token — resumed
        # streams have none (their first token predates this server)
        if self.slo is not None:
            self.slo.note_ttft(s.tenant, max(0.0, now - s.submitted_ts))
        s.pending.append(np.array([[t]], np.int32))
        s.pending_n += 1
        if s.gen >= s.max_new:
            self._finish(s, "done")
        else:
            self._emit_boundary(s)

    def _seat(self, slot: int, first, n: int) -> None:
        """Write a slot's next decode input, token ``first`` (1,) at count
        ``n``, into the vectors the next scan takes, where they are: into
        copies on the host where the vectors and the token are host arrays,
        by one small donated program on the device otherwise."""
        np = self._np
        if isinstance(first, np.ndarray) and isinstance(
                self._tok_vec, np.ndarray):
            tok, gen = self._tok_vec.copy(), self._gen_vec.copy()
            tok[slot], gen[slot] = first[0], n
        else:
            tok, gen = _seat_program()(
                self._tok_vec, self._gen_vec, np.int32(slot), first,
                np.int32(n))
        self._tok_vec, self._gen_vec = tok, gen

    # -- shared-prefix cache (attach on join, publish at boundaries) --------
    def _attach_prefix(self, s: GenStream, slot: int) -> None:
        """First-touch lookup (pump thread, right after the slot reset):
        digest the prefill source at grain boundaries, pin the longest
        cached run, and write its pages into the slot — prefill then
        starts at the first uncached token instead of token 0.

        The attach is capped at ``tp - 1`` chunks' worth so at least the
        final prompt token always prefills (its logits feed the
        unchanged token-1 pick).  RESUME joins share the path: their
        ``prefill_src`` starts with the same prompt bytes, so a resumed
        stream landing on a warm server skips the prefix too — and on a
        cache-COLD server simply prefills everything, bit-identically
        (the cache changes WHERE prefill starts, never what any chunk
        computes)."""
        pc = self.prefix
        tp = int(s.prefill_src.shape[1])
        max_chunks = (tp - 1) // pc.grain
        if max_chunks <= 0:
            return  # too short to share: neither a hit nor a miss
        s.prefix_digests = prefix_digests(
            s.prefill_src, pc.grain)[:max_chunks]
        entries = pc.acquire(s.prefix_digests)
        s.prefix_pub_i = len(entries)
        if not entries:
            return
        n = sum(e.tokens for e in entries)
        self._cache = self.model.attach_prefix(
            self._cache, slot, [e.pages for e in entries], n)
        s.prefix_entries = entries
        s.prefill_pos = n

    def _publish_prefix(self, s: GenStream, slot: int) -> None:
        """After each prefill chunk: when ``prefill_pos`` lands exactly
        on the next unpublished grain boundary, export that chunk's
        pages (a copy — donation-safe) and publish them under its chain
        digest.  The boundary moment is guaranteed to occur for every
        eligible chunk because the grain is a prefill_chunk multiple
        (and the sim twin's cumulative carry is only correct AT the
        boundary)."""
        pc = self.prefix
        g = pc.grain
        while s.prefix_pub_i < len(s.prefix_digests):
            i = s.prefix_pub_i
            if (i + 1) * g != s.prefill_pos:
                return  # boundary not (yet) reached this chunk
            d = s.prefix_digests[i]
            if not pc.contains(d):
                pages = self.model.export_prefix(
                    self._cache, slot, i * g, (i + 1) * g)
                pc.publish(d, i, pages, g)
            s.prefix_pub_i += 1

    def _prefill_one(self, s: GenStream) -> None:
        """One chunked-prefill step for a joining stream: reset pages on
        first touch, run one chunk, pick token 1 when the prompt is
        done.  Device work runs OUTSIDE the lock.

        RESUME joins prefill ``prefill_src`` = prompt + generated
        prefix[:-1] through the SAME buckets — the cache after the
        prefill is bit-identical to the incremental decode that built
        it on the dead server — then skip the pick entirely: the next
        decode input is the prefix's LAST token at absolute step
        ``resume_gen``, both known from the checkpoint."""
        np = self._np

        slot = np.int32(s.slot)
        dev0 = self._dev_s
        try:
            self._prefill_step(s, slot, dev0)
        finally:
            s.own_s += self._dev_s - dev0

    def _prefill_step(self, s: GenStream, slot, dev0: float) -> None:
        np = self._np

        if s.prefill_pos == 0:
            with span("nns.slots.reset", request=s.sid), self._on_device():
                self._cache = self.model.reset_slot(self._cache, slot)
                if self.prefix is not None:
                    self._attach_prefix(s, int(s.slot))
            if s.prefill_pos >= s.prefill_src.shape[1]:
                # defensive: attach is capped at tp-1, so the final
                # prompt token (whose logits pick token 1) always
                # prefills — this branch is unreachable by design
                raise AssertionError(
                    "prefix attach covered the whole prompt")
        tp = s.prefill_src.shape[1]
        n = min(self.prefill_chunk, tp - s.prefill_pos)
        with span("nns.slots.prefill", request=s.sid, pos=s.prefill_pos,
                  n=n):
            toks = s.prefill_src[:, s.prefill_pos:s.prefill_pos + n].astype(
                np.int32)
            self._cache, logits = self._device_step(
                self._prefill_fn(n), self.params, self._cache, toks, slot)
            s.prefill_pos += n
            if self.prefix is not None:
                self._publish_prefix(s, int(s.slot))
            with self._lock:
                self.prefill_chunks += 1
                self.prefill_tokens += n
                for name, c in self.model.prefill_counts(
                        s.prefill_pos - n, n).items():
                    self.model_counts[name] += c
            if s.prefill_pos < tp:
                return
            if s.resume_gen:
                # checkpointed restart: no pick, no token-1 emission — the
                # client already holds tokens 1..resume_gen
                with self._on_device():
                    self._seat(s.slot, np.array([s.resume_tok], np.int32),
                               s.resume_gen)
                now = self.clock()
                with self._lock:
                    if s.finished:  # cancelled/handed off during prefill
                        return
                    s.tok = s.resume_tok
                    s.gen = s.dispatched = s.resume_gen
                    s.last_token_ts = now
                    if s.resume_gen >= s.max_new:
                        self._finish(s, "done")  # defensive: nothing left
                    else:
                        s.state = "decoding"
                return
            # prompt fully prefilled: pick token 1 (raw gen_seed key — the
            # exact pick the unslotted prefill applies) and seat it where
            # the next scan takes it; its host value is read back with
            # that scan
            with self._on_device():
                self._seat(s.slot, self.model.pick_first(logits), 1)
            now = self.clock()
            with self._lock:
                if s.finished:  # cancelled during prefill
                    return
                # lane wait: join to this pick, less the seconds the pump
                # spent in this stream's own reset and prefill steps — the
                # rest is the stream waiting for its turn in the lane
                own = s.own_s + (self._dev_s - dev0)
                lane = max(0.0, now - s.joined_ts - own)
                self.first_tokens += 1
                self.lane_wait_s += lane
                if armed():
                    t_end = time.perf_counter()
                    record("nns.gen.lane_wait", t_end - lane - own, t_end,
                           request=s.sid, own_s=own)
                # the pace of the stream's first scan runs from its pick
                s.last_token_ts = now
                s.dispatched = 1
                s.state = "decoding"
                self._picked.append(s)
