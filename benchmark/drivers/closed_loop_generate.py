"""Traffic kind ``closed_loop_generate``: ``callers`` clients in this process,
each with a ``tensor_query_client`` of its own, send prompts to one
``tensor_generator`` server; a caller sends its next prompt when its last
answer is complete.

The mix's parameters (``traffic/<mix>.json``): ``callers``, the ladder
``prompt_lens`` (caller i starts at rung i and walks it, so every run offers
the same lengths in the same order to every caller), ``warm_prompt_lens``
(the least that touches each program once), ``ramp_s`` of closed-loop traffic
before the window opens.  The seed chooses token ids and weights, never the
amount of work.

What it measures at the clients, over all requests and all of the window:
``gen_gap_p95_ms`` (gap between successive token frames of one request over
the tokens of the later frame, every gap whose later frame arrived inside the
window), ``gen_tokens_per_s`` (generated tokens that reached a client inside
the window over its seconds), ``gen_ttft_p90_ms`` (send to first token frame,
every request sent inside the window; the run drains them after the window's
end, and a failed request counts as the request timeout).  Which of them a
cell holds to a bound is BENCHMARK.json's choice; the others are read as
per-layer metrics (``readers/driver_value.py``).
"""

from __future__ import annotations

import itertools
import threading
import time

import numpy as np

from .. import harness, work

_SID = itertools.count(41)


def prompts(mix, vocab, seed, caller):
    """The prompts of caller ``caller``, without end: lengths walk the ladder
    from rung ``caller`` on (the same for every seed), token ids are drawn
    uniformly over the vocabulary from (seed, caller).  ``shared_head`` tokens
    (0 unless the mix says otherwise), the same for every caller and request,
    come before each prompt's own tokens."""
    rng = np.random.default_rng([int(seed), int(caller)])
    head = np.random.default_rng([int(seed), 1 << 20]).integers(
        0, vocab, (1, mix.get("shared_head", 0)))
    ladder = mix["prompt_lens"]
    for rung in itertools.count(caller):
        own = rng.integers(0, vocab, (1, ladder[rung % len(ladder)]))
        yield np.concatenate([head, own], axis=1).astype(np.int32)


class Request:
    __slots__ = ("caller", "n", "prompt", "t_send", "frames", "tokens", "done", "failed")

    def __init__(self, caller, n, prompt):
        self.caller, self.n, self.prompt = caller, n, prompt
        self.t_send = None
        self.frames = []     # (arrival time, tokens in the frame)
        self.tokens = []
        self.done = threading.Event()
        self.failed = None


class Caller:
    """One closed-loop client: a persistent client pipeline and the walk
    along the ladder that belongs to caller ``i``."""

    def __init__(self, i, session, port):
        from nnstreamer_tpu.pipeline import parse_pipeline

        self.i, self.s = i, session
        w = session.cell.workload
        self.pipe = parse_pipeline(
            w["client"].format(port=port, timeout=w["request_timeout_s"]),
            name=f"bench-caller-{i}")
        self.pipe["out"].connect_new_data(self._on_frame)
        self.pipe.start()
        self.walk = prompts(session.mix, session.cfg["vocab"], session.seed, i)
        self.warm_rng = np.random.default_rng([session.seed, i, 1])
        self.current = None
        self.sent = 0
        self.thread = threading.Thread(target=self._loop, name=f"bench-caller-{i}", daemon=True)

    def _on_frame(self, fr):
        req, now = self.current, time.perf_counter()
        if req is None:
            return
        if fr.tensors:
            toks = np.asarray(fr.tensors[0]).reshape(-1)
            req.frames.append((now, len(toks)))
            req.tokens.extend(int(t) for t in toks)
        if fr.meta.get("evicted"):
            req.failed = "evicted"
        if fr.meta.get("final"):
            req.done.set()

    def send(self, prompt):
        req = Request(self.i, self.sent, prompt)
        self.sent += 1
        self.current = req
        self.s.requests.append(req)
        req.t_send = time.perf_counter()
        self.pipe["src"].push(prompt)
        if not req.done.wait(self.s.cell.workload["request_timeout_s"]):
            req.failed = "timeout"
        elif self.pipe.errors:
            req.failed = f"client error: {self.pipe.errors[0]}"
        return req

    def _loop(self):
        while not self.s.closing.is_set():
            if self.send(next(self.walk)).failed:
                break

    def close(self):
        self.pipe.stop()


class Session:
    def __init__(self, cell, seed, phases):
        self.cell, self.seed, self.phases = cell, seed, phases
        self.cfg, self.mix = cell.config, cell.traffic
        self.requests = []
        self.closing = threading.Event()
        self.server = None
        self.callers = []

    # -- set-up ---------------------------------------------------------------
    def setup(self):
        from nnstreamer_tpu.native import runtime as native
        from nnstreamer_tpu.pipeline import parse_pipeline

        w = self.cell.workload
        if self.mix["callers"] != w["slots"]:
            raise harness.CellError("closed_loop_generate: callers must equal slots")
        if (self.mix.get("shared_head", 0) + max(self.mix["prompt_lens"]) + w["max_new"]
                > self.cfg["seq"]):
            raise harness.CellError("longest prompt + max_new exceeds the model's positions")
        native.available()
        self.phases.mark("native mailbox", mailbox=native.mailbox_impl())
        custom = w["custom"].format(seed=harness.model_seed(self.seed), **self.cfg)
        sid = next(_SID)
        self.server = parse_pipeline(
            w["server"].format_map({**w, "custom": custom, "sid": sid}), name="bench-gen-server")
        self.server.start()
        port = self.server["ssrc"].props["port"]
        self.phases.mark("parameters, KV cache and server start")
        self.callers = [Caller(i, self, port) for i in range(self.mix["callers"])]
        self.phases.mark("client pipelines", callers=len(self.callers))
        # warm: one request per program-touching length, one after another so
        # that each phase row is one program family
        for c, n in zip(self.callers, self.mix["warm_prompt_lens"]):
            req = c.send(c.warm_rng.integers(
                0, self.cfg["vocab"], (1, n)).astype(np.int32))
            if req.failed:
                raise RuntimeError(f"warm request of {n} tokens failed: {req.failed}")
            self.phases.mark(f"warm request, prompt {n}")
        self.requests.clear()
        for c in self.callers:
            c.sent = 0
            c.thread.start()
        time.sleep(self.mix["ramp_s"])
        self.phases.mark("ramp, closed loop", requests=len(self.requests))

    def counters(self):
        h = self.server.health()[self.cell.workload["generator"]]
        # every count the element reports (the engine's, and the prefix
        # pool's where it is armed), so a later metric needs no edit here
        return {k: v for k, v in h.items()
                if isinstance(v, (int, float)) and not isinstance(v, bool)}

    # -- the window -------------------------------------------------------------
    def window(self, seconds, tracer=None):
        self.t0 = time.perf_counter()
        self.c0 = self.counters()
        if tracer:
            tracer.start()
        time.sleep(max(0.0, self.t0 + seconds - time.perf_counter()))
        self.c1 = self.counters()
        self.t1 = time.perf_counter()
        self.closing.set()
        if tracer:
            tracer.join()
        # drain: every request sent inside the window gets its answer; a
        # late one is late (its latency counts the wait), not lost
        deadline = time.perf_counter() + self.cell.workload["drain_s"]
        for c in self.callers:
            c.thread.join(max(0.1, deadline - time.perf_counter()))
        for r in self.requests:
            if not r.done.is_set() and not r.failed:
                r.failed = "not answered within the drain"
        self.server_errors = list(self.server.errors)
        return self.t0

    def _sent_in_window(self):
        return [r for r in self.requests if self.t0 <= r.t_send <= self.t1]

    def end_to_end(self):
        t0, t1 = self.t0, self.t1
        tokens = sum(n for r in self.requests for t, n in r.frames if t0 < t <= t1)
        gaps = [(b[0] - a[0]) / b[1] * 1e3
                for r in self.requests for a, b in zip(r.frames, r.frames[1:])
                if t0 < b[0] <= t1]
        worst = self.cell.workload["request_timeout_s"] * 1e3
        ttft = [worst if (r.failed or not r.frames) else (r.frames[0][0] - r.t_send) * 1e3
                for r in self._sent_in_window()]
        self.samples = {"gaps": len(gaps), "ttft": len(ttft)}
        return {"gen_tokens_per_s": tokens / (t1 - t0),
                "gen_gap_p95_ms": harness.percentile(gaps, 95),
                "gen_ttft_p90_ms": harness.percentile(ttft, 90)}

    def attempted_failed(self):
        sent = self._sent_in_window()
        return len(sent), sum(1 for r in sent if r.failed)

    def tokens_between(self, ta, tb):
        """What reached the clients in (ta, tb]: prompts whose first token
        frame arrived (their prefill is then done), decode tokens (all but a
        request's first, which the prefill picks), and the filled positions
        those decode tokens attended over, summed."""
        prompts, decode_tokens, filled = [], 0, 0
        for r in self.requests:
            n, done = r.prompt.shape[1], 0
            for k, (t, ntok) in enumerate(r.frames):
                if ta < t <= tb:
                    if k == 0:
                        prompts.append(n)
                    first = 1 if k == 0 else 0        # the prefill's pick
                    for j in range(done + first, done + ntok):
                        decode_tokens += 1
                        filled += n + j     # its step attends over the prompt and the j tokens before it
                done += ntok
        return {"prompts": prompts, "decode_tokens": decode_tokens, "filled": filled}

    def work_units(self, ta, tb, c0, c1):
        w = self.cell.workload
        units = self.tokens_between(ta, tb)
        units["steps"] = work.gpt_decode_steps(
            c1["gen_decode_steps"] - c0["gen_decode_steps"],
            c1["gen_completed"] - c0["gen_completed"], w["chunk"], w["max_new"])
        return units

    def facts(self):
        return {"window_s": self.t1 - self.t0, "requests": len(self.requests),
                "sent_in_window": len(self._sent_in_window()),
                "finished": sum(1 for r in self.requests if r.done.is_set() and not r.failed),
                "samples": getattr(self, "samples", {}),
                "slots": self.cell.workload["slots"], "chunk": self.cell.workload["chunk"],
                "max_new": self.cell.workload["max_new"]}

    def close(self):
        for c in self.callers:
            c.close()
        if self.server is not None:
            self.server.stop()
        self.server, self.callers = None, []

    # -- correct ------------------------------------------------------------------
    def compare(self, control=None):
        """A sample, drawn from the seed, of the requests the window finished,
        the longest prompt among them in it: the float32 reference runs once
        over each prompt with its served tokens, and every served token's
        logit is read against the reference's best at that position, as a
        share of the position's logit spread."""
        w = self.cell.workload
        limits = w["compare"]
        done = [r for r in self.requests
                if r.done.is_set() and not r.failed and r.frames
                and self.t0 < r.frames[-1][0] <= self.t1]
        bad = sum(1 for r in self.requests if r.failed) + len(self.server_errors)
        compared = {"requests_failed": {"value": float(bad), "limit": 0}}
        if not done:
            compared["finished_in_window"] = {"value": None, "limit": 0}
            return compared, None
        rng = np.random.default_rng(self.seed + 1)
        longest = max(done, key=lambda r: (r.prompt.shape[1], -r.t_send))
        rest = [r for r in done if r is not longest]
        k = min(limits["sample"] - 1, len(rest))
        picks = [longest] + [rest[i] for i in sorted(rng.choice(len(rest), k, replace=False))]
        ref = harness.load_module("configs", self.cfg["reference"])
        t_ref = time.perf_counter()
        params = ref.make_params(self.cfg, harness.model_seed(self.seed))
        length = self.mix.get("shared_head", 0) + max(self.mix["prompt_lens"]) + w["max_new"]
        gaps, low_gaps, short = [], [], 0
        for r in picks:
            n = r.prompt.shape[1]
            served = np.asarray(r.tokens, np.int64)
            short += abs(len(served) - w["max_new"])
            seq = np.zeros((length,), np.int32)
            seq[:n] = r.prompt[0]
            seq[n:n + len(served)] = served[:length - n]
            logits = np.asarray(ref.forward(params, seq, self.cfg))[n - 1:n - 1 + len(served)]
            spread = logits.max(-1) - logits.min(-1)
            ok = (served >= 0) & (served < logits.shape[1])
            at = logits[np.arange(len(served)), np.clip(served, 0, logits.shape[1] - 1)]
            gaps.extend(np.where(ok, (logits.max(-1) - at) / spread, 1e30).tolist())
            if control:
                low = np.asarray(ref.forward(params, seq, self.cfg, precision=control))
                pick = low[n - 1:n - 1 + len(served)].argmax(-1)
                at = logits[np.arange(len(served)), pick]
                low_gaps.extend(((logits.max(-1) - at) / spread).tolist())
        compared.update({
            "tokens_missing": {"value": float(short), "limit": 0},
            "token_gap_max": {"value": float(max(gaps)), "limit": limits["token_gap_max"]},
            "token_gap_mean": {"value": float(np.mean(gaps)), "limit": limits["token_gap_mean"]},
        })
        ctl = None
        if control:
            ctl = {"token_gap_max": float(max(low_gaps)),
                   "token_gap_mean": float(np.mean(low_gaps))}
        self.check_detail = {"requests": len(picks), "tokens": len(gaps),
                             "reference_and_control_s": time.perf_counter() - t_ref}
        return compared, ctl
