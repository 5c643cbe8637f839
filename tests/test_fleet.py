"""Fleet overload resilience: load-aware routing, consistent-hash
affinity, per-tenant admission, discovery-plane health, and the scripted
fleet chaos e2e (the acceptance contract of the fleet arc).

Covers, per Documentation/resilience.md "Fleet overload & tenancy":

* routing policy ranking (`rotate` | `least-inflight` | `ewma`) with the
  selection-side breaker guard: an OPEN-breaker remote is NEVER ranked
  ahead of a closed-breaker alternative, no matter how good its load
  signal looks — and EWMA rows evicted by `_rediscover` are never
  consulted again (both PR-7-era gaps, pinned here);
* rendezvous-hash affinity: fairness within ±25% of uniform across 8
  servers, and provably-minimal remapping on join/leave;
* the per-tenant shed truth table: quota, priority ordering, retry-after
  pacing, breaker-immunity of tenant-quota BUSY;
* discovery-plane health propagation (draining announce -> client
  deprioritization before any GOAWAY round trip);
* sustained tenant-quota shed -> rate-limited flight-recorder incident;
* the chaos e2e: 3 tcp servers under continuous 2-tenant load survive
  scripted kill + rolling restart + server join with zero lost or
  duplicated frames, exact per-tenant accounting, zero breaker trips
  from drains, bounded affinity remaps, and a hot-tenant burst that
  sheds ONLY the hot tenant.
"""

import math
import os
import sys
import time
from collections import Counter
from pathlib import Path

import numpy as np
import pytest

from nnstreamer_tpu.core import routing
from nnstreamer_tpu.core.continuity import PREFIX_GRAIN, prefix_route_key
from nnstreamer_tpu.core.liveness import (
    ServerBusyError,
    TenantAdmissionController,
    parse_tenant_quotas,
)
from nnstreamer_tpu.core.resilience import (
    CircuitBreaker,
    is_remote_application_error,
)
from nnstreamer_tpu.pipeline.element import make_element
from nnstreamer_tpu.pipeline.parser import parse_pipeline

sys.path.insert(0, str(Path(__file__).parent.parent / "tools"))


# ---------------------------------------------------------------------------
# Rendezvous-hash affinity: fairness + minimal remapping (property-style)
# ---------------------------------------------------------------------------
class TestRendezvousAffinity:
    KEYS = [f"sess-{i}" for i in range(2000)]
    FLEET8 = [(f"10.0.0.{i}", 7000 + i) for i in range(8)]

    def test_deterministic(self):
        t = self.FLEET8
        assert [routing.rendezvous_owner(k, t) for k in self.KEYS[:50]] == [
            routing.rendezvous_owner(k, t) for k in self.KEYS[:50]
        ]

    def test_fairness_within_25pct_of_uniform_across_8_servers(self):
        owners = Counter(
            routing.rendezvous_owner(k, self.FLEET8) for k in self.KEYS)
        ideal = len(self.KEYS) / len(self.FLEET8)
        assert set(owners) == set(range(8)), "every server owns keys"
        for i, n in owners.items():
            assert 0.75 * ideal <= n <= 1.25 * ideal, (
                f"server {i} owns {n} keys (ideal {ideal:.0f} +/- 25%)")

    def test_join_remaps_only_what_the_newcomer_wins(self):
        """Adding one server moves EXACTLY the keys the newcomer now
        owns — every other key keeps its owner (minimal remapping), and
        the moved fraction is ~1/N (within the fairness tolerance)."""
        before = routing.ownership_map(self.KEYS, self.FLEET8)
        grown = self.FLEET8 + [("10.0.0.8", 7008)]
        after = routing.ownership_map(self.KEYS, grown)
        moved = [k for k in self.KEYS if before[k] != after[k]]
        for k in moved:
            assert grown[after[k]] == ("10.0.0.8", 7008), (
                "a key may only move TO the joining server")
        assert len(moved) <= math.ceil(1.25 * len(self.KEYS) / len(grown))

    def test_leave_remaps_only_the_departed_servers_keys(self):
        before = routing.ownership_map(self.KEYS, self.FLEET8)
        survivors = self.FLEET8[:3] + self.FLEET8[4:]  # drop index 3
        after = routing.ownership_map(self.KEYS, survivors)
        for k in self.KEYS:
            if before[k] != 3:
                # survivors' keys keep their owner (compare by endpoint,
                # indices shift after the removal)
                assert self.FLEET8[before[k]] == survivors[after[k]]
        departed = [k for k in self.KEYS if before[k] == 3]
        moved = [
            k for k in self.KEYS
            if self.FLEET8[before[k]] != survivors[after[k]]
        ]
        assert sorted(moved) == sorted(departed)
        assert len(moved) <= math.ceil(
            1.25 * len(self.KEYS) / len(self.FLEET8))


# ---------------------------------------------------------------------------
# Prefix-affinity routing (PR 18): the remap math over REAL prefix
# digests, and the tier discipline for a draining/degraded prefix owner
# ---------------------------------------------------------------------------
class TestPrefixAffinityRouting:
    FLEET8 = [(f"10.0.0.{i}", 7000 + i) for i in range(8)]

    @staticmethod
    def _digest_keys(n=600, seed=5):
        """Route keys as the query client computes them: grain-aligned
        chain digests of synthetic prompts (not opaque session strings —
        the remap math must hold over the ACTUAL key distribution)."""
        rng = np.random.default_rng(seed)
        return [
            prefix_route_key(
                rng.integers(0, 997, (1, PREFIX_GRAIN + 17)).astype(
                    np.int32))
            for _ in range(n)
        ]

    def test_shared_prefix_maps_to_one_owner_distinct_prefixes_spread(
            self):
        """The tentpole's routing premise: clients sharing a prompt
        prefix compute the SAME route key (suffix divergence past the
        first grain is invisible to it) and so land on the one server
        whose prefix KV pages are warm, while distinct prefixes spread
        across the fleet."""
        rng = np.random.default_rng(7)
        base = rng.integers(0, 997, (1, PREFIX_GRAIN + 40)).astype(
            np.int32)
        fork = base.copy()
        fork[0, PREFIX_GRAIN + 5] ^= 1  # diverge AFTER the first grain
        assert prefix_route_key(base) == prefix_route_key(fork)
        other = base.copy()
        other[0, 3] ^= 1                # diverge INSIDE the prefix
        assert prefix_route_key(base) != prefix_route_key(other)
        owners = Counter(
            routing.rendezvous_owner(k, self.FLEET8)
            for k in self._digest_keys())
        assert set(owners) == set(range(8)), (
            "distinct prefixes must spread over every server")

    def test_join_steals_only_the_prefix_digests_the_newcomer_wins(self):
        """Minimal remap over prefix digests: a scale-up invalidates
        ONLY the warm prefix pages for keys the newcomer now owns
        (~1/N of them) — every other key keeps its warm server."""
        keys = self._digest_keys()
        before = routing.ownership_map(keys, self.FLEET8)
        grown = self.FLEET8 + [("10.0.0.8", 7008)]
        after = routing.ownership_map(keys, grown)
        moved = [k for k in keys if before[k] != after[k]]
        for k in moved:
            assert grown[after[k]] == ("10.0.0.8", 7008), (
                "a prefix digest may only move TO the joining server")
        assert len(moved) <= math.ceil(1.35 * len(keys) / len(grown))

    def test_leave_moves_exactly_the_departed_servers_digests(self):
        """A scale-down re-homes EXACTLY the departed server's prefix
        digests; every surviving server keeps its warm set bit-for-bit
        (compare by endpoint — indices shift after the removal)."""
        keys = self._digest_keys()
        before = routing.ownership_map(keys, self.FLEET8)
        survivors = self.FLEET8[:3] + self.FLEET8[4:]  # drop index 3
        after = routing.ownership_map(keys, survivors)
        departed = [k for k in keys if before[k] == 3]
        moved = [
            k for k in keys
            if self.FLEET8[before[k]] != survivors[after[k]]
        ]
        assert sorted(moved) == sorted(departed)

    def test_draining_owner_fails_over_in_tier_without_remap_thrash(
            self):
        """A draining (then degraded) prefix owner's traffic fails over
        to healthy remotes WITHOUT counting affinity remaps: the owner
        assignment is a pure function of the endpoint set, so tier
        demotion — a routing-order concern — must not thrash the
        `affinity_remaps` ledger, and the owner still outranks remotes
        in worse tiers (pages are warm there; it is wounded, not
        gone)."""
        el = _client_with_pool(3, **{"affinity-key": "prefix"})
        from nnstreamer_tpu.core.buffer import TensorFrame

        rng = np.random.default_rng(11)
        prompt = rng.integers(0, 997, (1, PREFIX_GRAIN + 8)).astype(
            np.int32)
        f = TensorFrame([prompt])
        key = prefix_route_key(prompt)
        owner = routing.rendezvous_owner(key, el._pstate.targets)
        addr = "{}:{}".format(*el._pstate.targets[owner])
        # healthy owner: promoted to the very front, zero remaps
        for first in range(3):
            assert el._route_order(el._pstate, f, first)[0] == owner
        assert el._affinity_remaps == 0
        for hint in ({"draining": True}, {"degraded": True}):
            with el._breakers_lock:
                el._endpoint_hints = {addr: hint}
                el._hints_ts = time.monotonic()
            for first in range(3):
                order = el._route_order(el._pstate, f, first)
                assert order[-1] == owner, (
                    f"{hint}: owner must yield to healthy remotes")
                assert set(order[:2]) == {i for i in range(3)
                                          if i != owner}
        # repeated failover routing counted ZERO owner changes
        assert el._affinity_remaps == 0
        # ...and a frame declaring a longer shared prefix still routes
        # deterministically (meta prefix_tokens -> deeper chain digest)
        f2 = TensorFrame([prompt], meta={"prefix_tokens": PREFIX_GRAIN})
        el._route_order(el._pstate, f2, 0)
        assert el._affinity_remaps == 0


# ---------------------------------------------------------------------------
# Routing policy ranking (pure units over core/routing.py)
# ---------------------------------------------------------------------------
class TestRoutingRanking:
    def test_rotate_is_rotation_order(self):
        tiers = {i: routing.TIER_OK for i in range(4)}
        assert routing.order_remotes("rotate", tiers, 2, 4) == [2, 3, 0, 1]

    def test_least_inflight_prefers_idle_with_rotation_tiebreak(self):
        tiers = {i: routing.TIER_OK for i in range(4)}
        infl = {0: 3, 1: 0, 2: 1, 3: 0}
        assert routing.order_remotes(
            "least-inflight", tiers, 3, 4, inflight=infl) == [3, 1, 2, 0]

    def test_ewma_prefers_fast_remote_inflight_tiebreak(self):
        tiers = {i: routing.TIER_OK for i in range(3)}
        scores = {0: 40.0, 1: 5.0, 2: 5.0}
        infl = {0: 0, 1: 2, 2: 0}
        assert routing.order_remotes(
            "ewma", tiers, 0, 3, inflight=infl, scores=scores) == [2, 1, 0]

    def test_unknown_endpoint_scores_neutral_mean(self):
        """A just-joined server (no EWMA row yet) is neither flooded nor
        starved: it ranks at the mean of the known rows."""
        addrs = ["a:1", "b:2", "c:3"]
        spans = {
            "a:1": {"e2e_ms": 10.0, "requests": 5},
            "b:2": {"e2e_ms": 30.0, "requests": 5},
        }
        scores = routing.ewma_scores(range(3), addrs, spans)
        assert scores[0] == 10.0 and scores[1] == 30.0
        assert scores[2] == pytest.approx(20.0)
        # a row that never completed a request carries no signal
        spans["c:3"] = {"e2e_ms": None, "requests": 0}
        assert routing.ewma_scores(
            range(3), addrs, spans)[2] == pytest.approx(20.0)

    @pytest.mark.parametrize("policy", routing.ROUTING_POLICIES)
    def test_down_tier_never_outranks_ok_tier(self, policy):
        """The selection-side guard: a breaker-open/cooled remote is
        never ranked ahead of ANY healthy one, even with the best load
        signal of the pool."""
        tiers = {0: routing.TIER_OK, 1: routing.TIER_DOWN,
                 2: routing.TIER_OK}
        infl = {0: 9, 1: 0, 2: 7}          # the down one looks idle...
        scores = {0: 90.0, 1: 0.1, 2: 70.0}  # ...and fast
        order = routing.order_remotes(
            policy, tiers, 1, 3, inflight=infl, scores=scores)
        assert order[-1] == 1
        assert set(order[:2]) == {0, 2}

    @pytest.mark.parametrize("policy", routing.ROUTING_POLICIES)
    def test_draining_ranks_between_ok_and_down(self, policy):
        tiers = {0: routing.TIER_DOWN, 1: routing.TIER_DRAINING,
                 2: routing.TIER_OK}
        order = routing.order_remotes(policy, tiers, 0, 3,
                                      inflight={}, scores={})
        assert order == [2, 1, 0]

    def test_affinity_owner_promoted_within_its_tier_only(self):
        tiers = {0: routing.TIER_OK, 1: routing.TIER_OK,
                 2: routing.TIER_DOWN}
        # healthy owner: jumps to the very front
        assert routing.order_remotes(
            "rotate", tiers, 0, 3, affinity_owner=1)[:2] == [1, 0]
        # down owner: stickiness must NOT pin a session to a dead host
        order = routing.order_remotes(
            "rotate", tiers, 0, 3, affinity_owner=2)
        assert order == [0, 1, 2]


# ---------------------------------------------------------------------------
# Element-level routing: the two bugfix pins + draining hints
# ---------------------------------------------------------------------------
def _client_with_pool(n=3, **props):
    """An unstarted query client with a synthetic pool (no sockets)."""
    from nnstreamer_tpu.elements.query import _PoolState

    el = make_element("tensor_query_client", "q")
    for k, v in props.items():
        el.props[k] = v
    targets = [("127.9.9.9", 7100 + i) for i in range(n)]
    el._pstate = _PoolState([object() for _ in range(n)], targets, 0)
    return el


def _trip_breaker(el, target):
    b = el._breaker_for(target)
    for _ in range(int(el.props["breaker-threshold"])):
        b.record_failure()
    assert b.state == CircuitBreaker.OPEN
    return b


class TestClientRouting:
    @pytest.mark.parametrize("policy",
                             ["rotate", "least-inflight", "ewma"])
    def test_open_breaker_never_selected_over_closed_alternative(
            self, policy):
        """BUGFIX PIN: whatever the policy and however attractive its
        load signal, a remote with an OPEN breaker is ordered after
        every closed-breaker alternative — so the failover loop can
        never dial it while a healthy remote exists."""
        el = _client_with_pool(3, routing=policy)
        _trip_breaker(el, el._pstate.targets[0])
        # make the tripped remote maximally attractive to the policies
        with el._breakers_lock:
            el._remote_inflight["127.9.9.9:7101"] = 5
            el._remote_inflight["127.9.9.9:7102"] = 7
            el._remote_spans["127.9.9.9:7100"] = {
                "e2e_ms": 0.1, "requests": 100}
            el._remote_spans["127.9.9.9:7101"] = {
                "e2e_ms": 80.0, "requests": 100}
            el._remote_spans["127.9.9.9:7102"] = {
                "e2e_ms": 90.0, "requests": 100}
        for first in range(3):
            order = el._route_order(el._pstate, None, first)
            assert order[-1] == 0, (
                f"open-breaker remote ranked {order} (policy={policy}, "
                f"first={first})")

    def test_evicted_ewma_rows_are_never_consulted(self):
        """BUGFIX PIN: after `_rediscover` evicts a vanished endpoint,
        its (frozen, possibly absurdly-good) EWMA row must not influence
        routing.  Lookup is by CURRENT target, so a stale row is
        unreachable; the live endpoints rank on their own signals."""
        el = _client_with_pool(2, routing="ewma")
        with el._breakers_lock:
            # vanished endpoint left a frozen "fastest ever" row behind
            el._remote_spans["10.66.66.66:9999"] = {
                "e2e_ms": 0.001, "requests": 10_000}
            el._remote_spans["127.9.9.9:7100"] = {
                "e2e_ms": 50.0, "requests": 10}
            el._remote_spans["127.9.9.9:7101"] = {
                "e2e_ms": 5.0, "requests": 10}
        order = el._route_order(el._pstate, None, 0)
        assert order == [1, 0]
        # and the real _rediscover eviction removes such rows outright
        # (pinned in PR 7; re-checked here against the routing path)
        with el._breakers_lock:
            keep = {f"{h}:{p}" for h, p in el._pstate.targets}
            for key in [k for k in el._remote_spans if k not in keep]:
                del el._remote_spans[key]
            assert set(el._remote_spans) == keep

    def test_draining_hint_deprioritizes_before_any_dial(self):
        """Discovery-plane health: a host that ANNOUNCED it is draining
        ranks below every serving host — the client never pays the
        GOAWAY round trip to learn what the broker already told it."""
        el = _client_with_pool(3, routing="rotate")
        with el._breakers_lock:
            el._endpoint_hints = {"127.9.9.9:7100": {"draining": True}}
            el._hints_ts = time.monotonic()
        for first in range(3):
            order = el._route_order(el._pstate, None, first)
            assert order[-1] == 0
        # ...but still above a breaker-open host
        _trip_breaker(el, el._pstate.targets[1])
        order = el._route_order(el._pstate, None, 0)
        assert order == [2, 0, 1]

    def test_stale_draining_hint_decays(self):
        """A hints generation older than the TTL stops deprioritizing:
        a drained-then-restarted host must regain traffic even when no
        failure ever triggers a rediscovery."""
        el = _client_with_pool(2, routing="rotate")
        with el._breakers_lock:
            el._endpoint_hints = {"127.9.9.9:7100": {"draining": True}}
            el._hints_ts = time.monotonic() - el._HINT_TTL_S - 1.0
        assert el._route_order(el._pstate, None, 0) == [0, 1]

    def test_no_duplicate_registry_samples_per_scrape(self):
        """affinity_remaps / remote_inflight export through exactly ONE
        collector path — duplicate series would be invalid Prometheus
        exposition and double-count on aggregation."""
        server = parse_pipeline(
            "tensor_query_serversrc name=ssrc id=986 connect-type=tcp ! "
            "tensor_filter framework=scaler custom=factor:2 ! "
            "tensor_query_serversink id=986")
        server.start()
        client = parse_pipeline(
            "appsrc name=src ! tensor_query_client name=q "
            "connect-type=tcp host=localhost "
            f"port={server['ssrc'].props['port']} affinity-key=sess ! "
            "tensor_sink name=out")
        client.start()
        try:
            from nnstreamer_tpu.core.buffer import TensorFrame

            client["src"].push(TensorFrame(
                [np.float32([1])], meta={"sess": "k"}))
            client["src"].end_of_stream()
            client.wait(timeout=30)
            snap = client.metrics_snapshot()
            by_key = Counter(
                (s.name, tuple(sorted(s.labels.items())))
                for s in snap.samples)
            dupes = {k: n for k, n in by_key.items() if n > 1}
            assert not dupes, f"duplicate series in one scrape: {dupes}"
        finally:
            client.stop()
            server.stop()

    def test_affinity_remap_counting(self):
        """A remap is an OWNER change for a known key — re-routing the
        same key to its unchanged owner counts nothing."""
        el = _client_with_pool(2, **{"affinity-key": "sess"})
        from nnstreamer_tpu.core.buffer import TensorFrame

        f = TensorFrame([np.float32([1])], meta={"sess": "k1"})
        el._route_order(el._pstate, f, 0)
        el._route_order(el._pstate, f, 1)
        assert el._affinity_remaps == 0
        owner = routing.rendezvous_owner("k1", el._pstate.targets)
        # shrink the fleet so k1's owner changes iff it owned it
        from nnstreamer_tpu.elements.query import _PoolState

        survivors = [t for i, t in enumerate(el._pstate.targets)
                     if i != owner]
        el._pstate = _PoolState([object()], survivors, 1)
        el._route_order(el._pstate, f, 0)
        assert el._affinity_remaps == 1

    def test_affinity_batch_uses_first_frame_key(self):
        el = _client_with_pool(3, **{"affinity-key": "sess"})
        from nnstreamer_tpu.core.buffer import TensorFrame

        f = TensorFrame([np.float32([1])], meta={"sess": "sticky"})
        owner = routing.rendezvous_owner("sticky", el._pstate.targets)
        for first in range(3):
            assert el._route_order(el._pstate, [f, f], first)[0] == owner


# ---------------------------------------------------------------------------
# Per-tenant admission truth table (core/liveness.py)
# ---------------------------------------------------------------------------
class TestTenantAdmission:
    def test_quota_shed_is_per_tenant_and_exactly_counted(self):
        a = TenantAdmissionController(quotas={"hot": 2})
        a.admit(tenant="hot")
        a.admit(tenant="hot")
        with pytest.raises(ServerBusyError) as ei:
            a.admit(tenant="hot")
        assert ei.value.reason == "quota" and ei.value.tenant == "hot"
        # other tenants are untouched by hot's quota
        a.admit(tenant="cold")
        a.admit(tenant="")  # unnamed: never quota-bound
        snap = a.snapshot()["tenants"]
        assert snap["hot"] == {
            "inflight": 2, "admitted": 2, "shed": 1, "quota": 2}
        assert snap["cold"]["shed"] == 0
        # release frees the quota slot
        a.release(tenant="hot")
        a.admit(tenant="hot")

    def test_retry_after_paces_with_shed_streak_and_resets(self):
        a = TenantAdmissionController(quotas={"t": 1},
                                      clock=lambda: 0.0)
        a.admit(tenant="t")
        afters = []
        for _ in range(10):
            with pytest.raises(ServerBusyError) as ei:
                a.admit(tenant="t", retry_after=0.05)
            afters.append(ei.value.retry_after)
        assert afters[0] == pytest.approx(0.05)
        assert afters[1] == pytest.approx(0.10)
        assert max(afters) == pytest.approx(
            0.05 * TenantAdmissionController.RETRY_AFTER_CAP)
        assert afters == sorted(afters)
        # an admit resets the pacing
        a.release(tenant="t")
        a.admit(tenant="t")
        a.release(tenant="t")
        a.admit(tenant="t")
        with pytest.raises(ServerBusyError) as ei:
            a.admit(tenant="t", retry_after=0.05)
        assert ei.value.retry_after == pytest.approx(0.05)

    def test_priority_classes_shed_low_first(self):
        """high=8, low=2 -> ceilings [2, 4, 6, 8]: under pressure the
        low classes hit their ceiling while priority 3 still has
        headroom (the weighted-shed order)."""
        a = TenantAdmissionController(high=8, low=2)
        for _ in range(6):
            a.admit(priority=3)
        for p in (0, 1, 2):
            with pytest.raises(ServerBusyError) as ei:
                a.admit(priority=p)
            assert ei.value.reason == "priority"
        a.admit(priority=3)  # 7/8: the top class is still admitted
        a.admit(priority=3)  # 8/8
        with pytest.raises(ServerBusyError) as ei:
            a.admit(priority=3)
        assert ei.value.reason == "load"

    def test_priority3_semantics_identical_to_base_watermark(self):
        """Requests without a priority class (= priority 3) see the
        EXACT pre-tenancy high/low hysteresis behavior."""
        a = TenantAdmissionController(high=4, low=1)
        for _ in range(4):
            a.admit()
        with pytest.raises(ServerBusyError):
            a.admit()
        a.release()
        a.release()  # inflight 2 > low 1: still shedding
        with pytest.raises(ServerBusyError):
            a.admit()
        a.release()  # inflight 1 <= low: band clears
        a.admit()

    def test_quota_checked_before_priority_and_load(self):
        a = TenantAdmissionController(high=8, low=2, quotas={"t": 1})
        a.admit(tenant="t", priority=0)
        with pytest.raises(ServerBusyError) as ei:
            a.admit(tenant="t", priority=0)
        assert ei.value.reason == "quota"

    def test_tenant_quota_busy_is_breaker_immune(self):
        a = TenantAdmissionController(quotas={"t": 1})
        a.admit(tenant="t")
        with pytest.raises(ServerBusyError) as ei:
            a.admit(tenant="t")
        assert is_remote_application_error(ei.value), (
            "tenant-quota BUSY must never count against the remote's "
            "breaker")

    def test_sustained_quota_shed_fires_rate_limited_incident(self):
        now = [0.0]
        fired = []
        a = TenantAdmissionController(
            quotas={"t": 1}, shed_window_s=5.0,
            on_sustained_shed=fired.append, clock=lambda: now[0])
        a.admit(tenant="t")
        for t in (0.0, 1.0, 4.9):
            now[0] = t
            with pytest.raises(ServerBusyError):
                a.admit(tenant="t")
        assert fired == []  # window not yet exceeded
        now[0] = 5.0
        with pytest.raises(ServerBusyError):
            a.admit(tenant="t")
        assert fired == ["t"]
        now[0] = 7.0  # rate limit: once per window
        with pytest.raises(ServerBusyError):
            a.admit(tenant="t")
        assert fired == ["t"]
        now[0] = 10.0
        with pytest.raises(ServerBusyError):
            a.admit(tenant="t")
        assert fired == ["t", "t"]
        # an admit ends the episode entirely
        a.release(tenant="t")
        now[0] = 20.0
        a.admit(tenant="t")
        a.release(tenant="t")
        assert a.snapshot()["tenants"]["t"]["shed"] == 6

    def test_load_and_priority_sheds_keep_flat_retry_after(self):
        """Streak-scaled pacing is a QUOTA property: global watermark /
        priority sheds keep the flat pre-tenancy retry-after, so
        unnamed clients sharing the \"\" ledger never couple each
        other's backoff."""
        a = TenantAdmissionController(high=2, low=0)
        a.admit()
        a.admit()
        for _ in range(10):
            with pytest.raises(ServerBusyError) as ei:
                a.admit(retry_after=0.05)
            assert ei.value.reason == "load"
            assert ei.value.retry_after == pytest.approx(0.05)

    def test_tenant_table_is_bounded_with_loud_eviction(self):
        """The tenant name is client-controlled wire input: the ledger
        table caps at TENANT_MAP_MAX, evicting only IDLE
        least-recently-active rows, and counts evictions."""
        a = TenantAdmissionController()
        held = [f"held-{i}" for i in range(4)]
        for t in held:
            a.admit(tenant=t)  # in flight: must never be evicted
        for i in range(TenantAdmissionController.TENANT_MAP_MAX * 2):
            a.admit(tenant=f"churn-{i}")
            a.release(tenant=f"churn-{i}")
        snap = a.snapshot()
        assert len(snap["tenants"]) <= (
            TenantAdmissionController.TENANT_MAP_MAX)
        assert snap["tenants_evicted"] > 0
        for t in held:
            assert snap["tenants"][t]["inflight"] == 1
        # aggregate history survives eviction
        assert snap["admitted"] == (
            len(held) + TenantAdmissionController.TENANT_MAP_MAX * 2)

    def test_parse_tenant_quotas(self):
        assert parse_tenant_quotas("a:8, b:4") == {"a": 8, "b": 4}
        assert parse_tenant_quotas("") == {}
        with pytest.raises(ValueError):
            parse_tenant_quotas("a:-1")
        with pytest.raises(ValueError):
            parse_tenant_quotas("nocolon")


# ---------------------------------------------------------------------------
# Tenant admission over the wire (both shapes of BUSY, exact accounting)
# ---------------------------------------------------------------------------
class TestTenantAdmissionE2E:
    def _server(self, sid, quotas, sleep=0.05, max_inflight=16):
        pipe = parse_pipeline(
            f"tensor_query_serversrc name=ssrc id={sid} connect-type=tcp "
            f"max-inflight={max_inflight} tenant-quotas={quotas} ! "
            f"identity sleep={sleep} ! "
            "tensor_filter framework=scaler custom=factor:2 ! "
            f"tensor_query_serversink id={sid}")
        pipe.start()
        return pipe, pipe["ssrc"].props["port"]

    def test_hot_tenant_sheds_and_recovers_without_breaker_trips(self):
        """A tenant over its quota is shed with BUSY (carried per-tenant
        retry-after), retries deliver everything eventually, the
        breaker never trips, and the server's per-tenant ledger is
        exact."""
        sp, port = self._server(981, "hot:1")
        client = parse_pipeline(
            "appsrc name=src ! tensor_query_client name=q connect-type=tcp "
            f"host=localhost port={port} tenant=hot busy-retries=40 "
            "retry-backoff=0.01 max-in-flight=4 timeout=5 ! "
            "tensor_sink name=out")
        client.start()
        try:
            n = 8
            for i in range(n):
                client["src"].push(np.float32([i]))
            client["src"].end_of_stream()
            client.wait(timeout=60)
            vals = sorted(
                float(f.tensors[0][0]) for f in client["out"].frames)
            assert vals == [i * 2.0 for i in range(n)]
            hq = client.health()["q"]
            assert hq["busy_replies"] > 0, "the quota actually bound"
            for snap in hq["breakers"].values():
                assert snap["trips"] == 0 and snap["state"] == "closed"
            tenants = sp.health()["ssrc"]["tenants"]
            assert tenants["hot"]["admitted"] == n
            assert tenants["hot"]["shed"] == hq["busy_replies"]
            assert tenants["hot"]["quota"] == 1
        finally:
            client.stop()
            sp.stop()

    def test_tenant_meta_crosses_grpc_too(self):
        pipe = parse_pipeline(
            "tensor_query_serversrc name=ssrc id=983 connect-type=grpc "
            "max-inflight=16 tenant-quotas=g:2 ! "
            "tensor_filter framework=scaler custom=factor:2 ! "
            "tensor_query_serversink id=983")
        pipe.start()
        client = parse_pipeline(
            "appsrc name=src ! tensor_query_client name=q "
            f"connect-type=grpc host=localhost "
            f"port={pipe['ssrc'].props['port']} tenant=g "
            "busy-retries=20 retry-backoff=0.01 max-in-flight=2 ! "
            "tensor_sink name=out")
        client.start()
        try:
            for i in range(4):
                client["src"].push(np.float32([i]))
            client["src"].end_of_stream()
            client.wait(timeout=30)
            assert sorted(
                float(f.tensors[0][0]) for f in client["out"].frames
            ) == [0.0, 2.0, 4.0, 6.0]
            assert pipe.health()["ssrc"]["tenants"]["g"]["admitted"] == 4
        finally:
            client.stop()
            pipe.stop()


# ---------------------------------------------------------------------------
# Sustained shed -> flight-recorder incident (e2e)
# ---------------------------------------------------------------------------
class TestSustainedShedIncident:
    def test_incident_dump_names_the_tenant(self, tmp_path):
        pipe = parse_pipeline(
            "tensor_query_serversrc name=ssrc id=984 connect-type=tcp "
            "max-inflight=16 tenant-quotas=drowning:1 shed-window=0.15 ! "
            "identity sleep=0.4 ! "
            "tensor_filter framework=scaler custom=factor:2 ! "
            "tensor_query_serversink id=984")
        pipe.enable_flight_recorder(dump_dir=str(tmp_path))
        pipe.start()
        client = parse_pipeline(
            "appsrc name=src ! tensor_query_client name=q connect-type=tcp "
            f"host=localhost port={pipe['ssrc'].props['port']} "
            "tenant=drowning busy-retries=60 retry-backoff=0.01 "
            "max-in-flight=4 timeout=10 ! tensor_sink name=out")
        client.start()
        try:
            for i in range(3):
                client["src"].push(np.float32([i]))
            deadline = time.monotonic() + 15
            dumps = []
            while time.monotonic() < deadline and not dumps:
                dumps = [p for p in os.listdir(tmp_path)
                         if "tenant_shed" in p]
                time.sleep(0.05)
            assert dumps, "sustained quota shed produced no incident dump"
            client["src"].end_of_stream()
            client.wait(timeout=60)
        finally:
            client.stop()
            pipe.stop()


# ---------------------------------------------------------------------------
# Discovery-plane health propagation (broker-level)
# ---------------------------------------------------------------------------
class TestDiscoveryHealth:
    def test_announce_update_is_visible_to_discoverers(self):
        from nnstreamer_tpu.distributed.hybrid import (
            Announcement,
            discover_endpoints,
        )
        from nnstreamer_tpu.distributed.mqtt import MiniBroker

        broker = MiniBroker()
        try:
            ann = Announcement(
                "127.0.0.1", broker.port, "nns/query/ft/one",
                {"host": "127.0.0.1", "port": 7199, "draining": False})
            seen = {}

            def validate(topic, info):
                seen[topic] = dict(info)
                return True

            discover_endpoints(
                "127.0.0.1", broker.port, "nns/query/ft/#",
                timeout_s=5.0, validate=validate)
            assert seen["nns/query/ft/one"]["draining"] is False
            ann.update({"draining": True, "inflight": 3})
            seen.clear()
            discover_endpoints(
                "127.0.0.1", broker.port, "nns/query/ft/#",
                timeout_s=5.0, validate=validate)
            assert seen["nns/query/ft/one"]["draining"] is True
            assert seen["nns/query/ft/one"]["inflight"] == 3
            assert seen["nns/query/ft/one"]["port"] == 7199
            ann.clear()
        finally:
            broker.close()

    def test_fresh_healthy_announce_overrides_stale_draining_hint(self):
        """A restarted server announces healthy on a NEW instance topic
        but the SAME host:port — its announce must override the dead
        instance's retained draining=true, or the healthy replacement
        would sit in TIER_DRAINING for a whole hint TTL."""
        import socket

        from nnstreamer_tpu.distributed.hybrid import Announcement
        from nnstreamer_tpu.distributed.mqtt import MiniBroker

        broker = MiniBroker()
        ls = socket.socket()
        ls.bind(("127.0.0.1", 0))
        ls.listen(1)  # probe_endpoint needs a live listener
        port = ls.getsockname()[1]
        try:
            old = Announcement(
                "127.0.0.1", broker.port, "nns/query/hint/old",
                {"host": "127.0.0.1", "port": port,
                 "connect_type": "tcp", "draining": True})
            new = Announcement(
                "127.0.0.1", broker.port, "nns/query/hint/new",
                {"host": "127.0.0.1", "port": port,
                 "connect_type": "tcp", "draining": False})
            el = make_element("tensor_query_client", "q")
            el.props["topic"] = "hint"
            el.props["dest-port"] = broker.port
            el.props["connect-type"] = "tcp"
            el.props["discovery-timeout"] = 10.0
            targets = el._discover_targets()
            assert targets == [("127.0.0.1", port)]
            assert el._endpoint_hints == {}, (
                "stale draining hint survived a fresh healthy announce: "
                f"{el._endpoint_hints}")
            old.clear()
            new.clear()
        finally:
            ls.close()
            broker.close()

    def test_serversrc_announces_draining_on_drain(self):
        from nnstreamer_tpu.distributed.hybrid import discover_endpoints
        from nnstreamer_tpu.distributed.mqtt import MiniBroker

        broker = MiniBroker()
        server = client = None
        try:
            server = parse_pipeline(
                "tensor_query_serversrc name=ssrc id=985 connect-type=tcp "
                "topic=drainft dest-host=127.0.0.1 "
                f"dest-port={broker.port} drain-deadline=5 ! "
                "identity sleep=0.5 ! "
                "tensor_filter framework=scaler custom=factor:2 ! "
                "tensor_query_serversink id=985")
            server.start()
            port = server["ssrc"].props["port"]
            # hold one request in flight so the drain STAYS draining
            client = parse_pipeline(
                "appsrc name=src ! tensor_query_client name=q "
                f"connect-type=tcp host=localhost port={port} timeout=10 "
                "! tensor_sink name=out")
            client.start()
            client["src"].push(np.float32([7]))
            deadline = time.monotonic() + 5
            core = server["ssrc"]._core
            while (core.admission.inflight == 0
                   and time.monotonic() < deadline):
                time.sleep(0.01)
            server["ssrc"].request_drain()
            deadline = time.monotonic() + 5
            state = {}
            while time.monotonic() < deadline:
                found = {}

                def validate(topic, info, _found=found):
                    _found[topic] = dict(info)
                    return True

                discover_endpoints(
                    "127.0.0.1", broker.port, "nns/query/drainft/#",
                    timeout_s=2.0, validate=validate)
                state = next(iter(found.values()), {})
                if state.get("draining"):
                    break
                time.sleep(0.05)
            assert state.get("draining") is True, (
                f"drain not propagated to the broker: {state}")
            client["src"].end_of_stream()
            client.wait(timeout=30)
        finally:
            if client is not None:
                client.stop()
            if server is not None:
                server.stop()
            broker.close()


# ---------------------------------------------------------------------------
# The fleet chaos e2e (acceptance)
# ---------------------------------------------------------------------------
@pytest.mark.chaos
class TestFleetChaos:
    """3 tcp servers under continuous 2-tenant load survive scripted
    kill + rolling restart + server join with zero lost/duplicated
    frames, exact per-tenant accounting, zero breaker trips from
    drains, and bounded affinity remaps; a hot-tenant burst at 2x quota
    sheds ONLY the hot tenant while the victim keeps >= 90% of its
    no-burst throughput."""

    KEYS = 300

    def test_fleet_survives_scripted_churn(self):
        from chaos_fleet import FleetHarness

        h = FleetHarness(tenant_quotas="A:6,B:2", server_sleep=0.01,
                         max_inflight=32, shed_window_s=30.0)
        try:
            self._run(h)
        finally:
            h.stop_all()

    def _run(self, h):
        for i in range(3):
            h.start_server(i)
        ca = h.make_client("A", tenant="A", routing="least-inflight",
                           busy_retries=12)
        cb = h.make_client("B", tenant="B", routing="ewma",
                           max_in_flight=2, busy_retries=12)
        ck = h.make_client("K", affinity=True, routing="rotate",
                           max_in_flight=8)
        keys = [f"sess-{k}" for k in range(self.KEYS)]
        seq = iter(range(10**6))

        def tenant_wave(n=16):
            for _ in range(n):
                ca.push(next(seq))
                cb.push(next(seq))
            ca.settle()
            cb.settle()

        def key_wave():
            for k in keys:
                ck.push(next(seq), key=k)
            ck.settle()

        # -- phase 1: baseline --------------------------------------------
        tenant_wave()
        key_wave()
        remaps0 = ck.health()["affinity_remaps"]

        # -- phase 2: rolling restart under load (GOAWAY, zero loss) ------
        for _ in range(24):
            ca.push(next(seq))
        roll = h.rolling_restart(0)
        assert roll["drain"]["dropped"] == 0
        ca.settle()
        tenant_wave()
        # same port came back: no membership change, no affinity remap
        key_wave()
        assert ck.health()["affinity_remaps"] == remaps0
        goaways = (roll["health"]["goaway_sent"]
                   + sum(c.health()["goaway_replies"]
                         for c in (ca, cb, ck)))
        assert goaways >= 1, "the roll was never observed as GOAWAY"

        # -- phase 3: server join (bounded remap) -------------------------
        h.add_server()
        assert h.refresh_client(ck), "join must swap the affinity pool"
        key_wave()
        remap_join = ck.health()["affinity_remaps"] - remaps0
        bound = math.ceil(self.KEYS / 3)
        assert 0 < remap_join <= bound, (
            f"join remapped {remap_join} keys (bound ceil(K/N) = {bound})")

        # -- phase 4: hard kill mid-load (zero loss, bounded remap) -------
        for _ in range(16):
            ca.push(next(seq))
            cb.push(next(seq))
        h.kill_server(2)
        ca.settle(timeout=60)
        cb.settle(timeout=60)
        for c in (ca, cb, ck):
            h.refresh_client(c)
        remaps_prekill = ck.health()["affinity_remaps"]
        tenant_wave()
        key_wave()
        remap_kill = ck.health()["affinity_remaps"] - remaps_prekill
        assert remap_kill <= math.ceil(self.KEYS / 3)

        # -- phase 5: hot-tenant burst at 2x quota ------------------------
        # baseline: the victim tenant alone
        a0 = len(ca.values())
        for _ in range(30):
            ca.push(next(seq))
        ca.settle(timeout=60)
        baseline_delivered = len(ca.values()) - a0
        assert baseline_delivered == 30
        # burst: B floods at ~2x its fleet quota (3 live servers x
        # quota 2 = 6 slots; 8+ concurrent singles, no retries) while
        # A keeps pushing its normal load
        tenants_before = h.fleet_tenants()
        burst = h.make_client(
            "Bburst", tenant="B", routing="least-inflight",
            max_in_flight=12, retries=0, busy_retries=0,
            degrade="skip", static_hosts=True)
        a1 = len(ca.values())
        for i in range(60):
            burst.push(next(seq))
            if i % 2 == 0:
                ca.push(next(seq))
        ca.settle(timeout=60)
        burst.settle(timeout=60)
        tenants_after = h.fleet_tenants()
        burst_delivered = len(ca.values()) - a1
        # victim keeps >= 90% of its no-burst baseline (count-based:
        # same 30-frame load, quota guarantees the slots)
        assert burst_delivered >= 0.9 * baseline_delivered, (
            f"victim tenant degraded: {burst_delivered}/30 delivered "
            f"under burst vs {baseline_delivered}/30 baseline")
        # the hot tenant absorbed ALL the shedding, exactly accounted
        shed_a = (tenants_after["A"]["shed"]
                  - tenants_before["A"]["shed"])
        shed_b = (tenants_after["B"]["shed"]
                  - tenants_before["B"]["shed"])
        bh = burst.health()
        assert shed_a == 0
        assert shed_b == bh["busy_replies"] > 0
        assert bh["busy_replies"] == bh["degraded_frames"]
        adm_b = (tenants_after["B"]["admitted"]
                 - tenants_before["B"]["admitted"])
        assert adm_b == len(burst.values())
        assert len(burst.values()) + bh["degraded_frames"] == 60

        # -- final verdict -------------------------------------------------
        for c in (ca, cb, ck, burst):
            c.finish()
        v = h.verdict()
        assert v["lost"] == 0 and v["duplicated"] == 0, v
        assert v["breaker_trips"] == 0, v
        # per-tenant ledgers stayed internally consistent fleet-wide
        tenants = v["tenants"]
        assert tenants["A"]["shed"] == 0
        assert tenants["B"]["shed"] >= shed_b


# ---------------------------------------------------------------------------
# The device-loss chaos e2e (acceptance — degrade, don't die)
# ---------------------------------------------------------------------------
@pytest.mark.chaos
class TestDeviceLossChaos:
    """A mesh member dies mid-decode under concurrent slotted
    generation load: every live stream hands off with resume state and
    lands bit-exact to the oracle, the engine re-meshes atomically onto
    the survivors (``gen_device_lost == 1`` / ``gen_remeshes == 1``,
    migrations exactly equal handoffs), the wounded server announces
    ``degraded:true`` on the discovery plane (observed client-side
    after one rediscovery and reflected in health), and ZERO breakers
    trip anywhere — the chip died, no server did."""

    def test_device_loss_survived_fleet_wide(self):
        from chaos_fleet import run_device_loss_script

        v = run_device_loss_script(servers=3, streams=4, seed=0)
        assert v["ok"], v
        assert v["exact"] == 4 and v["mismatched"] == 0, v
        assert v["gen"]["gen_device_lost"] == 1, v
        assert v["gen"]["gen_remeshes"] == 1, v
        assert v["handed_off"] >= 1, v
        assert v["resumes"]["stream_migrations"] == v["handed_off"], v
        assert v["degraded_announce_seen"] and v["victim_degraded_health"], v
        assert v["breaker_trips"] == 0, v
