"""Interned-verdict cache (engine/verdictcache.py) + the epoch planes that
invalidate it.

The load-bearing suite is the epoch-bump COMPLETENESS sweep: a seeded
mutation mix (status flips, threshold edits, override windows, policy
swaps, reservations, gang reserve/rollback, preemption, namespace churn)
where after EVERY mutation the cached ``pre_filter`` must agree with the
uncached recompute on the same state — any mutation site missing its
epoch bump serves a stale verdict here and fails the pin. The front-tier
mirror (sharding/front.py) gets the same treatment over the scatter
path, including the reshard/resync/attach global bumps.
"""

from __future__ import annotations

import random

import pytest

from conftest import normalize_reasons as norm
from kube_throttler_tpu.api.pod import Namespace, make_pod
from kube_throttler_tpu.api.types import (
    ClusterThrottle,
    ClusterThrottleSelector,
    ClusterThrottleSelectorTerm,
    ClusterThrottleSpec,
    LabelSelector,
    ResourceAmount,
    TemporaryThresholdOverride,
    Throttle,
    ThrottleSelector,
    ThrottleSelectorTerm,
    ThrottleSpec,
)
from kube_throttler_tpu.engine.store import Store
from kube_throttler_tpu.engine.verdictcache import VerdictCache
from kube_throttler_tpu.plugin import KubeThrottler, decode_plugin_args
from kube_throttler_tpu.plugin.framework import Status, StatusCode


def _throttle(name="t1", cpu="200m", grp="a", overrides=()):
    return Throttle(
        name=name,
        spec=ThrottleSpec(
            throttler_name="kube-throttler",
            threshold=ResourceAmount.of(requests={"cpu": cpu}),
            temporary_threshold_overrides=tuple(overrides),
            selector=ThrottleSelector(
                selector_terms=(
                    ThrottleSelectorTerm(
                        pod_selector=LabelSelector(match_labels={"grp": grp})
                    ),
                )
            ),
        ),
    )


def _cluster_throttle(name="ct1", cpu="500m", grp="a"):
    return ClusterThrottle(
        name=name,
        spec=ClusterThrottleSpec(
            throttler_name="kube-throttler",
            threshold=ResourceAmount.of(requests={"cpu": cpu}),
            selector=ClusterThrottleSelector(
                selector_terms=(
                    ClusterThrottleSelectorTerm(
                        LabelSelector(match_labels={"grp": grp}),
                        LabelSelector(),
                    ),
                )
            ),
        ),
    )


def _plugin(store, policies=None):
    config = {"name": "kube-throttler", "targetSchedulerName": "my-scheduler"}
    if policies is not None:
        config["policies"] = policies
    return KubeThrottler(
        decode_plugin_args(config), store, use_device=True, start_workers=False
    )


# --------------------------------------------------------------------------
# cache data structure
# --------------------------------------------------------------------------


class TestVerdictCacheUnit:
    def test_hit_requires_epoch_match(self):
        c = VerdictCache(capacity=8)
        st = Status(StatusCode.SUCCESS)
        c.put(("k",), 3, st)
        assert c.get(("k",), 3) is st
        # a bumped epoch sum invalidates without any explicit eviction
        assert c.get(("k",), 4) is None
        hits, misses, *_ = c.stats()
        assert hits == 1 and misses >= 1

    def test_unknown_key_misses(self):
        c = VerdictCache(capacity=8)
        assert c.get(("nope",), 0) is None
        assert c.stats()[1] == 1

    def test_put_replaces_stale_entry(self):
        c = VerdictCache(capacity=8)
        old, new = Status(StatusCode.SUCCESS), Status(StatusCode.ERROR, ("x",))
        c.put(("k",), 1, old)
        c.put(("k",), 2, new)
        assert c.get(("k",), 2) is new
        assert c.get(("k",), 1) is None

    def test_rotation_bounds_size(self):
        c = VerdictCache(capacity=16)  # segments of 8
        st = Status(StatusCode.SUCCESS)
        for i in range(200):
            c.put((f"k{i}",), 0, st)
        assert len(c) <= 16

    def test_rotation_promotes_hot_entries(self):
        c = VerdictCache(capacity=8)  # segments of 4
        hot = Status(StatusCode.SUCCESS)
        c.put(("hot",), 0, hot)
        for i in range(4):  # rotate: "hot" falls into the old segment
            c.put((f"cold{i}",), 0, hot)
        assert c.get(("hot",), 0) is hot  # old-segment hit promotes
        for i in range(4, 8):  # rotate again: promoted entry survives
            c.put((f"cold{i}",), 0, hot)
        assert c.get(("hot",), 0) is hot

    def test_invalidate_all(self):
        c = VerdictCache(capacity=8)
        c.put(("k",), 0, Status(StatusCode.SUCCESS))
        c.invalidate_all()
        assert len(c) == 0
        assert c.get(("k",), 0) is None
        assert c.stats()[3] == 1  # invalidations


# --------------------------------------------------------------------------
# plugin hot path
# --------------------------------------------------------------------------


class TestPluginCacheHotPath:
    def _stack(self):
        store = Store()
        plugin = _plugin(store)
        store.create_namespace(Namespace("default"))
        store.create_throttle(_throttle())
        plugin.run_pending_once()
        return store, plugin

    def test_repeat_verdict_is_a_cache_hit(self):
        _, plugin = self._stack()
        assert plugin.verdict_cache is not None
        pod = make_pod("p", labels={"grp": "a"}, requests={"cpu": "100m"})
        first = plugin.pre_filter(pod)
        hits0 = plugin.verdict_cache.stats()[0]
        second = plugin.pre_filter(pod)
        assert second is first  # the interned Status object itself
        assert plugin.verdict_cache.stats()[0] == hits0 + 1

    def test_same_shape_different_pod_shares_entry(self):
        _, plugin = self._stack()
        a = make_pod("a", labels={"grp": "a"}, requests={"cpu": "100m"})
        b = make_pod("b", labels={"grp": "a"}, requests={"cpu": "100m"})
        sa = plugin.pre_filter(a)
        hits0 = plugin.verdict_cache.stats()[0]
        sb = plugin.pre_filter(b)
        assert sb is sa
        assert plugin.verdict_cache.stats()[0] == hits0 + 1

    def test_unknown_namespace_is_uncacheable(self):
        _, plugin = self._stack()
        ghost = make_pod("g", namespace="ghost", requests={"cpu": "1m"})
        st1 = plugin.pre_filter(ghost)
        st2 = plugin.pre_filter(ghost)
        assert st1.code == StatusCode.ERROR and st2.code == StatusCode.ERROR
        # never entered the cache: the (shape, empty-cols) key would
        # collide with known-namespace pods that cached SUCCESS
        assert plugin.verdict_cache.stats()[0] == 0

    def test_exceeds_verdict_not_cached_and_reemits_event(self):
        from kube_throttler_tpu.plugin import RecordingEventRecorder

        store = Store()
        plugin = KubeThrottler(
            decode_plugin_args(
                {"name": "kube-throttler", "targetSchedulerName": "my-scheduler"}
            ),
            store,
            event_recorder=RecordingEventRecorder(),
            use_device=True,
            start_workers=False,
        )
        store.create_namespace(Namespace("default"))
        store.create_throttle(_throttle())
        plugin.run_pending_once()
        whale = make_pod("whale", labels={"grp": "a"}, requests={"cpu": "900m"})
        for _ in range(2):
            st = plugin.pre_filter(whale)
            assert any("exceeds-threshold" in r for r in st.reasons)
        # one Warning per CALL (the recorder aggregates identical events
        # into a count) — a cache hit would have swallowed the second
        assert sum(plugin.event_recorder.counts.values()) == 2

    def test_selector_edit_invalidates_memoized_cols(self):
        store, plugin = self._stack()
        pod = make_pod("p", labels={"grp": "b"}, requests={"cpu": "900m"})
        assert plugin.pre_filter(pod).code is StatusCode.SUCCESS  # matches nothing
        # the selector edit makes t1 match grp=b: the memoized cols for
        # the SAME pod object are now stale (index generation bumped)
        cur = store.get_throttle("default", "t1")
        store.update_throttle(
            Throttle(name="t1", spec=_throttle(grp="b").spec, status=cur.status)
        )
        plugin.run_pending_once()
        st = plugin.pre_filter(pod)
        assert st.code is not StatusCode.SUCCESS, st.reasons
        assert norm(st.reasons) == norm(plugin._pre_filter_uncached(pod).reasons)

    def test_policy_swap_drops_cache(self):
        _, plugin = self._stack()
        pod = make_pod("p", labels={"grp": "a"}, requests={"cpu": "100m"})
        plugin.pre_filter(pod)
        assert len(plugin.verdict_cache) > 0
        plugin.set_policy_specs(
            [{"name": "swap", "preemptionEnabled": True, "minPriorityGap": 1}]
        )
        assert len(plugin.verdict_cache) == 0


# --------------------------------------------------------------------------
# intra-batch dedupe
# --------------------------------------------------------------------------


class TestBatchDedupe:
    def test_batch_agrees_with_uncached_on_degenerate_mix(self):
        def build():
            store = Store()
            plugin = _plugin(store)
            store.create_namespace(Namespace("default"))
            store.create_throttle(_throttle(cpu="450m"))
            store.create_throttle(_throttle("t2", cpu="300m", grp="b"))
            store.create_cluster_throttle(_cluster_throttle())
            for i in range(40):  # one degenerate group: identical shape
                store.create_pod(
                    make_pod(
                        f"same{i}", labels={"grp": "a"},
                        requests={"cpu": "10m"}, node_name="n1", phase="Running",
                    )
                )
            for i in range(6):  # distinct shapes and groups
                store.create_pod(
                    make_pod(
                        f"diff{i}", labels={"grp": "ab"[i % 2]},
                        requests={"cpu": f"{(i + 1) * 50}m"},
                    )
                )
            store.create_pod(make_pod("lost", namespace="ghost"))
            plugin.run_pending_once()
            return plugin

        cached, uncached = build(), build()
        uncached.verdict_cache = None
        out_c = cached.pre_filter_batch()
        out_u = uncached.pre_filter_batch()
        assert out_c == out_u
        # the dedupe actually engaged: 46 known-ns pods collapsed into a
        # handful of (shape, cols) groups, one evaluation each
        hits1, misses1, *_ = cached.verdict_cache.stats()
        assert misses1 <= 15, f"dedupe evaluated {misses1} groups for 46 pods"
        # a second batch over unchanged state serves the groups from cache
        assert cached.pre_filter_batch() == out_u
        hits2, misses2, *_ = cached.verdict_cache.stats()
        assert hits2 > hits1, "warm batch never hit the cache"
        assert misses2 == misses1

    def test_batch_dedupe_declines_on_diverse_population(self):
        store = Store()
        plugin = _plugin(store)
        store.create_namespace(Namespace("default"))
        store.create_throttle(_throttle(cpu="10000m"))
        for i in range(300):  # every pod a distinct shape → groups*2 > pods
            store.create_pod(
                make_pod(f"p{i}", labels={"grp": "a"}, requests={"cpu": f"{i + 1}m"})
            )
        plugin.run_pending_once()
        out = plugin.pre_filter_batch()
        assert len(out["schedulable"]) == 300
        ref = plugin.verdict_cache
        plugin.verdict_cache = None
        assert plugin.pre_filter_batch() == out
        plugin.verdict_cache = ref

    # -- the decline from the pod count; the batch fingerprint -------------

    @staticmethod
    def _outcomes(plugin) -> dict:
        return {k[0]: v for k, v in plugin._dedupe_outcomes.collect().items()}

    @staticmethod
    def _population(case, seed, columnar=True):
        """A stack whose pods make the dedupe route of ``case``: shapes
        drawn from ``seed``, the groups fixed by the case."""
        rng = random.Random(seed)
        store = Store(columnar=columnar)
        plugin = _plugin(store)
        store.create_namespace(Namespace("default"))
        store.create_throttle(_throttle(cpu="30"))
        store.create_throttle(_throttle("t2", cpu="25", grp="b"))
        store.create_cluster_throttle(_cluster_throttle(cpu="40"))

        def shapes(k):  # k distinct cpu requests
            return [f"{m}m" for m in rng.sample(range(1, 5000), k)]

        if case == "degenerate":  # 400 pods over 6 shapes x 2 groups
            cpus = shapes(6)
            specs = [("default", "ab"[i % 2], rng.choice(cpus)) for i in range(400)]
        elif case == "diverse":  # every pod its own shape
            specs = [("default", "ab"[i % 2], c) for i, c in enumerate(shapes(400))]
        elif case == "small":  # distinct shapes, but at most 256 pods
            specs = [("default", "a", c) for c in shapes(200)]
        elif case == "cols":  # 190 shapes x 2 groups: too many groups, not shapes
            cpus = shapes(190)
            specs = [("default", "ab"[(i // 190) % 2], cpus[i % 190]) for i in range(400)]
        else:  # "ghost": degenerate, plus distinct shapes in a missing namespace
            cpus = shapes(5)
            specs = [("default", "a", rng.choice(cpus)) for _ in range(300)]
            specs += [("ghost", "a", c) for c in shapes(400)]
        for i, (ns, grp, cpu) in enumerate(specs):
            store.create_pod(
                make_pod(f"p{i}", namespace=ns, labels={"grp": grp}, requests={"cpu": cpu})
            )
        plugin.run_pending_once()
        return plugin

    @staticmethod
    def _full_pass_engages(plugin) -> bool:
        """The route the list-and-fingerprint pass alone would take."""
        pods = plugin.listers.pods.list()
        fps = [plugin.device_manager.verdict_fingerprint(p) for p in pods]
        groups = {fp[0] for fp in fps if fp is not None}
        return len(pods) <= plugin.BATCH_DEDUPE_MAX_PODS and not (
            plugin.grouping_declines(len(groups), len(pods))
        )

    @pytest.mark.parametrize("seed", [3, 2**31 + 5])
    @pytest.mark.parametrize(
        "case,outcome",
        [
            ("degenerate", "engaged"),
            ("diverse", "declined_groups"),
            ("small", "engaged"),
            ("cols", "declined_groups"),
            ("ghost", "engaged"),
        ],
    )
    def test_route_matches_full_fingerprint_pass(self, case, outcome, seed):
        plugin = self._population(case, seed)
        engages = self._full_pass_engages(self._population(case, seed))
        assert engages == (outcome == "engaged")
        out = plugin.pre_filter_batch()
        # one count per attempt, under the outcome the full pass implies
        assert self._outcomes(plugin) == {outcome: 1.0}
        uncached = self._population(case, seed)
        uncached.verdict_cache = None
        assert out == uncached.pre_filter_batch()
        plugin.pre_filter_batch()
        assert self._outcomes(plugin) == {outcome: 2.0}

    def test_frozen_dict_store_keeps_the_full_pass(self):
        # no arena, so no pod takes a key: the route is the full pass's
        plugin = self._population("diverse", 7, columnar=False)
        pods = plugin.listers.pods.list()
        assert plugin.device_manager.verdict_fingerprints(pods) == [None] * 400
        engages = self._full_pass_engages(self._population("diverse", 7, columnar=False))
        plugin.pre_filter_batch()
        assert self._outcomes(plugin) == {
            ("engaged" if engages else "declined_groups"): 1.0
        }

    def test_size_limit_declines_before_listing(self):
        plugin = self._population("degenerate", 11)
        plugin.BATCH_DEDUPE_MAX_PODS = 100
        arena = plugin.store.pod_arena
        made = arena.materializations_total
        out = plugin.pre_filter_batch()
        assert self._outcomes(plugin) == {"declined_size": 1.0}
        assert plugin.tracer.snapshot("store_list") is None
        assert arena.materializations_total == made
        # the same answers as the dedupe gives under the default limit
        engaged = self._population("degenerate", 11)
        assert engaged.pre_filter_batch() == out
        assert self._outcomes(engaged) == {"engaged": 1.0}

    @pytest.mark.parametrize("seed", [5, 2**31 + 9])
    @pytest.mark.parametrize("case", ["degenerate", "diverse", "small", "cols", "ghost"])
    def test_batch_fingerprints_equal_single(self, case, seed):
        plugin = self._population(case, seed)
        dm = plugin.device_manager
        def fresh():
            """The stored pods, plus pods no index holds a row for (the
            single-pod build); new objects each time, so no memo answers."""
            return plugin.listers.pods.list() + [
                make_pod(f"new{i}", namespace=ns, labels={"grp": "ab"[i % 2]},
                         requests={"cpu": f"{i + 1}00m"})
                for i, ns in enumerate(["default", "default", "ghost"])
            ]

        def agree():
            pods = fresh()
            want = [dm.verdict_fingerprint(p) for p in pods]
            assert dm.verdict_fingerprints(pods) == want
            return want

        assert sum(fp is not None for fp in agree()) >= 2
        # a deleted throttle leaves its column invalid, and status writes
        # move the epochs: the batch reads both as the single build does
        plugin.store.delete_throttle("default", "t2")
        plugin.run_pending_once()
        agree()
        # a second throttle over the "a" pods takes the freed column, so
        # those rows match two: t1's, then t3's
        plugin.store.create_throttle(_throttle("t3", cpu="20", grp="a"))
        plugin.run_pending_once()
        agree()
        # a column the index still matches but the device state holds
        # invalid (an upsert in flight) is left out of both
        ks = dm.throttle
        ks.thr_valid[ks.index.throttle_col("default/t1")] = False
        agree()

    def test_dedupe_fingerprints_in_one_batch(self, monkeypatch):
        plugin = self._population("diverse", 13)
        dm = plugin.device_manager
        single, batches = [], []
        real_one, real_many = dm.verdict_fingerprint, dm.verdict_fingerprints
        monkeypatch.setattr(dm, "verdict_fingerprint", lambda p: single.append(p) or real_one(p))
        monkeypatch.setattr(
            dm, "verdict_fingerprints", lambda ps: batches.append(len(ps)) or real_many(ps)
        )
        out = plugin.pre_filter_batch()
        assert single == [] and batches == [400]
        assert self._outcomes(plugin) == {"declined_groups": 1.0}
        assert len(out["schedulable"]) == 400
        uncached = self._population("diverse", 13)
        uncached.verdict_cache = None
        assert uncached.pre_filter_batch() == out

    def test_outcome_counter_is_scraped_from_metrics(self):
        import urllib.request

        from kube_throttler_tpu.server import ThrottlerHTTPServer

        plugin = self._population("diverse", 17)
        srv = ThrottlerHTTPServer(plugin, port=0)
        srv.start()
        try:
            base = f"http://127.0.0.1:{srv.port}"
            req = urllib.request.Request(f"{base}/v1/prefilter-batch", data=b"{}", method="POST")
            with urllib.request.urlopen(req, timeout=30) as resp:
                assert resp.status == 200
            with urllib.request.urlopen(f"{base}/metrics", timeout=30) as resp:
                text = resp.read().decode()
        finally:
            srv.stop()
        assert 'kube_throttler_batch_dedupe_total{outcome="declined_groups"} 1' in text


# --------------------------------------------------------------------------
# epoch-bump completeness: mutation sweep, cache ≡ recompute
# --------------------------------------------------------------------------


class TestEpochBumpCompleteness:
    @pytest.mark.parametrize("seed", [7, 19, 31])
    def test_mutation_sweep_cache_equals_recompute(self, seed):
        """Every mutation class that can change a verdict, in a seeded
        mix; after each one the cached path must agree with the uncached
        recompute for a probe population spanning matched/unmatched
        shapes. A missing epoch bump anywhere = a stale verdict here."""
        rng = random.Random(seed)
        store = Store()
        plugin = _plugin(
            store,
            policies=[{"name": "p0", "preemptionEnabled": True, "minPriorityGap": 1}],
        )
        assert plugin.verdict_cache is not None
        store.create_namespace(Namespace("default"))

        probes = [
            make_pod(
                f"probe{i}",
                labels={"grp": "ab"[i % 2]},
                requests={"cpu": f"{(i % 5 + 1)}00m"},
            )
            for i in range(6)
        ]
        reserved: list = []
        gangs: list = []

        def check():
            plugin.run_pending_once()  # status flips land (epoch-covered)
            for pod in probes:
                fresh = plugin._pre_filter_uncached(pod)
                for _ in range(2):  # miss-then-hit: both must match fresh
                    got = plugin.pre_filter(pod)
                    assert got.code == fresh.code, (pod.key, got.reasons, fresh.reasons)
                    assert norm(got.reasons) == norm(fresh.reasons), pod.key
            # the batch dedupe's fingerprints agree with the single-pod ones
            dm = plugin.device_manager
            listed = plugin.listers.pods.list() + probes
            assert dm.verdict_fingerprints(listed) == [dm.verdict_fingerprint(p) for p in listed]

        def op_throttle_edit():
            name = f"t{rng.randint(0, 3)}"
            thr = _throttle(name, cpu=f"{rng.randint(1, 6)}00m", grp=rng.choice("ab"))
            try:
                store.create_throttle(thr)
            except ValueError:
                cur = store.get_throttle("default", name)
                store.update_throttle(
                    Throttle(name=name, spec=thr.spec, status=cur.status)
                )

        def op_clusterthrottle_edit():
            name = f"ct{rng.randint(0, 1)}"
            ct = _cluster_throttle(
                name, cpu=f"{rng.randint(2, 8)}00m", grp=rng.choice("ab")
            )
            try:
                store.create_cluster_throttle(ct)
            except ValueError:
                cur = store.get_cluster_throttle(name)
                store.update_cluster_throttle(
                    ClusterThrottle(name=name, spec=ct.spec, status=cur.status)
                )

        def op_override_window():
            # an override that is active NOW halves (or floods) the
            # threshold; it reaches verdicts via the status write the
            # next reconcile stamps — which must bump the epoch
            cpu = rng.choice(["50m", "900m"])
            thr = _throttle(
                "t0",
                cpu="300m",
                grp="a",
                overrides=(
                    TemporaryThresholdOverride(
                        begin="2000-01-01T00:00:00Z",
                        end="2100-01-01T00:00:00Z",
                        threshold=ResourceAmount.of(requests={"cpu": cpu}),
                    ),
                ),
            )
            try:
                store.create_throttle(thr)
            except ValueError:
                cur = store.get_throttle("default", "t0")
                store.update_throttle(
                    Throttle(name="t0", spec=thr.spec, status=cur.status)
                )

        def op_pod_churn():
            if rng.random() < 0.6 or not store.list_pods("default"):
                store.create_pod(
                    make_pod(
                        f"w{rng.randrange(10**6)}",
                        labels={"grp": rng.choice("ab")},
                        requests={"cpu": f"{rng.randint(1, 4)}00m"},
                        node_name="n1",
                        phase="Running",
                    )
                )
            else:
                doomed = rng.choice(store.list_pods("default"))
                store.delete_pod("default", doomed.name)

        def op_reserve():
            pod = make_pod(
                f"r{rng.randrange(10**6)}",
                labels={"grp": rng.choice("ab")},
                requests={"cpu": f"{rng.randint(1, 3)}00m"},
            )
            if plugin.reserve(pod).is_success():
                reserved.append(pod)

        def op_unreserve():
            if reserved:
                plugin.unreserve(reserved.pop(rng.randrange(len(reserved))))

        def op_gang_reserve():
            gid = f"default/g{rng.randrange(10**6)}"
            members = [
                make_pod(
                    f"gm{rng.randrange(10**6)}",
                    labels={"grp": rng.choice("ab")},
                    requests={"cpu": "50m"},
                )
                for _ in range(2)
            ]
            if plugin.reserve_gang(gid, members).is_success():
                gangs.append(gid)

        def op_gang_rollback():
            if gangs:
                plugin.unreserve_gang(gangs.pop(rng.randrange(len(gangs))))

        def op_policy_swap():
            plugin.set_policy_specs(
                [
                    {
                        "name": f"p{rng.randrange(10**6)}",
                        "preemptionEnabled": bool(rng.getrandbits(1)),
                        "minPriorityGap": rng.randint(1, 3),
                    }
                ]
            )

        def op_preempt_cycle():
            members = [
                make_pod(
                    f"hi{rng.randrange(10**6)}",
                    labels={"grp": "a"},
                    requests={"cpu": "100m"},
                    priority=5,
                )
            ]
            # commit or infeasible-rollback — either way any evictions
            # land as pod deletes whose epoch bumps the probes see
            plugin.maybe_preempt_gang(f"default/pg{rng.randrange(10**6)}", members)

        def op_namespace_churn():
            if store.get_namespace("burst") is None:
                store.create_namespace(Namespace("burst"))
            else:
                store.delete_namespace("burst")

        ops = [
            op_throttle_edit,
            op_clusterthrottle_edit,
            op_override_window,
            op_pod_churn,
            op_pod_churn,
            op_reserve,
            op_unreserve,
            op_gang_reserve,
            op_gang_rollback,
            op_policy_swap,
            op_preempt_cycle,
            op_namespace_churn,
        ]
        for _ in range(40):
            rng.choice(ops)()
            check()


# --------------------------------------------------------------------------
# front-tier cache (scatter path)
# --------------------------------------------------------------------------


class TestFrontCache:
    def _build(self, n_shards=3):
        from kube_throttler_tpu.sharding.front import AdmissionFront
        from kube_throttler_tpu.sharding.ipc import LocalShard
        from kube_throttler_tpu.sharding.worker import ShardCore

        front = AdmissionFront(n_shards)
        cores = [ShardCore(i, n_shards, use_device=False) for i in range(n_shards)]
        for i, core in enumerate(cores):
            front.attach_shard(
                i, LocalShard(i, core, on_push=front.apply_status_push)
            )
        front.store.create_namespace(Namespace("default"))
        return front, cores

    @staticmethod
    def _teardown(front, cores):
        for core in cores:
            core.stop()
        front.stop()

    @staticmethod
    def _settle(front):
        assert front.drain(timeout=30.0)
        import time

        time.sleep(0.3)  # shard push loops flush on their own cadence

    @staticmethod
    def _fresh(front, pod):
        cache, front.verdict_cache = front.verdict_cache, None
        try:
            return front.pre_filter(pod)
        finally:
            front.verdict_cache = cache

    def test_scatter_cache_equals_recompute_under_churn(self):
        rng = random.Random(13)
        front, cores = self._build()
        try:
            assert front.verdict_cache is not None
            probes = [
                make_pod(
                    f"probe{i}",
                    labels={"grp": "ab"[i % 2]},
                    requests={"cpu": f"{(i % 4 + 1)}00m"},
                )
                for i in range(4)
            ]

            def check():
                self._settle(front)
                for pod in probes:
                    fresh = self._fresh(front, pod)
                    for _ in range(2):
                        got = front.pre_filter(pod)
                        assert got.code == fresh.code, (pod.key, got.reasons)
                        assert norm(got.reasons) == norm(fresh.reasons), pod.key

            for step in range(12):
                r = rng.random()
                if r < 0.4:
                    name = f"t{rng.randint(0, 4)}"
                    thr = _throttle(
                        name, cpu=f"{rng.randint(1, 5)}00m", grp=rng.choice("ab")
                    )
                    try:
                        front.store.create_throttle(thr)
                    except ValueError:
                        cur = front.store.get_throttle("default", name)
                        front.store.update_throttle(
                            Throttle(name=name, spec=thr.spec, status=cur.status)
                        )
                elif r < 0.7:
                    front.store.create_pod(
                        make_pod(
                            f"w{step}",
                            labels={"grp": rng.choice("ab")},
                            requests={"cpu": f"{rng.randint(1, 3)}00m"},
                            node_name="n1",
                            phase="Running",
                        )
                    )
                else:
                    front.reserve(
                        make_pod(
                            f"r{step}",
                            labels={"grp": rng.choice("ab")},
                            requests={"cpu": "100m"},
                        )
                    )
                check()
            # the cache is actually in play on this path
            assert front.verdict_cache.stats()[0] > 0
        finally:
            self._teardown(front, cores)

    def test_status_push_bumps_front_epoch(self):
        """A shard's status push (flip) must invalidate without any spec
        route: cache a verdict, flip the throttle via shard-side state,
        and pin that the pushed status re-derives the verdict."""
        front, cores = self._build(n_shards=1)
        try:
            front.store.create_throttle(_throttle(cpu="200m"))
            self._settle(front)
            pod = make_pod("p", labels={"grp": "a"}, requests={"cpu": "100m"})
            assert front.pre_filter(pod).code is StatusCode.SUCCESS
            # saturate the throttle: the shard reconciles, pushes the
            # flipped status back, and the Router's echo path must bump
            front.store.create_pod(
                make_pod(
                    "hog", labels={"grp": "a"}, requests={"cpu": "200m"},
                    node_name="n1", phase="Running",
                )
            )
            self._settle(front)
            st = front.pre_filter(pod)
            fresh = self._fresh(front, pod)
            assert st.code == fresh.code and st.code is not StatusCode.SUCCESS
        finally:
            self._teardown(front, cores)

    def test_structural_ops_bump_global_epoch(self):
        """Reshard cutover, finish/cancel, resync, and attach all change
        what a cached verdict means without touching per-key epochs —
        each must move the global counter (= fingerprint sum)."""
        from kube_throttler_tpu.sharding.ring import HashRing, RangeMove

        front, cores = self._build(n_shards=2)
        try:
            front.store.create_throttle(_throttle())
            self._settle(front)
            pod = make_pod("p", labels={"grp": "a"}, requests={"cpu": "100m"})

            def esum():
                fp = front._verdict_fingerprint(pod)
                assert fp is not None
                return fp[1]

            e0 = esum()
            move = RangeMove(index=0, lo=0, hi=1, src=0, dst=1)
            front.cutover_range(move)
            e1 = esum()
            assert e1 > e0
            front.finish_reshard(HashRing(2), 2)
            e2 = esum()
            assert e2 > e1
            front.cancel_reshard()
            e3 = esum()
            assert e3 > e2
            front.resync_shard(0)
            e4 = esum()
            assert e4 > e3
            front.attach_shard(0, front.shards[0])
            assert esum() > e4
        finally:
            self._teardown(front, cores)
