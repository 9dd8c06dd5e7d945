"""Tracing/profiling subsystem (SURVEY §5 TPU-native equivalent): phase
histograms through the metrics registry, klog-style verbosity, the
/debug/flags/v endpoint, tracer wiring through plugin/controllers and the
server, spans in a profiler trace, and the compile counter."""

import glob
import json
import os
import threading
import urllib.request

from kube_throttler_tpu.api import (
    LabelSelector,
    ResourceAmount,
    Throttle,
    ThrottleSelector,
    ThrottleSelectorTerm,
    ThrottleSpec,
)
from kube_throttler_tpu.api.pod import Namespace, make_pod
from kube_throttler_tpu.engine.store import Store
from kube_throttler_tpu.metrics import Registry
from kube_throttler_tpu.plugin import KubeThrottler, decode_plugin_args
from kube_throttler_tpu.utils import tracing


def _t1_throttle():
    return Throttle(
        name="t1",
        spec=ThrottleSpec(
            throttler_name="kube-throttler",
            threshold=ResourceAmount.of(requests={"cpu": "100m"}),
            selector=ThrottleSelector(
                selector_terms=(
                    ThrottleSelectorTerm(LabelSelector(match_labels={"throttle": "t1"})),
                )
            ),
        ),
    )


def _plugin(use_device=False):
    store = Store()
    store.create_namespace(Namespace("default"))
    plugin = KubeThrottler(
        decode_plugin_args({"name": "kube-throttler", "targetSchedulerName": "my-scheduler"}),
        store,
        use_device=use_device,
    )
    return store, plugin


class TestHistogram:
    def test_observe_buckets_sum_count(self):
        reg = Registry()
        h = reg.histogram_vec("h_test_seconds", "help", ["phase"], buckets=[0.1, 1.0])
        h.observe({"phase": "x"}, 0.05)
        h.observe({"phase": "x"}, 0.5)
        h.observe({"phase": "x"}, 5.0)
        counts, total, count = h.collect()[("x",)]
        assert counts == [1, 2]  # cumulative: ≤0.1 → 1, ≤1.0 → 2
        assert count == 3 and abs(total - 5.55) < 1e-9

    def test_exposition_format(self):
        reg = Registry()
        h = reg.histogram_vec("h_fmt_seconds", "help", ["phase"], buckets=[0.1])
        h.observe({"phase": "p"}, 0.01)
        text = reg.exposition()
        assert '# TYPE h_fmt_seconds histogram' in text
        assert 'h_fmt_seconds_bucket{phase="p",le="0.1"} 1' in text
        assert 'h_fmt_seconds_bucket{phase="p",le="+Inf"} 1' in text
        assert 'h_fmt_seconds_count{phase="p"} 1' in text


class TestVerbosity:
    def test_set_get_and_gate(self):
        prev = tracing.set_verbosity(3)
        try:
            assert tracing.get_verbosity() == 3
            assert tracing.v_enabled(2) and tracing.v_enabled(3)
            assert not tracing.v_enabled(4)
        finally:
            tracing.set_verbosity(prev)


class TestPhaseTracer:
    def test_trace_records_and_snapshot(self):
        reg = Registry()
        tr = tracing.PhaseTracer(reg)
        with tr.trace("phase_a"):
            pass
        snap = tr.snapshot("phase_a")
        assert snap is not None and snap["count"] == 1
        assert tr.snapshot("never") is None
        assert "kube_throttler_phase_duration_seconds" in reg.exposition()

    def test_noop_tracer(self):
        tr = tracing.NoopTracer()
        with tr.trace("x"):
            pass
        assert tr.snapshot("x") is None


class TestWiring:
    def test_plugin_phases_land_in_registry(self):
        store, plugin = _plugin()
        store.create_throttle(
            Throttle(
                name="t1",
                spec=ThrottleSpec(
                    throttler_name="kube-throttler",
                    threshold=ResourceAmount.of(requests={"cpu": "100m"}),
                    selector=ThrottleSelector(
                        selector_terms=(
                            ThrottleSelectorTerm(LabelSelector(match_labels={"throttle": "t1"})),
                        )
                    ),
                ),
            )
        )
        plugin.run_pending_once()
        pod = make_pod("p1", labels={"throttle": "t1"}, requests={"cpu": "50m"})
        store.create_pod(pod)
        plugin.pre_filter(pod)
        plugin.reserve(pod)
        plugin.unreserve(pod)
        for phase in ("prefilter", "reserve", "unreserve", "reconcile"):
            snap = plugin.tracer.snapshot(phase)
            assert snap is not None and snap["count"] >= 1, phase
        text = plugin.metrics_registry.exposition()
        assert 'phase="prefilter"' in text

    def test_device_check_phase(self):
        store, plugin = _plugin(use_device=True)
        store.create_throttle(
            Throttle(
                name="t1",
                spec=ThrottleSpec(
                    throttler_name="kube-throttler",
                    threshold=ResourceAmount.of(requests={"cpu": "100m"}),
                    selector=ThrottleSelector(
                        selector_terms=(
                            ThrottleSelectorTerm(LabelSelector(match_labels={"throttle": "t1"})),
                        )
                    ),
                ),
            )
        )
        plugin.run_pending_once()
        pod = make_pod("p1", labels={"throttle": "t1"}, requests={"cpu": "50m"})
        store.create_pod(pod)
        plugin.pre_filter(pod)
        snap = plugin.tracer.snapshot("device_check")
        assert snap is not None and snap["count"] >= 1


class TestDebugFlagsEndpoint:
    def test_put_debug_flags_v(self):
        from kube_throttler_tpu.server import ThrottlerHTTPServer

        store, plugin = _plugin()
        server = ThrottlerHTTPServer(plugin, host="127.0.0.1", port=0)
        server.start()
        try:
            prev = tracing.get_verbosity()
            req = urllib.request.Request(
                f"http://127.0.0.1:{server.port}/debug/flags/v",
                data=b"4",
                method="PUT",
            )
            body = urllib.request.urlopen(req, timeout=5).read().decode()
            assert "verbosity to 4" in body
            assert tracing.get_verbosity() == 4
            tracing.set_verbosity(prev)
        finally:
            server.stop()


class TestProfilerSpans:
    def _trace_spans(self, tmp_path, body, names):
        """Run ``body`` under a CPU profiler session; return the host
        plane's events named in ``names``, one list a thread: [[(name,
        start, end, stats)]]. (Threads other tests left running may add
        spans of their own.)"""
        import jax
        from jax.profiler import ProfileData

        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 1
        jax.profiler.start_trace(str(tmp_path), profiler_options=options)
        try:
            body()
        finally:
            jax.profiler.stop_trace()
        (path,) = glob.glob(os.path.join(str(tmp_path), "plugins", "profile", "*", "*.xplane.pb"))
        lines = []
        for plane in ProfileData.from_file(path).planes:
            if plane.name != "/host:CPU":
                continue
            for ln in plane.lines:
                evs = [(e.name, e.start_ns, e.start_ns + e.duration_ns, dict(e.stats))
                       for e in ln.events if e.name in names]
                if evs:
                    lines.append(sorted(evs, key=lambda e: e[1]))
        return lines

    def test_spans_of_two_threads_nest_in_the_host_plane(self, tmp_path):
        tr = tracing.PhaseTracer(Registry())
        # both threads inside their spans at once, so each keeps a line
        # of its own (a finished thread's id may be reused)
        both_in = threading.Barrier(2, timeout=10)

        def request(rid):
            with tr.trace("outer", rid=rid):
                with tr.trace("inner"):
                    both_in.wait()

        def body():
            threads = [threading.Thread(target=request, args=(rid,)) for rid in (7, 8)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=10)
                assert not t.is_alive()

        lines = self._trace_spans(tmp_path, body, ("kt.outer", "kt.inner"))
        assert len(lines) == 2  # one line per thread
        rids = set()
        for evs in lines:
            (outer, inner) = evs
            assert outer[0] == "kt.outer" and inner[0] == "kt.inner"
            assert outer[1] <= inner[1] and inner[2] <= outer[2]  # nested
            rids.add(outer[3]["rid"])
        assert rids == {7, 8}
        # the histogram sink is unchanged: every span observed
        assert tr.snapshot("outer")["count"] == 2 and tr.snapshot("inner")["count"] == 2

    def test_no_session_builds_no_annotation(self, monkeypatch):
        built = []

        class Counting:
            def __init__(self, *args, **kwargs):
                built.append(args)

        monkeypatch.setattr(tracing, "TraceAnnotation", Counting)
        tr = tracing.PhaseTracer(Registry())
        for _ in range(100):
            with tr.trace("off", rid=1):
                pass
        assert built == []
        assert tr.snapshot("off")["count"] == 100


class TestServerSpans:
    def test_prefilter_request_observes_server_phases(self):
        from kube_throttler_tpu.server import ThrottlerHTTPServer

        store, plugin = _plugin()
        store.create_throttle(_t1_throttle())
        plugin.run_pending_once()
        pod = make_pod("p1", labels={"throttle": "t1"}, requests={"cpu": "50m"})
        store.create_pod(pod)
        server = ThrottlerHTTPServer(plugin, host="127.0.0.1", port=0)
        server.start()
        try:
            req = urllib.request.Request(
                f"http://127.0.0.1:{server.port}/v1/prefilter",
                data=json.dumps({"podKey": pod.key}).encode(),
                headers={"Content-Type": "application/json"},
                method="POST",
            )
            body = json.loads(urllib.request.urlopen(req, timeout=5).read())
        finally:
            server.stop()
        assert body["code"] == "Success"
        for phase in ("http_wait", "http_prefilter", "http_decode", "http_encode", "prefilter"):
            snap = plugin.tracer.snapshot(phase)
            assert snap is not None and snap["count"] >= 1, phase
        # the route span encloses the plugin's
        assert plugin.tracer.snapshot("http_prefilter")["sum"] >= plugin.tracer.snapshot(
            "prefilter")["sum"]
        assert not server._httpd.accepted  # every stamp was taken by its handler

    def test_route_phases(self):
        from kube_throttler_tpu.server import _route_phase

        assert _route_phase("/v1/prefilter-batch") == "http_prefilter_batch"
        assert _route_phase("/v1/tick?x=1") == "http_tick"
        assert _route_phase("/v1/objects") == "http_objects"
        assert _route_phase("/no/such/thing") == "http_other"


class TestStoreListSpan:
    def test_batch_triage_observes_store_list(self):
        store, plugin = _plugin(use_device=True)
        store.create_throttle(_t1_throttle())
        plugin.run_pending_once()
        store.create_pod(make_pod("p1", labels={"throttle": "t1"}, requests={"cpu": "50m"}))
        out = plugin.pre_filter_batch()
        assert out["schedulable"] == {"default/p1": True}
        snap = plugin.tracer.snapshot("store_list")
        assert snap is not None and snap["count"] >= 1


class TestCompileCounter:
    def test_counts_a_fresh_jit_compile(self):
        import jax
        import jax.numpy as jnp

        from kube_throttler_tpu.metrics import METRIC_NAMES, register_compile_metrics

        reg = Registry()
        register_compile_metrics(reg)
        fam = "kube_throttler_xla_compiles_total"
        assert fam in METRIC_NAMES

        def counts():
            reg.flush()
            return {k[0]: v for k, v in reg.counter_vec(fam, "", ["event"]).collect().items()}

        before = counts()
        assert set(before) == {"trace", "compile", "cache_load"}
        jax.jit(lambda x: x * 7 + 3)(jnp.arange(13)).block_until_ready()
        after = counts()
        assert after["compile"] >= before["compile"] + 1
        assert after["trace"] >= before["trace"] + 1
