"""The span reduction on hand intervals, and on a trace recorded here, on
the CPU, around the daemon's own spans."""

import time

import pytest
import spantrace


def test_gap_labels_and_device_time_on_hand_intervals():
    events = {"/device:TPU:0": [("gather", 40, 60), ("gather", 90, 95)]}
    calls = [("triage", 0, 100)]
    # thread 1: a request whose store list runs under the first gap;
    # thread 2: a reconcile in flight at the second gap's middle
    spans = [
        [("http_prefilter_batch", 5, 99, {"rid": 3, "wait_us": 12}),
         ("prefilter_batch", 10, 98, {}),
         ("store_list", 12, 35, {}),
         ("batch_dispatch", 38, 62, {})],
        [("reconcile", 65, 80, {})],
    ]
    out = spantrace.reduce(events, (0, 100), calls, spans)
    gaps = {(label, round(s * 1e9)) for label, s in out["idle_gaps"]}
    assert gaps == {("triage/store_list", 40), ("triage/prefilter_batch+reconcile", 30),
                    ("triage/prefilter_batch", 5)}
    assert out["idle_by_span"]["triage/store_list"] == pytest.approx(40e-9)
    assert out["span_durations"]["store_list"] == pytest.approx([23e-9])
    assert out["span_durations"]["http_prefilter_batch"] == pytest.approx([94e-9])
    # the dispatch span holds the first gather whole; the batch span both
    assert out["device_in_span"]["batch_dispatch"] == pytest.approx([20e-9, 1])
    assert out["device_in_span"]["prefilter_batch"] == pytest.approx([25e-9, 1])
    assert out["device_in_span"]["reconcile"] == [0.0, 1]


def test_no_spans_leaves_the_route_label():
    out = spantrace.reduce({"/device:TPU:0": [("op", 10, 20)]}, (0, 30), [("tick", 0, 30)], [])
    assert {label for label, _ in out["idle_gaps"]} == {"tick"}
    assert out["span_durations"] == {} and out["device_in_span"] == {}


def test_reads_daemon_spans_from_a_cpu_trace(tmp_path):
    import devtrace
    import jax
    import jax.numpy as jnp

    from kube_throttler_tpu.metrics import Registry
    from kube_throttler_tpu.utils.tracing import PhaseTracer

    tracer = PhaseTracer(Registry())
    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((256, 256))
    f(x).block_until_ready()
    t0 = time.time_ns()
    jax.profiler.start_trace(str(tmp_path))
    for rid in range(3):
        with tracer.trace("http_tick", rid=rid, wait_us=5):
            with tracer.trace("tick_encode"):
                time.sleep(0.01)
            with tracer.trace("tick_device"):
                f(x).block_until_ready()
    jax.profiler.stop_trace()
    t1 = time.time_ns()
    path = devtrace.find_xplane(str(tmp_path))
    spans = spantrace.span_events(path)
    (line,) = [ln for ln in spans if ln[0][0] == "http_tick"]
    assert [p for p, *_ in line] == ["http_tick", "tick_encode", "tick_device"] * 3
    assert [st["rid"] for p, _, _, st in line if p == "http_tick"] == [0, 1, 2]
    events, _ = devtrace.device_events(path)
    out = spantrace.reduce(events, (0, t1 - t0), [("tick", 0, t1 - t0)], spans)
    assert len(out["span_durations"]["http_tick"]) == 3
    assert any(label == "tick/tick_encode" for label, _ in out["idle_gaps"])
    in_device, n = out["device_in_span"]["tick_device"]
    assert n == 3 and 0 < in_device <= sum(out["span_durations"]["tick_device"])
    assert out["device_in_span"]["tick_encode"][0] < in_device
