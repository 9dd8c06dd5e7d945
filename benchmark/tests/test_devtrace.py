"""The trace reduction on a trace recorded here, on the CPU, and on hand
intervals."""

import time

import devtrace


def test_union_and_gaps_on_hand_intervals():
    assert devtrace.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]
    events = {"/device:TPU:0": [("a", 0, 30), ("b", 20, 40), ("a", 70, 90)],
              "/device:TPU:1": [("a", 0, 100)]}
    out = devtrace.reduce(events, (10, 110), [("triage", 0, 60), ("tick", 60, 200)])
    # device 0 busy 30 + 20 of the 100 ns window, device 1 90: mean 70
    assert out["busy_s"] == 70e-9 and out["window_s"] == 100e-9
    assert out["device_ops"][0] == ["a", (20 + 20 + 90) / 2 / 1e9]
    gaps = {(label, round(s * 1e9)) for label, s in out["idle_gaps"]}
    assert gaps == {("triage", 30), ("tick", 20), ("tick", 10)}


def test_reduces_a_cpu_trace(tmp_path):
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((256, 256))
    f(x).block_until_ready()
    t0 = time.time_ns()
    jax.profiler.start_trace(str(tmp_path))
    for _ in range(5):
        f(x).block_until_ready()
        time.sleep(0.01)
    jax.profiler.stop_trace()
    t1 = time.time_ns()
    path = devtrace.find_xplane(str(tmp_path))
    events, modules = devtrace.device_events(path)
    assert not modules
    assert list(events) == ["/host:CPU"] and events["/host:CPU"]
    out = devtrace.reduce(events, (0, t1 - t0), [("matmul", 0, t1 - t0)])
    assert 0 < out["busy_s"] < out["window_s"]
    assert any(name.startswith("dot") for name, _ in out["device_ops"])
    assert all(label == "matmul" for label, _ in out["idle_gaps"])
