"""Faults planted under the timed path, for the test that sees ``correct``
come out false. ``daemon.py`` installs one when ``KTB_FAULT`` names it;
nothing else does.

- ``altered_answer``: every 5th PreFilter answer is replaced by its
  opposite, and one pod's verdict is flipped in every triage and tick answer;
- ``half_batch``: triage and tick answers leave out every other pod;
- ``state_unchanged``: Reserve and /v1/bind acknowledge and change
  nothing, so the state the next PreFilter reads has not moved.
"""


def install(name: str) -> None:
    from kube_throttler_tpu.plugin.framework import Status, StatusCode
    from kube_throttler_tpu.plugin.plugin import KubeThrottler
    from kube_throttler_tpu.server import ThrottlerHTTPServer

    if name == "altered_answer":
        pre_filter, batch, tick = (KubeThrottler.pre_filter, KubeThrottler.pre_filter_batch,
                                   KubeThrottler.full_tick_sharded)
        calls = [0]

        def altered_pre_filter(self, pod):
            status = pre_filter(self, pod)
            calls[0] += 1
            if calls[0] % 5:
                return status
            if status.code is StatusCode.SUCCESS:
                return Status(StatusCode.UNSCHEDULABLE_AND_UNRESOLVABLE,
                              ("throttle[active]=default/t0",))
            return Status(StatusCode.SUCCESS)

        def flip_one(out):
            key = next(iter(out["schedulable"]))
            out["schedulable"][key] = not out["schedulable"][key]
            return out

        KubeThrottler.pre_filter = altered_pre_filter
        KubeThrottler.pre_filter_batch = lambda self: flip_one(batch(self))
        KubeThrottler.full_tick_sharded = lambda self, *a: flip_one(tick(self, *a))
    elif name == "half_batch":
        batch, tick = KubeThrottler.pre_filter_batch, KubeThrottler.full_tick_sharded

        def halve(out):
            keys = list(out["schedulable"])
            out["schedulable"] = {k: out["schedulable"][k] for k in keys[::2]}
            return out

        KubeThrottler.pre_filter_batch = lambda self: halve(batch(self))
        KubeThrottler.full_tick_sharded = lambda self, *a: halve(tick(self, *a))
    elif name == "state_unchanged":
        post = ThrottlerHTTPServer._post

        def no_bind(self, h):
            if h.path == "/v1/bind":
                h._body()
                h._send(200, {"bound": "unchanged"})
                return
            post(self, h)

        ThrottlerHTTPServer._post = no_bind
        KubeThrottler.reserve = lambda self, pod, node="": Status(StatusCode.SUCCESS)
    else:
        raise ValueError(f"unknown fault {name!r}")
