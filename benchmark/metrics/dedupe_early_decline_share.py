"""Batch dedupe attempts in the window declined from the pod count alone,
before any pod was listed (``declined_size`` of
``kube_throttler_batch_dedupe_total``), over all attempts, in %. Nothing
where the daemon made no attempt or has no such counter."""

FAMILY = "kube_throttler_batch_dedupe_total"


def read(run):
    total = run.counter(FAMILY)
    return 100.0 * run.counter(FAMILY, outcome="declined_size") / total if total else None
