"""Listing the pods from the informer's store in a triage call:
``store_list`` seconds over ``prefilter_batch`` calls, in ms. Nothing
where the daemon has no ``store_list`` phase."""


def read(run):
    total, calls = run.span("store_list")
    _, n = run.span("prefilter_batch")
    return total / n * 1e3 if calls and n else None
