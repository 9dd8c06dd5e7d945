"""HTTP and JSON around PreFilter: client mean minus the ``prefilter`` span mean."""


def read(run):
    client, span = run.client_mean_ms("prefilter"), run.span_mean_ms("prefilter")
    return None if client is None or span is None else client - span
