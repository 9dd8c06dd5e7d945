"""HTTP and JSON around triage (the all-pods answer): client mean minus the
``prefilter_batch`` span mean."""


def read(run):
    client, span = run.client_mean_ms("triage"), run.span_mean_ms("prefilter_batch")
    return None if client is None or span is None else client - span
