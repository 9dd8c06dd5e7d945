"""HTTP and JSON around the tick: client mean minus the ``full_tick`` span mean."""


def read(run):
    client, span = run.client_mean_ms("tick"), run.span_mean_ms("full_tick")
    return None if client is None or span is None else client - span
