"""The daemon's HTTP handling of a triage call (the all-pods answer),
measured inside the daemon: ``http_prefilter_batch`` span mean minus
``prefilter_batch`` span mean, in ms. Nothing where the daemon has no
``http_prefilter_batch`` span."""


def read(run):
    route, plugin = run.span_mean_ms("http_prefilter_batch"), run.span_mean_ms("prefilter_batch")
    return None if route is None or plugin is None else route - plugin
