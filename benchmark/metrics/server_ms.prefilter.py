"""The daemon's HTTP handling of a PreFilter, measured inside the daemon:
``http_prefilter`` span mean minus ``prefilter`` span mean (decode, pod
lookup, encode and write), in ms. Nothing where the daemon has no
``http_prefilter`` span."""


def read(run):
    route, plugin = run.span_mean_ms("http_prefilter"), run.span_mean_ms("prefilter")
    return None if route is None or plugin is None else route - plugin
