"""The daemon's HTTP handling of a tick, measured inside the daemon:
``http_tick`` span mean minus ``full_tick`` span mean, in ms. Nothing
where the daemon has no ``http_tick`` span."""


def read(run):
    route, plugin = run.span_mean_ms("http_tick"), run.span_mean_ms("full_tick")
    return None if route is None or plugin is None else route - plugin
