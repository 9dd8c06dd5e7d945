"""How long a call waited in the daemon before its handler ran (handler
thread start, request line and headers): ``http_wait`` mean over the
window's calls of every route, in ms. Nothing where the daemon has no
``http_wait`` phase."""


def read(run):
    return run.span_mean_ms("http_wait")
