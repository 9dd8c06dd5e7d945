"""The plugin's PreFilter: ``prefilter`` span mean (verdict cache, host check)."""


def read(run):
    return run.span_mean_ms("prefilter")
