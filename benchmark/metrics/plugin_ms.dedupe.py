"""The host dedupe attempt that precedes the device path in triage:
``batch_dedupe`` span mean, in ms. It fingerprints every pod and declines
when the mix is not degenerate or holds more than 50,000 pods."""


def read(run):
    return run.span_mean_ms("batch_dedupe")
