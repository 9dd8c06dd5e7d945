"""Pod verdicts returned by /v1/prefilter-batch, over the window up to the
end of the last call sent in it."""


def read(run):
    span = run.busy_until("triage")
    return run.items("triage") / span if span else None
