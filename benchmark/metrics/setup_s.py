"""Set-up: from this process's start (state built, daemon spawned, ready,
statuses reconciled, warm-up done) to the start of the window."""


def read(run):
    return run.setup_s
