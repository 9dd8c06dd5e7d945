"""Seconds per /v1/tick: the window, up to the end of the last tick sent in
it, over the ticks sent in it."""


def read(run):
    span, n = run.busy_until("tick"), len(run.calls("tick"))
    return span / n if n else None
