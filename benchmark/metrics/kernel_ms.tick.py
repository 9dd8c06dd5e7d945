"""The tick program with its upload and readback: ``tick_device`` per
``full_tick``, in ms."""


def read(run):
    total, _ = run.span("tick_device")
    _, n = run.span("full_tick")
    return total / n * 1e3 if n else None
