"""Host work of a tick before the device: ``tick_snapshot`` + ``tick_encode``
per ``full_tick``, in ms."""


def read(run):
    _, n = run.span("full_tick")
    if not n:
        return None
    return (run.span("tick_snapshot")[0] + run.span("tick_encode")[0]) / n * 1e3
