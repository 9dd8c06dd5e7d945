"""The batch gather kernels with their dispatch and wait: ``batch_dispatch``
span mean, in ms."""


def read(run):
    return run.span_mean_ms("batch_dispatch")
