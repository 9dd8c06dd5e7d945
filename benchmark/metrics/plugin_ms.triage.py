"""The plugin's merge of the device verdicts into the per-pod answer:
``batch_merge`` span mean."""


def read(run):
    return run.span_mean_ms("batch_merge")
