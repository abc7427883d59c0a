"""Mean ``service.drift`` span (ms) per quiet step (drift decision noop):
the outlook, the shadow's per-tier sums and the drift decision.

Read from the traced run's program spans (``span_reduce.LAYER_NUMBERS``);
None without a trace or without its span or counter."""


def read(run):
    return None if run.spans is None else run.spans["layers"]["frontend.drift_ms"]
