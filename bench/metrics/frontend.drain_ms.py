"""Mean ``service.drain`` span (ms) per quiet step (drift decision noop):
the service draining its queue of submitted events into the shadow.

Read from the traced run's program spans (``span_reduce.LAYER_NUMBERS``);
None without a trace or without its span or counter."""


def read(run):
    return None if run.spans is None else run.spans["layers"]["frontend.drain_ms"]
