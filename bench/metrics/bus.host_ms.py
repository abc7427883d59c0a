"""Mean ``bus.pass`` span less its ``bus.solve`` spans (ms) per pass of the
cooperation bus: its host work (premask, vets, host packing, feedback).

Read from the traced run's program spans (``span_reduce.LAYER_NUMBERS``);
None without a trace or without its span or counter."""


def read(run):
    return None if run.spans is None else run.spans["layers"]["bus.host_ms"]
