"""Mean count of ``bus.solve`` spans per ``bus.pass``: the LocalSearch
solves (first and feedback rounds) a pass of the cooperation bus makes.

Read from the traced run's program spans (``span_reduce.LAYER_NUMBERS``);
None without a trace or without its span or counter."""


def read(run):
    return None if run.spans is None else run.spans["layers"]["bus.solves_per_pass"]
