"""Mean ``controller.evaluate`` span (ms) per pass: the evaluation of a
balance decision (projected metrics, limits, difference to balance).

Read from the traced run's program spans (``span_reduce.LAYER_NUMBERS``);
None without a trace or without its span or counter."""


def read(run):
    return None if run.spans is None else run.spans["layers"]["controller.evaluate_ms"]
