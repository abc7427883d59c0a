"""Mean over the ``shard.lanes`` counters of 1 - iters_sum / (lanes *
iters_max): the share of the batched shard solve's lanes that wait on the
slowest shard.

Read from the traced run's program spans (``span_reduce.LAYER_NUMBERS``);
None without a trace or without its span or counter."""


def read(run):
    return None if run.spans is None else run.spans["layers"]["shard.lane_idle_frac"]
