"""Mean ``shard.pass`` span less its ``shard.solve`` (ms) per sharded pass:
the host's share (partition, merge, never-worse guard, coordinator).

Read from the traced run's program spans (``span_reduce.LAYER_NUMBERS``);
None without a trace or without its span or counter."""


def read(run):
    return None if run.spans is None else run.spans["layers"]["shard.host_ms"]
