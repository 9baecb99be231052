"""Host encode (``Table.from_records`` with the session's vocabulary): the
mean of the benchmark's ``encode`` span over the window's batches, ms."""
from kgbench.layers import mean_ms


def read(run):
    return mean_ms(run.spans.durations("encode"))
