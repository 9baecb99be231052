"""Host upload (``Table.from_codes`` of every source, until the device
holds them): the mean of the benchmark's ``upload`` span per rebuild, ms."""
from kgbench.layers import mean_ms


def read(run):
    return mean_ms(run.spans.durations("upload"))
