"""Relational operators: device time of XLA sort operations (the δ's and
the ⋈'s sorts) over the device's busy time in the traced window, %."""
from kgbench.layers import sort_share_pct


def read(run):
    return sort_share_pct(run)
