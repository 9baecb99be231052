"""Device execution: the share of the traced window in which no operation
ran on the chip, %: 1 - (union of op intervals) / window."""
from kgbench.layers import idle_share_pct


def read(run):
    return idle_share_pct(run)
