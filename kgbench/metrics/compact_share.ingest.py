"""Relational operators: device time of the ops under the system's
``compact`` scope (``relalg.ops.compact``, inside whichever plan operator
called it) over the device's busy time in the traced window, %."""
from kgbench.progtrace import compact_share_pct


def read(run):
    return compact_share_pct(run)
