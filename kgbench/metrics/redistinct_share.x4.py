"""Result egress (``api/engine.py``): device time of the ops under the
system's ``redistinct`` scope (the jitted δ over the KG gathered from the
mesh, on one chip) over the device's busy time in the traced window, both
averaged over the mesh's chips, %."""
from kgbench.meshscopes import share_pct


def read(run):
    return share_pct(run, ("redistinct",))
