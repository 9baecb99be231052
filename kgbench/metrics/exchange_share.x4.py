"""Exchange (``plan/mesh.py``): device time of the ops under the system's
``exchange`` and ``distinct_global`` scopes (the ⋈'s all_gather or
all_to_all and the global δ's repartition) over the device's busy time in
the traced window, both averaged over the mesh's chips, %."""
from kgbench.meshscopes import EXCHANGE, share_pct


def read(run):
    return share_pct(run, EXCHANGE)
