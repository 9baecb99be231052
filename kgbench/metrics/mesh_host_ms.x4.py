"""Input distribution and result egress: device idle inside the system's
``engine.shard``, ``engine.unshard`` and ``engine.redistinct`` spans (rows
put on the mesh, the KG taken off it and padded for its re-δ) per
``engine.create_kg`` in the traced window, ms."""
from kgbench.meshscopes import host_ms


def read(run):
    return host_ms(run)
