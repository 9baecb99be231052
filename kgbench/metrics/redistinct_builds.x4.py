"""Result egress: mesh re-δ programs the system built in the window per
rebuild, from its own counter (``plan_cache_stats()["redistinct_builds"]``);
``None`` where the system keeps no such counter."""
from kgbench.layers import mean


def read(run):
    return mean(run.counters.get("redistinct_builds", ()))
