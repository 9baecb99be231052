"""Engine session: device idle inside the system's ``engine.open`` and
``engine.create_kg`` spans (a session opened, planned, run and counted)
per rebuild in the traced window, ms."""
from kgbench.progtrace import engine_idle_ms


def read(run):
    return engine_idle_ms(run, ("engine.open", "engine.create_kg"),
                          per="engine.create_kg")
