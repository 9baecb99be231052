"""Engine session: device idle inside the system's ``engine.ingest`` span
(append, plan-cache key, dispatch, overflow check, stats) per batch in the
traced window, ms."""
from kgbench.progtrace import engine_idle_ms


def read(run):
    return engine_idle_ms(run, ("engine.ingest",), per="engine.ingest")
