"""Engine session: device-to-host syncs per ``KGEngine.ingest``, from the
system's own transfer ledger (``relalg.count_transfers``), mean over the
window's batches."""
from kgbench.layers import mean


def read(run):
    return mean(run.counters.get("host_syncs", ()))
