"""Per-layer metric ``device_idle_share.*`` (see benchmark/readers.py)."""

from benchmark import readers


def read(run):
    return readers.device_idle_share(run)
