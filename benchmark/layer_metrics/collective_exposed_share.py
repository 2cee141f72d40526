"""Per-layer metric ``collective_exposed_share`` (see benchmark/readers.py)."""

from benchmark import readers


def read(run):
    return readers.collective_exposed_share(run)
