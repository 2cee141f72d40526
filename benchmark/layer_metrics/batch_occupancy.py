"""Per-layer metric ``batch_occupancy.*`` (see benchmark/readers.py)."""

from benchmark import readers


def read(run):
    return readers.batch_occupancy(run)
