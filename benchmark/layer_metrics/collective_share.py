"""Per-layer metric ``collective_share`` (see benchmark/readers.py)."""

from benchmark import readers


def read(run):
    return readers.collective_share(run)
