"""Per-layer metric ``train_mfu`` (see benchmark/readers.py)."""

from benchmark import readers


def read(run):
    return readers.train_mfu(run)
