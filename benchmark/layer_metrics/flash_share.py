"""Per-layer metric ``flash_share`` (see benchmark/readers.py)."""

from benchmark import readers


def read(run):
    return readers.flash_share(run)
