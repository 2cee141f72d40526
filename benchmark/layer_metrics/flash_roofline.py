"""Per-layer metric ``flash_roofline`` (see benchmark/readers.py)."""

from benchmark import readers


def read(run):
    return readers.flash_roofline(run)
