"""Per-layer metric ``compiles_in_window`` (see benchmark/readers.py)."""

from benchmark import readers


def read(run):
    return readers.compiles_in_window(run)
