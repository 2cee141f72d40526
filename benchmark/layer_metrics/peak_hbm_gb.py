"""Per-layer metric ``peak_hbm_gb.*`` (see benchmark/readers.py)."""

from benchmark import readers


def read(run):
    return readers.peak_hbm_gb(run)
