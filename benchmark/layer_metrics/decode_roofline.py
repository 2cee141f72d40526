"""Per-layer metric ``decode_roofline.*`` (see benchmark/readers.py)."""

from benchmark import readers


def read(run):
    return readers.decode_roofline(run)
