"""Per-layer metric ``prefix_hit_share.*`` (see benchmark/readers.py)."""

from benchmark import readers


def read(run):
    return readers.prefix_hit_share(run)
