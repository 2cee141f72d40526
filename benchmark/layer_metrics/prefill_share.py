"""Per-layer metric ``prefill_share.*`` (see benchmark/readers.py)."""

from benchmark import readers


def read(run):
    return readers.prefill_share(run)
