"""Per-layer metric ``train_step_ms`` (see benchmark/readers.py)."""

from benchmark import readers


def read(run):
    return readers.train_step_ms(run)
