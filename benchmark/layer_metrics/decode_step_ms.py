"""Per-layer metric ``decode_step_ms.*`` (see benchmark/readers.py)."""

from benchmark import readers


def read(run):
    return readers.decode_step_ms(run)
