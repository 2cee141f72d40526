"""Per-layer metric ``generator_late_p99_ms`` (see benchmark/readers.py)."""

from benchmark import readers


def read(run):
    return readers.percentile_ms(run, 'generator_late_s', 99)
