"""Per-layer metric ``queue_wait_p50_ms.*`` (see benchmark/readers.py)."""

from benchmark import readers


def read(run):
    return readers.percentile_ms(run, 'queue_wait_s', 50)
