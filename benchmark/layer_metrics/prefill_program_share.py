"""Per-layer metric ``prefill_program_share.*`` (see benchmark/inside.py)."""

from benchmark import inside


def read(run):
    return inside.prefill_program_share(run.trace)
