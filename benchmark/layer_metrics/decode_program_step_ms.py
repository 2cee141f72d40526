"""Per-layer metric ``decode_program_step_ms.*`` (see benchmark/inside.py)."""

from benchmark import inside


def read(run):
    return inside.decode_program_step_ms(run.trace)
