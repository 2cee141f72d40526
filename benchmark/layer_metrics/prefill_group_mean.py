"""Per-layer metric ``prefill_group_mean.*`` (see benchmark/inside.py)."""

from benchmark import inside, program_spans


def read(run):
    return inside.prefill_group_mean(program_spans.engine_spans())
