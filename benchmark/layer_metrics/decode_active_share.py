"""Per-layer metric ``decode_active_share.*`` (see benchmark/inside.py)."""

from benchmark import inside, program_spans


def read(run):
    return inside.decode_active_share(program_spans.engine_spans())
