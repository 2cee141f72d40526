"""Per-layer metric ``engine_host_share.*`` (see benchmark/inside.py)."""

from benchmark import inside, program_spans


def read(run):
    return inside.engine_host_share(program_spans.engine_spans())
