"""Per-layer metric ``experts_touched_mean.*`` (see benchmark/experts.py)."""

from benchmark import experts, program_spans


def read(run):
    return experts.chunk_stat_mean(program_spans.engine_spans(),
                                   "experts_touched")
