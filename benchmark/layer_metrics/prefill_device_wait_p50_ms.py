"""Per-layer metric ``prefill_device_wait_p50_ms.*`` (see benchmark/inside.py)."""

from benchmark import inside, program_spans


def read(run):
    return inside.prefill_device_wait_p50_ms(program_spans.engine_spans())
