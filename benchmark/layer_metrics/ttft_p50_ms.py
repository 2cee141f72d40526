"""Per-layer metric ``ttft_p50_ms.*``: the median beside the judged tail."""

from benchmark import readers


def read(run):
    return readers.median(run, "ttft_ms")
