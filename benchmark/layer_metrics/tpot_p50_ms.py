"""Per-layer metric ``tpot_p50_ms.*``: the median beside the judged tail."""

from benchmark import readers


def read(run):
    return readers.median(run, "tpot_ms")
