"""Per-layer metric ``flash_fwd_share`` (see benchmark/inside.py)."""

from benchmark import inside


def read(run):
    return inside.kernel_share(run.trace, ("flash_fwd",))
