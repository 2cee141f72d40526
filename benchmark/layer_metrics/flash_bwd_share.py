"""Per-layer metric ``flash_bwd_share`` (see benchmark/inside.py)."""

from benchmark import inside


def read(run):
    return inside.kernel_share(run.trace, ("flash_dq", "flash_dkv"))
