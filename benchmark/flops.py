"""What is the chip's and not a model's: the table of published peaks and
the roofline arithmetic, kept with the benchmark so that no later PR can
move it. The operations and bytes a model's block needs are its family's
counts (``benchmark/families/<family>.py``)."""

from __future__ import annotations

import json
import os

_HERE = os.path.dirname(os.path.abspath(__file__))


def peaks(device_kind: str) -> dict:
    """The chip's published peaks. A device that is not in the table is an
    error, not a default."""
    with open(os.path.join(_HERE, "peaks.json")) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise SystemExit(f"benchmark: no peaks for device kind "
                         f"{device_kind!r} in benchmark/peaks.json")
    return table[device_kind]


def roofline_share(flops: float, nbytes: float, seconds: float,
                   peak: dict) -> tuple:
    """Least time the chip could take over the time it took, in percent,
    and which roof bounds it."""
    t_flops = flops / peak["bf16_flops_per_s"]
    t_bytes = nbytes / peak["hbm_bytes_per_s"]
    bound = "compute" if t_flops >= t_bytes else "memory"
    return 100.0 * max(t_flops, t_bytes) / seconds, bound
