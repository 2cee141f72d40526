"""The device boundary: which process may open which TPU chip.

A chip belongs to one process at a time, and a process that has
initialised the TPU backend holds its chips until it exits. So the
driver, the GCS, the raylet and every worker without a ``TPU`` grant stay
off the device (``JAX_PLATFORMS=cpu``), chips are counted from their
device files and never through JAX, and a worker that is granted chips is
a process of its own that sees exactly those chips and nothing else
(reference analog: ``_private/accelerators/tpu.py`` — device-file
autodetect and ``TPU_VISIBLE_CHIPS`` per worker).

The one exception is a cluster started with ``JAX_PLATFORMS=cpu`` in its
environment (the test tier): every process honours it, granted or not.
"""

from __future__ import annotations

import glob
import math
import os
import sys

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
COMPILE_CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"

# Environment of a process that owns no chip.
UNGRANTED_ENV = {"JAX_PLATFORMS": "cpu"}

# libtpu's shape for a process that owns part of a host: chips per
# process along x,y,z. A grant of the whole host needs no shape.
_CHIP_BOUNDS = {1: "1,1,1", 2: "1,2,1", 4: "2,2,1", 8: "2,4,1"}


def cpu_only() -> bool:
    """True where the whole cluster was started with JAX held to the CPU."""
    return os.environ.get("JAX_PLATFORMS", "").strip().lower() == "cpu"


def tpu_chip_count() -> int:
    """Chips attached to this host, counted from their device files
    (``/dev/accel*`` on older generations, numbered VFIO groups since
    v5e). Initialising a backend to count them would take them."""
    if cpu_only():
        return 0
    accel = glob.glob("/dev/accel[0-9]*")
    if accel:
        return len(accel)
    try:
        return sum(name.isdigit() for name in os.listdir("/dev/vfio"))
    except FileNotFoundError:
        return 0


def chips_for(demand: dict) -> int:
    """Whole chips a resource demand needs: two processes cannot share
    a chip, so a fraction takes one."""
    return math.ceil(demand.get("TPU", 0) or 0)


def granted_env(chips: tuple, host_chips: int) -> dict:
    """Environment of a worker that owns ``chips`` (host-local indices)
    out of ``host_chips``. With the TPU platform named, a worker that
    finds no chip raises at its first use of JAX and never computes on
    the CPU."""
    if cpu_only():
        return dict(UNGRANTED_ENV)
    env = {"JAX_PLATFORMS": "tpu,cpu",
           "TPU_VISIBLE_CHIPS": ",".join(str(c) for c in chips),
           COMPILE_CACHE_ENV: compile_cache_dir()}
    if len(chips) < host_chips:
        bounds = _CHIP_BOUNDS.get(len(chips))
        if bounds is None:
            raise ValueError(
                f"a worker cannot own {len(chips)} of a host's "
                f"{host_chips} chips: a share is "
                f"{sorted(_CHIP_BOUNDS)} chips")
        env["TPU_CHIPS_PER_PROCESS_BOUNDS"] = bounds
        env["TPU_PROCESS_BOUNDS"] = "1,1,1"
    return env


def compile_cache_dir() -> str:
    """JAX's persistent compile cache: where the environment puts it,
    else one fixed directory in the checkout. The path is part of the
    cache key, so it never carries a pid, a time or a temp name."""
    return os.environ.get(COMPILE_CACHE_ENV) or os.path.join(
        _REPO_ROOT, ".jax_cache")


def enable_compile_cache() -> str:
    """Turn the persistent compile cache on for this process and the
    processes it starts, before its first compile. Where the environment
    names the directory JAX reads it there and nothing is set here."""
    path = compile_cache_dir()
    if not os.environ.get(COMPILE_CACHE_ENV):
        os.environ[COMPILE_CACHE_ENV] = path    # children inherit it
        jax = sys.modules.get("jax")
        if jax is not None:     # imported already: the env was read then
            jax.config.update("jax_compilation_cache_dir", path)
    return path
