"""Test fixtures.

Tests run on a virtual 8-device CPU platform so multi-chip sharding paths
compile and execute without TPU hardware (same mechanism the driver's
``dryrun_multichip`` uses): JAX_PLATFORMS and XLA_FLAGS are set before
jax's first import, and every process of every test cluster inherits
them (``ray_tpu/_private/accelerator.py`` honours an explicit
``JAX_PLATFORMS=cpu``, whatever a worker is granted).

Analog of the reference's ``ray_start_regular`` fixture
(``python/ray/tests/conftest.py:410``) for the runtime tests.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
# Disable the host memory monitor in tests: a CI host already above the
# 95% kill threshold would otherwise see random worker kills. The OOM
# tests opt back in explicitly.
os.environ.setdefault("RAY_TPU_MEMORY_USAGE_THRESHOLD", "0")
# Per-node dashboard agents default OFF in tests (a process per node in
# every throwaway cluster); test_dashboard_agent opts back in.
os.environ.setdefault("RAY_TPU_DASHBOARD_AGENT_ENABLED", "0")
# Append (not guard): XLA's flag parsing is last-occurrence-wins, so this
# forces 8 virtual devices even if the env already set a different count.
os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "")
    + " --xla_force_host_platform_device_count=8"
).strip()

import jax  # noqa: E402

from ray_tpu.runtime.prestart import jax_backends_initialized  # noqa: E402

if jax_backends_initialized():
    raise RuntimeError(
        "a JAX backend was already initialized before conftest ran; tests "
        "cannot force the cpu platform. Run pytest in a fresh process.")

import pytest  # noqa: E402


@pytest.fixture
def ray_tpu_start():
    """Fresh runtime per test (local in-process cluster)."""
    import ray_tpu

    ray_tpu.shutdown()
    rt = ray_tpu.init(num_cpus=8, num_tpus=0)
    yield rt
    ray_tpu.shutdown()


@pytest.fixture(scope="module")
def v5e_2x2():
    """The devices of a TPU that is described, not attached: the chip's
    own compiler refuses for them what it would refuse on the chip (a
    kernel it cannot tile or partition, a step that does not fit HBM), at
    no chip time. Nothing runs on them, so nothing read off them is a
    result or a time. (Code that asks ``jax.default_backend()`` sees the
    CPU under test.)"""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")   # else it logs to /tmp
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - no TPU compiler installed
        pytest.skip(f"cannot describe a v5e:2x2 topology: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: the next run would warn
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield topo.devices
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="session")
def cpu_mesh_devices():
    devices = jax.devices()
    assert len(devices) >= 8, f"expected >=8 virtual devices, got {len(devices)}"
    return devices


# the formulations ``ops.moe.moe_ffn_dropless`` has, as a test forces them
EXPERT_FORMULATIONS = ("as-chosen", "every-held-expert", "sorted-loop",
                       "sorted-kernel")


@pytest.fixture
def expert_formulation(request, monkeypatch):
    """``moe_ffn_dropless`` held to one formulation whatever its token
    count (``request.param``, one of ``EXPERT_FORMULATIONS``; use with
    ``indirect=True``): every held expert over every row; the rows sorted
    by expert and the plain loop over the experts (what a program lowered
    off the TPU runs past the line); the sorted rows through the Pallas
    kernel, interpreted at 16 rows a tile (what a TPU runs there: off the
    TPU ``lax.platform_dependent`` takes the default lowering, so the
    kernel is put in its place). ``as-chosen`` leaves the rule alone."""
    import functools

    from ray_tpu.ops import moe

    which = request.param
    assert which in EXPERT_FORMULATIONS, which
    if which == "every-held-expert":
        monkeypatch.setattr(moe, "DENSE_MAX_TOKENS", 1 << 30)
    elif which != "as-chosen":
        monkeypatch.setattr(moe, "DENSE_MAX_TOKENS", 0)
    if which == "sorted-kernel":
        monkeypatch.setattr(
            moe, "grouped_expert_ffn_reference", functools.partial(
                moe.grouped_expert_ffn_kernel, tile=16, interpret=True))
    return which
