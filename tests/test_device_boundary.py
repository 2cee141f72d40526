"""The device boundary (``ray_tpu/_private/accelerator.py``): one process
per chip. Nothing here needs a chip: a node may declare made-up ``TPU``
counts, and what a granted worker would see is read from its
environment. The sandbox has no chip, which is exactly the host a
granted worker must refuse to compute on.
"""

import os
import subprocess
import sys
import time

import pytest

import ray_tpu
from ray_tpu._private import accelerator
from ray_tpu.cluster_utils import Cluster

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_DEVICE_VARS = ("JAX_PLATFORMS", "TPU_VISIBLE_CHIPS",
                "TPU_CHIPS_PER_PROCESS_BOUNDS", "TPU_PROCESS_BOUNDS",
                accelerator.COMPILE_CACHE_ENV)


@pytest.fixture(scope="module")
def tpu_cluster():
    """A four-"chip" node whose cluster was NOT started with
    JAX_PLATFORMS=cpu: workers are spawned from this process's
    environment, so the variable is dropped while the module runs (jax
    here latched it at import and is not affected)."""
    was = os.environ.pop("JAX_PLATFORMS")
    ray_tpu.shutdown()
    c = Cluster()
    c.add_node(num_cpus=8, num_tpus=4)
    ray_tpu.init(address=c.gcs_address)
    yield c
    ray_tpu.shutdown()
    c.shutdown()
    os.environ["JAX_PLATFORMS"] = was


def _release(actor, tpus_after: float):
    """Kill a chip-holding actor and wait until its chips are back: the
    grant's resources return only when its process has exited (and an
    actor placed on busy resources fails instead of queueing)."""
    ray_tpu.kill(actor)
    deadline = time.monotonic() + 30
    while ray_tpu.available_resources().get("TPU", 0) < tpus_after:
        assert time.monotonic() < deadline, ray_tpu.available_resources()
        time.sleep(0.05)


@ray_tpu.remote
class Probe:
    def env(self):
        from ray_tpu.runtime import prestart

        return {"pid": os.getpid(), "forked": prestart.CHILD_INFO is not None,
                **{k: os.environ.get(k) for k in _DEVICE_VARS}}

    def platform(self):
        import jax

        return jax.devices()[0].platform


def test_ungranted_worker_is_held_to_cpu(tpu_cluster):
    env = ray_tpu.get(Probe.remote().env.remote())
    assert env["JAX_PLATFORMS"] == "cpu"
    assert env["TPU_VISIBLE_CHIPS"] is None


def test_granted_workers_see_their_own_chips_only(tpu_cluster):
    one = Probe.options(num_tpus=1).remote()
    two = Probe.options(num_tpus=2).remote()
    e1, e2 = ray_tpu.get([one.env.remote(), two.env.remote()])
    assert e1["pid"] != e2["pid"] and not e1["forked"] and not e2["forked"]
    assert e1["JAX_PLATFORMS"] == e2["JAX_PLATFORMS"] == "tpu,cpu"
    chips1 = set(e1["TPU_VISIBLE_CHIPS"].split(","))
    chips2 = set(e2["TPU_VISIBLE_CHIPS"].split(","))
    assert len(chips1) == 1 and len(chips2) == 2 and not chips1 & chips2
    # a share of the host is a topology of its own
    assert e1["TPU_CHIPS_PER_PROCESS_BOUNDS"] == "1,1,1"
    assert e2["TPU_CHIPS_PER_PROCESS_BOUNDS"] == "1,2,1"
    assert e1["TPU_PROCESS_BOUNDS"] == "1,1,1"
    # and the chip's owner shares the driver's compile cache
    assert e1[accelerator.COMPILE_CACHE_ENV] == accelerator.compile_cache_dir()
    _release(one, 2)
    _release(two, 4)


def test_granted_worker_without_a_chip_raises(tpu_cluster):
    """No chip on this host: the worker's first use of JAX fails. It does
    not fall back to computing on the CPU."""
    actor = Probe.options(num_tpus=1).remote()
    with pytest.raises(Exception, match="(?i)tpu"):
        ray_tpu.get(actor.platform.remote(), timeout=60)
    _release(actor, 4)


def test_chips_come_back_when_their_worker_has_exited(tpu_cluster):
    """All four chips, twice over: the second round can only be granted
    once the first round's processes are gone."""
    seen = []
    for _ in range(2):
        actor = Probe.options(num_tpus=4).remote()
        env = ray_tpu.get(actor.env.remote(), timeout=60)
        assert env["TPU_VISIBLE_CHIPS"].split(",") == ["0", "1", "2", "3"]
        # the whole host: libtpu's own topology, no share to describe
        assert env["TPU_CHIPS_PER_PROCESS_BOUNDS"] is None
        seen.append(env["pid"])
        _release(actor, 4)
    assert seen[0] != seen[1]


def test_tpu_task_runs_in_a_process_of_its_own(tpu_cluster):
    """A task's grant ends with the task, but its process would keep the
    chip: the worker is retired, and the next task gets a fresh one."""
    @ray_tpu.remote(num_tpus=1)
    def probe():
        return os.getpid(), os.environ.get("TPU_VISIBLE_CHIPS")

    first = ray_tpu.get(probe.remote(), timeout=60)
    second = ray_tpu.get(probe.remote(), timeout=60)
    assert first[0] != second[0]
    assert first[1] is not None and second[1] is not None


def test_explicit_cpu_cluster_is_honoured_by_granted_workers(monkeypatch):
    """The test tier: JAX_PLATFORMS=cpu for the whole cluster holds every
    process to the CPU, whatever it is granted."""
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    ray_tpu.shutdown()
    c = Cluster()
    c.add_node(num_cpus=2, num_tpus=1)
    ray_tpu.init(address=c.gcs_address)
    try:
        actor = Probe.options(num_tpus=1).remote()
        env, platform = ray_tpu.get([actor.env.remote(),
                                     actor.platform.remote()], timeout=60)
        assert env["JAX_PLATFORMS"] == "cpu" and platform == "cpu"
        assert env["TPU_VISIBLE_CHIPS"] is None
    finally:
        ray_tpu.shutdown()
        c.shutdown()


@pytest.mark.parametrize("accel,vfio,want", [
    (["/dev/accel0", "/dev/accel1", "/dev/accel2", "/dev/accel3"], None, 4),
    ([], ["0", "vfio"], 1),
    ([], ["0", "1", "2", "3", "vfio"], 4),
    ([], None, 0),
])
def test_chips_are_counted_from_device_files(monkeypatch, accel, vfio, want):
    def listdir(path):
        assert path == "/dev/vfio"
        if vfio is None:
            raise FileNotFoundError(path)
        return vfio

    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    monkeypatch.setattr(accelerator.glob, "glob", lambda pattern: accel)
    monkeypatch.setattr(accelerator.os, "listdir", listdir)
    assert accelerator.tpu_chip_count() == want


def _python(code: str, **env) -> subprocess.CompletedProcess:
    base = {k: v for k, v in os.environ.items() if k not in _DEVICE_VARS}
    return subprocess.run(
        [sys.executable, "-c", code], env={**base, **env}, cwd=REPO,
        capture_output=True, text=True, timeout=120)


def test_autodetect_leaves_no_backend_initialised():
    """ray_tpu.init's chip count in a process that may use JAX freely:
    it still must not open the device, or no worker ever could."""
    proc = _python(
        "import jax\n"
        "from ray_tpu.api import _autodetect_tpu_count\n"
        "from ray_tpu.runtime.prestart import jax_backends_initialized\n"
        "print(_autodetect_tpu_count(), jax_backends_initialized())")
    assert proc.returncode == 0, proc.stderr
    assert proc.stdout.split() == ["0", "False"]    # no chip in the sandbox


def test_chip_smoke_fails_fast_off_tpu():
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py")],
        env={**os.environ, "JAX_PLATFORMS": "cpu"}, cwd=REPO,
        capture_output=True, text=True, timeout=60)
    assert proc.returncode != 0
    assert '"ok": true' not in proc.stdout
    assert "not 'tpu'" in proc.stderr


@pytest.mark.parametrize("placed", [True, False], ids=["env", "default"])
def test_compile_cache_is_placed_from_outside(tmp_path, placed):
    """JAX_COMPILATION_CACHE_DIR set: JAX reads it and the program sets
    nothing. Unset: one fixed path in the checkout, which child processes
    inherit."""
    env = {accelerator.COMPILE_CACHE_ENV: str(tmp_path)} if placed else {}
    proc = _python(
        "import os, jax\n"
        "from ray_tpu._private.accelerator import enable_compile_cache\n"
        "print(enable_compile_cache())\n"
        "print(jax.config.jax_compilation_cache_dir)\n"
        "print(os.environ['JAX_COMPILATION_CACHE_DIR'])", **env)
    assert proc.returncode == 0, proc.stderr
    want = str(tmp_path) if placed else os.path.join(REPO, ".jax_cache")
    assert proc.stdout.split() == [want] * 3


def test_gcs_that_was_stalled_itself_declares_no_node_dead():
    """A TPU backend initialising in any process freezes a v5e host for
    seconds (5.4 s measured): the GCS wakes to find every beat overdue,
    its own included. It must charge the stall to itself, not to the
    nodes — and still catch a node that is silent while it runs."""
    from ray_tpu.runtime.gcs import GcsServer

    # a timeout so long that the server's own health thread sleeps
    # through the test: the ticks below are the only ones
    gcs = GcsServer(heartbeat_timeout_s=1000.0).start()
    try:
        gcs.rpc_register_node(None, None, node_id="n1",
                              address=["127.0.0.1", 1], store_name="s",
                              resources={"CPU": 1.0}, labels={})
        node = gcs._nodes["n1"]
        node.last_heartbeat -= 2000.0       # as seen after a 2000 s freeze
        gcs._health_tick(overslept=2000.0)
        assert node.alive
        node.last_heartbeat -= 2000.0       # silent while the GCS ran
        gcs._health_tick(overslept=0.0)
        assert not node.alive
    finally:
        gcs.stop()
