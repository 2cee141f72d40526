"""Scalability-envelope tests at CI scale.

Reference analog: ``release/benchmarks/`` (the scalability envelope —
many actors, deep task queues, many args/returns, large objects,
broadcast) and ``release/benchmarks/README.md``'s single-node
dimensions. The envelope at scale is ``scripts/run_envelope.py`` and the
nightly tier; these tests pin down the same AXES at sizes that run in
seconds, so a regression that breaks an axis (not just slows it) fails
the suite.
"""

import numpy as np
import pytest

import ray_tpu
from ray_tpu.utils.config import get_config

# CI-tier sizes are flags (RAY_TPU_ENVELOPE_* env overrides)
_N_ACTORS = get_config().envelope_actors
_N_QUEUED = get_config().envelope_queued_tasks
_N_ARGS = get_config().envelope_task_args


@pytest.fixture
def rt(ray_tpu_start):
    return ray_tpu_start


def test_many_actors_alive(rt):
    """Hundreds of concurrent trivial actors on one node
    (envelope axis: 40k actors cluster-wide)."""
    @ray_tpu.remote
    class A:
        def __init__(self, i):
            self.i = i

        def who(self):
            return self.i

    actors = [A.remote(i) for i in range(_N_ACTORS)]
    got = ray_tpu.get([a.who.remote() for a in actors])
    assert got == list(range(_N_ACTORS))
    for a in actors:
        ray_tpu.kill(a)


def test_deep_task_queue_drains(rt):
    """Tens of thousands of no-op tasks queued at once all complete
    (envelope axis: 1M queued on one node)."""
    @ray_tpu.remote
    def nop(i):
        return i

    n = _N_QUEUED
    refs = [nop.remote(i) for i in range(n)]
    out = ray_tpu.get(refs)
    assert out[0] == 0 and out[-1] == n - 1 and len(out) == n


def test_many_object_args_to_one_task(rt):
    """One task taking many ObjectRef args (envelope axis: 10k+;
    flag envelope_task_args)."""
    refs = [ray_tpu.put(i) for i in range(_N_ARGS)]

    @ray_tpu.remote
    def consume(*xs):
        return sum(xs)

    assert ray_tpu.get(consume.remote(*refs)) == sum(range(_N_ARGS))


def test_many_returns_from_one_task(rt):
    """One task returning 500 objects (envelope axis: 3k+)."""
    @ray_tpu.remote(num_returns=500)
    def produce():
        return tuple(range(500))

    refs = produce.remote()
    assert len(refs) == 500
    assert ray_tpu.get(refs[0]) == 0 and ray_tpu.get(refs[-1]) == 499


def test_many_objects_in_one_get(rt):
    """ray_tpu.get over 5,000 store objects (envelope axis: 10k+)."""
    refs = [ray_tpu.put(i) for i in range(5000)]
    assert ray_tpu.get(refs) == list(range(5000))


def test_large_object_integrity(rt):
    """A 256 MiB numpy object round-trips bit-exact through the shm
    store (envelope axis: 100 GiB max get; sized for CI)."""
    rng = np.random.default_rng(0)
    arr = rng.integers(0, 255, size=256 << 20, dtype=np.uint8)
    ref = ray_tpu.put(arr)
    out = ray_tpu.get(ref)
    assert out.nbytes == arr.nbytes
    # spot-check contents without a second full pass
    idx = rng.integers(0, arr.size, size=4096)
    np.testing.assert_array_equal(out[idx], arr[idx])


def test_nested_task_fanout(rt):
    """Tasks launching tasks: a two-level 20x20 fan-out completes
    (envelope axis: 10k simultaneous tasks via nested submission)."""
    @ray_tpu.remote
    def leaf(i, j):
        return i * 100 + j

    @ray_tpu.remote
    def branch(i):
        return sum(ray_tpu.get([leaf.remote(i, j) for j in range(20)]))

    total = sum(ray_tpu.get([branch.remote(i) for i in range(20)]))
    want = sum(i * 100 * 20 + sum(range(20)) for i in range(20))
    assert total == want
