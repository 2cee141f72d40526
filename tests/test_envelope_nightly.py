"""Scalability envelope — the NIGHTLY tier (one order above CI smoke).

Reference analog: ``release/benchmarks/README.md:9-31`` — the reference
proves its envelope on real clusters nightly (40k actors, 1M queued
tasks, 10k args). This tier runs the same axes at 10x the CI smoke
sizes (2,000 actors, 200k queued tasks, 5,000 args) on a multi-raylet
cluster of external OS processes. Minutes, not seconds — selected only
by ``ci/run_ci.sh --nightly`` (``pytest -m nightly``).
"""

import time

import pytest

import ray_tpu
from ray_tpu.cluster_utils import Cluster
from ray_tpu.utils.config import get_config

# slow as well: an explicit `-m 'not slow'` on the command line REPLACES
# the addopts default (`-m 'not nightly'`), and a minutes-long envelope
# tier must never ride into a bounded default/tier-1 run that way
pytestmark = [pytest.mark.nightly, pytest.mark.slow]

# tier sizes are flags (RAY_TPU_ENVELOPE_NIGHTLY_* env overrides):
# defaults 2,000 actors / 1,000,000 queued / 5,000 args
_N_ACTORS = get_config().envelope_nightly_actors
_N_QUEUED = get_config().envelope_nightly_queued_tasks
_N_ARGS = get_config().envelope_nightly_task_args


@pytest.fixture(scope="module")
def big_cluster():
    ray_tpu.shutdown()
    # 90s node-death timeout (reference: ~30s health-check window on
    # dedicated multi-core hosts): this tier runs 2k worker processes on
    # whatever host CI gives it — a raylet PROCESS starved of cpu for
    # tens of seconds must not get its node declared dead and its
    # objects tombstoned (liveness beats also ride a dedicated GCS
    # connection so they never queue behind flood control traffic)
    c = Cluster(external_gcs=True, heartbeat_timeout_s=90.0)
    # 3 external raylets + the head: every data/control plane hop is a
    # real OS-process boundary
    c.add_node(num_cpus=4)
    for _ in range(3):
        c.add_node(num_cpus=4, external=True)
    c.wait_for_nodes(4)
    ray_tpu.init(address=c.gcs_address)
    yield c
    ray_tpu.shutdown()
    c.shutdown()


def test_2000_actors_alive(big_cluster):
    """2,000 concurrent trivial actors across 4 nodes (reference axis:
    40k cluster-wide on 64 hosts ~= 600/host; this is 500/host)."""
    @ray_tpu.remote(num_cpus=0)
    class A:
        def __init__(self, i):
            self.i = i

        def who(self):
            return self.i

    n = _N_ACTORS
    t0 = time.monotonic()
    actors = [A.remote(i) for i in range(n)]
    try:
        # generous: spawning 2k interpreter processes is fork-bound —
        # on a starved CI host the ramp alone can take >10 minutes
        got = ray_tpu.get([a.who.remote() for a in actors], timeout=1800)
        create_s = time.monotonic() - t0
        assert got == list(range(n))
        # second round-trip on live actors (steady-state health)
        got2 = ray_tpu.get([a.who.remote() for a in actors], timeout=600)
        assert got2 == got
        print(f"\n{n} actors created+called in {create_s:.1f}s")
    finally:
        # ALWAYS reap: 2k leaked actor workers would starve the
        # module's remaining tests of the whole host
        for a in actors:
            ray_tpu.kill(a)


def test_1m_queued_tasks_drain(big_cluster):
    """1,000,000 no-op tasks queued at once all complete — REFERENCE
    SCALE for this axis (release/benchmarks/README.md:30: 1M on one
    m4.16xlarge). Submitted in windows so the host never holds 1M
    in-flight refs' results unconsumed."""
    @ray_tpu.remote
    def nop(i):
        return i

    n = _N_QUEUED
    window = 250_000
    t0 = time.monotonic()
    done = 0
    first_window_submit_s = None
    while done < n:
        take = min(window, n - done)
        refs = [nop.remote(done + i) for i in range(take)]
        if first_window_submit_s is None:
            first_window_submit_s = time.monotonic() - t0
        out = ray_tpu.get(refs, timeout=1800)
        assert len(out) == take and out[0] == done \
            and out[-1] == done + take - 1
        done += take
    total_s = time.monotonic() - t0
    print(f"\n{n} tasks: first-window submit {first_window_submit_s:.1f}s, "
          f"drain {total_s:.1f}s ({n / total_s:.0f} tasks/s)")


def test_5000_object_args_to_one_task(big_cluster):
    """One task consuming 5,000 ObjectRef args (reference axis: 10k)."""
    n = _N_ARGS
    refs = [ray_tpu.put(i) for i in range(n)]

    @ray_tpu.remote
    def consume(*xs):
        return sum(xs)

    assert ray_tpu.get(consume.remote(*refs),
                       timeout=600) == sum(range(n))


def test_flagship_1b_dryrun_in_subprocess():
    """The 1.0B-param fsdp-8 sharding dryrun (own subprocess: it
    re-initializes the jax platform)."""
    import subprocess
    import sys

    out = subprocess.run(
        [sys.executable, "-c",
         "import __graft_entry__ as g; g.dryrun_multichip_1b(8)"],
        capture_output=True, text=True, timeout=1200,
        cwd=str(__import__('pathlib').Path(__file__).resolve().parents[1]))
    assert out.returncode == 0, out.stderr[-2000:]
    assert "dryrun 1b ok" in out.stdout


def test_cross_node_task_spray(big_cluster):
    """Tasks land on every node (placement actually spreads under
    load); 4,000 tasks report their NODE id — a single node passing
    this is impossible, unlike a pid count (one 4-cpu node spawns 4+
    workers on its own)."""
    @ray_tpu.remote
    def where():
        return ray_tpu.get_runtime_context().get_node_id()

    nodes = set(ray_tpu.get([where.remote() for _ in range(4000)],
                            timeout=600))
    # queue-depth spillback must spread the flood across every raylet
    assert len(nodes) == 4, f"flood stayed on {len(nodes)} node(s)"


def test_trace_context_survives_steady_actor_phase(big_cluster):
    """Round-9 tracing leg: the steady actor phase runs with tracing
    ENABLED and (a) a traced slice of the steady calls lands in the GCS
    TraceStore as ONE trace whose worker-side ``run:`` spans prove the
    context crossed real process boundaries at this scale, (b) the warm
    actor-location resolve rate (``envelope_actor_resolves_per_sec``)
    stays within 30% of the
    tracing-off rate measured seconds earlier in the same session. The
    bound is deliberately generous (nightly hosts are noisy); the tight
    <3% hot-path fence lives in tests/test_tracing_plane.py.
    """
    from ray_tpu import api
    from ray_tpu.util import state as state_api
    from ray_tpu.util import tracing

    @ray_tpu.remote(num_cpus=0)
    class A:
        def __init__(self, i):
            self.i = i

        def who(self):
            return self.i

    n = _N_ACTORS
    actors = [A.remote(i) for i in range(n)]
    rt = api._runtime()
    try:
        assert ray_tpu.get([a.who.remote() for a in actors],
                           timeout=1800) == list(range(n))

        # baseline: warm location-resolve rate with tracing OFF
        t0 = time.monotonic()
        for a in actors:
            rt._actor_location(a._actor_id.hex())
        rate_off = n / max(time.monotonic() - t0, 1e-9)

        tracing.enable_tracing()
        try:
            # full steady round with tracing enabled; a bounded slice
            # rides inside ONE root span — the GCS store caps spans per
            # trace, and 2k submit+run pairs in a single trace would
            # blow past the cap while proving nothing more than 100 do.
            # The workers were spawned BEFORE enable_tracing(), so the
            # only way their spans exist at all is the wire context
            # carrying the switch across the RPC (execution_span
            # adoption) — exactly the survival this leg asserts.
            traced_slice = actors[:100]
            with tracing.span("nightly-steady") as root:
                ray_tpu.get([a.who.remote() for a in traced_slice],
                            timeout=600)
            ray_tpu.get([a.who.remote() for a in actors[100:]],
                        timeout=600)
            tid = root.trace_id

            # resolve rate again, tracing enabled
            t0 = time.monotonic()
            for a in actors:
                rt._actor_location(a._actor_id.hex())
            rate_on = n / max(time.monotonic() - t0, 1e-9)

            # context survived: worker-side run: spans for the traced
            # slice reached the GCS store under the SAME trace id
            trace = None
            deadline = time.monotonic() + 120
            while time.monotonic() < deadline:
                rt._metrics_pusher.flush_now()
                trace = state_api.get_trace(tid)
                if trace and any(s["name"].startswith("run:")
                                 for s in trace["spans"]):
                    break
                time.sleep(0.5)
            assert trace is not None, "trace never reached the GCS store"
            names = {s["name"] for s in trace["spans"]}
            assert any(nm.startswith("run:") for nm in names), names
            assert len({s["pid"] for s in trace["spans"]}) >= 2

            print(f"\nresolves/s: off={rate_off:.0f} on={rate_on:.0f} "
                  f"({rate_on / rate_off:.2f}x)")
            assert rate_on >= 0.7 * rate_off, (
                f"tracing regressed warm actor resolves: "
                f"{rate_on:.0f}/s vs {rate_off:.0f}/s tracing-off")
        finally:
            tracing.disable_tracing()
    finally:
        for a in actors:
            ray_tpu.kill(a)
