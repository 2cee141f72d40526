"""Training telemetry plane: step decomposition, MFU/goodput
accounting, straggler detection, and cluster-wide on-demand profiling
(reference: Ray Train's run-state tracking + the dashboard reporter
agent's py-spy profiling, ``dashboard/modules/reporter/``)."""

import json
import threading
import time
import urllib.request

import pytest

import ray_tpu
from ray_tpu import train as rtrain
from ray_tpu.air.config import FailureConfig, RunConfig, ScalingConfig
from ray_tpu.cluster_utils import Cluster
from ray_tpu.train.telemetry import StepTelemetry
from ray_tpu.util import state as state_api
from ray_tpu.util import tracing


@pytest.fixture
def rt(ray_tpu_start):
    return ray_tpu_start


@pytest.fixture
def cluster():
    ray_tpu.shutdown()
    c = Cluster()
    c.add_node(num_cpus=2)
    ray_tpu.init(address=c.gcs_address)
    yield c
    ray_tpu.shutdown()
    c.shutdown()


# ---------------------------------------------------------------------
# step decomposition + MFU (unit: no cluster)
# ---------------------------------------------------------------------

def test_step_decomposition_sums_to_wall():
    t = StepTelemetry("unit-decomp", 0)
    t.set_flops_per_step(1e9, peak_flops=1e12)

    with t.timeit("data_wait"):
        time.sleep(0.02)
    s1 = t.on_report({})
    # first-step residual is compile (jit tracing happens in step 1)
    assert s1["step"] == 1
    assert s1["stages"]["data_wait"] >= 0.02
    assert s1["stages"]["compile"] > 0
    assert "compute" not in s1["stages"]
    assert abs(sum(s1["stages"].values()) - s1["wall_s"]) < 1e-9
    assert s1["mfu"] == pytest.approx(1e9 / s1["wall_s"] / 1e12)

    with t.timeit("collective_sync"):
        time.sleep(0.01)
    s2 = t.on_report({})
    # steady-state residual is compute
    assert s2["stages"]["compute"] > 0
    assert "compile" not in s2["stages"]
    assert abs(sum(s2["stages"].values()) - s2["wall_s"]) < 1e-9

    # goodput buckets mirror the stage decomposition
    assert t.goodput["compile"] == pytest.approx(s1["stages"]["compile"])
    assert t.goodput["productive"] == pytest.approx(s2["stages"]["compute"])
    assert t.goodput["stall"] == pytest.approx(
        s1["stages"]["data_wait"] + s2["stages"]["collective_sync"])
    t.close()


@pytest.mark.nightly
@pytest.mark.slow
def test_stamping_costs_under_one_percent_of_a_steady_step():
    """The full stamping path (bucket close, residual split, metric
    emission, annex and watchdog) minus the disabled path's guard, each
    the least of five loops of 5,000, against the MEASURED steady step of
    the tiny Llama at 4 x 128 tokens on this host: never a difference of
    two noisy end-to-end rates. A timing, so it runs in the nightly tier
    (``ci/run_ci.sh --nightly``)."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import llama
    from ray_tpu.parallel.mesh import create_mesh
    from ray_tpu.train import session
    from ray_tpu.train.trainer import JaxTrainer, TrainConfig

    cfg = llama.llama_tiny()
    trainer = JaxTrainer(
        cfg, TrainConfig(mesh_axes={"dp": 1}, strategy="dp", warmup_steps=2,
                         total_steps=1000),
        mesh=create_mesh({"dp": 1}, devices=jax.devices()[:1]))
    state = trainer.init_state(jax.random.key(0))
    tokens = jax.random.randint(jax.random.key(1), (4, 129), 0,
                                cfg.vocab_size, dtype=jnp.int32)
    walls = []
    for _ in range(6):
        t0 = time.perf_counter()
        state, metrics = trainer.train_step(state, tokens)
        float(metrics["loss"])
        walls.append(time.perf_counter() - t0)
    step_wall = sum(walls[2:]) / len(walls[2:])     # past the compile

    def least(fn, iters=5000, k=5):
        best = float("inf")
        for _ in range(k):
            t0 = time.perf_counter()
            for _ in range(iters):
                fn()
            best = min(best, (time.perf_counter() - t0) / iters)
        return best

    probe = StepTelemetry("overhead-probe", 0, flops_per_step=1e9,
                          peak_flops=1e12, history_cap=8)
    try:
        hot = least(lambda: probe.on_report({}))
    finally:
        probe.close()
    cold = least(lambda: session.telemetry() is None)
    ratio = max(hot - cold, 0.0) / step_wall
    assert ratio < 0.01, (hot, cold, step_wall)


# ---------------------------------------------------------------------
# trainer integration: train.* series + goodput through the real fit
# ---------------------------------------------------------------------

def test_fit_emits_train_series_and_goodput(rt, tmp_path):
    def loop(config):
        for i in range(3):
            with rtrain.timeit("data_wait"):
                time.sleep(0.005)
            rtrain.report({"loss": 1.0 / (i + 1)})

    trainer = rtrain.DataParallelTrainer(
        loop,
        train_loop_config={"flops_per_step": 1e9, "peak_flops": 1e12},
        scaling_config=ScalingConfig(num_workers=2),
        run_config=RunConfig(storage_path=str(tmp_path),
                             name="telemetry-fit"))
    result = trainer.fit()
    assert result.error is None

    q = state_api.cluster_metrics("train.step_s",
                                  tags={"run": "telemetry-fit"},
                                  group_by=["rank"])
    ranks = {g["tags"]["rank"] for g in q.get("groups") or []}
    assert ranks == {"0", "1"}, q

    mfu = state_api.cluster_metrics("train.mfu",
                                    tags={"run": "telemetry-fit"},
                                    group_by=["rank"])
    assert mfu.get("groups"), "declared FLOPs must produce train.mfu"

    g = state_api.train_goodput("telemetry-fit")
    assert set(g["ranks"]) >= {"0", "1"}
    assert g["buckets"]["productive"] > 0
    assert g["buckets"]["stall"] > 0          # the data_wait sleeps
    assert g["buckets"]["compile"] > 0        # first-step residual
    assert 0 < g["goodput_fraction"] <= 1


def test_failure_retry_lands_in_restart_bucket(rt, tmp_path):
    """Satellite 3: a mid-run failure + FailureConfig retry books the
    retry gap as restart badput, and productive time resumes counting
    on the new attempt."""
    marker = tmp_path / "failed_once"

    def flaky(config):
        for i in range(3):
            rtrain.report({"i": i})
            if i == 1 and not marker.exists():
                marker.write_text("x")
                raise RuntimeError("transient-failure")

    trainer = rtrain.DataParallelTrainer(
        flaky, scaling_config=ScalingConfig(num_workers=1),
        run_config=RunConfig(storage_path=str(tmp_path / "exp"),
                             name="retry-run",
                             failure_config=FailureConfig(max_failures=1)))
    result = trainer.fit()
    assert result.error is None

    g = state_api.train_goodput("retry-run")
    assert g["buckets"]["restart"] > 0, g
    # the second attempt's steps 2..3 are steady-state -> productive
    assert g["buckets"]["productive"] > 0, g
    assert "driver" in g["ranks"]  # restart is driver-recorded


def test_elastic_reform_books_restart_and_resumes(tmp_path):
    """Satellite 3 (elastic flavor): a reform mid-run lands its wall
    clock in the restart bucket and step decomposition keeps summing
    after it."""
    import jax

    from ray_tpu.models import llama
    from ray_tpu.train.elastic import ElasticTrainer
    from ray_tpu.train.trainer import TrainConfig

    cfg = llama.LlamaConfig(
        vocab_size=64, d_model=16, n_layers=1, n_heads=2, n_kv_heads=2,
        d_ff=32, head_dim=8, remat="none")
    et = ElasticTrainer(
        cfg, TrainConfig(total_steps=50, warmup_steps=1),
        checkpoint_dir=str(tmp_path / "ck"), devices=jax.devices()[:2],
        checkpoint_every=2, run_name="elastic-telemetry")

    import numpy as np
    rng = np.random.default_rng(0)

    def data():
        while True:
            yield rng.integers(0, 64, size=(4, 9)).astype(np.int32)

    it = data()
    state = et.init_state(jax.random.key(0))
    state = et.fit(state, it, steps=2)       # checkpoint at step 2
    assert len(et.telemetry.history) == 2
    prod_before = et.telemetry.goodput["productive"]

    state = et.reform(devices=jax.devices()[:2])
    assert et.telemetry.goodput["restart"] == 0  # restart is run-level
    state = et.fit(state, it, steps=2)
    # productive-step time RESUMED counting after the reform
    assert et.telemetry.goodput["productive"] > prod_before
    for stamp in et.telemetry.history:
        assert abs(sum(stamp["stages"].values()) - stamp["wall_s"]) < 1e-9
    et.close()

    g = state_api.train_goodput("elastic-telemetry")
    assert g["buckets"]["restart"] > 0, g
    assert g["buckets"]["productive"] > 0, g


# ---------------------------------------------------------------------
# stragglers + watchdog
# ---------------------------------------------------------------------

def test_stragglers_and_watchdog_token():
    t0 = StepTelemetry("straggle-run", 0)
    t1 = StepTelemetry("straggle-run", 1)
    for _ in range(3):
        with t0.timeit("compute"):
            pass
        t0.on_report({})
    with t1.timeit("compute"):
        pass
    t1.on_report({})

    # each rank holds an in-flight watchdog token for its NEXT step, so
    # a stuck step surfaces in the stuck-call report
    def train_calls():
        return [c for c in tracing.local_stuck_calls(threshold_s=0.0)
                if c.get("kind") == "train_step"
                and str(c.get("detail", "")).startswith("straggle-run:")]

    calls = train_calls()
    assert len(calls) == 2, calls
    assert any(c["detail"] == "straggle-run:rank1:step2" for c in calls)

    # lagger publishes its final progress, then the front rank moves on
    # (lag_s = front rank's last stamp minus this rank's)
    t1.close()
    time.sleep(0.05)
    t0.close()
    # close retires the tokens (no dangling 'stuck' entries)
    assert not train_calls()

    rep = state_api.train_stragglers("straggle-run", skew_s=0.01)
    assert rep["max_step"] == 3
    lagger = rep["ranks"]["1"]
    assert lagger["behind_steps"] == 2
    assert lagger["straggler"] is True
    assert rep["stragglers"] == ["1"]
    assert rep["ranks"]["0"]["straggler"] is False


# ---------------------------------------------------------------------
# satellite 1: sampler lifecycle
# ---------------------------------------------------------------------

def test_sampler_reentrant_idempotent_joins():
    from ray_tpu.util.profiling import Sampler

    s = Sampler(hz=200)
    s.start()
    s.start()                                 # re-entrant
    time.sleep(0.1)
    s.stop()                                  # inner stop: still running
    assert any(t.name == "ray_tpu-sampler" for t in threading.enumerate())
    res = s.stop()                            # outer stop: joins
    assert res["samples"] > 0
    assert not any(t.name == "ray_tpu-sampler"
                   for t in threading.enumerate())
    again = s.stop()                          # extra stop: no-op
    assert again["samples"] == res["samples"]


def test_sampler_caps_stack_table():
    from ray_tpu.util.profiling import Sampler

    stop_evt = threading.Event()

    def busy():
        while not stop_evt.is_set():
            sum(range(64))

    th = threading.Thread(target=busy, daemon=True)
    th.start()
    try:
        s = Sampler(hz=200, max_stacks=1)
        s.start()
        time.sleep(0.3)
        res = s.stop()
    finally:
        stop_evt.set()
        th.join(timeout=5)
    # >= 2 distinct stacks (this thread + busy) against a 1-entry cap
    assert res["dropped_stacks"] > 0, res
    assert len(res["folded"].splitlines()) == 1


# ---------------------------------------------------------------------
# tentpole acceptance: cluster-wide profiling fan-out
# ---------------------------------------------------------------------

def test_profile_cluster_merges_multiple_processes(cluster):
    @ray_tpu.remote
    def spin(seconds):
        t0 = time.monotonic()
        n = 0
        while time.monotonic() - t0 < seconds:
            n += 1
        return n

    refs = [spin.remote(8) for _ in range(2)]
    time.sleep(0.8)                    # workers are now inside spin()
    prof = state_api.profile_cluster(duration_s=1.0, hz=50)
    assert prof["errors"] == {}, prof["errors"]
    pids = {m["pid"] for m in prof["procs"].values()
            if isinstance(m, dict) and m.get("pid")}
    # >= 3 distinct OS processes in ONE merged window (acceptance):
    # driver/gcs/raylet share the test process; each worker is its own
    assert len(pids) >= 3, prof["procs"]
    assert any(k.startswith("worker:") for k in prof["procs"])
    assert "driver" in prof["procs"] and "gcs" in prof["procs"]
    # merged collapsed stacks carry the per-proc prefix and the hot fn
    assert "spin" in prof["folded"]
    assert any(line.startswith("driver;")
               for line in prof["folded"].splitlines())
    for r in refs:
        ray_tpu.cancel(r, force=True)


def test_dashboard_profile_endpoints(cluster):
    from ray_tpu.dashboard import start_dashboard, stop_dashboard

    @ray_tpu.remote
    def napper():
        time.sleep(8)

    ref = napper.remote()
    time.sleep(0.8)
    dash = start_dashboard()
    try:
        with urllib.request.urlopen(
                dash.url + "/api/profile?duration=0.5&hz=50",
                timeout=60) as resp:
            prof = json.loads(resp.read())
        assert prof["procs"] and prof["folded"]
        # satellite 2: one-shot dump, no sampling window
        with urllib.request.urlopen(
                dash.url + "/api/profile/stacks?proc=driver",
                timeout=30) as resp:
            body = json.loads(resp.read())
        assert "MainThread" in json.dumps(body)
        with urllib.request.urlopen(
                dash.url + "/api/profile/stacks?proc=gcs",
                timeout=30) as resp:
            gcs_body = json.loads(resp.read())
        assert gcs_body, gcs_body
    finally:
        stop_dashboard()
        ray_tpu.cancel(ref, force=True)
