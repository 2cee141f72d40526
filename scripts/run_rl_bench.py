"""RL plane throughput bench: vectorized rollouts + LearnerGroup
env-steps/s on a pixel-shaped (84x84) observation env.

Reference analog: the rllib suites in ``release/release_tests.yaml``
(Atari/MuJoCo-class throughput runs) — this gives the RL plane a
recorded perf number like train/serve/core have.

Usage (the mesh learner mode wants >1 device — use the virtual CPU
mesh):

    cd /root/repo && JAX_PLATFORMS=cpu \
      XLA_FLAGS=--xla_force_host_platform_device_count=8 \
      python scripts/run_rl_bench.py [round]

Writes RLBENCH_r{N}.json at the repo root.
"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

def main():
    rnd = sys.argv[1] if len(sys.argv) > 1 else "05"
    # the mesh learner mode wants 8 devices: the virtual CPU platform
    # of __graft_entry__.dryrun_multichip. Everything below runs in this
    # one process (the local runtime's workers are its threads).
    import __graft_entry__ as graft

    graft._force_cpu_platform(8)
    import ray_tpu
    from ray_tpu.rllib import IMPALAConfig

    ray_tpu.init(num_cpus=8, num_tpus=0)
    try:
        algo = (IMPALAConfig()
                .environment("PixelCartPole-v0")
                .rollouts(num_rollout_workers=2, num_envs_per_worker=8)
                .training(unroll_length=32, num_learners=2,
                          learner_mode="mesh", hidden=128, seed=0)
                .build())
        # warm one iteration (spawns workers, compiles the learner)
        t0 = time.monotonic()
        algo.train()
        warm_s = time.monotonic() - t0
        # run until BOTH floors are met: a minimum wall-clock (default
        # 30s — a 2s single-shot measurement is one scheduler draw, not
        # a throughput number) and a minimum iteration count (variance
        # needs samples). Per-iteration rates are recorded so the
        # artifact itself shows spread, not just the mean.
        min_elapsed = float(os.environ.get("RL_BENCH_MIN_ELAPSED_S", "30"))
        min_iters = int(os.environ.get("RL_BENCH_MIN_ITERS", "8"))
        steps_per_iter = 2 * 8 * 32     # workers * envs * unroll
        iter_rates = []
        t0 = time.monotonic()
        steps = 0
        while len(iter_rates) < min_iters or \
                time.monotonic() - t0 < min_elapsed:
            it0 = time.monotonic()
            algo.train()
            iter_rates.append(
                round(steps_per_iter / (time.monotonic() - it0), 1))
            steps += steps_per_iter
        el = time.monotonic() - t0
        algo.stop()
        mean = sum(iter_rates) / len(iter_rates)
        std = (sum((r - mean) ** 2 for r in iter_rates)
               / len(iter_rates)) ** 0.5
        out = {
            "metric": "rl_env_steps_per_sec",
            "value": round(steps / el, 1),
            "unit": "env-steps/s",
            "detail": {
                "env": "PixelCartPole-v0 (84x84 pixel obs)",
                "obs_dim": 84 * 84,
                "rollout_workers": 2,
                "envs_per_worker": 8,
                "unroll_length": 32,
                "learners": 2,
                "learner_mode": "mesh",
                "iters": len(iter_rates),
                "elapsed_s": round(el, 1),
                "first_iter_s": round(warm_s, 1),
                "iter_rates": iter_rates,
                "iter_rate_mean": round(mean, 1),
                "iter_rate_std": round(std, 1),
                "iter_rate_min": min(iter_rates),
                "iter_rate_max": max(iter_rates),
            },
        }
    finally:
        ray_tpu.shutdown()
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), f"RLBENCH_r{rnd}.json")
    with open(path, "w") as f:
        json.dump(out, f, indent=1)
    print(f"wrote {path}")
    print(json.dumps(out))


if __name__ == "__main__":
    main()
