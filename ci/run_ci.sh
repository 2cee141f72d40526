#!/usr/bin/env bash
# CI pipeline for ray_tpu (reference analog: the reference's ci/ +
# .buildkite pipelines — lint, C++ build + sanitizer suites, Python
# tests, multi-chip dryrun). Run locally with `bash ci/run_ci.sh`;
# .github/workflows/ci.yml invokes the same stages.
set -euo pipefail
cd "$(dirname "$0")/.."

stage() { echo; echo "=== CI stage: $1 ==="; }

# --nightly: ONLY the scaled scalability-envelope tier (minutes; the
# reference runs its envelope nightly on real clusters —
# release/benchmarks/README.md)
if [ "${1:-}" = "--nightly" ]; then
  stage "nightly scalability envelope (2k actors / 1M tasks / 5k args / 4 nodes)"
  python -m pytest tests/test_envelope_nightly.py -m nightly -q -s
  stage "nightly fork-server envelope (10k actors via preforked zygotes)"
  JAX_PLATFORMS=cpu python -m pytest tests/test_fork_envelope_nightly.py \
    -m nightly -q -s
  stage "nightly actor control plane (40k actors through the batched plane)"
  JAX_PLATFORMS=cpu python -m pytest tests/test_actor_plane_nightly.py \
    -m nightly -q -s
  stage "nightly serve soak (paged engine page/refcount flatness)"
  python -m pytest tests/test_serve_soak_nightly.py -m nightly -q -s
  stage "nightly serve autoscaling swing (square wave, pushed metrics)"
  JAX_PLATFORMS=cpu python -m pytest tests/test_serve_autoscale_nightly.py \
    -m nightly -q -s
  stage "nightly RL plane (pixel-obs throughput + learning)"
  # conftest forces the 8-device virtual CPU platform the mesh
  # learners need
  python -m pytest tests/test_rllib_extras.py -m nightly -q -s
  stage "nightly chaos matrix (raylet<->raylet + owner<->worker partitions)"
  # the full partition matrix holds each cut across >= 2 heartbeat
  # timeouts; the fast default tier runs only the driver<->GCS smoke
  JAX_PLATFORMS=cpu python -m pytest tests/test_chaos_partitions.py \
    -m nightly -q -s
  stage "nightly crash chaos soak (3 seeds x 300s, worker/replica/raylet/GCS + partitions)"
  # seeded process-kill + partition schedule over a mixed workload
  # (tasks, actors, serve) with conservation invariants: every
  # submitted call resolves or raises typed, nothing wedges, planes
  # stay intact. The script exits non-zero on any violation; the
  # report carries the per-class MTTR means and the health-probe
  # overhead.
  JAX_PLATFORMS=cpu python scripts/run_chaos_soak.py --seeds 0,1,2 \
    --duration 300 --out /tmp/chaos_nightly.json
  stage "nightly log plane (rotation holds disk bounded under worker churn at scale)"
  # a flood of printing workers must keep the node's log dir under the
  # rotation budget (max_bytes * (rotate_count+1) per proc) while every
  # line still reaches the store — proves capture rotation + monitor
  # cleanup hold disk bounded for the envelope tiers above
  JAX_PLATFORMS=cpu python -m pytest tests/test_log_plane_nightly.py \
    -m nightly -q -s
  stage "nightly memory leak soak (50k ref churn across 2 raylets, planted leak)"
  # churns >= 50k owned refs through put/submit/release cycles on a
  # two-external-raylet cluster: the leak detector must flag ZERO
  # false positives on the churn (refs die promptly), then flag
  # exactly the one deliberately-held ref with its creation call site
  JAX_PLATFORMS=cpu python -m pytest tests/test_memory_leak_nightly.py \
    -m nightly -q -s
  stage "nightly train telemetry overhead (stamping < 1% of a steady step)"
  # the decomposition summing to the step wall and goodput through a
  # real fit are in the default tier (tests/test_observability_train.py);
  # the overhead fence is a timing, so it runs here
  JAX_PLATFORMS=cpu python -m pytest tests/test_observability_train.py \
    -m nightly -q -s
  echo "nightly tiers: green"
  exit 0
fi

stage "lint (syntax + bytecode compile of every source)"
python -m compileall -q ray_tpu tests __graft_entry__.py chip_smoke.py

stage "native build (shm store, collectives, scheduler, capi, crc)"
make -C src -j"$(nproc)" all

if [ "${SKIP_SANITIZERS:-0}" != "1" ]; then
  stage "native sanitizer suites (ASan + TSan on the shm store)"
  make -C src sanitizers
fi

stage "python unit + integration tests"
python -m pytest tests/ -x -q

stage "multi-chip dryrun (virtual 8-device mesh: fsdp_tp/sp/ep/pp/hybrid)"
# SKIP_1B here: the flagship leg has its own gated stage below (the
# driver's dryrun runs it INLINE via dryrun_multichip's default)
SKIP_1B=1 JAX_PLATFORMS=cpu \
  XLA_FLAGS=--xla_force_host_platform_device_count=8 \
  python -c "import __graft_entry__ as g; g.dryrun_multichip(8)"

if [ "${SKIP_1B:-0}" != "1" ]; then
  stage "flagship-size dryrun (1.0B params, fsdp over 8 virtual devices; minutes)"
  python -c "import __graft_entry__ as g; g.dryrun_multichip_1b(8)"
fi

stage "single-chip compile check of the flagship entry"
JAX_PLATFORMS=cpu python - <<'EOF'
import jax
import __graft_entry__ as g
fn, args = g.entry()
jax.jit(fn).lower(*args).compile()
print("entry() compiles")
EOF

echo
echo "CI: all stages green"
