"""WorkerGroup + BackendExecutor: rank actors for SPMD training.

Reference analog: ``python/ray/train/_internal/worker_group.py``
(``WorkerGroup:102``) and ``backend_executor.py`` (``BackendExecutor:66``,
``start:125``, ``start_training:424``). The reference's backend hook runs
``torch.distributed.init_process_group`` on every rank
(``train/torch/config.py:63``); the TPU-native analog wires each rank for
``jax.distributed.initialize`` — coordinator address published through the
GCS KV (replacing torch's TCP store rendezvous). On a single host the
ranks share one process group trivially and the mesh is per-rank local.
"""

from __future__ import annotations

import os
from typing import Any, Callable

import ray_tpu
from ray_tpu._private import accelerator
from ray_tpu.air.config import ScalingConfig
from ray_tpu.train.session import TrainContext, _init_session


@ray_tpu.remote
class _RankWorker:
    """One rank of the SPMD group (reference: per-rank train worker actor).
    """

    def __init__(self, rank: int, world_size: int, coordinator: str | None,
                 env: dict | None = None):
        self.rank = rank
        self.world_size = world_size
        self.coordinator = coordinator
        for k, v in (env or {}).items():
            os.environ[k] = str(v)
        # multi-host TPU bootstrap (jax.distributed): only when a
        # coordinator is published AND this process owns TPU chips
        if coordinator and not accelerator.cpu_only():
            import jax

            jax.distributed.initialize(
                coordinator_address=coordinator,
                num_processes=world_size, process_id=rank)

    def run(self, fn_blob_or_fn, config: dict, bus, trial_dir: str,
            restore_checkpoint: str | None = None, run_name: str = ""):
        import cloudpickle

        from ray_tpu.train import session as _session_mod

        fn = (cloudpickle.loads(fn_blob_or_fn)
              if isinstance(fn_blob_or_fn, bytes) else fn_blob_or_fn)
        ctx = TrainContext(rank=self.rank, world_size=self.world_size,
                           local_rank=self.rank, trial_dir=trial_dir,
                           experiment_name=run_name,
                           restore_checkpoint=restore_checkpoint)
        _init_session(ctx, bus)
        # trainer-config FLOPs declaration (the alternative to calling
        # session.set_flops_per_step() inside the loop)
        if isinstance(config, dict) and config.get("flops_per_step"):
            _session_mod.set_flops_per_step(
                config["flops_per_step"], config.get("peak_flops"))
        try:
            try:
                result = fn(config) if _wants_config(fn) else fn()
            finally:
                t = _session_mod.telemetry()
                if t is not None:
                    t.close()
        except BaseException as e:  # noqa: BLE001
            import traceback

            ray_tpu.get(bus.mark_done.remote(
                self.rank, error=f"{type(e).__name__}: {e}\n"
                                 f"{traceback.format_exc()}"))
            raise
        ray_tpu.get(bus.mark_done.remote(self.rank))
        return result

    def execute(self, fn, *args, **kwargs):
        return fn(*args, **kwargs)

    def ping(self):
        return self.rank


def _wants_config(fn) -> bool:
    import inspect

    try:
        return len(inspect.signature(fn).parameters) >= 1
    except (TypeError, ValueError):
        return True


class WorkerGroup:
    """N rank actors created per ScalingConfig (placement-group backed in
    the reference; resource demands express the same constraint here)."""

    def __init__(self, scaling: ScalingConfig, env: dict | None = None):
        self.scaling = scaling
        n = scaling.num_workers
        res = scaling.worker_resources()
        coordinator = None  # single-host: no jax.distributed rendezvous
        self.workers = [
            _RankWorker.options(
                num_cpus=res.get("CPU", 1),
                num_tpus=res.get("TPU") or None,
                resources={k: v for k, v in res.items()
                           if k not in ("CPU", "TPU")} or None,
            ).remote(rank, n, coordinator, env)
            for rank in range(n)
        ]

    def execute_async(self, fn, *args, **kwargs):
        return [w.execute.remote(fn, *args, **kwargs) for w in self.workers]

    def execute(self, fn, *args, **kwargs):
        return ray_tpu.get(self.execute_async(fn, *args, **kwargs))

    def healthy(self) -> bool:
        try:
            ray_tpu.get([w.ping.remote() for w in self.workers], timeout=10)
            return True
        except Exception:  # noqa: BLE001
            return False

    def shutdown(self):
        for w in self.workers:
            try:
                ray_tpu.kill(w)
            except Exception:  # noqa: BLE001
                pass


class BackendExecutor:
    """Launches the user training loop on all ranks and streams reports
    (reference: BackendExecutor.start_training:424)."""

    def __init__(self, scaling: ScalingConfig, env: dict | None = None):
        self.scaling = scaling
        self.group = WorkerGroup(scaling, env=env)
        from ray_tpu.train.session import _ReportBus

        self.bus = _ReportBus.remote(scaling.num_workers)

    def start_training(self, train_fn: Callable, config: dict,
                       trial_dir: str,
                       restore_checkpoint: str | None = None,
                       run_name: str = "") -> list:
        import cloudpickle

        blob = cloudpickle.dumps(train_fn, protocol=5)
        return [w.run.remote(blob, config, self.bus, trial_dir,
                             restore_checkpoint, run_name)
                for w in self.group.workers]

    def poll_reports(self) -> tuple[list, bool]:
        return ray_tpu.get(self.bus.drain.remote())

    def shutdown(self):
        self.group.shutdown()
        try:
            ray_tpu.kill(self.bus)
        except Exception:  # noqa: BLE001
            pass
