"""Training telemetry: step-time decomposition, MFU, goodput buckets.

Reference analog: Ray Train's run/worker state tracking
(``python/ray/train/_internal/state/``) plus the goodput accounting the
reference leaves to external tools (TensorBoard profiles / cloud
goodput exporters). Here both ride the in-repo observability planes:
per-step series go out through the per-worker MetricsPusher (metrics
plane, PR 4), each step is a span under the run's trace (tracing plane,
PR 6), and cumulative run progress piggybacks on metric frames as an
annex so ``util.state.train_goodput`` / ``train_stragglers`` can answer
even after the windowed series expire.

One :class:`StepTelemetry` lives per rank session (created by
``session._init_session``). The contract with the training loop:

- ``session.timeit("data_wait")`` / ``"collective_sync"`` /
  ``"checkpoint"`` / ``"compute"`` context managers accumulate measured
  wall clock into the CURRENT step's buckets.
- ``session.report(...)`` closes the step: step wall = time since the
  previous report (or since the first instrumented activity, for step
  1). Whatever the explicit buckets did not cover is the residual —
  attributed to ``compile`` on the first step (jit tracing +
  compilation happen inside the first ``train_step``) and ``compute``
  afterwards. The decomposition therefore sums to the observed step
  wall BY CONSTRUCTION; the bench asserts it anyway.

Goodput buckets (cumulative, per rank):

- ``init``       session start -> first instrumented activity
- ``compile``    first-step residual
- ``productive`` per-step compute
- ``checkpoint`` save/restore wall inside steps
- ``stall``      data_wait + collective_sync
- ``restart``    elastic reform / trainer retry gaps (driver-recorded
                 via :func:`record_run_bucket`)

goodput_fraction = productive / total.
"""

from __future__ import annotations

import contextlib
import hashlib
import threading
import time
import uuid

GOODPUT_BUCKETS = ("init", "compile", "productive", "checkpoint",
                   "stall", "restart")
STEP_STAGES = ("data_wait", "compute", "collective_sync", "checkpoint",
               "compile")
# step stage -> goodput bucket
_STAGE_TO_BUCKET = {"data_wait": "stall", "collective_sync": "stall",
                    "compute": "productive", "checkpoint": "checkpoint",
                    "compile": "compile"}

ANNEX_PREFIX = "train/progress/"

# Peak dense bf16 TFLOP/s of one chip, keyed by jax's ``device_kind``
# (source: Google Cloud TPU documentation, the page of each generation).
# MFU here divides by it, and a TPU that is not in it is an error, never
# a default.
PEAK_TFLOPS = {"TPU v4": 275.0, "TPU v5 lite": 197.0, "TPU v5e": 197.0,
               "TPU v5": 459.0, "TPU v5p": 459.0, "TPU v6 lite": 918.0,
               "TPU v6e": 918.0}


def _enabled() -> bool:
    try:
        from ray_tpu.utils.config import get_config

        return bool(get_config().train_telemetry_enabled)
    except Exception:  # noqa: BLE001 - config unavailable during boot
        return True


def run_trace_id(run: str) -> str:
    """Deterministic trace id for a run: every rank's step spans land in
    the SAME trace without any rendezvous."""
    return hashlib.sha1(f"train:{run}".encode()).hexdigest()[:16]


def detect_peak_flops() -> float | None:
    """Per-chip peak FLOP/s of the local jax device. None off TPU —
    callers must declare a peak for MFU there."""
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        return None
    if dev.device_kind not in PEAK_TFLOPS:
        raise ValueError(
            f"no peak FLOP/s known for device kind {dev.device_kind!r}: "
            "add it, with its source, to PEAK_TFLOPS in "
            "ray_tpu/train/telemetry.py")
    return PEAK_TFLOPS[dev.device_kind] * 1e12


class StepTelemetry:
    """Per-rank step clock: bucket accumulation, residual attribution,
    MFU, goodput counters, progress annex, step spans, and the
    watchdog's in-flight token for the currently-running step."""

    def __init__(self, run: str, rank: int, *, world_size: int = 1,
                 flops_per_step: float | None = None,
                 peak_flops: float | None = None,
                 history_cap: int = 4096):
        self.run = run or "default"
        self.rank = int(rank)
        self.world_size = world_size
        self.flops_per_step = flops_per_step
        self.peak_flops = peak_flops
        self.step = 0
        self.history: list[dict] = []
        self._history_cap = history_cap
        self._created = time.monotonic()
        self._step_start: float | None = None
        self._buckets: dict[str, float] = {}
        self.goodput: dict[str, float] = {b: 0.0 for b in GOODPUT_BUCKETS}
        self._last_annex = 0.0
        self._inflight_token: int | None = None
        self._closed = False
        self._lock = threading.Lock()
        self._metrics = None   # lazily-built metric handles

    # -- declaration ---------------------------------------------------

    def set_flops_per_step(self, flops: float,
                           peak_flops: float | None = None) -> None:
        self.flops_per_step = float(flops)
        if peak_flops is not None:
            self.peak_flops = float(peak_flops)

    # -- bucket accumulation -------------------------------------------

    @contextlib.contextmanager
    def timeit(self, bucket: str):
        """Accumulate the block's wall clock into ``bucket`` for the
        current step. First use also marks the step start (pre-step
        time becomes the ``init`` goodput bucket)."""
        self._ensure_step_start()
        t0 = time.monotonic()
        try:
            yield
        finally:
            dt = time.monotonic() - t0
            with self._lock:
                self._buckets[bucket] = self._buckets.get(bucket, 0.0) + dt

    def mark_gap(self) -> None:
        """Restart the step clock at 'now', discarding the wall clock
        since the last report — for out-of-band gaps (elastic reform,
        retry pauses) that are already accounted to a bucket via
        :func:`record_run_bucket` and must not leak into the next step's
        residual."""
        if self._step_start is not None:
            self._step_start = time.monotonic()

    def _ensure_step_start(self) -> float:
        if self._step_start is None:
            now = time.monotonic()
            self._step_start = now
            self.goodput["init"] += now - self._created
            self._watchdog_begin()
        return self._step_start

    # -- step close (called from session.report) ----------------------

    def on_report(self, metrics: dict | None = None) -> dict:
        """Close the current step; returns the stamp dict
        ``{step, wall_s, stages, mfu}``. ``stages`` sums to ``wall_s``
        exactly (residual attribution)."""
        start = self._ensure_step_start()
        now = time.monotonic()
        wall = max(now - start, 0.0)
        with self._lock:
            stages = dict(self._buckets)
            self._buckets = {}
        explicit = sum(stages.values())
        residual = max(wall - explicit, 0.0)
        sink = "compile" if self.step == 0 else "compute"
        stages[sink] = stages.get(sink, 0.0) + residual
        self.step += 1
        mfu = None
        if self.flops_per_step and self.peak_flops and wall > 0:
            mfu = self.flops_per_step / wall / self.peak_flops
        stamp = {"step": self.step, "wall_s": wall, "stages": stages,
                 "mfu": mfu}
        if len(self.history) < self._history_cap:
            self.history.append(stamp)
        for stage, dt in stages.items():
            self.goodput[_STAGE_TO_BUCKET.get(stage, "productive")] += dt
        self._emit_metrics(stamp)
        self._emit_span(stamp, start_mono=start)
        self._publish_annex(stamp)
        # the watchdog token rolls over: this step finished, the next
        # one is now in flight (close() retires the dangling token)
        self._watchdog_end()
        self._step_start = now
        self._watchdog_begin()
        return stamp

    # -- emission ------------------------------------------------------

    def _metric_handles(self):
        if self._metrics is None:
            from ray_tpu.util import metrics as _m

            self._metrics = {
                "step_s": _m.histogram(
                    "train.step_s", "Training step wall clock (s)",
                    tag_keys=("run", "rank")),
                "stage_s": _m.histogram(
                    "train.step_stage_s",
                    "Per-stage step decomposition (s)",
                    tag_keys=("run", "rank", "stage")),
                "mfu": _m.gauge(
                    "train.mfu", "Model FLOPs utilization (0..1)",
                    tag_keys=("run", "rank")),
                "steps": _m.counter(
                    "train.steps_total", "Training steps completed",
                    tag_keys=("run", "rank")),
                "goodput": _m.counter(
                    "train.goodput_s",
                    "Run wall clock attributed per goodput bucket (s)",
                    tag_keys=("run", "rank", "bucket")),
            }
        return self._metrics

    def _emit_metrics(self, stamp: dict) -> None:
        from ray_tpu.util import metrics as _m

        if not (_m.enabled() and _enabled()):
            return
        h = self._metric_handles()
        tags = {"run": self.run, "rank": str(self.rank)}
        h["step_s"].observe(stamp["wall_s"], tags)
        h["steps"].inc(1, tags)
        for stage, dt in stamp["stages"].items():
            h["stage_s"].observe(dt, {**tags, "stage": stage})
        if stamp["mfu"] is not None:
            h["mfu"].set(stamp["mfu"], tags)
        for bucket, dt in stamp["stages"].items():
            h["goodput"].inc(dt, {**tags,
                                  "bucket": _STAGE_TO_BUCKET.get(
                                      bucket, "productive")})

    def _emit_span(self, stamp: dict, *, start_mono: float) -> None:
        from ray_tpu.util import tracing as _t

        if not _t.is_enabled():
            return
        wall_start = time.time() - (time.monotonic() - start_mono)
        parent = _t.SpanContext(trace_id=run_trace_id(self.run),
                                span_id=uuid.uuid4().hex[:16])
        step_ctx = _t.emit(
            "train.step", start=wall_start, duration=stamp["wall_s"],
            parent=parent, kind="train",
            attrs={"run": self.run, "rank": self.rank,
                   "step": stamp["step"], "mfu": stamp["mfu"]})
        offset = wall_start
        for stage, dt in sorted(stamp["stages"].items()):
            if dt <= 0:
                continue
            _t.emit(f"train.step.{stage}", start=offset, duration=dt,
                    parent=step_ctx, kind="train",
                    attrs={"run": self.run, "rank": self.rank,
                           "stage": stage})
            offset += dt

    def _publish_annex(self, stamp: dict, force: bool = False) -> None:
        if not _enabled():
            return
        now = time.monotonic()
        try:
            from ray_tpu.utils.config import get_config

            interval = float(get_config().train_progress_interval_s)
        except Exception:  # noqa: BLE001
            interval = 0.5
        if not force and now - self._last_annex < interval:
            return
        self._last_annex = now
        from ray_tpu.runtime import metrics_plane as _mp

        _mp.set_annex(
            f"{ANNEX_PREFIX}{self.run}/{self.rank}",
            {"run": self.run, "rank": self.rank, "step": self.step,
             "ts": time.time(), "step_s": stamp["wall_s"],
             "goodput": dict(self.goodput)})

    # -- watchdog ------------------------------------------------------

    def _watchdog_begin(self) -> None:
        from ray_tpu.util import tracing as _t

        self._inflight_token = _t.call_started(
            "train_step", f"{self.run}:rank{self.rank}:step{self.step + 1}")

    def _watchdog_end(self) -> None:
        from ray_tpu.util import tracing as _t

        _t.call_finished(self._inflight_token)
        self._inflight_token = None

    # -- teardown ------------------------------------------------------

    def close(self) -> None:
        """Retire the in-flight token and force a final annex publish so
        the last step/goodput totals are visible cluster-wide."""
        if self._closed:
            return
        self._closed = True
        self._watchdog_end()
        if self.step > 0 or any(v > 0 for v in self.goodput.values()):
            last = self.history[-1] if self.history else \
                {"wall_s": 0.0}
            self._publish_annex(last, force=True)


# ---------------------------------------------------------------------
# driver-side bucket recording (restart badput: trainer retries and
# elastic reforms happen OUTSIDE any rank session)

_driver_goodput: dict[tuple[str, str], dict[str, float]] = {}
_driver_lock = threading.Lock()


def record_run_bucket(run: str, bucket: str, seconds: float,
                      *, rank: str = "driver") -> None:
    """Attribute ``seconds`` of a run's wall clock to a goodput bucket
    from outside a rank session (DataParallelTrainer retry gaps,
    ElasticTrainer reforms). Rides the same counter + annex paths as
    per-step accounting so ``train_goodput`` sees one merged picture."""
    if seconds <= 0 or not _enabled():
        return
    run = run or "default"
    with _driver_lock:
        cum = _driver_goodput.setdefault(
            (run, rank), {b: 0.0 for b in GOODPUT_BUCKETS})
        cum[bucket] = cum.get(bucket, 0.0) + seconds
        snapshot = dict(cum)
    from ray_tpu.util import metrics as _m

    if _m.enabled():
        _m.counter("train.goodput_s",
                   "Run wall clock attributed per goodput bucket (s)",
                   tag_keys=("run", "rank", "bucket")).inc(
            seconds, {"run": run, "rank": rank, "bucket": bucket})
    from ray_tpu.runtime import metrics_plane as _mp

    _mp.set_annex(f"{ANNEX_PREFIX}{run}/{rank}",
                  {"run": run, "rank": rank, "step": 0,
                   "ts": time.time(), "step_s": 0.0,
                   "goodput": snapshot})
