"""Central flag registry.

Analog of the reference's ``RAY_CONFIG`` macro system
(``src/ray/common/ray_config_def.h`` — 209 typed flags, each overridable via a
``RAY_<name>`` environment variable). Here: typed flags declared once, each
overridable via ``RAY_TPU_<NAME>`` env vars or a ``system_config`` dict passed
to ``init()``.
"""

from __future__ import annotations

import os
from dataclasses import dataclass, fields
from typing import Any


def _env_override(name: str, default: Any) -> Any:
    raw = os.environ.get(f"RAY_TPU_{name.upper()}")
    if raw is None:
        return default
    ty = type(default)
    if ty is bool:
        return raw.lower() in ("1", "true", "yes", "on")
    if ty is int:
        return int(raw)
    if ty is float:
        return float(raw)
    return raw


@dataclass
class Config:
    """Runtime configuration flags. Defaults mirror the reference's semantics
    where applicable (e.g. 5 MiB transfer chunks, ``ray_config_def.h:355``)."""

    # --- scheduling ---
    # Hybrid policy spread threshold (reference: RAY_scheduler_spread_threshold).
    scheduler_spread_threshold: float = 0.5
    # Top-k fraction of nodes considered for random tie-break in hybrid policy.
    scheduler_top_k_fraction: float = 0.2
    scheduler_top_k_absolute: int = 1
    # Max tasks a worker lease request pipelines (reference lease batching).
    max_tasks_in_flight_per_worker: int = 10

    # --- object store ---
    # Per-node shared-memory store capacity (bytes). 0 = auto (30% of RAM).
    object_store_memory: int = 0
    # Objects smaller than this stay in the owner's in-process memory store.
    max_direct_call_object_size: int = 100 * 1024
    # Node-to-node transfer chunk size (reference: 5 MiB).
    object_transfer_chunk_size: int = 5 * 1024 * 1024
    # Fraction of store capacity at which LRU eviction kicks in.
    object_store_eviction_fraction: float = 0.8
    # Enable automatic spilling to disk under memory pressure.
    object_spilling_enabled: bool = True
    # Per-node dashboard agent process (reference: dashboard/agent.py);
    # observability queries bypass the raylet data plane through it.
    dashboard_agent_enabled: bool = True
    # Spill loop thresholds: start spilling above `high`, stop below `low`
    # (fractions of store capacity; reference:
    # RAY_object_spilling_threshold + LocalObjectManager).
    object_spilling_high_fraction: float = 0.8
    object_spilling_low_fraction: float = 0.5
    # Directory for spilled object files ("" = a per-raylet temp dir).
    object_spilling_directory: str = ""
    # --- object transfer (reference: ObjectManager chunked push/pull;
    # chunk size ray_config_def.h:355, PullManager admission control
    # pull_manager.h:52) ---
    object_transfer_chunk_bytes: int = 5 << 20
    # cap on bytes in flight across all pulls, as a fraction of the
    # destination store's capacity
    object_transfer_inflight_fraction: float = 0.25

    # --- memory monitor (reference: common/memory_monitor.h:52 +
    # raylet/worker_killing_policy*.cc) ---
    # Host memory-used fraction above which the raylet kills a worker to
    # relieve pressure (reference default 0.95). <= 0 disables.
    memory_usage_threshold: float = 0.95
    # Sampling period for the monitor loop.
    memory_monitor_refresh_ms: int = 250
    # OOM kills draw from their own per-task budget (reference:
    # RAY_task_oom_retries) so host pressure — possibly caused by an
    # unrelated process — cannot burn a task's max_retries lineage budget;
    # re-dispatch backs off exponentially while pressure persists.
    task_oom_retries: int = 3

    # --- distributed reference counting (reference:
    # core_worker/reference_count.h:61 — here: per-process local counts
    # reported to a centralized GCS refcount table keyed by client id;
    # zero-count primaries are released cluster-wide) ---
    ref_counting_enabled: bool = True
    # How often each process flushes its ref-count deltas / heartbeats.
    ref_flush_interval_s: float = 0.1
    # A client (driver or worker runtime) missing heartbeats this long is
    # dead: its ref contributions are dropped and its non-detached actors
    # killed (reference: GcsActorManager owner-death handling,
    # gcs_actor_manager.cc:632). Generous by design: a falsely-reaped
    # LIVE client loses objects and actors — under a 200k-task burst the
    # control plane can delay beat processing by tens of seconds.
    client_timeout_s: float = 45.0
    # Grace before contains-edge releases propagate to inner objects
    # (covers the borrower-incref-in-flight window).
    ref_release_grace_s: float = 0.5
    # Ray-client (client://) session survival after its last connection
    # drops: a reconnecting client resumes its refs/actors within this
    # window (reference: client proxier 30s reconnect grace).
    client_reconnect_grace_s: float = 30.0
    # Client-liveness heartbeat period (empty ref_update when idle).
    # 9x margin under client_timeout_s; at 2k workers/host this is the
    # dominant idle GCS load, so it must stay coarse.
    ref_heartbeat_interval_s: float = 5.0

    # --- resource sync (reference: ray_syncer.h:86 + the raylet
    # heartbeat period, ray_config_def.h raylet_report_resources_period) ---
    # Liveness heartbeat period; the VERSIONED resource syncer (event-
    # driven, below) carries the scheduling view, so this only bounds
    # failure detection.
    raylet_heartbeat_interval_s: float = 0.5
    # Debounce for event-driven resource pushes: a dispatch burst
    # becomes one push; scheduling-view staleness ~ RPC latency + this.
    resource_sync_push_delay_s: float = 0.01
    # Ready-queue depth beyond which a submitted task spills back
    # through the GCS view even though `available` looks healthy
    # (per-task acquire/release hides saturation from averages).
    scheduler_spillback_queue_depth: int = 32
    # Hard cap on cached per-address actor-call clients (leak backstop
    # for actor churn). Must exceed the driver's LIVE actor count:
    # evicting a live client drops in-flight frames and storms resends.
    actor_client_cache_size: int = 8192
    # --- submission pipeline ---
    # Max unacked actor tasks per actor (outbox + frames in flight).
    # Deep enough that the submitter never stalls waiting for enqueue
    # acks at 10k+ calls/s (reference analog: max_pending_calls /
    # the async gRPC stream depth in DirectActorTaskSubmitter).
    actor_submit_window: int = 4096
    # Tasks packed per lease push RPC (64 measured ~20% faster than 32
    # at 4 leases; reference analog: the lease request batching).
    lease_group_size: int = 64
    # In-flight push GROUPS per lease (hides the owner round trip;
    # deeper measured WORSE — pusher-thread churn).
    lease_pipeline_depth: int = 2
    # Max concurrent leases (pusher threads) per resource shape.
    max_leases_per_shape: int = 64
    # Cached per-address actor/worker RPC clients before closed-entry
    # eviction starts (hard cap is actor_client_cache_size).
    actor_client_soft_cap: int = 256
    # Pickle-once function-export cache entries per driver.
    fn_export_cache_size: int = 512
    # Unpickle-once function cache entries per worker.
    worker_fn_cache_size: int = 256
    # Linger before flushing a burst of put-pin reports (driver) /
    # task-return reports (worker) into one batched raylet RPC.
    put_report_linger_s: float = 0.0005
    # Task events per GCS flush, and the staleness-bounding timer.
    task_event_batch_size: int = 128
    task_event_flush_interval_s: float = 2.0
    # Max ids per C call into the shm store (bounds the process-shared
    # mutex hold; ShmObjectStore.BATCH_WINDOW).
    store_batch_window: int = 4096

    # --- workers ---
    num_workers: int = 0  # 0 = num_cpus
    # How long a spawned worker may take to register before its actor
    # creation is failed (reference: worker_register_timeout_seconds).
    # A worker that DIED is detected by process polling, not this; the
    # deadline only bounds hung-but-alive spawns, so it is generous —
    # actor-flood fork storms starve fresh interpreters for >30s.
    worker_register_timeout_s: float = 600.0
    worker_lease_timeout_s: float = 30.0
    # A granted lease whose owner never dials the worker's push port is
    # handed back after this long (runtime/worker_main.py watchdog).
    lease_never_dialed_timeout_s: float = 10.0
    # Server-side parking window for a lease request before the owner is
    # told to retry (runtime/lease.py; reference: worker lease backoff).
    lease_block_s: float = 5.0

    # --- worker prestart / fork-server (runtime/prestart.py; env
    # overrides RAY_TPU_PRESTART_* — reference analog:
    # worker_pool.h:354 PrestartWorkers + idle-worker eviction knobs) ---
    # Master switch for the zygote fork path AND the demand-driven
    # prestart policy loop. Every miss (template cold, dead, containered
    # env) degrades to the plain Popen spawn.
    prestart_enabled: bool = True
    # Warm floor: forked-but-idle workers the policy loop keeps alive
    # for the default env even with an empty lease queue.
    prestart_min_workers: int = 0
    # Cumulative spawn requests for one env key before its template is
    # created. A template costs one interpreter start + the preload
    # imports; short-lived pools (a test cluster spawning a handful of
    # workers) never amortize that, so the first N-1 requests cold-spawn
    # without paying it. Burst workloads (actor fan-out) cross the
    # threshold within the first wave. An explicit warm() call,
    # prestart_min_workers > 0, or a key that once crossed the threshold
    # (respawn after template death) bypasses the gate.
    prestart_spawn_threshold: int = 8
    # Policy tick: how often lease-queue depth is sampled into a
    # prestart/evict decision.
    prestart_policy_interval_s: float = 0.25
    # Idle workers beyond the demand-predicted target older than this
    # are evicted (0 disables idle eviction; env-key mismatch eviction
    # at the cap is separate and always on).
    prestart_idle_timeout_s: float = 300.0
    # Fork request/reply deadline on the template control pipe; on
    # expiry the template is presumed wedged and killed (cold fallback).
    prestart_fork_timeout_s: float = 15.0
    # Spawn burst cap per policy tick (keeps one tick from forking the
    # whole max_workers budget at once on a deep queue).
    prestart_max_forks_per_tick: int = 8
    # Live zygote templates per node (LRU-evicted beyond this): one per
    # runtime-env key in active use.
    prestart_max_templates: int = 4

    # --- fault tolerance ---
    task_max_retries: int = 3
    # Min seconds between lineage re-submissions of the same lost object
    # (and the grace before budget exhaustion is declared terminal). Must
    # exceed the longest expected task re-execution time.
    lineage_resubmit_grace_s: float = 60.0
    # Max lineage entries the owner keeps for reconstruction (reference:
    # RAY_max_lineage_bytes); oldest dropped beyond this.
    lineage_max_entries: int = 100_000
    # LEGACY-path tasks only (placement-constrained / lease fallbacks —
    # submitted to the raylet queue, where no lease connection watches
    # them): outputs with NO location after this grace are presumed lost
    # in flight and resubmitted from lineage. Lease-path tasks never use
    # this — their owner observes the lease break synchronously.
    task_pending_resubmit_grace_s: float = 20.0
    actor_max_restarts: int = 0
    health_check_period_s: float = 1.0
    health_check_failure_threshold: int = 5
    # --- control-plane RPC retry/backoff (ReconnectingRpcClient) ---
    # Total redial window after a connection loss before the failure is
    # surfaced to the caller.
    rpc_redial_window_s: float = 10.0
    # Hard cap on redial attempts inside the window (0 = window only).
    rpc_redial_max_attempts: int = 0
    # Exponential backoff between redials: initial delay, multiplier,
    # ceiling, and jitter fraction (reference: the gRPC client retry
    # policy's exponential backoff with jitter).
    rpc_backoff_initial_s: float = 0.05
    rpc_backoff_multiplier: float = 2.0
    rpc_backoff_max_s: float = 2.0
    rpc_backoff_jitter: float = 0.2

    # --- actor control plane (batched, pipelined creation/resolution;
    # reference analog: GcsActorManager batch scheduling + the GCS
    # pubsub-driven actor table in core_worker's ActorInfoAccessor) ---
    # Driver-side registration coalescer: linger before a burst of
    # create_actor calls is flushed as ONE register_actors RPC, and the
    # max actors packed per frame.
    actor_register_linger_s: float = 0.002
    actor_register_batch_size: int = 512
    # Unacked registrations in flight before create_actor blocks
    # (memory backstop: each entry carries the pickled creation spec).
    actor_register_window: int = 8192
    # GCS placement executor: bounded worker threads fanning host_actors
    # batches out per raylet (was: one daemon thread per actor), and the
    # max placements packed per host_actors RPC.
    gcs_placement_pool_size: int = 8
    gcs_placement_batch_size: int = 256
    # Driver subscribes to CH_ACTOR and resolves locations from the
    # pushed table (get_actor polling survives only as a gap fallback).
    actor_pubsub_enabled: bool = True
    # GCS-side per-subscriber coalesce window for CH_ACTOR events: an
    # actor_ready burst becomes one framed batch per subscriber instead
    # of one inline send_msg per actor per subscriber. 0 = inline.
    actor_pubsub_flush_s: float = 0.002
    # How long the driver waits on the pushed table before falling back
    # to one counted get_actor poll (covers events published before the
    # subscription landed or lost across a redial).
    actor_resolve_fallback_s: float = 1.0
    # Hard deadline on resolving an actor's location (pushed table wait
    # + fallback polls) before the call errors ActorUnavailableError.
    # Envelope floods raise this (RAY_TPU_ACTOR_RESOLVE_TIMEOUT_S): on
    # a saturated host the tail of a 500-actor wave can legitimately
    # take minutes to come ALIVE.
    actor_resolve_timeout_s: float = 60.0
    # Raylet-side linger coalescing worker actor_ready messages into one
    # actors_ready GCS ack batch.
    actor_ready_linger_s: float = 0.002
    # Nightly 40k control-plane axis (tests/test_actor_plane_nightly.py):
    # cumulative actors driven through the batched plane in windows.
    envelope_nightly_plane_actors: int = 40_000
    envelope_plane_window: int = 500

    # --- fault injection (runtime/fault_injection.py; env overrides
    # RAY_TPU_FAULT_INJECTION_* — the chaos tier's knobs) ---
    # Master switch: off = the plane is never consulted beyond one
    # boolean read per message.
    fault_injection_enabled: bool = False
    # Base seed for probabilistic rules (deterministic replay).
    fault_injection_seed: int = 0
    # Startup plan: inline JSON, or @/path/to/plan.json.
    fault_injection_plan: str = ""
    # Poll period for the GCS KV plan key (runtime open/heal switch).
    fault_injection_kv_poll_s: float = 0.25

    # --- TPU / device plane ---
    # Logical mesh axis names, outer to inner. ICI-contiguous inner axes.
    mesh_axis_names: str = "dp,fsdp,tp"
    # Default matmul precision for the device plane.
    default_matmul_precision: str = "bfloat16"
    # Checkpointing: async by default.
    async_checkpointing: bool = True

    # --- serve LLM engine (ray_tpu.serve.paged_llm) ---
    # Steady-state decode steps per device dispatch: large chunks
    # amortize per-dispatch overhead (throughput), small chunks
    # bound how long a new request waits behind in-flight work (TTFT).
    serve_decode_chunk: int = 16
    # Short chunk used while admissions are imminent (_use_drain_chunk).
    serve_drain_chunk: int = 8
    # KV page size (tokens) for the paged engine.
    serve_kv_page_size: int = 128
    # Prefix cache on shared prompt prefixes (chat/system prompts).
    serve_prefix_cache_enabled: bool = True
    # Continuous admission: the engine loop opens a timed admission
    # window between decode-chunk dispatches, so a request arriving
    # mid-chunk prefills behind ONE in-flight chunk instead of waiting
    # out the whole double-buffered pipeline (~2.5 chunks of
    # queue_wait). This is the fraction of the EMA chunk period the
    # window may wait before dispatching the next chunk (the remainder
    # covers dispatch overhead so the device never idles between chunks).
    serve_admission_window_frac: float = 0.75
    # Prefix-affinity routing: handles score replicas by the longest
    # cached prefix advertised in their pushed page-hash digests and
    # fall back to power-of-two-choices when nothing matches.
    serve_prefix_routing_enabled: bool = True
    # Min interval between a replica's prefix-digest annex publishes.
    serve_digest_publish_interval_s: float = 0.2
    # A digest older than this is ignored by the router (replica dead
    # or metrics plane partitioned — fall back to p2c).
    serve_digest_ttl_s: float = 5.0
    # Proactive replica health probing: the controller pings every
    # replica on this period and replaces ones that stop answering,
    # instead of waiting for a request to trip over the corpse.
    serve_health_probing_enabled: bool = True
    serve_health_probe_period_s: float = 0.5
    serve_health_probe_timeout_s: float = 1.0
    # Consecutive probe timeouts before a replica is declared dead
    # (a typed actor-death error from the runtime is immediate).
    serve_health_probe_failures: int = 3
    # Scale-down grace: a draining replica keeps serving its in-flight
    # requests (digest retracted, route unpublished) up to this long
    # before the controller kills it anyway.
    serve_drain_timeout_s: float = 5.0

    # --- envelope / benchmark tiers (tests/test_envelope*.py) ---
    envelope_actors: int = 200
    envelope_queued_tasks: int = 20_000
    envelope_task_args: int = 1000
    envelope_nightly_actors: int = 2_000
    envelope_nightly_queued_tasks: int = 1_000_000
    envelope_nightly_task_args: int = 5_000
    # Nightly fork-pool actor axis (tests/test_envelope_nightly.py):
    # actors created through the zygote fork path in one cluster.
    envelope_nightly_fork_actors: int = 10_000

    # --- observability ---
    metrics_report_interval_s: float = 2.0
    event_buffer_size: int = 10000
    log_level: str = "INFO"

    # --- cluster metrics plane (util/metrics.py + runtime/metrics_plane.py;
    # reference analog: the opencensus stats registry pushed to the node
    # metrics agent and scraped by Prometheus — here each process pushes
    # delta frames straight to the GCS time-series store) ---
    # Master switch for hot-path instrumentation AND the push loop.
    # RAY_TPU_METRICS_ENABLED=0 turns every timer into one cached
    # boolean read (the <3% overhead gate measures against this).
    metrics_enabled: bool = True
    # Delta-frame push period per process (driver / worker / raylet /
    # GCS self-ingest). Coarse by design: at 2k workers/host this is
    # idle control-plane load next to the ref heartbeat.
    metrics_push_interval_s: float = 2.0
    # Ring-buffer time-series store on the GCS: window width and how
    # many windows are kept per (metric, tags) series.
    metrics_window_s: float = 5.0
    metrics_windows: int = 60
    # Bounded pusher buffer: frames queued past this are DROPPED (the
    # plane is strictly best-effort — a slow/partitioned GCS must never
    # block or backpressure a hot path).
    metrics_push_buffer: int = 8
    # No reader: the envelope leg it sampled went with the pre-chip
    # benchmark (ROADMAP Queue 3 item 6 takes it next).
    bench_profile_enabled: bool = False

    # --- distributed tracing plane (util/tracing.py; reference analog:
    # OpenTelemetry spans exported per process — here spans ride the
    # metrics-plane push into a GCS TraceStore ring) ---
    # Per-process push ring: spans queued past this are DROPPED (same
    # drop-not-block contract as the metrics pusher buffer).
    trace_buffer_spans: int = 4096
    # Max spans shipped per pusher tick.
    trace_push_max_spans: int = 1024
    # Flight recorder: in-memory ring of recent spans + RPC events kept
    # even when collection is off, dumped on SIGTERM or on demand.
    trace_flight_spans: int = 4096
    trace_flight_window_s: float = 30.0
    # File exporter rotation cap per spans-<pid>.jsonl.
    trace_file_max_bytes: int = 64 << 20
    # Tail-based retention: normal traces are kept 1-in-N; error/slow
    # traces (any span >= trace_slow_s) always survive eviction longest.
    trace_sample_n: int = 1
    trace_slow_s: float = 1.0
    # GCS TraceStore ring bounds (traces / total spans).
    trace_store_traces: int = 512
    trace_store_spans: int = 20000
    # Default threshold for util.state.stuck_calls().
    trace_stuck_threshold_s: float = 10.0

    # --- cluster log plane (runtime/log_plane.py; reference analog:
    # per-worker session log files + log_monitor.py tailing them into
    # GCS pubsub and the dashboard) ---
    # Master switch for the in-process stdout/stderr tee in workers /
    # external raylets / external GCS (the Popen fd capture stays on
    # regardless — interpreter crashes must leave last words somewhere).
    log_capture_enabled: bool = True
    # Rotation bounds per capture file (<proc>.log, .log.1, ...):
    # rotate past log_max_bytes, keep log_rotate_count old generations
    # (env: RAY_TPU_LOG_MAX_BYTES / RAY_TPU_LOG_ROTATE_COUNT).
    log_max_bytes: int = 16 << 20
    log_rotate_count: int = 3
    # Log-monitor tail/push period and its bounded pending-entry queue:
    # entries queued past the cap are DROPPED oldest-first (same
    # drop-not-block contract as the metrics pusher buffer).
    log_push_interval_s: float = 0.25
    log_push_buffer: int = 256
    # GCS LogStore rings: recent lines kept per process, and the global
    # error ring feeding summarize_errors (deduplicated groups).
    log_store_lines: int = 2000
    log_store_error_lines: int = 2000
    log_store_error_groups: int = 256
    # Driver echo budget per SOURCE process (token bucket, lines/s): a
    # chatty worker is summarized, not allowed to bury the terminal.
    log_echo_rate_lines_s: float = 200.0
    # task_id -> (file, start, end) offset-segment annex: how many
    # recent task segments each worker publishes on its metric frames.
    log_segments_max: int = 128
    # Flight-recorder log tail (last captured lines in crash dumps).
    log_tail_lines: int = 50

    # --- cluster memory plane (runtime/refcount.py ownership snapshots,
    # object_manager occupancy decomposition, util.state.memory_summary;
    # reference analog: `ray memory` / memory_summary() aggregating every
    # core worker's reference table plus plasma occupancy) ---
    # Capture creation call sites on owned objects (one raw-frame walk
    # per put / task submission at the OWNING site only; the
    # memory_accounting_overhead_ratio fence measures with this ON).
    memory_callsite_enabled: bool = True
    # Entries per mem/owners annex payload, largest-first (the
    # remainder is counted, not shipped — the annex must stay a small
    # piggyback on metric frames, never a bulk channel).
    memory_annex_max_entries: int = 512
    # Leak detector: an owned ref older than this with zero borrowers,
    # zero submitted-task pins, zero contained-in edges, and an IDLE
    # owner is flagged (surfaced through summarize_errors()).
    memory_leak_threshold_s: float = 300.0
    # Owner idle horizon for the leak detector: a process with any ref
    # churn (non-empty flush) inside this window is considered active,
    # so a busy driver holding refs on purpose is never flagged.
    memory_leak_idle_s: float = 30.0

    # --- training telemetry plane (train/telemetry.py; reference
    # analog: Ray Train's _internal/state run tracking — here per-step
    # decomposition/MFU/goodput ride the metrics+tracing planes) ---
    # Master switch for per-step stamping. Off turns session.report's
    # telemetry hook and the goodput/annex publishes into no-ops.
    train_telemetry_enabled: bool = True
    # Progress-annex publish throttle per rank (the straggler/goodput
    # payload piggybacking on metric frames).
    train_progress_interval_s: float = 0.5
    # A rank is a straggler when it is >=1 step behind AND its last
    # step-end lags the front rank by more than this.
    train_straggler_skew_s: float = 5.0
    # On-demand cluster profiling (util/profiling.py Sampler):
    # per-request duration cap and the folded-stack table bound
    # (distinct stacks past the cap are dropped and counted).
    profile_max_duration_s: float = 30.0
    profile_folded_max_stacks: int = 10000

    def __post_init__(self):
        for f in fields(self):
            setattr(self, f.name, _env_override(f.name, getattr(self, f.name)))

    def apply_overrides(self, overrides: dict | None):
        if not overrides:
            return self
        for k, v in overrides.items():
            if not hasattr(self, k):
                raise ValueError(f"Unknown config flag: {k!r}")
            setattr(self, k, v)
        return self


_global_config: Config | None = None


def get_config() -> Config:
    global _global_config
    if _global_config is None:
        _global_config = Config()
    return _global_config


def reset_config():
    global _global_config
    _global_config = None
