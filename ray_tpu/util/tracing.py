"""Distributed tracing plane: spans around every remote call, collected
cluster-wide through the metrics-plane transport.

Reference analog: ``python/ray/util/tracing/tracing_helper.py``
(``_inject_tracing_into_function:326``, ``_inject_tracing_into_class:450``)
— the reference wraps every remote function with OpenTelemetry spans and
propagates context in task metadata, exporting through an OTel exporter
each process configures. Here there is no OTel dependency: context rides
task specs AND a ``_trace`` header on every framed RPC; finished spans
land in a per-process bounded ring drained by the MetricsPusher into the
GCS :class:`TraceStore` (same drop-not-block contract as metric frames),
with an optional JSONL file exporter kept for local runs.

Five cooperating pieces:

- **Propagation** — ``submission_context``/``execution_span`` thread
  context through task specs (tasks + actor calls); ``wire_context``/
  ``server_span`` do the same for raw framed RPCs so spans parent across
  driver→GCS→raylet→worker hops.
- **Collection** — ``_emit`` feeds a bounded push ring; the metrics
  pusher ships it via ``push_spans`` into the GCS ``TraceStore`` ring
  (tail-based retention: error/slow traces survive longest, normals are
  sampled 1-in-``trace_sample_n``).
- **Flight recorder** — every process keeps the last
  ``trace_flight_window_s`` of spans + RPC events in memory;
  ``dump_flight`` writes them on SIGTERM (``install_crash_dump``) or on
  demand via ``util.state.flight_record``.
- **Stuck-call watchdog** — ``call_started``/``call_finished`` maintain
  an in-flight registry (RPCs, pulls, leases) surfaced through
  ``local_stuck_calls`` / ``util.state.stuck_calls``.
- **Device plane** — ``phase`` is ``span`` for a loop that feeds a
  device (the serving engine's): on while ``recording()``, which adds
  "a ``jax.profiler`` session is live in this process" to
  ``is_enabled()``, and entered as a ``TraceAnnotation`` too, so the
  loop's phases lie in the profiler's trace on the device's clock
  (docs/tracing_plane.md §1a). ``recorded_spans`` reads them back.

Usage:
    ray_tpu.util.tracing.enable_tracing()          # collected plane
    ray_tpu.util.tracing.enable_tracing("/tmp/tr") # + JSONL exporter
    ... run work ...
    trace = ray_tpu.util.state.get_trace(trace_id)

Span records: {"name", "trace_id", "span_id", "parent_id", "start",
"duration", "pid", "kind"} (+ optional "attrs", "error").
``to_chrome_trace`` converts to chrome://tracing format (complements
ray_tpu.timeline(), which covers task lifecycle events without
cross-task parentage).
"""

from __future__ import annotations

import contextlib
import contextvars
import json
import logging
import os
import signal
import sys
import tempfile
import threading
import time
import uuid
from collections import OrderedDict, deque
from dataclasses import dataclass

logger = logging.getLogger("ray_tpu.tracing")

_ENV_DIR = "RAY_TPU_TRACE_DIR"
_ENV_ON = "RAY_TPU_TRACE_ENABLED"

# ambient span context (submission captures it; execution restores it)
_current: contextvars.ContextVar["SpanContext | None"] = \
    contextvars.ContextVar("ray_tpu_trace_ctx", default=None)

_write_lock = threading.Lock()


def _cfg_attr(name: str, default):
    """Config flag with an import-cycle-safe fallback (tracing is
    imported by modules the config module itself pulls in)."""
    try:
        from ray_tpu.utils.config import get_config

        return getattr(get_config(), name, default)
    except Exception:  # pragma: no cover - early-import fallback
        return default


@dataclass
class SpanContext:
    trace_id: str
    span_id: str

    def to_dict(self) -> dict:
        return {"trace_id": self.trace_id, "span_id": self.span_id}

    @staticmethod
    def from_dict(d: dict | None) -> "SpanContext | None":
        if not d:
            return None
        return SpanContext(d["trace_id"], d["span_id"])


def enable_tracing(trace_dir: str | None = None) -> None:
    """Turn tracing on for this process AND every worker spawned after
    (the switch is inherited through the environment, like the
    reference's tracing startup hook). ``trace_dir`` is optional: with
    one, finished spans are ALSO appended to per-pid JSONL files;
    without one, collection is ring-buffer + pusher only."""
    if trace_dir:
        os.makedirs(trace_dir, exist_ok=True)
        os.environ[_ENV_DIR] = trace_dir
    os.environ[_ENV_ON] = "1"
    global _enabled_cache
    _enabled_cache = (True, time.monotonic())


def disable_tracing() -> None:
    os.environ.pop(_ENV_DIR, None)
    os.environ.pop(_ENV_ON, None)
    global _enabled_cache
    _enabled_cache = (False, time.monotonic())


# (value, checked_at): is_enabled sits on the per-call submit hot path —
# an os.environ read per submit measurably taxes 10k+ calls/s, so the
# env probe is cached with a short TTL. enable/disable invalidate
# immediately; a worker learning of tracing purely via inherited env
# sees it within the TTL (observability-only lag).
_enabled_cache: tuple[bool, float] = (False, -1.0)


def is_enabled() -> bool:
    global _enabled_cache
    value, checked = _enabled_cache
    now = time.monotonic()
    if now - checked > 0.2:
        on = os.environ.get(_ENV_ON)
        value = bool(os.environ.get(_ENV_DIR)) or \
            bool(on and on not in ("0", "false", "False"))
        _enabled_cache = (value, now)
    return value


def current_context() -> SpanContext | None:
    return _current.get()


def bind(ctx: SpanContext | None):
    """Set the ambient context explicitly (worker threads don't inherit
    contextvars — chunked pulls and executor threads re-bind the
    captured context). Returns the reset token."""
    return _current.set(ctx)


# ---------------------------------------------------------------------------
# span sinks: push ring (drained by the metrics pusher), flight ring
# (recent-history recorder), optional JSONL file
# ---------------------------------------------------------------------------

_ring_lock = threading.Lock()
_push_ring: deque | None = None
_flight: deque | None = None


def _rings() -> tuple[deque, deque]:
    global _push_ring, _flight
    if _push_ring is None:
        with _ring_lock:
            if _push_ring is None:
                _flight = deque(
                    maxlen=int(_cfg_attr("trace_flight_spans", 4096)))
                _push_ring = deque(
                    maxlen=int(_cfg_attr("trace_buffer_spans", 4096)))
    return _push_ring, _flight


def drain_spans(max_n: int | None = None) -> list[dict]:
    """Pop up to ``max_n`` finished spans for shipment (pusher tick).
    Oldest first; the ring itself already dropped anything past its
    bound, so drain never blocks and never grows."""
    ring, _ = _rings()
    if not ring:
        return []
    if max_n is None:
        max_n = int(_cfg_attr("trace_push_max_spans", 1024))
    out: list[dict] = []
    with _ring_lock:
        while ring and len(out) < max_n:
            out.append(ring.popleft())
    return out


def requeue_spans(spans: list[dict]) -> None:
    """Put spans back at the FRONT after a failed push (bounded: the
    ring's maxlen still drops the overflow — drop-not-block)."""
    if not spans:
        return
    ring, _ = _rings()
    with _ring_lock:
        ring.extendleft(reversed(spans))


def _file_sink(record: dict) -> None:
    trace_dir = os.environ.get(_ENV_DIR)
    if not trace_dir:
        return
    path = os.path.join(trace_dir, f"spans-{os.getpid()}.jsonl")
    line = json.dumps(record)
    cap = int(_cfg_attr("trace_file_max_bytes", 64 << 20))
    with _write_lock:
        with open(path, "a") as f:
            f.write(line + "\n")
            size = f.tell()
        if cap > 0 and size > cap:
            # single-generation rotation: the previous generation is
            # overwritten, bounding disk at ~2x the cap per process
            try:
                os.replace(path, path + ".1")
            except OSError:  # pragma: no cover - fs race
                pass


def _emit(record: dict) -> None:
    ring, flight = _rings()
    with _ring_lock:
        ring.append(record)
        flight.append(record)
    _file_sink(record)


def _record(name: str, ctx: SpanContext, parent_id: str | None,
            start: float, duration: float, kind: str, attrs: dict | None,
            error: bool = False) -> None:
    """One finished span into the rings (and the file sink)."""
    rec = {
        "name": name,
        "trace_id": ctx.trace_id,
        "span_id": ctx.span_id,
        "parent_id": parent_id,
        "start": start,
        "duration": duration,
        "pid": os.getpid(),
        "kind": kind,
    }
    if attrs:
        rec["attrs"] = attrs
    if error:
        rec["error"] = True
    _emit(rec)


# ``jax.profiler.TraceAnnotation``, once JAX is there to ask (this module
# never imports it: most processes that trace never touch a device)
_annotation = None


def _profiling() -> bool:
    """Whether a ``jax.profiler`` session is live in this process: one
    atomic read. A process that has not imported JAX cannot be
    profiling."""
    global _annotation
    if _annotation is None:
        profiler = getattr(sys.modules.get("jax"), "profiler", None)
        _annotation = getattr(profiler, "TraceAnnotation", None)
        if _annotation is None:
            return False
    return _annotation.is_enabled()


def recording() -> bool:
    """The rule for "on" where the host feeds a device (``phase``):
    ``is_enabled()``, or a ``jax.profiler`` session live in this process.
    Whoever profiles a process (an operator tracing a replica,
    ``benchmark/run.py --trace 1``) gets its device-feeding loop's spans
    for exactly the profiled slice, with no switch to set."""
    return is_enabled() or _profiling()


class LiveSpan(SpanContext):
    """A span being timed, and its own context: inside its ``with`` block
    the ambient context points at it, so spans opened there parent to it.
    ``set`` adds counts to its ``attrs`` until the block ends. While a
    ``jax.profiler`` session is live it also enters a ``TraceAnnotation``
    of the same name, so the span lies in the profiler's trace on the
    clock of the device events. An escaping exception marks the record
    ``error`` (tail-based retention keeps such traces)."""

    def __init__(self, name: str, kind: str,
                 parent: SpanContext | None, attrs: dict | None,
                 trace_id: str | None = None):
        if parent is None and trace_id is None:
            parent = _current.get()
        super().__init__(
            trace_id=(parent.trace_id if parent
                      else trace_id or uuid.uuid4().hex[:16]),
            span_id=uuid.uuid4().hex[:16])
        self.name, self.kind = name, kind
        self.parent_id = parent.span_id if parent else None
        self.attrs = attrs

    def set(self, **counts) -> None:
        if self.attrs is None:
            self.attrs = counts
        else:
            self.attrs.update(counts)

    def __enter__(self) -> "LiveSpan":
        self._annotated = None
        if _profiling():
            self._annotated = _annotation(self.name)
            self._annotated.__enter__()
        self._token = _current.set(self)
        self._start = time.time()
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        duration = time.time() - self._start
        _current.reset(self._token)
        if self._annotated is not None:
            self._annotated.__exit__(exc_type, exc, tb)
        _record(self.name, self, self.parent_id, self._start, duration,
                self.kind, self.attrs, error=exc_type is not None)
        return False


class _NoPhase:
    """What ``phase`` hands back while nothing records: falsy, so a
    caller skips working out counts nobody will read."""

    def __bool__(self) -> bool:
        return False

    def __enter__(self) -> "_NoPhase":
        return self

    def __exit__(self, *exc) -> bool:
        return False

    def set(self, **counts) -> None:
        pass


_NO_SPAN = contextlib.nullcontext()
_NO_PHASE = _NoPhase()


def span(name: str, *, kind: str = "local",
         parent: SpanContext | None = None,
         attrs: dict | None = None):
    """Record one span around a ``with`` block (see ``LiveSpan``); the
    block gets the span's context, or None with tracing off."""
    if not is_enabled():
        return _NO_SPAN
    return LiveSpan(name, kind, parent, attrs)


def phase(name: str, *, kind: str = "local",
          parent: SpanContext | None = None, trace_id: str | None = None,
          attrs: dict | None = None):
    """One phase of a loop that feeds a device, as a ``LiveSpan`` while
    ``recording()`` and at the cost of that one check otherwise. The
    block always gets an object with ``set`` (counts known only at the
    phase's end), falsy when nothing records. ``trace_id`` makes a root
    span of a given trace (one trace id an engine)."""
    if not recording():
        return _NO_PHASE
    return LiveSpan(name, kind, parent, attrs, trace_id)


def emit(name: str, *, start: float, duration: float,
         parent: SpanContext | None = None, kind: str = "local",
         attrs: dict | None = None) -> SpanContext:
    """Emit one already-timed span (the serve engine stamps a request's
    TTFT stages and, from its watcher thread, the device's runs, and
    emits them after the fact). Returns the span's context so children
    can parent to it."""
    ctx = SpanContext(
        trace_id=parent.trace_id if parent else uuid.uuid4().hex[:16],
        span_id=uuid.uuid4().hex[:16],
    )
    # a parent with no span id is the root of a trace of its own
    _record(name, ctx, (parent.span_id or None) if parent else None,
            start, duration, kind, attrs)
    return ctx


# ---------------------------------------------------------------------------
# RPC header propagation (runtime/rpc.py attaches/restores these)
# ---------------------------------------------------------------------------

def wire_context():
    """Compact ``(trace_id, span_id)`` for the RPC ``_trace`` header, or
    None when tracing is off / no ambient span (untraced RPCs carry no
    header and produce no server spans — heartbeats stay span-free)."""
    if not is_enabled():
        return None
    cur = _current.get()
    if cur is None:
        return None
    return (cur.trace_id, cur.span_id)


@contextlib.contextmanager
def server_span(method: str, wire):
    """Server-dispatch side of RPC propagation: restore the caller's
    context so handler-side spans (and nested RPCs) parent across the
    hop. No-op without a header."""
    if wire is None or not is_enabled():
        yield None
        return
    try:
        parent = SpanContext(str(wire[0]), str(wire[1]))
    except (TypeError, IndexError, KeyError):
        yield None
        return
    with span(f"rpc:{method}", kind="rpc", parent=parent) as ctx:
        yield ctx


# ---------------------------------------------------------------------------
# stuck-call watchdog: in-flight call registry
# ---------------------------------------------------------------------------

_inflight_lock = threading.Lock()
_inflight: dict[int, dict] = {}
_inflight_next = 0


def call_started(kind: str, detail: str, target=None) -> int:
    """Register one in-flight call (RPC / pull / lease / actor call).
    Always on: two locked dict ops per call are noise next to a socket
    round trip, and the watchdog must see calls that hung BEFORE anyone
    thought to enable tracing."""
    global _inflight_next
    cur = _current.get()
    entry = {
        "kind": kind,
        "detail": detail,
        "target": target,
        "start": time.time(),
        "mono": time.monotonic(),
        "pid": os.getpid(),
        "thread": threading.current_thread().name,
        "trace_id": cur.trace_id if cur else None,
        "span_id": cur.span_id if cur else None,
    }
    with _inflight_lock:
        _inflight_next += 1
        token = _inflight_next
        _inflight[token] = entry
    return token


def call_finished(token: int | None) -> None:
    if token is None:
        return
    with _inflight_lock:
        _inflight.pop(token, None)


class _Inflight:
    """Class-based (not generator) context manager: task execution is a
    hot path and this runs with tracing OFF too."""

    __slots__ = ("_token",)

    def __init__(self, kind: str, detail: str, target=None):
        self._token = call_started(kind, detail, target)

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        call_finished(self._token)
        return False


def inflight(kind: str, detail: str, target=None) -> _Inflight:
    """Scope-shaped call_started/call_finished pair, for call sites
    where the whole in-flight window is one lexical block (task
    execution); registered-RPC style token threading stays available
    for split start/finish sites."""
    return _Inflight(kind, detail, target)


def local_stuck_calls(threshold_s: float | None = None) -> list[dict]:
    """In-flight calls older than ``threshold_s`` (default
    ``trace_stuck_threshold_s``), oldest first, with their parent span
    chain coordinates (trace_id/span_id) when the call was traced."""
    if threshold_s is None:
        threshold_s = float(_cfg_attr("trace_stuck_threshold_s", 10.0))
    now = time.monotonic()
    with _inflight_lock:
        entries = [dict(e) for e in _inflight.values()
                   if now - e["mono"] >= threshold_s]
    for e in entries:
        e["age_s"] = now - e.pop("mono")
    entries.sort(key=lambda e: -e["age_s"])
    # a stuck TASK report is actionable without a second query: append
    # the hung task's last captured log lines (needs the in-process
    # capture; target carries the task_id the execution bracket stamps)
    try:
        from ray_tpu.runtime import log_plane as _log_plane

        if _log_plane.active_capture() is not None:
            for e in entries:
                if e.get("kind") in ("task", "actor_task") \
                        and e.get("target"):
                    e["log_tail"] = _log_plane.recent_lines(
                        e["target"], 5)
    except Exception:  # pragma: no cover - teardown
        pass
    return entries


# ---------------------------------------------------------------------------
# flight recorder
# ---------------------------------------------------------------------------

def record_event(name: str, **attrs) -> None:
    """Append one point event (RPC drop, router decision, lease grant)
    to the flight ring only — never shipped, only dumped."""
    if not is_enabled():
        return
    _, flight = _rings()
    rec = {"event": name, "ts": time.time(), "pid": os.getpid()}
    if attrs:
        rec.update(attrs)
    with _ring_lock:
        flight.append(rec)


def flight_snapshot(last_s: float | None = None) -> dict:
    """The last ``last_s`` seconds (default ``trace_flight_window_s``)
    of spans + events, plus every currently in-flight call. Pure local
    memory — works while the GCS is unreachable."""
    if last_s is None:
        last_s = float(_cfg_attr("trace_flight_window_s", 30.0))
    cutoff = time.time() - last_s
    _, flight = _rings()
    with _ring_lock:
        records = list(flight)
    spans_out, events_out = [], []
    for r in records:
        if "event" in r:
            if r["ts"] >= cutoff:
                events_out.append(r)
        elif r["start"] + r.get("duration", 0.0) >= cutoff:
            spans_out.append(r)
    # a crashed/partitioned worker's last words ride the dump: the last
    # ~50 captured log lines (empty when no capture is installed)
    try:
        from ray_tpu.runtime import log_plane as _log_plane

        log_tail = _log_plane.log_tail(50)
    except Exception:  # pragma: no cover - teardown
        log_tail = []
    return {
        "pid": os.getpid(),
        "ts": time.time(),
        "window_s": last_s,
        "spans": spans_out,
        "events": events_out,
        "inflight": local_stuck_calls(0.0),
        "log_tail": log_tail,
    }


def local_trace(trace_id: str) -> list[dict]:
    """Spans of one trace still in the local flight ring (local-mode
    ``util.state.get_trace`` backend)."""
    return sorted((r for r in recorded_spans()
                   if r.get("trace_id") == trace_id),
                  key=lambda r: r["start"])


def recorded_spans(prefix: str = "") -> list[dict]:
    """Finished spans still in this process's flight ring whose name
    starts with ``prefix``, oldest first. Reading takes nothing away
    (the pusher drains the other ring), so a run can be read once it has
    ended: the benchmark's per-layer readers do."""
    _, flight = _rings()
    with _ring_lock:
        records = list(flight)
    return [r for r in records
            if "event" not in r and r["name"].startswith(prefix)]


# What each instruction of a compiled device program is a piece of
# (``util/program_scopes.py``), one record an executable. Not in the span
# rings: the pusher drains one of them to the GCS store, and a map is
# hundreds of KB. Bounded: a record of the same program and executable
# replaces the one before it.
_scope_maps: dict[tuple, dict] = {}


def record_scopes(program: str, executable: str, scopes: dict,
                  inferred: dict) -> None:
    """Keep ``{instruction: [result shape, scope]}`` of one executable of
    the device program ``program`` (its module's name), and which of
    those scopes were not the instruction's own (``{instruction: rule}``,
    ``util/program_scopes.py:instruction_scopes``)."""
    with _ring_lock:
        _scope_maps[program, executable] = {
            "program": program, "executable": executable, "scopes": scopes,
            "inferred": inferred}


def recorded_scopes() -> list[dict]:
    """The records ``record_scopes`` kept in this process, oldest first:
    ``program``, ``executable`` (the backend's fingerprint of it),
    ``scopes`` and ``inferred``. Read beside ``recorded_spans`` once a run has ended: an
    ``XLA Ops`` event of a profiler's trace is joined to its instruction
    here by name and result shape."""
    with _ring_lock:
        return list(_scope_maps.values())


def dump_flight(path: str | None = None, last_s: float | None = None) -> str:
    """Write the flight snapshot as JSON; returns the path. Defaults to
    ``flight-<pid>-<ts>.json`` in the trace dir (or tempdir)."""
    snap = flight_snapshot(last_s)
    if path is None:
        base = os.environ.get(_ENV_DIR) or tempfile.gettempdir()
        path = os.path.join(
            base, f"flight-{os.getpid()}-{int(snap['ts'])}.json")
    with open(path, "w") as f:
        json.dump(snap, f)
    return path


_crash_dump_installed = False


def install_crash_dump() -> bool:
    """Chain a SIGTERM handler that dumps the flight ring before the
    process dies (local files only — no network, so it works through a
    partition). Safe off the main thread (no-op there) and idempotent."""
    global _crash_dump_installed
    if _crash_dump_installed:
        return True
    try:
        prev = signal.getsignal(signal.SIGTERM)

        def _on_term(signum, frame):
            try:
                if is_enabled():
                    dump_flight()
            except Exception:  # pragma: no cover - dying anyway
                pass
            if callable(prev) and prev not in (
                    signal.SIG_IGN, signal.SIG_DFL):
                prev(signum, frame)
            else:
                raise SystemExit(143)

        signal.signal(signal.SIGTERM, _on_term)
        _crash_dump_installed = True
        return True
    except ValueError:  # not the main thread
        return False


# ---------------------------------------------------------------------------
# task-spec propagation (unchanged wire shape; api.py calls these)
# ---------------------------------------------------------------------------

def submission_context(function_name: str) -> dict | None:
    """Called at .remote() time: returns the wire context for the spec
    (a fresh 'submit' span parented to the ambient one)."""
    if not is_enabled():
        return None
    parent = _current.get()
    ctx = SpanContext(
        trace_id=parent.trace_id if parent else uuid.uuid4().hex[:16],
        span_id=uuid.uuid4().hex[:16],
    )
    _record(f"submit:{function_name}", ctx,
            parent.span_id if parent else None, time.time(), 0.0, "submit",
            None)
    wire = ctx.to_dict()
    # Cluster-mode workers are spawned by the RAYLET, whose environ never
    # saw the driver's enable_tracing() — so the trace dir must ride the
    # wire context, not env inheritance.
    wire["trace_dir"] = os.environ.get(_ENV_DIR)
    return wire


@contextlib.contextmanager
def execution_span(function_name: str, wire_ctx: dict | None):
    """Wraps task execution; parents to the submitter's span."""
    if wire_ctx is None:
        yield
        return
    global _enabled_cache
    wire_dir = wire_ctx.get("trace_dir")
    changed = False
    if wire_dir and os.environ.get(_ENV_DIR) != wire_dir:
        # adopt/sync the submitter's trace dir: workers are spawned by
        # the raylet (no env inheritance from the driver), and a warm
        # worker must follow the driver when it switches directories
        os.environ[_ENV_DIR] = wire_dir
        changed = True
    if not os.environ.get(_ENV_ON):
        # a wire context only exists when the submitter traces: adopt
        # the dir-less switch too, so worker-side spans reach the ring
        os.environ[_ENV_ON] = "1"
        changed = True
    if changed:
        _enabled_cache = (True, time.monotonic())
    if not is_enabled():
        yield
        return
    with span(f"run:{function_name}", kind="task",
              parent=SpanContext.from_dict(wire_ctx)):
        yield


# ---------------------------------------------------------------------------
# GCS-side collected store
# ---------------------------------------------------------------------------

class TraceStore:
    """Bounded trace ring on the GCS with tail-based retention.

    Spans arrive via ``push_spans`` grouped here by trace_id. When over
    budget (``max_traces`` traces / ``max_spans`` total spans), eviction
    walks classes in order: unsampled-normal first (trace_id hash not
    selected by the 1-in-``sample_n`` sampler), then sampled-normal,
    then error/slow — so the traces most worth keeping die last. Within
    a class, oldest-activity first."""

    def __init__(self, max_traces: int = 512, max_spans: int = 20000,
                 sample_n: int = 1, slow_s: float = 1.0,
                 per_trace_spans: int = 1024):
        self.max_traces = max(1, int(max_traces))
        self.max_spans = max(1, int(max_spans))
        self.sample_n = max(1, int(sample_n))
        self.slow_s = float(slow_s)
        self.per_trace_spans = max(1, int(per_trace_spans))
        self._lock = threading.Lock()
        # trace_id -> {"spans", "first", "last", "error", "slow", "srcs"}
        self._traces: "OrderedDict[str, dict]" = OrderedDict()
        self._total_spans = 0
        self.dropped_spans = 0
        self.evicted_traces = 0

    def _sampled(self, trace_id: str) -> bool:
        if self.sample_n <= 1:
            return True
        try:
            return int(trace_id[:8], 16) % self.sample_n == 0
        except ValueError:
            return True

    def _class_of(self, t: dict, trace_id: str) -> int:
        if t["error"] or t["slow"]:
            return 2
        return 1 if self._sampled(trace_id) else 0

    def ingest(self, src: str, spans: list[dict]) -> int:
        accepted = 0
        with self._lock:
            for s in spans:
                tid = s.get("trace_id")
                if not tid or "start" not in s:
                    self.dropped_spans += 1
                    continue
                t = self._traces.get(tid)
                if t is None:
                    t = {"spans": [], "first": s["start"], "last": 0.0,
                         "error": False, "slow": False, "srcs": set()}
                    self._traces[tid] = t
                if len(t["spans"]) >= self.per_trace_spans:
                    self.dropped_spans += 1
                    continue
                t["spans"].append(s)
                self._total_spans += 1
                accepted += 1
                end = s["start"] + s.get("duration", 0.0)
                t["first"] = min(t["first"], s["start"])
                t["last"] = max(t["last"], end)
                if s.get("error"):
                    t["error"] = True
                if s.get("duration", 0.0) >= self.slow_s:
                    t["slow"] = True
                if src:
                    t["srcs"].add(src)
            self._evict_locked()
        return accepted

    def _evict_locked(self) -> None:
        while (len(self._traces) > self.max_traces
               or self._total_spans > self.max_spans):
            victim = None
            for klass in (0, 1, 2):
                candidates = [(t["last"], tid)
                              for tid, t in self._traces.items()
                              if self._class_of(t, tid) == klass]
                if candidates:
                    victim = min(candidates)[1]
                    break
            if victim is None:  # pragma: no cover - defensive
                break
            gone = self._traces.pop(victim)
            self._total_spans -= len(gone["spans"])
            self.evicted_traces += 1

    def get(self, trace_id: str) -> dict | None:
        with self._lock:
            t = self._traces.get(trace_id)
            if t is None:
                return None
            spans = sorted(t["spans"], key=lambda s: s["start"])
            return {
                "trace_id": trace_id,
                "spans": spans,
                "first": t["first"],
                "last": t["last"],
                "error": t["error"],
                "slow": t["slow"],
                "srcs": sorted(t["srcs"]),
            }

    def list(self, limit: int = 50) -> list[dict]:
        with self._lock:
            items = [
                {
                    "trace_id": tid,
                    "spans": len(t["spans"]),
                    "first": t["first"],
                    "last": t["last"],
                    "duration_s": max(0.0, t["last"] - t["first"]),
                    "error": t["error"],
                    "slow": t["slow"],
                    "srcs": sorted(t["srcs"]),
                    "root": next(
                        (s["name"] for s in t["spans"]
                         if not s.get("parent_id")),
                        t["spans"][0]["name"] if t["spans"] else ""),
                }
                for tid, t in self._traces.items()
            ]
        items.sort(key=lambda i: -i["last"])
        return items[:max(0, int(limit))]

    def stats(self) -> dict:
        with self._lock:
            return {"traces": len(self._traces),
                    "spans": self._total_spans,
                    "dropped_spans": self.dropped_spans,
                    "evicted_traces": self.evicted_traces}


def build_waterfall(spans: list[dict]) -> list[dict]:
    """Depth-first waterfall rows for a trace: each span with its tree
    depth and millisecond offset from the trace start (the dashboard
    renders these directly as offset/width bars)."""
    if not spans:
        return []
    spans = sorted(spans, key=lambda s: (s["start"], s.get("name", "")))
    t0 = spans[0]["start"]
    by_id = {s["span_id"]: s for s in spans}
    children: dict[str | None, list[dict]] = {}
    roots: list[dict] = []
    for s in spans:
        pid = s.get("parent_id")
        if pid and pid in by_id:
            children.setdefault(pid, []).append(s)
        else:
            roots.append(s)
    rows: list[dict] = []

    def _walk(s: dict, depth: int) -> None:
        rows.append({
            "name": s["name"],
            "span_id": s["span_id"],
            "parent_id": s.get("parent_id"),
            "depth": depth,
            "kind": s.get("kind"),
            "pid": s.get("pid"),
            "start": s["start"],
            "duration": s.get("duration", 0.0),
            "offset_ms": (s["start"] - t0) * 1e3,
            "dur_ms": s.get("duration", 0.0) * 1e3,
            "error": bool(s.get("error")),
            "attrs": s.get("attrs"),
        })
        for c in children.get(s["span_id"], ()):
            _walk(c, depth + 1)

    for r in roots:
        _walk(r, 0)
    return rows


# ---------------------------------------------------------------------------
# file exporter (kept for local runs; bounded + streaming)
# ---------------------------------------------------------------------------

def iter_spans(trace_dir: str):
    """Stream span records from a trace dir without loading every file
    into memory. Rotated generations (``.jsonl.1``) are yielded before
    their live file so a per-pid stream stays roughly chronological."""
    if not os.path.isdir(trace_dir):
        return
    names = [fn for fn in os.listdir(trace_dir)
             if fn.startswith("spans-")
             and (fn.endswith(".jsonl") or fn.endswith(".jsonl.1"))]
    # (base name, generation) — generation 0 is the rotated (older) file
    names.sort(key=lambda fn: (
        fn[:-2] if fn.endswith(".1") else fn,
        0 if fn.endswith(".1") else 1))
    for fn in names:
        try:
            with open(os.path.join(trace_dir, fn)) as f:
                for line in f:
                    line = line.strip()
                    if line:
                        yield json.loads(line)
        except FileNotFoundError:  # rotated away mid-iteration
            continue


def read_spans(trace_dir: str) -> list[dict]:
    return list(iter_spans(trace_dir))


def to_chrome_trace(spans: list[dict]) -> list[dict]:
    return [
        {
            "name": s["name"],
            "cat": s["kind"],
            "ph": "X",
            "ts": s["start"] * 1e6,
            "dur": max(s["duration"], 1e-6) * 1e6,
            "pid": s["pid"],
            "tid": s["trace_id"],
            "args": {"span_id": s["span_id"],
                     "parent_id": s.get("parent_id")},
        }
        for s in spans
    ]


def export_chrome_trace(trace_dir: str | None = None,
                        filename: str | None = None) -> list[dict]:
    """One chrome://tracing file for the whole story: tracing spans AND
    ``ray_tpu.timeline()`` task lifecycle events, merged on a shared
    wall-clock domain.

    Spans are recorded with ``time.time()``; task events are recorded
    monotonic but wall-anchored at record time inside each producing
    process (``wall_start``/``wall_end``), so both series line up in one
    viewer without post-hoc clock matching.

    pid/tid mapping (one row group per OS process):

    - pid — the OS pid of the producing process for BOTH kinds, so a
      worker's spans and its task executions share a process group.
    - tid — for spans, the ``trace_id`` (one lane per distributed call
      tree: submit + run spans of a call nest on one line); for task
      events, the executing thread name (one lane per executor thread).

    ``trace_dir`` defaults to the active trace dir (``enable_tracing``);
    with tracing off, the export is the timeline alone. Task events need
    an initialized runtime — without one the export is the spans alone.
    The merged list is stable-sorted by (ts, pid, name) so repeated
    exports of the same data diff cleanly. Returns the event list;
    ``filename`` additionally dumps it as JSON.
    """
    if trace_dir is None:
        trace_dir = os.environ.get(_ENV_DIR)
    events: list[dict] = []
    if trace_dir:
        events.extend(to_chrome_trace(read_spans(trace_dir)))
    try:
        import ray_tpu

        events.extend(ray_tpu.timeline())
    except (ImportError, RuntimeError, AttributeError, TypeError) as e:
        # no initialized runtime (or a partially torn-down one): the
        # export is spans-only — say why instead of silently shrinking
        logger.info("export_chrome_trace: skipping timeline merge: %s", e)
    # attributed log lines as instant events on the emitting task's
    # trace lane (tid = trace_id, same lane its spans render on): this
    # process's capture plus — cluster mode — the GCS log store rings
    try:
        from ray_tpu.runtime import log_plane as _log_plane

        events.extend(_log_plane.chrome_instant_events())
        from ray_tpu.runtime import core as _core
        if _core.is_initialized():
            from ray_tpu.util import state as _state

            recs: list = []
            listing = _state.list_logs()
            for proc_name in (listing.get("procs") or {}):
                got = _state.get_log(proc=proc_name, tail=1000)
                recs.extend(got.get("lines") or [])
            events.extend(_log_plane.chrome_instant_events(recs))
    except Exception as e:  # noqa: BLE001 - observability only
        logger.info("export_chrome_trace: skipping log merge: %s", e)
    # stable order so repeated exports of the same spans diff cleanly
    events.sort(key=lambda e: (e.get("ts", float("inf")),
                               e.get("pid", 0), e.get("name", "")))
    # process_name metadata so the viewer labels each pid row group
    for pid in sorted({e["pid"] for e in events if "pid" in e}):
        events.append({"name": "process_name", "ph": "M", "pid": pid,
                       "args": {"name": f"ray_tpu pid {pid}"}})
    if filename:
        with open(filename, "w") as f:
            json.dump(events, f)
    return events
