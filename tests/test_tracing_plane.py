"""Distributed tracing plane (round 9): RPC-level context propagation,
cluster span collection through the metrics pusher into the GCS
TraceStore, the serve one-trace acceptance, the stuck-call watchdog,
the flight recorder, and the < 3% tracing-enabled hot-path gate.

Reference analog: util/tracing/tracing_helper.py (OpenTelemetry
export); here spans ride the repo's own metrics plane instead — see
docs/tracing_plane.md for the divergence rationale."""

import json
import os
import signal
import subprocess
import sys
import time
import urllib.request

import pytest

import ray_tpu
from ray_tpu.util import tracing


@pytest.fixture(autouse=True)
def _tracing_off_after():
    yield
    tracing.disable_tracing()


# ---------------------------------------------------------------------------
# propagation: the _trace header on framed RPCs
# ---------------------------------------------------------------------------

def test_rpc_carries_trace_context():
    """A client call made inside a span restores that span's trace as
    the ambient context in the server handler (rpc.py `_trace` header +
    server_span), so server-side spans parent across the wire."""
    from ray_tpu.runtime.rpc import RpcClient, RpcServer

    seen = {}

    class Srv(RpcServer):
        def rpc_probe(self, conn, send_lock):
            ctx = tracing.current_context()
            seen["ctx"] = (ctx.trace_id, ctx.span_id) if ctx else None
            return "ok"

    tracing.enable_tracing()
    srv = Srv("127.0.0.1", 0).start()
    client = RpcClient(srv.address)
    try:
        with tracing.span("client-root") as root:
            assert client.call("probe") == "ok"
        assert seen["ctx"] is not None
        assert seen["ctx"][0] == root.trace_id
        # the server-side span landed in the flight ring with the
        # client's trace id and the rpc: naming convention
        spans = tracing.local_trace(root.trace_id)
        assert any(s["name"] == "rpc:probe" for s in spans)
    finally:
        client.close()
        srv.stop()


def test_untraced_rpc_has_no_header():
    """With no ambient span the request carries no `_trace` key and the
    handler sees no context — the untraced path stays untouched."""
    from ray_tpu.runtime.rpc import RpcClient, RpcServer

    seen = {}

    class Srv(RpcServer):
        def rpc_probe(self, conn, send_lock):
            seen["ctx"] = tracing.current_context()
            return "ok"

    tracing.enable_tracing()
    srv = Srv("127.0.0.1", 0).start()
    client = RpcClient(srv.address)
    try:
        assert client.call("probe") == "ok"
        assert seen["ctx"] is None
    finally:
        client.close()
        srv.stop()


# ---------------------------------------------------------------------------
# push ring + TraceStore (collection layer, no cluster needed)
# ---------------------------------------------------------------------------

def test_span_ring_bounded_drop_not_block():
    from ray_tpu.utils.config import get_config

    tracing.enable_tracing()
    tracing.drain_spans()                     # start from empty
    cap = get_config().trace_buffer_spans
    for i in range(cap + 50):
        tracing.emit(f"s{i}", start=time.time(), duration=0.0)
    drained = tracing.drain_spans(max_n=cap + 100)
    assert len(drained) <= cap                # oldest dropped, no growth
    # requeue is bounded too: re-draining returns what fits
    tracing.requeue_spans(drained)
    assert len(tracing.drain_spans(max_n=cap + 100)) <= cap


def test_trace_store_tail_retention():
    """Eviction order under pressure: unsampled normals first, then
    sampled normals, then (only if it must) error/slow traces —
    tail-based sampling keeps what an operator would want to read."""
    store = tracing.TraceStore(max_traces=4, max_spans=1000,
                               sample_n=10**9,   # no normal survives
                               slow_s=0.5)

    def spans_for(tid, *, error=False, dur=0.0, at=0.0):
        return [{"trace_id": tid, "span_id": f"{tid}-s", "name": "root",
                 "start": at, "duration": dur, "error": error}]

    for i in range(4):
        store.ingest("t", spans_for(f"{i:032x}", at=float(i)))
    # an error trace and a slow trace push two normals out
    store.ingest("t", spans_for("e" * 32, error=True, at=10.0))
    store.ingest("t", spans_for("f" * 32, dur=2.0, at=11.0))
    held = {s["trace_id"] for s in store.list(limit=10)}
    assert "e" * 32 in held and "f" * 32 in held
    assert len(held) <= 4
    st = store.stats()
    assert st["evicted_traces"] >= 2


def test_trace_store_per_trace_span_cap():
    store = tracing.TraceStore(max_traces=4, max_spans=10**6,
                               sample_n=1, slow_s=10.0,
                               per_trace_spans=8)
    tid = "a" * 32
    store.ingest("t", [{"trace_id": tid, "span_id": f"s{i}",
                        "name": f"n{i}", "start": float(i),
                        "duration": 0.0} for i in range(50)])
    assert len(store.get(tid)["spans"]) <= 8


def test_waterfall_rows():
    t0 = 100.0
    spans = [
        {"trace_id": "t", "span_id": "a", "name": "root", "start": t0,
         "duration": 0.3},
        {"trace_id": "t", "span_id": "b", "parent_id": "a",
         "name": "child", "start": t0 + 0.1, "duration": 0.1},
    ]
    rows = tracing.build_waterfall(spans)
    assert [r["name"] for r in rows] == ["root", "child"]
    assert rows[0]["depth"] == 0 and rows[1]["depth"] == 1
    assert rows[1]["offset_ms"] == pytest.approx(100.0)
    assert rows[1]["dur_ms"] == pytest.approx(100.0)


# ---------------------------------------------------------------------------
# stuck-call watchdog
# ---------------------------------------------------------------------------

def test_stuck_call_watchdog_sees_hung_rpc():
    """A deliberately-hung RPC appears in the in-flight registry with
    the trace/span ids of the span it was made under, and disappears
    once the reply lands (acceptance: injected hang -> stuck_calls())."""
    from ray_tpu.runtime.rpc import RpcClient, RpcServer

    release = {"t": 0.6}

    class Srv(RpcServer):
        def rpc_hang(self, conn, send_lock):
            time.sleep(release["t"])
            return "done"

    tracing.enable_tracing()
    srv = Srv("127.0.0.1", 0).start()
    client = RpcClient(srv.address, timeout=10)
    try:
        with tracing.span("caller") as root:
            pending = client.call_async("hang")
            time.sleep(0.2)
            stuck = tracing.local_stuck_calls(0.1)
            hung = [c for c in stuck if c["detail"] == "hang"]
            assert hung, stuck
            assert hung[0]["kind"] == "rpc"
            assert hung[0]["age_s"] >= 0.1
            assert hung[0]["trace_id"] == root.trace_id
            # the public API surfaces the same registry
            from ray_tpu.util import state as state_api

            out = state_api.stuck_calls(threshold_s=0.1)
            assert any(c["detail"] == "hang" for c in out["driver"])
            assert pending.result() == "done"
        # reply landed -> registry entry cleared
        assert not [c for c in tracing.local_stuck_calls(0.0)
                    if c["detail"] == "hang"]
    finally:
        client.close()
        srv.stop()


def test_stuck_call_cleared_on_timeout():
    """A call that times out (server never answers) must not leak its
    registry entry — the timeout pop finishes the token."""
    import socket
    from ray_tpu.runtime.rpc import RpcClient

    srv = socket.create_server(("127.0.0.1", 0))   # accepts, never replies
    client = RpcClient(srv.getsockname(), timeout=0.3)
    try:
        with pytest.raises(Exception):
            client.call("never")
        assert not [c for c in tracing.local_stuck_calls(0.0)
                    if c["detail"] == "never"]
    finally:
        client.close()
        srv.close()


# ---------------------------------------------------------------------------
# flight recorder
# ---------------------------------------------------------------------------

def test_flight_snapshot_window_and_dump(tmp_path):
    tracing.enable_tracing(str(tmp_path))
    old = time.time() - 3600.0
    tracing.emit("ancient", start=old, duration=0.001)
    tracing.emit("fresh", start=time.time(), duration=0.001)
    tracing.record_event("marker", detail="x")
    snap = tracing.flight_snapshot(last_s=60.0)
    names = {s["name"] for s in snap["spans"]}
    assert "fresh" in names and "ancient" not in names
    assert any(e["event"] == "marker" for e in snap["events"])
    path = tracing.dump_flight(str(tmp_path / "dump.json"), last_s=60.0)
    dumped = json.load(open(path))
    assert dumped["pid"] == os.getpid()
    assert any(s["name"] == "fresh" for s in dumped["spans"])


def test_crash_dump_on_sigterm(tmp_path):
    """SIGTERM to a process with the crash handler installed leaves a
    flight-<pid>-*.json in the trace dir — no network involved, so it
    works through any partition."""
    code = (
        "import os, signal, time\n"
        "from ray_tpu.util import tracing\n"
        "tracing.enable_tracing(os.environ['TD'])\n"
        "tracing.install_crash_dump()\n"
        "with tracing.span('doomed'):\n"
        "    pass\n"
        "print('ready', flush=True)\n"
        "time.sleep(30)\n"
    )
    env = {**os.environ, "TD": str(tmp_path), "JAX_PLATFORMS": "cpu"}
    proc = subprocess.Popen([sys.executable, "-c", code], env=env,
                            stdout=subprocess.PIPE)
    try:
        assert proc.stdout.readline().strip() == b"ready"
        proc.send_signal(signal.SIGTERM)
        proc.wait(timeout=20)
    finally:
        if proc.poll() is None:
            proc.kill()
    dumps = [f for f in os.listdir(tmp_path)
             if f.startswith(f"flight-{proc.pid}-")]
    assert dumps, os.listdir(tmp_path)
    snap = json.load(open(tmp_path / dumps[0]))
    assert any(s["name"] == "doomed" for s in snap["spans"])


def test_flight_record_state_api_local():
    from ray_tpu.util import state as state_api

    tracing.enable_tracing()
    tracing.emit("local-span", start=time.time(), duration=0.001)
    out = state_api.flight_record()
    assert "local" in out
    assert any(s["name"] == "local-span" for s in out["local"]["spans"])


# ---------------------------------------------------------------------------
# bounded file exporter (satellite)
# ---------------------------------------------------------------------------

def test_span_file_rotation(tmp_path, monkeypatch):
    from ray_tpu.utils.config import get_config

    tracing.enable_tracing(str(tmp_path))
    monkeypatch.setattr(get_config(), "trace_file_max_bytes", 4096)
    for i in range(400):
        tracing.emit(f"rotate-me-{i}", start=time.time(), duration=0.0,
                     attrs={"pad": "x" * 64})
    live = tmp_path / f"spans-{os.getpid()}.jsonl"
    rolled = tmp_path / f"spans-{os.getpid()}.jsonl.1"
    assert rolled.exists()
    # the live file may have just rotated away entirely; when present
    # it respects the cap (plus one record of slack)
    if live.exists():
        assert live.stat().st_size <= 4096 + 4096
    # iter_spans streams rotated-then-live so order is oldest-first and
    # nothing is lost beyond the single-generation rotation bound
    names = [s["name"] for s in tracing.iter_spans(str(tmp_path))]
    assert names
    assert names[-1] == "rotate-me-399"
    idx = [int(n.split("-")[-1]) for n in names]
    assert idx == sorted(idx)


def test_chrome_export_stable_sorted(tmp_path):
    tracing.enable_tracing(str(tmp_path))
    now = time.time()
    with tracing.span("b-span"):
        pass
    tracing.emit("a-span", start=now, duration=0.001)
    ev1 = tracing.export_chrome_trace(str(tmp_path))
    ev2 = tracing.export_chrome_trace(str(tmp_path))
    assert ev1 == ev2                          # deterministic re-export
    xs = [e for e in ev1 if e.get("ph") == "X"]
    assert xs == sorted(xs, key=lambda e: (e["ts"], e["pid"],
                                           e["name"]))


# ---------------------------------------------------------------------------
# overhead gate: tracing-enabled hot path < 3% (PR-4 methodology:
# amortized factor measurement, not end-to-end wall-clock diffing)
# ---------------------------------------------------------------------------

def test_tracing_enabled_hot_path_overhead():
    """Gate: with RAY_TPU_TRACE_ENABLED=1 but no ambient span (the
    steady state of every hot path — spans only exist inside explicitly
    traced requests), RPC dispatch pays one wire_context() probe per
    call. Measure the real per-call RPC cost and the probe cost
    separately (each stable under min-of-k; an end-to-end diff of two
    network loops cannot resolve a ~100ns probe) and gate the ratio."""
    from ray_tpu.runtime.rpc import RpcClient, RpcServer

    class Srv(RpcServer):
        def rpc_echo(self, conn, send_lock, *, x):
            return x

    srv = Srv("127.0.0.1", 0).start()
    client = RpcClient(srv.address)
    try:
        def rpc_loop(n=300):
            t0 = time.perf_counter()
            for i in range(n):
                client.call("echo", x=i)
            return (time.perf_counter() - t0) / n

        def probe_cost(n=200000):
            tracing.enable_tracing()
            t0 = time.perf_counter()
            for _ in range(n):
                tracing.wire_context()
            t1 = time.perf_counter()
            tracing.disable_tracing()
            t2 = time.perf_counter()
            for _ in range(n):
                tracing.wire_context()
            t3 = time.perf_counter()
            return ((t1 - t0) - (t3 - t2)) / n

        tracing.disable_tracing()
        rpc_loop(50)                          # warm
        probe_cost(1000)
        t_op = min(rpc_loop() for _ in range(3))
        t_delta = min(probe_cost() for _ in range(5))
        overhead = t_delta / t_op
        assert overhead < 0.03, \
            f"trace probe costs {overhead:.2%}/RPC (gate: 3%): " \
            f"{t_delta*1e9:.0f}ns probe on a {t_op*1e6:.0f}us call"
    finally:
        client.close()
        srv.stop()


# ---------------------------------------------------------------------------
# cluster acceptance: spans collected across processes into the GCS
# ---------------------------------------------------------------------------

@pytest.fixture
def traced_cluster(monkeypatch):
    from ray_tpu.cluster_utils import Cluster
    from ray_tpu.utils.config import reset_config

    monkeypatch.setenv("RAY_TPU_TRACE_ENABLED", "1")
    monkeypatch.setenv("RAY_TPU_METRICS_PUSH_INTERVAL_S", "0.2")
    reset_config()
    tracing.enable_tracing()
    ray_tpu.shutdown()
    c = Cluster(external_gcs=True)
    c.add_node(num_cpus=2, external=True)
    ray_tpu.init(address=c.gcs_address)
    c.wait_for_nodes(1)
    yield c
    ray_tpu.shutdown()
    c.shutdown()
    tracing.disable_tracing()
    reset_config()


def test_cluster_trace_collected_by_id(traced_cluster):
    """The tentpole acceptance (tasks): one driver-rooted trace whose
    submit-side and worker-side spans cross process boundaries, pushed
    by each process's MetricsPusher, retrievable from the GCS
    TraceStore by trace id via util.state."""
    from ray_tpu import api
    from ray_tpu.util import state as state_api

    @ray_tpu.remote
    def traced_task(x):
        return x * 2

    with tracing.span("driver-root") as root:
        assert ray_tpu.get(traced_task.remote(21), timeout=60) == 42
    tid = root.trace_id

    rt = api._runtime()
    deadline = time.monotonic() + 30
    trace = None
    while time.monotonic() < deadline:
        rt._metrics_pusher.flush_now()        # driver spans -> GCS now
        trace = state_api.get_trace(tid)
        if trace and len(trace["spans"]) >= 3:
            names = {s["name"] for s in trace["spans"]}
            if (any(n.startswith("run:") for n in names)
                    and any(n.startswith("submit:") for n in names)):
                break
        time.sleep(0.25)
    assert trace is not None, "trace never reached the GCS store"
    names = {s["name"] for s in trace["spans"]}
    assert "driver-root" in names
    assert any(n.startswith("submit:") and n.endswith("traced_task")
               for n in names), names
    assert any(n.startswith("run:") and n.endswith("traced_task")
               for n in names), names
    # spans arrived from more than one process
    assert len({s["pid"] for s in trace["spans"]}) >= 2
    # and the listing surfaces it newest-first with the root name
    listed = state_api.list_traces(limit=20)
    assert any(t["trace_id"] == tid for t in listed)


def test_cluster_actor_call_traced_and_stuck_visible(traced_cluster):
    """A deliberately slow actor method shows up in the cluster-wide
    stuck_calls() fan-out — the executing WORKER's always-on in-flight
    registry — carrying the trace id of the span it was called under
    (acceptance: hung call appears with its parent span chain)."""
    from ray_tpu.util import state as state_api

    @ray_tpu.remote
    class Slow:
        def work(self, t):
            time.sleep(t)
            return "done"

    a = Slow.remote()
    ray_tpu.get(a.work.remote(0.0), timeout=60)    # actor is up
    with tracing.span("actor-root") as root:
        ref = a.work.remote(3.0)
        time.sleep(0.8)
        mine = []
        deadline = time.monotonic() + 6
        while time.monotonic() < deadline and not mine:
            out = state_api.stuck_calls(threshold_s=0.3)
            for procs in out.get("nodes", {}).values():
                if not isinstance(procs, dict):
                    continue
                for calls in procs.values():
                    if not isinstance(calls, list):
                        continue
                    mine += [c for c in calls
                             if c["kind"] == "actor_task"
                             and "work" in c["detail"]]
            if not mine:
                time.sleep(0.3)
        assert mine, out
        assert mine[0]["trace_id"] == root.trace_id
        assert mine[0]["age_s"] >= 0.3
        assert ray_tpu.get(ref, timeout=60) == "done"
    # finished execution left the registry
    out = state_api.stuck_calls(threshold_s=0.0)
    for procs in out.get("nodes", {}).values():
        if isinstance(procs, dict):
            for calls in procs.values():
                if isinstance(calls, list):
                    assert not [c for c in calls
                                if c["kind"] == "actor_task"
                                and "work" in c["detail"]]


def test_cluster_flight_record_and_gcs_endpoints(traced_cluster):
    """flight_record("gcs") and per-node flight_record(node_id) answer
    over RPC; the GCS's own spans are collected by its self-loop."""
    from ray_tpu import api
    from ray_tpu.util import state as state_api

    @ray_tpu.remote
    def ping():
        return 1

    with tracing.span("flight-root"):
        assert ray_tpu.get(ping.remote(), timeout=60) == 1

    out = state_api.flight_record("gcs")
    assert "gcs" in out and "pid" in out["gcs"]
    rt = api._runtime()
    nodes = rt._gcs.call("get_nodes", alive_only=True)
    assert nodes
    nid = nodes[0]["node_id"]
    out = state_api.flight_record(nid)
    assert nid in out
    # raylet answer carries its own window plus its workers'
    assert "raylet" in out[nid]


# ---------------------------------------------------------------------------
# serve acceptance: ONE trace across handle -> router -> replica ->
# engine, with stage child spans summing into the traced TTFT
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_serve_request_is_one_trace(ray_tpu_start, tmp_path):
    import jax
    from ray_tpu import serve
    from ray_tpu.models import llama
    from ray_tpu.serve.llm import LLMDeployment
    from ray_tpu.util import state as state_api

    def tiny_builder():
        cfg = llama.llama_tiny()
        return cfg, llama.init_params(cfg, jax.random.key(0))

    tracing.enable_tracing(str(tmp_path))
    try:
        dep = serve.deployment(LLMDeployment).bind(
            tiny_builder, max_batch=2, max_len=64)
        handle = serve.run(dep, name="llm_traced")
        got = handle.call([3, 17, 99], max_new_tokens=4)
        assert len(got) == 4

        # engine stage spans are emitted when the first token's async
        # copy lands; give the drain a beat
        deadline = time.monotonic() + 20
        spans = []
        while time.monotonic() < deadline:
            spans = [s for s in tracing.read_spans(str(tmp_path))
                     if s["name"].startswith(("serve.", "engine."))]
            if any(s["name"] == "engine.prefill" for s in spans):
                break
            time.sleep(0.2)
        by_name = {}
        for s in spans:
            by_name.setdefault(s["name"], s)
        for required in ("serve.request:llm_traced", "serve.route",
                         "engine.request", "engine.queue_wait",
                         "engine.device_wait", "engine.prefill"):
            assert required in by_name, sorted(by_name)
        # ONE trace: every serve span and the request's engine spans
        # share the request root (the engine loop's own phases,
        # engine.iteration and its children, are the engine's trace)
        tid = by_name["serve.request:llm_traced"]["trace_id"]
        loop = {s["trace_id"] for s in spans
                if s["name"] == "engine.iteration"}
        assert len(loop) == 1 and tid not in loop
        spans = [s for s in spans if s["trace_id"] == tid]
        assert {s["name"] for s in spans} >= set(by_name) - {
            "engine.iteration", "engine.admit", "engine.dispatch_prefill",
            "engine.dispatch_decode", "engine.wait_arrivals",
            "engine.wait_device", "engine.emit"}
        # the replica-side run span is in the same trace too
        run = [s for s in tracing.read_spans(str(tmp_path))
               if s["trace_id"] == tid and s["name"].startswith("run:")]
        assert run, "replica execution span missing from the trace"
        # stage children tile the engine.request parent (traced TTFT)
        req = by_name["engine.request"]
        stage_sum = sum(s["duration"] for s in spans
                        if s["name"].startswith("engine.")
                        and s["name"] != "engine.request")
        assert stage_sum == pytest.approx(req["duration"], rel=0.05)
        assert by_name["engine.queue_wait"]["parent_id"] == \
            req["span_id"]

        # retrievable by id via util.state and rendered by the
        # dashboard waterfall endpoint
        trace = state_api.get_trace(tid)
        assert trace is not None and trace["trace_id"] == tid
        from ray_tpu.dashboard import Dashboard

        dash = Dashboard(port=0).start()
        try:
            with urllib.request.urlopen(
                    f"{dash.url}/api/trace/{tid}", timeout=10) as r:
                body = json.loads(r.read())
            assert body["trace"]["trace_id"] == tid
            rows = body["waterfall"]
            assert any(r_["name"] == "engine.prefill" for r_ in rows)
            depth = {r_["name"]: r_["depth"] for r_ in rows}
            assert depth["serve.request:llm_traced"] == 0
            assert depth["engine.prefill"] > depth["engine.request"] - 1
        finally:
            dash.stop()
    finally:
        serve.shutdown()
