"""State API, metrics, timeline, CLI tests (reference: state/metrics
tests + scripts tests)."""

import json

import pytest

import ray_tpu
from ray_tpu.util import metrics as m
from ray_tpu.util import state


@pytest.fixture
def rt(ray_tpu_start):
    return ray_tpu_start


def test_state_local_mode(rt):
    @ray_tpu.remote
    class A:
        def ping(self):
            return 1

    a = A.options(name="obs_actor").remote()
    ray_tpu.get(a.ping.remote())
    actors = state.list_actors()
    assert any(x["name"] == "obs_actor" for x in actors)
    nodes = state.list_nodes()
    assert len(nodes) == 1 and nodes[0]["alive"]
    summary = state.cluster_summary()
    assert summary["initialized"] and summary["mode"] == "local"
    assert summary["actors"].get("ALIVE", 0) >= 1


def test_task_timeline(rt, tmp_path):
    @ray_tpu.remote
    def traced(x):
        return x

    ray_tpu.get([traced.remote(i) for i in range(3)])
    trace = ray_tpu.timeline(str(tmp_path / "trace.json"))
    assert len([e for e in trace if e["name"].endswith("traced")]) == 3
    loaded = json.loads((tmp_path / "trace.json").read_text())
    assert loaded and loaded[0]["ph"] == "X"


def test_state_cluster_mode():
    from ray_tpu.cluster_utils import Cluster

    ray_tpu.shutdown()
    c = Cluster()
    c.add_node(num_cpus=2)
    try:
        ray_tpu.init(address=c.gcs_address)

        @ray_tpu.remote
        class B:
            def ping(self):
                return 1

        b = B.options(name="cl_actor").remote()
        ray_tpu.get(b.ping.remote())
        assert any(x["name"] == "cl_actor" for x in state.list_actors())
        assert state.cluster_summary()["mode"] == "cluster"
        assert state.list_jobs() == [] or isinstance(state.list_jobs(), list)
    finally:
        ray_tpu.shutdown()
        c.shutdown()


def test_metrics_counter_gauge_histogram():
    c = m.Counter("test_requests_total", "reqs", ("route",))
    c.inc(tags={"route": "/a"})
    c.inc(2, tags={"route": "/a"})
    g = m.Gauge("test_inflight", "inflight")
    g.set(5)
    h = m.Histogram("test_latency_s", "lat", boundaries=(0.1, 1.0))
    h.observe(0.05)
    h.observe(0.5)
    h.observe(5.0)
    text = m.export_prometheus()
    assert 'test_requests_total{route="/a"} 3.0' in text
    assert "test_inflight 5.0" in text
    assert "test_latency_s_count 3" in text
    assert 'test_latency_s_bucket{le="0.1"} 1' in text


def test_cli_status_and_list(capsys):
    from ray_tpu.cluster_utils import Cluster
    from ray_tpu.scripts.cli import main

    ray_tpu.shutdown()
    c = Cluster()
    c.add_node(num_cpus=2)
    addr = f"{c.gcs_address[0]}:{c.gcs_address[1]}"
    try:
        main(["status", "--address", addr])
        out = capsys.readouterr().out
        assert "Nodes: 1 alive" in out
        main(["list", "nodes", "--address", addr])
        rows = json.loads(capsys.readouterr().out)
        assert len(rows) == 1
        main(["memory", "--address", addr])
        out = capsys.readouterr().out
        assert "Cluster memory summary (mode=" in out
        assert "\nOwned: " in out and " owners | store allocated " in out
    finally:
        c.shutdown()
