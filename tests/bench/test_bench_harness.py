"""The harness end to end on the CPU at toy size, in a temporary copy to
which configurations, model families (one of them not Llama-shaped),
traffic mixes, cells and a per-layer metric are added as new files and
entries; and the contract of BENCHMARK.json."""

import json
import os
import re
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import bench_pins  # noqa: E402
import bench_toy  # noqa: E402

from benchmark import harness  # noqa: E402

ROOT = bench_toy.REPO
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")


@pytest.fixture(scope="module")
def toy(tmp_path_factory):
    return bench_toy.make_toy(str(tmp_path_factory.mktemp("bench")))


def last_json(stdout: str):
    line = stdout.strip().splitlines()[-1]
    return line, (json.loads(line.split(" ", 1)[1])
                  if line.startswith("rehearsal ") else None)


def test_added_files_are_found_by_name_alone(toy):
    bench, cell, config, traffic = harness.load_cell("toy-chat", root=toy)
    assert cell["config"] == "toy-serve" and config["hidden_size"] == 64
    assert traffic["generator"] == "chat_sessions"
    assert config["family"] == "toy_family"     # a family added as a file
    for family in ("toy_family", "toy_norope", "toy_gpt"):
        assert os.path.exists(os.path.join(toy, "benchmark", "families",
                                           family + ".py"))
    names = [m["name"] for m in harness.cell_metrics(
        bench, "toy-chat", "per_layer")]
    assert "toy_bursts" in names and "prefix_hit_share.chat" in names
    assert "train_mfu" not in names and "compiles_in_window" in names
    assert harness.load_reader("toy_bursts", root=toy)(
        type("Run", (), {"counters": {"occupancy_samples": [1, 2]}})) == 2.0
    with pytest.raises(SystemExit):
        harness.load_cell("no-such-cell", root=toy)


@pytest.mark.parametrize("cell,counted", [
    ("toy-chat", {"prefix_hit_share.chat", "compiles_in_window",
                  "toy_bursts"}),
    ("toy-doc", {"prefix_hit_share", "compiles_in_window"}),
    ("toy-train", {"compiles_in_window"})])
def test_rehearsal_runs_the_cell_and_prints_counts_only(toy, cell, counted):
    """A traced run on the CPU: the run is correct and counts what a CPU
    can count; no time, rate, share of a device or roofline is printed,
    and there is no result line."""
    r = bench_toy.run_cell(toy, cell, trace=1)
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-2000:]
    line, got = last_json(r.stdout)
    assert got is not None, line
    assert got["correct"] is True and got["failed"] == 0
    assert got["attempted"] > 0 and got["device"]["platform"] == "cpu"
    assert set(got["metrics"]) == counted
    # every prefill and decode program is warmed through the public path.
    # The engine's short "drain" decode chunk cannot be reached on purpose
    # that way (it is taken when a request waits at the instant a chunk
    # is dispatched): the backlog meets it in its ramp; the toy chat, whose
    # chunks last a millisecond, may meet it in the window, once for each
    # of its three decode cells at most (on the chip: PERF.md, PR 23)
    assert got["metrics"]["compiles_in_window"]["value"] <= (
        3.0 if cell == "toy-chat" else 0.0)
    assert "memory_peak_bytes" not in got["device"]


def test_a_family_that_is_not_llama_shaped_is_files_only(toy):
    """A GPT-2 block through the trainer's normal path: the adapter, the
    reference and the counts are the family's one file, written into the
    copy beside what is there (``make_toy`` checks that nothing that was
    there changed). The first two steps' losses are held to the family's
    own reference, and the operations a token are the family's count."""
    r = bench_toy.run_cell(toy, "toy-gpt-train")
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-2000:]
    _, got = last_json(r.stdout)
    assert got["correct"] is True and got["failed"] == 0
    # 6 x (2 x (4 x 64 x 64 + 2 x 64 x 128) + 64 x 512) + 3 x 2 x 2 x 64 x 64
    assert "flops_per_token=638976.0000" in r.stdout
    assert "loss_gap=0.00" in r.stdout


def test_the_familys_reference_decides_correct(toy):
    """The same engine, weights and traffic as ``toy-doc`` (which reads
    ``correct`` true against the toy family's own copy of the reference,
    above) under a family whose reference leaves out the rotary embedding:
    every request finishes, and the run is not correct."""
    r = bench_toy.run_cell(toy, "toy-doc-norope", seconds=1.0)
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-2000:]
    _, got = last_json(r.stdout)
    assert got["correct"] is False and got["failed"] == 0
    assert got["attempted"] > 0
    gap = float(re.search(r"bench reference: token_gap=([0-9.]+)",
                          r.stdout).group(1))
    assert gap > 10 * 0.1           # far over TOKEN_GAP_TOL, not near it


def test_rehearsal_of_the_sharded_train_cell_on_four_devices(toy):
    """The fsdp-4 path end to end on four virtual CPU devices: the sharded
    step's first loss is held to the plain reference's."""
    r = bench_toy.run_cell(toy, "toy-train4", devices=4)
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-2000:]
    _, got = last_json(r.stdout)
    assert got["correct"] is True and got["device"]["count"] == 4
    assert "loss_gap=0.00" in r.stdout


def test_no_tpu_no_result_line(toy):
    r = bench_toy.run_cell(toy, "toy-train", rehearse=False)
    assert r.returncode != 0
    lines = r.stdout.strip().splitlines()
    assert not lines or not lines[-1].startswith("{")
    assert "not a TPU" in r.stderr


def test_end_to_end_rehearsal_prints_no_rate(toy):
    r = bench_toy.run_cell(toy, "toy-doc", trace=0)
    assert r.returncode == 0, r.stderr[-2000:]
    _, got = last_json(r.stdout)
    assert got["metrics"] == {}      # every end-to-end metric is a time


def test_benchmark_json_keeps_the_contract(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert 1 <= bench["run_seconds"] <= 51
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) < 65536
    cells = {w["name"]: w for w in bench["workloads"]}
    configs = {c["name"]: c for c in bench["configs"]}
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.1
    assert sum(w["chips"] == 4 for w in cells.values()) <= max(
        1, len(cells) // 4)
    for w in cells.values():
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["config"] in configs and len(w["why"]) <= 200
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert os.path.exists(os.path.join(
            ROOT, "benchmark", "traffic", w["traffic"] + ".json"))
    for c in configs.values():
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        with open(os.path.join(ROOT, c["file"])) as f:
            data = json.load(f)
        # the rule, not one literal: what is cut is depth, the layer
        # pattern, the experts held or the vocabulary, and every key of it
        # differs from the source's value
        bench_pins.check_reduced(c, data)
    for m in bench["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert 0.01 <= m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")
    for m in bench["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert NAME.match(m["name"]) and m["moves"] in e2e
        assert re.match(r"^[A-Za-z0-9_/%.\-]{1,16}$", m["unit"])
        assert harness.load_reader(m["name"]) is not None
        # the metric it moves is reported wherever this one is
        here = m.get("workloads", list(cells))
        there = e2e[m["moves"]].get("workloads", list(cells))
        assert set(here) <= set(there), m["name"]
    # one entry a metric: no name twice, and in no cell two entries of
    # one reader (a stem with a suffix for each end-to-end metric its
    # cells report); room for the next cells' own
    names = [m["name"] for m in bench["per_layer"]]
    assert len(names) == len(set(names)) < 64
    for name in cells:
        mine = [m for m in bench["end_to_end"]
                if name in m.get("workloads", [name])]
        assert len(mine) >= 2
        assert harness.cell_metrics(bench, name, "per_layer")
        stems = [bench_pins.stem(m["name"]) for m in harness.cell_metrics(
            bench, name, "per_layer")]
        assert len(stems) == len(set(stems)), name


def test_files_under_paths_have_contract_names(bench):
    ok = re.compile(r"^[A-Za-z0-9_.\-/]+$")
    for path in bench["paths"]:
        for d, dirs, files in os.walk(os.path.join(ROOT, path)):
            dirs[:] = [x for x in dirs if x != "__pycache__"]
            for name in files:
                rel = os.path.relpath(os.path.join(d, name), ROOT)
                assert ok.match(rel), rel
