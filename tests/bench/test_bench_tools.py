"""The builder's tools under ``benchmark/tools/`` (PR 49) and the run's own
record of a stall: the sampler's reading of ``schedstat`` samples (which of
three kinds a stall of the benchmark's thread was), a sampled child, what
``runs.py`` keeps of a run's output, and ``serving.StallWatch``."""

import gc
import json
import os
import sys
import time

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import bench_toy  # noqa: E402

sys.path.insert(0, os.path.join(bench_toy.REPO, "benchmark", "tools"))

import runs  # noqa: E402
import stall_sampler  # noqa: E402

from benchmark import serving  # noqa: E402

PID, OTHER = 100, 101
MS = 1_000_000      # nanoseconds


def samples(states: list, period: float = 0.01) -> list:
    """One sample a state of the main thread: ``polling`` (five
    timeslices, a tenth running), ``running``, ``waiting`` (runnable, no
    core) or ``blocked`` (asleep); another thread runs all through the
    blocked samples."""
    t, run, wait, slices, other = 0.0, 0, 0, 0, 0
    out = [(t, {PID: (run, wait, slices), OTHER: (other, 0, 0)})]
    for state in states:
        t += period
        if state == "polling":
            run, slices = run + 1 * MS, slices + 5
        elif state == "running":
            run, slices = run + 10 * MS, slices + 1
        elif state == "waiting":
            wait, slices = wait + 9 * MS, slices + 1
        else:
            other += 10 * MS
        out.append((t, {PID: (run, wait, slices), OTHER: (other, 0, 0)}))
    return out


@pytest.mark.parametrize("state", ["running", "waiting", "blocked"])
def test_a_stall_reads_as_one_of_three_kinds(state):
    found = stall_sampler.episodes(
        samples(["polling"] * 20 + [state] * 150 + ["polling"] * 20),
        PID, 0.25, 0.01)
    (e,) = found
    assert e["kind"] == state and e["seconds"] == pytest.approx(1.5, abs=0.08)
    assert e["start"] == pytest.approx(0.2, abs=0.06)
    if state == "running":      # the thread's own work
        assert e["main_run_s"] == pytest.approx(1.5, abs=0.08)
    if state == "waiting":      # runnable and given no core: the machine
        assert e["main_wait_s"] > 1.3 and e["main_run_s"] < 0.1
    if state == "blocked":      # asleep while another thread ran: its holder
        assert e["main_slices"] <= 5 and e["top"] == [[OTHER, pytest.approx(
            1.5, abs=0.08)]]
    assert e["sampler_late"] == 0


def test_short_stretches_and_polling_are_no_episode():
    assert stall_sampler.episodes(samples(["polling"] * 300), PID, 0.25,
                                  0.01) == []
    assert stall_sampler.episodes(
        samples(["polling"] * 5 + ["blocked"] * 20 + ["polling"] * 5),
        PID, 0.25, 0.01) == []
    assert stall_sampler.classify(0.0, 0.0, 0, 0.0) == "polling"
    # a sampler that was itself held up says so on the episode
    late = samples(["blocked"] * 40)
    late[20:] = [(t + 0.5, row) for t, row in late[20:]]
    (e,) = stall_sampler.episodes(late, PID, 0.25, 0.01)
    assert e["sampler_gap_max_s"] == pytest.approx(0.51) and \
        e["sampler_late"] == 1


def test_a_kernel_without_schedstat_gives_the_same_three_numbers():
    """From a thread's ``stat`` line (state and CPU ticks): CPU time as
    nanoseconds run, the time it was seen runnable with its CPU time
    standing still as runnable-wait, and a timeslice wherever it ran or
    wanted to."""
    tasks = stall_sampler.Tasks(os.getpid())
    tasks.source = "stat"
    tick = stall_sampler.TICK_NS

    def line(state: str, ticks: int) -> bytes:
        return (f"77 (python3 (x)) {state} 1 1 1 0 -1 0 0 0 0 0 "
                f"{ticks} 2 0 0 20 0 9 0").encode()

    assert tasks._from_stat(77, line("S", 5), 1.00) == (7 * tick, 0, 0)
    assert tasks._from_stat(77, line("R", 6), 1.01) == (8 * tick, 0, 1)
    # runnable and not a tick further: it waited for a core
    got = tasks._from_stat(77, line("R", 6), 1.02)
    assert got[0] == 8 * tick and got[2] == 2
    assert got[1] == pytest.approx(0.01e9, rel=1e-3)
    # asleep: nothing grows
    assert tasks._from_stat(77, line("S", 6), 1.03) == got


def test_the_sampler_beside_a_child_names_its_long_sleep(tmp_path):
    """A child that sleeps in short steps, then once for 0.6 s on its main
    thread: the record holds that stretch as ``blocked`` (a thread asleep
    has no timeslice however busy the machine is, so this holds under the
    tests' own load) and the child's exit code is the sampler's."""
    out = tmp_path / "record.json"
    child = ("import time\n"
             "for _ in range(100): time.sleep(0.002)\n"
             "time.sleep(0.6)\n"
             "for _ in range(100): time.sleep(0.002)\n"
             "raise SystemExit(7)\n")
    rc = stall_sampler.main(["--out", str(out), "--least", "0.3", "--",
                             sys.executable, "-c", child])
    assert rc == 7
    record = json.loads(out.read_text())
    assert record["samples"] > 50 and record["threads"] >= 1
    assert record["source"] in ("schedstat", "stat")
    asleep = [e for e in record["episodes"] if e["kind"] == "blocked"]
    assert asleep and 0.4 <= max(e["seconds"] for e in asleep) <= 1.5


OUTPUT = """bench start: workload=serve-chat seed=7 compile_cache=/x/.jax_cache
bench stalls: {"window": [100.0, 151.0], "holdups": [[120.0, 1.7, "sleep"]], "collections": [], "gc_counts": [3, 0, 0], "threads": {"9": "MainThread"}}
bench open_loop: scheduled=900 measured=512 slots_mean=11.2500 engine_error=None
bench setup: total_s=41.2000 backend_init_s=14.1000
bench compile_cache: hits=70 misses=0
bench counts: attempted=512 failed=0 correct=True compiles_in_window=0
{"correct": true, "attempted": 512, "failed": 0, "metrics": {"ttft_p90_ms": {"value": 201.5, "unit": "ms"}}, "device": {"platform": "tpu"}}
"""


def test_runs_keeps_a_runs_lines_its_stalls_and_its_result():
    got = runs.parse(OUTPUT)
    assert got["result"]["metrics"]["ttft_p90_ms"]["value"] == 201.5
    assert got["stalls"]["holdups"] == [[120.0, 1.7, "sleep"]]
    assert got["said"]["open_loop"] == {
        "scheduled": 900, "measured": 512, "slots_mean": 11.25,
        "engine_error": "None"}
    assert got["said"]["counts"]["compiles_in_window"] == 0
    rest = {"main_run_s": 0.0, "main_wait_s": 0.0, "others_run_s": 0.0,
            "machine_steal_s": 0.0, "sampler_gap_max_s": 0.01}
    record = {"episodes": [
        dict(rest, kind="blocked", start=50.0, seconds=2.0),    # set-up
        dict(rest, kind="waiting", start=119.9, seconds=1.8,    # the stall
             main_wait_s=1.7, machine_steal_s=20.0),
        dict(rest, kind="running", start=170.0, seconds=0.5),   # the drain
        dict(rest, kind="running", start=190.0, seconds=0.5)],  # afterwards
        "sampler_held": [[60.0, 0.2, "sleep"], [120.5, 1.1, "sleep"]]}
    cut = runs.in_window(record, got["stalls"])
    assert [e["start"] for e in cut["episodes"]] == [119.9, 170.0]
    assert cut["sampler_held"] == [[120.5, 1.1, "sleep"]]
    line = dict(got, cell="serve-chat", seed=7, trace=0, set=[], rc=0,
                wall_s=111.0, sampler=cut)
    brief = json.loads(runs.brief(line))
    assert brief["metrics"] == {"ttft_p90_ms": 201.5}
    assert brief["episodes"] == [
        ["waiting", 1.8, 0.0, 1.7, 0.0, 20.0, 0.01],
        ["running", 0.5, 0.0, 0.0, 0.0, 0.0, 0.01]]
    assert brief["notes"]["measured"] == 512 and brief["setup"] == 41.2
    assert runs.parse("no result here\n")["result"] is None


def test_the_runs_own_watch_notes_holdups_and_long_collections(capsys):
    watch = serving.StallWatch()
    t = time.perf_counter()
    assert watch.took(t - 0.5, 0.002, "sleep") >= t     # half a second late
    watch.took(t, 0.002, "sleep")                       # on time: no note
    watch._on_gc("start", {"generation": 2})
    watch._t -= 0.05                                    # a 50 ms collection
    watch._on_gc("stop", {"generation": 2})
    gc.collect()
    watch.report(10.0, 61.0)
    assert watch._on_gc not in gc.callbacks
    line = capsys.readouterr().out.strip()
    assert line.startswith("bench stalls: ")
    got = json.loads(line[len("bench stalls: "):])
    assert got["window"] == [10.0, 61.0]
    ((at, late, what),) = got["holdups"]
    assert what == "sleep" and late == pytest.approx(0.5, abs=0.05)
    assert any(c[2] == 2 and c[1] >= 0.05 for c in got["collections"])
    assert got["gc_counts"][2] >= 1
    assert "MainThread" in got["threads"].values()
