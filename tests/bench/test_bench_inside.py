"""The readers that measure the serving layers and the kernels from inside
(``benchmark/inside.py``, PR 24): on synthetic traces with named programs
and kernels, on synthetic span lists, and on the spans a toy serve cell
leaves in the process's ring when it is run with a profiler session."""

import json
import os
import subprocess
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import bench_pins  # noqa: E402
import bench_toy  # noqa: E402
from test_bench_trace import engine_like_trace  # noqa: E402

from benchmark import harness, inside, readers, systems  # noqa: E402
from benchmark.trace import Trace  # noqa: E402

SPAN_METRICS = ("engine_host_share", "prefill_device_wait_p50_ms",
                "prefill_run_p50_ms", "decode_active_share")


def repeated(trace, times: int):
    """The engine-like trace ``times`` times end to end."""
    dev, period = trace.devices[0], trace.extent_s
    out = {"modules": [], "ops": [], "async_ops": []}
    for k in range(times):
        out["modules"] += [(n, s + k * period, e + k * period)
                           for n, s, e in dev["modules"]]
        out["ops"] += [(n, s + k * period, e + k * period)
                       for n, s, e in dev["ops"]]
    return Trace([out], [], extent_s=period * times)


def regrouped(trace):
    """What a perf_opt PR on the layer loop may do, and a model with more
    than one layer loop does by itself: no operation of the decode
    program repeats steps x layers times any more."""
    dev = trace.devices[0]
    ops = [(f"%body.{i} = f32[1]{{0}} fusion(f32[1]{{0}} %x)"
            if n.startswith("%body") else n, s, e)
           for i, (n, s, e) in enumerate(dev["ops"])]
    return Trace([{"modules": dev["modules"], "ops": ops, "async_ops": []}],
                 [], extent_s=trace.extent_s)


@pytest.mark.parametrize("reshape", [lambda t: t, regrouped],
                         ids=["scanned", "regrouped"])
def test_engine_programs_are_told_apart_by_their_names(reshape):
    """Whole decode runs: two of chunk 4 (17 s each) and one of chunk 2
    (9 s), 43 s over 10 steps, however the programs loop inside."""
    trace = reshape(engine_like_trace())
    assert inside.decode_program_step_ms(trace) == pytest.approx(4300.0)
    # one prefill run is no sample of five
    assert inside.prefill_program_share(trace) is None
    five = reshape(repeated(engine_like_trace(), 5))
    assert inside.prefill_program_share(five) == pytest.approx(
        100.0 * 5 * 5.0 / five.busy_s())
    assert inside.prefill_program_runs_ms(five) == pytest.approx([5000.0] * 5)
    # the runs that an edge cut in one copy are whole inside five: all
    # but the first and the last, 255 s over 74 steps
    assert inside.decode_program_step_ms(five) == pytest.approx(255e3 / 74)
    run = type("Run", (), {"trace": five})
    assert readers.device_idle_share(run) == pytest.approx(
        100.0 * (1 - five.busy_s() / five.extent_s))


def test_decode_roofline_is_the_familys_bytes_over_the_named_step(monkeypatch):
    """``decode_roofline.*``: the step time of the programs found by name
    under the bytes the configuration's family counts from the run's
    counters; a family with nothing to count gives no metric."""
    with open(os.path.join(bench_toy.REPO, "benchmark", "configs",
                           "mistral-7b-v0.3-d12.json")) as f:
        config = json.load(f)
    counters = {"live_kv_tokens_mean": 20000.0}
    run = type("Run", (), {"trace": engine_like_trace(), "config": config,
                           "counters": counters,
                           "device": {"platform": "tpu",
                                      "kind": "TPU v5 lite"}})
    family = systems.family(config)
    nbytes = family.decode_step_bytes(config, counters)
    assert nbytes > family.decode_step_bytes(config, {}) > 5e9
    assert readers.decode_roofline(run) == pytest.approx(
        100.0 * nbytes / 819e9 / 4.3)
    assert harness.load_reader("decode_roofline.doc")(run) == pytest.approx(
        readers.decode_roofline(run))
    monkeypatch.setattr(family, "decode_step_bytes", lambda c, k: None)
    assert readers.decode_roofline(run) is None
    run.trace = None
    assert readers.decode_roofline(run) is None


def kernel(name, out="bf16[6,32,2048,128]{3,2,1,0}"):
    return (f"%{name} = {out} custom-call(bf16[6,32,2048,128]{{3,2,1,0}} "
            f'%q), custom_call_target="tpu_custom_call"')


def test_flash_kernels_apart_by_their_names():
    mm = "%fusion.1 = bf16[8,8]{1,0} fusion(bf16[8,8]{1,0} %x), kind=kOutput"
    ops, modules = [], []
    for step in range(3):
        t0 = 10.0 * step
        modules.append(("jit__step(7)", t0, t0 + 10.0))
        for layer in range(2):
            t = t0 + 5.0 * layer
            ops += [(kernel("flash_fwd.6"), t, t + 0.25),
                    (mm, t + 0.25, t + 4.0),
                    (kernel("flash_dq.12"), t + 4.0, t + 4.5),
                    (kernel("flash_dkv.12"), t + 4.5, t + 5.0)]
    run = type("Run", (), {"trace": Trace(
        [{"modules": modules, "ops": ops, "async_ops": []}], [],
        extent_s=30.0)})
    fwd = inside.kernel_share(run.trace, ("flash_fwd",))
    bwd = inside.kernel_share(run.trace, ("flash_dq", "flash_dkv"))
    assert fwd == pytest.approx(5.0) and bwd == pytest.approx(20.0)
    assert harness.load_reader("flash_fwd_share")(run) == pytest.approx(5.0)
    assert harness.load_reader("flash_bwd_share")(run) == pytest.approx(20.0)
    # a kernel with no name (the parent's) is in neither
    unnamed = Trace([{"modules": modules, "async_ops": [], "ops": [
        (kernel("shard_map.417"), s, e) if "custom-call" in n else (n, s, e)
        for n, s, e in ops]}], [], extent_s=30.0)
    assert inside.kernel_share(unnamed, ("flash_fwd",)) is None


def span(name, start, duration, parent=None, sid=None, **attrs):
    rec = {"name": name, "start": start, "duration": duration,
           "span_id": sid or f"{name}@{start}", "parent_id": parent,
           "trace_id": "t"}
    if attrs:
        rec["attrs"] = attrs
    return rec


def loop_spans(n=6):
    """n iterations of 1 s: 0.1 admit (one prefill dispatch of 2, which
    waited 0.3 s on the device and ran 0.2 s), 0.25 waiting for arrivals,
    0.05 decode dispatch (3 of 4 slots), 0.5 waiting for the device,
    0.05 emit, 0.05 named by no phase."""
    out = []
    for i in range(n):
        t, it = float(i), f"it{i}"
        out += [
            span("engine.iteration", t, 1.0, sid=it, seq=i),
            span("engine.admit", t, 0.1, it, sid=f"ad{i}"),
            span("engine.dispatch_prefill", t + 0.02, 0.05, f"ad{i}",
                 sid=f"dp{i}", seq=2 * i, group=2, bucket=16),
            span("device.run", t + 0.37, 0.2, f"dp{i}", kind="prefill",
                 seq=2 * i, wait_s=0.3),
            span("engine.wait_arrivals", t + 0.1, 0.25, it, what="window"),
            span("engine.dispatch_decode", t + 0.35, 0.05, it, sid=f"dd{i}",
                 seq=2 * i + 1, chunk=16, live=3, slots=4),
            span("device.run", t + 0.57, 0.7, f"dd{i}", kind="decode",
                 seq=2 * i + 1, wait_s=0.17),
            span("engine.wait_device", t + 0.4, 0.5, it, what="chunk"),
            span("engine.emit", t + 0.9, 0.05, it, what="chunk"),
        ]
    return out


def test_span_readers_on_a_synthetic_loop():
    spans = loop_spans()
    assert inside.engine_host_share(spans) == pytest.approx(25.0)
    assert inside.prefill_device_wait_p50_ms(spans) == pytest.approx(300.0)
    assert inside.prefill_run_p50_ms(spans) == pytest.approx(200.0)
    assert inside.decode_active_share(spans) == pytest.approx(75.0)
    shares = inside.phase_shares(spans)
    assert shares == pytest.approx({
        "admit": 10.0, "wait_arrivals": 25.0, "dispatch_decode": 5.0,
        "wait_device": 50.0, "emit": 5.0, "self": 5.0})
    # the watcher's stamps against a trace's clock, run by run
    mods = [("jit_paged_prefill_w8(1)", 10.0 * i, 10.0 * i + 0.197)
            for i in range(6)]
    check = inside.stamp_check(spans, Trace(
        [{"modules": mods, "ops": [], "async_ops": []}], []))
    assert check["host_stamps_p50_ms"] == pytest.approx(200.0)
    assert check["device_clock_p50_ms"] == pytest.approx(197.0)
    assert check["run_by_run_diff_max_ms"] == pytest.approx(3.0)
    assert "run_by_run_diff_p50_ms" not in inside.stamp_check(
        spans, Trace([{"modules": mods[:5], "ops": [], "async_ops": []}], []))
    # a child whose iteration the ring dropped is skipped, not counted
    # against the others
    orphan = span("engine.wait_device", 9.0, 50.0, "dropped", what="chunk")
    assert inside.engine_host_share(spans + [orphan]) == pytest.approx(25.0)
    # fewer than five samples is no median; no spans (the parent) is None
    for read in (inside.engine_host_share, inside.prefill_run_p50_ms,
                 inside.prefill_device_wait_p50_ms,
                 inside.decode_active_share):
        assert read(loop_spans(4)) is None
        assert read(None) is None and read([]) is None


def test_stage_medians_need_all_five_children():
    spans = []
    for i in range(7):
        rid = f"r{i}"
        spans.append(span("engine.request", 0.0, 0.5 + 0.01 * i, sid=rid))
        for name, d in zip(inside.STAGES, (0.01 * i, 0.3, 0.15, 0.04, 0.01)):
            if i == 6 and name == "ship":
                continue            # dropped from the ring: not counted
            spans.append(span("engine." + name, 0.0, d, rid))
    got = inside.stage_medians_ms(spans)
    assert got["requests"] == 6
    assert got["device_wait"] == pytest.approx(300.0)
    assert got["queue_wait"] == pytest.approx(25.0)
    assert got["ttft"] == pytest.approx(525.0)
    assert inside.stage_medians_ms(None) == {"requests": 0}
    assert inside.summary(loop_spans())["prefill_dispatches"] == 6


def test_new_readers_return_none_without_a_trace_or_spans(monkeypatch):
    """A CPU rehearsal has no device trace, and the parent commit's
    program records no spans and has no accessor: None, never a raise."""
    from benchmark import program_spans
    from ray_tpu.util import tracing

    run = type("Run", (), {"trace": None, "counters": {}, "config": {}})
    for name in ("decode_program_step_ms.chat", "prefill_program_share.doc",
                 "flash_fwd_share", "flash_bwd_share"):
        assert harness.load_reader(name)(run) is None
    monkeypatch.delattr(tracing, "recorded_spans")
    program_spans.engine_spans.cache_clear()
    try:
        assert program_spans.engine_spans() is None
        for name in SPAN_METRICS:
            assert harness.load_reader(name + ".chat")(run) is None
    finally:
        program_spans.engine_spans.cache_clear()


@pytest.mark.parametrize("cell,stems", [
    ("serve-chat", SPAN_METRICS + ("decode_program_step_ms",
                                   "prefill_program_share")),
    ("serve-doc", ("engine_host_share", "decode_active_share",
                   "decode_program_step_ms", "prefill_program_share")),
    ("train-2k", ("flash_fwd_share", "flash_bwd_share")),
    ("train-2k-fsdp4", ("flash_fwd_share", "flash_bwd_share"))])
def test_new_entries_name_their_cells_and_are_not_counters(bench, cell,
                                                           stems):
    """PR 24's entries, each found by its name with the cell under its
    ``workloads``, wherever in the list they stand."""
    for m in bench_pins.reports(bench, cell, stems).values():
        assert m["workloads"] and m["source"] in ("device_trace",
                                                  "program_span")


DRIVER = '''
import json, sys
sys.path.insert(0, ".")
from benchmark import harness
rc = harness.main(["--workload", sys.argv[1], "--seed", "3", "--seconds",
                   "5", "--trace", "1", "--rehearse", "--set", "trace_s=4"])
from benchmark import program_spans
run = type("Run", (), {"trace": None})
values = {name: harness.load_reader(name + sys.argv[2])(run)
          for name in json.loads(sys.argv[3])}
print("inside " + json.dumps({"rc": rc, "values": values,
      "spans": len(program_spans.engine_spans())}))
'''


@pytest.mark.parametrize("cell,suffix", [("toy-chat", ".chat"),
                                         ("toy-doc", "")])
def test_rehearsed_serve_cell_leaves_spans_every_reader_can_read(
        tmp_path, cell, suffix):
    """A toy serve cell with a profiler session over most of its window,
    in this file's own copy: afterwards the process's ring holds the
    engine loop's spans, and every by-span reader gives a finite value
    (counts of a CPU run: never printed as a result)."""
    root = bench_toy.make_toy(str(tmp_path))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=bench_toy.REPO + os.pathsep
               + os.environ.get("PYTHONPATH", ""),
               JAX_COMPILATION_CACHE_DIR=os.path.join(root, ".jax_cache"))
    names = [n for n in SPAN_METRICS
             if suffix == ".chat" or "p50" not in n]
    r = subprocess.run(
        [sys.executable, "-c", DRIVER, cell, suffix, json.dumps(names)],
        cwd=root, env=env, capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-2000:]
    lines = r.stdout.strip().splitlines()
    got = json.loads(lines[-1].split(" ", 1)[1])
    assert got["rc"] == 0 and got["spans"] > 50
    rehearsal = json.loads(lines[-2].split(" ", 1)[1])
    assert rehearsal["correct"] is True
    # the rehearsal line still prints counters only
    assert not any(n.split(".")[0] in SPAN_METRICS
                   for n in rehearsal["metrics"])
    assert any(line.startswith("bench engine_spans:") for line in lines)
    for name, value in got["values"].items():
        assert value is not None and value == value, (name, got)
        assert 0.0 <= value < 1e6
    assert 0.0 < got["values"]["engine_host_share"] <= 100.0
    assert 0.0 < got["values"]["decode_active_share"] <= 100.0
    assert got["values"]["engine_host_share"] == got["values"][
        "engine_host_share"]
