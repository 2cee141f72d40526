"""The reduction from a profiler trace to the per-layer metrics: on a small
trace recorded on a v5e in PR 23 (a one-off recorder, not kept: four rounds of a
three-matmul program and the flash kernel, with benchmark spans and a 5 ms
sleep between rounds), on synthetic events for what that trace lacks
(collectives, the engine's programs and phases), and on a trace of host
spans recorded here."""

import os
import threading
import time

import pytest

from benchmark import trace as tr
from benchmark.trace import Trace

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                    "small.xplane.pb")


@pytest.fixture(scope="module")
def recorded():
    return Trace.from_file(DATA)


def test_recorded_trace_planes_and_spans(recorded):
    assert len(recorded.devices) == 1
    dev = recorded.devices[0]
    assert len(dev["modules"]) == 8 and len(dev["ops"]) == 48
    names = {n for n, _, _ in recorded.spans}
    assert names == {"bench.step_call", "bench.wait", "bench.sleep"}


def test_recorded_trace_busy_and_program_time(recorded):
    busy = recorded.busy_s()
    step_s, step_runs = recorded.module_time(
        lambda n: n.startswith("jit_small_step("))
    flash_s, flash_runs = recorded.module_time(
        lambda n: n.startswith("jit_flash("))
    assert (step_runs, flash_runs) == (4, 4)
    assert step_s == pytest.approx(168.8e-6, rel=0.01)
    assert flash_s == pytest.approx(581.6e-6, rel=0.01)
    # operations fill their programs but for launch gaps
    assert busy == pytest.approx(step_s + flash_s, rel=0.02)
    extent = (max(e for _, _, e in recorded.devices[0]["ops"])
              - min(s for _, s, _ in recorded.devices[0]["ops"]))
    assert 1.0 - busy / extent > 0.9        # the sleeps leave it idle


def test_recorded_trace_finds_the_kernel_by_its_custom_call(recorded):
    kernel = recorded.op_time(lambda n: tr.KERNEL_TARGET in n)
    assert kernel == pytest.approx(4 * 117.0e-6, rel=0.01)
    top = recorded.top_ops(3)
    assert top[0][0] == "flash.1__bf16_1_8_2048_128"
    assert top[0][1] == pytest.approx(kernel)
    assert all(len(name) <= 64 and set(name) <= set(
        "abcdefghijklmnopqrstuvwxyzABCDEFGHIJKLMNOPQRSTUVWXYZ0123456789_.-")
        for name, _ in recorded.top_ops(10))


def test_recorded_trace_names_what_the_host_did_in_a_gap(recorded):
    gaps = recorded.idle_gaps(3)
    assert [g[0] for g in gaps] == ["bench.sleep"] * 3
    assert all(5e-3 < g[1] < 8e-3 for g in gaps)
    assert recorded.collective_times() == (0.0, 0.0)
    # the traced window is what the trace holds, host threads included
    assert recorded.extent_s == pytest.approx(0.0292, abs=0.005)


@pytest.mark.parametrize("name,want", [
    ("%fusion.5 = bf16[32,14336]{1,0:T(8,128)(2,1)} fusion(bf16[32,4096]"
     "{1,0} %p), kind=kOutput, calls=%fused_computation.5", "fusion"),
    ("%copy-start = (bf16[8]{0:T(8,128)(2,1)S(1)}, bf16[8]{0}, u32[]{:S(2)})"
     " copy-start(bf16[8]{0} %w.1)", "copy-start"),
    ("%all-gather-start.3 = (bf16[1024]{0}, bf16[4096]{0}) all-gather-start("
     "bf16[1024]{0} %p), replica_groups={{0,1,2,3}}", "all-gather-start"),
    ("%flash.1 = bf16[1,8,2048,128]{3,2,1,0:T(8,128)(2,1)S(1)} custom-call("
     "bf16[1,8,2048,128]{3,2,1,0} %q), custom_call_target=\"tpu_custom_call\"",
     "custom-call")])
def test_opcode_of_an_operation_name(name, want):
    assert tr.opcode(name) == want
    assert tr.is_collective(name) == want.startswith("all-gather")


def test_union_and_subtract():
    assert tr.union_length([(0, 2), (1, 3), (5, 6)]) == pytest.approx(4)
    assert tr.union_length([]) == 0
    assert tr.subtract_length([(0, 10)], [(2, 3), (5, 7)]) == pytest.approx(7)
    assert tr.subtract_length([(0, 4), (6, 8)], [(3, 7)]) == pytest.approx(4)
    assert tr.subtract_length([(0, 4)], []) == pytest.approx(4)


def synthetic(n_devices=2):
    ag = "%all-gather.1 = bf16[8]{0} all-gather(bf16[2]{0} %p)"
    ar = "%all-reduce-start = f32[8]{0} all-reduce-start(f32[8]{0} %g)"
    mm = "%fusion.1 = bf16[8,8]{1,0} fusion(bf16[8,8]{1,0} %x), kind=kOutput"
    dev = {"modules": [("jit__step(1)", 0.0, 10.0)],
           "ops": [(mm, 0.0, 4.0), (ag, 4.0, 5.0), (mm, 5.0, 9.0)],
           "async_ops": [(ar, 6.0, 10.0)]}
    return Trace([dict(dev) for _ in range(n_devices)],
                 [("bench.step_call", 0.0, 1.0), ("bench.wait", 1.0, 10.0)])


def test_collectives_total_and_exposed():
    t = synthetic()
    total, exposed = t.collective_times()
    # all-gather 4..5 (alone: exposed), all-reduce 6..10 (9..10 exposed)
    assert total == pytest.approx(5.0) and exposed == pytest.approx(2.0)
    assert t.busy_s() == pytest.approx(10.0)
    assert t.module_time(lambda n: n.startswith("jit__step("))[0] == 10.0


def engine_like_trace(layers=3):
    """Programs named as the engine names its own: decode programs of
    chunk steps x L layers (two chunk sizes), a prefill program (one pass
    over the layers) and a program with no loop; the first and last runs
    are cut short by the trace's edges."""
    ops, modules, t = [], [], 0.0
    for program, passes in (
            ("jit_paged_decode_c4_w2(1)", 1),       # cut short
            ("jit_paged_decode_c4_w2(1)", 4),
            ("jit_paged_decode_c2_w2(2)", 2),
            ("jit_paged_prefill_w2(3)", 1),
            ("jit_scatter_firsts(4)", 0),
            ("jit_paged_decode_c4_w2(1)", 4),
            ("jit_paged_decode_c2_w2(2)", 1)):      # cut short
        start = t
        for _ in range(passes):
            for _ in range(layers):
                ops.append(("%body.1 = f32[1]{0} fusion(f32[1]{0} %x)",
                            t, t + 0.5))
                t += 1.0
            ops.append(("%head.1 = f32[1]{0} fusion(f32[1]{0} %x)",
                        t, t + 0.5))
            t += 1.0
        ops.append(("%tail = f32[1]{0} fusion(f32[1]{0} %x)", t, t + 0.5))
        t += 1.0
        modules.append((program, start, t))
        t += 1.0
    return Trace([{"modules": modules, "ops": ops, "async_ops": []}], [],
                 extent_s=t)


def gap_trace(spans):
    """Two operations with an idle gap from 1.0 to 3.0 between them."""
    return Trace([{"modules": [], "ops": [("%a = f32[1]{0} add()", 0.0, 1.0),
                                          ("%a = f32[1]{0} add()", 3.0, 4.0)],
                   "async_ops": []}], spans)


@pytest.mark.parametrize("spans,want", [
    # the engine's phase inside its iteration, not the client's sleep,
    # though the sleep is the shortest span that covers the gap
    ([("engine.iteration", 0.0, 4.0), ("engine.wait_device", 0.5, 3.5),
      ("bench.sleep", 0.9, 3.1)], "engine.wait_device"),
    # of two phases, the one that covers most of the gap
    ([("engine.iteration", 0.0, 1.5), ("engine.admit", 0.2, 1.4),
      ("engine.iteration", 1.5, 4.0), ("engine.wait_arrivals", 1.6, 3.9),
      ("bench.read", 0.0, 4.0)], "engine.wait_arrivals"),
    # the iteration itself where no phase of it covers the gap
    ([("engine.iteration", 0.0, 4.0), ("engine.emit", 0.0, 0.9)],
     "engine.iteration"),
    # a cell whose program records no phase: the benchmark's own spans,
    # innermost first
    ([("bench.wait", 0.0, 4.0), ("bench.step_call", 0.9, 3.1)],
     "bench.step_call"),
    ([("bench.wait", 3.5, 4.0)], "no-benchmark-span")])
def test_idle_gap_is_named_by_the_innermost_phase_that_covers_it(spans, want):
    assert gap_trace(spans).idle_gaps() == [[want, 2.0]]


def test_engine_phases_are_kept_from_a_trace_file(tmp_path):
    """A profiler session on the CPU: no device plane, but the host spans
    the reduction keeps are the engine's phases beside the benchmark's,
    and nothing else's."""
    import jax

    def client():
        with jax.profiler.TraceAnnotation("bench.sleep"):
            time.sleep(0.002)

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        thread = threading.Thread(target=client)
        thread.start()
        with jax.profiler.TraceAnnotation("engine.iteration"):
            with jax.profiler.TraceAnnotation("engine.wait_device"):
                time.sleep(0.004)
            with jax.profiler.TraceAnnotation("other.thing"):
                time.sleep(0.001)
        thread.join(timeout=10)
    finally:
        jax.profiler.stop_trace()
    trace = Trace.from_file(tr.find_xplane(str(tmp_path)))
    assert trace.devices == [] and trace.idle_gaps() == []
    assert sorted(n for n, _, _ in trace.spans) == [
        "bench.sleep", "engine.iteration", "engine.wait_device"]
    inner = {n: (s, e) for n, s, e in trace.spans}
    assert (inner["engine.iteration"][0] <= inner["engine.wait_device"][0]
            < inner["engine.wait_device"][1] <= inner["engine.iteration"][1])


def test_gap_with_no_span_is_named_so():
    assert gap_trace([]).idle_gaps() == [["no-benchmark-span", 2.0]]
    assert Trace([], []).busy_s() == 0.0 and Trace([], []).top_ops() == []
