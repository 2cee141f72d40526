"""``prefill_ms_per_ktoken`` (PR 56): the reader's arithmetic on synthetic
span records, and its entry in ``BENCHMARK.json``."""

import pytest

from benchmark import inside
from benchmark.layer_metrics.prefill_ms_per_ktoken import ms_per_ktoken

import bench_pins


def dispatch(i, new_tokens, seconds, child=True):
    """A prefill dispatch's span and, with ``child``, its run's."""
    spans = [{"name": "engine.dispatch_prefill", "span_id": f"p{i}",
              "duration": 0.002, "attrs": {
                  "seq": i, "group": 1, "bucket": 8192,
                  "new_tokens": new_tokens}}]
    if child:
        spans.append({"name": "device.run", "span_id": f"r{i}",
                      "parent_id": f"p{i}", "duration": seconds,
                      "attrs": {"kind": "prefill", "seq": i, "wait_s": 0.0}})
    return spans


def slice_of(pairs=3):
    """``pairs`` times a cold document (6,000 tokens, 0.266 s) and a
    question behind it (100 tokens, 0.011 s)."""
    spans = []
    for i in range(pairs):
        spans += dispatch(2 * i, 6000, 0.266) + dispatch(2 * i + 1, 100,
                                                         0.011)
    return spans


def test_it_is_the_runs_seconds_over_the_dispatches_new_tokens():
    # 0.277 s for 6,100 tokens, whatever the slice's number of such pairs
    assert ms_per_ktoken(slice_of(3)) == pytest.approx(45.41, abs=0.005)
    assert ms_per_ktoken(slice_of(5)) == pytest.approx(
        ms_per_ktoken(slice_of(3)))
    # a slice that happens to hold one cold document more moves it less
    # than it moves the programs' share of the device
    more = slice_of(3) + dispatch(6, 6000, 0.266)
    assert ms_per_ktoken(more) == pytest.approx(
        (4 * 0.266 + 3 * 0.011) * 1e6 / (4 * 6000 + 3 * 100))


def test_a_dispatch_without_its_run_is_skipped():
    spans = slice_of(3) + dispatch(6, 6000, 0.0, child=False)
    assert ms_per_ktoken(spans) == pytest.approx(ms_per_ktoken(slice_of(3)))
    # a decode dispatch's run has another parent and is none of it
    spans.append({"name": "device.run", "span_id": "d", "parent_id": "dec",
                  "duration": 9.0, "attrs": {"kind": "decode", "seq": 99}})
    assert ms_per_ktoken(spans) == pytest.approx(ms_per_ktoken(slice_of(3)))


def test_under_min_samples_or_without_spans_it_is_none():
    assert inside.MIN_SAMPLES == 5
    assert ms_per_ktoken(slice_of(2)) is None             # four dispatches
    assert ms_per_ktoken(slice_of(2) + dispatch(4, 6000, 0.0, child=False)) \
        is None
    assert ms_per_ktoken(None) is None and ms_per_ktoken([]) is None
    # a program whose dispatch spans carry no count (before PR 38)
    old = slice_of(3)
    for s in old:
        s["attrs"].pop("new_tokens", None)
    assert ms_per_ktoken(old) is None


def test_the_entry_is_listed_for_the_cells_of_prefill_fill_share(bench):
    own = bench_pins.entry(bench["per_layer"], "prefill_ms_per_ktoken")
    twin = bench_pins.entry(bench["per_layer"], "prefill_fill_share")
    assert own == {"name": "prefill_ms_per_ktoken", "unit": "ms/ktoken",
                   "better": "lower", "source": "program_span",
                   "layer": "prefill program",
                   "moves": "serve_tokens_per_s",
                   "workloads": own["workloads"]}
    assert set(own["workloads"]) <= set(twin["workloads"])
    assert "serve-brief-gen" in own["workloads"]
