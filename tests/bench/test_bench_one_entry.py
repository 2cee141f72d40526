"""One entry a metric (PR 49): what a cell read under ``<stem>.<cell's
suffix>`` before, it reads under the one entry of that stem after, and the
number is the same. ``data/readings_b0cf2bd.json`` holds what every
reader of every cell gave on commit b0cf2bd (128 entries, one a cell) for
the inputs ``inputs()`` builds: the trace recorded on a v5e that
``tests/bench/data`` has, the engine-like trace five times over, the
synthetic loop's, chunks' and prefills' spans, and one set of counters.
The file was written by this module run as a script against that commit's
``benchmark/`` (``PYTHONPATH=<checkout of b0cf2bd> python3
tests/bench/test_bench_one_entry.py``), never against this tree's."""

import json
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import bench_pins  # noqa: E402
from test_bench_dispatch_account import chunk_spans, prefill_spans  # noqa: E402
from test_bench_inside import loop_spans, repeated  # noqa: E402
from test_bench_trace import DATA, engine_like_trace  # noqa: E402

from benchmark import harness, program_spans  # noqa: E402
from benchmark.trace import Trace  # noqa: E402

READINGS = os.path.join(os.path.dirname(DATA), "readings_b0cf2bd.json")
# the suffix a cell's entries had while each cell had entries of its own
OLD_SUFFIX = {"serve-doc": ".doc", "serve-chat": ".chat",
              "serve-moe-gen": ".moe", "serve-code-gen": ".code",
              "serve-instruct-gen": ".ssm", "serve-note-gen": ".note",
              "serve-reason-gen": ".nano", "train-2k": ".train",
              "train-2k-fsdp4": ".train"}
# retired by stem in every cell at once (PERF.md, Findings, PR 49)
RETIRED = {"batch_occupancy", "paged_attn_kernel_share",
           "prefill_group_mean"}
COUNTERS = {
    "occupancy_samples": [24, 28, 32, 32], "live_kv_tokens_mean": 21000.0,
    "prefix_hit_pages": 310, "prefix_lookup_pages": 500, "max_batch": 32,
    "generator_late_s": [0.0001 * i for i in range(40)],
    "queue_wait_s": [0.002 * i for i in range(40)],
    "ttft_ms": [100.0 + i for i in range(40)],
    "tpot_ms": [9.0 + 0.01 * i for i in range(40)],
    "tokens_per_s": 1500.0, "peak_hbm_bytes": 9_190_000_000,
    "compiles_in_window": 0, "seq_len": 2048, "batch": 16, "chips": 4,
    "tokens_per_s_per_chip": 7947.6}


def inputs() -> list:
    """[(trace, spans)]: what a reader may find."""
    spans = loop_spans() + chunk_spans() + prefill_spans()
    return [(Trace.from_file(DATA), spans),
            (repeated(engine_like_trace(), 5), spans),
            (None, None)]


def readings(cell: str, names: dict) -> dict:
    """{stem: [the reader's value on each of ``inputs()``]} for the cell's
    entries ``names`` ({stem: the entry's name})."""
    _, _, config, traffic = harness.load_cell(cell)
    out = {}
    for stem, name in sorted(names.items()):
        read, values = harness.load_reader(name), []
        for trace, spans in inputs():
            program_spans.engine_spans = lambda spans=spans: spans
            run = type("Run", (), {
                "trace": trace, "config": config, "traffic": traffic,
                "counters": COUNTERS,
                "device": {"platform": "tpu", "kind": "TPU v5 lite",
                           "count": 4 if cell.endswith("fsdp4") else 1}})
            values.append(read(run))
        out[stem] = values
    return out


def stems(bench: dict, cell: str, by_suffix: bool) -> dict:
    """{stem: name} of the per-layer entries the cell reports; with
    ``by_suffix`` the names are b0cf2bd's (a suffix a cell), else this
    tree's (``.chat`` and ``.train`` alone are suffixes)."""
    out = {}
    for m in harness.cell_metrics(bench, cell, "per_layer"):
        name, cut = m["name"], OLD_SUFFIX[cell]
        if not by_suffix:
            out[bench_pins.stem(name)] = name
        else:
            out[name[:-len(cut)] if name.endswith(cut) else name] = name
    return out


@pytest.fixture
def restore_spans():
    keep = program_spans.engine_spans
    yield
    program_spans.engine_spans = keep


@pytest.mark.parametrize("cell", sorted(OLD_SUFFIX))
def test_a_cell_reads_every_number_it_read_and_the_same(cell, restore_spans):
    with open(READINGS) as f:
        before = json.load(f)[cell]
    bench, *_ = harness.load_cell(cell)
    mine = stems(bench, cell, by_suffix=False)
    # every stem the cell reported it reports, but for the retired ones
    assert set(before) - RETIRED <= set(mine), cell
    after = readings(cell, {s: mine[s] for s in before if s in mine})
    for stem, values in after.items():
        assert values == pytest.approx(before[stem], rel=1e-12, abs=0,
                                       nan_ok=True), (cell, stem)
    # and what it reads came out of something: not every reader is silent
    assert sum(v is not None for vs in after.values() for v in vs) >= 8


if __name__ == "__main__":          # against a checkout of b0cf2bd only
    root = os.path.dirname(os.path.dirname(os.path.abspath(
        harness.__file__)))
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        old = json.load(f)
    table = {cell: readings(cell, stems(old, cell, by_suffix=True))
             for cell in sorted(OLD_SUFFIX)}
    with open(READINGS, "w") as f:
        json.dump(table, f, indent=1, sort_keys=True)
    print(READINGS, {c: len(t) for c, t in table.items()})
