"""The readers of ``decode_delivered_share.*``, ``decode_overrun_share.*``
and ``prefill_fill_share.*`` (PR 38; ``benchmark/dispatch_account.py``) on
synthetic span lists, their entries found by name with every serving cell
under ``workloads``, and a toy serve cell whose spans all three can read."""

import json
import os
import subprocess
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import bench_pins  # noqa: E402
import bench_toy  # noqa: E402

from benchmark import dispatch_account, harness, inside  # noqa: E402
from benchmark import program_spans  # noqa: E402

BACKLOG_CELLS = ("serve-doc", "serve-moe-gen", "serve-code-gen",
                 "serve-instruct-gen", "serve-note-gen", "serve-reason-gen")
# (stem, cell): (better, the end-to-end metric it moves there)
ENTRIES = {
    **{("decode_delivered_share", c): ("higher", "serve_tokens_per_s")
       for c in BACKLOG_CELLS},
    **{("decode_overrun_share", c): ("lower", "serve_tokens_per_s")
       for c in BACKLOG_CELLS},
    **{("prefill_fill_share", c): ("higher", "serve_tokens_per_s")
       for c in BACKLOG_CELLS},
    ("prefill_fill_share", "serve-chat"): ("higher", "ttft_p90_ms")}
IDS = [f"{s}-{c}" for s, c in sorted(ENTRIES)]


def emit(i, **attrs):
    return {"name": "engine.emit", "span_id": f"e{i}", "parent_id": "it",
            "duration": 0.001, "attrs": dict(what="chunk", **attrs)}


def prefill(i, **attrs):
    return {"name": "engine.dispatch_prefill", "span_id": f"p{i}",
            "parent_id": "admit", "duration": 0.002, "attrs": attrs}


def chunk_spans(n=6):
    """n chunks of 8 steps x 4 slots: three slots live, one answer ends
    three steps before its chunk's end and one slot's chunk was in flight
    when its answer ended; the last chunk is a whole program of 16 steps
    for one live slot."""
    spans = [emit(i, tokens=13, slot_steps=32, overrun_tail=3,
                  overrun_ahead=8, vacant=8, seq=2 * i, chunk=8, drain=True,
                  finished=1) for i in range(n - 1)]
    spans.append(emit(n, tokens=16, slot_steps=64, overrun_tail=0,
                      overrun_ahead=0, vacant=48, seq=2 * n, chunk=16,
                      drain=False, finished=0))
    # first tokens are no chunk, and another span's counts are not read
    spans.append({"name": "engine.emit", "span_id": "f", "duration": 0.001,
                  "attrs": {"what": "firsts", "tokens": 2, "finished": 0}})
    spans.append({"name": "engine.dispatch_decode", "span_id": "d",
                  "duration": 0.001, "attrs": {"live": 3, "slots": 4,
                                               "chunk": 8, "seq": 1}})
    return spans


def prefill_spans(n=6, token_rows=True):
    """n dispatches of two prompts in a 64 bucket holding 70 new tokens,
    as this PR's engine writes them (``token_rows``) or as its parent
    does."""
    own = {"token_rows": 128} if token_rows else {}
    return [prefill(i, seq=i, group=2, bucket=64, new_tokens=70,
                    cached_tokens=32, missed_pages=1, attn_kernel=0, **own)
            for i in range(n)]


def test_decode_shares_are_sums_over_the_slices_chunks():
    spans = chunk_spans()
    # five chunks of 13 of 32 and one of 16 of 64
    assert dispatch_account.decode_delivered_share(spans) == pytest.approx(
        100.0 * (5 * 13 + 16) / (5 * 32 + 64))
    # live slot-steps: 5 x 24 + 16; past an answer's end: 5 x 11
    assert dispatch_account.decode_overrun_share(spans) == pytest.approx(
        100.0 * 55 / 136)
    for a in (s["attrs"] for s in spans if "slot_steps" in s["attrs"]):
        assert (a["tokens"] + a["overrun_tail"] + a["overrun_ahead"]
                + a["vacant"]) == a["slot_steps"]
    # one miscounted step shows
    spans[0]["attrs"]["overrun_tail"] += 1
    assert dispatch_account.decode_overrun_share(spans) == pytest.approx(
        100.0 * 56 / 136)


@pytest.mark.parametrize("share", [dispatch_account.decode_delivered_share,
                                   dispatch_account.decode_overrun_share],
                         ids=["delivered", "overrun"])
def test_decode_shares_need_the_account_and_five_chunks(share):
    assert share(chunk_spans(inside.MIN_SAMPLES - 1)) is None
    assert share(None) is None and share([]) is None
    # the parent's chunk emissions carry tokens and finished alone
    parent = [emit(i, tokens=13, finished=1) for i in range(8)]
    assert share(parent) is None
    # all slots vacant, were that to happen: nothing live to take a share of
    hollow = [emit(i, tokens=0, slot_steps=32, overrun_tail=0,
                   overrun_ahead=0, vacant=32) for i in range(6)]
    assert dispatch_account.decode_overrun_share(hollow) is None
    assert dispatch_account.decode_delivered_share(hollow) == 0.0


@pytest.mark.parametrize("token_rows", [True, False],
                         ids=["this-engine", "the-parents-spans"])
def test_prefill_fill_share_is_new_tokens_over_group_times_bucket(
        token_rows):
    spans = prefill_spans(token_rows=token_rows) + chunk_spans()
    assert dispatch_account.prefill_fill_share(spans) == pytest.approx(
        100.0 * 70 / 128)
    # a dispatch of one short suffix in the smallest bucket beside them
    spans.append(prefill(9, seq=9, group=1, bucket=16, new_tokens=5,
                         **({"token_rows": 16} if token_rows else {})))
    assert dispatch_account.prefill_fill_share(spans) == pytest.approx(
        100.0 * (6 * 70 + 5) / (6 * 128 + 16))
    assert dispatch_account.prefill_fill_share(
        prefill_spans(inside.MIN_SAMPLES - 1, token_rows)) is None
    assert dispatch_account.prefill_fill_share(None) is None
    # a span without the counts (a program older than PR 24's) is no sample
    assert dispatch_account.prefill_fill_share(
        [prefill(i, seq=i) for i in range(6)]) is None


@pytest.mark.parametrize("stem,cell", sorted(ENTRIES), ids=IDS)
def test_each_reader_is_found_by_its_metrics_name(monkeypatch, stem, cell):
    name = bench_pins.reports(bench_pins.committed(), cell,
                              [stem])[stem]["name"]
    read = harness.load_reader(name)
    run = type("Run", (), {"trace": None})
    spans = chunk_spans() + prefill_spans(token_rows=False)
    monkeypatch.setattr(program_spans, "engine_spans", lambda: spans)
    want = getattr(dispatch_account, stem)(spans)
    assert read(run) == pytest.approx(want) and 0.0 < want < 100.0
    # a program that records no spans at all (before PR 24): None, quietly
    monkeypatch.setattr(program_spans, "engine_spans", lambda: None)
    assert read(run) is None


@pytest.mark.parametrize("stem,cell", sorted(ENTRIES), ids=IDS)
def test_the_entry_keeps_the_contract(bench, stem, cell):
    """Found by NAME with the cell under ``workloads``, wherever later
    PRs' entries put it and whatever cells they add to it."""
    better, moves = ENTRIES[stem, cell]
    m = bench_pins.reports(bench, cell, [stem], moves=moves)[stem]
    assert {k: m[k] for k in ("unit", "better", "source", "layer")} == {
        "unit": "%", "better": better, "source": "program_span",
        "layer": "engine scheduler"}
    # the cell reports the end-to-end metric the share moves
    assert cell in bench_pins.entry(bench["end_to_end"], moves)["workloads"]


def test_no_cell_without_an_engine_reports_them(bench):
    """``serve-chat``'s program is 32 wide for the slots its rate fills
    and its overrun costs no client anything: no decode entry there; the
    train cells run no engine."""
    stems = {s for s, _ in ENTRIES}
    for cell in ("train-2k", "train-2k-fsdp4"):
        assert not stems & {bench_pins.stem(n)
                            for n in bench_pins.reported(bench, cell)}
    assert stems & {bench_pins.stem(n) for n in bench_pins.reported(
        bench, "serve-chat")} == {"prefill_fill_share"}


DRIVER = '''
import json, sys
sys.path.insert(0, ".")
from benchmark import harness
rc = harness.main(["--workload", "toy-doc", "--seed", "3", "--seconds",
                   "5", "--trace", "1", "--rehearse", "--set", "trace_s=4"])
from benchmark import program_spans
run = type("Run", (), {"trace": None})
values = {name: harness.load_reader(name)(run)
          for name in json.loads(sys.argv[1])}
sums = {}
for s in program_spans.engine_spans():
    a = s.get("attrs", {})
    if s["name"] == "engine.emit" and a.get("what") == "chunk":
        for k in ("tokens", "slot_steps", "overrun_tail", "overrun_ahead",
                  "vacant"):
            sums[k] = sums.get(k, 0) + a[k]
    if s["name"] == "engine.dispatch_prefill":
        for k in ("token_rows", "new_tokens"):
            sums[k] = sums.get(k, 0) + a[k]
print("inside " + json.dumps({"rc": rc, "values": values, "sums": sums}))
'''


def test_a_rehearsed_serve_cell_reports_the_three(tmp_path):
    """The toy backlog cell with a profiler session over most of its
    window: the engine's spans carry the accounts, the three readers give
    the shares of their sums, and the rehearsal line still prints
    counters only (counts of a CPU run, never a result)."""
    root = bench_toy.make_toy(str(tmp_path))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=bench_toy.REPO + os.pathsep
               + os.environ.get("PYTHONPATH", ""),
               JAX_COMPILATION_CACHE_DIR=os.path.join(root, ".jax_cache"))
    names = ["decode_delivered_share", "decode_overrun_share",
             "prefill_fill_share"]
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        listed = [m["name"] for m in harness.cell_metrics(
            json.load(f), "toy-doc", "per_layer")]
    assert set(names) <= set(listed)
    r = subprocess.run([sys.executable, "-c", DRIVER, json.dumps(names)],
                       cwd=root, env=env, capture_output=True, text=True,
                       timeout=300)
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-2000:]
    lines = r.stdout.strip().splitlines()
    got = json.loads(lines[-1].split(" ", 1)[1])
    rehearsal = json.loads(lines[-2].split(" ", 1)[1])
    assert got["rc"] == 0 and rehearsal["correct"] is True
    assert not set(names) & set(rehearsal["metrics"])
    sums, values = got["sums"], got["values"]
    assert (sums["tokens"] + sums["overrun_tail"] + sums["overrun_ahead"]
            + sums["vacant"]) == sums["slot_steps"] > 0
    assert values["decode_delivered_share"] == pytest.approx(
        100.0 * sums["tokens"] / sums["slot_steps"])
    assert values["decode_overrun_share"] == pytest.approx(
        100.0 * (sums["overrun_tail"] + sums["overrun_ahead"])
        / (sums["slot_steps"] - sums["vacant"]))
    assert values["prefill_fill_share"] == pytest.approx(
        100.0 * sums["new_tokens"] / sums["token_rows"])
    # answers of 4-12 tokens in chunks of 8 and 16: every answer ends
    # inside a chunk; a suffix is padded to 16 tokens at the least
    assert 0.0 < values["decode_delivered_share"] < 100.0
    assert 0.0 < values["decode_overrun_share"] < 100.0
    assert 0.0 < values["prefill_fill_share"] <= 100.0
