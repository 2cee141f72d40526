"""The benchmark's traffic generators: what makes a schedule repeat."""

import collections
import json
import os

import numpy as np
import pytest

from benchmark.generators import chat_sessions, doc_backlog, grid

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
MODEL = {"vocab_size": 32768}
BIG_SEED = 2 ** 31 + 12345      # the driver's seeds pass 32 signed bits


def traffic(name):
    with open(os.path.join(ROOT, "benchmark", "traffic", name + ".json")) as f:
        return json.load(f)


def chat(seed, seconds=51):
    return chat_sessions.build(traffic("chat-sessions-steady"), MODEL, {},
                               seed, seconds)


def shape_multiset(reqs):
    return sorted((len(r.prompt), r.max_new_tokens) for r in reqs)


def test_chat_same_seed_same_schedule():
    a, b = chat(BIG_SEED), chat(BIG_SEED)
    assert len(a["requests"]) == len(b["requests"])
    for x, y in zip(a["requests"], b["requests"]):
        assert x.due == y.due and x.max_new_tokens == y.max_new_tokens
        assert np.array_equal(x.prompt, y.prompt)
    assert all(np.array_equal(p, q)
               for p, q in zip(a["prefill"], b["prefill"]))


def test_chat_two_seeds_same_work_other_order():
    a, b = chat(1)["requests"], chat(BIG_SEED)["requests"]
    assert len(a) == len(b)
    assert shape_multiset(a) == shape_multiset(b)
    assert [r.due for r in a] != [r.due for r in b]
    assert not np.array_equal(a[0].prompt, b[0].prompt)


def test_chat_measured_count_nearly_fixed():
    p = traffic("chat-sessions-steady")
    counts = []
    for seed in (1, 2, 3, BIG_SEED):
        reqs = chat(seed)["requests"]
        lo, hi = p["ramp_s"], p["ramp_s"] + 51
        counts.append(sum(lo <= r.due < hi for r in reqs))
    assert max(counts) - min(counts) <= 0.05 * min(counts)
    assert min(counts) >= 100       # a 90th percentile needs ten beyond it


def test_chat_turn_extends_previous_turn():
    reqs = chat(5)["requests"]
    sessions = collections.defaultdict(dict)
    for r in reqs:
        sessions[r.session][r.turn] = r
    extended = 0
    for turns in sessions.values():
        for k, r in turns.items():
            if k:
                prev = turns[k - 1]
                assert len(r.prompt) > len(prev.prompt) + prev.max_new_tokens
                assert np.array_equal(r.prompt[:len(prev.prompt)],
                                      prev.prompt)
                extended += 1
    assert extended > 50


def test_chat_lengths_inside_their_clips():
    p = traffic("chat-sessions-steady")
    sched = chat(9)
    for r in sched["requests"]:
        assert (len(r.prompt) + r.max_new_tokens
                <= p["max_context_tokens"])
        assert (p["answer_tokens"]["min"] <= r.max_new_tokens
                <= p["answer_tokens"]["max"])
        assert 0 <= r.due < sched["period_s"]
        assert r.prompt.dtype == np.int32 and r.prompt.min() >= 1
    dues = [r.due for r in sched["requests"]]
    assert dues == sorted(dues)


def test_chat_sessions_under_way_are_prefilled():
    """A turn that wraps to the schedule's start belongs to a session
    already under way: its predecessor's prompt is in the prefill list."""
    sched = chat(11)
    period = sched["period_s"]
    firsts = {}
    for r in sched["requests"]:
        firsts.setdefault(r.session, {})[r.turn] = r
    wrapped = [(t[k - 1], t[k]) for t in firsts.values() for k in t
               if k and t[k].due < t[k - 1].due]
    assert wrapped
    held = {p.tobytes() for p in sched["prefill"]}
    for prev, _ in wrapped:
        assert prev.prompt.tobytes() in held
    assert period == pytest.approx(8 + 51 + 24)


@pytest.mark.parametrize("seed", [0, BIG_SEED])
def test_doc_backlog_cycles_keep_the_multiset(seed):
    p = traffic("doc-backlog")
    per_cycle = p["docs_per_cycle"] * p["askings"]
    a = doc_backlog.Backlog(p, 32768, seed).take(2 * per_cycle)
    b = doc_backlog.Backlog(p, 32768, seed + 1).take(per_cycle)
    # every cycle offers the same documents, questions and answers; the
    # seed pairs them anew
    for part in (a[per_cycle:], b):
        assert (sum(len(r.prompt) for r in part)
                == sum(len(r.prompt) for r in a[:per_cycle]))
    # documents keep their length across seeds; questions are permuted
    assert (sorted(r.max_new_tokens for r in a[:per_cycle])
            == sorted(r.max_new_tokens for r in b))
    assert [r.rid for r in a] == list(range(2 * per_cycle))


def test_doc_backlog_askings_share_the_document_and_are_spaced():
    p = traffic("doc-backlog")
    reqs = doc_backlog.Backlog(p, 32768, 4).take(
        p["docs_per_cycle"] * p["askings"])
    by_doc = collections.defaultdict(list)
    for i, r in enumerate(reqs):
        by_doc[r.session].append((i, r))
    for askings in by_doc.values():
        assert len(askings) == p["askings"]
        (i0, r0), (i1, r1), (i2, r2) = askings
        doc = min(len(r0.prompt), len(r1.prompt), len(r2.prompt)) - 64
        assert np.array_equal(r0.prompt[:doc], r1.prompt[:doc])
        assert np.array_equal(r0.prompt[:doc], r2.prompt[:doc])
        assert i1 - i0 == p["wave_docs"] and i2 - i1 == p["wave_docs"]
        assert 1024 + 32 <= len(r0.prompt) <= 1792 + 64
        assert 64 <= r0.max_new_tokens <= 128


@pytest.mark.parametrize("spec,lo,hi", [
    ({"dist": "lognormal", "median": 64, "sigma": 1.0, "min": 16,
      "max": 512}, 16, 512),
    ({"dist": "uniform", "min": 32, "max": 64}, 32, 64),
    ({"dist": "zipf", "n": 4, "s": 1.0}, 0, 3)])
def test_grid_is_sorted_clipped_and_seed_free(spec, lo, hi):
    g = grid.grid(spec, 101)
    assert g == sorted(g) and lo <= g[0] and g[-1] <= hi
    assert g == grid.grid(spec, 101)
    rng = grid.rng_for(BIG_SEED, 1)
    assert sorted(grid.permuted(rng, g)) == g


def test_grid_lognormal_median_and_zipf_shares():
    g = grid.grid({"dist": "lognormal", "median": 128, "sigma": 0.7,
                   "min": 32, "max": 384}, 1001)
    assert g[500] == 128
    assert sum(x >= 64 for x in g) / len(g) == pytest.approx(0.84, abs=0.02)
    z = collections.Counter(grid.grid({"dist": "zipf", "n": 4, "s": 1.0},
                                      1200))
    assert z[0] == pytest.approx(1200 * 12 / 25, abs=2)
    assert z[3] == pytest.approx(1200 * 3 / 25, abs=2)
